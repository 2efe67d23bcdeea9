PYTHONPATH := src
export PYTHONPATH

.PHONY: test collect kernel-smoke quickstart bench-smoke elastic-smoke \
	async-smoke cluster-smoke sim-smoke lint lint-hlo

# tier-1 verify (ROADMAP.md); the lint gates, the collect gate, the
# sub-byte wire kernel smoke, the pipelined-round smoke, and the two-tier
# cluster smoke run first so import/invariant/layout/billing/overlap/
# topology drift fails before the suite
test: lint lint-hlo collect kernel-smoke async-smoke cluster-smoke sim-smoke
	python -m pytest -x -q

# Source lint: ruff (ruff.toml) when installed; otherwise the no-deps
# fallback tools/mini_lint.py (F401 unused / F811 same-scope duplicate
# imports) so the gate still runs in the hermetic container.
lint:
	@if command -v ruff >/dev/null 2>&1; then \
	    ruff check .; \
	else \
	    echo "ruff not found; running tools/mini_lint.py fallback"; \
	    python tools/mini_lint.py; \
	fi

# Static round-invariant gate (DESIGN.md §9): repro.launch.analyze lowers
# every entry point — hermes_round (open + closed) and make_round_jit's
# executable, the async dispatch/commit halves, the elastic shrink/grow
# rounds, the per-pod train step, and the wire-path Pallas kernels — and
# runs the
# repro.analysis rules over the optimized HLO / jaxprs: collective
# placement vs the wire registry, donation/aliasing, retrace/host-sync
# guards, and the Pallas tile lint.  --self-test additionally proves each
# rule is live by analyzing deliberately-broken fixtures (fp32 hoist,
# dropped donation, host sync in the round loop, misaligned tiles) and
# asserting the expected named violation fires.
lint-hlo:
	REPRO_ANALYZE_DEVICES=8 python -m repro.launch.analyze --self-test \
	    --out results/analysis/lint_hlo.json

# Import-graph smoke gate: every test module must collect with zero import
# errors.  This is the regression class that once shipped a missing
# `repro.dist` package — cheap enough to run on every commit.
collect:
	python -m pytest --collect-only -q

# Sub-byte wire gate (ISSUE 5/6): pack/unpack + packed fused-merge kernels
# in interpret mode (REPRO_WIRE_KERNEL=1 forces the Pallas path on CPU),
# then the dryrun byte audit — both the push-level check (the compress
# step's collective ships exactly the billed bytes) and the round-level
# one (the FULL hermes_round lowering crosses the pod axis with exactly
# the billed payload arrays, int4 <= 0.5625 B/element, closed rounds zero
# cross-pod collectives) for every registered format.
kernel-smoke:
	REPRO_WIRE_KERNEL=1 python benchmarks/kernel_bench.py --smoke
	REPRO_DRYRUN_DEVICES=8 python -m repro.launch.hermes_dryrun --byte-audit \
	    --out results/dryrun_opt/hermes_byte_audit_smoke.json

quickstart:
	python examples/quickstart.py

# Pipelined-round gate (DESIGN.md §8): the async byte audit (the round's
# one model-sized cross-pod gather lives in the dispatch half and matches
# the billed wire operands; the closed dispatch and the commit half lower
# to zero cross-pod collectives; int4 stays <= 0.5625 B/element) plus the
# staleness-parity/drain accounting, then the sync-vs-async straggler
# study asserting the async round wall-clock lands strictly below sync on
# a >=2x heterogeneous cluster.
async-smoke:
	REPRO_ROUND_AUDIT_DEVICES=8 python -m repro.launch.round_audit \
	    --async-only --out results/dryrun_opt/async_round_audit.json
	python benchmarks/straggler.py --fast --async-only \
	    --out results/bench/async_overlap_smoke.json

# Billing-regression gate: asserts int4 < int8 < fp16 wire bytes against a
# real parameter tree and drives a tiny int4 (stochastic-rounding) Hermes
# run through the compressed push path.  A payload_bytes regression fails
# this before it can skew the paper's §V-B communication numbers.
# --wire-bytes additionally lowers the full round on a forced 8-device
# mesh and asserts round-level int4 <= 0.5625 B/element measured from the
# cross-pod collectives (results/bench/wire_path.json).
bench-smoke:
	python benchmarks/comm_overhead.py --smoke
	python benchmarks/kernel_bench.py --wire-bytes

# Failure-path gate (DESIGN.md §7): the in-flight pod-shrink/rejoin demos
# (drop-pod + grow-after-shrink bit-identity, data re-split, checkpoint
# restart) and both elastic dryruns — shrink (masked round == reduced-size
# round, compress step still collective-free on the survivors' mesh) and
# grow (shrink->grow round trip == never-resized run, compress step still
# collective-free on the regrown mesh).  Small forced device counts so it
# runs on every `make`-level check, not just when someone remembers the
# env var.
elastic-smoke:
	REPRO_ELASTIC_DEVICES=8 python -m repro.launch.elastic
	REPRO_DRYRUN_DEVICES=8 python -m repro.launch.hermes_dryrun --drop-pod \
	    --out results/dryrun_opt/hermes_elastic_smoke.json
	REPRO_DRYRUN_DEVICES=8 python -m repro.launch.hermes_dryrun --rejoin-pod \
	    --out results/dryrun_opt/hermes_rejoin_smoke.json

# Fleet-scale engine gate (DESIGN.md §11): the batch/surrogate engine's
# prate x cluster x wire sweep at {100, 1k} workers with the full churn
# trace, asserting admission monotonicity (lower prate => fewer PS
# pushes and fewer wire bytes), the per-cell wall-clock bound, and that
# the clustered slow tier never ships more than the flat push volume.
# The committed reference sweep (with the 10k tier) is
# BENCH_sim_scale.json at the repo root.
sim-smoke:
	python benchmarks/sim_scale.py --fast \
	    --out results/bench/sim_scale_smoke.json

# Two-tier topology gate (DESIGN.md §10): lower the cluster round on a
# (2, 2, 2, 1) mesh and assert, per wire format, that the only
# model-sized operands crossing the slow cluster axis are exactly the
# n_clusters re-encoded packed partials (slow-tier bytes scale with
# clusters, not pods; closed rounds cross nothing on either tier), run
# the executed n_clusters=1 bit-identity pin against hermes_round, and
# prove the per-cluster shrink (survivors' compress step collective-free,
# 3 resize cycles bit-identical to the never-resized oracle).
cluster-smoke:
	REPRO_DRYRUN_DEVICES=8 python -m repro.launch.hermes_dryrun --byte-audit \
	    --clusters 2 --out results/dryrun_opt/hermes_cluster_smoke.json
