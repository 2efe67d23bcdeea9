"""repro.analysis: parser edge cases + every rule proven live.

Three layers:

* **Parser regressions** (pure text, no devices): async ``-start/-done``
  pairs counted once, degenerate iota replica groups, the bare
  ``replica_groups={}`` form, empty ``branch_computations``, a collective
  two cond levels deep (cond branch -> fusion -> collective), and the
  ``input_output_alias`` header parse.
* **Rule mechanics in-process** (single device): each rule's named
  violation classes fire on synthetic HLO / toy callables, and the clean
  counterparts pass — including the donation rule against a real jitted
  executable with and without ``donate_argnums``, and the Pallas tile
  lint over every wire kernel in :func:`repro.kernels.ops.wire_lint_cases`.
* **The CI gate end to end** (subprocess, forced 8-device mesh):
  ``repro.launch.analyze --self-test`` analyzes every entry point clean
  AND proves each rule live on its deliberately-violating fixture — the
  fp32 GSPMD hoist, the dropped ``pending``/``pod_params`` donation, the
  ``bool(any_push)``-per-round host sync, and a misaligned BlockSpec.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest
import jax
import jax.numpy as jnp

from repro.analysis import (
    AnalysisError, CollectivePlacement, DonationAliasing, PallasTileLint,
    RetraceGuard, analyze, available_rules, control_traffic_allowance,
    cross_pod_collectives, donated_param_numbers, parse_hlo_cost,
    parse_input_output_aliases, parse_replica_groups,
)

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# Parser regressions (no devices, pure text)
# ---------------------------------------------------------------------------

ASYNC_PAIR_HLO = """\
HloModule async_pair

ENTRY %main (p0: f32[8,128]) -> f32[16,128] {
  %p0 = f32[8,128] parameter(0)
  %ag-start = f32[16,128] all-gather-start(%p0), replica_groups={{0,1}}, dimensions={0}
  ROOT %ag-done = f32[16,128] all-gather-done(%ag-start)
}
"""


def test_async_start_done_counted_once():
    cost = parse_hlo_cost(ASYNC_PAIR_HLO)
    assert cost.collective_counts == {"all-gather": 1}
    assert len(cost.collective_ops) == 1
    rec = cost.collective_ops[0]
    assert rec["kind"] == "all-gather"
    assert rec["operands"] == [
        {"dtype": "f32", "dims": [8, 128], "bytes": 8 * 128 * 4}]
    assert rec["replica_groups"] == [[0, 1]]


@pytest.mark.parametrize("attrs,expect", [
    # iota form: arange(8).reshape(2,4).T -> pod-interleaved pairs
    ("replica_groups=[4,2]<=[2,4]T(1,0)",
     [[0, 4], [1, 5], [2, 6], [3, 7]]),
    # no transpose: identity permutation
    ("replica_groups=[2,4]<=[2,4]", [[0, 1, 2, 3], [4, 5, 6, 7]]),
    # one group of everything
    ("replica_groups=[8]<=[8]", [[0, 1, 2, 3, 4, 5, 6, 7]]),
    # degenerate: size-1 axes
    ("replica_groups=[1,1]<=[1,1]", [[0]]),
    # degenerate: zero-sized dims must not crash (or div-by-zero)
    ("replica_groups=[0,0]<=[0,0]", None),
    # literal form
    ("replica_groups={{0,2},{1,3}}", [[0, 2], [1, 3]]),
    # bare {} = "one group of all replicas": unparsable -> None
    ("replica_groups={}", None),
    ("no groups here at all", None),
    # collective-permute: one group per (source, target) pair
    ("channel_id=95, source_target_pairs={{0,0},{1,2},{2,1},{3,3}}",
     [[0, 0], [1, 2], [2, 1], [3, 3]]),
])
def test_replica_group_forms(attrs, expect):
    assert parse_replica_groups(attrs) == expect


EMPTY_BRANCHES_HLO = """\
HloModule empty_branches

ENTRY %main (pred: s32[], p: f32[4]) -> f32[4] {
  %pred = s32[] parameter(0)
  %p = f32[4] parameter(1)
  ROOT %cond = f32[4] conditional(%pred), branch_computations={}
}
"""


def test_empty_branch_computations_contribute_nothing():
    cost = parse_hlo_cost(EMPTY_BRANCHES_HLO)
    assert cost.collective_ops == []
    assert cost.collective_counts == {}


TWO_LEVELS_HLO = """\
HloModule two_cond_levels

%deep (dp: f32[8,128]) -> f32[16,128] {
  %dp = f32[8,128] parameter(0)
  ROOT %ag = f32[16,128] all-gather(%dp), replica_groups={{0,1}}, dimensions={0}
}

%br0 (a0: f32[8,128]) -> f32[16,128] {
  %a0 = f32[8,128] parameter(0)
  ROOT %bc = f32[16,128] broadcast(%a0), dimensions={0,1}
}

%br1 (a1: f32[8,128]) -> f32[16,128] {
  %a1 = f32[8,128] parameter(0)
  ROOT %fu = f32[16,128] fusion(%a1), kind=kLoop, calls=%deep
}

ENTRY %main (pred: s32[], p: f32[8,128]) -> f32[16,128] {
  %pred = s32[] parameter(0)
  %p = f32[8,128] parameter(1)
  ROOT %cond = f32[16,128] conditional(%pred, %p, %p), branch_computations={%br0, %br1}
}
"""


def test_collective_two_cond_levels_deep_is_not_dropped():
    """cond branch -> fusion -> all-gather must keep its structured
    record, or the cross-pod audit silently passes a hidden gather."""
    cost = parse_hlo_cost(TWO_LEVELS_HLO)
    assert cost.collective_counts == {"all-gather": 1}
    assert len(cost.collective_ops) == 1
    rec = cost.collective_ops[0]
    assert rec["computation"] == "deep"
    # at 2 devices / 2 pods (1 device per pod), {0,1} crosses
    recs = cross_pod_collectives(cost, n_devices=2, n_pods=2)
    assert len(recs) == 1 and recs[0]["name"] == rec["name"]
    # at 2 devices / 1 pod nothing crosses
    assert cross_pod_collectives(cost, n_devices=2, n_pods=1) == []


ALIAS_HEADER_HLO = """\
HloModule donated, input_output_alias={ {0}: (1, {}, may-alias), {1}: (2, {0}, must-alias) }, entry_computation_layout={(f32[4],f32[4])->(f32[4],f32[4])}

ENTRY %main (p0: f32[4], p1: f32[4]) -> (f32[4], f32[4]) {
  %p0 = f32[4] parameter(0)
  %p1 = f32[4] parameter(1)
  ROOT %t = (f32[4], f32[4]) tuple(%p0, %p1)
}
"""


def test_parse_input_output_aliases():
    entries = parse_input_output_aliases(ALIAS_HEADER_HLO)
    assert entries == [
        {"output_index": (0,), "param_number": 1, "param_index": (),
         "kind": "may-alias"},
        {"output_index": (1,), "param_number": 2, "param_index": (0,),
         "kind": "must-alias"},
    ]
    assert parse_input_output_aliases("HloModule bare\n") == []


def test_registry_and_allowance():
    assert set(available_rules()) >= {
        "collective-placement", "donation-aliasing", "retrace-guard",
        "pallas-tile"}
    assert control_traffic_allowance(2) == 16
    assert control_traffic_allowance(4) == 24


# ---------------------------------------------------------------------------
# CollectivePlacement on synthetic HLO (2 devices = 2 pods)
# ---------------------------------------------------------------------------

CROSSING_HLO = ASYNC_PAIR_HLO  # one f32[8,128] all-gather across {0,1}
WIRE_SPEC = ("f32", (8, 128), 8 * 128 * 4)


def test_collective_placement_fp32_crossing_is_named():
    rule = CollectivePlacement(n_devices=2, n_pods=2)  # no specs licensed
    with pytest.raises(AnalysisError) as e:
        analyze(CROSSING_HLO, rules=[rule], label="fp32-hoist-synthetic")
    assert {v.cls for v in e.value.violations} == {"fp32-model-crossing"}


def test_collective_placement_clean_with_matching_spec():
    rule = CollectivePlacement([WIRE_SPEC], n_devices=2, n_pods=2,
                               billed_bytes=WIRE_SPEC[2])
    report = analyze(CROSSING_HLO, rules=[rule], label="licensed")
    assert report.ok
    assert rule.classification["payload_bytes"] == WIRE_SPEC[2]
    assert rule.classification["unexpected"] == []


def test_collective_placement_billing_drift():
    rule = CollectivePlacement([WIRE_SPEC], n_devices=2, n_pods=2,
                               billed_bytes=WIRE_SPEC[2] + 1)
    with pytest.raises(AnalysisError) as e:
        analyze(CROSSING_HLO, rules=[rule], label="drift")
    assert {v.cls for v in e.value.violations} == {"billing-drift"}


def test_collective_placement_missing_wire_operand():
    ghost = ("s8", (8, 128), 8 * 128)
    rule = CollectivePlacement([WIRE_SPEC, ghost], n_devices=2, n_pods=2)
    with pytest.raises(AnalysisError) as e:
        analyze(CROSSING_HLO, rules=[rule], label="ghost-spec")
    assert {v.cls for v in e.value.violations} == {"missing-wire-operand"}


def test_collective_placement_expect_none():
    rule = CollectivePlacement(n_devices=2, n_pods=2, expect_none=True)
    with pytest.raises(AnalysisError) as e:
        analyze(CROSSING_HLO, rules=[rule], label="must-be-local")
    assert {v.cls for v in e.value.violations} == {
        "unexpected-cross-pod-collective"}
    # the same executable is fine when both devices sit in ONE pod
    rule1 = CollectivePlacement(n_devices=2, n_pods=1, expect_none=True)
    assert analyze(CROSSING_HLO, rules=[rule1], label="one-pod").ok


# ---------------------------------------------------------------------------
# DonationAliasing against real jitted executables (single device)
# ---------------------------------------------------------------------------

def _donate_fn(x, y):
    return x + y, y * 2.0


def test_donation_aliasing_honored_and_dropped():
    x = jnp.zeros((128,), jnp.float32)
    donated = {"x": range(*donated_param_numbers((x, x), (0,))[0])}

    lowered = jax.jit(_donate_fn, donate_argnums=(0,)).lower(x, x)
    assert analyze(lowered, rules=[DonationAliasing(donated)],
                   label="donated").ok

    # donate_argnums drift: same function, donation dropped -> named class
    bare = jax.jit(_donate_fn).lower(x, x)
    with pytest.raises(AnalysisError) as e:
        analyze(bare, rules=[DonationAliasing(donated)], label="dropped")
    assert {v.cls for v in e.value.violations} == {"dropped-donation"}


def test_donated_param_numbers_flat_ranges():
    x = jnp.zeros((4,), jnp.float32)
    args = ({"a": x, "b": (x, x)}, x, [x, x])
    assert donated_param_numbers(args, (0, 2)) == {0: (0, 3), 2: (4, 6)}


# ---------------------------------------------------------------------------
# RetraceGuard on toy round loops
# ---------------------------------------------------------------------------

def _bad_round_loop(rounds, any_push):
    pushed = 0
    for _ in range(rounds):
        if bool(any_push):          # the PR 4 per-round host sync
            pushed += 1
    return pushed


def _good_round_loop(rounds, any_push):
    pushed = 0
    for _ in range(rounds):
        flag = _host_fetch(any_push)
        if bool(flag):
            pushed += 1
    return pushed


def _host_fetch(x):
    return bool(x)


def _item_in_loop(xs):
    total = 0.0
    for x in xs:
        total += x.item()
    return total


def test_retrace_guard_flags_host_sync_in_loop():
    rule = RetraceGuard(check_args=False)
    with pytest.raises(AnalysisError) as e:
        analyze(None, rules=[rule], fn=_bad_round_loop, label="bad-loop")
    assert {v.cls for v in e.value.violations} == {"host-sync-in-loop"}

    with pytest.raises(AnalysisError) as e:
        analyze(None, rules=[RetraceGuard(check_args=False)],
                fn=_item_in_loop, label="item-loop")
    assert {v.cls for v in e.value.violations} == {"host-sync-in-loop"}


def test_retrace_guard_allows_sanctioned_fetcher():
    rule = RetraceGuard(check_args=False, allow=("_host_fetch",))
    assert analyze(None, rules=[rule], fn=_good_round_loop,
                   label="good-loop").ok


def test_retrace_guard_weak_type_args():
    rule = RetraceGuard(scan_source=False)
    with pytest.raises(AnalysisError) as e:
        analyze(None, rules=[rule], example_args=(1.0,), label="weak")
    assert {v.cls for v in e.value.violations} == {"weak-type-arg"}
    strong = RetraceGuard(scan_source=False)
    assert analyze(None, rules=[strong],
                   example_args=(jnp.float32(1.0),), label="strong").ok


# ---------------------------------------------------------------------------
# PallasTileLint: every wire kernel clean; bad fixtures fire
# ---------------------------------------------------------------------------

def test_wire_kernels_pass_tile_lint():
    from repro.kernels.ops import wire_lint_cases
    cases = wire_lint_cases()
    assert len(cases) >= 6
    for label, fn, args in cases:
        report = analyze(None, rules=[PallasTileLint()], fn=fn,
                         example_args=args, label=f"kernel[{label}]")
        assert report.ok, report.violations


def test_pack_pairing_constants_agree():
    assert analyze(None, rules=[PallasTileLint(check_constants=True)],
                   label="pack-constants").ok


def test_tile_lint_flags_misaligned_blockspec():
    import jax.experimental.pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def bad(x):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((64, 250), jnp.float32),
            grid=(8, 3),
            in_specs=[pl.BlockSpec((8, 100), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((8, 100), lambda i, j: (i, j)),
        )(x)

    with pytest.raises(AnalysisError) as e:
        analyze(None, rules=[PallasTileLint()], fn=bad,
                example_args=(jax.ShapeDtypeStruct((64, 250), jnp.float32),),
                label="bad-tiles")
    assert "tile-misaligned" in {v.cls for v in e.value.violations}


def test_tile_lint_flags_low_precision_accumulate():
    import jax.experimental.pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + x_ref[...]   # f16 add: must be fp32

    def bad(x):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float16),
        )(x)

    with pytest.raises(AnalysisError) as e:
        analyze(None, rules=[PallasTileLint()], fn=bad,
                example_args=(jax.ShapeDtypeStruct((16, 128), jnp.float16),),
                label="f16-accum")
    assert "low-precision-accumulate" in {v.cls for v in e.value.violations}


# ---------------------------------------------------------------------------
# The CI gate end to end: launch.analyze over every entry point + fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lint_hlo(tmp_path_factory):
    """Run ``make lint-hlo`` exactly as CI does, on its own 8-device
    runtime (in-process jax here is single-device)."""
    out = tmp_path_factory.mktemp("analysis") / "lint_hlo.json"
    env = dict(os.environ)
    env["REPRO_ANALYZE_DEVICES"] = "8"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO / "src"), env.get("PYTHONPATH", "")) if p)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.analyze", "--self-test",
         "--out", str(out)],
        env=env, cwd=str(REPO), capture_output=True, text=True,
        timeout=900)
    assert r.returncode == 0, (
        f"launch.analyze failed\n--- stdout ---\n{r.stdout[-4000:]}\n"
        f"--- stderr ---\n{r.stderr[-4000:]}")
    with open(out) as f:
        return json.load(f)


def test_every_entry_point_analyzes_clean(lint_hlo):
    assert lint_hlo["ok"] is True
    labels = {t["label"] for t in lint_hlo["targets"]}
    # the entry-point coverage the issue names
    for want in ("hermes_round[", "hermes_round_closed[", "hermes_dispatch[",
                 "hermes_commit[", "elastic_shrink_round[",
                 "elastic_grow_round[", "train_step[", "train_hermes"):
        assert any(lbl.startswith(want) for lbl in labels), (want, labels)
    assert all(t["ok"] for t in lint_hlo["targets"])


def test_commit_half_is_pod_local_and_donates(lint_hlo):
    """The async commit executable (production ``make_async_round_jits``
    jit) lowers with zero cross-pod collectives AND its ``pod_params`` /
    ``pending`` donations survive into ``input_output_alias``."""
    commit = [t for t in lint_hlo["targets"]
              if t["label"].startswith("hermes_commit[")]
    assert commit and all(t["ok"] for t in commit)
    rules = set(commit[0]["rules"])
    assert {"collective-placement", "donation-aliasing"} <= rules


def test_each_rule_proven_live_by_fixture(lint_hlo):
    fired = {f["expected_class"]: f["raised"]
             for f in lint_hlo["self_test"]}
    assert fired == {
        "fp32-model-crossing": True,   # the PR 5 GSPMD hoist, re-created
        "dropped-donation": True,      # commit jitted without donate_argnums
        "host-sync-in-loop": True,     # bool(any_push) per round (PR 4)
        "tile-misaligned": True,       # BlockSpec not dividing the array
    }
