#!/usr/bin/env python3
"""Bring-up check of the Hermes LM trainer on a TPU.

Drives the trainer's own entry points (``repro.launch.train``) in this one
process, with random weights from a fixed seed, at the published widths of
phi3-mini-3.8b (d_model 3072, 32 heads, d_ff 8192, vocab 32064), cut only
in depth.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the placed pod path on four chips

One chip:

  (a) device check: the first JAX device must be a TPU, or the script
      exits non-zero before any phase;
  (b) ``train_single`` at 2 layers, adamw, batch 2 x 2048 tokens;
  (c) ``train_hermes`` at 1 layer, 2 pods, sgd, batch 1 x 2048 tokens per
      pod, a round every step, so gates open and the int4 wire's Pallas
      kernels (nibble pack, packed dequant-merge) run compiled; the
      compiled round is checked for ``tpu_custom_call``.

Four chips (``--four-chips``), and nothing else:

  (d) ``train_hermes`` at phi3 widths, 1 layer, 4 pods placed one per chip
      (``make_pod_mesh(4)``): every pod-stacked tree must hold exactly one
      pod's rows on each chip, the global model must be replicated, and the
      compiled pod step must have no cross-chip collective;
  (e) the same placed path against the unplaced run (``mesh=None``, all
      four pods on one chip) at a size one chip holds (the ``lm100m``
      preset): gates identical every round, losses within ``LOSS_RTOL``.

Earlier lines report step times (host clock, after the step's results were
fetched), losses, merges, peak device memory and kernel counts.  The last
line is one JSON object naming the device; it is printed only when every
phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import re
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "phi3-mini-3.8b"
SEED = 0

# (e): placed and unplaced runs execute the same per-pod math; they may
# differ only where XLA reassociates float sums differently for a program
# partitioned one pod per chip than for one batching four pods on a chip
# (f32 accumulation, about 1e-6 relative per step), and where such a
# difference moves a value across an int4 stochastic-rounding boundary
# (one quantization step on that element).  A relative loss difference of
# 1e-3 leaves two orders of magnitude of room above that; a pod trained
# on the wrong rows or merged from the wrong payload is caught by the
# identical-gates and row-placement checks.
LOSS_RTOL = 1e-3

# a collective op in compiled HLO text, sync or async (``-start``) form
COLLECTIVE_RE = re.compile(
    r"\s(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def device_check(need: int):
    """Phase (a): a TPU, with at least ``need`` devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (first device: {devs[0].platform} "
             f"{devs[0].device_kind}); this check only runs on the chip")
    check(len(devs) >= need, f"need {need} TPU devices, JAX sees {len(devs)}")
    return devs


def peak_bytes(devs) -> str:
    stats = [d.memory_stats() or {} for d in devs]
    return ", ".join(f"dev{d.id} {s.get('peak_bytes_in_use', 'n/a')}"
                     for d, s in zip(devs, stats))


def step_seconds(log_times) -> float:
    """Median wall seconds per step after the first (compiling) one."""
    gaps = [(t1 - t0) / (s1 - s0) for (s0, t0), (s1, t1)
            in zip(log_times, log_times[1:])]
    check(bool(gaps), "too few logged steps to time a step")
    return statistics.median(gaps)


def finite(*xs) -> bool:
    return all(math.isfinite(x) for x in xs)


def phase_single(T, devs) -> None:
    from repro.config import OptimizerConfig
    cfg = T._preset(ARCH, 2)
    out = T.train_single(cfg, steps=5, batch=2, seq=2048,
                         opt_cfg=OptimizerConfig(name="adamw", lr=1e-4),
                         log_every=1, seed=SEED)
    first, last = out["first_loss"], out["last_loss"]
    check(finite(first, last), f"single: non-finite loss {first} -> {last}")
    print(f"[single] {ARCH} layers=2 adamw batch=2x2048: first loss "
          f"{first:.4f}, last loss {last:.4f}, step "
          f"{step_seconds(out['log_times']):.4f} s (device "
          f"{devs[0].device_kind}); first step incl. compile "
          f"{out['log_times'][0][1]:.1f} s", flush=True)
    print(f"[single] peak_bytes_in_use: {peak_bytes(devs[:1])}", flush=True)


def hermes_args(T, cfg, pods: int, hcfg, mesh=None):
    """Abstract ``make_round_jit`` arguments for ``pods`` replicas of
    ``cfg``, placed as ``train_hermes`` places them."""
    import jax
    import jax.numpy as jnp
    from repro.dist.hermes_sync import hermes_pod_state
    from repro.models import init_lm

    pod_sh, rep_sh = T.pod_shardings(mesh)

    def sds(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    params = jax.eval_shape(lambda k: init_lm(cfg, k)[0],
                            jax.random.PRNGKey(SEED))
    pod_params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((pods,) + a.shape, a.dtype), params)
    gup = jax.eval_shape(lambda: hermes_pod_state(hcfg, pods))
    return (sds(pod_params, pod_sh), sds(gup, pod_sh),
            jax.ShapeDtypeStruct((pods,), jnp.float32), sds(params, rep_sh),
            jax.ShapeDtypeStruct((), jnp.float32), sds(pod_params, pod_sh),
            jax.ShapeDtypeStruct((2,), jnp.uint32))


def run_hermes(T, cfg, *, pods, batch, seq, steps, opt_cfg, mesh=None):
    from repro.config import HermesConfig
    # a round every step (lam=1) and a mild gate threshold, so the gate
    # opens once its loss queue holds two entries (the third round on)
    hcfg = HermesConfig(alpha=-0.5, lam=1, eta=1.0)
    out = T.train_hermes(cfg, steps=steps, batch=batch, seq=seq, pods=pods,
                         opt_cfg=opt_cfg, hcfg=hcfg, log_every=1, seed=SEED,
                         mesh=mesh)
    first, last = out["history"][0][1], out["global_loss"]
    check(finite(first, last, *out["pod_losses"]),
          f"hermes: non-finite loss {first} -> {last}, pods "
          f"{out['pod_losses']}")
    check(out["merges"] >= 1,
          f"hermes: no gate opened in {out['rounds']} rounds")
    return out, hcfg


def phase_hermes(T, devs) -> None:
    from repro.config import OptimizerConfig
    from repro.dist.wire import resolve_kernel_dispatch
    from repro.kernels import ops
    cfg = T._preset(ARCH, 1)
    # sgd, the paper's worker optimizer: adamw's two moment trees per pod
    # do not fit beside the pod params, w_global and the error residuals
    opt = OptimizerConfig(name="sgd", lr=0.1, grad_clip=1.0)
    out, hcfg = run_hermes(T, cfg, pods=2, batch=1, seq=2048, steps=6,
                           opt_cfg=opt)
    print(f"[hermes] {ARCH} layers=1 pods=2 sgd batch=1x2048 int4: first "
          f"pod loss {out['history'][0][1]:.4f}, last global loss "
          f"{out['global_loss']:.4f}, merges {out['merges']}/"
          f"{out['rounds']} rounds, step+round "
          f"{step_seconds(out['log_times']):.4f} s (device "
          f"{devs[0].device_kind}); first step incl. compile "
          f"{out['log_times'][0][1]:.1f} s", flush=True)
    print(f"[hermes] peak_bytes_in_use: {peak_bytes(devs[:1])}", flush=True)
    check(not ops._interpret() and resolve_kernel_dispatch(
        hcfg.kernel_dispatch), "wire kernels would not run compiled")
    text = T.make_round_jit(hcfg).lower(
        *hermes_args(T, cfg, 2, hcfg)).compile().as_text()
    n = text.count("tpu_custom_call")
    print(f"[hermes] compiled round: {n} tpu_custom_call", flush=True)
    check(n > 0, "the compiled round holds no Pallas kernel")


def placement_ok(out, pods: int) -> None:
    for name, rows in out["pod_rows"].items():
        if rows is None:
            continue
        held = sorted(tuple(r) for r in rows.values())
        check(len(rows) == pods and held == [(p,) for p in range(pods)],
              f"{name}: chips hold pod rows {rows}, want one pod each")
    check(out["w_global_replicated"], "w_global is not replicated")


def pod_step_collectives(T, cfg, opt_cfg, mesh, pods, batch, seq) -> int:
    import jax
    import jax.numpy as jnp
    from repro.models import init_lm
    from repro.optim import make_optimizer
    optimizer = make_optimizer(opt_cfg)
    pod_sh, _ = T.pod_shardings(mesh)
    params = jax.eval_shape(lambda k: init_lm(cfg, k)[0],
                            jax.random.PRNGKey(SEED))
    pp = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((pods,) + a.shape, a.dtype), params)
    po = jax.eval_shape(jax.vmap(optimizer.init), pp)
    bt = {k: jax.ShapeDtypeStruct((pods, batch, seq), jnp.int32)
          for k in ("tokens", "targets")}
    args = [jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=pod_sh), t) for t in (pp, po, bt)]
    text = T.make_pod_step(cfg, optimizer, mesh).lower(
        *args).compile().as_text()
    return len(COLLECTIVE_RE.findall(text))


def phase_placed(T, devs) -> None:
    from repro.config import OptimizerConfig
    from repro.launch.mesh import make_pod_mesh
    mesh = make_pod_mesh(4)
    cfg = T._preset(ARCH, 1)
    opt = OptimizerConfig(name="sgd", lr=0.1, grad_clip=1.0)
    out, _ = run_hermes(T, cfg, pods=4, batch=1, seq=2048, steps=5,
                        opt_cfg=opt, mesh=mesh)
    placement_ok(out, 4)
    n = pod_step_collectives(T, cfg, opt, mesh, 4, 1, 2048)
    print(f"[placed] {ARCH} layers=1 pods=4 on {mesh.devices.shape} mesh: "
          f"rows per chip {out['pod_rows']['pod_params']}, merges "
          f"{out['merges']}/{out['rounds']}, global loss "
          f"{out['global_loss']:.4f}, step+round "
          f"{step_seconds(out['log_times']):.4f} s (4x "
          f"{devs[0].device_kind}); pod step cross-chip collectives {n}",
          flush=True)
    print(f"[placed] peak_bytes_in_use: {peak_bytes(devs[:4])}", flush=True)
    check(n == 0, f"the placed pod step has {n} cross-chip collectives")


def phase_compare(T, devs) -> None:
    from repro.config import OptimizerConfig
    from repro.launch.mesh import make_pod_mesh
    mesh = make_pod_mesh(4)
    cfg = T._preset("lm100m")
    opt = OptimizerConfig(name="sgd", lr=0.1, grad_clip=1.0)
    kw = dict(pods=4, batch=1, seq=512, steps=8, opt_cfg=opt)
    placed, _ = run_hermes(T, cfg, mesh=mesh, **kw)
    plain, _ = run_hermes(T, cfg, **kw)
    placement_ok(placed, 4)
    n = pod_step_collectives(T, cfg, opt, mesh, 4, 1, 512)
    check(n == 0, f"the placed pod step has {n} cross-chip collectives")
    check(placed["gates"] == plain["gates"],
          f"gates differ: placed {placed['gates']} vs unplaced "
          f"{plain['gates']}")
    pairs = ([(placed["global_loss"], plain["global_loss"])]
             + list(zip(placed["pod_losses"], plain["pod_losses"])))
    rel = max(abs(a - b) / abs(b) for a, b in pairs)
    print(f"[compare] lm100m pods=4 sgd batch=1x512, {placed['rounds']} "
          f"rounds: gates identical {placed['gates']}; global loss placed "
          f"{placed['global_loss']!r} unplaced {plain['global_loss']!r}; "
          f"max relative loss difference {rel:.3e} (tolerance "
          f"{LOSS_RTOL:g}); merges {placed['merges']}; step+round placed "
          f"{step_seconds(placed['log_times']):.4f} s, unplaced "
          f"{step_seconds(plain['log_times']):.4f} s (device "
          f"{devs[0].device_kind})", flush=True)
    check(rel <= LOSS_RTOL, f"loss differs by {rel:.3e} > {LOSS_RTOL:g}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-pod placed path on four chips "
                         "and its comparison with the unplaced run")
    args = ap.parse_args()

    devs = device_check(4 if args.four_chips else 1)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import train as T
    except ImportError as e:
        fail(f"cannot import the trainer from {ROOT / 'src'}: {e}")
    cache = T.configure_compile_cache()
    print(f"chip_smoke: {len(devs)} x {devs[0].device_kind}, compile cache "
          f"{cache}", flush=True)

    if args.four_chips:
        phase_placed(T, devs)
        phase_compare(T, devs)
    else:
        phase_single(T, devs)
        phase_hermes(T, devs)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
