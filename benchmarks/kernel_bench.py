"""Kernel microbenchmarks (framework layer, no paper table).

Wall-clock on CPU for the jnp formulations (scan vs chunked vs blocked) —
the *relative* numbers motivate the Pallas kernels; the kernels themselves
are timed in interpret mode only for correctness, not speed (CPU container;
TPU is the target).  Derived column = achieved GFLOP/s of the jnp path.

Two extra modes for the sub-byte wire path (ISSUE 5):

* ``--wire-bytes`` — per-format **measured** payload bytes at LM scale
  (the ``lm100m`` parameter tree via ``jax.eval_shape``, no allocation),
  written to ``results/bench/wire_path.json`` so the physical B/element of
  every registered format is a tracked trajectory artifact.
* ``--smoke`` — correctness gate for the Makefile ``kernel-smoke`` target:
  pack/unpack round-trip exactness, packed-vs-unpacked fused-merge
  bit-identity, and the half-width payload invariant, all through the
  kernel dispatch path (run it under ``REPRO_WIRE_KERNEL=1`` to execute
  the Pallas kernels in interpret mode on CPU).
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.models.attention import blocked_attention, naive_attention
from repro.models.rwkv import wkv_scan, wkv_chunked
from repro.models.rglru import lru_scan, lru_scan_sequential


def _time(fn, *args, reps=3) -> float:
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    return (time.time() - t0) / reps * 1e6  # us


def run(*, fast: bool = False) -> List[Dict]:
    rows = []
    B, S, H, D = (1, 512, 4, 32) if fast else (2, 1024, 8, 64)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    attn_flops = 4.0 * B * H * S * S * D

    f_naive = jax.jit(lambda q, k, v: naive_attention(q, k, v, causal=True))
    f_block = jax.jit(lambda q, k, v: blocked_attention(q, k, v, causal=True,
                                                        q_chunk=256,
                                                        kv_chunk=256))
    for name, fn in [("attn_naive", f_naive), ("attn_blocked_jnp", f_block)]:
        us = _time(fn, q, k, v)
        rows.append({"name": name, "us_per_call": round(us, 1),
                     "derived": f"{attn_flops / us / 1e3:.1f}GFLOP/s"})

    T = 512 if fast else 2048
    Hh, Dd = 4, 32
    r = jax.random.normal(ks[3], (B, T, Hh, Dd)) * 0.5
    kk = jax.random.normal(ks[4], (B, T, Hh, Dd)) * 0.5
    vv = jax.random.normal(ks[5], (B, T, Hh, Dd)) * 0.5
    lw = -jnp.exp(jax.random.normal(ks[0], (B, T, Hh, Dd)) * 0.3 - 2.0)
    u = jnp.zeros((Hh, Dd))
    s0 = jnp.zeros((B, Hh, Dd, Dd))
    wkv_flops = 4.0 * B * T * Hh * Dd * Dd
    f_scan = jax.jit(lambda *a: wkv_scan(*a))
    f_chunk = jax.jit(lambda *a: wkv_chunked(*a))
    for name, fn in [("wkv6_scan", f_scan), ("wkv6_chunked", f_chunk)]:
        us = _time(fn, r, kk, vv, lw, u, s0)
        rows.append({"name": name, "us_per_call": round(us, 1),
                     "derived": f"{wkv_flops / us / 1e3:.1f}GFLOP/s"})

    W = 256 if fast else 1024
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (B, T, W))) * 0.2 + 0.8
    b = jax.random.normal(ks[2], (B, T, W)) * 0.1
    f_assoc = jax.jit(lambda a, b: lru_scan(a, b, None))
    f_seq = jax.jit(lambda a, b: lru_scan_sequential(a, b, None))
    for name, fn in [("rglru_assoc", f_assoc), ("rglru_seq", f_seq)]:
        us = _time(fn, a, b)
        rows.append({"name": name, "us_per_call": round(us, 1),
                     "derived": f"{2.0 * B * T * W / us / 1e3:.1f}GFLOP/s"})
    rows += run_wire(fast=fast)
    return rows


def run_wire(*, fast: bool = False) -> List[Dict]:
    """The quantized wire path: encode / pack / fused-merge timings.

    jnp formulations (the CPU fallback path), one LM-block-sized leaf;
    derived column = effective wire GB/s (payload bytes produced or merged
    per wall second) so the packed rows show the bytes halving directly.
    """
    from repro.dist.wire import block_axis, get_format
    from repro.kernels import ref

    rows: List[Dict] = []
    n_pods = 2
    shape = (768, 2048) if fast else (4096, 2048)
    x = jax.random.normal(jax.random.PRNGKey(0), (n_pods,) + shape) * 0.1
    ax = block_axis((n_pods,) + shape)
    key = jax.random.PRNGKey(1)
    for mode in ("int8", "int4"):
        fmt = get_format(mode)
        enc = jax.jit(lambda v, k, _f=fmt: _f.encode(v, rng=k))
        us = _time(enc, x, key)
        pb = sum(int(a.size) * a.dtype.itemsize
                 for a in enc(x, key).values())
        rows.append({"name": f"wire_encode_{mode}", "us_per_call": round(us, 1),
                     "derived": f"{pb / us / 1e3:.2f}GB/s;payload={pb}B"})

    q8 = get_format("int8").encode(x)["q"]
    f_pack = jax.jit(lambda q: ref.pack_nibbles_ref(q, axis=ax))
    packed = f_pack(q8)
    us = _time(f_pack, q8)
    rows.append({"name": "pack_nibbles_jnp", "us_per_call": round(us, 1),
                 "derived": f"{packed.size / us / 1e3:.2f}GB/s(out)"})
    f_unpack = jax.jit(lambda p: ref.unpack_nibbles_ref(p, axis=ax))
    us = _time(f_unpack, packed)
    rows.append({"name": "unpack_nibbles_jnp", "us_per_call": round(us, 1),
                 "derived": f"{q8.size / us / 1e3:.2f}GB/s(out)"})

    g = jax.random.normal(jax.random.PRNGKey(2), shape)
    w2 = jnp.array([0.5, 1.25])
    denom = 0.7 + float(w2.sum())
    p4 = get_format("int4").encode(x, rng=key)
    merged_bytes = p4["q_packed"].size + 4 * p4["scales"].size
    f_ref = jax.jit(lambda g, q, s: ref.dequant_merge_packed_ref(
        g, q, s, w2, denom, True, axis=ax))
    us = _time(f_ref, g, p4["q_packed"], p4["scales"])
    rows.append({"name": "dequant_merge_packed_jnp",
                 "us_per_call": round(us, 1),
                 "derived": f"{merged_bytes / us / 1e3:.2f}GB/s(payload)"})
    q4 = ref.unpack_nibbles_ref(p4["q_packed"], axis=ax)
    f_ref8 = jax.jit(lambda g, q, s: ref.dequant_merge_ref(
        g, q, s, w2, denom, True, axis=ax))
    us = _time(f_ref8, g, q4, p4["scales"])
    gbs = (q4.size + 4 * p4["scales"].size) / us / 1e3
    rows.append({"name": "dequant_merge_unpacked_jnp",
                 "us_per_call": round(us, 1),
                 "derived": f"{gbs:.2f}GB/s(payload)"})
    return rows


def _round_level_bytes() -> Dict:
    """Round-level B/element per format, measured from the lowered HLO.

    Spawns ``repro.launch.round_audit --pin-only`` in a forced-8-device
    subprocess (the parent may be a 1-device runtime): each format's full
    ``hermes_round`` is lowered on a ``(pod, data, model)`` mesh and the
    cross-pod collective operands are classified against the billed wire
    specs, so the numbers come from what the collective physically ships,
    not the billing model.
    """
    import json
    import os
    import subprocess
    import sys
    import tempfile

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"  # the child must not reach for the TPU
    with tempfile.TemporaryDirectory() as td:
        tmp = os.path.join(td, "round_audit.json")
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.round_audit",
             "--pin-only", "--out", tmp],
            env=env, capture_output=True, text=True, timeout=900)
        if r.returncode != 0:
            raise RuntimeError(
                f"round_audit --pin-only failed:\n{r.stderr[-4000:]}")
        with open(tmp) as f:
            return json.load(f)


def wire_bytes(*, out: str = "results/bench/wire_path.json") -> Dict:
    """Measured per-format wire bytes: billed (lm100m tree) + round-level.

    Two columns per format: ``payload_bytes``/``bytes_per_element`` are
    the Level-A bill for one push of the lm100m parameter tree;
    ``round_bytes_per_element`` is measured from the lowered full round's
    cross-pod collectives (see :func:`_round_level_bytes`) and is the
    number README's wire table quotes as *measured on the wire*.
    """
    import json
    import os

    from repro.dist.compression import payload_bytes
    from repro.dist.wire import available_formats
    from repro.launch.train import _preset
    from repro.models import init_lm

    cfg = _preset("lm100m")
    params = jax.eval_shape(lambda k: init_lm(cfg, k)[0],
                            jax.random.PRNGKey(0))
    n_elts = sum(math.prod(s.shape) for s in jax.tree.leaves(params))
    rec = {"bench": "wire_path", "arch": "lm100m", "elements": n_elts,
           "formats": {}}
    for name in available_formats():
        b = payload_bytes(params, name)
        rec["formats"][name] = {
            "payload_bytes": b,
            "bytes_per_element": round(b / n_elts, 6),
        }
    audit = _round_level_bytes()
    rec["round_audit_devices"] = audit["devices"]
    for name, entry in audit["formats"].items():
        low = entry["lowering"]
        rec["formats"].setdefault(name, {}).update({
            "round_bytes_per_element": low["round_bytes_per_element"],
            "round_control_bytes": low["control_bytes"],
            "closed_round_cross_pod_collectives":
                low["closed_cross_pod_collectives"],
        })
    # the tentpole invariant, pinned in the trajectory artifact itself:
    # int4 physically ships at most nibbles + fp32 block scales — both as
    # billed for the lm100m tree and as lowered for the full round
    assert rec["formats"]["int4"]["bytes_per_element"] <= 0.5625, rec
    assert (rec["formats"]["int4"]["payload_bytes"]
            <= 0.53 * rec["formats"]["int8"]["payload_bytes"]), rec
    assert rec["formats"]["int4"]["round_bytes_per_element"] <= 0.5625, rec
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def smoke() -> Dict:
    """Kernel-path correctness gate (Makefile ``kernel-smoke``).

    Run under ``REPRO_WIRE_KERNEL=1`` so encode/decode route through the
    Pallas pack kernels in interpret mode; the merge kernels are exercised
    directly.  Asserts: exact pack round-trip over the full nibble range,
    the packed fused merge bit-identical to the unpacked kernel (packing
    is a layout change, not a semantics change), payloads physically
    half-width, and ref-oracle agreement.
    """
    import numpy as np

    from repro.dist.wire import block_axis, get_format
    from repro.kernels import dequant_merge as D
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-8, 8, size=(3, 512, 5)), jnp.int8)
    p = ops.pack_int4(q, axis=1)
    assert p.shape == (3, 256, 5)
    np.testing.assert_array_equal(np.asarray(ops.unpack_int4(p, axis=1)),
                                  np.asarray(q))
    np.testing.assert_array_equal(np.asarray(p),
                                  np.asarray(ref.pack_nibbles_ref(q, axis=1)))

    fmt = get_format("int4")
    n_pods, shape = 2, (7, 300)
    x = jnp.asarray(rng.normal(0, 0.1, (n_pods,) + shape), jnp.float32)
    pay = fmt.encode(x, rng=jax.random.PRNGKey(0))
    ax = block_axis((n_pods,) + shape)
    assert pay["q_packed"].shape[ax] == fmt.packed_len(shape[ax - 1])
    q_trim = fmt.unpack_payload(pay, (n_pods,) + shape)
    assert pay["q_packed"].size * 2 == q_trim.size  # two nibbles per byte
    nb = pay["scales"].shape[ax]
    widths = [(0, 0)] * q_trim.ndim
    widths[ax] = (0, nb * 256 - q_trim.shape[ax])
    q_full = jnp.pad(q_trim, widths)
    g = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    w2 = jnp.array([0.5, 1.25])
    denom = 0.7 + float(w2.sum())
    out_p = D.dequant_merge_packed(g, pay["q_packed"], pay["scales"], w2,
                                   denom, True, axis=ax, interpret=True)
    out_u = D.dequant_merge(g, q_full, pay["scales"], w2, denom, True,
                            axis=ax, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_u))
    want = ref.dequant_merge_packed_ref(g, pay["q_packed"], pay["scales"],
                                        w2, denom, True, axis=ax)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(want),
                               atol=1e-5)
    return {"pack_roundtrip": "exact", "packed_merge": "bit-identical",
            "payload_halved": True, "ok": True}


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--wire-bytes", action="store_true",
                    help="write results/bench/wire_path.json (measured "
                         "per-format payload bytes at LM scale)")
    ap.add_argument("--smoke", action="store_true",
                    help="pack/unpack + packed-merge kernel correctness "
                         "gate (interpret mode)")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        print(json.dumps(smoke(), indent=2))
    elif args.wire_bytes:
        print(json.dumps(wire_bytes(), indent=2))
    else:
        for row in run(fast=args.fast):
            print(f"{row['name']},{row['us_per_call']},{row['derived']}")
