"""Compile the wire path for a described TPU v5e (2x2) without the chip.

The TPU compiler is installed with JAX, and it compiles for a topology that
is described, not attached: what it refuses here (misaligned tiles, too
much fast memory, a kernel that cannot be partitioned) it would refuse on
the chip.  Nothing runs, so these tests say nothing about results or times.

* the six wire kernels at phi3-mini widths, leaves of (3072, 8192) with
  2-pod payloads, called on the kernel modules with ``interpret=False``;
* the Hermes round placed on four described chips, with the kernels on,
  which must keep each kernel per device (a Mosaic kernel cannot be
  partitioned automatically);
* the Hermes cell's synchronous round on one described chip, whose
  compiler-added ``copy`` instructions are counted: the round's
  ``lax.cond`` must not make the compiler copy the donated model around it.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
from __future__ import annotations

import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

N_PODS = 2
ROWS, COLS = 3072, 8192           # phi3-mini d_model x d_ff
BLOCK = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_cases():
    from repro.kernels import dequant_merge, loss_weighted_update, pack
    from repro.kernels import quantize
    f32, i8 = jnp.float32, jnp.int8
    g = ((ROWS, COLS), f32)
    q = ((N_PODS, ROWS, COLS), i8)
    qp = ((N_PODS, ROWS, COLS // 2), i8)
    sc = ((N_PODS, ROWS, COLS // BLOCK), f32)
    w2, s, flag = ((N_PODS,), f32), ((), f32), ((), jnp.bool_)
    merge_ax = 2                  # block_axis((2, 3072, 8192))
    return {
        "quantize_int8": (partial(quantize.quantize_int8, block=BLOCK),
                          [g]),
        "pack_int4": (partial(pack.pack_int4, axis=2), [q]),
        "unpack_int4": (partial(pack.unpack_int4, axis=2), [qp]),
        "loss_weighted_update": (
            loss_weighted_update.loss_weighted_update,
            [g, ((N_PODS, ROWS, COLS), f32), s, w2, s, flag]),
        "dequant_merge": (partial(dequant_merge.dequant_merge, block=BLOCK,
                                  axis=merge_ax), [g, q, sc, w2, s, flag]),
        "dequant_merge_packed": (
            partial(dequant_merge.dequant_merge_packed, block=BLOCK,
                    axis=merge_ax), [g, qp, sc, w2, s, flag]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_wire_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(partial(fn, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.fixture(scope="module")
def placed_round_text(topo):
    """The int4 round on a (4, 1, 1) pod mesh of described chips, kernels
    forced on, compiled: its HLO text."""
    from repro.config import HermesConfig
    from repro.dist.hermes_sync import hermes_pod_state
    from repro.kernels import ops
    from repro.launch.train import make_round_jit, pod_shardings

    # the kernel wrappers pick interpret mode from the host backend (the
    # CPU here); steer them to the chip's lowering for this compile, and
    # drop jit caches that may hold interpret-mode traces of the same
    # shapes from other tests
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        jax.clear_caches()
        try:
            pods = 4
            mesh = Mesh(np.asarray(topo.devices[:pods], dtype=object)
                        .reshape(pods, 1, 1), ("pod", "data", "model"))
            hcfg = HermesConfig(alpha=-0.5, lam=1, eta=1.0,
                                compression="int4", kernel_dispatch="on")
            pod_sh, rep_sh = pod_shardings(mesh)
            params = {"w": jax.ShapeDtypeStruct((256, 1024), jnp.float32),
                      "b": jax.ShapeDtypeStruct((1024,), jnp.float32)}

            def sds(tree, sharding, lead=()):
                return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                    lead + a.shape, a.dtype, sharding=sharding), tree)

            gup = jax.eval_shape(lambda: hermes_pod_state(hcfg, pods))
            args = (sds(params, pod_sh, (pods,)), sds(gup, pod_sh),
                    jax.ShapeDtypeStruct((pods,), jnp.float32),
                    sds(params, rep_sh),
                    jax.ShapeDtypeStruct((), jnp.float32),
                    sds(params, pod_sh, (pods,)),
                    jax.ShapeDtypeStruct((2,), jnp.uint32))
            yield make_round_jit(hcfg, mesh).lower(*args).compile().as_text()
        finally:
            jax.clear_caches()


def test_placed_round_keeps_kernels_per_device(placed_round_text):
    """The placed round compiles, and its kernels are real TPU custom
    calls."""
    assert placed_round_text.count("tpu_custom_call") > 0


def test_placed_round_carries_every_phase_scope(placed_round_text):
    """The chip's compiler keeps the round's phase scopes in the op
    metadata, the payload gather's among them."""
    from repro.utils import tracing
    for s in tracing.SCOPES:
        assert f"/{s}/" in placed_round_text, s


# The cell's round as its parent compiled it (the whole merge, refresh and
# error-feedback select under the cond; pods and residual donated, the
# global model not), counted by ``_round_copies`` below: 156 copies of
# 29,660,196,992 bytes on the open path, 49 of them in the entry.
PARENT_OPEN_COPY_BYTES = 29_660_196_992
_COPY_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\w+\[[\d,]*\])\S*\s+copy\(")
_CALLEE_RE = re.compile(r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _round_copies(text):
    """``(entry, open path)``: the dims and bytes of each ``copy``
    instruction in the round's ENTRY computation, and in every computation
    an open round runs (ENTRY, the cond's open branch, the loops under it).
    A copy inside a fusion is a layout change of that fusion's own pass,
    not a pass over memory, so fusion bodies are not walked."""
    from repro.analysis.hlo_parse import (
        shape_bytes, shape_dims, split_computations,
    )
    comps, entry = split_computations(text)

    def copies(name):
        return [(tuple(shape_dims(m.group(1))), shape_bytes(m.group(1)))
                for m in map(_COPY_RE.match, comps[name]) if m]

    seen, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            if " fusion(" in line:
                continue
            todo += _CALLEE_RE.findall(line)
            branches = _BRANCHES_RE.search(line)
            if branches:
                names = [c.strip().lstrip("%")
                         for c in branches.group(1).split(",")]
                # lax.cond's index 0 is the false (closed) branch
                todo += names[1:] if name == entry else names
    return copies(entry), [c for n in seen for c in copies(n)]


@pytest.fixture(scope="module")
def cell_round(topo, one_chip):
    """The Hermes cell's synchronous round as ``train_hermes`` builds it
    (``make_round_jit``): phi3-mini widths at one layer, 2 pods, int4 with
    error feedback, the jnp wire; compiled for one described chip.
    Returns its HLO text and the dims of its donated leaves."""
    from repro.config import HermesConfig
    from repro.dist.hermes_sync import hermes_pod_state
    from repro.launch.train import _preset, make_round_jit
    from repro.models import init_lm

    hcfg = HermesConfig(alpha=-0.5, beta=0.1, lam=1, eta=1.0, window=10,
                        compression="int4", error_feedback=True,
                        kernel_dispatch="off")
    params = jax.eval_shape(
        lambda k: init_lm(_preset("phi3-mini-3.8b", 1), k)[0],
        jax.random.PRNGKey(0))

    def sds(tree, lead=()):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            lead + a.shape, a.dtype, sharding=one_chip), tree)

    def scalar(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pods = sds(params, (N_PODS,))
    gup = sds(jax.eval_shape(lambda: hermes_pod_state(hcfg, N_PODS)))
    args = (pods, gup, scalar((N_PODS,), jnp.float32), sds(params),
            scalar((), jnp.float32), pods, scalar((2,), jnp.uint32))
    text = make_round_jit(hcfg).lower(*args).compile().as_text()
    return text, {tuple(a.shape) for a in jax.tree.leaves((pods, params))}


def test_round_does_not_copy_the_model_around_its_cond(cell_round):
    """The cond carries only the global model and the residual, so no pod,
    residual or global-model leaf is copied in the round's entry, and the
    open path copies at most 40% of the bytes the parent formulation did
    (what is left: the int4 wire's layout changes)."""
    text, donated = cell_round
    entry, open_path = _round_copies(text)
    in_entry = [c for c in entry if c[0] in donated]
    assert not in_entry, in_entry
    moved = sum(b for _, b in open_path)
    assert moved <= 0.4 * PARENT_OPEN_COPY_BYTES, (
        moved, len(open_path), PARENT_OPEN_COPY_BYTES)


def test_cell_round_carries_its_phase_scopes(cell_round):
    """The round's phases keep their scopes through the chip's compiler
    (the unplaced round ships nothing, so it has no gather)."""
    from repro.utils import tracing
    text, _ = cell_round
    for s in (tracing.GATE, tracing.ENCODE, tracing.MERGE, tracing.REFRESH):
        assert f"/{s}/" in text, s
