"""Compile the wire path for a described TPU v5e (2x2) without the chip.

The TPU compiler is installed with JAX, and it compiles for a topology that
is described, not attached: what it refuses here (misaligned tiles, too
much fast memory, a kernel that cannot be partitioned) it would refuse on
the chip.  Nothing runs, so these tests say nothing about results or times.

* the six wire kernels at phi3-mini widths, leaves of (3072, 8192) with
  2-pod payloads, called on the kernel modules with ``interpret=False``;
* the Hermes round placed on four described chips, with the kernels on,
  which must keep each kernel per device (a Mosaic kernel cannot be
  partitioned automatically).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
from __future__ import annotations

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
