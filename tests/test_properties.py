"""Hypothesis property tests on the system's invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.config import HermesConfig
from repro.core.allocator import (
    dual_binary_search, detect_outliers, predicted_time,
)
from repro.core.gup import gup_init, gup_update
from repro.core.loss_sgd import loss_weighted_merge
from repro.dist.compression import quantize_int8, dequantize_int8
from repro.kernels import ref


# ---------------------------------------------------------------------------
# Allocator invariants
# ---------------------------------------------------------------------------

@given(k=st.floats(1e-4, 1.0), target=st.floats(0.05, 50.0))
@settings(max_examples=80, deadline=None)
def test_alloc_valid_and_near_target(k, target):
    a = dual_binary_search(k, target, dss_domain=(16, 60000))
    assert a.mbs in (2, 4, 8, 16, 32, 64, 128, 256)
    assert 16 <= a.dss <= 60000 or a.dss == a.mbs
    assert a.dss >= a.mbs
    t = predicted_time(k, 1, a.dss, a.mbs)
    # never more than one mini-batch step over the target
    assert t <= target + k + 1e-9


@given(st.lists(st.floats(0.1, 10.0), min_size=4, max_size=24))
@settings(max_examples=60, deadline=None)
def test_outliers_subset_and_extremes(times):
    d = {f"w{i}": t for i, t in enumerate(times)}
    out = detect_outliers(d)
    assert set(out) <= set(d)
    # the cluster median is never an outlier
    med = sorted(times)[len(times) // 2]
    med_key = [k for k, v in d.items() if v == med][0]
    assert med_key not in out


# ---------------------------------------------------------------------------
# GUP invariants
# ---------------------------------------------------------------------------

@given(st.lists(st.floats(0.01, 10.0), min_size=3, max_size=60))
@settings(max_examples=60, deadline=None)
def test_gup_alpha_bounded_and_counters_consistent(losses):
    cfg = HermesConfig(alpha=-1.3, beta=0.1, lam=3)
    s = gup_init(cfg)
    pushes = 0
    for x in losses:
        p, s = gup_update(s, float(x))
        pushes += p
        assert cfg.alpha_min - 1e-9 <= s.alpha <= cfg.alpha_max + 1e-9
        assert len(s.queue) <= cfg.window
    assert s.pushes == pushes
    assert s.iterations == len(losses)


@given(st.lists(st.floats(1.0, 1.000001), min_size=5, max_size=30))
@settings(max_examples=30, deadline=None)
def test_gup_never_pushes_on_constant_loss(losses):
    cfg = HermesConfig(alpha=-0.5)
    s = gup_init(cfg)
    for x in losses:
        p, s = gup_update(s, 1.0)
        assert not p  # sigma == 0 -> z undefined -> no push


# ---------------------------------------------------------------------------
# Loss-weighted merge invariants
# ---------------------------------------------------------------------------

@given(l1=st.floats(0.01, 100.0), l2=st.floats(0.01, 100.0),
       a=st.floats(-5, 5), b=st.floats(-5, 5))
@settings(max_examples=80, deadline=None)
def test_merge_between_operands(l1, l2, a, b):
    s = {"x": jnp.float32(a)}
    g = {"x": jnp.float32(b)}
    m = float(loss_weighted_merge(s, g, l1, l2)["x"])
    lo, hi = min(a, b), max(a, b)
    assert lo - 1e-4 <= m <= hi + 1e-4


# ---------------------------------------------------------------------------
# Quantization invariants
# ---------------------------------------------------------------------------

@given(st.integers(1, 2000), st.floats(1e-3, 1e3))
@settings(max_examples=40, deadline=None)
def test_quantize_error_bound(n, scale):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(0, scale, n), jnp.float32)
    q, s = ref.quantize_int8_ref(x)
    xr = ref.dequantize_int8_ref(q, s, x.shape)
    err = np.abs(np.asarray(x - xr))
    per_block_bound = np.repeat(np.asarray(s[:, 0]), 256)[:n] * 0.5 + 1e-7
    assert np.all(err <= per_block_bound)


@given(st.integers(1, 3000), st.floats(1e-3, 1e3))
@settings(max_examples=40, deadline=None)
def test_dist_quantize_roundtrip_bounded(n, scale):
    """dist.compression round-trip error <= half an int8 step per block."""
    rng = np.random.default_rng(n + 7)
    x = jnp.asarray(rng.normal(0, scale, n), jnp.float32)
    q, s = quantize_int8(x)
    xr = dequantize_int8(q, s, x.shape)
    err = np.abs(np.asarray(x - xr))
    bound = np.repeat(np.asarray(s[:, 0]), 256)[:n] * 0.5 + 1e-7
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert np.all(err <= bound)


@given(st.integers(1, 5000))
@settings(max_examples=40, deadline=None)
def test_payload_bytes_matches_int8_wire_format(n):
    """int8 billing is the *measured* wire payload — and because block
    padding is trimmed off the wire, that is exactly one byte per real
    element plus one fp32 scale per 256-block, with int8 < fp16 < none for
    any payload > 8 elements."""
    from repro.dist.compression import compress_tree, payload_bytes
    tree = {"g": jnp.zeros((n,), jnp.float32)}
    nblocks = -(-n // 256)
    assert payload_bytes(tree, "int8") == n + 4 * nblocks
    assert payload_bytes(tree, "fp16") == 2 * n
    assert payload_bytes(tree, "none") == 4 * n
    if n > 8:  # below ~8 elements the per-block scale dominates
        assert payload_bytes(tree, "int8") < payload_bytes(tree, "fp16") \
            < payload_bytes(tree, "none")


@given(st.integers(1, 2000), st.floats(1e-3, 1e3), st.integers(0, 2 ** 16))
@settings(max_examples=30, deadline=None)
def test_int4_stochastic_error_bounded_per_block(n, scale, seed):
    """int4 stochastic rounding never errs by more than one step (= the
    per-block scale) on any element, for any rounding key."""
    from repro.dist.wire import get_format
    rng = np.random.default_rng(n + seed)
    x = jnp.asarray(rng.normal(0, scale, n), jnp.float32)
    fmt = get_format("int4")
    p = fmt.encode(x, rng=jax.random.PRNGKey(seed))
    xr = fmt.decode(p, x.shape, x.dtype)
    err = np.abs(np.asarray(x - xr))
    step = np.repeat(np.asarray(p["scales"]), 256)[:n]
    assert np.all(err <= step + 1e-6)
    # the wire array is nibble-packed; every unpacked nibble is int4
    assert p["q_packed"].dtype == jnp.int8
    assert p["q_packed"].shape == (fmt.packed_len(n),)
    q = fmt.unpack_payload(p, x.shape)
    assert q.shape == x.shape
    assert np.abs(np.asarray(q)).max() <= 7


@given(st.integers(8, 256), st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_int4_stochastic_rounding_unbiased(n, seed):
    """E[decode(encode(x))] = x: averaging reconstructions over many
    independent rounding keys converges on x itself (a deterministic
    floor/round would leave a fixed bias of up to one step)."""
    from repro.dist.wire import get_format
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1.0, n), jnp.float32)
    fmt = get_format("int4")
    keys = jax.random.split(jax.random.PRNGKey(seed), 256)
    recs = jax.vmap(
        lambda k: fmt.decode(fmt.encode(x, rng=k), x.shape, x.dtype))(keys)
    mean_err = np.abs(np.asarray(jnp.mean(recs, 0) - x))
    step = np.repeat(np.asarray(fmt.encode(x)["scales"]), 256)[:n]
    # se of the mean is <= step/2/sqrt(256) = step/32; allow 8 sigma —
    # far under the ~0.5-step mean bias a deterministic floor would leave
    assert np.all(mean_err <= step * 0.25 + 1e-6)


@given(st.integers(9, 5000))
@settings(max_examples=25, deadline=None)
def test_int4_payload_bytes_below_int8(n):
    """The packed int4 payload measures the paired nibble bytes — 128 per
    whole 256-block plus ceil(rem/2) for a final partial block — plus the
    same scales: strictly below int8's byte-per-element for any n >= 2."""
    from repro.dist.compression import payload_bytes
    from repro.dist.wire import Int4Format
    tree = {"g": jnp.zeros((n,), jnp.float32)}
    nblocks = -(-n // 256)
    assert Int4Format.packed_len(n) == \
        (n // 256) * 128 + (n % 256 + 1) // 2
    assert payload_bytes(tree, "int4") == \
        Int4Format.packed_len(n) + 4 * nblocks
    assert payload_bytes(tree, "int4") < payload_bytes(tree, "int8")


@given(st.integers(1, 8), st.integers(1, 6), st.integers(0, 2 ** 16))
@settings(max_examples=30, deadline=None)
def test_pack_unpack_roundtrip_property(nb, lead, seed):
    """Nibble pack/unpack recovers every int4 value in [-8, 7] exactly —
    sign included — for any whole-block axis length and leading shape."""
    from repro.kernels import ref
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.integers(-8, 8, size=(lead, nb * 256)), jnp.int8)
    p = ref.pack_nibbles_ref(q, axis=1)
    assert p.shape == (lead, nb * 128) and p.dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(ref.unpack_nibbles_ref(p, axis=1)), np.asarray(q))


@given(st.integers(1, 4000), st.integers(0, 2 ** 16))
@settings(max_examples=20, deadline=None)
def test_payload_bytes_equals_measured_nbytes_property(n, seed):
    """For every registered format, the billed payload_bytes equal the
    summed nbytes of what encode actually emits (padding edges included)."""
    from repro.dist.wire import available_formats, get_format
    x = jnp.asarray(np.random.default_rng(seed).normal(0, 1, n), jnp.float32)
    for name in available_formats():
        fmt = get_format(name)
        p = fmt.encode(x, rng=jax.random.PRNGKey(seed))
        measured = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in p.values())
        assert fmt.payload_bytes(x.shape) == measured, name


@given(st.integers(2, 600), st.integers(0, 2 ** 16))
@settings(max_examples=25, deadline=None)
def test_error_feedback_telescopes(n, seed):
    """Summing k error-fed reconstructions recovers k*x up to one final
    residual — the telescoping identity error feedback exists for."""
    from repro.dist.compression import compress_tree
    rng = np.random.default_rng(seed)
    x = {"g": jnp.asarray(rng.normal(0, 1, n), jnp.float32)}
    err = None
    acc = np.zeros(n, np.float32)
    k = 4
    for _ in range(k):
        rec, err = compress_tree(x, mode="int8", error=err)
        acc = acc + np.asarray(rec["g"])
    # sum of what crossed the wire = k*x - final residual (exact identity)
    np.testing.assert_allclose(acc, k * np.asarray(x["g"])
                               - np.asarray(err["g"]), atol=1e-4)
