"""Masked segment-reduce (MIN/MAX) kernel validation
(kernels/segment_sum — the segment-reduce family added with
``group_by_agg``).

Pallas kernel (interpret=True on this CPU container) and the XLA
``segment_min``/``segment_max`` reference vs a numpy loop. MIN/MAX are
order-independent reductions, so there is NO float carve-out here:
every dtype must match the oracle bit for bit, including the NaN
poisoning rule (a NaN in a *valid* float lane propagates to its
segment, matching ``np.minimum``/``np.maximum`` accumulation) and the
empty-segment identity (±inf / integer dtype extremes — the backend
rewrites those to NULL fills downstream). Hypothesis-free so it runs
on minimal installs.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.segment_sum.kernel import (  # noqa: E402
    masked_segment_reduce_kernel, segment_tiling)
from repro.kernels.segment_sum.ops import masked_segment_reduce  # noqa: E402
from repro.kernels.segment_sum.ref import (  # noqa: E402
    masked_segment_reduce_ref, reduce_identity)


def _numpy_oracle(vals, ids, valid, num_segments, op):
    ident = reduce_identity(vals.dtype, op)
    red = np.full(num_segments, ident, dtype=vals.dtype)
    counts = np.zeros(num_segments, dtype=np.int32)
    fn = np.minimum if op == "min" else np.maximum
    for v, i, ok in zip(vals, ids, valid):
        if ok:
            red[i] = fn(red[i], v)      # NaN propagates, like reference
            counts[i] += 1
    return red, counts


def _case(n, num_segments, dtype, seed, p_valid=0.7, p_nan=0.0):
    r = np.random.default_rng(seed)
    ids = r.integers(0, num_segments, n).astype(np.int32)
    valid = r.random(n) < p_valid
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        vals = r.integers(max(info.min, -50), min(info.max, 50),
                          n).astype(dtype)
    else:
        vals = r.normal(size=n).astype(dtype)
        if p_nan:
            vals[r.random(n) < p_nan] = np.nan
    return vals, ids, valid


@pytest.mark.parametrize("n,num_segments", [
    (1000, 37),          # ragged both axes
    (1024, 512),         # exact block multiples
    (5, 3),              # smaller than any block
    (2000, 1),           # single segment
    (5000, 1000),        # 16 x 5 kernel tiles
])
@pytest.mark.parametrize("op", ["min", "max"])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_int32_bit_exact(n, num_segments, op, use_pallas):
    vals, ids, valid = _case(n, num_segments, np.int32, seed=n)
    want_r, want_c = _numpy_oracle(vals, ids, valid, num_segments, op)
    got_r, got_c = masked_segment_reduce(
        jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid),
        num_segments, op=op, use_pallas=use_pallas,
        block_n=256, block_s=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_r), want_r)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)


@pytest.mark.parametrize("op", ["min", "max"])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_float32_bit_exact_including_nan_poisoning(op, use_pallas):
    """MIN/MAX never reorder-drift: float comparisons are exact, and a
    NaN in a valid lane must poison exactly its own segment."""
    vals, ids, valid = _case(3000, 50, np.float32, seed=1, p_nan=0.05)
    want_r, want_c = _numpy_oracle(vals, ids, valid, 50, op)
    got_r, got_c = masked_segment_reduce(
        jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid), 50,
        op=op, use_pallas=use_pallas, block_n=512, block_s=32,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got_r), want_r)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)


@pytest.mark.parametrize("op", ["min", "max"])
def test_nan_in_invalid_lane_does_not_poison(op):
    vals = np.array([np.nan, 1.0, np.nan, 2.0], dtype=np.float32)
    ids = np.array([0, 0, 1, 1], dtype=np.int32)
    valid = np.array([False, True, False, True])
    r, c = masked_segment_reduce(
        jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid), 2,
        op=op, use_pallas=True, block_n=128, block_s=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(r),
                                  np.array([1.0, 2.0], np.float32))
    np.testing.assert_array_equal(np.asarray(c), [1, 1])


@pytest.mark.parametrize("op", ["min", "max"])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_empty_segments_hold_identity(op, use_pallas):
    vals, ids, _ = _case(500, 11, np.int32, seed=2)
    valid = np.zeros(500, dtype=bool)
    r, c = masked_segment_reduce(
        jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid), 11,
        op=op, use_pallas=use_pallas, block_n=128, block_s=8,
        interpret=True)
    ident = reduce_identity(np.dtype(np.int32), op)
    assert np.asarray(r).tolist() == [ident] * 11
    assert np.asarray(c).sum() == 0


@pytest.mark.parametrize("op", ["min", "max"])
def test_64bit_values_take_xla_segment_ops_under_pallas(op):
    """With ``use_pallas`` set, 64-bit values (x64 on) route to the XLA
    segment ops: the Pallas kernels are 32-bit."""
    vals, ids, valid = _case(1000, 37, np.int64, seed=6)
    vals = vals * np.int64(1 << 40)
    want_r, want_c = _numpy_oracle(vals, ids, valid, 37, op)
    with jax.enable_x64(True):
        got_r, got_c = masked_segment_reduce(
            jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid), 37,
            op=op, use_pallas=True, interpret=True)
        assert got_r.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(got_r), want_r)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)


def _assert_distinct_multi_tile_grids(n, num_segments, tilings):
    """The tilings compared must really differ, each with >= 2 tiles on
    both grid axes (segments, rows)."""
    grids = set()
    for block_n, block_s in tilings:
        rb, rows, bs, s_pad = segment_tiling(n, num_segments, block_n,
                                             block_s)
        grids.add((s_pad // bs, rows // rb))
    assert len(grids) == len(tilings), grids
    assert min(min(g) for g in grids) >= 2, grids


@pytest.mark.parametrize("op", ["min", "max"])
def test_kernel_block_shape_invariance(op):
    """Tiling is a perf knob: output must not depend on block sizes —
    and MIN/MAX make this exact even for floats."""
    vals, ids, valid = _case(5000, 100, np.float32, seed=3, p_nan=0.1)
    tilings = ((1024, 8), (2048, 32), (3072, 64))
    _assert_distinct_multi_tile_grids(len(vals), 100, tilings)
    outs = []
    for block_n, block_s in tilings:
        r, c = masked_segment_reduce_kernel(
            jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid),
            100, op, block_n=block_n, block_s=block_s, interpret=True)
        outs.append((np.asarray(r), np.asarray(c)))
    for r, c in outs[1:]:
        np.testing.assert_array_equal(r, outs[0][0])
        np.testing.assert_array_equal(c, outs[0][1])


@pytest.mark.parametrize("op", ["min", "max"])
def test_kernel_matches_xla_ref(op):
    vals, ids, valid = _case(2048, 96, np.int32, seed=4)
    a = masked_segment_reduce_ref(jnp.asarray(vals), jnp.asarray(ids),
                                  jnp.asarray(valid), 96, op)
    b = masked_segment_reduce_kernel(jnp.asarray(vals),
                                     jnp.asarray(ids),
                                     jnp.asarray(valid), 96, op,
                                     block_n=512, block_s=32,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_unknown_op_raises():
    vals = jnp.asarray(np.zeros(4, np.int32))
    ids = jnp.asarray(np.zeros(4, np.int32))
    ok = jnp.asarray(np.ones(4, bool))
    with pytest.raises(ValueError, match="unknown segment reduce op"):
        masked_segment_reduce(vals, ids, ok, 2, op="median",
                              interpret=True)


def test_jax_backend_pallas_minmax_matches_reference():
    """The jax backend with the Pallas kernel enabled satisfies the
    backend semantics contract on MIN/MAX (bit-exact, no carve-out)."""
    from repro.data.tables import Table
    from repro.exec.jax_backend import JaxBackend

    r = np.random.default_rng(5)
    f = r.normal(size=3000).astype(np.float32)
    f[r.random(3000) < 0.05] = np.nan
    t = Table({"k": r.integers(0, 40, 3000).astype(np.int64),
               "v": r.integers(-1000, 1000, 3000).astype(np.int32),
               "f": f})
    be = JaxBackend(use_pallas=True, interpret=True)
    got = t.group_by(["k"]).agg(("min", "v"), ("max", "v"),
                                ("min", "f"), ("max", "f"),
                                backend=be)
    want = t.group_by(["k"]).agg(("min", "v"), ("max", "v"),
                                 ("min", "f"), ("max", "f"),
                                 backend="reference")
    assert got.fingerprint() == want.fingerprint()
