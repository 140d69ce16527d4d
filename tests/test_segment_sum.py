"""Masked segment-sum kernel validation (kernels/segment_sum).

Pallas kernel (interpret=True on this CPU container) and the XLA
``segment_sum`` oracle vs a numpy loop: integer sums must be exact
(associative even under wraparound); float sums compare with
tolerance. Hypothesis-free so it runs on minimal installs; shape
sweeps cover padding on both the row and segment axes.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.segment_sum.kernel import (  # noqa: E402
    masked_segment_sum_kernel, segment_tiling)
from repro.kernels.segment_sum.ops import masked_segment_sum  # noqa: E402
from repro.kernels.segment_sum.ref import (  # noqa: E402
    masked_segment_sum_ref)


def _numpy_oracle(vals, ids, valid, num_segments):
    sums = np.zeros(num_segments, dtype=vals.dtype)
    counts = np.zeros(num_segments, dtype=np.int32)
    for v, i, ok in zip(vals, ids, valid):
        if ok:
            sums[i] += v
            counts[i] += 1
    return sums, counts


def _case(n, num_segments, dtype, seed, p_valid=0.7):
    r = np.random.default_rng(seed)
    ids = r.integers(0, num_segments, n).astype(np.int32)
    valid = r.random(n) < p_valid
    if np.issubdtype(dtype, np.integer):
        vals = r.integers(-50, 50, n).astype(dtype)
    else:
        vals = r.normal(size=n).astype(dtype)
    return vals, ids, valid


@pytest.mark.parametrize("n,num_segments", [
    (1000, 37),          # ragged both axes
    (1024, 512),         # exact block multiples
    (5, 3),              # smaller than any block
    (2000, 1),           # single segment
    (5000, 1000),        # 16 x 5 kernel tiles
])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_int32_exact(n, num_segments, use_pallas):
    vals, ids, valid = _case(n, num_segments, np.int32, seed=n)
    want_s, want_c = _numpy_oracle(vals, ids, valid, num_segments)
    got_s, got_c = masked_segment_sum(
        jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid),
        num_segments, use_pallas=use_pallas,
        block_n=256, block_s=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_s), want_s)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_float32_tolerance(use_pallas):
    vals, ids, valid = _case(3000, 50, np.float32, seed=1)
    want_s, want_c = _numpy_oracle(vals.astype(np.float64), ids, valid,
                                   50)
    got_s, got_c = masked_segment_sum(
        jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid), 50,
        use_pallas=use_pallas, block_n=512, block_s=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got_s), want_s,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)


def test_all_invalid_lanes_give_zero_sums_and_counts():
    vals, ids, _ = _case(500, 11, np.int32, seed=2)
    valid = np.zeros(500, dtype=bool)
    s, c = masked_segment_sum(
        jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid), 11,
        use_pallas=True, block_n=128, block_s=8, interpret=True)
    assert np.asarray(s).sum() == 0 and np.asarray(c).sum() == 0


def test_empty_input():
    s, c = masked_segment_sum(
        jnp.asarray(np.array([], np.float32)),
        jnp.asarray(np.array([], np.int32)),
        jnp.asarray(np.array([], bool)), 5, use_pallas=True,
        interpret=True)
    assert np.asarray(s).shape == (5,)
    assert np.asarray(c).sum() == 0


def test_64bit_values_take_xla_segment_ops_under_pallas():
    """The Pallas kernels are 32-bit; with ``use_pallas`` set, 64-bit
    values (x64 on) route to the XLA segment ops, never the kernel."""
    vals, ids, valid = _case(1000, 37, np.int64, seed=6)
    vals = vals * np.int64(1 << 40)          # needs all 64 bits
    want_s, want_c = _numpy_oracle(vals, ids, valid, 37)
    with jax.enable_x64(True):
        got_s, got_c = masked_segment_sum(
            jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid), 37,
            use_pallas=True, interpret=True)
        assert got_s.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(got_s), want_s)
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


def test_kernel_block_shape_invariance():
    """Tiling is a perf knob: output must not depend on block sizes."""
    vals, ids, valid = _case(5000, 100, np.int32, seed=3)
    tilings = ((1024, 8), (2048, 32), (3072, 64))
    _assert_distinct_multi_tile_grids(len(vals), 100, tilings)
    outs = []
    for block_n, block_s in tilings:
        s, c = masked_segment_sum_kernel(
            jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(valid), 100,
            block_n=block_n, block_s=block_s, interpret=True)
        outs.append((np.asarray(s), np.asarray(c)))
    for s, c in outs[1:]:
        np.testing.assert_array_equal(s, outs[0][0])
        np.testing.assert_array_equal(c, outs[0][1])


def test_kernel_matches_xla_ref():
    vals, ids, valid = _case(2048, 96, np.int32, seed=4)
    a = masked_segment_sum_ref(jnp.asarray(vals), jnp.asarray(ids),
                               jnp.asarray(valid), 96)
    b = masked_segment_sum_kernel(jnp.asarray(vals), jnp.asarray(ids),
                                  jnp.asarray(valid), 96,
                                  block_n=512, block_s=32,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_jax_backend_pallas_mode_matches_reference():
    """The jax backend with the Pallas kernel enabled still satisfies
    the backend semantics contract (int32 -> bit-exact)."""
    from repro.data.tables import Table
    from repro.exec.jax_backend import JaxBackend

    r = np.random.default_rng(5)
    t = Table({"k": r.integers(0, 40, 3000).astype(np.int64),
               "v": r.integers(-1000, 1000, 3000).astype(np.int32)})
    be = JaxBackend(use_pallas=True, interpret=True)
    got = t.group_by_sum(["k"], "v", out="s", backend=be)
    want = t.group_by_sum(["k"], "v", out="s", backend="reference")
    assert got.fingerprint() == want.fingerprint()
