"""Masked hash-probe kernel validation (kernels/hash_join).

The masked probe is the fused ``filter_select``-into-join primitive of
the optimizer's probe-fusion rewrite: probe rows whose mask is 0 must
report ``count == 0`` (and a zeroed start) exactly as if they had been
filtered out before probing — but without ever materializing the
filtered probe side; in the Pallas kernel the mask rides into VMEM
beside the probe slots and the dropped rows never leave it. Mirrors
``test_hash_join_kernel.py``: brute-force oracle parity across shape
sweeps (padding on both axes), block-shape invariance, the ops-level
dispatch contract (numpy fallback == XLA ref == Pallas kernel,
bit-exact int32), plus the mask-specific edges: all-filtered,
none-filtered, and mask values beyond {0, 1}.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.hash_join.kernel import (  # noqa: E402
    masked_hash_probe_kernel, probe_tiling)
from repro.kernels.hash_join.ops import (  # noqa: E402
    build_probe_table_np, hash_probe_np, masked_hash_probe,
    masked_hash_probe_np)
from repro.kernels.hash_join.ref import masked_hash_probe_ref  # noqa: E402


def _case(n_build, n_probe, table_size, seed):
    r = np.random.default_rng(seed)
    slots = np.sort(r.integers(0, table_size, n_build)).astype(np.int32)
    probes = r.integers(-2, table_size + 2, n_probe).astype(np.int32)
    mask = (r.random(n_probe) < 0.6).astype(np.int32)
    return slots, probes, mask


def _oracle(slots_sorted, probes, mask, table_size):
    """Filter-then-probe, row by row: the semantics being fused."""
    starts = np.zeros(len(probes), np.int32)
    counts = np.zeros(len(probes), np.int32)
    for i, p in enumerate(probes):
        if mask[i] and 0 <= p < table_size:
            run = np.flatnonzero(slots_sorted == p)
            if len(run):
                starts[i] = run[0]
                counts[i] = len(run)
    return starts, counts


def _all_impls(ts, tc, probes, mask):
    return [
        masked_hash_probe_np(ts, tc, probes, mask),
        masked_hash_probe_ref(jnp.asarray(ts), jnp.asarray(tc),
                              jnp.asarray(probes), jnp.asarray(mask)),
        masked_hash_probe_kernel(jnp.asarray(ts), jnp.asarray(tc),
                                 jnp.asarray(probes), jnp.asarray(mask),
                                 block_n=64, block_t=16, interpret=True),
    ]


@pytest.mark.parametrize("n_build,n_probe,table_size", [
    (200, 501, 37),      # ragged everything
    (256, 512, 64),      # exact block multiples
    (3, 5, 2),           # smaller than any block
    (0, 7, 4),           # empty build side
    (100, 0, 16),        # empty probe side
    (3000, 5000, 1000),  # 5 x 8 kernel tiles: crosses table tiles
])
def test_masked_probe_matches_brute_force(n_build, n_probe, table_size):
    slots, probes, mask = _case(n_build, n_probe, table_size,
                                seed=n_probe)
    ts, tc = build_probe_table_np(slots, table_size)
    want_s, want_c = _oracle(slots, probes, mask, table_size)
    for got_s, got_c in _all_impls(ts, tc, probes, mask):
        got_s, got_c = np.asarray(got_s), np.asarray(got_c)
        np.testing.assert_array_equal(got_c, want_c)
        hit = want_c > 0
        np.testing.assert_array_equal(got_s[hit], want_s[hit])
        # masked-off rows must read as a clean miss, not stale state
        off = mask == 0
        assert not got_c[off].any()
        assert not got_s[off].any()


@pytest.mark.parametrize("fill", [0, 1])
def test_degenerate_masks(fill):
    """none-filtered (mask all 1) must equal the unmasked probe;
    all-filtered (mask all 0) must return all-zero outputs."""
    slots, probes, _ = _case(300, 700, 50, seed=9)
    ts, tc = build_probe_table_np(slots, 50)
    mask = np.full(len(probes), fill, dtype=np.int32)
    if fill:
        want_s, want_c = hash_probe_np(ts, tc, probes)
        # unmasked probe may leave starts nonzero on miss rows; the
        # masked contract zeroes them — compare on hits + counts.
        hit = want_c > 0
    else:
        want_s = want_c = np.zeros(len(probes), np.int32)
        hit = want_c > 0
    for got_s, got_c in _all_impls(ts, tc, probes, mask):
        np.testing.assert_array_equal(np.asarray(got_c), want_c)
        np.testing.assert_array_equal(np.asarray(got_s)[hit],
                                      want_s[hit])


def test_mask_is_truthiness_not_equality():
    """Any nonzero mask value keeps the row (the backends hand in
    bool-derived int32, but the kernel contract is mask != 0)."""
    slots = np.sort(np.array([1, 1, 3], np.int32))
    ts, tc = build_probe_table_np(slots, 5)
    probes = np.array([1, 1, 3, 3], np.int32)
    mask = np.array([2, 0, -7, 0], np.int32)
    for s, c in _all_impls(ts, tc, probes, mask):
        assert np.asarray(c).tolist() == [2, 0, 1, 0]


def _assert_distinct_multi_tile_grids(n, t, tilings):
    """The tilings compared must really differ, each with >= 2 tiles on
    both grid axes (probe rows, table slots)."""
    grids = set()
    for block_n, block_t in tilings:
        rb, rows, bt, t_pad = probe_tiling(n, t, block_n, block_t)
        grids.add((rows // rb, t_pad // bt))
    assert len(grids) == len(tilings), grids
    assert min(min(g) for g in grids) >= 2, grids


def test_kernel_block_shape_invariance():
    """Tiling is a perf knob: output must not depend on block sizes."""
    slots, probes, mask = _case(3000, 5000, 1000, seed=3)
    ts, tc = build_probe_table_np(slots, 1000)
    tilings = ((1024, 128), (2048, 256), (3072, 512))
    _assert_distinct_multi_tile_grids(len(probes), 1000, tilings)
    outs = []
    for block_n, block_t in tilings:
        s, c = masked_hash_probe_kernel(
            jnp.asarray(ts), jnp.asarray(tc), jnp.asarray(probes),
            jnp.asarray(mask), block_n=block_n, block_t=block_t,
            interpret=True)
        outs.append((np.asarray(s), np.asarray(c)))
    for s, c in outs[1:]:
        np.testing.assert_array_equal(s, outs[0][0])
        np.testing.assert_array_equal(c, outs[0][1])


def test_ops_wrapper_dispatches_pallas_and_ref():
    slots, probes, mask = _case(300, 700, 50, seed=4)
    ts, tc = build_probe_table_np(slots, 50)
    a = masked_hash_probe(jnp.asarray(ts), jnp.asarray(tc),
                          jnp.asarray(probes), jnp.asarray(mask),
                          use_pallas=False, interpret=True)
    b = masked_hash_probe(jnp.asarray(ts), jnp.asarray(tc),
                          jnp.asarray(probes), jnp.asarray(mask),
                          use_pallas=True, block_n=128, block_t=32,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_kernel_stays_int32_under_x64_scope():
    """The sharded backend calls the masked probe inside an enable_x64
    scope; accumulators and the mask slab are dtype-pinned int32."""
    slots, probes, mask = _case(100, 200, 20, seed=5)
    ts, tc = build_probe_table_np(slots, 20)
    with jax.enable_x64(True):
        s, c = masked_hash_probe(jnp.asarray(ts), jnp.asarray(tc),
                                 jnp.asarray(probes), jnp.asarray(mask),
                                 use_pallas=True, block_n=64, block_t=8,
                                 interpret=True)
    want_s, want_c = masked_hash_probe_np(ts, tc, probes, mask)
    np.testing.assert_array_equal(np.asarray(c), want_c)
    hit = want_c > 0
    np.testing.assert_array_equal(np.asarray(s)[hit], want_s[hit])
