"""Hash-probe kernel validation (kernels/hash_join).

Pallas kernel (interpret=True on this CPU container) and the XLA
gather oracle vs the numpy fallback: the probe is pure int32 in /
int32 out, so everything is bit-exact — no tolerance anywhere. Shape
sweeps cover padding on both the probe and table axes; the numpy
fallback is part of the contract (``kernels.fallback`` routes the
execution backends through it when JAX/x64 cannot serve a dtype).
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.hash_join.kernel import (  # noqa: E402
    hash_probe_kernel, probe_tiling)
from repro.kernels.hash_join.ops import (  # noqa: E402
    build_probe_table_np, hash_probe, hash_probe_np)
from repro.kernels.hash_join.ref import (  # noqa: E402
    build_probe_table, hash_probe_ref)


def _case(n_build, n_probe, table_size, seed, dup=True):
    r = np.random.default_rng(seed)
    hi = table_size if dup else min(table_size * 4, 2**30)
    slots = np.sort(r.integers(0, table_size, n_build)).astype(np.int32)
    probes = r.integers(-2, hi + 2, n_probe).astype(np.int32)
    return slots, probes


def _oracle(slots_sorted, probes, table_size):
    starts = np.zeros(len(probes), np.int32)
    counts = np.zeros(len(probes), np.int32)
    for i, p in enumerate(probes):
        if 0 <= p < table_size:
            run = np.flatnonzero(slots_sorted == p)
            if len(run):
                starts[i] = run[0]
                counts[i] = len(run)
    return starts, counts


@pytest.mark.parametrize("n_build,n_probe,table_size", [
    (200, 501, 37),      # ragged everything
    (256, 512, 64),      # exact block multiples
    (3, 5, 2),           # smaller than any block
    (0, 7, 4),           # empty build side
    (100, 0, 16),        # empty probe side
    (3000, 5000, 1000),  # 5 x 8 kernel tiles: crosses table tiles
])
def test_build_and_probe_match_brute_force(n_build, n_probe,
                                           table_size):
    slots, probes = _case(n_build, n_probe, table_size, seed=n_probe)
    ts_np, tc_np = build_probe_table_np(slots, table_size)
    ts, tc = build_probe_table(jnp.asarray(slots), table_size)
    np.testing.assert_array_equal(np.asarray(ts), ts_np)
    np.testing.assert_array_equal(np.asarray(tc), tc_np)

    want_s, want_c = _oracle(slots, probes, table_size)
    for got_s, got_c in [
        hash_probe_np(ts_np, tc_np, probes),
        hash_probe_ref(jnp.asarray(ts_np), jnp.asarray(tc_np),
                       jnp.asarray(probes)),
        hash_probe_kernel(jnp.asarray(ts_np), jnp.asarray(tc_np),
                          jnp.asarray(probes), block_n=64, block_t=16,
                          interpret=True),
    ]:
        got_c = np.asarray(got_c)
        np.testing.assert_array_equal(got_c, want_c)
        # starts are only meaningful where a match exists
        hit = want_c > 0
        np.testing.assert_array_equal(np.asarray(got_s)[hit],
                                      want_s[hit])


def test_invalid_build_slots_are_dropped():
    """Out-of-range build slots (padding / other shards' key ranges)
    must not contribute to any (start, count)."""
    slots = np.array([0, 0, 2, 9, 9, -1], dtype=np.int32)
    slots = np.sort(slots)
    ts, tc = build_probe_table_np(slots, 5)
    assert tc.tolist() == [2, 0, 1, 0, 0]
    s, c = hash_probe_np(ts, tc, np.array([0, 2, 9, -1], np.int32))
    assert c.tolist() == [2, 1, 0, 0]


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
    slots, probes = _case(3000, 5000, 1000, seed=3)
    ts, tc = build_probe_table_np(slots, 1000)
    tilings = ((1024, 128), (2048, 256), (3072, 512))
    _assert_distinct_multi_tile_grids(len(probes), 1000, tilings)
    outs = []
    for block_n, block_t in tilings:
        s, c = hash_probe_kernel(
            jnp.asarray(ts), jnp.asarray(tc), jnp.asarray(probes),
            block_n=block_n, block_t=block_t, interpret=True)
        outs.append((np.asarray(s), np.asarray(c)))
    for s, c in outs[1:]:
        np.testing.assert_array_equal(s, outs[0][0])
        np.testing.assert_array_equal(c, outs[0][1])


def test_ops_wrapper_dispatches_pallas_and_ref():
    slots, probes = _case(300, 700, 50, seed=4)
    ts, tc = build_probe_table_np(slots, 50)
    a = hash_probe(jnp.asarray(ts), jnp.asarray(tc),
                   jnp.asarray(probes), use_pallas=False, interpret=True)
    b = hash_probe(jnp.asarray(ts), jnp.asarray(tc),
                   jnp.asarray(probes), use_pallas=True,
                   block_n=128, block_t=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_kernel_stays_int32_under_x64_scope():
    """The sharded backend calls the probe inside an enable_x64 scope;
    the kernel's accumulators are dtype-pinned so the Pallas stores
    stay int32."""
    slots, probes = _case(100, 200, 20, seed=5)
    ts, tc = build_probe_table_np(slots, 20)
    with jax.enable_x64(True):
        s, c = hash_probe(jnp.asarray(ts), jnp.asarray(tc),
                          jnp.asarray(probes), use_pallas=True,
                          block_n=64, block_t=8, interpret=True)
    want_s, want_c = hash_probe_np(ts, tc, probes)
    np.testing.assert_array_equal(np.asarray(c), want_c)
    hit = want_c > 0
    np.testing.assert_array_equal(np.asarray(s)[hit], want_s[hit])
