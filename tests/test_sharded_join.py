"""Sharded-backend specifics (DESIGN.md §10) beyond the differential
suite (which already runs ``sharded`` and ``auto`` through every
registered-backend case in test_exec_backends.py):

- mesh-shape cases: the same join must fingerprint identically on 1,
  2 and 8 forced host devices (subprocess-isolated like
  test_multidevice.py — the main pytest process keeps 1 CPU device);
- the Pallas hash-probe path (REPRO_HASHJOIN_PALLAS) as a backend
  configuration, not just a kernel unit;
- the stats -> backend auto-selection decision table as a pure
  function;
- cache tokens: backend switches AND mesh-shape changes must move
  engine cache keys (the float-SUM summation-order carve-out makes a
  mesh change observable, so a stale cross-mesh hit is a correctness
  bug);
- the shared numpy-fallback plumbing: 64-bit keys/values that cannot
  lower warn once, naming jax_enable_x64.
"""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro import exec as exec_backends  # noqa: E402
from repro.data.tables import Table, col  # noqa: E402
from repro.exec.auto import choose_group_by, choose_join  # noqa: E402
from repro.exec.sharded import ShardedBackend  # noqa: E402
from repro.exec.stats import TableStats, collect_stats  # noqa: E402
from repro.kernels import fallback  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# mesh shapes (subprocess: forced host platform device count)
# ---------------------------------------------------------------------------

_MESH_BODY = """
    import numpy as np
    from repro.data.tables import Table, col

    r = np.random.default_rng(7)
    n, m = 4000, 3000
    left = Table({
        "k": r.integers(0, 500, n).astype(np.int64),
        "s": np.array([None if r.random() < 0.1 else f"u{i%7}"
                       for i in range(n)], dtype=object),
        "x": r.normal(size=n)})
    right = Table({
        "k": r.integers(0, 500, m).astype(np.int64),
        "s": np.array([None if r.random() < 0.1 else f"u{i%5}"
                       for i in range(m)], dtype=object),
        "w": r.integers(-100, 100, m).astype(np.int64)})
    for keys in (["k"], ["s"], ["k", "s"]):
        for how in ("inner", "left"):
            want = left.join(right, on=keys, how=how,
                             backend="reference").fingerprint()
            got = left.join(right, on=keys, how=how,
                            backend="sharded").fingerprint()
            assert got == want, (keys, how)
    print("MESH_JOIN ok", jax.device_count())
"""


@pytest.mark.parametrize("n_devices", [2, 8])
def test_sharded_join_matches_reference_on_mesh(n_devices):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        import jax
        assert jax.device_count() == {n_devices}, jax.devices()
    """) + textwrap.dedent(_MESH_BODY)
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert f"MESH_JOIN ok {n_devices}" in r.stdout


def test_sharded_join_single_device_inprocess():
    """1-device mesh runs the full exchange+probe path in-process."""
    r = np.random.default_rng(3)
    left = Table({"k": r.integers(0, 50, 300).astype(np.int64),
                  "x": r.normal(size=300)})
    right = Table({"k": r.integers(0, 50, 200).astype(np.int64),
                   "w": r.integers(0, 9, 200).astype(np.int64)})
    for how in ("inner", "left"):
        assert (left.join(right, on=["k"], how=how,
                          backend="sharded").fingerprint()
                == left.join(right, on=["k"], how=how,
                             backend="reference").fingerprint())


def test_sharded_pallas_probe_matches_reference():
    """REPRO_HASHJOIN_PALLAS=1 configuration: the probe inner loop runs
    through the Pallas kernel (direct-address table path)."""
    be = ShardedBackend(use_pallas_probe=True)
    r = np.random.default_rng(11)
    left = Table({"k": r.integers(0, 99, 400).astype(np.int64),
                  "x": r.integers(-5, 5, 400).astype(np.int64)})
    right = Table({"k": r.integers(0, 99, 150).astype(np.int64),
                   "w": r.normal(size=150)})
    for how in ("inner", "left"):
        assert (left.join(right, on=["k"], how=how,
                          backend=be).fingerprint()
                == left.join(right, on=["k"], how=how,
                             backend="reference").fingerprint())


def test_sharded_pallas_probe_crosses_table_tiles():
    """A key span of thousands of slots: the kernel's table axis runs
    several tiles (default block_t) and its probe axis several blocks."""
    be = ShardedBackend(use_pallas_probe=True)
    r = np.random.default_rng(12)
    left = Table({"k": r.integers(0, 3000, 3000).astype(np.int32),
                  "x": r.integers(-5, 5, 3000).astype(np.int32)})
    right = Table({"k": r.integers(0, 3000, 1500).astype(np.int32),
                   "w": r.normal(size=1500).astype(np.float32)})
    for how in ("inner", "left"):
        assert (left.join(right, on=["k"], how=how,
                          backend=be).fingerprint()
                == left.join(right, on=["k"], how=how,
                             backend="reference").fingerprint())


def test_sharded_wide_span_and_negative_keys():
    """Hash-partition mode (span past the slot budget) and rebase mode
    (negative keys) both hold the bit-for-bit contract."""
    wide_l = Table({"k": np.array([0, 2**28, 2**30, 5, -7],
                                  dtype=np.int64),
                    "l": np.arange(5, dtype=np.int64)})
    wide_r = Table({"k": np.array([2**30, 0, 2**28, 2**28, -7],
                                  dtype=np.int64),
                    "r": np.arange(5, dtype=np.int64)})
    for how in ("inner", "left"):
        assert (wide_l.join(wide_r, on=["k"], how=how,
                            backend="sharded").fingerprint()
                == wide_l.join(wide_r, on=["k"], how=how,
                               backend="reference").fingerprint())


def test_sharded_narrow_and_mixed_width_int_keys():
    """Narrow signed keys must widen to int64 before the rebase —
    native-width subtraction wraps int8 spans — and same-kind
    mixed-width keys (int16 vs int64) must not overflow casting the
    joint min into the narrow dtype (post-review regressions)."""
    l8 = Table({"k": np.array([-100, 0, 100, 50], dtype=np.int8),
                "l": np.arange(4, dtype=np.int64)})
    r8 = Table({"k": np.array([100, -100, 50], dtype=np.int8),
                "r": np.arange(3, dtype=np.int64)})
    for how in ("inner", "left"):
        assert (l8.join(r8, on=["k"], how=how,
                        backend="sharded").fingerprint()
                == l8.join(r8, on=["k"], how=how,
                           backend="reference").fingerprint())
    l16 = Table({"k": np.array([0, 5, 10], dtype=np.int16),
                 "l": np.arange(3, dtype=np.int64)})
    r64 = Table({"k": np.array([5, -100_000], dtype=np.int64),
                 "r": np.arange(2, dtype=np.int64)})
    for how in ("inner", "left"):
        assert (l16.join(r64, on=["k"], how=how,
                         backend="sharded").fingerprint()
                == l16.join(r64, on=["k"], how=how,
                            backend="reference").fingerprint())


def test_sharded_uint64_keys_past_int64_range():
    """uint64 keys whose MIN exceeds 2**63 must rebase in the native
    dtype — an int64 intermediate raised OverflowError (post-review
    regression). Small span -> slot-code path; huge span -> codes."""
    base = 2**64 - 100
    left = Table({"k": np.array([base, base + 7, base + 3],
                                dtype=np.uint64),
                  "l": np.arange(3, dtype=np.int64)})
    right = Table({"k": np.array([base + 3, base, base + 3],
                                 dtype=np.uint64),
                   "r": np.arange(3, dtype=np.int64)})
    for how in ("inner", "left"):
        assert (left.join(right, on=["k"], how=how,
                          backend="sharded").fingerprint()
                == left.join(right, on=["k"], how=how,
                             backend="reference").fingerprint())
    # span wider than int64 as well (codes path)
    wide = Table({"k": np.array([1, 2**64 - 2], dtype=np.uint64),
                  "l": np.arange(2, dtype=np.int64)})
    wide_r = Table({"k": np.array([2**64 - 2, 5], dtype=np.uint64),
                    "r": np.arange(2, dtype=np.int64)})
    assert (wide.join(wide_r, on=["k"], backend="sharded").fingerprint()
            == wide.join(wide_r, on=["k"],
                         backend="reference").fingerprint())


def test_offset_dense_keys_keep_table_mode():
    """Keys dense in a range far from zero must rebase into table mode
    (the Pallas-able direct-address path), not lose it to the
    no-rebase shortcut (post-review regression)."""
    from repro.exec.sharded import MAX_TABLE_SPAN

    r = np.random.default_rng(2)
    base = 2**30
    lcols = {"k": (base + r.integers(0, 1000, 200).astype(np.int64),
                   None)}
    rcols = {"k": (base + r.integers(0, 1000, 100).astype(np.int64),
                   None)}
    be = ShardedBackend()
    lk, rk, span = be._device_keys(lcols, rcols, ["k"])
    assert 0 < span <= MAX_TABLE_SPAN, "rebase must keep table mode"
    # and the pallas-probe configuration joins it correctly
    left = Table({"k": lcols["k"][0], "l": np.arange(200,
                                                     dtype=np.int64)})
    right = Table({"k": rcols["k"][0], "r": np.arange(100,
                                                      dtype=np.int64)})
    pb = ShardedBackend(use_pallas_probe=True)
    assert (left.join(right, on=["k"], backend=pb).fingerprint()
            == left.join(right, on=["k"],
                         backend="reference").fingerprint())


def test_sharded_right_occurrence_order_with_duplicates():
    left = Table({"k": np.array([2, 1, 2], dtype=np.int64),
                  "l": np.array([0, 1, 2], dtype=np.int64)})
    right = Table({"k": np.array([2, 1, 2], dtype=np.int64),
                   "r": np.array([20, 10, 21], dtype=np.int64)})
    j = left.join(right, on=["k"], backend="sharded")
    assert j.to_pydict() == {
        "k": [2, 2, 1, 2, 2], "l": [0, 0, 1, 2, 2],
        "r": [20, 21, 10, 20, 21]}


# ---------------------------------------------------------------------------
# auto-selection decision table
# ---------------------------------------------------------------------------

def _stats(n, kinds=("i",), card=None, span=None, lo=0):
    return TableStats(n_rows=n, key_kinds=tuple(kinds),
                      est_key_cardinality=card, int_key_span=span,
                      int_key_lo=None if span is None else lo,
                      int_key_hi=None if span is None else lo + span - 1)


def test_choose_join_decision_table():
    # tiny -> reference (per-call constants dominate)
    assert choose_join(_stats(10, span=10), _stats(5, span=5),
                       n_devices=8, sharded_available=True) \
        == "reference"
    # dense single int key -> vectorized bincount path
    assert choose_join(_stats(50_000, span=60_000),
                       _stats(50_000, span=60_000),
                       n_devices=8, sharded_available=True) \
        == "vectorized"
    # large sparse keys on a real mesh -> sharded
    assert choose_join(_stats(500_000, span=16_000_000),
                       _stats(500_000, span=16_000_000),
                       n_devices=8, sharded_available=True) \
        == "sharded"
    # same stats, single device -> stay vectorized
    assert choose_join(_stats(500_000, span=16_000_000),
                       _stats(500_000, span=16_000_000),
                       n_devices=1, sharded_available=True) \
        == "vectorized"
    # same stats, sharded unavailable -> vectorized
    assert choose_join(_stats(500_000, span=16_000_000),
                       _stats(500_000, span=16_000_000),
                       n_devices=8, sharded_available=False) \
        == "vectorized"
    # large but object keys (no span) -> sharded still handles via
    # factorized codes
    assert choose_join(_stats(500_000, kinds=("O",)),
                       _stats(500_000, kinds=("O",)),
                       n_devices=8, sharded_available=True) \
        == "sharded"
    # mid-size -> vectorized
    assert choose_join(_stats(5_000, span=10**9), _stats(5_000,
                                                         span=10**9),
                       n_devices=8, sharded_available=True) \
        == "vectorized"
    # disjoint key ranges: each side's span is tiny but the JOINT span
    # is huge — must not be routed as dense (post-review regression)
    assert choose_join(_stats(500_000, span=100_000, lo=0),
                       _stats(500_000, span=100_000, lo=10**9),
                       n_devices=8, sharded_available=True) \
        == "sharded"


def test_choose_group_by_decision_table():
    assert choose_group_by(_stats(10), np.dtype(np.int32),
                           jax_available=True) == "reference"
    assert choose_group_by(_stats(500_000), np.dtype(np.int32),
                           jax_available=True) == "jax"
    assert choose_group_by(_stats(500_000), np.dtype(np.int32),
                           jax_available=False) == "vectorized"
    # 64-bit values cannot lower without x64 -> vectorized
    if not jax.config.jax_enable_x64:
        assert choose_group_by(_stats(500_000), np.dtype(np.int64),
                               jax_available=True) == "vectorized"
    assert choose_group_by(_stats(500_000), np.dtype(object),
                           jax_available=True) == "vectorized"
    assert choose_group_by(_stats(5_000), np.dtype(np.int32),
                           jax_available=True) == "vectorized"


def test_collect_stats_shapes_the_decision():
    r = np.random.default_rng(0)
    cols = {"k": (r.integers(0, 100, 5000).astype(np.int64), None),
            "v": (r.normal(size=5000), None)}
    st = collect_stats(cols, ["k"])
    assert st.n_rows == 5000
    assert st.single_int_key
    assert st.int_key_span is not None and st.int_key_span <= 100
    assert 50 <= st.est_key_cardinality <= 100
    # NULL keys do not crash the sampler
    ks = np.array([None, "a", "b", None] * 100, dtype=object)
    st2 = collect_stats({"k": (ks, None)}, ["k"])
    assert st2.key_kinds == ("O",) and st2.est_key_cardinality == 2


def test_auto_backend_differential_and_delegation():
    r = np.random.default_rng(5)
    t = Table({"k": r.integers(0, 30, 500).astype(np.int64),
               "v": r.integers(-99, 99, 500).astype(np.int32)})
    u = Table({"k": r.integers(0, 30, 300).astype(np.int64),
               "w": r.normal(size=300)})
    assert (t.join(u, on=["k"], backend="auto").fingerprint()
            == t.join(u, on=["k"], backend="reference").fingerprint())
    assert (t.group_by_sum(["k"], "v", out="s",
                           backend="auto").fingerprint()
            == t.group_by_sum(["k"], "v", out="s",
                              backend="reference").fingerprint())


# ---------------------------------------------------------------------------
# cache tokens: backend AND mesh identity fold into engine cache keys
# ---------------------------------------------------------------------------

def test_cache_tokens_distinguish_mesh_shapes():
    one = ShardedBackend(n_devices=1)
    eight = ShardedBackend(n_devices=8)
    assert one.cache_token() != eight.cache_token()
    assert one.name == eight.name == "sharded"
    # the inherited segment-sum Pallas flag regroups float SUMs, so it
    # must move the token too (post-review regression)
    assert (ShardedBackend(n_devices=8, use_pallas=True).cache_token()
            != eight.cache_token())
    # host backends keep the bare-name token
    assert exec_backends.get_backend("vectorized").cache_token() \
        == "vectorized"
    assert exec_backends.get_backend("reference").cache_token() \
        == "reference"
    # auto's token pins policy version + thresholds + device count
    tok = exec_backends.get_backend("auto").cache_token()
    assert tok.startswith("auto[v") and "devices=" in tok


def test_engine_cache_key_moves_with_mesh_shape(monkeypatch):
    from repro.core import schema as S
    from repro.core.dag import Pipeline
    from repro.core.engine import cache_key
    from repro.core.planner import plan

    Src = S.Schema.of("Src", k=int, v=int)
    Agg = S.Schema.of("Agg", k=S.Nullable[int], s=S.Nullable[int])
    p = Pipeline("mesh_fp")
    p.source("src", Src)

    @p.node()
    def agg(df: Src = "src") -> Agg:
        return df.group_by_sum(["k"], "v", out="s")

    step = plan(p).steps[0]
    snaps = {"df": "snap0"}
    keys = set()
    for ndev in (1, 2, 8):
        be = ShardedBackend(n_devices=ndev)
        monkeypatch.setattr(exec_backends, "_active", "sharded")
        monkeypatch.setitem(exec_backends._instances, "sharded", be)
        keys.add(cache_key(step, snaps))
    assert len(keys) == 3, "mesh shape must move every cache key"


# ---------------------------------------------------------------------------
# numpy-fallback plumbing (shared with the jax backend)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.config.jax_enable_x64,
                    reason="fallback only fires with x64 off")
def test_x64_fallback_warns_once_naming_the_fix():
    fallback.reset_fallback_warnings()
    huge = np.array([2**40, 3, 2**40 + 1, 2**62], dtype=np.int64)
    left = Table({"k": huge, "l": np.arange(4, dtype=np.int64)})
    right = Table({"k": huge[::-1].copy(),
                   "r": np.arange(4, dtype=np.int64)})
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = left.join(right, on=["k"], backend="sharded")
        left.join(right, on=["k"], backend="sharded")  # second call
    ours = [x for x in w
            if issubclass(x.category, fallback.NumpyFallbackWarning)]
    assert len(ours) == 1, "must warn exactly once per (op, dtype)"
    assert "jax_enable_x64" in str(ours[0].message)
    # and the fallback result is still correct
    assert got.fingerprint() == left.join(
        right, on=["k"], backend="reference").fingerprint()


@pytest.mark.skipif(jax.config.jax_enable_x64,
                    reason="fallback only fires with x64 off")
def test_jax_backend_group_by_warns_on_64bit_values():
    fallback.reset_fallback_warnings()
    t = Table({"k": np.arange(100, dtype=np.int64) % 5,
               "v": np.arange(100, dtype=np.int64)})
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        g = t.group_by_sum(["k"], "v", out="s", backend="jax")
    ours = [x for x in w
            if issubclass(x.category, fallback.NumpyFallbackWarning)]
    assert len(ours) == 1
    assert "jax_enable_x64" in str(ours[0].message)
    assert g.fingerprint() == t.group_by_sum(
        ["k"], "v", out="s", backend="reference").fingerprint()


# ---------------------------------------------------------------------------
# planner stats metadata
# ---------------------------------------------------------------------------

def test_plan_records_input_stats():
    from repro.core import schema as S
    from repro.core.dag import Pipeline
    from repro.core.planner import plan

    Src = S.Schema.of("Src2", k=int, v=int)
    Out = S.Schema.of("Out2", k=int, v=int)
    p = Pipeline("stats_meta")
    p.source("src", Src)

    @p.node()
    def out(df: Src = "src") -> Out:
        return df.select([col("k"), col("v")])

    st = TableStats(n_rows=123, key_kinds=("i",),
                    est_key_cardinality=7, int_key_span=10)
    pl = plan(p, table_stats={"src": st})
    assert pl.steps[0].input_stats == {"src": st}
    assert "rows=123" in pl.describe()
    # stats are optional metadata: plans without them stay identical
    pl2 = plan(p)
    assert pl2.steps[0].input_stats is None
    assert pl2.code_hash == pl.code_hash
