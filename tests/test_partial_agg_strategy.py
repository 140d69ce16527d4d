"""The mesh's partial aggregation under both per-shard strategies.

``_partial_agg_fn`` reduces each shard's rows to per-slot partials
before the ``all_to_all``: with a packed sort (CPU meshes, <= 32-bit
integers) or with segment ops (every other platform — the path a TPU
mesh takes). Both run here on a forced 4-device host mesh, in one
subprocess, and must equal a numpy reference bit for bit: NULL values,
an all-NULL group, empty slots, slab padding rows, sums that wrap.
The selector that picks between them is checked in-process.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.exec.sharded import _agg_strategies, _agg_strategy  # noqa: E402
from repro.kernels.segment_sum.ref import reduce_identity  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NDEV = 4
SEG_SHARD = 64                 # 256 slots; the data uses 0..150
N_ROWS = 1001                  # 4 x 251 lanes: 3 padding rows
N_USED = 151
ALL_NULL_SLOT = 7
STATS = ("max", "min", "sum")


def _stats(dt) -> tuple:
    """Float sums depend on the order shards add them: min/max only."""
    return STATS if np.dtype(dt).kind in "iu" else ("max", "min")


# case -> (value dtype per column, how values are drawn)
CASES = {
    "int8": (("|i1",), "full"),
    "int16": (("<i2",), "full"),
    "int32": (("<i4",), "full"),
    "uint32": (("<u4",), "full"),
    "int32_wrap": (("<i4",), "near_max"),
    "int32_and_float32": (("<i4", "<f4"), "full"),
}
STRATEGIES = ("packed", "segment")


def _columns(case: str):
    """(gid, [(values, ok)]) for ``case``, from a fixed seed."""
    dtypes, draw = CASES[case]
    r = np.random.default_rng(sorted(CASES).index(case))
    gid = r.integers(0, N_USED, N_ROWS).astype(np.int32)
    gid[r.random(N_ROWS) < 0.1] = N_USED - 1   # one heavy slot
    cols = []
    for dt in map(np.dtype, dtypes):
        if dt.kind == "f":
            vals = r.normal(size=N_ROWS).astype(dt)
        elif draw == "near_max":
            info = np.iinfo(dt)
            vals = r.integers(info.max - 3, info.max, N_ROWS,
                              endpoint=True).astype(dt)
        else:
            info = np.iinfo(dt)
            vals = r.integers(info.min, info.max, N_ROWS,
                              endpoint=True).astype(dt)
        ok = r.random(N_ROWS) > 0.2
        ok[gid == ALL_NULL_SLOT] = False
        cols.append((vals, ok))
    return gid, cols


def _reference(gid, vals, ok):
    """Per-slot count, wrapping sum, min and max over the valid rows,
    in the value dtype; empty slots read 0 and the reduce identity."""
    nseg = NDEV * SEG_SHARD
    dt = vals.dtype
    g, v = gid[ok], vals[ok]
    cnt = np.bincount(g, minlength=nseg).astype(np.int32)
    if dt.kind == "f":
        s = np.zeros(nseg, dt)
        np.add.at(s, g, v)
    else:
        s = np.zeros(nseg, np.int64)
        np.add.at(s, g, v.astype(np.int64))
        s = s.astype(dt)                   # modular, as the dtype wraps
    lo = np.full(nseg, reduce_identity(dt, "min"), dt)
    hi = np.full(nseg, reduce_identity(dt, "max"), dt)
    np.minimum.at(lo, g, v)
    np.maximum.at(hi, g, v)
    return {"count": cnt, "sum": s, "min": lo, "max": hi}


_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import numpy as np
import jax
assert jax.device_count() == 4, jax.devices()
sys.path.insert(0, {tests!r})
import test_partial_agg_strategy as t
from repro.exec.sharded import _partial_agg_fn

chunk = -(-t.N_ROWS // t.NDEV)
pad = t.NDEV * chunk - t.N_ROWS

def slab(a, fill):
    a = np.concatenate([a, np.full(pad, fill, a.dtype)])
    return a.reshape(t.NDEV, chunk)

out = {{}}
for case, (dtypes, _draw) in t.CASES.items():
    gid, cols = t._columns(case)
    sig = tuple((dt, t._stats(dt)) for dt in dtypes)
    # padding rows: slot 0 (which holds real rows too), ok=False and a
    # value that would show in every stat if it were counted
    args = [slab(gid, np.int32(0))]
    for vals, ok in cols:
        big = (np.finfo(vals.dtype).max if vals.dtype.kind == "f"
               else np.iinfo(vals.dtype).max)
        args += [slab(vals, big), slab(ok, False)]
    for strategy in t.STRATEGIES:
        # the packed sort takes integers only: floats keep segment ops
        strats = tuple(strategy if np.dtype(dt).kind in "iu"
                       else "segment" for dt in dtypes)
        fn = _partial_agg_fn(t.NDEV, t.SEG_SHARD, sig, False, False,
                             strats)
        with jax.enable_x64(True):
            res = [np.asarray(o).reshape(-1) for o in fn(*args)]
        out[case + "/" + strategy] = [
            [r.dtype.str, r.view(np.uint8).tolist()] for r in res]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_outputs():
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    script = textwrap.dedent(_SCRIPT).format(
        tests=os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("case", list(CASES))
def test_strategy_matches_numpy_bit_for_bit(mesh_outputs, case, strategy):
    got = [np.frombuffer(bytes(b), dtype=np.dtype(dt))
           for dt, b in mesh_outputs[f"{case}/{strategy}"]]
    gid, cols = _columns(case)
    want = []
    for vals, ok in cols:
        ref = _reference(gid, vals, ok)
        # the program's order: count, then sum, min, max as asked
        want += [ref[s] for s in ("count", "sum", "min", "max")
                 if s == "count" or s in _stats(vals.dtype)]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        # bytes, so float NaN/-0.0 and integer wraps compare exactly
        assert g.tobytes() == w.tobytes()
    # the fixture's data covers what the case claims to
    cnt = want[0]
    assert cnt[ALL_NULL_SLOT] == 0 and (gid == ALL_NULL_SLOT).any()
    assert (cnt[N_USED:] == 0).all()


def test_wrap_case_wraps():
    gid, [(vals, ok)] = _columns("int32_wrap")
    exact = np.bincount(gid[ok], weights=vals[ok].astype(np.float64))
    assert exact.max() > np.iinfo(np.int32).max


@pytest.mark.parametrize("platform,dtype,use_pallas,want", [
    ("cpu", "|i1", False, "packed"),
    ("cpu", "<i2", False, "packed"),
    ("cpu", "<i4", False, "packed"),
    ("cpu", "<u4", False, "packed"),
    ("cpu", "<i4", True, "segment"),
    ("cpu", "<f4", False, "segment"),
    ("cpu", "<f8", False, "segment"),
    ("cpu", "<i8", False, "segment"),
    ("cpu", "<u8", False, "segment"),
    ("tpu", "<i4", False, "segment"),
    ("tpu", "<u4", False, "segment"),
    ("tpu", "|i1", False, "segment"),
    ("tpu", "<f4", False, "segment"),
    ("tpu", "<i8", False, "segment"),
    ("tpu", "<i4", True, "segment"),
    ("gpu", "<i4", False, "segment"),
])
def test_strategy_follows_the_platform(platform, dtype, use_pallas, want):
    assert _agg_strategy(np.dtype(dtype), platform, use_pallas) == want


def test_strategies_read_the_mesh_platform():
    """The backend's mesh here is the host CPU: one entry per column."""
    sig = (("<i4", ("sum",)), ("<f8", ("sum",)), ("<u2", ("min",)))
    assert _agg_strategies(1, sig, False) == ("packed", "segment",
                                              "packed")
    assert _agg_strategies(1, sig, True) == ("segment",) * 3
