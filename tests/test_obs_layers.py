"""Spans inside the node (DESIGN.md §14): snapshot reads and writes,
contract checks, logical ops, key codes, row emission and device calls
with their bytes — where each opens, under which parent, and what it
counts; and that the untraced path opens none of them."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import repro.obs as obs
from repro import exec as rexec
from repro.core import quality
from repro.core.dag import Pipeline
from repro.core.planner import plan
from repro.core.runner import Client
from repro.data.tables import Table
from repro.sql.discovery import schema_from_snapshot

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_FACT, N_DIM = 4000, 300
NEW_SPANS = {"snapshot_read", "snapshot_write", "contract_check",
             "key_codes", "row_emit", "kernel", "op.filter", "op.project",
             "op.join", "op.aggregate", "op.sort", "op.limit",
             "op.reorder"}
QUERY = ("SELECT d.g, SUM(f.v) AS t FROM f JOIN d ON f.k = d.k "
         "WHERE f.v > 10 GROUP BY d.g ORDER BY t DESC LIMIT 5")


def _tables() -> dict[str, Table]:
    r = np.random.default_rng(7)
    return {
        "f": Table({"k": r.integers(0, N_DIM, N_FACT).astype(np.int32),
                    "v": r.integers(0, 100, N_FACT).astype(np.int32),
                    "s": np.array([f"s{i % 17}" for i in range(N_FACT)],
                                  dtype=object)}),
        "d": Table({"k": np.arange(N_DIM, dtype=np.int32),
                    "g": (np.arange(N_DIM) % 7).astype(np.int32)})}


def _stored_bytes(t: Table) -> int:
    """The column arrays as a snapshot stores them: strings as
    fixed-width unicode (4 bytes a character), the rest as they are."""
    total = 0
    for name in t.column_names():
        v = t.column(name)
        if v.dtype == object:
            total += 4 * max(len(x) for x in v) * len(v)
        else:
            total += v.nbytes
    return total


def _client() -> Client:
    c = Client()
    for name, t in _tables().items():
        c.write_source_table("main", name, t)
    return c


def _tree(rec):
    spans = rec.spans()
    by_id = {s.span_id: s for s in spans}
    return spans, (lambda s: by_id.get(s.parent_id))


def _assert_no_self_nesting(rec):
    spans, parent = _tree(rec)
    for s in spans:
        p = parent(s)
        while p is not None:
            assert p.name != s.name, f"{s.name} nests in itself"
            p = parent(p)


def _traced_query(backend: str):
    c = _client()
    with rexec.use_backend(backend), obs.tracing() as rec:
        res = c.sql(QUERY, cache=False)
    return c, rec, res


def test_query_spans_open_under_node_and_ops():
    _, rec, res = _traced_query("jax")
    spans, parent = _tree(rec)
    (node,) = rec.spans("node")
    ops = [s for s in spans if s.name.startswith("op.")]
    assert {s.name for s in ops} >= {"op.join", "op.aggregate",
                                     "op.sort", "op.limit"}
    assert all(parent(s) is node for s in ops)
    for name in ("key_codes", "row_emit", "kernel"):
        found = rec.spans(name)
        assert found, name
        assert all(parent(s).name.startswith("op.") for s in found)
    for name in ("contract_check", "snapshot_write"):
        (s,) = rec.spans(name)
        assert parent(s) is node
    reads = rec.spans("snapshot_read")
    # the two sources inside the node; the result read back under sql
    assert sorted(parent(s).name for s in reads) == ["node", "node",
                                                     "sql"]
    (limit,) = rec.spans("op.limit")
    assert limit.attrs["rows_out"] == res.table.num_rows == 5
    _assert_no_self_nesting(rec)


def test_snapshot_spans_count_rows_columns_and_bytes():
    c, rec, res = _traced_query("vectorized")
    tables = _tables()
    by_rows = {s.attrs["rows"]: s for s in rec.spans("snapshot_read")}
    # the optimized query scans f's k and v, leaving its string s
    # unread, and every column of d
    scanned = {"f": ("k", "v"), "d": ("k", "g")}
    for name, t in tables.items():
        s = by_rows[t.num_rows]
        kept = Table(_data={n: t._data[n] for n in scanned[name]})
        assert s.attrs["columns"] == len(scanned[name])
        assert s.attrs["columns_skipped"] == (1 if name == "f" else 0)
        assert s.attrs["str_columns"] == 0
        assert s.attrs["bytes"] == _stored_bytes(kept)
    # unoptimized, nothing is pruned and every column is read
    with obs.tracing() as whole:
        c.sql(QUERY, cache=False, optimizer_passes=())
    whole_rows = {s.attrs["rows"]: s for s in whole.spans("snapshot_read")}
    for name, t in tables.items():
        s = whole_rows[t.num_rows]
        assert s.attrs["columns"] == len(t.column_names())
        assert s.attrs["columns_skipped"] == 0
        assert s.attrs["str_columns"] == (1 if name == "f" else 0)
        assert s.attrs["bytes"] == _stored_bytes(t)
    (write,) = rec.spans("snapshot_write")
    out = res.table
    assert write.attrs["rows"] == out.num_rows
    assert write.attrs["columns"] == len(out.column_names())
    assert write.attrs["bytes"] == _stored_bytes(out)
    assert by_rows[out.num_rows].attrs["bytes"] == write.attrs["bytes"]
    assert by_rows[out.num_rows].attrs["columns_skipped"] == 0
    (check,) = rec.spans("contract_check")
    assert check.attrs == {"table": "query", "rows": out.num_rows,
                           "columns": len(out.column_names())}


def test_join_key_codes_and_row_emission_count_rows():
    _, rec, _ = _traced_query("vectorized")
    (join,) = rec.spans("op.join")
    _, parent = _tree(rec)
    (codes,) = [s for s in rec.spans("key_codes") if parent(s) is join]
    assert codes.attrs == {"rows": N_FACT + N_DIM, "keys": 1,
                           "object_keys": 0}
    (emit,) = rec.spans("row_emit")
    assert parent(emit) is join
    assert emit.attrs["rows_out"] == join.attrs["rows_out"]


def test_object_keys_are_counted():
    t = _tables()["f"]
    right = Table({"s": np.array([f"s{i}" for i in range(17)],
                                 dtype=object),
                   "w": np.arange(17, dtype=np.int64)})
    with obs.tracing() as rec:
        out = t.join(right, on=["s"], backend="vectorized")
    (codes,) = rec.spans("key_codes")
    assert codes.attrs == {"rows": N_FACT + 17, "keys": 1,
                           "object_keys": 1}
    (emit,) = rec.spans("row_emit")
    assert emit.attrs == {"rows_out": out.num_rows, "columns": 4}


def test_jax_kernel_span_counts_the_bytes_it_copies():
    _, rec, _ = _traced_query("jax")
    (kernel,) = rec.spans("kernel")
    (agg,) = rec.spans("op.aggregate")
    rows = kernel.attrs["rows"]
    assert kernel.attrs["op"] == "jax.segment_sum"
    assert kernel.attrs["segments"] == agg.attrs["rows_out"] == 7
    # int32 values + int32 segment ids + bool mask
    assert kernel.attrs["h2d_bytes"] == rows * (4 + 4 + 1)
    # int32 sums and counts fetched back
    assert kernel.attrs["d2h_bytes"] == 7 * (4 + 4)


def test_min_max_kernel_span():
    r = np.random.default_rng(3)
    t = Table({"k": r.integers(0, 9, 500).astype(np.int32),
               "v": r.normal(size=500).astype(np.float32)})
    with obs.tracing() as rec:
        t.group_by(["k"]).agg(("max", "v", "m"), backend="jax")
    (kernel,) = rec.spans("kernel")
    assert kernel.attrs["op"] == "jax.segment_reduce"
    assert kernel.attrs["h2d_bytes"] == 500 * (4 + 4 + 1)
    assert kernel.attrs["segments"] == 9


def test_run_spans_under_node():
    c = _client()
    base = c.catalog.head("main")
    p = Pipeline("layers")
    for name, snap in base.tables.items():
        p.source(name, schema_from_snapshot(c.store, snap, name))
    p.sql_query(name="by_g", query="SELECT d.g, SUM(f.v) AS t FROM f "
                "JOIN d ON f.k = d.k GROUP BY d.g")
    with rexec.use_backend("jax"), obs.tracing() as rec:
        res = c.run(plan(p), "main", cache=False,
                    verifiers={"by_g": [quality.expect_unique("g")]})
    assert res.state.status == "committed"
    _, parent = _tree(rec)
    (node,) = rec.spans("node")
    for name in ("contract_check", "snapshot_write"):
        (s,) = rec.spans(name)
        assert parent(s) is node
    reads = rec.spans("snapshot_read")
    # two source loads in the node, the output read by the verifier
    assert sorted(parent(s).name for s in reads) == ["node", "node",
                                                     "verifier"]
    assert all(parent(s).name == "op.aggregate"
               for s in rec.spans("kernel"))
    _assert_no_self_nesting(rec)


class _Watching(obs.NullRecorder):
    """A disabled recorder that notes any span or event asked of it."""

    def __init__(self):
        self.asked = []

    def span(self, name, /, **attrs):
        self.asked.append(name)
        return super().span(name, **attrs)

    def start_span(self, name, /, **attrs):
        self.asked.append(name)
        return super().start_span(name, **attrs)


@pytest.mark.parametrize("backend", ["vectorized", "jax"])
def test_untraced_path_opens_no_span_inside_the_node(backend):
    traced_client, _, traced = _traced_query(backend)
    c = _client()
    watching = _Watching()
    prev = obs.install(watching)
    try:
        with rexec.use_backend(backend):
            res = c.sql(QUERY, cache=False)
            # a projected read, as the engine's source loads make
            part = Table.from_blobs(c.store,
                                    c.catalog.head("main").tables["f"],
                                    columns=("k",))
    finally:
        obs.install(prev)
    assert part.column_names() == ["k"]
    assert not NEW_SPANS & set(watching.asked), watching.asked
    assert res.table.fingerprint() == traced.table.fingerprint()


_MESH = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
assert jax.device_count() == 4, jax.devices()
import repro.obs as obs
from repro.data.tables import Table

r = np.random.default_rng(5)
n = 4000
# keys 0..599: 600 slots round up to 4 x 256, so the owner shards hold
# 256, 256 and 88 slots and the fourth none
k = r.permutation(np.arange(n) % 600).astype(np.int32)
t = Table({"k": k, "v": r.integers(0, 9, n).astype(np.int32)})
dim = Table({"k": np.arange(600, dtype=np.int32),
             "w": np.arange(600, dtype=np.int32)})
with obs.tracing() as rec:
    t.group_by(["k"]).agg(("sum", "v", "s"), backend="sharded")
    t.join(dim, on=["k"], backend="sharded")
print(json.dumps({"groups": int(len(np.unique(k))),
                  "kernels": [s.attrs for s in rec.spans("kernel")],
                  "names": [s.name for s in rec.spans()]}))
"""


def test_sharded_kernel_spans_on_a_forced_four_device_mesh():
    import json

    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_MESH)],
                       env=env, capture_output=True, text=True,
                       timeout=420)
    assert r.returncode == 0, r.stderr[-4000:]
    got = json.loads(r.stdout.splitlines()[-1])
    agg, probe = got["kernels"]
    assert agg["op"] == "sharded.partial_agg"
    assert sum(agg["groups_per_shard"]) == got["groups"] == 600
    assert agg["groups_per_shard"] == [256, 256, 88, 0]
    assert agg["segments"] == 4 * 256
    # gid, value and mask slabs: 4000 rows, no padding
    assert agg["h2d_bytes"] == 4000 * (4 + 4 + 1)
    assert agg["d2h_bytes"] > 0
    assert probe["op"] == "sharded.exchange_probe"
    assert probe["rows"] == 4600
    assert probe["h2d_bytes"] == probe["all_to_all_bytes"]
    assert probe["d2h_bytes"] > 0
    assert sum(probe["rows_left_per_shard"]) == 4000
    assert probe["rows_right_per_shard"] == [256, 256, 88, 0]
    assert got["names"].count("key_codes") == 3
    assert "row_emit" in got["names"]
