"""The projected snapshot read: ``Table.from_blobs(columns=...)`` fetches
only the named column blobs, and the engine loads each source once with
the union of the columns the plan's scans of it keep — the whole table
wherever a step may look past its scans. Optimized (pruned) and
unoptimized runs publish the same tables bit for bit."""
from types import SimpleNamespace

import numpy as np
import pytest

import repro.obs as obs
from repro.core import logical as L
from repro.core import schema as S
from repro.core.catalog import Catalog
from repro.core.dag import Pipeline
from repro.core.engine import source_columns
from repro.core.planner import plan
from repro.core.runner import Client
from repro.core.store import FileStore, MemoryStore
from repro.data.tables import Table, _ColumnData
from repro.optimizer import optimize
from repro.sql.discovery import schema_from_snapshot


class _Counting:
    """Notes the key of every array fetched from the store."""

    def __init__(self, *args):
        super().__init__(*args)
        self.fetched: list[str] = []

    def get_array(self, key):
        self.fetched.append(key)
        return super().get_array(key)


class CountingMemoryStore(_Counting, MemoryStore):
    pass


class CountingFileStore(_Counting, FileStore):
    pass


def _stores(tmp_path):
    return {"memory": CountingMemoryStore(),
            "file": CountingFileStore(str(tmp_path / "lake"))}


def _read_columns(store, snap: str) -> set[str]:
    """The manifest columns of ``snap`` whose blobs were fetched."""
    got = set(store.fetched)
    return {n for n, m in store.get_json(snap)["columns"].items()
            if m["values"] in got or (m["valid"] or "") in got}


def _all_columns(store, snap: str) -> set[str]:
    return set(store.get_json(snap)["columns"])


N = 600


def _nullable(values: np.ndarray, every: int) -> _ColumnData:
    valid = np.ones(len(values), dtype=bool)
    valid[::every] = False
    return _ColumnData(values, valid)


def _sources() -> dict[str, Table]:
    """Every column of distinct content (blobs are content-addressed),
    with string and nullable columns among those scanned and skipped."""
    r = np.random.default_rng(11)
    names = np.array([None if i % 9 == 0 else f"n{i % 23}"
                      for i in range(N)], dtype=object)
    return {
        "f": Table({"k": r.integers(0, 40, N).astype(np.int32),
                    "v": r.integers(0, 1000, N).astype(np.int64),
                    "w": _nullable(r.integers(0, 50, N).astype(np.int32),
                                   7),
                    "name": names,
                    "note": np.array([f"note-{i}" for i in range(N)],
                                     dtype=object),
                    "x": r.normal(size=N)}),
        "d": Table({"k": np.arange(40, dtype=np.int32) + 0,
                    "g": (np.arange(40) % 6).astype(np.int64),
                    "label": np.array([f"d{i}" if i % 5 else None
                                       for i in range(40)], dtype=object),
                    "pad": np.arange(40, dtype=np.float32) * 0.5})}


def _client(store) -> Client:
    c = Client(Catalog(store))
    for name, t in _sources().items():
        c.write_source_table("main", name, t)
    return c


# -- Table.from_blobs(columns=...) ------------------------------------------

@pytest.mark.parametrize("kind", ["memory", "file"])
def test_from_blobs_fetches_only_named_columns(kind, tmp_path):
    store = _stores(tmp_path)[kind]
    t = _sources()["f"]
    snap = t.to_blobs(store)
    store.fetched.clear()
    got = Table.from_blobs(store, snap,
                           columns=["name", "k", "w", "absent"])
    # the manifest's order, not the request's; absent names skipped
    order = [n for n in store.get_json(snap)["columns"]
             if n in ("name", "k", "w")]
    assert got.column_names() == order
    assert _read_columns(store, snap) == {"k", "w", "name"}
    # the values of all three, the validity of the nullable w and name
    assert len(store.fetched) == 5
    full = Table.from_blobs(store, snap)
    for n in got.column_names():
        a = Table(_data={n: got._data[n]})
        b = Table(_data={n: full._data[n]})
        assert a.fingerprint() == b.fingerprint()
    assert got.validity("w").tolist() == t.validity("w").tolist()
    assert got.column("name").tolist() == t.column("name").tolist()


def test_from_blobs_without_columns_reads_everything():
    store = CountingMemoryStore()
    t = _sources()["f"]
    snap = t.to_blobs(store)
    store.fetched.clear()
    assert Table.from_blobs(store, snap).fingerprint() == t.fingerprint()
    assert _read_columns(store, snap) == set(t.column_names())
    store.fetched.clear()
    assert Table.from_blobs(store, snap, columns=["absent"]) \
        .column_names() == []
    assert store.fetched == []


def test_projection_leaves_snapshot_keys_unchanged():
    store = MemoryStore()
    t = _sources()["f"]
    snap = t.to_blobs(store)
    part = Table.from_blobs(store, snap, columns=["k", "name"])
    assert Table.from_blobs(store, snap).to_blobs(store) == snap
    again = Table(_data={n: t._data[n] for n in ("k", "name")})
    assert part.to_blobs(store) == again.to_blobs(store)


def test_traced_projected_read_counts_what_it_read_and_skipped():
    store = MemoryStore()
    t = _sources()["f"]
    snap = t.to_blobs(store)
    with obs.tracing() as rec:
        Table.from_blobs(store, snap, columns=["k", "name", "absent"])
        Table.from_blobs(store, snap)
    part, full = rec.spans("snapshot_read")
    assert part.attrs == {"columns": 2, "columns_skipped": 4,
                          "str_columns": 1, "rows": N,
                          # k; name's values as "U3" and validity
                          "bytes": t.column("k").nbytes
                          + N * 4 * len("n22") + N}
    assert full.attrs["columns"] == 6
    assert full.attrs["columns_skipped"] == 0


# -- the engine's source loads ----------------------------------------------

QUERY = ("SELECT d.g, SUM(f.v) AS t FROM f JOIN d ON f.k = d.k "
         "WHERE f.w > 10 GROUP BY d.g ORDER BY t DESC")


def _sql_reads(store, c, **kw):
    c.sql(QUERY, cache=False, **kw)      # discovery and row-count memos
    store.fetched.clear()
    res = c.sql(QUERY, cache=False, **kw)
    head = c.catalog.head("main").tables
    return res, {t: _read_columns(store, head[t]) for t in ("f", "d")}


@pytest.mark.parametrize("kind", ["memory", "file"])
def test_pruned_query_reads_only_scanned_columns(kind, tmp_path):
    store = _stores(tmp_path)[kind]
    c = _client(store)
    res, reads = _sql_reads(store, c)
    assert reads == {"f": {"k", "v", "w"}, "d": {"k", "g"}}
    plain, whole = _sql_reads(store, c, optimizer_passes=())
    assert whole == {"f": {"k", "v", "w", "name", "note", "x"},
                     "d": {"k", "g", "label", "pad"}}
    assert res.table.fingerprint() == plain.table.fingerprint()
    assert res.table.num_rows == 6


def _q18_pipeline(c: Client, branch: str) -> Pipeline:
    base = c.catalog.head(branch)
    p = Pipeline("q18")
    for table, snap in base.tables.items():
        p.source(table, schema_from_snapshot(c.store, snap, table))
    p.sql_query(name="order_qty", query=(
        "SELECT l_orderkey, SUM(l_quantity) AS sum_qty FROM lineitem "
        "GROUP BY l_orderkey"))
    p.sql_query(name="large_volume_customer", query=(
        "SELECT c.c_name, c.c_custkey, o.o_orderkey, o.o_orderdate, "
        "o.o_totalprice, SUM(l.l_quantity) AS sum_quantity FROM customer c "
        "JOIN orders o ON c.c_custkey = o.o_custkey JOIN lineitem l ON "
        "o.o_orderkey = l.l_orderkey JOIN order_qty q ON o.o_orderkey = "
        "q.l_orderkey WHERE q.sum_qty > 120 GROUP BY c.c_name, c.c_custkey, "
        "o.o_orderkey, o.o_orderdate, o.o_totalprice ORDER BY o_totalprice "
        "DESC, o_orderdate LIMIT 20"))
    return p


def _q18_client(store) -> Client:
    r = np.random.default_rng(18)
    n_c, n_o, n_l = 50, 300, 1500
    c = Client(Catalog(store))
    c.write_source_table("main", "customer", Table({
        "c_custkey": np.arange(n_c, dtype=np.int32) + 1,
        "c_name": np.array([f"Customer#{i:09d}" for i in range(n_c)],
                           dtype=object),
        "c_address": np.array([f"addr {i}" for i in range(n_c)],
                              dtype=object),
        "c_acctbal": r.normal(size=n_c)}))
    c.write_source_table("main", "orders", Table({
        "o_orderkey": np.arange(n_o, dtype=np.int32) * 4 + 3,
        "o_custkey": r.integers(1, n_c + 1, n_o).astype(np.int32),
        "o_orderdate": r.integers(8000, 10000, n_o).astype(np.int32) * 3,
        "o_totalprice": np.round(r.uniform(900, 5e5, n_o), 2),
        "o_orderstatus": np.array(["FOP"[i % 3] for i in range(n_o)],
                                  dtype=object),
        "o_comment": np.array([f"c{i}" for i in range(n_o)],
                              dtype=object)}))
    c.write_source_table("main", "lineitem", Table({
        "l_orderkey": (r.integers(0, n_o, n_l) * 4 + 3).astype(np.int64),
        "l_quantity": r.integers(1, 51, n_l).astype(np.int16),
        "l_extendedprice": r.uniform(900, 1e5, n_l),
        "l_shipmode": np.array([("AIR", "MAIL", None)[i % 3]
                                for i in range(n_l)], dtype=object),
        "l_comment": np.array([f"l{i}" for i in range(n_l)],
                              dtype=object)}))
    return c


@pytest.mark.parametrize("kind", ["memory", "file"])
def test_q18_pipeline_loads_each_source_once_with_its_scanned_columns(
        kind, tmp_path):
    store = _stores(tmp_path)[kind]
    c = _q18_client(store)
    head = c.catalog.head("main").tables
    published = {}
    for optimized in (True, False):
        branch = f"b{int(optimized)}"
        c.create_branch(branch, "main")
        pl = plan(_q18_pipeline(c, branch))
        if optimized:
            pl = optimize(pl)
        store.fetched.clear()
        with obs.tracing() as rec:
            res = c.run(pl, branch, cache=False)
        assert res.state.status == "committed"
        reads = {t: _read_columns(store, head[t]) for t in head}
        loads = [s.attrs for s in rec.spans("snapshot_read")
                 if s.attrs.get("rows") == 1500]
        if optimized:
            assert reads == {"customer": {"c_custkey", "c_name"},
                             "orders": {"o_orderkey", "o_custkey",
                                        "o_orderdate", "o_totalprice"},
                             "lineitem": {"l_orderkey", "l_quantity"}}
            assert loads == [{"columns": 2, "columns_skipped": 3,
                              "str_columns": 0, "rows": 1500,
                              "bytes": 1500 * (8 + 2)}]
        else:
            assert reads == {t: _all_columns(store, head[t])
                             for t in head}
            assert [a["columns_skipped"] for a in loads] == [0]
        published[optimized] = {
            t: c.read_table(branch, t).fingerprint()
            for t in ("order_qty", "large_volume_customer")}
    assert published[True] == published[False]
    assert c.read_table("b1", "large_volume_customer").num_rows > 0


Totals = S.Schema.of("Totals", k=int, s=int)


def test_two_steps_with_different_columns_share_one_load():
    store = CountingMemoryStore()
    c = _client(store)
    head = c.catalog.head("main").tables
    p = Pipeline("two")
    for table, snap in head.items():
        p.source(table, schema_from_snapshot(store, snap, table))
    p.sql_query(name="by_v", query="SELECT k, SUM(v) AS sv FROM f "
                "GROUP BY k")
    p.sql_query(name="by_x", query="SELECT name, MAX(x) AS mx FROM f "
                "GROUP BY name")
    pl = optimize(plan(p))
    # pruning keeps each step's output names in its scans too
    assert source_columns(pl)["f"] == {"k", "v", "sv", "name", "x", "mx"}
    assert len(pl.waves) == 1          # both read f concurrently
    store.fetched.clear()
    with obs.tracing() as rec:
        res = c.run(pl, "main", cache=False)
    assert res.state.status == "committed"
    assert _read_columns(store, head["f"]) == {"k", "v", "name", "x"}
    (load,) = rec.spans("snapshot_read")
    assert load.attrs["columns"] == 4
    assert load.attrs["columns_skipped"] == 2
    src = _sources()["f"]
    by_v = c.read_table("main", "by_v")
    want = {k: int(src.column("v")[src.column("k") == k].sum())
            for k in np.unique(src.column("k"))}
    assert dict(zip(by_v.column("k").tolist(),
                    by_v.column("sv").tolist())) == want


def test_opaque_node_and_unpruned_scans_read_the_whole_table():
    store = CountingMemoryStore()
    c = _client(store)
    head = c.catalog.head("main").tables
    p = Pipeline("mixed")
    for table, snap in head.items():
        p.source(table, schema_from_snapshot(store, snap, table))
    F = schema_from_snapshot(store, head["f"], "f")

    @p.node(name="opaque")
    def opaque(df: F = "f") -> Totals:
        return Table({"k": df.column("k").astype(np.int64),
                      "s": df.column("v")})

    # every column of d is referenced: pruning leaves the scan whole
    p.sql_query(name="dims", query="SELECT k, g, label, pad FROM d")
    pl = optimize(plan(p))
    assert source_columns(pl) == {"f": None, "d": None}
    store.fetched.clear()
    assert c.run(pl, "main", cache=False).state.status == "committed"
    for t in ("f", "d"):
        assert _read_columns(store, head[t]) == _all_columns(store,
                                                             head[t])


def test_a_scan_of_all_columns_beside_a_pruned_scan_reads_everything():
    store = CountingMemoryStore()
    c = _client(store)
    head = c.catalog.head("main").tables
    p = Pipeline("both")
    for table, snap in head.items():
        p.source(table, schema_from_snapshot(store, snap, table))
    p.sql_query(name="narrow", query="SELECT k, SUM(v) AS sv FROM f "
                "GROUP BY k")
    p.sql_query(name="wide", query="SELECT * FROM f WHERE k > 3")
    pl = optimize(plan(p))
    assert source_columns(pl)["f"] is None
    store.fetched.clear()
    assert c.run(pl, "main", cache=False).state.status == "committed"
    assert _read_columns(store, head["f"]) == _all_columns(store,
                                                         head["f"])


def _step(inputs, logical):
    return SimpleNamespace(node=SimpleNamespace(inputs=inputs),
                           logical=logical)


_PRUNED = L.Project(L.Scan("f", ("k", "v")), ())


@pytest.mark.parametrize("steps,want", [
    ([_step({"a": "f"}, _PRUNED)], {"f": {"k", "v"}}),
    ([_step({"a": "f"}, _PRUNED),
      _step({"a": "f"}, L.Scan("f", ("v", "x")))], {"f": {"k", "v", "x"}}),
    ([_step({"a": "f"}, L.Join(L.Scan("f", ("k",)), L.Scan("f", ("w",)),
                               ("k",)))], {"f": {"k", "w"}}),
    # an opaque body, a scan of every column, an input never scanned
    ([_step({"a": "f"}, _PRUNED), _step({"a": "f"}, None)], {"f": None}),
    ([_step({"a": "f"}, L.Scan("f"))], {"f": None}),
    ([_step({"a": "f", "b": "d"}, _PRUNED)], {"f": {"k", "v"}, "d": None}),
])
def test_source_columns_is_the_union_or_the_whole_table(steps, want):
    assert source_columns(SimpleNamespace(steps=steps)) == want
