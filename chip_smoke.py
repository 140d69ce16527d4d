"""Chip smoke test: the lakehouse data plane on a TPU, end to end.

    python chip_smoke.py               # one chip: every device configuration
    python chip_smoke.py --chips 4     # the sharded radix exchange, 4-chip mesh

One process, no children. It writes a star schema made from ``--seed``
— ``fact`` at the row count of TPC-H SF1 ``lineitem`` (6,001,215),
``users`` and ``items`` dimensions, every key and value column 32-bit
so nothing needs x64 — and drives the normal entry points:
``Client.sql`` at ``main`` and one transactional ``Client.run`` that
publishes to a branch and is read back at the new head. Each step runs
under each device execution configuration, twice (cold: first call,
compile included; warm), and must match bit for bit
(``Table.fingerprint``) the ``vectorized`` host backend running the
unoptimized query. Everything runs traced, and any ``degradation``
event, ``exec.numpy_fallbacks`` or ``sharded.downgrades`` fails the
run. Wall times printed on the way are bring-up notes, not metrics.

``--chips 4`` runs only what exists across chips: the sharded join in
table and hash mode and the pre-exchange partial aggregation on a
4-device mesh, under ``sharded`` and ``auto``, against ``vectorized``.

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``. Without a TPU the script exits
non-zero before doing any work and prints no such line.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_FACT = 6_001_215          # TPC-H SF1 lineitem rows
N_USERS = 150_000
N_ITEMS = 200_000
N_DEAD_COLS = 8             # fact payload no query reads
N_SEGMENTS = 64

GROUP_QUERY = ("SELECT item_id, SUM(qty), COUNT(qty), MIN(qty), "
               "MAX(qty), AVG(qty) FROM fact GROUP BY item_id")
JOIN_NODE = ("SELECT f.user_id, f.item_id, f.qty, u.segment "
             "FROM fact f JOIN users u ON f.user_id = u.user_id")
ROLLUP_NODE = ("SELECT segment, SUM(qty), COUNT(qty), MIN(qty), "
               "MAX(qty) FROM fact_users GROUP BY segment")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def note(msg: str) -> None:
    print(msg, flush=True)


# -- data ----------------------------------------------------------------
def star_schema(seed: int, n_fact: int = N_FACT, n_users: int = N_USERS,
                n_items: int = N_ITEMS, key_scale: int = 1) -> dict:
    """fact / users / items, all 32-bit. ``key_scale`` multiplies
    user_id on both sides (a sparse key space of the same rows)."""
    from repro.data.tables import Table

    rng = np.random.default_rng(seed)
    scale = np.int32(key_scale)
    fact = {"user_id": rng.integers(0, n_users, n_fact, dtype=np.int32)
            * scale,
            "item_id": rng.integers(0, n_items, n_fact, dtype=np.int32),
            "qty": rng.integers(1, 51, n_fact, dtype=np.int32),
            "amount": rng.standard_normal(n_fact, dtype=np.float32)}
    for i in range(N_DEAD_COLS):
        fact[f"pay{i}"] = rng.standard_normal(n_fact, dtype=np.float32)
    uid = np.arange(n_users, dtype=np.int32)
    users = {"user_id": uid * scale,
             "segment": uid % np.int32(N_SEGMENTS)}
    items = {"item_id": np.arange(n_items, dtype=np.int32),
             "weight": rng.standard_normal(n_items, dtype=np.float32)}
    return {"fact": Table(fact), "users": Table(users),
            "items": Table(items)}


def new_client(tables: dict):
    from repro.core.runner import Client

    client = Client()
    for name, table in tables.items():
        client.write_source_table("main", name, table)
    return client


def rollup_plan(client):
    """Two-node pipeline over the catalog's tables: join, then group-by."""
    from repro.core.dag import Pipeline
    from repro.core.planner import plan
    from repro.sql.discovery import schema_from_snapshot

    head = client.catalog.head("main")
    p = Pipeline("smoke_rollup")
    for name in ("fact", "users"):
        p.source(name, schema_from_snapshot(client.store,
                                            head.tables[name], name))
    p.sql_query(name="fact_users", query=JOIN_NODE)
    p.sql_query(name="rollup", query=ROLLUP_NODE)
    return plan(p)


# -- steps -----------------------------------------------------------------
def sql_step(client, query: str, passes=None):
    def step():
        return client.sql(query, optimizer_passes=passes,
                          cache=False).table
    return step


def run_step(client):
    """Client.run on a fresh branch; returns the published tables read
    back at the branch's new head."""
    from repro.core.quality import expect_row_count

    pl = rollup_plan(client)
    n_fact = client.read_table("main", "fact").num_rows
    runs = itertools.count()

    def step():
        branch = f"smoke-run-{next(runs)}"
        client.create_branch(branch, "main")
        res = client.run(pl, branch, cache=False, verifiers={
            "fact_users": [expect_row_count(n_fact, n_fact)],
            "rollup": [expect_row_count(N_SEGMENTS, N_SEGMENTS)]})
        head = client.catalog.head(branch).id
        check(res.state.status == "committed",
              f"run on {branch} ended {res.state.status}")
        check(res.state.final_commit == head,
              f"{branch} head {head} is not the published commit "
              f"{res.state.final_commit}")
        return tuple(client.read_table(head, t)
                     for t in ("fact_users", "rollup"))
    return step


def fingerprint(out) -> str:
    if isinstance(out, tuple):
        return "+".join(t.fingerprint() for t in out)
    return out.fingerprint()


def rows(out) -> int:
    if isinstance(out, tuple):
        return out[-1].num_rows
    return out.num_rows


# -- configurations ----------------------------------------------------------
def register_configs(ndev: int) -> list[str]:
    """Device configurations, registered through the public exec API.
    ``interpret`` is left to the backends, which decide it from the
    platform: compiled on the chip, interpreted in a CPU rehearsal."""
    from repro import exec as rexec
    from repro.exec.jax_backend import JaxBackend
    from repro.exec.sharded import ShardedBackend

    factories = {
        "jax.xla": lambda: JaxBackend(use_pallas=False),
        "jax.pallas": lambda: JaxBackend(use_pallas=True),
        "sharded.packed": lambda: ShardedBackend(
            n_devices=ndev, use_pallas=False, use_pallas_probe=False),
        "sharded.pallas_probe": lambda: ShardedBackend(
            n_devices=ndev, use_pallas=False, use_pallas_probe=True),
    }
    for name, factory in factories.items():
        rexec.register(f"smoke.{name}", factory)
    return ["auto"] + [f"smoke.{name}" for name in factories]


def degradations(rec) -> list:
    events = [ev for s in rec.spans() for ev in s.events]
    events += rec.orphan_events()
    return [ev for ev in events if ev["name"] == "degradation"]


def exchanges(rec) -> list[tuple[str, tuple[int, ...]]]:
    """Each distinct sharded exchange in the trace: its mode (join:
    table / hash; group-by: agg) and the rows each mesh shard got (both
    sides of a join added up)."""
    seen = {}
    for s in rec.spans("kernel"):
        if not s.attrs.get("op", "").startswith("sharded."):
            continue
        per = sum(np.asarray(s.attrs[key], dtype=np.int64)
                  for key in ("rows_left_per_shard", "rows_right_per_shard",
                              "rows_per_shard") if key in s.attrs)
        seen[(s.attrs.get("mode", "agg"), tuple(per.tolist()))] = None
    return list(seen)


def run_config(backend: str, steps: dict, want: dict) -> dict:
    """Run each step cold then warm under ``backend``, traced; check
    every result against ``want`` and the trace for degradations.
    Returns the ``auto.*`` decision counters and the sharded exchanges."""
    import repro.obs as obs
    from repro import exec as rexec

    with obs.tracing() as rec, rexec.use_backend(backend):
        for name, step in steps.items():
            times = []
            for _ in range(2):
                t0 = time.perf_counter()
                out = step()
                times.append(time.perf_counter() - t0)
                got = fingerprint(out)
                check(got == want[name],
                      f"{backend} {name}: fingerprint {got} != "
                      f"vectorized {want[name]}")
            note(f"  {backend:22} {name:14} cold {times[0]:.3f}s "
                 f"warm {times[1]:.3f}s rows_out {rows(out)}")
    counters = rec.metrics.snapshot()["counters"]
    bad = degradations(rec)
    check(not bad, f"{backend}: degradation events {bad}")
    for c in ("exec.numpy_fallbacks", "sharded.downgrades"):
        check(counters.get(c, 0) == 0,
              f"{backend}: {c} = {counters.get(c)}")
    auto = {k: v for k, v in counters.items() if k.startswith("auto.")}
    note(f"  {backend:22} auto_decision {json.dumps(auto, sort_keys=True)}")
    return {"auto_decision": auto, "exchanges": exchanges(rec)}


def reference(steps: dict) -> dict:
    """Fingerprints of ``steps`` on the ``vectorized`` host backend.
    Their queries run unoptimized: on a mesh the ``partial_agg`` pass
    would send even a ``vectorized`` group-by to the sharded backend."""
    from repro import exec as rexec

    with rexec.use_backend("vectorized"):
        want = {}
        for name, step in steps.items():
            t0 = time.perf_counter()
            out = step()
            want[name] = fingerprint(out)
            note(f"  {'vectorized':22} {name:14} "
                 f"{time.perf_counter() - t0:.3f}s rows_out {rows(out)}")
    return want


def check_landing(label: str, mode: str, seen: list, ndev: int) -> None:
    """Rows must land on every device, each exchange checked on its own.
    Hash and agg exchanges spread rows over all shards. A table-mode
    join gives each shard a power-of-two slice of the key span
    (``exec.sharded``), so a span short of ``ndev`` slices leaves the
    top shards empty — 150,000 user keys in 65,536-key slices fill 3 of
    4 (ROADMAP B4). A table-mode phase therefore needs at least one
    table exchange that reaches every shard."""
    check(mode in {m for m, _ in seen}, f"{label}: no {mode} exchange ran")
    for m, per in seen:
        check(len(per) == ndev, f"{label}: {m} exchange on {len(per)} "
              f"shards, not {ndev}")
        check(m == "table" or min(per) > 0,
              f"{label}: {m} exchange left a shard empty: {list(per)}")
    table = [per for m, per in seen if m == "table"]
    check(mode != "table" or any(min(per) > 0 for per in table),
          f"{label}: no table exchange reached all {ndev} shards: {table}")


# -- phases ---------------------------------------------------------------
def one_chip(seed: int, **sizes) -> None:
    from benchmarks.sql_front_door import QUERY as STAR_QUERY

    t0 = time.perf_counter()
    client = new_client(star_schema(seed, **sizes))
    note(f"setup: star schema written in {time.perf_counter() - t0:.3f}s")
    queries = {"sql.star": STAR_QUERY, "sql.group_by": GROUP_QUERY}
    run = run_step(client)
    want = reference({**{name: sql_step(client, q, passes=())
                         for name, q in queries.items()},
                      "run.pipeline": run})
    steps = {**{name: sql_step(client, q) for name, q in queries.items()},
             "run.pipeline": run}
    decisions = {backend: run_config(backend, steps, want)["auto_decision"]
                 for backend in register_configs(1)}
    check(decisions["auto"].get("auto.group_by_agg.jax", 0) > 0,
          "auto never sent a group-by to the device")


def four_chips(seed: int, ndev: int = 4, **sizes) -> None:
    from benchmarks.sql_front_door import QUERY as STAR_QUERY
    from repro import exec as rexec
    from repro.exec.sharded import MAX_TABLE_SPAN, ShardedBackend, _get_mesh

    note(f"mesh: {list(_get_mesh(ndev).devices.flat)}")
    rexec.register("smoke.sharded", lambda: ShardedBackend(
        n_devices=ndev, use_pallas=False, use_pallas_probe=False))
    dense = new_client(star_schema(seed, **sizes))
    # the smallest user_id multiplier whose span is past the sharded
    # table budget: the same rows, joined in hash mode
    key_scale = MAX_TABLE_SPAN // (sizes.get("n_users", N_USERS) - 1) + 1
    sparse = new_client(star_schema(seed, key_scale=key_scale, **sizes))
    phases = {
        # name: (client, query, backends, exchange mode that must run)
        "star.table": (dense, STAR_QUERY, ["smoke.sharded"], "table"),
        "star.hash": (sparse, STAR_QUERY, ["smoke.sharded", "auto"],
                      "hash"),
        "group_by": (dense, GROUP_QUERY, ["smoke.sharded", "auto"],
                     "agg"),
    }
    for name, (client, query, backends, mode) in phases.items():
        want = reference({name: sql_step(client, query, passes=())})
        steps = {name: sql_step(client, query)}
        for backend in backends:
            r = run_config(backend, steps, want)
            for m, per in r["exchanges"]:
                note(f"  {backend:22} {name:14} {m} exchange, rows per "
                     f"shard {list(per)}")
            check_landing(f"{backend} {name}", mode, r["exchanges"], ndev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded exchange on a 4-chip mesh")
    args = ap.parse_args(argv)

    # libtpu logs under /tmp unless told otherwise; write nothing outside
    # the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    note(f"devices: {devices} platform={dev.platform} "
         f"kind={dev.device_kind} count={len(devices)} cache={cache}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    note(f"total: {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
