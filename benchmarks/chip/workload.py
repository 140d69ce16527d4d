"""The one general traffic generator: a traffic mix file drives a Client.

A mix (``traffic/<mix>.json``) names its entry point (``sql``: one
``Client.sql`` per unit; ``run``: one ``Client.run`` of a pipeline per
unit, published to a fresh branch), its units and their parameters, and
whether the node cache is on. One client sends them in a closed loop,
in rotation, against ``main``. This module turns a mix into a sequence
of unit instances from the seed and sends them; it knows no query and
no table by name.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any

import numpy as np

BRANCH = "main"          # the tables are written to it and read from it


@dataclasses.dataclass(frozen=True)
class Instance:
    """One unit of work with its parameters bound."""

    unit: dict
    params: dict

    @property
    def name(self) -> str:
        return self.unit["name"]

    def text(self, sql: str) -> str:
        return sql.format(**self.params)


def instances(mix: dict) -> list[Instance]:
    """Every unit with every value of its parameters, in file order;
    parameters with several lists advance together."""
    out = []
    for unit in mix["units"]:
        params = unit.get("params", {})
        n = max((len(v) for v in params.values()), default=1)
        for i in range(n):
            out.append(Instance(unit, {k: v[i % len(v)]
                                       for k, v in params.items()}))
    return out


def rotation(mix: dict, seed: int):
    """The order of sending: every instance in turn, from a start drawn
    from the seed, forever. Every seed sends the same set of units in
    another order."""
    inst = instances(mix)
    start = int(np.random.default_rng(seed).integers(len(inst)))
    return itertools.islice(itertools.cycle(inst), start, None)


def warmup(mix: dict, seed: int) -> list[Instance]:
    """Set-up's units: each unit once, with the parameter values that
    the window sends first."""
    out: dict[str, Instance] = {}
    for inst in rotation(mix, seed):
        out.setdefault(inst.name, inst)
        if len(out) == len(mix["units"]):
            return list(out.values())
    raise AssertionError("unreachable")     # pragma: no cover


def load_tables(client, tables: dict) -> None:
    """Write every generated table to ``BRANCH``."""
    from repro.data.tables import Table

    for name, cols in tables.items():
        client.write_source_table(BRANCH, name, Table(cols),
                                  message=f"load {name}")


def _verifier(spec: list):
    from repro.core import quality

    fn, args = spec
    return getattr(quality, fn)(*args)


class Sender:
    """Sends unit instances of one mix to one client."""

    def __init__(self, client, mix: dict, *, max_workers: int | None):
        if mix["entry"] not in ("sql", "run"):
            raise ValueError(f"unknown entry {mix['entry']!r}")
        self.client = client
        self.mix = mix
        self.max_workers = max_workers
        self.cache = bool(mix.get("cache", False))
        self._branches = itertools.count()

    def send(self, inst: Instance) -> Any:
        """Run one unit to its end; returns what the check reads back."""
        if self.mix["entry"] == "sql":
            res = self.client.sql(inst.text(inst.unit["sql"]), BRANCH,
                                  cache=self.cache)
            return res.table
        return self._run(inst)

    def _run(self, inst: Instance) -> dict:
        from repro.core.dag import Pipeline
        from repro.core.planner import plan
        from repro.optimizer import optimize
        from repro.sql.discovery import schema_from_snapshot

        client = self.client
        branch = f"bench-{inst.name}-{next(self._branches)}"
        client.create_branch(branch, BRANCH)
        base = client.catalog.head(branch)
        p = Pipeline(inst.name)
        for table, snap in base.tables.items():
            p.source(table, schema_from_snapshot(client.store, snap, table))
        for node in inst.unit["nodes"]:
            p.sql_query(name=node["name"], query=inst.text(node["sql"]))
        verifiers = {t: [_verifier(s) for s in specs]
                     for t, specs in inst.unit.get("verifiers", {}).items()}
        res = client.run(optimize(plan(p)), branch, verifiers=verifiers,
                         cache=self.cache, max_workers=self.max_workers)
        return {"branch": branch, "base": base.id, "result": res}
