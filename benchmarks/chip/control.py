"""The check's control: the reference, in a lower precision, in the
program's place.

    python benchmarks/chip/control.py --workload tpch_sf1.q18_run \
        --seeds 21,22,23

For each seed it makes the cell's tables at full size, computes the
answer of every unit of the mix with the plain reference at the
configuration's ``control`` dtype (the step a later change would be
tempted to take: int32 for SSB's int64 revenue, float32 for TPC-H's
int32 money), and hands those answers to the same check that judges
the program's. The control must come out not correct on every seed;
the compared numbers are printed per seed. The benchmark's own runs
never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE)]

import numpy as np  # noqa: E402

import harness  # noqa: E402
import workload  # noqa: E402
from registry import Registry  # noqa: E402


def control_checks(reg: Registry, name: str, seed: int, *,
                   scale: float = 1.0, mix: dict | None = None) -> dict:
    """The compared numbers when the control answers every unit of
    ``name``'s mix once."""
    cell = reg.cell(name)
    cfg = reg.config(cell["config"])
    mix = mix if mix is not None else reg.traffic(cell["traffic"])
    dtype = np.dtype(cfg["control"]["dtype"])
    tables = harness.make_tables(reg, name, seed, scale)
    answers = []
    for inst in workload.instances(mix):
        got = reg.reference(cell["config"], inst.name).answer(
            tables, inst.params, dtype)
        answers.append((inst, got if mix["entry"] == "run"
                        else {"result": got}, True))
    return harness.check(reg, cell["config"], mix, tables, answers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    reg = Registry()
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = control_checks(reg, args.workload, seed)
        failed = any(c["value"] > c["limit"] for c in checks.values())
        failed_all &= failed
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_failed": failed, "checks": checks}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
