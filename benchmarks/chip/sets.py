"""Run one cell several times, one process per run, and report spreads.

    python benchmarks/chip/sets.py --workload ssb_sf1.flight1 \
        --seeds 11,12,13 --seconds 51 [--trace 0] [--out runs.jsonl]

Each run is ``run.py`` in a child process (this parent never touches
JAX, so each child has the chip to itself). Every result line is
appended to ``--out`` with its seed and wall time; then, per end-to-end
metric, the median and the spread (distance between the first and third
quartile of ``statistics.quantiles(values, n=4)`` over the median) are
printed. This is how a bound is measured: two such sets with the same
seeds, and the wider of their spreads.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, str(RUN), "--workload", args.workload,
             "--seed", str(seed), "--seconds", str(args.seconds),
             "--trace", str(args.trace)],
            capture_output=True, text=True)
        wall = time.time() - t0
        lines = proc.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except json.JSONDecodeError:
            result = None
        row = {"workload": args.workload, "seed": seed, "rc": proc.returncode,
               "wall_s": wall, "result": result,
               "stderr_tail": proc.stderr[-2000:]}
        rows.append(row)
        if args.out:
            with args.out.open("a") as f:
                f.write(json.dumps(row) + "\n")
        brief = ({k: v["value"] for k, v in result["metrics"].items()}
                 if result else proc.stderr[-1500:])
        print(f"seed {seed} rc {proc.returncode} wall {wall:.1f}s "
              f"correct {result and result['correct']} "
              f"attempted {result and result['attempted']} {brief}",
              flush=True)
    ok = [r["result"] for r in rows if r["result"]]
    if len(ok) >= 2:
        for name in ok[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in ok
                    if name in r["metrics"]]
            if len(vals) >= 2 and statistics.median(vals):
                print(f"{args.workload} {name}: median "
                      f"{statistics.median(vals)!r} spread "
                      f"{spread(vals)!r} n {len(vals)}", flush=True)
    return 0 if all(r["rc"] == 0 and r["result"] and r["result"]["correct"]
                    for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
