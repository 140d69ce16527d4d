"""Run one cell of the on-chip benchmark once.

    python benchmarks/chip/run.py --workload ssb_sf1.flight1 --seed 7 \
        --seconds 10 --trace 0

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` (see ``registry.py``). The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checks``: each number compared with the reference beside its
limit, which also end standard error. Without a TPU, or with fewer
chips than the cell asks for, it exits 1 and prints no such line.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import harness
    from registry import Registry

    reg = Registry()
    cell = reg.cell(args.workload)
    tables = None
    if "tpu" in os.environ.get("JAX_PLATFORMS", "tpu").split(","):
        # the tables are made from the seed while JAX brings the chip up
        tables = ThreadPoolExecutor(1).submit(
            harness.make_tables, reg, args.workload, args.seed)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (platform {devices[0].platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), reg=reg, t_start=T_START,
                              tables=tables)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
