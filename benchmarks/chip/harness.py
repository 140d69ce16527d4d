"""One run of one cell: set-up, warm-up, the measured window, the check.

``run_cell`` is everything ``run.py`` does after it has found the chip:
it makes the cell's tables from the seed, writes them to a fresh
``Client``, warms every unit of the mix, measures a window of
``seconds`` (the unit in flight at the end is finished and counted),
reads the answers back and compares them with the plain reference,
and returns the result line as a dict. With ``trace`` it also records
the program's spans on the profiler's clock and reduces the device
trace to the cell's per-layer metrics.
"""
from __future__ import annotations

import shutil
import sys
import time
import traceback
from types import SimpleNamespace

import workload
from registry import ROOT, Registry

TRACE_DIR = ROOT / ".chipbench" / "trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _CompileCounter:
    """Counts XLA executables obtained (compiled or loaded from the
    persistent cache) while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_) -> None:
        if self.on and event == COMPILE_EVENT:
            self.count += 1


def _note(t_start: float, what: str) -> None:
    print(f"setup {time.time() - t_start:9.3f}s {what}", file=sys.stderr,
          flush=True)


def _send(sender, inst):
    """One unit; a unit that raises is reported and returns ``None``."""
    try:
        return sender.send(inst)
    except Exception:               # counted as failed, never fatal
        traceback.print_exc(file=sys.stderr)
        return None


def _answers(sender, mix: dict, done: list) -> list:
    """What each window unit left behind, read back from the catalog:
    ``(instance, {table: columns} | None, commits_ok)``."""
    import compare

    client = sender.client
    out = []
    for inst, got in done:
        if got is None:
            out.append((inst, None, False))
        elif mix["entry"] == "sql":
            out.append((inst, {"result": compare.columns(got)}, True))
        else:
            res, branch = got["result"], got["branch"]
            head = client.catalog.head(branch)
            one = (res.state.status == "committed"
                   and res.state.final_commit == head.id
                   and tuple(head.parents[:1]) == (got["base"],))
            tables = {n["name"]: compare.columns(
                client.read_table(head.id, n["name"]))
                for n in inst.unit["nodes"] if n["name"] in head.tables}
            out.append((inst, tables, one))
    return out


def check(reg: Registry, config: str, mix: dict, tables: dict,
          answers: list) -> dict:
    """Compare every answer with the reference; returns the compared
    numbers, each ``{"value": n, "limit": 0}``."""
    import compare

    want_of: dict = {}
    wrong = missing = commits = 0
    for inst, got, one in answers:
        key = (inst.name, tuple(sorted(inst.params.items())))
        if key not in want_of:
            want = reg.reference(config, inst.name).answer(tables,
                                                           inst.params)
            want_of[key] = want if mix["entry"] == "run" else {
                "result": want}
        want = want_of[key]
        if got is None or set(got) != set(want):
            missing += 1
            continue
        order = {n["name"]: n.get("order_by", ())
                 for n in inst.unit.get("nodes", ())}
        order["result"] = inst.unit.get("order_by", ())
        bad = [f"{t}: {why}" for t in want
               if (why := compare.mismatch(got[t], want[t], order[t]))]
        if bad:
            wrong += 1
            print(f"check: {inst.name} {inst.params} differs: {bad}",
                  file=sys.stderr)
        if mix["entry"] == "run" and not one:
            commits += 1
    checks = {"wrong_answers": wrong, "missing_answers": missing}
    if mix["entry"] == "run":
        checks["runs_not_one_commit"] = commits
    return {k: {"value": v, "limit": 0} for k, v in checks.items()}


def make_tables(reg: Registry, name: str, seed: int,
                scale: float = 1.0) -> dict:
    """Cell ``name``'s tables from the seed (numpy only, no JAX)."""
    config = reg.cell(name)["config"]
    return reg.generator(config).make(reg.config(config), seed, scale)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             reg: Registry, t_start: float, scale: float = 1.0,
             tables=None) -> dict:
    """``tables``, where given, is a future of ``make_tables``'s result
    (``run.py`` makes the tables while JAX brings the chip up)."""
    import jax
    from repro import exec as rexec
    from repro import obs
    from repro.core.runner import Client

    cell = reg.cell(name)
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    host = cfg["host"]
    jax.config.update("jax_enable_x64", bool(host["jax_enable_x64"]))
    compiles = _CompileCounter()

    _note(t_start, "started; JAX and the device are up")
    tables = (tables.result() if tables is not None
              else make_tables(reg, name, seed, scale))
    _note(t_start, "tables generated")
    client = Client()
    workload.load_tables(client, tables)
    _note(t_start, "tables written to the catalog")
    sender = workload.Sender(client, mix, max_workers=host["max_workers"])
    failed_setup = failed = 0
    with rexec.use_backend(host["backend"]):
        for inst in workload.warmup(mix, seed):
            failed_setup += _send(sender, inst) is None
            _note(t_start, f"warmed {inst.name} {inst.params}")

        rec = prev = None
        if trace:
            from recorder import AnnotatingRecorder
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            rec = AnnotatingRecorder()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            prev = obs.install(rec)
        done = []
        window = jax.profiler.TraceAnnotation("chipbench/window")
        compiles.on = True
        t0 = time.perf_counter()
        setup_s = time.time() - t_start
        cpu0 = time.process_time()
        with window:
            ends, cpu = [], []
            for inst in workload.rotation(mix, seed):
                got = _send(sender, inst)
                failed += got is None
                done.append((inst, got))
                ends.append(time.perf_counter())
                cpu.append(time.process_time())
                if ends[-1] - t0 >= seconds:
                    break
        t1 = time.perf_counter()
        compiles.on = False
        if trace:
            obs.install(prev)
            jax.profiler.stop_trace()

    each = [b - a for a, b in zip([t0] + ends, ends)]
    print(f"window {t1 - t0:.3f}s, {len(done)} units, each (s): "
          + " ".join(f"{e:.3f}" for e in each), file=sys.stderr, flush=True)
    # the process's CPU seconds in each unit, all threads: a unit slow on
    # the wall clock but not here waited (descheduled, I/O), not worked
    print("cpu (s): " + " ".join(
        f"{b - a:.3f}" for a, b in zip([cpu0] + cpu, cpu)), file=sys.stderr,
        flush=True)
    devices = jax.local_devices()[:cell["chips"]]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    t_check = time.time()
    answers = _answers(sender, mix, done)
    checks = check(reg, cell["config"], mix, tables, answers)
    print(f"read back and checked {len(answers)} answers in "
          f"{time.time() - t_check:.3f}s", file=sys.stderr, flush=True)
    checks["failed_units"] = {"value": failed_setup + failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(done), "failed": failed}
    if trace:
        import trace_reduce
        tr = trace_reduce.read(TRACE_DIR, n_devices=len(devices))
        ctx = SimpleNamespace(
            units=len(done), spans=rec.spans(), trace=tr,
            compiles=compiles.count, device_kind=dev.device_kind,
            work=_work(reg, cell["config"], tables, done))
        metrics = {}
        for m in reg.per_layer(name):
            value = reg.metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s())
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = tr.breakdown()
    else:
        values = {"setup_s": setup_s,
                  mix["unit_metric"]: (t1 - t0) / max(1, len(done))}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in reg.end_to_end(name)}
        result["device"] = device
    result["checks"] = checks
    return result


def _work(reg: Registry, config: str, tables: dict, done: list) -> list:
    """The logical work of each window unit, as its reference counts it
    (for rooflines); empty where the reference does not count it."""
    memo: dict = {}
    out = []
    for inst, _ in done:
        key = (inst.name, tuple(sorted(inst.params.items())))
        if key not in memo:
            ref = reg.reference(config, inst.name)
            memo[key] = (ref.work(tables, inst.params)
                         if hasattr(ref, "work") else {})
        out.append(memo[key])
    return out
