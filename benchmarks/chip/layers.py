"""Readings shared by the per-layer metric files in ``metrics/``.

Each function takes the reader's context (``harness.run_cell`` builds
it: ``units`` finished in the window, the program's ``spans``, the
reduced device ``trace``, ``compiles`` in the window, ``device_kind``,
and each unit's logical ``work``) and returns a number, or ``None``
where there is nothing to read.
"""
from __future__ import annotations


def span_ms(ctx, *names: str) -> float | None:
    """Milliseconds per unit spent in the program's spans of ``names``
    (host clock, from the flight recorder)."""
    spans = [s for s in ctx.spans if s.name in names and s.t1 is not None]
    if not spans or not ctx.units:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / ctx.units


def device_busy_ms(ctx) -> float | None:
    """Milliseconds per unit in which a device op ran (union of op
    intervals in the window, device trace)."""
    if not ctx.trace.ops or not ctx.units:
        return None
    return 1e3 * ctx.trace.busy_s() / ctx.units


def host_exec_ms(ctx) -> float | None:
    """Milliseconds per unit inside ``node`` spans with no device op
    running (the spans as annotations on the device trace's clock)."""
    from trace_reduce import length, overlap

    nodes = ctx.trace.span_intervals("repro/node")
    if not nodes or not ctx.units:
        return None
    host = length(nodes) - overlap(nodes, ctx.trace.busy())
    return host / 1e6 / ctx.units
