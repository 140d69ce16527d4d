"""Reduce the profiler's trace to device busy time, idle gaps and op times.

``read(dir)`` loads the ``.xplane.pb`` that ``jax.profiler`` wrote under
``dir`` and keeps three things, all in nanoseconds on the trace's one
clock: the device ops (``/device:TPU:<n>`` planes, line ``XLA Ops``,
each named ``<module>/<op>`` from the ``XLA Modules`` line it lies in),
the host annotations the harness and its recorder opened
(``chipbench/window`` and ``repro/<span>``), and the window. The
arithmetic lives in :class:`Trace`, which a test can build by hand.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

WINDOW = "chipbench/window"
_OP = re.compile(r"%?([A-Za-z_][\w-]*?)(?:\.\d+)?\s*=")


def merge(intervals) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in merge(intervals))


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def overlap(a, b) -> int:
    """Length of the intersection of two interval sets."""
    a, b = merge(a), merge(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def op_name(module: str, text: str) -> str:
    """``jit_f(123)`` + ``%fusion.3 = s32[...] ...`` -> ``jit_f/fusion``."""
    m = _OP.match(text)
    op = m.group(1) if m else text.split(" ")[0]
    return f"{module.split('(')[0]}/{op}"


@dataclasses.dataclass
class Trace:
    """``ops``: (device, name, start, end); ``spans``: (name, start,
    end); ``window``: (start, end)."""

    ops: list[tuple[int, str, int, int]]
    spans: list[tuple[str, int, int]]
    window: tuple[int, int]
    n_devices: int = 1

    def _ops_in_window(self):
        lo, hi = self.window
        return [(d, n, max(s, lo), min(e, hi)) for d, n, s, e in self.ops
                if min(e, hi) > max(s, lo)]

    def busy(self, device: int | None = None) -> list[tuple[int, int]]:
        return merge((s, e) for d, _, s, e in self._ops_in_window()
                     if device is None or d == device)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        return sum(length(self.busy(d)) for d in range(self.n_devices)
                   ) / self.n_devices / 1e9

    def span_intervals(self, name: str) -> list[tuple[int, int]]:
        return clip([(s, e) for n, s, e in self.spans if n == name],
                    *self.window)

    def op_seconds(self) -> dict[str, float]:
        """Device seconds per op name, summed over devices."""
        out: dict[str, float] = {}
        for _, name, s, e in self._ops_in_window():
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return out

    def idle_gaps(self) -> dict[str, float]:
        """Idle device time (device 0) by the innermost host span open
        during it: the one that started last. Idle time under no span is
        ``(none)``."""
        lo, hi = self.window
        busy = self.busy(0)
        gaps, cur = [], lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            gaps.append((cur, hi))
        spans = sorted(((s, e, n) for n, s, e in self.spans
                        if n != WINDOW), key=lambda t: t[0])
        cuts = sorted({lo, hi, *(s for s, _, _ in spans if lo < s < hi),
                       *(e for _, e, _ in spans if lo < e < hi)})
        out: dict[str, float] = {}
        g = 0
        for a, b in zip(cuts, cuts[1:]):
            inner = "(none)"
            best = None
            for s, e, n in spans:
                if s > a:
                    break
                if e >= b and (best is None or s >= best):
                    best, inner = s, n
            while g < len(gaps) and gaps[g][1] <= a:
                g += 1
            k, idle = g, 0
            while k < len(gaps) and gaps[k][0] < b:
                idle += max(0, min(b, gaps[k][1]) - max(a, gaps[k][0]))
                k += 1
            if idle:
                out[inner] = out.get(inner, 0.0) + idle / 1e9
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def read(trace_dir: Path, n_devices: int = 1) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops, spans = [], []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              e.name) for e in lines.get("XLA Modules", []))
            mi = 0
            for e in sorted(lines.get("XLA Ops", []),
                            key=lambda e: e.start_ns):
                s = int(e.start_ns)
                while mi < len(modules) and modules[mi][1] < s:
                    mi += 1
                mod = (modules[mi][2] if mi < len(modules)
                       and modules[mi][0] <= s else "?")
                ops.append((dev, op_name(mod, e.name), s,
                            s + int(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(("repro/", "chipbench/")):
                        s = int(e.start_ns)
                        spans.append((e.name, s, s + int(e.duration_ns)))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError("the trace holds no chipbench/window annotation")
    return Trace(ops=ops, spans=spans, window=windows[0],
                 n_devices=n_devices)
