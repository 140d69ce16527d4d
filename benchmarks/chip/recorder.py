"""A flight recorder whose spans also land on the profiler's clock.

Installed through ``repro.obs.install`` for the traced window: every
span the program opens is recorded as the program's own recorder records
it and is also opened as ``jax.profiler.TraceAnnotation("repro/<span>")``,
so the device trace shows what the host was doing around each op.
"""
from __future__ import annotations

import threading

import jax
from repro.obs import TraceRecorder


class _Annotated:
    __slots__ = ("inner", "ann")

    def __init__(self, inner, name: str):
        self.inner = inner
        self.ann = jax.profiler.TraceAnnotation(f"repro/{name}")

    def __enter__(self):
        self.ann.__enter__()
        return self.inner.__enter__()

    def __exit__(self, *exc):
        try:
            return self.inner.__exit__(*exc)
        finally:
            self.ann.__exit__(*exc)


class AnnotatingRecorder(TraceRecorder):
    def __init__(self):
        super().__init__()
        self._ann_lock = threading.Lock()
        self._open_ann: dict[int, object] = {}

    def span(self, name: str, /, **attrs):
        return _Annotated(super().span(name, **attrs), name)

    def start_span(self, name: str, /, **attrs):
        sp = super().start_span(name, **attrs)
        ann = jax.profiler.TraceAnnotation(f"repro/{name}")
        ann.__enter__()
        with self._ann_lock:
            self._open_ann[sp.span_id] = ann
        return sp

    def end_span(self, span) -> None:
        with self._ann_lock:
            ann = self._open_ann.pop(getattr(span, "span_id", None), None)
        super().end_span(span)
        if ann is not None:
            ann.__exit__(None, None, None)
