"""The readers of the snapshot read's skipped columns, on hand-built
contexts."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from registry import Registry  # noqa: E402


def _span(name, t0, t1, **attrs):
    return SimpleNamespace(name=name, t0=t0, t1=t1, attrs=attrs)


def _unprojected_spans():
    """Spans as tracing wrote them before the read took columns: no
    ``columns_skipped`` on any ``snapshot_read``."""
    return [
        _span("node", 0.0, 1.0),
        _span("snapshot_read", 0.0, 0.3, columns=17, str_columns=5,
              rows=10, bytes=100),
        _span("snapshot_read", 0.9, 1.0, columns=2, str_columns=0,
              rows=2, bytes=16),
        _span("op.aggregate", 0.3, 0.6, rows_out=2),
    ]


@pytest.mark.parametrize("metric", ["snapshot_skip_share.query",
                                    "snapshot_skip_share.run"])
def test_skip_share_reads_only_spans_that_count_skips(metric):
    reg = Registry()
    reads = [_span("snapshot_read", 0.0, 0.1, columns=4,
                   columns_skipped=13, str_columns=0, rows=10, bytes=160),
             _span("snapshot_read", 0.1, 0.2, columns=2,
                   columns_skipped=0, str_columns=1, rows=2, bytes=16)]
    ctx = SimpleNamespace(spans=_unprojected_spans() + reads, units=2)
    assert reg.metric(metric).read(ctx) == pytest.approx(13 / 19)
    ctx.spans = _unprojected_spans()
    assert reg.metric(metric).read(ctx) is None
