"""The trace reduction and the roofline arithmetic on hand-built traces."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import peaks  # noqa: E402
import trace_reduce as tr  # noqa: E402
from registry import Registry  # noqa: E402

MS = 1_000_000          # ns


def _trace() -> tr.Trace:
    # window 0..100 ms; device ops 10-20, 15-30 (overlap), 60-70 ms
    ops = [(0, "jit_masked_segment_sum/fusion", 10 * MS, 20 * MS),
           (0, "jit_masked_segment_sum/fusion", 15 * MS, 30 * MS),
           (0, "jit_other/copy", 60 * MS, 70 * MS)]
    spans = [("chipbench/window", 0, 100 * MS),
             ("repro/sql", 0, 50 * MS),
             ("repro/node", 5 * MS, 45 * MS),
             ("repro/run", 50 * MS, 100 * MS),
             ("repro/publication_attempt", 80 * MS, 90 * MS)]
    return tr.Trace(ops=ops, spans=spans, window=(0, 100 * MS))


def test_merge_length_overlap():
    assert tr.merge([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tr.length([(1, 3), (2, 4), (10, 11)]) == 4
    assert tr.overlap([(0, 10)], [(2, 3), (5, 20)]) == 6
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_busy_is_the_union_of_device_ops():
    t = _trace()
    assert t.busy() == [(10 * MS, 30 * MS), (60 * MS, 70 * MS)]
    assert t.busy_s() == pytest.approx(0.030)
    assert t.window_s() == pytest.approx(0.100)
    assert t.op_seconds() == pytest.approx(
        {"jit_masked_segment_sum/fusion": 0.025, "jit_other/copy": 0.010})


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = _trace().idle_gaps()
    # idle: 0-10 (sql 0-5, node 5-10), 30-60 (node 30-45, sql 45-50,
    # run 50-60), 70-100 (run 70-80 and 90-100, publication 80-90)
    assert gaps == pytest.approx({"repro/sql": 0.010, "repro/node": 0.020,
                                  "repro/run": 0.030,
                                  "repro/publication_attempt": 0.010})
    assert sum(gaps.values()) == pytest.approx(0.070)


def test_breakdown_orders_by_time():
    b = _trace().breakdown(top=1)
    assert b["device_ops"] == [["jit_masked_segment_sum/fusion",
                                pytest.approx(0.025)]]
    assert b["idle_gaps"] == [["repro/run", pytest.approx(0.030)]]


def test_ops_outside_the_window_do_not_count():
    t = _trace()
    t.window = (25 * MS, 65 * MS)
    assert t.busy_s() == pytest.approx(0.010)


def test_op_names_from_the_hlo_text():
    text = ("%fusion.12 = s32[1500000]{0:T(1024)} fusion(s32[6001215]"
            "{0:T(1024)} %i.1), kind=kCustom")
    assert tr.op_name("jit_masked_segment_sum(1234)", text) == \
        "jit_masked_segment_sum/fusion"
    assert tr.op_name("jit_f(1)", "copy-start.3 = ...") == "jit_f/copy-start"


def test_segreduce_roofline_arithmetic():
    reg = Registry()
    t = _trace()
    rows, segs = 6_001_215, 1_500_000
    ctx = SimpleNamespace(trace=t, device_kind="TPU v5 lite",
                          work=[{"segment_reduce": [(rows, segs, 4)]}])
    got = reg.metric("segreduce_roofline.run").read(ctx)
    nbytes = rows * (4 + 4 + 1) + segs * (4 + 4)
    want = 100 * nbytes / 819e9 / 0.025
    assert got == pytest.approx(want)
    assert 0 < got <= 100
    # nothing to read: no segment op, or no counted work
    ctx.work = [{}]
    assert reg.metric("segreduce_roofline.run").read(ctx) is None


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v99")
    reg = Registry()
    ctx = SimpleNamespace(trace=_trace(), device_kind="cpu",
                          work=[{"segment_reduce": [(10, 2, 4)]}])
    with pytest.raises(KeyError):
        reg.metric("segreduce_roofline.run").read(ctx)


def test_host_exec_and_device_busy_per_unit():
    reg = Registry()
    ctx = SimpleNamespace(trace=_trace(), units=2, spans=[])
    # node 5-45 ms, device busy 10-30 inside it: 20 ms of host, 2 units
    assert reg.metric("host_exec_ms.query").read(ctx) == pytest.approx(10)
    assert reg.metric("device_busy_ms.run").read(ctx) == pytest.approx(15)


def test_span_metrics_read_the_flight_recorder():
    reg = Registry()
    spans = [SimpleNamespace(name="parse", t0=0.0, t1=0.002),
             SimpleNamespace(name="compile", t0=0.002, t1=0.006),
             SimpleNamespace(name="optimizer_pass", t0=0.0, t1=0.001),
             SimpleNamespace(name="verifier", t0=1.0, t1=1.5),
             SimpleNamespace(name="publication_attempt", t0=2.0, t1=2.1)]
    ctx = SimpleNamespace(spans=spans, units=2)
    assert reg.metric("sql_frontend_ms.query").read(ctx) == \
        pytest.approx(3.0)
    assert reg.metric("optimizer_ms.query").read(ctx) == pytest.approx(0.5)
    assert reg.metric("validate_ms.run").read(ctx) == pytest.approx(250)
    assert reg.metric("publish_ms.run").read(ctx) == pytest.approx(50)
    ctx.spans = []
    assert reg.metric("validate_ms.run").read(ctx) is None
