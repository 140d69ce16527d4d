"""The readers of the spans inside a node, on hand-built contexts, and the
four-chip Q18 cell rehearsed on a forced four-device host mesh."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import trace_reduce as tr  # noqa: E402
from registry import Registry  # noqa: E402

MS = 1_000_000          # ns
MESH_CELL = "tpch_sf1_mesh4.q18_run"


def _span(name, t0, t1, **attrs):
    return SimpleNamespace(name=name, t0=t0, t1=t1, attrs=attrs)


def _spans():
    return [
        _span("node", 0.0, 1.0),
        _span("snapshot_read", 0.0, 0.3, rows=10, bytes=100),
        _span("snapshot_read", 0.9, 1.0, rows=2, bytes=16),
        _span("op.aggregate", 0.3, 0.6, rows_out=2),
        _span("key_codes", 0.3, 0.35, rows=10, keys=1, object_keys=0),
        _span("kernel", 0.35, 0.5, op="jax.segment_sum", rows=10,
              segments=2, h2d_bytes=3_000_000, d2h_bytes=16),
        _span("op.join", 0.6, 0.8, rows_out=10),
        _span("key_codes", 0.6, 0.62, rows=12, keys=1, object_keys=1),
        _span("row_emit", 0.62, 0.8, rows_out=10, columns=3),
        _span("contract_check", 0.8, 0.85, table="t", rows=10,
              columns=3),
        _span("snapshot_write", 0.85, 0.9, columns=3, rows=10,
              bytes=120),
        # tracing from before this instrumentation: no byte counts
        _span("kernel", 0.5, 0.55, op="sharded.partial_agg", rows=10),
    ]


@pytest.mark.parametrize("metric,want", [
    ("snapshot_read_ms.query", 200.0),
    ("snapshot_read_ms.run", 200.0),
    ("snapshot_write_ms.run", 25.0),
    ("contract_check_ms.run", 25.0),
    ("key_codes_ms.query", 35.0),
    ("key_codes_ms.run", 35.0),
    ("row_emit_ms.query", 90.0),
    ("row_emit_ms.run", 90.0),
    ("h2d_mb.query", 1.5),
    ("h2d_mb.run", 1.5),
])
def test_span_readers_per_unit(metric, want):
    reg = Registry()
    ctx = SimpleNamespace(spans=_spans(), units=2)
    assert reg.metric(metric).read(ctx) == pytest.approx(want)
    ctx.spans = [s for s in ctx.spans if s.name in ("node", "op.join")]
    assert reg.metric(metric).read(ctx) is None


def test_h2d_reads_only_spans_that_count_bytes():
    reg = Registry()
    old = [s for s in _spans() if s.name != "kernel"
           or "h2d_bytes" not in s.attrs]
    ctx = SimpleNamespace(spans=old, units=1)
    assert reg.metric("h2d_mb.run").read(ctx) is None


def _mesh_trace(ops):
    return tr.Trace(ops=ops, spans=[("chipbench/window", 0, 100 * MS)],
                    window=(0, 100 * MS), n_devices=4)


def test_exchange_is_all_to_all_device_time_per_device_and_run():
    reg = Registry()
    ops = [(d, "jit_body/all_to_all", 10 * MS, (12 + d) * MS)
           for d in range(4)]
    ops += [(0, "jit_mapped/fusion", 20 * MS, 40 * MS),
            (2, "jit_mapped/all-to-all-done", 50 * MS, 51 * MS)]
    ctx = SimpleNamespace(trace=_mesh_trace(ops), units=2)
    # all-to-all: 2 + 3 + 4 + 5 + 1 ms over 4 devices, 2 runs
    assert reg.metric("exchange_ms.run").read(ctx) == \
        pytest.approx(15 / 4 / 2)
    ctx.trace = _mesh_trace([(0, "jit_mapped/fusion", 0, 5 * MS)])
    assert reg.metric("exchange_ms.run").read(ctx) is None


def test_shard_share_sums_probes_and_groups_over_the_window():
    reg = Registry()
    spans = [
        _span("kernel", 0, 1, op="sharded.partial_agg",
              groups_per_shard=[524288, 524288, 451424, 0],
              rows_per_shard=[1500304] * 4),
        _span("kernel", 1, 2, op="sharded.partial_agg",
              groups_per_shard=[524288, 524288, 451424, 0]),
        _span("kernel", 2, 3, op="sharded.exchange_probe",
              rows_left_per_shard=[10, 10, 10, 10],
              rows_right_per_shard=[0, 0, 0, 40]),
        _span("kernel", 3, 4, op="jax.segment_sum", rows=9),
        _span("node", 0, 4),
    ]
    ctx = SimpleNamespace(spans=spans, units=2)
    per_shard = [1048586, 1048586, 902858, 50]
    assert reg.metric("shard_rows_max_share.run").read(ctx) == \
        pytest.approx(max(per_shard) / sum(per_shard))
    ctx.spans = spans[:2]
    assert reg.metric("shard_rows_max_share.run").read(ctx) == \
        pytest.approx(524288 / 1_500_000)
    # an even split over four shards reads a quarter
    ctx.spans = [_span("kernel", 0, 1, op="sharded.partial_agg",
                       groups_per_shard=[5, 5, 5, 5])]
    assert reg.metric("shard_rows_max_share.run").read(ctx) == 0.25
    # partial aggregation traced without groups_per_shard: nothing
    ctx.spans = [_span("kernel", 0, 1, op="sharded.partial_agg",
                       rows_per_shard=[5, 5, 5, 5]), spans[-1]]
    assert reg.metric("shard_rows_max_share.run").read(ctx) is None


def test_mesh_cell_runs_the_q18_traffic_on_four_chips():
    reg = Registry()
    cell = reg.cell(MESH_CELL)
    assert cell["chips"] == 4
    assert reg.traffic(cell["traffic"])["units"] == \
        reg.traffic(reg.cell("tpch_sf1.q18_run")["traffic"])["units"]
    metrics = {m["name"] for m in reg.per_layer(MESH_CELL)}
    assert {"exchange_ms.run", "shard_rows_max_share.run", "h2d_mb.run",
            "host_exec_ms.run"} <= metrics


_REHEARSAL = """
import json, sys, time
sys.path[:0] = [{here!r}, {src!r}]
import copy
import jax
assert jax.device_count() == 4, jax.devices()
import harness
from registry import Registry


class SmallQuantities(Registry):
    def traffic(self, name):
        mix = copy.deepcopy(super().traffic(name))
        for unit in mix["units"]:
            unit["params"]["quantity"] = [150, 200, 250]
        return mix


r = harness.run_cell({cell!r}, 2**31 + 77, 0.5, True,
                     reg=SmallQuantities(), t_start=time.time(),
                     scale=0.01)
print(json.dumps(r))
"""


def test_mesh_cell_rehearses_on_a_forced_host_mesh():
    """The four-chip cell, traced, at a hundredth of its size on four
    host devices, with ``auto``'s mesh threshold scaled down with the
    data: correct, and every span reader of the cell reads something."""
    script = textwrap.dedent(_REHEARSAL).format(
        here=str(HERE), src=str(ROOT / "src"), cell=MESH_CELL)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", REPRO_AUTO_SHARD_ROWS="1000",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["correct"], result["checks"]
    got = result["metrics"]
    for name in ("snapshot_read_ms.run", "snapshot_write_ms.run",
                 "contract_check_ms.run", "key_codes_ms.run",
                 "h2d_mb.run", "host_exec_ms.run"):
        assert got[name]["value"] > 0, name
    assert 0.25 <= got["shard_rows_max_share.run"]["value"] <= 1
    # no TPU planes on the host: no device op to read
    assert "exchange_ms.run" not in got
