"""The check must fail its control and every fault a cell can have.

The control is the plain reference in the configuration's lower
precision, put in the program's place. The faults break the timed path
underneath a whole run of the harness (with the look for a chip
skipped): a step that returns its state unchanged, half of the rows
left out of the sum, an answer altered where it is produced. One chip
has no exchange between chips, so that fault does not apply.
"""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import control  # noqa: E402
import harness  # noqa: E402
from registry import Registry  # noqa: E402

SEED = 2**31 + 99
CELLS = ("ssb_sf1.flight1", "tpch_sf1.q18_run")


class SmallQuantities(Registry):
    """Q18's thresholds lowered so that a CPU-sized run selects orders."""

    def traffic(self, name):
        mix = copy.deepcopy(super().traffic(name))
        for unit in mix["units"]:
            if "quantity" in unit.get("params", {}):
                unit["params"]["quantity"] = [150, 200, 250]
        return mix


@pytest.fixture
def x64_restored():
    import jax
    before = jax.config.jax_enable_x64
    yield
    jax.config.update("jax_enable_x64", before)


@pytest.fixture
def device_path(monkeypatch):
    from repro.exec import auto
    monkeypatch.setattr(auto, "DEVICE_ROWS", 1000)


def _run(name: str) -> dict:
    return harness.run_cell(name, SEED, 0.5, False, reg=SmallQuantities(),
                            t_start=time.time(), scale=0.01)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, x64_restored, device_path):
    r = _run(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    checks = control.control_checks(SmallQuantities(), name, SEED,
                                    scale=0.01)
    assert checks["wrong_answers"]["value"] > 0, checks


def _stale_state(monkeypatch):
    """Every execution after the first returns the first one's outcome:
    a step that leaves its state unchanged."""
    from repro.core.engine import PlanExecutor
    real = PlanExecutor.execute
    first = {}

    def execute(self, *a, **kw):
        out = real(self, *a, **kw)
        return first.setdefault("outcome", out)
    monkeypatch.setattr(PlanExecutor, "execute", execute)


def _half_rows(monkeypatch):
    """The device sum leaves out every other row."""
    import jax.numpy as jnp
    from repro.exec import jax_backend
    real = jax_backend.masked_segment_sum

    def half(values, ids, valid, n, **kw):
        keep = (jnp.arange(values.shape[0]) % 2) == 0
        return real(values, ids, valid & keep, n, **kw)
    monkeypatch.setattr(jax_backend, "masked_segment_sum", half)


def _altered(monkeypatch):
    """The device sum's first segment is off by one."""
    from repro.exec import jax_backend
    real = jax_backend.masked_segment_sum

    def altered(values, ids, valid, n, **kw):
        sums, counts = real(values, ids, valid, n, **kw)
        return sums.at[0].add(1), counts
    monkeypatch.setattr(jax_backend, "masked_segment_sum", altered)


@pytest.mark.parametrize("fault", [_stale_state, _half_rows, _altered],
                         ids=["state_unchanged", "half_rows", "altered"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_makes_the_run_not_correct(name, fault, monkeypatch,
                                         x64_restored, device_path):
    fault(monkeypatch)
    r = _run(name)
    assert not r["correct"]
    caught = [k for k in ("wrong_answers", "failed_units")
              if r["checks"][k]["value"] > 0]
    assert caught, r["checks"]


def test_control_dtype_is_lower_than_the_stated_one():
    reg = Registry()
    for name in ("ssb_sf1", "tpch_sf1"):
        cfg = reg.config(name)
        assert np.dtype(cfg["control"]["dtype"]).itemsize <= 4
