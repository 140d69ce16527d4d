"""The cells' references against the program, discovery by name, and the
command's refusal to run without a chip. CPU only, at small scale."""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import harness  # noqa: E402
import workload  # noqa: E402
from registry import Registry  # noqa: E402

SEED = 2**31 + 12345            # larger than 32 signed bits hold
# Q18's spec thresholds (312..315) select nothing at a CPU test's scale;
# these select a few hundred orders there, so LIMIT 100 and the order
# are exercised too.
SMALL_QUANTITIES = [150, 200, 250]


@pytest.fixture
def x64_restored():
    import jax
    before = jax.config.jax_enable_x64
    yield
    jax.config.update("jax_enable_x64", before)


@pytest.fixture
def device_path(monkeypatch):
    """Let ``auto`` send small aggregations to the device backend, so a
    CPU test drives both of its paths."""
    from repro.exec import auto
    monkeypatch.setattr(auto, "DEVICE_ROWS", 1000)


class SmallQuantities(Registry):
    def traffic(self, name):
        mix = copy.deepcopy(super().traffic(name))
        for unit in mix["units"]:
            if "quantity" in unit.get("params", {}):
                unit["params"]["quantity"] = SMALL_QUANTITIES
        return mix


def _every_unit(reg: Registry, name: str, scale: float) -> dict:
    """Send every unit instance of the cell's mix once through the
    program and check each against the reference."""
    import jax
    from repro import exec as rexec
    from repro.core.runner import Client

    cell = reg.cell(name)
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    jax.config.update("jax_enable_x64", cfg["host"]["jax_enable_x64"])
    tables = reg.generator(cell["config"]).make(cfg, SEED, scale)
    client = Client()
    workload.load_tables(client, tables)
    sender = workload.Sender(client, mix, max_workers=1)
    with rexec.use_backend(cfg["host"]["backend"]):
        done = [(inst, sender.send(inst))
                for inst in workload.instances(mix)]
    answers = harness._answers(sender, mix, done)
    return harness.check(reg, cell["config"], mix, tables, answers), answers


@pytest.mark.parametrize("name,registry", [
    ("ssb_sf1.flight1", Registry),
    ("tpch_sf1.q18_run", Registry),
    ("tpch_sf1.q18_run", SmallQuantities),
])
def test_references_agree_with_the_program(name, registry, x64_restored,
                                            device_path):
    checks, answers = _every_unit(registry(), name, scale=0.01)
    assert checks and all(c["value"] == 0 for c in checks.values()), checks
    assert len(answers) == len(workload.instances(
        registry().traffic(registry().cell(name)["traffic"])))


def test_small_quantities_select_rows_and_cut_at_the_limit(x64_restored):
    _, answers = _every_unit(SmallQuantities(), "tpch_sf1.q18_run", 0.01)
    sizes = [len(got["large_volume_customer"]["o_orderkey"])
             for _, got, _ in answers]
    assert sizes[0] == 100 and 0 < min(sizes)


def test_rotation_gives_every_seed_the_same_units():
    mix = Registry().traffic("q18_run")
    a = [i.params["quantity"] for i, _ in
         zip(workload.rotation(mix, 1), range(8))]
    b = [i.params["quantity"] for i, _ in
         zip(workload.rotation(mix, SEED), range(8))]
    assert sorted(a) == sorted(b) == sorted([312, 313, 314, 315] * 2)
    warm = workload.warmup(Registry().traffic("flight1"), SEED)
    assert sorted(i.name for i in warm) == ["q1_1", "q1_2", "q1_3"]


def _dropped_in(base: Path) -> dict:
    """A configuration, a traffic mix, a per-layer metric and a
    reference written as new files, and the BENCHMARK.json naming them."""
    (base / "configs").mkdir(parents=True)
    (base / "traffic").mkdir()
    (base / "metrics").mkdir()
    (base / "reference" / "tiny").mkdir(parents=True)
    (base / "configs" / "tiny.json").write_text(json.dumps({
        "generator": "tiny.py", "rows": {"t": 5000},
        "host": {"backend": "auto", "max_workers": 1,
                 "jax_enable_x64": False},
        "control": {"dtype": "int8", "why": "test"}}))
    (base / "configs" / "tiny.py").write_text(
        "import numpy as np\n"
        "def make(cfg, seed, scale=1.0):\n"
        "    rng = np.random.default_rng(seed)\n"
        "    n = cfg['rows']['t']\n"
        "    return {'t': {'k': rng.integers(0, 50, n).astype(np.int32),\n"
        "                  'v': rng.integers(0, 1000, n).astype(np.int32)}}\n")
    (base / "traffic" / "tiny_mix.json").write_text(json.dumps({
        "entry": "sql", "unit_metric": "query_s", "cache": False,
        "units": [{"name": "sum_by_k",
                   "sql": "SELECT k, SUM(v) AS s FROM t GROUP BY k"}]}))
    (base / "reference" / "tiny" / "sum_by_k.py").write_text(
        "import numpy as np\nimport plain\n"
        "def answer(tables, params, dtype=np.int32):\n"
        "    k, s = plain.group_sum(tables['t']['k'], tables['t']['v'],"
        " dtype)\n"
        "    return {'k': k, 's': s}\n")
    (base / "metrics" / "tiny_units.py").write_text(
        "def read(ctx):\n    return float(ctx.units)\n")
    return {
        "configs": [{"name": "tiny"}],
        "workloads": [{"name": "tiny.mix", "config": "tiny",
                       "traffic": "tiny_mix", "chips": 1}],
        "end_to_end": [
            {"name": "query_s", "unit": "s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "tiny_units", "unit": "count",
                       "moves": "query_s"}],
    }


def test_new_files_are_found_by_name(tmp_path, x64_restored):
    reg = Registry(base=tmp_path, benchmark=_dropped_in(tmp_path))
    plain = harness.run_cell("tiny.mix", SEED, 0.3, False, reg=reg,
                             t_start=time.time())
    assert plain["correct"], plain["checks"]
    assert set(plain["metrics"]) == {"query_s", "setup_s"}
    traced = harness.run_cell("tiny.mix", SEED, 0.3, True, reg=reg,
                              t_start=time.time())
    assert traced["correct"]
    assert traced["metrics"]["tiny_units"]["value"] == traced["attempted"]
    assert list(traced)[-1] == "checks"


def _no_result(proc) -> bool:
    for line in proc.stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (json.JSONDecodeError, TypeError):
            continue
    return True


def _env() -> dict:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_the_command_refuses_to_run_without_a_chip():
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "ssb_sf1.flight1", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=_env(), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "tpch_sf1.q18_run", "--seed", "3", "--seconds", "1",
         "--trace", "1"], cwd=tmp_path, env=_env(), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)
