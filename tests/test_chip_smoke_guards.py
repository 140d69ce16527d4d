"""Guards around the chip entry points that a CPU host can check.

- ``chip_smoke.py`` refuses to run without a TPU: non-zero exit, and
  its last line is not the ``"ok": true`` contract line;
- the persistent compilation cache follows ``JAX_COMPILATION_CACHE_DIR``
  when it is set, and otherwise one fixed path inside the checkout;
- no data-plane kernel entry point defaults ``interpret``: the caller
  decides it from the platform (``exec.jax_backend``), so a forgotten
  argument cannot run the interpreter on the chip;
- the smoke's phases, with all their checks (fingerprints against
  ``vectorized``, degradation and downgrade detection, the exchanges'
  landing on every mesh device), rehearse on the CPU at a small size,
  each in a child process: the one-chip phase on one device, the
  four-chip phase on a forced 4-device host mesh.
"""
import inspect
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0, r.stdout
    lines = r.stdout.strip().splitlines()
    assert lines, r.stderr
    assert '"ok": true' not in lines[-1]
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir_rule(env_dir, tmp_path, monkeypatch):
    jax = pytest.importorskip("jax")
    from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    want = str(tmp_path / "cache")
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(CHECKOUT_CACHE_DIR)
    try:
        assert enable_compile_cache() == want
        if env_dir:
            # JAX reads the variable itself; nothing is set in code
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def _interpret_entry_points():
    pytest.importorskip("jax")
    from repro.kernels.hash_join import kernel as hk, ops as ho
    from repro.kernels.segment_sum import kernel as sk, ops as so

    found = []
    for mod in (sk, so, hk, ho):
        for name, fn in vars(mod).items():
            if name.startswith("_") or not callable(fn):
                continue
            try:
                params = inspect.signature(fn).parameters
            except (TypeError, ValueError):
                continue
            if "interpret" in params:
                found.append((f"{mod.__name__}.{name}",
                              params["interpret"]))
    return found


def test_no_kernel_entry_point_defaults_interpret():
    found = _interpret_entry_points()
    names = {n.rsplit(".", 1)[1] for n, _ in found}
    assert names >= {"masked_segment_sum_kernel",
                     "masked_segment_reduce_kernel", "masked_segment_sum",
                     "masked_segment_reduce", "hash_probe_kernel",
                     "masked_hash_probe_kernel", "hash_probe",
                     "masked_hash_probe"}, json.dumps(sorted(names))
    defaulted = [n for n, p in found if p.default is not inspect.Parameter.empty]
    assert defaulted == []


_REHEARSAL_SIZES = "n_fact=20000, n_users=3000, n_items=4000"
_REHEARSAL_ENV = {
    # auto's row thresholds, scaled down with the data so that its
    # device routes still run
    "one_chip": {"REPRO_AUTO_DEVICE_ROWS": "1000"},
    "four_chips": {"REPRO_AUTO_SHARD_ROWS": "1000", "XLA_FLAGS":
                   "--xla_force_host_platform_device_count=4"},
}


@pytest.mark.parametrize("phase", sorted(_REHEARSAL_ENV))
def test_smoke_phase_rehearses_on_cpu(phase):
    script = (f"import chip_smoke as cs; "
              f"cs.{phase}(0, {_REHEARSAL_SIZES}); print('REHEARSED')")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"), **_REHEARSAL_ENV[phase])
    r = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.splitlines()[-1] == "REHEARSED"
    if phase == "four_chips":
        for mode in ("table", "hash", "agg"):
            assert f"{mode} exchange, rows per shard" in r.stdout
