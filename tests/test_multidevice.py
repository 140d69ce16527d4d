"""Multi-device semantics on 8 forced host devices (subprocess-isolated:
the main pytest process must keep seeing 1 CPU device).

These are the strongest CPU-side checks of large-scale runnability:
numerical EQUALITY between the sharded and single-device programs, real
elastic rescaling across mesh shapes, and a real pipeline-parallel run.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(body: str, n: int = 8, timeout: int = 420) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n}"
        import jax
        assert jax.device_count() == {n}, jax.devices()
        import numpy as np
        import jax.numpy as jnp
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    """A TP+DP train step on a (2,2,2) pod/data/model mesh produces the
    same loss and parameters as the unsharded single-device step."""
    run_with_devices("""
        from repro.configs import get_smoke_config
        from repro.distributed.sharding import make_rules, use_rules
        from repro.launch.specs import safe_params_sharding, _with_rules
        from repro.models import model as MDL
        from repro.training.optimizer import AdamWConfig, adamw_init
        from repro.training.train_loop import TrainConfig, make_train_step
        from jax.sharding import NamedSharding

        cfg = get_smoke_config("phi4_mini_3b")
        params = MDL.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, 32)).astype(np.int32))
        tc = TrainConfig(remat=None, block_q=16, block_kv=16)
        step = make_train_step(cfg, AdamWConfig(lr=1e-3), tc)

        # reference: single-device jit
        p1, o1, m1 = jax.jit(step)(params, opt, toks, toks)

        # sharded: (pod,data,model) = (2,2,2)
        mesh = jax.make_mesh(
            (2, 2, 2), ("pod", "data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 3)
        rules = make_rules("train", mesh, seq_parallel=True)
        with use_rules(rules):
            psh = safe_params_sharding(params, mesh, rules)
            osh = safe_params_sharding(opt, mesh, rules)
            tsh = NamedSharding(mesh, rules.resolve("batch", None))
        with mesh:
            jitted = jax.jit(_with_rules(step, rules),
                             in_shardings=(psh, osh, tsh, tsh))
            p2, o2, m2 = jitted(params, opt, toks, toks)

        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, \\
            (float(m1["loss"]), float(m2["loss"]))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=5e-2, atol=5e-3)
        print("SHARDED_MATCHES_SINGLE ok")
    """)


def test_elastic_rescale_8_to_4_to_2():
    """Restore the same logical params onto shrinking meshes (losing a
    'pod'), continuing with identical forward results — the paper's
    partial-vs-total-failure upgrade applied to cluster capacity."""
    run_with_devices("""
        from repro.configs import get_smoke_config
        from repro.distributed.elastic import reshard
        from repro.distributed.sharding import make_rules
        from repro.models import model as MDL

        cfg = get_smoke_config("xlstm_350m")
        params = MDL.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.zeros((4, 16), jnp.int32)
        ref, _ = MDL.forward(params, cfg, toks)
        ref = np.asarray(ref, np.float32)

        host = jax.tree.map(np.asarray, params)
        for shape, axes in (((2, 2, 2), ("pod", "data", "model")),
                            ((2, 2), ("data", "model")),
                            ((2, 1), ("data", "model"))):
            ndev = int(np.prod(shape))
            devs = np.array(jax.devices()[:ndev]).reshape(shape)
            mesh = jax.sharding.Mesh(devs, axes)
            rules = make_rules("train", mesh)
            placed = reshard(host, mesh, rules)
            with mesh:
                out, _ = jax.jit(lambda p, t: MDL.forward(p, cfg, t))(
                    placed, toks)
            np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                       rtol=2e-2, atol=2e-2)
            print(f"RESHARD {shape} ok")
    """)


def test_pipeline_parallel_two_stages():
    """GPipe-style pipeline over a real 2-device 'pipe' axis equals the
    sequential composition of the stages."""
    run_with_devices("""
        from repro.distributed.pipeline_parallel import pipeline_forward

        S, M, B, D = 2, 4, 8, 16
        ws = jax.random.normal(jax.random.PRNGKey(0), (S, D, D)) * 0.3

        def stage(p, x):
            return jnp.tanh(x @ p["w"])

        x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
        want = x
        for s in range(S):
            want = jnp.tanh(want @ ws[s])

        mesh = jax.make_mesh((2,), ("pipe",))
        got = pipeline_forward(stage, {"w": ws}, x, mesh=mesh,
                               num_microbatches=M)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        print("PIPELINE ok")
    """, n=2)


def test_grad_compression_real_pod_axis():
    """int8+error-feedback psum over a REAL 2-pod axis: the compressed
    all-reduce of identical per-pod grads equals the plain mean."""
    run_with_devices("""
        from repro.distributed.grad_compression import compressed_psum_pod

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        g = {"w": jnp.asarray(np.random.default_rng(0).normal(
            size=(512,)).astype(np.float32))}
        red, err = compressed_psum_pod(g, mesh)
        np.testing.assert_allclose(np.asarray(red["w"]),
                                   np.asarray(g["w"]),
                                   rtol=0, atol=float(
                                       jnp.max(jnp.abs(g["w"]))) / 100)
        print("COMPRESSED_PSUM ok")
    """)
