"""Compiles of the data plane's device programs for a described TPU v5e.

Interpret mode cannot see what the TPU compiler refuses — illegal block
shapes, relayouts Mosaic cannot do, too much VMEM. These tests compile
with ``interpret=False`` for a described ``v5e:2x2`` topology, no chip
attached: the four Pallas kernels at real widths, and the 4-device
sharded exchange-and-probe and partial aggregation with the
``all_to_all`` in their HLO. A compile that passes is not a chip run.

The topology is described inside a module fixture (never at import):
only the worker that runs these tests loads the TPU compiler. Keep
every such compile in this one file.
"""
import importlib.util

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.hash_join.kernel import (  # noqa: E402
    hash_probe_kernel, masked_hash_probe_kernel)
from repro.kernels.segment_sum.kernel import (  # noqa: E402
    masked_segment_reduce_kernel, masked_segment_sum_kernel)

N_ROWS = 1 << 20          # rows / probe lanes
N_SEGMENTS = 4096
N_SLOTS = 65536           # probe table slots
NDEV = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # only an install without the TPU plug-in may skip; any other
    # refusal to describe the chip is a failure of this guard.
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler here")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip: keep it off.
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _kernel_case(name):
    i32, f32 = jnp.int32, jnp.float32
    rows = ((N_ROWS,), i32), ((N_ROWS,), i32), ((N_ROWS,), jnp.bool_)
    table = ((N_SLOTS,), i32), ((N_SLOTS,), i32)
    probes = ((N_ROWS,), i32)
    if name == "masked_segment_sum":
        return (lambda v, s, m: masked_segment_sum_kernel(
            v, s, m, N_SEGMENTS, interpret=False)), rows
    if name == "masked_segment_reduce":
        return (lambda v, s, m: masked_segment_reduce_kernel(
            v, s, m, N_SEGMENTS, "min", interpret=False)), (
            ((N_ROWS,), f32),) + rows[1:]
    if name == "hash_probe":
        return (lambda ts, tc, p: hash_probe_kernel(
            ts, tc, p, interpret=False)), table + (probes,)
    return (lambda ts, tc, p, m: masked_hash_probe_kernel(
        ts, tc, p, m, interpret=False)), table + (probes, probes)


@pytest.mark.parametrize("name", ["masked_segment_sum",
                                  "masked_segment_reduce", "hash_probe",
                                  "masked_hash_probe"])
def test_kernel_compiles_for_v5e(topo, name):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def described_mesh(topo, monkeypatch):
    """Steer the sharded backend's mesh onto the described devices."""
    from jax.sharding import Mesh

    import repro.exec.sharded as sharded

    mesh = Mesh(np.array(topo.devices[:NDEV]), ("shard",))
    monkeypatch.setattr(sharded, "_get_mesh", lambda ndev: mesh)
    sharded._probe_fn.cache_clear()
    sharded._partial_agg_fn.cache_clear()
    yield mesh
    sharded._probe_fn.cache_clear()
    sharded._partial_agg_fn.cache_clear()


def test_sharded_exchange_probe_compiles_for_4_chips(described_mesh):
    """Table mode with the Pallas probe: all_to_all + kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.exec.sharded import _probe_fn

    cap = N_ROWS // (NDEV * NDEV)
    fn = _probe_fn(NDEV, cap, cap, N_SLOTS, np.dtype(np.int32).str,
                   True, False)
    slab = jax.ShapeDtypeStruct(
        (NDEV, NDEV, cap), jnp.int32,
        sharding=NamedSharding(described_mesh, P("shard", None, None)))
    with jax.enable_x64(True):
        text = fn.lower(slab, slab).compile().as_text()
    assert "all-to-all" in text
    assert "tpu_custom_call" in text


def test_sharded_partial_agg_compiles_for_4_chips(described_mesh):
    """Pre-exchange partial aggregation through the Pallas segment
    kernels: all_to_all of the reduced partials + kernels."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.exec.sharded import _partial_agg_fn

    chunk = N_ROWS // NDEV
    fn = _partial_agg_fn(NDEV, N_SEGMENTS // NDEV,
                         (("<i4", ("max", "min", "sum")),), True, False)
    spec = NamedSharding(described_mesh, P("shard", None))
    args = [jax.ShapeDtypeStruct((NDEV, chunk), dt, sharding=spec)
            for dt in (jnp.int32, jnp.int32, jnp.bool_)]
    with jax.enable_x64(True):
        text = fn.lower(*args).compile().as_text()
    assert "all-to-all" in text
    assert "tpu_custom_call" in text


def test_sharded_partial_agg_takes_segment_ops_on_4_chips(described_mesh):
    """Without ``use_pallas`` the TPU mesh reduces each shard with XLA
    segment ops: the all_to_all of the partials and no ``while`` — the
    packed strategy's binary search over every slot is one."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.exec.sharded import _agg_strategies, _partial_agg_fn

    sig = (("<i4", ("sum",)),)
    assert _agg_strategies(NDEV, sig, False) == ("segment",)
    chunk = N_ROWS // NDEV
    fn = _partial_agg_fn(NDEV, N_SEGMENTS // NDEV, sig, False, False)
    spec = NamedSharding(described_mesh, P("shard", None))
    args = [jax.ShapeDtypeStruct((NDEV, chunk), dt, sharding=spec)
            for dt in (jnp.int32, jnp.int32, jnp.bool_)]
    with jax.enable_x64(True):
        text = fn.lower(*args).compile().as_text()
    assert "all-to-all" in text
    assert not re.search(r"\bwhile\(", text)
