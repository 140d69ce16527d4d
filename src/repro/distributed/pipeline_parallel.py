"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

Optional at the assigned scales (TP×FSDP fits every arch on the v5e
mesh), but required posture for 1000+ nodes: stages are mapped onto the
``pipe`` axis with ``shard_map``; microbatches stream through stages via
``jax.lax.ppermute`` (neighbor ICI transfers only — no all-gathers), with
the standard (S−1+M)/M bubble.

The stage function is any ``x -> x`` block stack; weights for stage i
live only on pipe rank i (stacked leading `pipe` dim, sharded).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable[[Any, jax.Array], jax.Array],
                     stage_params: Any, x: jax.Array, *, mesh: Mesh,
                     num_microbatches: int) -> jax.Array:
    """Run x (B, ...) through S pipeline stages with M microbatches.

    ``stage_params`` leaves have leading dim S sharded over ``pipe``.
    Returns the final-stage output for the full batch.
    """
    S = mesh.shape["pipe"]
    M = num_microbatches
    B = x.shape[0]
    assert B % M == 0

    def body(params, xin):
        # params: this stage's slice (leading dim 1); xin: (B, ...)
        rank = jax.lax.axis_index("pipe")
        p = jax.tree.map(lambda a: a[0], params)
        mb = xin.reshape(M, B // M, *xin.shape[1:])

        steps = M + S - 1
        buf = jnp.zeros_like(mb[0])
        out = jnp.zeros_like(mb)

        def step(carry, t):
            buf, out = carry
            # stage 0 injects microbatch t (if any); others use received
            inject = jax.lax.dynamic_index_in_dim(
                mb, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            cur = jnp.where(rank == 0, inject, buf)
            live = (t - rank >= 0) & (t - rank < M)
            y = stage_fn(p, cur)
            y = jnp.where(live, y, buf)
            # last stage collects its finished microbatch
            done_idx = jnp.clip(t - (S - 1), 0, M - 1)
            collect = (rank == S - 1) & (t - (S - 1) >= 0) & \
                (t - (S - 1) < M)
            out = jax.lax.cond(
                collect,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, done_idx, 0),
                lambda o: o, out)
            # shift activations to the next stage
            nxt = jax.lax.ppermute(
                y, "pipe", [(i, (i + 1) % S) for i in range(S)])
            return (nxt, out), None

        (buf, out), _ = jax.lax.scan(step, (buf, out),
                                     jnp.arange(steps))
        # broadcast final outputs from the last stage to all ranks
        out = jax.lax.psum(
            jnp.where(rank == S - 1, out, jnp.zeros_like(out)), "pipe")
        return out.reshape(B, *x.shape[1:])

    other = tuple(a for a in mesh.axis_names if a != "pipe")
    pspec = jax.tree.map(lambda _: P("pipe"), stage_params)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False,
    )(stage_params, x)
