"""Gradient compression for the slow (inter-pod) all-reduce.

On a multi-pod mesh the ``pod`` axis crosses DCN/optical links an order
of magnitude slower than intra-pod ICI. We therefore do the intra-pod
gradient reduction at full precision (implicit, via pjit), and compress
only the cross-pod stage: int8 block-quantized all-reduce with **error
feedback** (the quantization residual is added to the next step's
gradient), which keeps SGD convergence guarantees (Karimireddy et al.,
error-feedback SGD).

Implemented with ``shard_map`` over the ``pod`` axis. The wire payload is
the int8 tensor + one fp32 scale per 256-block ⇒ ~4x fewer bytes than a
bf16 all-reduce with an fp32 accumulator, on the slowest links. (The
reference implementation below psums the *dequantized* payload so it
runs on any backend; a production TPU build would register an int8
all-reduce — the roofline collective-bytes accounting in
`repro.roofline` models the int8 wire format.)
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def quantize_int8(x: jax.Array, block: int = 256
                  ) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-block int8 quantization of the flattened tensor."""
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array, size: int,
                    shape) -> jax.Array:
    out = (q.astype(jnp.float32) * scale).reshape(-1)[:size]
    return out.reshape(shape)


def _leaf_compressed_psum(g: jax.Array, e: jax.Array, npod: int,
                          block: int) -> tuple[jax.Array, jax.Array]:
    """One leaf inside shard_map: quantize(+error feedback), psum, deq."""
    gf = g.astype(jnp.float32) + e
    q, scale = quantize_int8(gf, block)
    local_deq = dequantize_int8(q.astype(jnp.int32), scale,
                                gf.size, gf.shape)
    new_e = gf - local_deq            # residual kept for next step
    qsum = jax.lax.psum(q.astype(jnp.float32) * scale, "pod")
    deq = qsum.reshape(-1)[:gf.size].reshape(gf.shape) / npod
    return deq.astype(g.dtype), new_e


def compressed_psum_pod(grads: Any, mesh: Mesh, *,
                        error: Any | None = None,
                        block: int = 256) -> tuple[Any, Any]:
    """All-reduce ``grads`` over the ``pod`` axis with int8 compression
    + error feedback. Returns (reduced_grads, new_error).

    ``grads`` leaves must be replicated over `pod` from the intra-pod
    reduction (the pure-DP boundary); other axes are untouched.
    """
    if "pod" not in mesh.axis_names:
        return grads, (error if error is not None else
                       jax.tree.map(lambda g: jnp.zeros(g.shape,
                                                        jnp.float32), grads))

    npod = mesh.shape["pod"]
    flat, treedef = jax.tree.flatten(grads)
    if error is None:
        err_flat = [jnp.zeros(g.shape, jnp.float32) for g in flat]
    else:
        err_flat = treedef.flatten_up_to(error)

    def mapped(*leaves):
        n = len(leaves) // 2
        outs = [_leaf_compressed_psum(g, e, npod, block)
                for g, e in zip(leaves[:n], leaves[n:])]
        return tuple(x for pair in outs for x in pair)

    specs = tuple(P() for _ in flat)
    out = jax.shard_map(mapped, mesh=mesh, in_specs=specs * 2,
                        out_specs=specs * 2, check_vma=False)(
        *flat, *err_flat)
    red = jax.tree.unflatten(treedef, list(out[0::2]))
    new_err = jax.tree.unflatten(treedef, list(out[1::2]))
    return red, new_err
