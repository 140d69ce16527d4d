"""Masked segment-sum Pallas TPU kernel (the GROUP BY SUM hot loop).

Tiling: grid = (n_seg_tiles, n_row_tiles) with the *row* dimension
minor (sequential), so each segment tile's accumulator lives in the
revisited output block across row steps — the same carried-accumulator
pattern as the flash-attention kernel's n_kv dimension.

Layout (what the TPU lowering accepts at any row count): the n input
lanes are laid out lane-dense as a ``(rows, 128)`` array and read in
``(block_n // 128, 128)`` blocks; the outputs are ``(s_pad, 128)``
accumulators read in ``(block_s, 128)`` blocks, segments on sublanes.
Every block's last two dims are then multiples of (8, 128) or the
whole array. Per 128-lane input row the body compares a sublane iota
of segment ids against the row (a sublane broadcast — no lane-to-column
relayout) and folds the one-hot contribution into the accumulator
elementwise; the final 128-way lane reduction is one XLA reduce in the
wrapper. No MXU matmul, so integer sums stay exact (integer addition is
associative even under wraparound; only float sums are order-sensitive,
covered by tolerance in tests). Invalid lanes and row padding fall out
of the same one-hot mask.

Values narrower than 32 bits are widened for the kernel (an int32
accumulator truncated back to int8/int16 is the same wrapping sum;
min/max are unchanged by widening).

VMEM at (block_n=1024, block_s=512), f32: in blocks 3·2·4KB + out
accumulators 2·2·256KB + one-hot temporaries ≈ 2MB « the 16MB scoped
default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.segment_sum.ref import reduce_identity

LANES = 128
SUBLANES = 8


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def row_layout(n: int, block_n: int) -> tuple[int, int]:
    """(rows per block, padded row count) for n lanes laid out as
    (rows, 128): a block is the whole array or a multiple of 8 rows."""
    rows = max(1, -(-n // LANES))
    rb = max(1, block_n // LANES)
    if rows <= rb:
        return rows, rows
    rb = round_up(rb, SUBLANES)
    return rb, round_up(rows, rb)


def lane_rows(x, rows: int, fill=0):
    """Pad a 1-D array to rows*128 lanes and lay it out (rows, 128)."""
    pad = rows * LANES - x.shape[0]
    if pad:
        x = jnp.pad(x, (0, pad), constant_values=fill)
    return x.reshape(rows, LANES)


def pallas_call_32(body, **kw):
    """``pl.pallas_call`` traced with x64 off. The kernels are 32-bit by
    construction; under an ambient x64 scope (the sharded backend's)
    Python-int literals in the index maps would trace as int64, which
    the TPU lowering refuses."""
    def run(*args):
        with jax.enable_x64(False):
            return pl.pallas_call(body, **kw)(*args)
    return run


def _kernel_dtype(dtype) -> np.dtype:
    """32-bit twin of a <= 32-bit value dtype (the kernel's lane type)."""
    dtype = np.dtype(dtype)
    if dtype.itemsize > 4:
        raise TypeError(
            f"64-bit values ({dtype}) never reach the Pallas segment "
            f"kernels; the ops wrappers route them to the XLA segment "
            f"ops")
    if dtype.kind == "f":
        return np.dtype(np.float32)
    if dtype.kind == "u":
        return np.dtype(np.uint32)
    return np.dtype(np.int32)


def segment_tiling(n: int, num_segments: int, block_n: int,
                   block_s: int) -> tuple[int, int, int, int]:
    """(rows per block, padded rows, segment tile, padded segments) of
    the segment kernels for n lanes; the grid is (s_pad // block_s,
    rows // rb)."""
    rb, rows = row_layout(n, block_n)
    block_s = round_up(max(1, min(block_s, num_segments)), SUBLANES)
    return rb, rows, block_s, round_up(max(num_segments, 1), block_s)


def _layout(values, segment_ids, valid, num_segments: int,
            block_n: int, block_s: int):
    """Shared tiling of both segment kernels: lane-dense (rows, 128)
    inputs (padding lanes are invalid) and a sublane-aligned segment
    tile."""
    rb, rows, block_s, s_pad = segment_tiling(
        values.shape[0], num_segments, block_n, block_s)
    kdt = _kernel_dtype(values.dtype)
    v2 = lane_rows(values.astype(kdt), rows)
    id2 = lane_rows(segment_ids.astype(jnp.int32), rows)
    m2 = lane_rows(valid.astype(jnp.int32), rows)   # padding: masked
    grid = (s_pad // block_s, rows // rb)
    in_specs = [pl.BlockSpec((rb, LANES), lambda s, r: (r, 0))] * 3
    out_spec = pl.BlockSpec((block_s, LANES), lambda s, r: (s, 0))
    return kdt, rb, block_s, s_pad, grid, in_specs, out_spec, (v2, id2, m2)


def _segsum_body(v_ref, id_ref, m_ref, sum_ref, cnt_ref, *,
                 rows: int, block_s: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # global segment id per accumulator sublane (2D iota: TPU rule)
    seg = (jax.lax.broadcasted_iota(jnp.int32, (block_s, LANES), 0)
           + pl.program_id(0) * block_s)
    zero = jnp.zeros((), sum_ref.dtype)
    acc = sum_ref[...]
    cnt = cnt_ref[...]
    for r in range(rows):
        # lane i of this row contributes to sublane j iff it is valid
        # and its segment id is seg[j]: a sublane broadcast of the row.
        hit = (seg == id_ref[r:r + 1, :]) & (m_ref[r:r + 1, :] != 0)
        acc = acc + jnp.where(hit, v_ref[r:r + 1, :], zero)
        cnt = cnt + hit.astype(jnp.int32)
    sum_ref[...] = acc
    cnt_ref[...] = cnt


def masked_segment_sum_kernel(values, segment_ids, valid,
                              num_segments: int, *,
                              block_n: int = 1024, block_s: int = 512,
                              interpret: bool):
    """values: (n,) <= 32-bit; segment_ids: (n,) int32; valid: (n,) bool.

    Pads n to whole (8·128)-lane blocks (padding lanes masked invalid)
    and num_segments to a block_s multiple (sliced off on return).
    Returns (sums (num_segments,) values.dtype, counts (num_segments,)
    int32).
    """
    kdt, rb, block_s, s_pad, grid, in_specs, out_spec, args = _layout(
        values, segment_ids, valid, num_segments, block_n, block_s)
    body = functools.partial(_segsum_body, rows=rb, block_s=block_s)
    sums, counts = pallas_call_32(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, LANES), kdt),
            jax.ShapeDtypeStruct((s_pad, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    # dtype pinned: the 128-way lane fold wraps in the accumulator
    # dtype (an ambient x64 scope would otherwise promote it).
    sums = jnp.sum(sums, axis=1, dtype=kdt)[:num_segments]
    counts = jnp.sum(counts, axis=1, dtype=jnp.int32)[:num_segments]
    return sums.astype(values.dtype), counts


def _segreduce_body(v_ref, id_ref, m_ref, red_ref, cnt_ref, nan_ref, *,
                    rows: int, block_s: int, op: str, ident):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        red_ref[...] = jnp.full_like(red_ref, ident)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        nan_ref[...] = jnp.zeros_like(nan_ref)

    seg = (jax.lax.broadcasted_iota(jnp.int32, (block_s, LANES), 0)
           + pl.program_id(0) * block_s)
    idv = jnp.asarray(ident, red_ref.dtype)
    fold = jnp.minimum if op == "min" else jnp.maximum
    red = red_ref[...]
    cnt = cnt_ref[...]
    nan = nan_ref[...]
    for r in range(rows):
        vals = v_ref[r:r + 1, :]
        isnan = vals != vals                 # all-False for int dtypes
        hit = (seg == id_ref[r:r + 1, :]) & (m_ref[r:r + 1, :] != 0)
        # NaN lanes are parked at the identity here; the wrapper
        # re-poisons their segments from the NaN counts so min/max
        # stay a clean VPU fold.
        red = fold(red, jnp.where(hit & ~isnan, vals, idv))
        cnt = cnt + hit.astype(jnp.int32)
        nan = nan + (hit & isnan).astype(jnp.int32)
    red_ref[...] = red
    cnt_ref[...] = cnt
    nan_ref[...] = nan


def masked_segment_reduce_kernel(values, segment_ids, valid,
                                 num_segments: int, op: str, *,
                                 block_n: int = 1024, block_s: int = 512,
                                 interpret: bool):
    """Tiled Pallas masked segment MIN/MAX — segment-sum's tiling, an
    identity-initialised carried accumulator, and a NaN-count output so
    float NaN propagation matches the host backends bit-for-bit.

    Returns (reduced (num_segments,) values.dtype, counts int32).
    """
    kdt, rb, block_s, s_pad, grid, in_specs, out_spec, args = _layout(
        values, segment_ids, valid, num_segments, block_n, block_s)
    ident = reduce_identity(kdt, op)
    body = functools.partial(_segreduce_body, rows=rb, block_s=block_s,
                             op=op, ident=ident)
    red, counts, nans = pallas_call_32(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, LANES), kdt),
            jax.ShapeDtypeStruct((s_pad, LANES), jnp.int32),
            jax.ShapeDtypeStruct((s_pad, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    fold = jnp.min if op == "min" else jnp.max
    red = fold(red, axis=1)[:num_segments]
    counts = jnp.sum(counts, axis=1, dtype=jnp.int32)[:num_segments]
    if jnp.issubdtype(kdt, jnp.floating):
        nans = jnp.sum(nans, axis=1, dtype=jnp.int32)[:num_segments]
        red = jnp.where(nans > 0, jnp.asarray(jnp.nan, kdt), red)
    # empty segments hold the widened identity: map them to the value
    # dtype's own identity (what the ref oracle returns).
    red = jnp.where(counts > 0, red.astype(values.dtype),
                    jnp.asarray(reduce_identity(values.dtype, op),
                                values.dtype))
    return red, counts
