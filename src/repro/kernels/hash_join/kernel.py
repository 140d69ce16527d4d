"""Hash-probe Pallas TPU kernel (the hash-join inner loop).

Probes an open-addressing build table — (start, count) slot arrays in
VMEM; ``ref.build_probe_table`` documents the canonical sorted-side
construction, and ``exec.sharded.probe_table`` builds the equivalent
arrival-order variant inline under ``shard_map`` — for a block of
probe lanes at a time. TPU Pallas has no vector gather from VMEM, so
the lookup is a one-hot matmul on the MXU: tile the table over the
minor grid dimension, build the (table slot × probe lane) one-hot of
each probe row against the tile's slots, and multiply the tile's
values through it. A lane's slot falls in exactly one tile (the hash
is perfect over dense codes — see ref.py), so summing the products
across table tiles IS the gather.

Exactness: the table's int32 (start, count) values are split into
their four bytes, eight rows of integers in [0, 255] — exact in bf16,
like the 0/1 one-hot — that the MXU multiplies in one bf16 pass and
accumulates in f32 without rounding (each output lane receives at most
one nonzero byte); the wrapper reassembles the bytes, so the result is
bit-identical to ``ref.hash_probe_ref`` (negative starts included).

Tiling: grid = (n_probe_tiles, n_table_tiles), table minor
(sequential), so each probe tile's output block is revisited across
table steps and carries the accumulated bytes. Probe lanes are laid
out lane-dense as (rows, 128) and read in (block_n // 128, 128)
blocks; the byte table is (8, t_pad) read in (8, block_t) blocks; the
output is (rows, 8, 128). Every block's last two dims are multiples of
(8, 128) or the whole array, and the body needs no lane-to-column
relayout: the table-slot iota runs down the sublanes and each probe
row is broadcast across them. Invalid lanes (slot outside
[0, table_size): NULL/NaN keys, other shards' ranges, padding) match
no slot and emit count 0 — the masked probe.

VMEM at (block_n=1024, block_t=512): slot/mask blocks 2·2·4KB + byte
table 2·8KB + out 2·32KB + one-hot temporaries ≈ 0.5MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.segment_sum.kernel import (LANES, lane_rows,
                                              pallas_call_32, round_up,
                                              row_layout)

_BYTE_ROWS = 8          # 4 start bytes + 4 count bytes


def _byte_table(table_start, table_count, t_pad: int):
    """(8, t_pad) bf16: the little-endian bytes of start then count."""
    def pad(x):
        return jnp.pad(x.astype(jnp.int32), (0, t_pad - x.shape[0]))

    words = jax.lax.bitcast_convert_type(
        jnp.stack([pad(table_start), pad(table_count)]), jnp.uint32)
    shifts = jnp.arange(4, dtype=jnp.uint32) * jnp.uint32(8)
    byts = (words[:, None, :] >> shifts[None, :, None]) & jnp.uint32(0xFF)
    return byts.reshape(_BYTE_ROWS, t_pad).astype(jnp.bfloat16)


def _from_bytes(acc, n: int):
    """(rows, 8, 128) f32 byte accumulators -> (starts, counts) int32."""
    byts = acc.astype(jnp.uint32).transpose(1, 0, 2).reshape(
        2, 4, -1)[:, :, :n]
    shifts = jnp.arange(4, dtype=jnp.uint32) * jnp.uint32(8)
    # the bytes occupy disjoint bits, so their sum is their OR
    words = jnp.sum(byts << shifts[None, :, None], axis=1,
                    dtype=jnp.uint32)
    out = jax.lax.bitcast_convert_type(words, jnp.int32)
    return out[0], out[1]


def _probe_body(slot_ref, *refs, rows: int, block_t: int, masked: bool):
    if masked:
        mask_ref, tab_ref, out_ref = refs
    else:
        tab_ref, out_ref = refs
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # table slot per one-hot sublane (2D iota: TPU rule)
    slot = (jax.lax.broadcasted_iota(jnp.int32, (block_t, LANES), 0)
            + ti * block_t)
    tab = tab_ref[...]                        # (8, block_t)
    one = jnp.ones((), jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    for r in range(rows):
        # probe lane i reads table slot j iff its slot is j (and, for
        # the filter-fused variant, its mask is set — a masked lane
        # never leaves VMEM).
        hit = slot == slot_ref[r:r + 1, :]
        if masked:
            hit = hit & (mask_ref[r:r + 1, :] != 0)
        onehot = jnp.where(hit, one, zero).astype(jnp.bfloat16)
        out_ref[r] += jnp.dot(tab, onehot,
                              preferred_element_type=jnp.float32)


def probe_tiling(n: int, t: int, block_n: int,
                 block_t: int) -> tuple[int, int, int, int]:
    """(rows per block, padded rows, table tile, padded slots) of the
    probe kernels for n probe lanes over t table slots; the grid is
    (rows // rb, t_pad // block_t)."""
    rb, rows = row_layout(n, block_n)
    block_t = round_up(max(1, min(block_t, t)), LANES)
    return rb, rows, block_t, round_up(max(t, 1), block_t)


def _probe(table_start, table_count, probe_slots, probe_mask, *,
           block_n: int, block_t: int, interpret: bool):
    n = probe_slots.shape[0]
    rb, rows, block_t, t_pad = probe_tiling(n, table_start.shape[0],
                                            block_n, block_t)
    args = [lane_rows(probe_slots.astype(jnp.int32), rows, fill=-1)]
    if probe_mask is not None:
        args.append(lane_rows(probe_mask.astype(jnp.int32), rows))
    args.append(_byte_table(table_start, table_count, t_pad))
    row_spec = pl.BlockSpec((rb, LANES), lambda p, ti: (p, 0))
    body = functools.partial(_probe_body, rows=rb, block_t=block_t,
                             masked=probe_mask is not None)
    acc = pallas_call_32(
        body,
        grid=(rows // rb, t_pad // block_t),
        in_specs=[row_spec] * (len(args) - 1) + [
            pl.BlockSpec((_BYTE_ROWS, block_t), lambda p, ti: (0, ti))],
        out_specs=pl.BlockSpec((rb, _BYTE_ROWS, LANES),
                               lambda p, ti: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _BYTE_ROWS, LANES),
                                       jnp.float32),
        interpret=interpret,
    )(*args)
    return _from_bytes(acc, n)


def hash_probe_kernel(table_start, table_count, probe_slots, *,
                      block_n: int = 1024, block_t: int = 512,
                      interpret: bool):
    """probe_slots: (n,) int32; table_start/table_count: (T,) int32.

    Pads n to whole (8·128)-lane blocks (padding lanes get slot -1,
    i.e. masked) and T to a block_t multiple (empty slots carry count
    0). Returns (starts (n,) int32, counts (n,) int32) — bit-identical
    to ``ref.hash_probe_ref``.
    """
    return _probe(table_start, table_count, probe_slots, None,
                  block_n=block_n, block_t=block_t, interpret=interpret)


def masked_hash_probe_kernel(table_start, table_count, probe_slots,
                             probe_mask, *, block_n: int = 1024,
                             block_t: int = 512, interpret: bool):
    """Filter-fused probe: lanes with ``probe_mask == 0`` emit (0, 0).

    Same tiling/padding contract as :func:`hash_probe_kernel` (padding
    lanes get mask 0 as well as slot -1 — doubly dead). Bit-identical
    to ``ref.masked_hash_probe_ref``.
    """
    return _probe(table_start, table_count, probe_slots, probe_mask,
                  block_n=block_n, block_t=block_t, interpret=interpret)
