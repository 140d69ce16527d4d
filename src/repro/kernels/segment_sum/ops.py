"""Jit'd public wrappers: XLA segment ops or the Pallas kernels.

The wrappers hold the one routing rule between them: the Pallas kernels
take values of at most 32 bits, so 64-bit values (x64 on) take the XLA
segment ops even when ``use_pallas`` is set.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.segment_sum.kernel import (masked_segment_reduce_kernel,
                                              masked_segment_sum_kernel)
from repro.kernels.segment_sum.ref import (masked_segment_reduce_ref,
                                           masked_segment_sum_ref)


def _pallas(use_pallas: bool, values) -> bool:
    return use_pallas and values.dtype.itemsize <= 4


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "use_pallas", "block_n", "block_s", "interpret"))
def masked_segment_sum(values, segment_ids, valid, num_segments: int, *,
                       use_pallas: bool = False,
                       block_n: int = 1024, block_s: int = 512,
                       interpret: bool):
    """Per-segment SUM over valid lanes + valid-lane counts.

    ``use_pallas=False`` (default) lowers to XLA's scatter-add
    (``jax.ops.segment_sum``); ``use_pallas=True`` runs the tiled
    Pallas kernel for values of at most 32 bits. ``interpret`` has
    no default: the caller decides it from the platform (the execution
    backends do, in ``exec.jax_backend``). Both return (sums
    values.dtype, counts int32).
    """
    if not _pallas(use_pallas, values):
        return masked_segment_sum_ref(values, segment_ids, valid,
                                      num_segments)
    return masked_segment_sum_kernel(
        values, segment_ids, valid, num_segments,
        block_n=block_n, block_s=block_s, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "op", "use_pallas", "block_n", "block_s",
    "interpret"))
def masked_segment_reduce(values, segment_ids, valid, num_segments: int,
                          *, op: str, use_pallas: bool = False,
                          block_n: int = 1024, block_s: int = 512,
                          interpret: bool):
    """Per-segment MIN/MAX over valid lanes + valid-lane counts.

    ``op`` is ``"min"`` or ``"max"``; NaN in a valid float lane poisons
    its segment, empty segments return the identity (NULL upstream).
    Same XLA-vs-Pallas switch as :func:`masked_segment_sum`.
    """
    if op not in ("min", "max"):
        raise ValueError(f"unknown segment reduce op: {op!r}")
    if not _pallas(use_pallas, values):
        return masked_segment_reduce_ref(values, segment_ids, valid,
                                         num_segments, op)
    return masked_segment_reduce_kernel(
        values, segment_ids, valid, num_segments, op,
        block_n=block_n, block_s=block_s, interpret=interpret)
