"""Public hash-probe wrapper: XLA gather, Pallas kernel, or numpy.

Mirrors ``kernels/segment_sum/ops.py``: ``use_pallas=False`` (default)
lowers the probe to the XLA gather oracle (``ref.hash_probe_ref``);
``use_pallas=True`` runs the tiled one-hot kernel (``interpret`` has no
default: the caller decides it from the platform). Both are jit-friendly
and are what ``exec.sharded`` calls *inside* its ``shard_map`` body, so
the per-shard probe inner loop runs on the device that owns the shard.

:func:`hash_probe_np` / :func:`build_probe_table_np` are the numpy
floor: bit-identical to the oracle and importable without JAX, so
:func:`hash_probe` stays callable on JAX-less installs (the sharded
backend itself never reaches that branch — it cannot construct
without JAX; ``kernels.fallback`` degrades its *key coding* upstream
instead — but the differential tests and any host-side caller probe
through the same contract). Slot arrays are int32 by construction
(dense codes are bounded by the row count, which the sharded backend
caps at 2**31), so the probe itself never needs x64.
"""
from __future__ import annotations

import functools

import numpy as np


def hash_probe_np(table_start: np.ndarray, table_count: np.ndarray,
                  probe_slots: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Numpy fallback — same contract as ``ref.hash_probe_ref``."""
    table_size = len(table_start)
    slots = probe_slots.astype(np.int64, copy=False)
    ok = (slots >= 0) & (slots < table_size)
    idx = np.where(ok, slots, 0)
    if table_size == 0:
        z = np.zeros(len(probe_slots), np.int32)
        return z, z.copy()
    starts = np.where(ok, table_start[idx], 0).astype(np.int32)
    counts = np.where(ok, table_count[idx], 0).astype(np.int32)
    return starts, counts


def masked_hash_probe_np(table_start: np.ndarray,
                         table_count: np.ndarray,
                         probe_slots: np.ndarray,
                         probe_mask: np.ndarray
                         ) -> tuple[np.ndarray, np.ndarray]:
    """Numpy fallback — same contract as ``ref.masked_hash_probe_ref``:
    lanes with a falsy mask emit (0, 0)."""
    starts, counts = hash_probe_np(table_start, table_count,
                                   probe_slots)
    keep = probe_mask.astype(bool, copy=False)
    zero = np.int32(0)
    return (np.where(keep, starts, zero).astype(np.int32),
            np.where(keep, counts, zero).astype(np.int32))


def build_probe_table_np(slots_sorted: np.ndarray, table_size: int
                         ) -> tuple[np.ndarray, np.ndarray]:
    """Numpy build — same contract as ``ref.build_probe_table``."""
    s = slots_sorted.astype(np.int64, copy=False)
    in_range = (s >= 0) & (s < table_size)
    counts = np.bincount(s[in_range], minlength=table_size
                         ).astype(np.int32)
    starts = np.concatenate([np.zeros(1, np.int32),
                             np.cumsum(counts)[:-1].astype(np.int32)])
    return starts, counts


@functools.lru_cache(maxsize=None)
def _jitted(use_pallas: bool, block_n: int, block_t: int,
            interpret: bool):
    import jax

    from repro.kernels.hash_join.kernel import hash_probe_kernel
    from repro.kernels.hash_join.ref import hash_probe_ref

    def probe(table_start, table_count, probe_slots):
        if not use_pallas:
            return hash_probe_ref(table_start, table_count, probe_slots)
        return hash_probe_kernel(table_start, table_count, probe_slots,
                                 block_n=block_n, block_t=block_t,
                                 interpret=interpret)

    return jax.jit(probe)


def hash_probe(table_start, table_count, probe_slots, *,
               use_pallas: bool = False, block_n: int = 1024,
               block_t: int = 512, interpret: bool):
    """Per-probe-lane (start, count) into the slot-sorted build array.

    Accepts jax arrays (traced or concrete) or numpy arrays; numpy
    inputs without an importable JAX take :func:`hash_probe_np` — the
    shared fallback path of ``kernels.fallback``.
    """
    if isinstance(probe_slots, np.ndarray):
        try:
            import jax  # noqa: F401
        except ImportError:
            return hash_probe_np(np.asarray(table_start),
                                 np.asarray(table_count), probe_slots)
    return _jitted(use_pallas, block_n, block_t, interpret)(
        table_start, table_count, probe_slots)


@functools.lru_cache(maxsize=None)
def _jitted_masked(use_pallas: bool, block_n: int, block_t: int,
                   interpret: bool):
    import jax

    from repro.kernels.hash_join.kernel import masked_hash_probe_kernel
    from repro.kernels.hash_join.ref import masked_hash_probe_ref

    def probe(table_start, table_count, probe_slots, probe_mask):
        if not use_pallas:
            return masked_hash_probe_ref(table_start, table_count,
                                         probe_slots, probe_mask)
        return masked_hash_probe_kernel(
            table_start, table_count, probe_slots, probe_mask,
            block_n=block_n, block_t=block_t, interpret=interpret)

    return jax.jit(probe)


def masked_hash_probe(table_start, table_count, probe_slots,
                      probe_mask, *, use_pallas: bool = False,
                      block_n: int = 1024, block_t: int = 512,
                      interpret: bool):
    """Filter-fused probe: :func:`hash_probe` with a per-lane keep
    mask; masked-out lanes emit (0, 0). Same dispatch ladder (XLA
    oracle / Pallas kernel / numpy floor)."""
    if isinstance(probe_slots, np.ndarray):
        try:
            import jax  # noqa: F401
        except ImportError:
            return masked_hash_probe_np(
                np.asarray(table_start), np.asarray(table_count),
                probe_slots, np.asarray(probe_mask))
    return _jitted_masked(use_pallas, block_n, block_t, interpret)(
        table_start, table_count, probe_slots, probe_mask)
