"""JAX execution backend: segment-sum aggregation on the accelerator.

Inherits the vectorized backend's join/filter/concat and key
factorization (host-side, numpy) — including the filter-fused
``masked_hash_join`` (key-validity ANDing), so the optimizer's
probe-fusion rewrite benefits this backend with no code here — and
overrides only the aggregation inner loops: per-group SUM/MEAN run
through :func:`repro.kernels.segment_sum.ops.masked_segment_sum` and
MIN/MAX through :func:`~repro.kernels.segment_sum.ops.
masked_segment_reduce` — XLA segment ops by default, or the Pallas
kernels when constructed with ``use_pallas=True``
(env ``REPRO_SEGSUM_PALLAS=1``).

Exactness contract with the ``reference`` oracle:

- integer dtypes are bit-exact (integer addition is associative, even
  under wraparound), so the differential suite holds bit-for-bit;
- float sums are exact up to summation order (device reductions are
  not sequential) — tests compare float sums with tolerance;
- dtypes the device cannot represent faithfully fall back to the
  vectorized numpy path: object columns always, and 64-bit numerics
  whenever ``jax_enable_x64`` is off (the default — silently truncating
  int64 to int32 would be a correctness bug, not a speedup).
"""
from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp

from repro.exec.base import fill_value
from repro.exec.vectorized import _NOOP_CTX, VectorizedBackend
from repro.kernels import fallback
from repro.kernels.segment_sum.ops import (masked_segment_reduce,
                                           masked_segment_sum)
from repro.obs import get_recorder

__all__ = ["JaxBackend"]


class JaxBackend(VectorizedBackend):
    name = "jax"

    def __init__(self, *, use_pallas: bool | None = None,
                 interpret: bool | None = None):
        if use_pallas is None:
            use_pallas = os.environ.get("REPRO_SEGSUM_PALLAS") == "1"
        if interpret is None:
            # CPU containers interpret; real TPUs compile.
            interpret = jax.default_backend() == "cpu"
        self.use_pallas = use_pallas
        self.interpret = interpret

    def cache_token(self) -> str:
        # device reductions regroup float SUMs (the documented
        # carve-out), and the Pallas kernel tiles differently from XLA
        # scatter-add — both are summation-order state a cache hit must
        # not survive.
        suffix = "+pallas" if self.use_pallas else ""
        return f"{self.name}{suffix}[devices={len(jax.devices())}]"

    def _supported(self, dtype: np.dtype) -> bool:
        """Route through the shared numpy-fallback plumbing
        (kernels.fallback): a 64-bit dtype that cannot lower because
        ``jax_enable_x64`` is off warns ONCE naming the env fix —
        degraded perf used to be silent (the whole op quietly ran the
        numpy path)."""
        if not fallback.device_supports_dtype(dtype):
            if fallback.x64_is_the_fix(dtype):
                fallback.warn_numpy_fallback(
                    f"{self.name}.group_by_agg", dtype)
            return False
        return True

    @staticmethod
    def _segment_ids(order: np.ndarray, bounds: np.ndarray,
                     grp_order: np.ndarray, n_groups: int,
                     n: int) -> np.ndarray:
        """Per-row int32 segment ids in output (first-appearance) order,
        from the group-run structure the vectorized base already
        computed; a traced run records them as a ``key_codes`` span over
        the one group id."""
        rec = get_recorder()
        with (rec.span("key_codes", rows=n, keys=1, object_keys=0)
              if rec.enabled else _NOOP_CTX):
            run_lengths = np.diff(np.r_[bounds, n])
            inv_code = np.empty(n, dtype=np.int64)
            inv_code[order] = np.repeat(np.arange(n_groups), run_lengths)
            rank = np.empty(n_groups, dtype=np.int64)
            rank[grp_order] = np.arange(n_groups)
            return rank[inv_code].astype(np.int32)

    @staticmethod
    def _kernel_span(rec, op: str, values: np.ndarray, gid: np.ndarray,
                     ok: np.ndarray, n_groups: int):
        """The ``kernel`` span of one device call, from the host-to-device
        copies of values, segment ids and mask to the end of the fetch
        (the caller sets ``d2h_bytes``); a no-op context when off."""
        if not rec.enabled:
            return _NOOP_CTX
        return rec.span("kernel", op=op, rows=len(values),
                        segments=n_groups,
                        h2d_bytes=values.nbytes + gid.nbytes + ok.nbytes)

    def _aggregate(self, values: np.ndarray, ok: np.ndarray,
                   order: np.ndarray, bounds: np.ndarray,
                   grp_order: np.ndarray, n_groups: int
                   ) -> tuple[np.ndarray, np.ndarray]:
        if n_groups == 0 or not self._supported(values.dtype):
            return super()._aggregate(values, ok, order, bounds,
                                      grp_order, n_groups)
        gid = self._segment_ids(order, bounds, grp_order, n_groups,
                                len(values))
        with self._kernel_span(get_recorder(), "jax.segment_sum", values,
                               gid, ok, n_groups) as sp:
            sums, counts = masked_segment_sum(
                jnp.asarray(values), jnp.asarray(gid), jnp.asarray(ok),
                n_groups, use_pallas=self.use_pallas,
                interpret=self.interpret)
            sums, counts = np.asarray(sums), np.asarray(counts)
            if sp is not None:
                sp.set(d2h_bytes=sums.nbytes + counts.nbytes)
        # empty segments already hold 0 == the canonical numeric fill
        return sums.astype(values.dtype, copy=False), counts > 0

    def _agg_minmax(self, fn: str, values: np.ndarray, ok: np.ndarray,
                    order: np.ndarray, bounds: np.ndarray,
                    grp_order: np.ndarray, n_groups: int
                    ) -> tuple[np.ndarray, np.ndarray]:
        vdt = values.dtype
        if (n_groups == 0 or vdt == object or vdt.kind not in "fiu"
                or not self._supported(vdt)):
            return super()._agg_minmax(fn, values, ok, order, bounds,
                                       grp_order, n_groups)
        gid = self._segment_ids(order, bounds, grp_order, n_groups,
                                len(values))
        with self._kernel_span(get_recorder(), "jax.segment_reduce",
                               values, gid, ok, n_groups) as sp:
            red, counts = masked_segment_reduce(
                jnp.asarray(values), jnp.asarray(gid), jnp.asarray(ok),
                n_groups, op=fn, use_pallas=self.use_pallas,
                interpret=self.interpret)
            red, counts = np.array(red), np.asarray(counts)
            if sp is not None:
                sp.set(d2h_bytes=red.nbytes + counts.nbytes)
        # empty segments hold the reduce identity (±inf / dtype
        # extremes), not the canonical fill — rewrite them.
        red = red.astype(vdt, copy=False)
        has = counts > 0
        red[~has] = fill_value(vdt)
        return red, has
