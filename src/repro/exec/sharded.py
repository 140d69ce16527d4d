"""Shard-aware distributed hash join across the JAX device mesh.

Extends the ``jax`` backend (which already runs aggregation through
``kernels/segment_sum``) with a mesh-parallel ``hash_join``: the join
inner loop — the dominant cost of every pipeline wave — is partitioned
over a 1-D ``("shard",)`` mesh so each device owns one key range and
probes only its cache-resident slice, instead of the vectorized
backend's whole-table binary search whose every step misses cache at
1e6+ rows. DESIGN.md §10.

Division of labor (host steps are numpy, device steps run under
``shard_map``):

1. **Key coding** (host). Single same-kind integer keys are rebased to
   ``key - min`` and ship raw when the span fits int32 — no
   factorization at all, the sharded twin of the vectorized backend's
   direct-address fast path, except the key space is *distributed*:
   each shard owns ``span/ndev`` of it, so the trick keeps working at
   spans where the single-host bincount heuristic gives up. Everything
   else (multi-column, object, cross-kind, wide-span keys) goes
   through the existing joint factorization
   (``vectorized._join_codes``) to dense codes — the factorization IS
   the hash, so the per-shard slot space is perfect (collision-free).
   64-bit keys that cannot lower because ``jax_enable_x64`` is off
   degrade to the vectorized backend through the shared
   ``kernels.fallback`` plumbing — loudly, not silently. Unmatchable
   rows (NULL / NaN keys) are coded to the dtype-max sentinel.
2. **Radix partition** (host). Rows are counting-sorted (a per-chunk
   byte radix pass — no comparison sort anywhere on the host path)
   into ``(src_device, owner_shard, capacity)`` slabs — owner =
   contiguous key range, or a mixing hash for wide-span raw keys.
   Capacity is exact (one bincount), so the exchange can never
   overflow; shapes round to powers of two so the jit cache stays
   small. The host keeps the permutation, so devices exchange *keys
   only* and results map back with pure index arithmetic.
3. **all_to_all + per-shard probe** (device). A tiled ``all_to_all``
   turns the src-major slabs into owner-major rows (arrival order ==
   global row order — this is what preserves the reference's
   right-occurrence order). Each shard sorts its build keys (one
   single-operand sort; sentinels sink to the end) and emits per probe
   lane the (start, count) of its match run. Two probe strategies:

   - default: two ``searchsorted`` passes over the shard-local sorted
     run — with build sides deduplicated by construction (the common
     FK shape, detected on device by an adjacent-equal scan) the
     grouped layout is the sorted order itself and per-lane ranks come
     from one more binary search; duplicate build keys take a
     ``lax.cond`` branch that stable-sorts (key, arrival) pairs
     instead.
   - ``REPRO_HASHJOIN_PALLAS=1`` (the TPU compile target): build the
     open-addressing (start, count) direct-address table over the
     shard's slot range and probe it through ``kernels/hash_join`` —
     the Pallas one-hot probe kernel, or its XLA gather oracle under
     ``interpret``-less CPU runs. Mirrors ``kernels/segment_sum``:
     the kernel is the accelerator path, the host default is whatever
     measures fastest there.
4. **Ragged emission** (host). Per-shard (start, count) pairs are
   offset by the shard's stride, scattered back to original left row
   order through the kept permutation, and expanded by the vectorized
   backend's ``_emit_join`` — which is what makes the output
   bit-for-bit identical to ``reference``, row order included.

Aggregation (PR 7) moves onto the mesh too: ``group_by_agg`` runs
per-shard *partial* aggregation under ``shard_map`` BEFORE the
``all_to_all`` exchange. Each shard reduces its local rows to one
partial stat vector per (distinct key, needed stat), so the exchange
ships one lane per (shard, key slot) instead of one per input row;
the key's owner shard combines the partials (add for SUM/COUNT,
min/max for MIN/MAX), and MEAN is finalized from the shipped
sum+count after the exchange — it is never shipped as a value. The
per-shard reduction mirrors the join's two probe strategies: <= 32-bit
integer values take a packed single-operand sort (counts/sums/min/max
all fall out of run boundaries — no scatter, which XLA:CPU serializes
per row), while float and 64-bit values, plus the ``use_pallas`` TPU
target, run the masked ``kernels/segment_sum`` family (NaN
propagation baked into each partial). First-appearance output order
never rides the exchange at all: the host already materialized the
dense slot codes for the rebase, so one reversed fancy assignment
recovers each slot's first row and one small argsort over distinct
keys (never over rows) orders the output. Eligibility mirrors the
join's direct-address fast path (single integer key, affordable span,
device-lowerable value dtypes); everything else falls back to the
inherited jax/vectorized path. Filter and concat stay inherited.
"""
from __future__ import annotations

import functools
import os
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.exec.base import (AggSpec, Columns, _column_length, fill_value,
                             normalize_agg_specs, payload_validity)
from repro.exec.jax_backend import JaxBackend
from repro.exec.vectorized import (_NOOP_CTX, _and_key_validity,
                                   _join_codes, dense_span_affordable,
                                   key_codes_span)
from repro.kernels import fallback
from repro.kernels.hash_join.ops import hash_probe, masked_hash_probe
from repro.kernels.segment_sum.ops import (masked_segment_reduce,
                                           masked_segment_sum)
from repro.kernels.segment_sum.ref import reduce_identity
from repro.obs import get_recorder

__all__ = ["ShardedBackend"]

# Key spans up to this use contiguous-range partitioning with a
# power-of-two per-shard slot space ("table" mode — required for the
# Pallas direct-address path; also keeps the bucket computation a pure
# shift with the dtype-max sentinel safely out of shard range). Wider
# key spaces hash-partition ("hash" mode); anything that fits int32
# still ships as int32.
MAX_TABLE_SPAN = 1 << 26


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def _round_cap(n: int) -> int:
    """Slab capacity rounding: up to the next multiple of the value's
    third-highest bit — at most 12.5% padding (a pure power of two
    wastes up to 2x at awkward sizes), while keeping the set of
    distinct jit shapes small."""
    n = max(int(n), 64)
    gran = max(64, 1 << (n.bit_length() - 3))
    return -(-n // gran) * gran


def _mix32(h: np.ndarray) -> np.ndarray:
    """Deterministic int32 mixing hash (wraparound multiply)."""
    h = h ^ (h >> np.int32(16))
    with np.errstate(over="ignore"):
        h = (h * np.int32(0x45D9F3B)).astype(np.int32)
    h = h ^ (h >> np.int32(13))
    return h & np.int32(0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _get_mesh(ndev: int):
    return jax.make_mesh((ndev,), ("shard",),
                         devices=jax.devices()[:ndev])


@functools.lru_cache(maxsize=64)
def _probe_fn(ndev: int, cap_l: int, cap_r: int, span_shard: int,
              np_dtype: str, use_pallas: bool, interpret: bool,
              masked: bool = False):
    """Build + jit the shard_map'd exchange-and-probe for one static
    signature. Unmatchable lanes (NULL/NaN keys and slab padding)
    carry the dtype-max sentinel and can match nothing: they sort to
    the end, fall outside every table slot, and are masked out of
    counts. ``span_shard`` > 0 selects the direct-address slot space
    of "table" mode (required for the Pallas path); 0 means wide-span
    raw keys. ``masked`` adds a probe-side keep-mask slab and routes
    through the filter-fused Pallas probe (table mode only — the
    caller host-poisons keys to the sentinel on every other route)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _get_mesh(ndev)
    dtype = np.dtype(np_dtype)
    sent = dtype.type(np.iinfo(dtype).max)

    def exchange(slab):                  # (1, ndev, cap) -> (ndev*cap,)
        x = jax.lax.all_to_all(slab[0], "shard", split_axis=0,
                               concat_axis=0, tiled=True)
        # src-major flatten: arrival order == global row order, which
        # is what lets the grouped layouts below reproduce the
        # reference's right-occurrence order within a key.
        return x.reshape(-1)

    def probe_packed(lk, rk):
        """Packed-sort strategy for int32 keys (the CPU-mesh default).

        One single-operand sort of ``key << 32 | arrival`` orders the
        build side by key with ties in arrival — i.e. global row —
        order, so the grouped layout AND its arrival translation
        (``gidx``) fall out of the same sort with no stable pair sort,
        no scatter, and no separate duplicate-key path. Sentinel lanes
        (padding / NULL keys) pack highest and sink to the tail. The
        probe is one binary search; the count is a hit-check gather
        when the build keys are unique (the common FK shape) and a
        second binary search otherwise."""
        m = rk.shape[0]
        iota = jnp.arange(m, dtype=jnp.int64)
        packed = (rk.astype(jnp.int64) << 32) | iota
        p_srt = jax.lax.sort(packed)
        k_srt = (p_srt >> 32).astype(jnp.int32)
        gidx = (p_srt & jnp.int64(0xFFFFFFFF)).astype(jnp.int32)
        starts = jnp.searchsorted(k_srt, lk).astype(jnp.int32)
        dup = jnp.any((k_srt[1:] == k_srt[:-1]) & (k_srt[1:] != sent))

        def fast(_):
            hit = (k_srt[jnp.minimum(starts, m - 1)] == lk) \
                & (lk != sent)
            return hit.astype(jnp.int32)

        def slow(_):
            ends = jnp.searchsorted(k_srt, lk, side="right")
            return jnp.where(lk != sent,
                             ends - starts.astype(ends.dtype),
                             0).astype(jnp.int32)

        counts = jax.lax.cond(dup, slow, fast, None)
        return starts, counts, gidx

    def probe_wide(lk, rk):
        """int64 keys (jax_enable_x64 verified upstream): stable
        (key, arrival) pair sort + two binary searches."""
        m = rk.shape[0]
        iota = jnp.arange(m, dtype=jnp.int32)
        k_srt, gidx = jax.lax.sort((rk, iota), num_keys=1,
                                   is_stable=True)
        starts = jnp.searchsorted(k_srt, lk, side="left")
        ends = jnp.searchsorted(k_srt, lk, side="right")
        counts = jnp.where(lk != sent, ends - starts, 0)
        return (starts.astype(jnp.int32), counts.astype(jnp.int32),
                gidx)

    def probe_table(lk, rk, lmask=None):
        """Direct-address strategy (the Pallas/TPU path): build the
        open-addressing (start, count) table over this shard's slot
        range, probe through kernels/hash_join. Grouped layout is
        arrival order (unique) or sorted order (duplicates).
        ``lmask`` (filter-fused probe) zeroes masked lanes inside the
        kernel — the filtered rows never leave VMEM."""
        m = rk.shape[0]
        iota = jnp.arange(m, dtype=jnp.int32)
        base = (jax.lax.axis_index("shard") * span_shard).astype(
            jnp.int32)
        slot_r = rk - base               # sentinel -> far out of range
        slot_l = lk - base
        counts_tab = jnp.zeros(span_shard, jnp.int32).at[slot_r].add(
            1, mode="drop")
        unique = jnp.max(counts_tab, initial=0) <= 1

        def fast(_):
            # unique build keys: the grouped layout IS arrival order;
            # start[slot] = the one arrival position.
            pos_tab = jnp.full(span_shard, -1, jnp.int32).at[
                slot_r].set(iota, mode="drop")
            return pos_tab, iota

        def slow(_):
            # duplicate keys: stable-sort the shard by slot (ties keep
            # arrival == global row order) and scatter-min run starts.
            srt, gidx = jax.lax.sort(
                (jnp.where(rk != sent, slot_r, span_shard), iota),
                num_keys=1, is_stable=True)
            pos_tab = jnp.full(span_shard, m, jnp.int32).at[srt].min(
                jnp.arange(m, dtype=jnp.int32), mode="drop")
            return pos_tab, gidx

        pos_tab, gidx = jax.lax.cond(unique, fast, slow, None)
        if lmask is None:
            starts, counts = hash_probe(pos_tab, counts_tab, slot_l,
                                        use_pallas=use_pallas,
                                        interpret=interpret)
        else:
            starts, counts = masked_hash_probe(
                pos_tab, counts_tab, slot_l, lmask,
                use_pallas=use_pallas, interpret=interpret)
        return starts, counts, gidx

    def body_masked(l_slab, m_slab, r_slab):
        # fused-filter path: selected only for table mode + Pallas, so
        # the probe is always the direct-address kernel with the mask
        # slab riding next to the key slab (same owner-major layout).
        lk = l_slab[0].reshape(-1)
        lmask = m_slab[0].reshape(-1)
        rk = exchange(r_slab)
        starts, counts, gidx = probe_table(lk, rk, lmask)
        return starts[None, :], counts[None, :], gidx[None, :]

    def body(l_slab, r_slab):
        # build side: all_to_all so each device owns every row of its
        # key range. Probe side: the host already laid slabs out
        # owner-major (same src-major arrival order the exchange would
        # produce), so probes just flatten — one collective, not two.
        lk = l_slab[0].reshape(-1)
        rk = exchange(r_slab)
        if use_pallas and span_shard:
            probe = probe_table
        elif dtype.itemsize > 4:
            probe = probe_wide
        else:
            probe = probe_packed
        starts, counts, gidx = probe(lk, rk)
        return starts[None, :], counts[None, :], gidx[None, :]

    spec = P("shard", None, None)
    out = P("shard", None)
    fn = body_masked if masked else body
    in_specs = (spec,) * (3 if masked else 2)
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=(out, out, out), check_vma=False)
    shard = NamedSharding(mesh, spec)
    return jax.jit(mapped, in_shardings=(shard,) * len(in_specs))


@functools.lru_cache(maxsize=64)
def _partial_agg_fn(ndev: int, seg_shard: int, col_sig: tuple,
                    use_pallas: bool, interpret: bool):
    """Build + jit the shard_map'd partial-aggregation exchange for one
    static signature. ``col_sig`` is a tuple of (dtype str, stats
    tuple) per distinct value column, stats drawn from
    {"sum", "min", "max"} — COUNT partials are always produced (they
    double as output validity and the MEAN divisor).

    Protocol per shard: reduce local rows to (nseg,) partial vectors,
    ``all_to_all`` each vector (one lane per (shard, key slot) — never
    one per row), then the owner shard combines its slot range: add
    for sum/count, min/max for min/max. Two per-column reduction
    strategies, the aggregation twin of the join's packed/table probe
    split:

    - packed (the CPU-mesh default for <= 32-bit integer values): one
      single-operand sort of ``slot << 32 | order-biased value`` —
      counts are run lengths, the sum is a difference of two lanes of
      one wrapping cumsum (modular, so bit-identical to the
      reference), and min/max are the run's first/last element. No
      scatter anywhere: XLA:CPU lowers segment ops to a serial
      per-row scatter that costs ~10x the sort at benchmark shapes.
    - kernels/segment_sum family (``use_pallas`` — the TPU compile
      target — plus float and 64-bit values, whose NaN propagation
      and non-reorderable sums want the masked kernels). NaN
      poisoning is baked into each shard's partial by
      ``masked_segment_reduce``, and jnp.min/max propagate it
      through the combine."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _get_mesh(ndev)
    nseg = ndev * seg_shard

    def combine(x, mode: str):
        y = jax.lax.all_to_all(x, "shard", split_axis=0,
                               concat_axis=0, tiled=True)
        y = y.reshape(ndev, seg_shard)
        if mode == "sum":
            # dtype pinned: int partial sums must wrap in the value
            # dtype (associative, so bit-identical to the reference),
            # not promote to the platform int.
            return jnp.sum(y, axis=0, dtype=y.dtype)[None, :]
        if mode == "min":
            return jnp.min(y, axis=0)[None, :]
        return jnp.max(y, axis=0)[None, :]

    def reduce_packed(gid, vals, ok, stats, dtype):
        n_rows = gid.shape[0]
        jdt = jnp.dtype(dtype)
        # invalid lanes (and slab padding, which arrives ok=False) go
        # to the dead slot nseg: they sort past every real run and no
        # searchsorted target ever reaches them.
        gg = jnp.where(ok, gid, jnp.int32(nseg)).astype(jnp.int64)
        v64 = vals.astype(jnp.int64)
        if dtype.kind == "u":
            key = v64 & jnp.int64(0xFFFFFFFF)
        else:            # bias bit 31: two's complement -> uint order
            key = (v64 ^ jnp.int64(0x80000000)) & jnp.int64(0xFFFFFFFF)
        p = jax.lax.sort((gg << 32) | key)
        sg = (p >> 32).astype(jnp.int32)
        sk = p & jnp.int64(0xFFFFFFFF)
        if dtype.kind == "u":
            sv = sk.astype(jdt)
        else:            # xor undoes the bias; int32 wrap restores sign
            sv = (sk ^ jnp.int64(0x80000000)).astype(jnp.int32) \
                .astype(jdt)
        slots = jnp.arange(nseg, dtype=jnp.int32)
        starts = jnp.searchsorted(sg, slots, side="left") \
            .astype(jnp.int32)
        ends = jnp.searchsorted(sg, slots, side="right") \
            .astype(jnp.int32)
        cnt = ends - starts
        outs = [combine(cnt, "sum")]
        if "sum" in stats:
            # wrapping cumsum in the value dtype: the boundary
            # difference is the exact modular group sum.
            cs = jnp.cumsum(sv, dtype=jdt)
            zero = jnp.zeros((), jdt)
            tot = jnp.where(ends > 0, cs[jnp.maximum(ends, 1) - 1],
                            zero)
            base = jnp.where(starts > 0, cs[jnp.maximum(starts, 1) - 1],
                             zero)
            outs.append(combine((tot - base).astype(jdt), "sum"))
        if "min" in stats:
            mn = sv[jnp.minimum(starts, n_rows - 1)]
            outs.append(combine(
                jnp.where(cnt > 0, mn,
                          jnp.asarray(reduce_identity(dtype, "min"),
                                      jdt)), "min"))
        if "max" in stats:
            mx = sv[jnp.maximum(ends, 1) - 1]
            outs.append(combine(
                jnp.where(cnt > 0, mx,
                          jnp.asarray(reduce_identity(dtype, "max"),
                                      jdt)), "max"))
        return outs

    def reduce_kernels(gid, vals, ok, stats):
        s, cnt = masked_segment_sum(
            vals, gid, ok, nseg,
            use_pallas=use_pallas, interpret=interpret)
        outs = [combine(cnt, "sum")]
        if "sum" in stats:
            outs.append(combine(s, "sum"))
        for op in ("min", "max"):
            if op in stats:
                r, _ = masked_segment_reduce(
                    vals, gid, ok, nseg, op=op,
                    use_pallas=use_pallas, interpret=interpret)
                outs.append(combine(r, op))
        return outs

    def body(gid_slab, *col_slabs):
        gid = gid_slab[0]
        outs = []
        i = 0
        for dt_str, stats in col_sig:
            dtype = np.dtype(dt_str)
            vals = col_slabs[i][0]
            ok = col_slabs[i + 1][0]
            i += 2
            if (dtype.kind in "iu" and dtype.itemsize <= 4
                    and not use_pallas):
                outs += reduce_packed(gid, vals, ok, stats, dtype)
            else:
                outs += reduce_kernels(gid, vals, ok, stats)
        return tuple(outs)

    spec = P("shard", None)
    n_in = 1 + 2 * len(col_sig)
    mapped = jax.shard_map(body, mesh=mesh, in_specs=(spec,) * n_in,
                           out_specs=spec, check_vma=False)
    shard = NamedSharding(mesh, spec)
    return jax.jit(mapped, in_shardings=(shard,) * n_in)


class ShardedBackend(JaxBackend):
    name = "sharded"

    def __init__(self, *, n_devices: int | None = None,
                 use_pallas: bool | None = None,
                 use_pallas_probe: bool | None = None,
                 interpret: bool | None = None):
        super().__init__(use_pallas=use_pallas, interpret=interpret)
        if use_pallas_probe is None:
            use_pallas_probe = os.environ.get(
                "REPRO_HASHJOIN_PALLAS") == "1"
        self.use_pallas_probe = use_pallas_probe
        self.n_devices = (n_devices if n_devices is not None
                          else len(jax.devices()))

    # cache-key interaction (DESIGN.md §10): a mesh change regroups row
    # placement (and, through the inherited device aggregation, float
    # SUM summation order under the documented carve-out), so the shard
    # count must move every engine cache key — and so must the
    # inherited segment-sum Pallas flag, whose tiling regroups float
    # sums too. The probe strategy flag is deliberately absent: probe
    # outputs are integer-exact identical across strategies.
    def cache_token(self) -> str:
        suffix = "+pallas" if self.use_pallas else ""
        return f"{self.name}{suffix}[devices={self.n_devices}]"

    # -- join -----------------------------------------------------------
    def hash_join(self, left: Columns, right: Columns,
                  on: Sequence[str], how: str = "inner") -> Columns:
        return self._sharded_join(left, right, on, how, None)

    def masked_hash_join(self, left: Columns, right: Columns,
                         on: Sequence[str], how: str = "inner", *,
                         left_mask: "np.ndarray | None" = None,
                         right_mask: "np.ndarray | None" = None
                         ) -> Columns:
        """Filter-fused distributed join. The right mask folds into the
        key validity on the host before coding (masked build rows code
        to the sentinel and land in the drop bucket — they never ship).
        The left (probe) mask rides to the device as a slab and is
        applied *inside* the Pallas probe kernel when table mode is
        active — the filtered rows never leave VMEM; every other route
        host-poisons the coded keys to the sentinel, which the existing
        sentinel machinery drops for free. ``how='left'`` with a left
        mask must prefilter (a masked row must not emit as unmatched).
        """
        if left_mask is not None and how != "inner":
            left = self.filter_select(left, left_mask)
            left_mask = None
        if right_mask is not None:
            right = _and_key_validity(right, on, right_mask)
        return self._sharded_join(left, right, on, how, left_mask)

    def _host_fallback(self, left: Columns, right: Columns,
                       on: Sequence[str], how: str,
                       probe_mask: "np.ndarray | None", *,
                       reason: str = "keys cannot lower") -> Columns:
        # sharded -> vectorized downgrade: structured degradation event
        # so run manifests show it (the dtype-driven routes ALSO warn
        # one-time via fallback.warn_numpy_fallback upstream).
        rec = get_recorder()
        if rec.enabled:
            rec.event("degradation", kind="sharded_downgrade",
                      op="hash_join", reason=reason)
            rec.metrics.counter("sharded.downgrades").inc()
        if probe_mask is None:
            return super().hash_join(left, right, on, how)
        return super().masked_hash_join(left, right, on, how,
                                        left_mask=probe_mask)

    def _sharded_join(self, left: Columns, right: Columns,
                      on: Sequence[str], how: str,
                      probe_mask: "np.ndarray | None") -> Columns:
        n_left = _column_length(left)
        n_right = _column_length(right)
        ndev = max(1, self.n_devices)
        if n_left == 0 or n_right == 0:
            return self._host_fallback(left, right, on, how, probe_mask,
                                       reason="empty input side")
        if n_left >= 2**31 or n_right >= 2**31:
            return self._host_fallback(left, right, on, how, probe_mask,
                                       reason="row count exceeds int32")
        if ndev > 255:                  # buckets are uint8
            return self._host_fallback(
                left, right, on, how, probe_mask,
                reason=f"{ndev} devices exceeds the uint8 bucket space "
                       f"(255)")

        rec = get_recorder()
        with key_codes_span(rec, on, left, right):
            keyed = self._device_keys(left, right, on)
        if keyed is None:               # cannot lower: vectorized path
            return self._host_fallback(
                left, right, on, how, probe_mask,
                reason="keys cannot lower to the device without losing "
                       "bits")
        lk, rk, span = keyed
        if span == 0:                   # no valid key anywhere
            if probe_mask is not None and how != "inner":
                left = self.filter_select(left, probe_mask)
                n_left = _column_length(left)
            return self._emit_join(
                left, right, how, n_left,
                np.zeros(n_left, np.int64), np.zeros(n_left, np.int64),
                np.array([], dtype=np.int64))
        # power-of-two per-shard slot space: buckets become a shift and
        # the dtype-max sentinel lands safely past the last shard.
        span_shard = (_next_pow2(-(-span // ndev))
                      if 0 < span <= MAX_TABLE_SPAN else 0)

        # fused-filter dispatch: table mode + Pallas keeps the mask on
        # the device (in-VMEM); every other route poisons masked lanes
        # to the sentinel here — they bucket to the drop lane and never
        # even ship.
        fused = (probe_mask is not None and self.use_pallas_probe
                 and span_shard > 0)
        with key_codes_span(rec, on, left, right):
            if probe_mask is not None and not fused:
                sent = lk.dtype.type(np.iinfo(lk.dtype).max)
                lk = np.where(np.asarray(probe_mask, dtype=bool), lk,
                              sent)
            lb = _buckets(lk, ndev, span_shard)
            rb = _buckets(rk, ndev, span_shard)
            l_slab, l_idx, cap_l = _partition(lk, lb, ndev)
            r_slab, r_idx, cap_r = _partition(rk, rb, ndev)
        if ndev * cap_l >= 2**31 or ndev * cap_r >= 2**31:
            # padded per-shard lane counts must fit the int32 arrival
            # positions the probes pack — possible past ~2e9 rows with
            # heavy bucket skew even though the raw row counts passed
            # the guard above.
            return self._host_fallback(
                left, right, on, how, probe_mask,
                reason="padded slab lanes exceed int32 arrival space "
                       "(bucket skew)")
        # probe side ships owner-major (src stays the minor axis, so
        # per-device arrival order matches what the build side's
        # all_to_all produces).
        l_slab = np.ascontiguousarray(l_slab.transpose(1, 0, 2))

        fn = _probe_fn(ndev, cap_l, cap_r, span_shard, lk.dtype.str,
                       self.use_pallas_probe, self.interpret,
                       masked=fused)
        if fused:
            keep = np.asarray(probe_mask, dtype=bool)
            m_slab = np.where(
                l_idx >= 0, keep[np.clip(l_idx, 0, None)], False
            ).astype(np.int32)
            m_slab = np.ascontiguousarray(m_slab.transpose(1, 0, 2))
            args = (l_slab, m_slab, r_slab)
        else:
            args = (l_slab, r_slab)
        kernel_ctx = _NOOP_CTX
        if rec.enabled:
            # every slab in `args` is copied to the mesh and crosses it
            # through all_to_all
            bytes_moved = sum(a.nbytes for a in args)
            # valid rows each owner shard probes / builds (slab padding
            # and unmatchable rows excluded)
            kernel_ctx = rec.span(
                "kernel", op="sharded.exchange_probe", ndev=ndev,
                mode=("table" if span_shard > 0 else "hash"),
                fused_mask=fused, all_to_all_bytes=bytes_moved,
                rows=n_left + n_right, segments=ndev * span_shard,
                h2d_bytes=bytes_moved,
                rows_left=n_left, rows_right=n_right,
                rows_left_per_shard=(l_idx >= 0).sum(axis=(0, 2)).tolist(),
                rows_right_per_shard=(r_idx >= 0).sum(
                    axis=(0, 2)).tolist())
            rec.metrics.histogram(
                "sharded.all_to_all_bytes").observe(bytes_moved)
        # the span ends after the fetch, where the host waits for the
        # device. The packed/wide probes carry int64 intermediates; the
        # x64 scope is thread-local and only governs types traced inside.
        with kernel_ctx as sp:
            with jax.enable_x64(True):
                out = fn(*args)
            starts, counts, gidx = (np.asarray(o) for o in out)
            if sp is not None:
                sp.set(d2h_bytes=starts.nbytes + counts.nbytes
                       + gidx.nbytes)

        # map device results back through the kept permutation: the
        # grouped layout is the per-shard arrival order permuted by
        # gidx, and arrival order is the host's own slab layout — so
        # the translation to global row ids is one gather, and padding
        # arrival cells (-1) become holes the emission never reads.
        # Per-key runs are contiguous on exactly one shard, so
        # concatenating shard layouts (stride = ndev*cap_r) is a valid
        # grouped layout for the shared ragged emission.
        stride = ndev * cap_r
        arr_l = l_idx.transpose(1, 0, 2).reshape(ndev, ndev * cap_l)
        arr_r = r_idx.transpose(1, 0, 2).reshape(ndev, stride)
        ridx = np.take_along_axis(
            arr_r, gidx.astype(np.int64, copy=False), axis=1
        ).reshape(-1)
        # int64 accumulators: the ragged emission cumsums counts, and
        # a >2**31-row join output must not wrap there.
        starts_g = np.zeros(n_left, np.int64)
        counts_g = np.zeros(n_left, np.int64)
        m = arr_l >= 0
        starts_g[arr_l[m]] = (starts.astype(np.int64)
                              + (np.arange(ndev, dtype=np.int64)
                                 * stride)[:, None])[m]
        counts_g[arr_l[m]] = counts[m]
        return self._emit_join(left, right, how, n_left, starts_g,
                               counts_g,
                               ridx.astype(np.int64, copy=False))

    # -- key coding ------------------------------------------------------
    def _device_keys(self, left: Columns, right: Columns,
                     on: Sequence[str]):
        """(lkeys, rkeys, span) with unmatchable rows already coded to
        the dtype-max sentinel; span > 0 = int32 slot codes ("table"
        mode), span < 0 = raw keys, hash partition ("hash" mode);
        span == 0 = no valid keys at all. None when the keys cannot
        lower to the device without losing bits (the shared
        numpy-fallback plumbing warns)."""
        raw = self._raw_int_keys(left, right, on)
        if raw is not None:
            return raw
        lcodes, rcodes = _join_codes(left, right, on)
        card = int(max(lcodes.max(initial=-1),
                       rcodes.max(initial=-1))) + 1
        if card == 0:
            return lcodes.astype(np.int32), rcodes.astype(np.int32), 0
        if card >= 2**31 - 64:
            # row counts are int32-checked upstream, so a cardinality
            # past the int32 code space is unreachable in practice —
            # keep the guard anyway (codes must fit int32 + sentinel).
            fallback.warn_numpy_fallback(
                "sharded.hash_join", np.dtype(np.int64),
                reason="joint key cardinality exceeds the int32 code "
                       "space")
            return None
        sent = np.int32(np.iinfo(np.int32).max)
        lk = lcodes.astype(np.int32)
        rk = rcodes.astype(np.int32)
        lk[lk < 0] = sent
        rk[rk < 0] = sent
        return lk, rk, card

    def _raw_int_keys(self, left: Columns, right: Columns,
                      on: Sequence[str]):
        """Single same-kind integer key: ship rebased raw values (numpy
        equality == Python equality for int kinds), skipping
        factorization — the sharded twin of the vectorized
        direct-address fast path, distributed so it scales past the
        single-host span budget."""
        if len(on) != 1:
            return None
        lv, lval = left[on[0]]
        rv, rval = right[on[0]]
        if (lv.dtype == object or rv.dtype == object
                or lv.dtype.kind not in "iu"
                or lv.dtype.kind != rv.dtype.kind):
            return None
        lok = payload_validity(lv, lval)
        rok = payload_validity(rv, rval)
        if not lok.any() or not rok.any():
            return None                   # codes path handles trivially
        lo = min(int(lv[lok].min()), int(rv[rok].min()))
        hi = max(int(lv[lok].max()), int(rv[rok].max()))
        span = hi - lo + 1
        sent32 = np.int32(np.iinfo(np.int32).max)
        if (0 <= lo and hi < 2**31 - 64
                and (hi < MAX_TABLE_SPAN or span > MAX_TABLE_SPAN)):
            # values are already valid int32 slot codes — no rebase
            # pass; span = hi+1 keeps shard 0 a touch wider, which the
            # exact capacity computation absorbs. NOT taken when only
            # the rebased span fits the table budget (dense-but-offset
            # keys): the shortcut must never cost table mode — and
            # with it the Pallas probe path — that the rebase below
            # would keep.
            lk = lv.astype(np.int32)
            rk = rv.astype(np.int32)
            lk[~lok] = sent32
            rk[~rok] = sent32
            return lk, rk, hi + 1
        if span <= 2**31 - 64:
            # rebase to slot codes: the distributed key space absorbs
            # the sparsity (span/ndev slots per shard). Two exact
            # routes: uint64 subtracts in its native dtype (lo is the
            # joint min, so no wrap — an int64 intermediate would
            # overflow past 2**63); every other kind widens to int64
            # first (native-width subtraction would wrap int8/int16
            # spans, and lo — the min across BOTH sides, possibly a
            # wider dtype — need not fit the narrow dtype at all).
            # Either way the rebased values are < span < 2**31.
            def rebase(v):
                if v.dtype.kind == "u" and v.dtype.itemsize == 8:
                    return (v - v.dtype.type(lo)).astype(np.int32)
                return (v.astype(np.int64) - lo).astype(np.int32)

            lk = rebase(lv)
            rk = rebase(rv)
            lk[~lok] = sent32
            rk[~rok] = sent32
            return lk, rk, span
        if -2**63 <= lo and hi <= 2**63 - 2:
            if not fallback.device_supports_dtype(np.dtype(np.int64)):
                # NOT a whole-op fallback: the join still runs on the
                # mesh through factorized int32 codes — what degrades
                # is the key coding (a host np.unique pass replaces
                # shipping raw int64 keys). Warn with the accurate
                # scope, still naming the env fix.
                fallback.warn_numpy_fallback(
                    "sharded.hash_join", lv.dtype,
                    reason="wide-span 64-bit keys take the host "
                           "factorization path; enable jax_enable_x64 "
                           "(e.g. JAX_ENABLE_X64=1) to ship raw int64 "
                           "keys to the device")
                return None               # codes path (still sharded)
            sent = np.int64(np.iinfo(np.int64).max)
            lk = lv.astype(np.int64)
            rk = rv.astype(np.int64)
            lk[~lok] = sent
            rk[~rok] = sent
            return lk, rk, -1
        return None                       # uint64 tail: codes path

    # -- aggregation -----------------------------------------------------
    def group_by_agg(self, cols: Columns, keys: Sequence[str],
                     specs: Sequence[AggSpec]) -> Columns:
        specs = normalize_agg_specs(cols, keys, specs)
        partial = self._partial_group_by(cols, keys, specs)
        if partial is not None:
            return partial
        return super().group_by_agg(cols, keys, specs)

    def _partial_group_by(self, cols: Columns, keys: Sequence[str],
                          specs: tuple[AggSpec, ...]
                          ) -> "Columns | None":
        """Mesh partial-aggregation path; None when ineligible (the
        inherited jax/vectorized path takes over). Eligibility mirrors
        the join's direct-address fast path: one integer-kind key whose
        span is dense enough to direct-address, every value column
        device-lowerable. NULL keys take one extra slot (SQL: one NULL
        group); integer keys cannot be NaN, so slots are exact."""
        n = _column_length(cols)
        ndev = max(1, self.n_devices)
        if n == 0 or n >= 2**31 - 2 or ndev > 255 or len(keys) != 1:
            return None
        kv, kvalid = cols[keys[0]]
        if kv.dtype == object or kv.dtype.kind not in "iu":
            return None
        # every value column must lower losslessly (the 64-bit-off
        # fallback warns in the inherited path, not here)
        want: dict[str, set] = {}
        for fn, value, _out in specs:
            vdt = cols[value][0].dtype
            if (vdt == object or vdt.kind not in "fiu"
                    or not fallback.device_supports_dtype(vdt)):
                return None
            stats = want.setdefault(value, set())
            if fn in ("sum", "mean"):
                stats.add("sum")
            elif fn in ("min", "max"):
                stats.add(fn)
        kok = payload_validity(kv, kvalid)
        any_null = not bool(kok.all())
        if kok.any():
            lo = int(kv[kok].min())
            span = int(kv[kok].max()) - lo + 1
        else:
            lo, span = 0, 0
        if span > MAX_TABLE_SPAN or not dense_span_affordable(span, n):
            return None
        n_slots = span + (1 if any_null else 0)   # last slot = NULL group
        seg_shard = _next_pow2(-(-n_slots // ndev))
        if ndev * seg_shard > MAX_TABLE_SPAN:
            return None
        nseg = ndev * seg_shard

        # host: O(n) rebase to dense slot codes — no sort, no factorize
        def rebase(v):
            if v.dtype.kind == "u" and v.dtype.itemsize == 8:
                return (v - v.dtype.type(lo)).astype(np.int32)
            return (v.astype(np.int64) - lo).astype(np.int32)

        chunk = -(-n // ndev)
        pad = ndev * chunk - n

        def slab(arr, fill):
            if pad:
                arr = np.concatenate(
                    [arr, np.full(pad, fill, dtype=arr.dtype)])
            return arr.reshape(ndev, chunk)

        rec = get_recorder()
        with key_codes_span(rec, keys, cols):
            gid = rebase(kv)
            if any_null:
                gid[~kok] = np.int32(span)
            # first-appearance per slot stays on the host: the rebase
            # already materialized gid, so a reversed fancy assignment
            # (later writes win, so the reversed order leaves each slot
            # holding its FIRST row) beats shipping a row-id slab and a
            # whole extra segment reduce through the exchange.
            first = np.full(n_slots, n, dtype=np.int64)
            first[gid[::-1]] = np.arange(n - 1, -1, -1, dtype=np.int64)
            codes = np.flatnonzero(first < n)    # the slots in use

            gid_slab = slab(gid, np.int32(0))    # padding: slot 0, ok=False
            col_sig = []
            col_slabs = []
            col_names = list(want)
            for name in col_names:
                values, valid = cols[name]
                ok = payload_validity(values, valid)
                col_sig.append((values.dtype.str,
                                tuple(sorted(want[name]))))
                col_slabs.append(slab(values, fill_value(values.dtype)))
                col_slabs.append(slab(ok, False))

        fn = _partial_agg_fn(ndev, seg_shard, tuple(col_sig),
                             self.use_pallas, self.interpret)
        kernel_ctx = _NOOP_CTX
        if rec.enabled:
            # the exchange ships one lane per (shard, key slot) per
            # partial vector — reduced slabs, never input rows: per
            # column one COUNT partial (int32) plus one value-dtype
            # partial per requested stat, each ndev*nseg lanes.
            lanes = ndev * ndev * seg_shard
            bytes_moved = sum(
                lanes * (4 + np.dtype(dt).itemsize * len(stats))
                for dt, stats in col_sig)
            # groups_per_shard: the slots in use that each owner shard
            # holds after the exchange (its contiguous seg_shard range)
            kernel_ctx = rec.span(
                "kernel", op="sharded.partial_agg", ndev=ndev,
                rows=n, slots=n_slots, segments=nseg,
                all_to_all_bytes=bytes_moved,
                h2d_bytes=gid_slab.nbytes + sum(a.nbytes
                                                for a in col_slabs),
                rows_per_shard=[min(chunk, max(0, n - d * chunk))
                                for d in range(ndev)],
                groups_per_shard=np.bincount(
                    codes // seg_shard, minlength=ndev).tolist())
            rec.metrics.histogram(
                "sharded.all_to_all_bytes").observe(bytes_moved)
        # the span ends after the fetch, where the host waits for the
        # device. The packed strategy sorts int64-packed lanes; the x64
        # scope is thread-local and only governs types traced inside.
        with kernel_ctx as sp:
            with jax.enable_x64(True):
                outs = [np.asarray(o).reshape(-1) for o in
                        fn(gid_slab, *col_slabs)]
            if sp is not None:
                sp.set(d2h_bytes=sum(o.nbytes for o in outs))

        # unpack in the body's emission order
        stats_of: dict[str, dict[str, np.ndarray]] = {}
        i = 0
        for name, (_dt, stats) in zip(col_names, col_sig):
            got = {"count": outs[i]}
            i += 1
            for s in ("sum", "min", "max"):
                if s in stats:
                    got[s] = outs[i]
                    i += 1
            stats_of[name] = got

        # host finalize: presence + first-appearance order from ONE
        # small argsort over distinct keys (never over rows)
        out_codes = codes[np.argsort(first[codes], kind="stable")]
        kdt = kv.dtype
        if kdt.kind == "u" and kdt.itemsize == 8:
            keyvals = kdt.type(lo) + out_codes.astype(kdt)
        else:
            keyvals = (out_codes + lo).astype(kdt)
        kmask = np.ones(len(out_codes), dtype=bool)
        if any_null:
            kmask = out_codes != span
            keyvals[~kmask] = fill_value(kdt)
        data: dict[str, tuple[np.ndarray, np.ndarray | None]] = {
            keys[0]: (keyvals, kmask)}
        for fname, value, out_name in specs:
            got = stats_of[value]
            cnt = got["count"][out_codes].astype(np.int64)
            if fname == "count":
                data[out_name] = (cnt, None)
                continue
            has = cnt > 0
            vdt = cols[value][0].dtype
            if fname == "sum":
                s = got["sum"][out_codes].astype(vdt, copy=True)
                s[~has] = fill_value(vdt)
                data[out_name] = (s, has)
            elif fname == "mean":
                m = got["sum"][out_codes].astype(np.float64)
                np.divide(m, cnt, out=m, where=has)
                m[~has] = fill_value(np.dtype(np.float64))
                data[out_name] = (m, has)
            else:
                r = got[fname][out_codes].astype(vdt, copy=True)
                r[~has] = fill_value(vdt)
                data[out_name] = (r, has)
        return data


def _buckets(keys: np.ndarray, ndev: int, span_shard: int
             ) -> np.ndarray:
    """Owner shard per row, uint8; >= ndev for unmatchable rows (they
    sort to the tail of every chunk and are never placed).

    Range mode is a single shift: span_shard is a power of two no
    wider than MAX_TABLE_SPAN/ndev, so the int32 sentinel (all ones
    below bit 31) shifts to >= 255 — no separate sentinel pass."""
    if span_shard > 0:
        sh = span_shard.bit_length() - 1
        # valid codes shift below ndev; the sentinel shifts to at least
        # 16*ndev, so clipping to ndev (the drop bucket) is exact.
        return np.minimum(keys >> sh, ndev).astype(np.uint8)
    sent = keys.dtype.type(np.iinfo(keys.dtype).max)
    if keys.dtype.itemsize > 4:
        folded = ((keys >> 32) ^ keys).astype(np.int32)
    else:
        folded = keys.astype(np.int32)
    b = _mix32(folded).astype(np.int64) % ndev
    return np.where(keys != sent, b, ndev).astype(np.uint8)


def _partition(keys: np.ndarray, buckets: np.ndarray, ndev: int
               ) -> tuple[np.ndarray, np.ndarray, int]:
    """Host radix partition into (src, owner, cap) slabs.

    One byte-radix (counting) argsort per source chunk — numpy's
    stable integer argsort is a radix sort, so the host path never
    pays a comparison sort. Returns (key slabs, original-row-index
    slabs (-1 padding), cap). Stable per (src, owner) pair — rows keep
    original order, which the device-side arrival order inherits.
    """
    n = len(keys)
    chunk = -(-n // ndev)
    counts = np.bincount(
        (np.arange(n, dtype=np.int64) // chunk) * (ndev + 1) + buckets,
        minlength=ndev * (ndev + 1)).reshape(ndev, ndev + 1)
    cap = _round_cap(int(counts[:, :ndev].max()))
    sent = keys.dtype.type(np.iinfo(keys.dtype).max)
    slab = np.full((ndev, ndev, cap), sent, dtype=keys.dtype)
    idx = np.full((ndev, ndev, cap), -1, dtype=np.int32)
    for s in range(ndev):
        lo = s * chunk
        hi = min(n, lo + chunk)
        if lo >= hi:
            continue
        order = np.argsort(buckets[lo:hi], kind="stable")
        ks = keys[lo:hi][order]
        rows = (order + lo).astype(np.int32)
        off = 0
        for d in range(ndev):
            c = int(counts[s, d])
            slab[s, d, :c] = ks[off:off + c]
            idx[s, d, :c] = rows[off:off + c]
            off += c
    return slab, idx, cap
