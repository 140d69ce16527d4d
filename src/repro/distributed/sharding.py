"""Logical-axis sharding rules (MaxText-style) for all architectures.

Model code never names physical mesh axes. It tags tensors with *logical*
axis names (``"batch"``, ``"heads"``, ``"ff"`` …) via :func:`lshard`;
a :class:`AxisRules` mapping — per arch × shape, chosen by the launcher —
resolves logical names to physical mesh axes. This is what makes the same
model definition runnable on the single-pod (data, model) mesh, the
multi-pod (pod, data, model) mesh, or a laptop (no mesh: rules inactive).

Physical axes:
  pod    — slow inter-pod links: pure DP (+ compressed grad all-reduce)
  data   — intra-pod DP / FSDP axis; batch dim; decode: also KV-seq shards
  model  — TP axis: heads / ff / vocab / experts; decode: KV-seq shards

Non-divisible dims (e.g. 40 heads over a 16-way model axis) rely on
GSPMD's implicit padding — legal, costs padding waste that the roofline
report surfaces (see EXPERIMENTS.md §Roofline notes).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["AxisRules", "use_rules", "lshard", "logical_spec",
           "named_sharding", "TRAIN_RULES", "DECODE_RULES", "FSDP_RULES",
           "current_rules"]

AxisEntry = str | tuple[str, ...] | None


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mapping logical axis name -> physical mesh axis (or axes, or None)."""

    rules: Mapping[str, AxisEntry]
    mesh: Mesh | None = None

    def resolve(self, *names: str | None) -> P:
        out = []
        used: set[str] = set()
        for n in names:
            if n is None:
                out.append(None)
                continue
            entry = self.rules.get(n)
            # drop axes the mesh doesn't have (single-pod vs multi-pod)
            if entry is not None and self.mesh is not None:
                have = set(self.mesh.axis_names)
                if isinstance(entry, tuple):
                    entry = tuple(a for a in entry if a in have) or None
                elif entry not in have:
                    entry = None
            # a mesh axis may appear at most once per spec: first logical
            # name wins (e.g. under sequence parallelism `heads` takes
            # `model`; `seq` then resolves to None inside attention)
            if entry is not None:
                if isinstance(entry, tuple):
                    entry = tuple(a for a in entry if a not in used) or None
                    if entry:
                        used.update(entry)
                elif entry in used:
                    entry = None
                else:
                    used.add(entry)
            out.append(entry)
        return P(*out)


_local = threading.local()


def current_rules() -> AxisRules | None:
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def use_rules(rules: AxisRules | None):
    prev = current_rules()
    _local.rules = rules
    try:
        yield rules
    finally:
        _local.rules = prev


def logical_spec(*names: str | None) -> P:
    r = current_rules()
    if r is None:
        return P(*([None] * len(names)))
    return r.resolve(*names)


def lshard(x: jax.Array, *names: str | None) -> jax.Array:
    """Apply a logical sharding constraint (no-op outside rules/mesh)."""
    r = current_rules()
    if r is None or r.mesh is None:
        return x
    spec = r.resolve(*names)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(r.mesh, spec))


def safe_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop sharding on dims the mesh cannot divide evenly.

    Explicit jit argument shardings require divisibility (unlike
    intermediate constraints, which GSPMD pads); replication of the
    offending dim is always correct — e.g. whisper's 1500 encoder
    frames on a 16-way axis.
    """
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for ax in axes:
            n *= mesh.shape.get(ax, 1)
        out.append(entry if shape[i] % n == 0 else None)
    return P(*out)


def named_sharding(mesh: Mesh, *names: str | None,
                   rules: AxisRules | None = None) -> NamedSharding:
    r = rules or current_rules() or AxisRules({}, mesh)
    r = dataclasses.replace(r, mesh=mesh)
    return NamedSharding(mesh, r.resolve(*names))


# ---------------------------------------------------------------------------
# Standard rule sets
# ---------------------------------------------------------------------------

# Megatron-style TP + DP for training / prefill. Activations keep d_model
# unsharded; heads/ff/vocab split over `model`; batch over (pod, data).
TRAIN_RULES: dict[str, AxisEntry] = {
    "batch": ("pod", "data"),
    "seq": None,              # sequence stays local in training
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "expert_cap": None,
    "kv_seq": None,
    # parameter axes
    "p_embed_vocab": "model",
    "p_heads": "model",
    "p_kv_heads": "model",
    "p_ff": "model",
    "p_embed": None,          # FSDP_RULES overrides to ("data",)
    "p_experts": "model",
    "p_moe_inner": None,      # FSDP_RULES overrides to ("data",)
    "layers": None,
}

# FSDP: parameters additionally sharded over `data` on their d_model axis
# (all-gathered on use). Required to fit the ≥100B archs.
FSDP_RULES: dict[str, AxisEntry] = dict(
    TRAIN_RULES,
    p_embed=("data",),
    p_moe_inner=("data",),
)

# Megatron-style sequence parallelism: the residual stream between blocks
# is sharded over `model` along seq (the norm/elementwise regions), and
# GSPMD converts the TP all-reduces into all-gather + reduce-scatter
# pairs around attention/FFN. Mandatory at train_4k/prefill_32k on v5e:
# an unsharded per-layer residual (B_loc·S·d·2B, e.g. 1.6 GB for
# command-r) × L rematerialization carries would not fit HBM.
SP_SUFFIX: dict[str, AxisEntry] = {"seq": "model"}

# Decode: KV cache sequence-sharded over `model` (flash-decode partial
# softmax: works for ANY head count — no divisibility constraint), batch
# over (pod, data). Weights stay TP-sharded.
DECODE_RULES: dict[str, AxisEntry] = dict(
    TRAIN_RULES,
    batch=("pod", "data"),
    kv_seq="model",
    heads=None,            # activations: 1-token q, replicate heads
    kv_heads=None,
)


# Pure data parallelism: batch spans EVERY mesh axis; parameters are
# replicated. The right strategy for small models (xlstm-350m: d=1024)
# where 16-way TP makes every activation collective ~40× the compute
# (measured: EXPERIMENTS.md §Perf C1). Grad all-reduce is the only
# collective left.
DP_ONLY_RULES: dict[str, AxisEntry] = {
    **{k: None for k in TRAIN_RULES},
    "batch": ("pod", "data", "model"),
}


def make_rules(kind: str, mesh: Mesh | None, *, fsdp: bool = False,
               seq_parallel: bool = False,
               dp_only: bool = False) -> AxisRules:
    # NOTE: prefill returns the KV cache in the decode layout — its seq
    # axis shards over `model` (resolve() dedups against SP's use).
    if dp_only and kind in ("train", "prefill"):
        base = dict(DP_ONLY_RULES)
        if fsdp:
            # ZeRO-style: params/opt sharded over `data`, gathered on
            # use — lets 3–9B models run pure-DP (granite: experts stay
            # LOCAL per token, no dispatch collectives at all)
            base["p_embed"] = ("data",)
            base["p_moe_inner"] = ("data",)
        return AxisRules(base, mesh)
    if kind in ("train", "prefill"):
        base = dict(FSDP_RULES if fsdp else TRAIN_RULES)
        if seq_parallel:
            base.update(SP_SUFFIX)
        if kind == "prefill":
            base["kv_seq"] = "model"
    elif kind == "decode":
        base = dict(DECODE_RULES)
        if fsdp:
            base["p_embed"] = ("data",)
            base["p_moe_inner"] = ("data",)
    else:
        raise ValueError(kind)
    return AxisRules(base, mesh)
