"""Exchange (``exec/sharded.py``): the largest share of the exchanged
work that one owner shard holds, summed over the window's sharded
``kernel`` spans: probe and build rows (``rows_left_per_shard`` +
``rows_right_per_shard``) of each join, occupied key slots
(``groups_per_shard``) of each partial aggregation. An even split over
n shards reads 1/n. Moves ``run_s``."""
import numpy as np

KEYS = {"sharded.exchange_probe": ("rows_left_per_shard",
                                   "rows_right_per_shard"),
        "sharded.partial_agg": ("groups_per_shard",)}


def read(ctx):
    per_shard = 0
    for s in ctx.spans:
        keys = KEYS.get(s.attrs.get("op")) if s.name == "kernel" else None
        if keys and all(k in s.attrs for k in keys):
            for k in keys:
                per_shard = per_shard + np.asarray(s.attrs[k], np.int64)
    total = int(np.sum(per_shard))
    if total <= 0:
        return None
    return float(np.max(per_shard)) / total
