"""Snapshot storage (``data/tables.py``, ``core/store.py``): the
``snapshot_write`` spans (``Table.to_blobs`` of each node output) per
run. Moves ``run_s``."""
import layers


def read(ctx):
    return layers.span_ms(ctx, "snapshot_write")
