"""Snapshot storage (``data/tables.py``, ``core/store.py``): the
``snapshot_read`` spans (``Table.from_blobs``: source loads, verifier
reads) per run. Moves ``run_s``."""
import layers


def read(ctx):
    return layers.span_ms(ctx, "snapshot_read")
