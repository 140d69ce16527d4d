"""Snapshot storage (``data/tables.py``, ``core/store.py``): the
``snapshot_read`` spans (``Table.from_blobs``: source loads, cache hits,
the result read back) per query. Moves ``query_s``."""
import layers


def read(ctx):
    return layers.span_ms(ctx, "snapshot_read")
