"""Snapshot storage (``data/tables.py``): the share of the manifest
columns the window's ``snapshot_read`` spans left unread, their
``columns_skipped`` over ``columns`` + ``columns_skipped``. A read of
every column skips none. Moves ``run_s``."""


def read(ctx):
    reads = [s.attrs for s in ctx.spans
             if s.name == "snapshot_read" and "columns_skipped" in s.attrs]
    total = sum(a["columns"] + a["columns_skipped"] for a in reads)
    if not total:
        return None
    return sum(a["columns_skipped"] for a in reads) / total
