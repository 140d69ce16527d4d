"""Device (host-to-device transfer): the bytes the ``kernel`` spans copy
to the device (their ``h2d_bytes``), in MB (1e6 bytes) per query. Moves
``query_s``."""


def read(ctx):
    copied = [s.attrs["h2d_bytes"] for s in ctx.spans
              if s.name == "kernel" and "h2d_bytes" in s.attrs]
    if not copied or not ctx.units:
        return None
    return sum(copied) / 1e6 / ctx.units
