"""Optimizer (``optimizer/passes.py``): the ``optimizer_pass`` spans per
query. Moves ``query_s``."""
import layers


def read(ctx):
    return layers.span_ms(ctx, "optimizer_pass")
