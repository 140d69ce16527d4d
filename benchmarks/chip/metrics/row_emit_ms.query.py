"""Engine and host operators (``exec/``): the ``row_emit`` spans (join
emission and the right side's gathers) per query. Moves ``query_s``."""
import layers


def read(ctx):
    return layers.span_ms(ctx, "row_emit")
