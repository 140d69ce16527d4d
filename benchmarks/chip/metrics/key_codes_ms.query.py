"""Engine and host operators (``exec/``): the ``key_codes`` spans (host
key lowering, factorization, probe runs, segment ids and slab
partition) per query. Moves ``query_s``."""
import layers


def read(ctx):
    return layers.span_ms(ctx, "key_codes")
