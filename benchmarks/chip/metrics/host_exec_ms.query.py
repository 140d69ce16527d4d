"""Engine and host operators (``core/engine.py``, ``exec/``): time
inside ``node`` spans with no device op running, per query. Moves
``query_s``."""
import layers


def read(ctx):
    return layers.host_exec_ms(ctx)
