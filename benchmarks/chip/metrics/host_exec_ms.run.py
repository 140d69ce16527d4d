"""Engine and host operators (``core/engine.py``, ``exec/``): time
inside ``node`` spans with no device op running, per run. Moves
``run_s``."""
import layers


def read(ctx):
    return layers.host_exec_ms(ctx)
