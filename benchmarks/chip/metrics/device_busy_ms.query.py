"""Device (XLA ops, ``kernels/``): the union of device-op intervals in
the window, per query. Moves ``query_s``."""
import layers


def read(ctx):
    return layers.device_busy_ms(ctx)
