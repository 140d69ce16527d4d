"""Device: XLA executables compiled or loaded inside the window (JAX's
``backend_compile`` monitoring events); set-up warms every shape, so it
should read 0. Moves ``query_s``."""


def read(ctx):
    return float(ctx.compiles)
