"""Exchange (``exec/sharded.py``): device time of the all-to-all ops
(XLA ``all-to-all``, the sharded backend's exchange; the compiled
module names them ``all_to_all.<n>``), in milliseconds per device per
run. Moves ``run_s``."""


def read(ctx):
    seconds = sum(s for name, s in ctx.trace.op_seconds().items()
                  if "all-to-all" in name.split("/")[-1].replace("_", "-"))
    if seconds <= 0 or not ctx.units:
        return None
    return 1e3 * seconds / ctx.trace.n_devices / ctx.units
