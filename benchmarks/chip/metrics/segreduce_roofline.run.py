"""Kernel: segment reduce (``kernels/segment_sum``, XLA segment ops).

The least time the chip could take for the window's segment reductions,
over the device time of every op in a module whose name holds
``segment_sum`` or ``segment_reduce``, in percent. The least time comes
from each call's logical shapes, as the reference counts them (the same
whatever implements the call): ``rows`` x (value + int32 segment id +
bool mask) bytes in and ``segments`` x (sum + int32 count) bytes out, at
the chip's HBM bandwidth. One add per row is far below the chip's
operation peak, so the bytes bound holds. Moves ``run_s``.
"""
from peaks import peaks


def read(ctx):
    seg_s = sum(s for name, s in ctx.trace.op_seconds().items()
                if "segment_sum" in name.split("/")[0]
                or "segment_reduce" in name.split("/")[0])
    nbytes = sum(rows * (vb + 4 + 1) + segs * (vb + 4)
                 for w in ctx.work
                 for rows, segs, vb in w.get("segment_reduce", ()))
    if seg_s <= 0 or nbytes <= 0:
        return None
    least_s = nbytes / peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / seg_s
