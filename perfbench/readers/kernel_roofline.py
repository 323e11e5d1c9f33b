"""A kernel's share of its roofline: the least time the chip could take
for the window's calls (``params.work`` gives their operations and
bytes from shapes; the larger of operations over peak FLOP/s and bytes
over peak bytes/s) over the kernel's summed device time in the trace.
Nothing matched in the trace: nothing returned."""
from .. import kernels, trace_reduce, work


def read(metric, ctx):
    if ctx.peak is None:
        return None
    p = metric["params"]
    seconds, calls = trace_reduce.op_seconds(ctx.trace, p["pattern"])
    if not calls or seconds <= 0:
        return None
    ops, nbytes = work.resolve(p["work"])(ctx)
    least, bound_by = kernels.least_seconds(ops, nbytes, ctx.peak)
    ctx.note(f"{metric['name']}: {calls} kernel calls, {seconds:.4f} s on "
             f"the device; least {least:.4f} s, bound by {bound_by}")
    return 100.0 * least / seconds
