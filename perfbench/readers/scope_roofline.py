"""A scope's share of its roofline: the least time the chip could take
for the work done under ``params.scopes`` in the window's executions of
``params.program`` (``params.work`` gives operations and bytes; the
larger of operations over peak FLOP/s and bytes over peak bytes/s) over
the device time of the operations under those scopes, forward or
transposed.  ``params.pattern`` keeps only the operations whose HLO
text matches it (a Pallas kernel's ``custom_call_target``);
``params.also`` adds the program's operations whose HLO text matches
it wherever they lie (XLA's grouped-product kernels carry no jax name,
so no scope).  The program not in the trace, nothing counted, or no
work recorded: nothing returned."""
import re

from .. import kernels, span_reduce, work
from ..trace_reduce import Trace


def read(metric, ctx):
    if ctx.peak is None:
        return None
    p = metric["params"]
    _spans, names = span_reduce.of(ctx)
    trace = ctx.trace
    if "pattern" in p:
        rx = re.compile(p["pattern"])
        trace = Trace(trace.t0, trace.t1, trace.n_devices,
                      [o for o in trace.ops if rx.search(o[1])],
                      trace.modules, trace.spans)
    got = span_reduce.scope_seconds(trace, names, p["program"], p["scopes"])
    if got is not None and "also" in p:
        rx = re.compile(p["also"])
        more = span_reduce.scope_seconds(
            Trace(trace.t0, trace.t1, trace.n_devices,
                  [o for o in trace.ops if rx.search(o[1])],
                  trace.modules, trace.spans),
            names, p["program"], p["scopes"])
        got = (got[0] + more[2],) + got[1:]     # those under no such scope
    if got is None or got[0] <= 0:
        return None
    counted = work.resolve(p["work"])(ctx)
    if counted is None:
        return None
    seconds = got[0] * got[1]
    # the work is the whole window's; the device time is that of the
    # program's whole executions inside the traced window
    ops, nbytes = (x * got[1] / ctx.facts["steps"] for x in counted)
    least, bound_by = kernels.least_seconds(ops, nbytes, ctx.peak)
    ctx.note(f"{metric['name']}: {got[1]} executions, {seconds:.4f} s on "
             f"the device under {p['scopes']}; least {least:.4f} s, bound "
             f"by {bound_by}")
    return 100.0 * least / seconds
