"""The device's idle share of the traced window: 1 - (union of the
device operations' intervals) / window."""
from .. import trace_reduce


def read(metric, ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_seconds(ctx.trace)
                    / ctx.trace.window_s)
