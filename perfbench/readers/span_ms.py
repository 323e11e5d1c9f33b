"""The median duration, in milliseconds, of the program's ``mx.<span>``
phases that ran inside the traced window (``params.span``; the spans
come from ``mxnet_tpu.tracing.phase``).  A program without that phase:
nothing returned."""
import statistics

from .. import span_reduce


def read(metric, ctx):
    spans, _names = span_reduce.of(ctx)
    found = spans.named(metric["params"]["span"])
    if not found:
        return None
    ctx.note(f"{metric['name']}: {len(found)} mx.{metric['params']['span']} "
             f"spans in the window")
    return 1e3 * statistics.median(s.end - s.start for s in found)
