"""The whole step's share of the chip's peak: the model's FLOPs for
the work the window finished (``perfbench/flops.py``, named by the
configuration's ``flops`` key) over window seconds times chips times
peak FLOP/s."""
from .. import work


def read(metric, ctx):
    if ctx.peak is None:
        return None
    total = work.resolve(ctx.cfg["flops"])(ctx)
    if not total:
        return None
    return 100.0 * total / (ctx.facts["window_s"] * len(ctx.devices)
                            * ctx.peak["flops_per_s"])
