"""How far a running count moved inside the traced window: the tag
``params.tag`` of the window's last ``mx.<params.span>`` phase less
that of its first.  A program without that phase or tag: nothing
returned."""
from .. import span_reduce


def read(metric, ctx):
    p = metric["params"]
    spans, _names = span_reduce.of(ctx)
    found = [s for s in spans.named(p["span"]) if p["tag"] in s.tags]
    if not found:
        return None
    return float(found[-1].tags[p["tag"]] - found[0].tags[p["tag"]])
