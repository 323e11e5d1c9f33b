"""The program's own phases and scopes in a profiler trace.

What is read, beside what ``trace_reduce`` reads: on the host planes the
program's ``mx.`` phases (``mxnet_tpu.tracing.phase``: a
``jax.profiler.TraceAnnotation`` whose keyword tags come back as the
event's stats), each with the thread (the plane's line) it ran on,
clipped to ``pb.window``; and for the device's operations the name of
the jax operation each came from (``jit(mx_train_step)/mx.optim/add``:
the program, then the ``jax.named_scope``s around it).  On this libtpu
that name is the stat ``tf_op`` of the event's *metadata*, which
``jax.profiler.ProfileData`` does not surface (an event's ``stats`` are
its own: ``device_offset_ps``, ``device_duration_ps``), so the
metadata table is read from the serialized XSpace's wire format here.
A trace of a program without phases or scopes reads as none of either.

    python -m perfbench.span_reduce <file.xplane.pb>      # look at one
    python -m perfbench.span_reduce <file.xplane.pb> <steps> <ops> <out.txt>
                  # cut a small fixture: the window's first <steps>
                  # whole steps, the <ops> longest operations of each
"""
import re
import sys
from collections import namedtuple
from dataclasses import dataclass, field

from . import trace_reduce

PREFIX = "mx."
OP_NAME_STAT = "tf_op"

Span = namedtuple("Span", "name thread start end tags")


@dataclass
class Spans:
    t0: float                   # the window, seconds on the trace clock
    t1: float
    epoch_ns: int               # the session's start on the epoch clock
    spans: list = field(default_factory=list)   # Span, clipped, by start

    def named(self, name):
        """The spans called ``mx.<name>`` that began and ended inside
        the window (one the window cuts is not a whole unit)."""
        return [s for s in self.spans if s.name == PREFIX + name
                and s.start > self.t0 and s.end < self.t1]


def _data(path):
    """(ProfileData, the serialized XSpace) of an ``.xplane.pb`` file or
    of the text form that :func:`cut` writes."""
    from jax.profiler import ProfileData
    if path.endswith(".txt"):
        with open(path) as f:
            raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    else:
        with open(path, "rb") as f:
            raw = f.read()
    return ProfileData.from_serialized_xspace(raw), raw


def load(path, data=None):
    """The ``mx.`` spans of a trace, clipped to ``pb.window`` (the
    whole trace where there is no window)."""
    data = data or _data(path)[0]
    found, window, epoch_ns = [], None, 0
    for plane in data.planes:
        if plane.name == "Task Environment":
            epoch_ns = int(dict(plane.stats).get("profile_start_time", 0))
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for n, line in enumerate(plane.lines):
            thread = f"{plane.name}#{n}:{line.name}"
            for e in line.events:
                if e.name == trace_reduce.WINDOW:
                    window = (e.start_ns * 1e-9, e.end_ns * 1e-9)
                elif e.name.startswith(PREFIX):
                    found.append(Span(e.name, thread, e.start_ns * 1e-9,
                                      e.end_ns * 1e-9, dict(e.stats)))
    if window is None:
        window = (min((s.start for s in found), default=0.0),
                  max((s.end for s in found), default=0.0))
    t0, t1 = window
    spans = sorted((s._replace(start=max(s.start, t0), end=min(s.end, t1))
                    for s in found if s.end > t0 and s.start < t1),
                   key=lambda s: s.start)
    return Spans(t0, t1, epoch_ns, spans)


def coverage(spans, thread):
    """The share of the window that ``thread``'s ``mx.`` spans cover."""
    covered = trace_reduce._union((s.start, s.end) for s in spans.spans
                                  if s.thread == thread)
    return sum(e - s for s, e in covered) / (spans.t1 - spans.t0)


def of(ctx):
    """(the spans, the operations' names) of a traced run of the
    benchmark, read once a run and kept on its ``ctx``."""
    if getattr(ctx, "mx", None) is None:
        from . import harness
        path = trace_reduce.find_xplane(
            harness.Profiler(ctx.cell["name"]).dir)
        data, raw = _data(path)
        ctx.mx = (load(path, data), op_names(raw))
    return ctx.mx


# ------------------------------------------------- the XSpace's wire format
def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one serialized message: an int for a
    varint, the bytes for everything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, value


def _entry(buf):
    """The value message of one map entry, as {field: last value}."""
    return dict(_fields(dict(_fields(buf))[2]))


def op_names(raw):
    """{HLO text of a device operation: the jax operation it came
    from}, from the event metadata of the device planes (XPlane 4:
    event_metadata, 5: stat_metadata; XEventMetadata 2: name, 5: stats;
    XStat 1: metadata_id, 5: str_value, 7: ref_value).  Empty where the
    trace's operations carry no such stat."""
    out = {}
    for number, plane in _fields(raw):
        if number != 1:
            continue
        parts = list(_fields(plane))
        name = next((v for f, v in parts if f == 2), b"").decode()
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        stat_name = {}
        for f, v in parts:
            if f == 5:
                meta = _entry(v)
                stat_name[meta.get(1)] = meta.get(2, b"").decode()
        wanted = {k for k, v in stat_name.items() if v == OP_NAME_STAT}
        for f, v in parts:
            if f != 4:
                continue
            text = op = None
            for f2, v2 in _fields(dict(_fields(v))[2]):
                if f2 == 2:
                    text = v2.decode(errors="replace")
                elif f2 == 5:
                    stat = dict(_fields(v2))
                    if stat.get(1) in wanted:
                        op = (stat[5].decode(errors="replace") if 5 in stat
                              else stat_name.get(stat.get(7), ""))
            if text and op:
                out[text] = op
    return out


# ------------------------------------------------------ device time by scope
def scope_seconds(trace, names, program, scopes):
    """Device seconds a step in the operations of ``jit_<program>``
    whose jax operation lies under one of ``scopes`` (a fusion counts
    where its root does), over the program's whole executions in the
    window.  Returns (seconds a step, executions, seconds a step of the
    program's operations under none of them and under no scope at all);
    None where the program did not run.  On several devices a step is
    the mean over every device's executions."""
    runs = trace_reduce.executions(trace, program)
    if not runs:
        return None
    under = re.compile(r"(^|[/(])(%s)([/)]|$)" % "|".join(
        re.escape(s) for s in scopes))
    ours = f"jit({program})/"
    inside = other = 0.0
    for _dev, _r0, _r1, ops in runs:
        for _d, text, s, e in ops:
            op = names.get(text, "")
            if op.startswith(ours) and under.search(op[len(ours):]):
                inside += e - s
            else:
                other += e - s
    return inside / len(runs), len(runs), other / len(runs)


def scoped(names, program):
    """Whether any operation of ``program`` carries a ``mx.`` scope."""
    ours = f"jit({program})/"
    return any(op.startswith(ours) and PREFIX in op for op in names.values())


# ------------------------------------------------------------- by hand, CLI
def cut(path, steps, ops, out_path, program="mx_train_step"):
    """Write, as an XSpace text proto, the window's first ``steps``
    whole executions of ``program`` with the ``ops`` longest operations
    of each (named, with the jax operation each came from) and the
    ``mx.`` spans (with their tags) that began while they ran: a small
    fixture for the tests of this file and of its readers."""
    data, raw = _data(path)
    trace, spans, names = trace_reduce.load(path), load(path, data), \
        op_names(raw)
    runs = sorted((s, e, n) for d, n, s, e in trace.modules
                  if n.startswith(f"jit_{program}("))[:steps]
    a, b = runs[0][0] - 1e-3, runs[-1][1] + 1e-3
    esc = lambda x: x.replace("\\", "\\\\").replace('"', '\\"')  # noqa: E731
    ps = lambda t: round((t - a) * 1e12)                         # noqa: E731
    brief = lambda text: text if len(text) <= 90 else text[:86] + " ..."  # noqa: E731

    device = ['planes { id: 1 name: "/device:TPU:0"',
              '  lines { id: 1 name: "XLA Ops"']
    meta = {}
    for s, e, _n in runs:
        inside = [o for o in trace.ops if o[2] >= s and o[3] <= e]
        longest = sorted(inside, key=lambda o: o[2] - o[3])[:ops]
        for _d, text, os_, oe in sorted(longest, key=lambda o: o[2]):
            mid = meta.setdefault((brief(text), names.get(text, "")),
                                  len(meta) + 1)
            device.append(f"    events {{ metadata_id: {mid} offset_ps: "
                          f"{ps(os_)} duration_ps: {ps(oe) - ps(os_)} }}")
    device += ["  }", '  lines { id: 2 name: "XLA Modules"']
    for s, e, n in runs:
        mid = meta.setdefault((n, ""), len(meta) + 1)
        device.append(f"    events {{ metadata_id: {mid} offset_ps: {ps(s)} "
                      f"duration_ps: {ps(e) - ps(s)} }}")
    device.append("  }")
    for (text, op), mid in meta.items():
        stat = f' stats {{ metadata_id: 1 str_value: "{esc(op)}" }}' \
            if op else ""
        device.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                      f'name: "{esc(text)}"{stat} }} }}')
    device += [f'  stat_metadata {{ key: 1 value {{ id: 1 name: '
               f'"{OP_NAME_STAT}" }} }}', "}"]

    host = ['planes { id: 100 name: "/host:CPU"']
    kept = [s for s in spans.spans if a <= s.start < b]
    threads = sorted({s.thread for s in kept})
    meta, stats = {trace_reduce.WINDOW: 1}, {}
    for lid, thread in enumerate(threads, 1):
        host.append(f'  lines {{ id: {lid} name: '
                    f'"{esc(thread.split(":", 1)[1])}"')
        if lid == 1:
            host.append(f"    events {{ metadata_id: 1 offset_ps: 0 "
                        f"duration_ps: {ps(b)} }}")
        for s in kept:
            if s.thread != thread:
                continue
            mid = meta.setdefault(s.name, len(meta) + 1)
            tags = "".join(
                f" stats {{ metadata_id: {stats.setdefault(k, len(stats) + 1)}"
                f" int64_value: {int(v)} }}" for k, v in s.tags.items())
            host.append(f"    events {{ metadata_id: {mid} offset_ps: "
                        f"{ps(s.start)} duration_ps: "
                        f"{ps(min(s.end, b)) - ps(s.start)}{tags} }}")
        host.append("  }")
    for name, mid in meta.items():
        host.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                    f'name: "{esc(name)}" }} }}')
    for name, sid in stats.items():
        host.append(f'  stat_metadata {{ key: {sid} value {{ id: {sid} '
                    f'name: "{esc(name)}" }} }}')
    host.append("}")
    with open(out_path, "w") as f:
        f.write("\n".join(device + host) + "\n")


def describe(path, programs=("mx_train_step",)):
    """What a person looks at: each ``mx.`` span's count, median and
    last tags; each thread's cover; device time a step by scope."""
    import statistics
    data, raw = _data(path)
    spans, out = load(path, data), []
    out.append(f"window {spans.t1 - spans.t0:.4f} s, session began at "
               f"{spans.epoch_ns} ns of the epoch")
    for name in sorted({s.name for s in spans.spans}):
        ss = spans.named(name[len(PREFIX):])
        if ss:
            out.append(f"  {name}: {len(ss)} spans, median "
                       f"{statistics.median(s.end - s.start for s in ss) * 1e3:.4f}"
                       f" ms, sum {sum(s.end - s.start for s in ss):.4f} s, "
                       f"last tags {ss[-1].tags}")
    for thread in sorted({s.thread for s in spans.spans}):
        out.append(f"  thread {thread}: mx. spans cover "
                   f"{100 * coverage(spans, thread):.2f}% of the window")
    names = op_names(raw)
    out.append(f"{len(names)} device operations carry a {OP_NAME_STAT} stat")
    trace = trace_reduce.load(path)
    for program in programs:
        for scopes in (["mx.fwd", "mx.loss"], ["mx.optim"],
                       ["mx.collective"]):
            got = scope_seconds(trace, names, program, scopes)
            if got:
                out.append(f"  jit_{program} under {scopes}: "
                           f"{got[0] * 1e3:.4f} ms a step over {got[1]} "
                           f"steps ({got[2] * 1e3:.4f} ms elsewhere)")
    return "\n".join(out)


if __name__ == "__main__":
    if len(sys.argv) == 2:
        print(describe(sys.argv[1]))
    else:       # <file> <steps> <ops> <out.txt>
        cut(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
