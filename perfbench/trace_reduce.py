"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but
``jax.profiler.ProfileData``.

What is read: on every device plane (``/device:TPU:<n>``) the line
``XLA Ops`` (one event per executed HLO operation, named by its whole
HLO text; a Pallas kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``) and the line ``XLA Modules``
(one event per executed program, named ``jit_<function>(<id>)``); on
the host plane the benchmark's own
``jax.profiler.TraceAnnotation`` spans, whose names start with ``pb.``.
``pb.window`` spans the traced window.  A trace with no device plane
(the CPU profiler's, in a rehearsal) has spans and no operations.

    python -m perfbench.trace_reduce <file.xplane.pb>     # look at one
    python -m perfbench.trace_reduce <file.xplane.pb> <start_s> <seconds> <out.txt>
                                            # cut a small fixture from one
"""
import glob
import os
import re
import sys
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ANNOTATION_PREFIX = "pb."
WINDOW = "pb.window"


@dataclass
class Trace:
    t0: float                       # window start, seconds on the trace clock
    t1: float
    n_devices: int
    ops: list = field(default_factory=list)      # (device, HLO text, start, end)
    modules: list = field(default_factory=list)  # (device, name, start, end)
    spans: list = field(default_factory=list)    # (name, start, end) host

    @property
    def window_s(self):
        return self.t1 - self.t0


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _stats(event):
    try:
        return {k: v for k, v in event.stats}
    except Exception:               # noqa: BLE001 — a stat jax cannot decode
        return {}


def load(path):
    """A trace from an ``.xplane.pb`` file, or from the text form that
    :func:`cut` writes (``*.txt``)."""
    from jax.profiler import ProfileData
    if path.endswith(".txt"):
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = list(data.planes)
    device_planes = [(int(DEVICE_PLANE.match(p.name).group(1)), p)
                     for p in planes if DEVICE_PLANE.match(p.name)]
    ops, modules, spans = [], [], []
    for dev, plane in device_planes:
        for line in plane.lines:
            if line.name == OPS_LINE:
                for e in line.events:       # the name is the HLO text
                    s = e.start_ns * 1e-9
                    ops.append((dev, e.name, s, s + e.duration_ns * 1e-9))
            elif line.name == MODULES_LINE:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    modules.append((dev, e.name, s,
                                    s + e.duration_ns * 1e-9))
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns * 1e-9
                if e.name.startswith(ANNOTATION_PREFIX):
                    spans.append((e.name, s, s + e.duration_ns * 1e-9))
    window = [sp for sp in spans if sp[0] == WINDOW]
    if window:
        t0, t1 = window[0][1], window[0][2]
    elif ops:
        t0, t1 = min(o[2] for o in ops), max(o[3] for o in ops)
    else:
        raise ValueError(f"{path}: no device operation and no window")
    clip = lambda s, e: (max(s, t0), min(e, t1))    # noqa: E731
    ops = [(d, n, *clip(s, e)) for d, n, s, e in ops if e > t0 and s < t1]
    modules = [(d, n, s, e) for d, n, s, e in modules if s >= t0 and e <= t1]
    return Trace(t0, t1, max(1, len(device_planes)), ops, modules, spans)


def _union(intervals):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_seconds(trace):
    """Seconds in which an operation ran on the device, averaged over
    the device planes."""
    total = 0.0
    for dev in {o[0] for o in trace.ops}:
        total += sum(e - s for s, e in _union(
            (o[2], o[3]) for o in trace.ops if o[0] == dev))
    return total / trace.n_devices


def executions(trace, program):
    """[(device, start, end, its operations that lie whole inside)] of
    the whole executions of ``jit_<program>`` in the window, device by
    device: on several devices the executions overlap in time, and an
    operation belongs to an execution of its own device.  Swept once a
    trace and program."""
    cache = trace.__dict__.setdefault("_executions", {})
    if program not in cache:
        runs = sorted((d, s, e) for d, n, s, e in trace.modules
                      if n.startswith(f"jit_{program}("))
        ops = sorted(trace.ops, key=lambda o: (o[0], o[2]))
        out, at = [], 0
        for dev, r0, r1 in runs:
            while at < len(ops) and (ops[at][0], ops[at][2]) < (dev, r0):
                at += 1
            inside = []
            while at < len(ops) and ops[at][0] == dev and ops[at][2] < r1:
                if ops[at][3] <= r1:
                    inside.append(ops[at])
                at += 1
            out.append((dev, r0, r1, inside))
        cache[program] = out
    return cache[program]


def op_seconds(trace, pattern):
    """Summed device time and count of the operations whose HLO text
    matches ``pattern`` (per device: averaged over planes)."""
    rx = re.compile(pattern)
    hit = [o for o in trace.ops if rx.search(o[1])]
    return (sum(o[3] - o[2] for o in hit) / trace.n_devices,
            len(hit) // trace.n_devices)


HLO = re.compile(r"^%?([^\s.]+)[.\d]* = \(?(\w+\[[\d,]*\])")


def kind_and_shape(name):
    """``copy f32[24,769,16,16,64]`` from an event's HLO text
    (``%copy.79 = f32[24,769,16,16,64]{...} copy(...)``): the
    instruction's name without its number, which on the TPU says what a
    fusion holds (``convolution_add_fusion``), and its (first) result
    shape.  The 24 layers' instances of one operation share it."""
    m = HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def top_ops(trace, n=10):
    """[[name, seconds], ...]: the device operations that took most
    time in the window, summed by :func:`kind_and_shape`, each name
    followed by how many executions it sums."""
    total, count = {}, {}
    for d, name, s, e in trace.ops:
        name = kind_and_shape(name)
        total[name] = total.get(name, 0.0) + (e - s) / trace.n_devices
        count[name] = count.get(name, 0) + 1
    return [[f"{k} x{count[k] // trace.n_devices}", v]
            for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace, n=10):
    """[[what the host was doing, seconds], ...]: device 0's idle time
    in the window, summed by the innermost benchmark span that covers
    each gap's middle (``unannotated`` where none does), largest
    first."""
    first = min((o[0] for o in trace.ops), default=0)
    busy = _union((o[2], o[3]) for o in trace.ops if o[0] == first)
    edges = [trace.t0] + [x for iv in busy for x in iv] + [trace.t1]
    spans = [sp for sp in trace.spans if sp[0] != WINDOW]
    total = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = (min(cover, key=lambda sp: sp[2] - sp[1])[0]
                if cover else "unannotated")
        total[name] = total.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def cut(path, start_s, seconds, out_path):
    """Write the part of a trace that this file reads (device
    operations, programs, ``pb.`` spans) between ``start_s`` and
    ``start_s + seconds`` after the window opens, as an XSpace text
    proto: a small fixture for the test of this file."""
    tr = load(path)
    a, b = tr.t0 + start_s, tr.t0 + start_s + seconds
    esc = lambda x: x.replace("\\", "\\\\").replace('"', '\\"')  # noqa: E731

    def plane(pid, name, lines):
        meta, out = {}, [f'planes {{ id: {pid} name: "{name}"']
        for lid, (lname, events) in enumerate(lines, 1):
            out.append(f'  lines {{ id: {lid} name: "{lname}"')
            for ename, s, e in events:
                mid = meta.setdefault(ename, len(meta) + 1)
                out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{round((s - a) * 1e12)} duration_ps: "
                           f"{round((e - s) * 1e12)} }}")
            out.append("  }")
        for ename, mid in meta.items():
            out.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                       f'name: "{esc(ename)}" }} }}')
        out.append("}")
        return out

    inside = lambda s, e: s >= a and e <= b     # noqa: E731

    def brief(name):        # enough of the HLO text for the patterns
        if len(name) <= 90:
            return name
        target = re.search(r'custom_call_target="[^"]*"', name)
        return name[:70] + " ... " + (target.group(0) if target else "")
    text = []
    for dev in sorted({o[0] for o in tr.ops}):
        text += plane(dev + 1, f"/device:TPU:{dev}", [
            (OPS_LINE, [(brief(n), s, e) for d, n, s, e in tr.ops
                        if d == dev and inside(s, e)]),
            (MODULES_LINE, [(n, s, e) for d, n, s, e in tr.modules
                            if d == dev and inside(s, e)])])
    spans = [(n, max(s, a), min(e, b)) for n, s, e in tr.spans
             if e > a and s < b and n != WINDOW]
    text += plane(100, "/host:CPU", [
        ("benchmark", [(WINDOW, a, b)] + spans)])
    with open(out_path, "w") as f:
        f.write("\n".join(text) + "\n")


def describe(path, n=25):
    """What a person looks at before writing a reader: planes, lines,
    and the names that took most time."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            total, count, sample = {}, 0, {}
            for e in line.events:
                count += 1
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns
                sample.setdefault(e.name, e)
            out.append(f"  LINE {line.name}: {count} events")
            for name, ns in sorted(total.items(),
                                   key=lambda kv: -kv[1])[:n]:
                st = {k: str(v)[:80] for k, v in _stats(sample[name]).items()}
                out.append(f"    {ns * 1e-6:10.3f} ms  {name[:90]}  {st}")
    return "\n".join(out)


if __name__ == "__main__":
    if len(sys.argv) == 2:
        print(describe(sys.argv[1]))
    else:       # <file> <start_s> <seconds> <out.txt>
        cut(sys.argv[1], float(sys.argv[2]), float(sys.argv[3]), sys.argv[4])
