"""Device time of the collective operations of one program
(``params.program``, which the trace shows as ``jit_<program>``), a
step: what exists only across chips.

An operation of the ``XLA Ops`` line is a collective where its
instruction's name, or the opcode in its HLO text, is one of
``params.collectives`` (``all-reduce``, ``all-gather``, ...;
``async-collective`` is what the TPU compiler calls the fusion pair it
makes of one), plain or as ``-start`` / ``-done``.  On every device,
inside every whole execution of the program in the window, a collective
is *in progress* during a plain operation, and from the beginning of a
``-start`` to the end of the ``-done`` that closes it (the oldest open
start of its kind: first in, first out).  ``params.what`` picks the
metric, each the mean over all executions on all devices:

- ``total``: ms a step in which a collective is in progress (the union
  of those intervals);
- ``exposed``: of that, ms a step in which the device runs no other
  operation: a plain collective whole, of a pair the two operations
  themselves and whatever idles between them.  Never above ``total``;
- ``roofline``: the least time for the bytes ``params.work`` counts one
  chip sending a step, at the chip's published inter-chip bandwidth
  (``peaks.py`` ``ici_bytes_per_s``), over ``total`` of these kinds, %.

The program not in the trace, or no collective in its executions (one
device): nothing returned, never 0.

    python -m perfbench.readers.collective_time <file.xplane.pb | .txt>
"""
import re
import sys
from types import SimpleNamespace

from .. import trace_reduce, work

KINDS = ("all-reduce", "reduce-scatter", "all-gather", "collective-permute",
         "all-to-all", "async-collective")


def _matcher(kinds):
    alt = "|".join(re.escape(k) for k in kinds)
    named = re.compile(rf"^%?({alt})(-start|-done)?[.\d]* = ")
    opcode = re.compile(rf"[ }})]({alt})(-start|-done)?\(")

    def kind_of(text):
        m = named.match(text) or opcode.search(text)
        return (m.group(1), m.group(2) or "") if m else None

    return kind_of


def _minus(a, b):
    """Seconds of the merged intervals ``a`` outside the merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(b) and b[k][0] < e:
            total += max(0.0, b[k][0] - at)
            at = max(at, b[k][1])
            k += 1
        total += max(0.0, e - at)
    return total


def in_progress(trace, program, kinds):
    """(seconds a step in which a collective of ``kinds`` is in
    progress, seconds a step of that with no other operation running,
    executions, {kind with its form: operations a step}) over the whole
    executions of ``jit_<program>`` on every device; None where the
    program did not run."""
    runs = trace_reduce.executions(trace, program)
    if not runs:
        return None
    kind_of = _matcher(kinds)
    total = exposed = 0.0
    seen = {}
    for _dev, _r0, _r1, ops in runs:
        busy, others, opened = [], [], {}
        for _d, text, s, e in ops:
            kind = kind_of(text)
            if kind is None:
                others.append((s, e))
                continue
            seen[kind[0] + kind[1]] = seen.get(kind[0] + kind[1], 0) + 1
            if kind[1] == "-done" and opened.get(kind[0]):
                busy.append((opened[kind[0]].pop(0), e))
            else:
                busy.append((s, e))
                if kind[1] == "-start":
                    opened.setdefault(kind[0], []).append(s)
        busy = trace_reduce._union(busy)
        total += sum(e - s for s, e in busy)
        exposed += _minus(busy, trace_reduce._union(others))
    n = len(runs)
    return total / n, exposed / n, n, {k: v / n for k, v in seen.items()}


def read(metric, ctx):
    p = metric["params"]
    got = in_progress(ctx.trace, p["program"], p["collectives"])
    if got is None or not got[3]:
        return None
    total, exposed, runs, seen = got
    name, what = metric["name"], p["what"]
    said = ", ".join(f"{k} x{v:g}" for k, v in sorted(seen.items()))
    if what in ("total", "exposed"):
        ctx.note(f"{name}: a collective in progress {1e3 * total:.4f} ms a "
                 f"step, {1e3 * exposed:.4f} of them with no other "
                 f"operation on the device, over {runs} executions of "
                 f"jit_{p['program']} on {ctx.trace.n_devices} devices; a "
                 f"step: {said}")
        return 1e3 * (total if what == "total" else exposed)
    if ctx.peak is None or total <= 0:
        return None
    _ops, nbytes = work.resolve(p["work"])(ctx)
    least = nbytes / ctx.facts["steps"] / ctx.peak["ici_bytes_per_s"]
    ctx.note(f"{name}: {nbytes / ctx.facts['steps']:.0f} bytes a chip sends "
             f"a step, least {1e3 * least:.4f} ms at "
             f"{ctx.peak['ici_bytes_per_s']:.3g} bytes/s; {p['collectives']} "
             f"in progress {1e3 * total:.4f} ms a step ({said})")
    return 100.0 * least / total


def describe(path, program="mx_train_step"):
    notes = []
    ctx = SimpleNamespace(trace=trace_reduce.load(path), note=notes.append)
    read({"name": "total", "params": {"program": program, "what": "total",
                                      "collectives": KINDS}}, ctx)
    return notes[0] if notes else f"no collective of jit_{program} in {path}"


if __name__ == "__main__":
    print(describe(sys.argv[1]))
