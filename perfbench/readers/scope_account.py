"""The device-time account of one program's step (``params.program``,
which the trace shows as ``jit_<program>``): every operation that ran
inside a whole execution in the window goes, by the jax name it came
from (the stat ``tf_op``), to exactly one of

- a *leaf*: the innermost ``mx.`` scope in its name that is no
  container (``CONTAINERS``, which mxnet_tpu.tracing.SCOPE_CONTAINERS
  names too: ``mx.fwd`` holds the forward's leaves and, transposed, the
  backward's); a fusion counts where its root does;
- *unscoped*: its name is an operation's of this program
  (``jit(<program>)/...``) and has no leaf in it;
- *unnamed*: it has no jax operation's name: none at all (``copy-done``,
  ``slice-done``) or an argument's (``params['...']``: the layout
  copies XLA makes of a leaf).

``params.what`` picks the metric:

- ``step``: the median duration of the program's executions (the
  ``XLA Modules`` events that lie whole in the window), ms;
- ``scopes``: ms a step under the leaves ``params.scopes``.  Nothing
  returned, never 0, where no operation of the program carries any of
  them: a model without that part, a program from before these scopes
  (the parent's), or a stale executable (jax leaves scope names out of
  its persistent cache's key, PERF.md section 7); the note says what
  the program does carry;
- ``unnamed``, ``unscoped``: ms a step, with the five largest kinds (by
  HLO opcode and shape and the argument's name; by jax name; digits
  folded) in the note.

The leaves, unnamed and unscoped sum to the operations' time a step by
construction; ``step`` notes that sum beside the executions' own
duration.  A trace in which the program did not run: nothing returned.
On several devices each device's operations are matched to that
device's executions, and a step is the mean over all of them.  Imports
nothing of the program.

``METRICS`` names the eight metrics the account is read by, with the
``params`` of each one's file (``perfbench/metrics/<name>.json``, PR 43;
``tests/perfbench_checks/test_scope_account.py`` holds each file to the
table).  ``train.ffn_device_ms`` and ``train.attn_dense_device_ms`` list
the cells whose model has the part; the other six are read in every
training cell.

    python -m perfbench.readers.scope_account <file.xplane.pb>  # the account
"""
import re
import statistics
import sys
from collections import Counter
from types import SimpleNamespace

from .. import span_reduce, trace_reduce

SCOPE = re.compile(r"(?:^|[/(])(mx\.[\w.]+)(?=[/):]|$)")
KIND = re.compile(r"^%?([^\s.]+)\S* = \(?(\w+\[[\d,]*\])")
CONTAINERS = ("mx.fwd",)
PROGRAM = "mx_train_step"
METRICS = {
    "train.step_device_ms": {"what": "step"},
    "train.attn_proj_device_ms": {"what": "scopes",
                                  "scopes": ["mx.attn.proj"]},
    "train.head_loss_device_ms": {"what": "scopes",
                                  "scopes": ["mx.head", "mx.loss"]},
    "train.norm_embed_device_ms": {"what": "scopes",
                                   "scopes": ["mx.norm", "mx.embed"]},
    "train.ffn_device_ms": {"what": "scopes", "scopes": ["mx.ffn.dense"]},
    "train.attn_dense_device_ms": {"what": "scopes",
                                   "scopes": ["mx.attn.dense"]},
    "train.unnamed_device_ms": {"what": "unnamed"},
    "train.unscoped_device_ms": {"what": "unscoped"},
}


def account(trace, names, program):
    """The account of ``jit_<program>``, seconds a step: ``step``
    (median execution), ``steps``, ``ops`` (summed operation time),
    ``leaves`` {leaf: seconds}, ``unnamed`` and ``unscoped`` {kind:
    seconds}, ``carried`` (every ``mx.`` scope in the program's names)
    and ``nested`` (seconds under two different leaves at once, counted
    at the inner one).  None where the program did not run."""
    runs = sorted((d, s, e) for d, n, s, e in trace.modules
                  if n.startswith(f"jit_{program}("))
    if not runs:
        return None
    leaves, unnamed, unscoped = Counter(), Counter(), Counter()
    carried, kinds, nested, run = set(), {}, 0.0, 0
    for dev, text, s, e in sorted(trace.ops, key=lambda o: (o[0], o[2])):
        while run < len(runs) and (runs[run][0], runs[run][2]) < (dev, s):
            run += 1
        if run == len(runs) or runs[run][0] != dev \
                or s < runs[run][1] or e > runs[run][2]:
            continue
        if text not in kinds:
            op = names.get(text, "")
            found = SCOPE.findall(op)
            carried.update(found)
            inner = [f for f in found if f not in CONTAINERS]
            if inner:
                kinds[text] = (leaves, inner[-1], len(set(inner)) > 1)
            elif op.startswith(f"jit({program})"):
                kinds[text] = (unscoped, _brief(op), False)
            else:
                m = KIND.match(text)    # copy f32[8,2688,1856]
                kind = f"{m.group(1)} {m.group(2)}" if m else text[:60]
                if op:
                    kind += " of " + re.sub(r"\d+", "N", op).rstrip(":")
                kinds[text] = (unnamed, kind, False)
        where, key, two = kinds[text]
        where[key] += (e - s) / len(runs)
        nested += two * (e - s) / len(runs)
    return SimpleNamespace(
        step=statistics.median(e - s for _d, s, e in runs), steps=len(runs),
        ops=sum(map(sum, (leaves.values(), unnamed.values(),
                          unscoped.values()))),
        leaves=leaves, unnamed=unnamed, unscoped=unscoped,
        carried=carried, nested=nested)


def _brief(op):
    """A jax name without the program, its digits folded, the blocks
    between the outermost and the last two components left out."""
    parts = re.sub(r"\d+", "N", op).rstrip(":").split("/")[1:]
    return "/".join(parts if len(parts) <= 4
                    else parts[:1] + ["..."] + parts[-2:])


def _largest(kinds, n=5):
    return "; ".join(f"{1e3 * v:.4f} {k}"
                     for k, v in kinds.most_common(n)) or "none"


def read(metric, ctx):
    p = metric["params"]
    program, what = p["program"], p["what"]
    if not hasattr(ctx, "accounts"):
        ctx.accounts = {}
    if program not in ctx.accounts:
        _spans, names = span_reduce.of(ctx)
        ctx.accounts[program] = account(ctx.trace, names, program)
    a = ctx.accounts[program]
    if a is None:
        return None
    name = metric["name"]
    if what == "step":
        named = sum(a.leaves.values())
        ctx.note(
            f"{name}: {1e3 * a.step:.4f} ms, the median of {a.steps} "
            f"executions of jit_{program}; its operations sum to "
            f"{1e3 * a.ops:.4f} ms a step = {1e3 * named:.4f} under "
            f"{len(a.leaves)} leaves + "
            f"{1e3 * sum(a.unnamed.values()):.4f} unnamed + "
            f"{1e3 * sum(a.unscoped.values()):.4f} unscoped "
            f"({1e3 * a.nested:.4f} under two leaves, counted at the "
            f"inner); by leaf: "
            + ", ".join(f"{k} {1e3 * v:.4f}"
                        for k, v in sorted(a.leaves.items())))
        return 1e3 * a.step
    if what == "scopes":
        if not a.carried & set(p["scopes"]):
            ctx.note(
                f"{name}: jit_{program} carries {sorted(a.carried)} and "
                f"none of {p['scopes']}: a model without that part, or an "
                f"executable from before these scopes, served by the "
                f"compile cache?")
            return None
        got = sum(a.leaves[s] for s in p["scopes"])
        ctx.note(f"{name}: {1e3 * got:.4f} ms a step under {p['scopes']} "
                 f"over {a.steps} executions of jit_{program}")
        return 1e3 * got
    kinds = getattr(a, what)            # "unnamed" or "unscoped"
    got = sum(kinds.values())
    ctx.note(f"{name}: {1e3 * got:.4f} ms a step, "
             f"{100 * got / a.step:.2f}% of the step; the largest: "
             f"{_largest(kinds)}")
    return 1e3 * got


def describe(path, program=PROGRAM):
    """The account of a trace file, as lines: each of ``METRICS`` with
    its note, then every leaf and the twelve largest kinds of the
    unnamed and of the unscoped."""
    data, raw = span_reduce._data(path)
    notes = []
    ctx = SimpleNamespace(trace=trace_reduce.load(path), note=notes.append,
                          mx=(span_reduce.load(path, data),
                              span_reduce.op_names(raw)))
    out = []
    for name, params in METRICS.items():
        got = read({"name": name, "params": {"program": program, **params}},
                   ctx)
        out.append(f"{name} = {got}")
    a = ctx.accounts[program]
    if a is None:
        return f"jit_{program} did not run in {path}"
    out += notes
    out += [f"  {1e3 * v:10.4f} ms  {k}" for k, v in sorted(a.leaves.items())]
    for what in ("unnamed", "unscoped"):
        kinds = getattr(a, what)
        out.append(f"  {1e3 * sum(kinds.values()):10.4f} ms  {what}")
        out += [f"    {1e3 * v:10.4f} ms  {k}"
                for k, v in kinds.most_common(12)]
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
