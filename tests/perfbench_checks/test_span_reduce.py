"""Checks of ``perfbench/span_reduce.py`` and its three readers
(``span_ms``, ``span_tag_delta``, ``scope_time``) on a small recorded
trace, on the CPU: ``fixtures/train_slice.xspace.txt``, cut by
``span_reduce.cut`` from a traced chip run of
``bert-large.pretrain_b32_l128`` (PR 31): the window's first two whole
executions of ``jit_mx_train_step`` with the twelve longest operations
of each, and the ``mx.train.*`` phases that began while they ran.  The
expected values are worked out again here from the file's text."""
import json
import os
import re
import statistics
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PB = os.path.join(REPO, "perfbench")
sys.path.insert(0, REPO)
FIXTURE = os.path.join(PB, "fixtures", "train_slice.xspace.txt")
PARENT = os.path.join(PB, "fixtures", "serve_slice.xspace.txt")
CELL = "bert-large.pretrain_b32_l128"
NEW = ["train.dispatch_ms", "train.h2d_ms", "train.compiles_in_window",
       "train.optim_device_ms", "train.fwd_bwd_device_ms"]
EVENT = re.compile(r"events \{ metadata_id: (\d+) offset_ps: (\d+) "
                   r"duration_ps: (\d+)((?: stats \{[^}]*\})*) \}")


def _by_hand():
    """{line name: [(event name, jax operation name, offset ps,
    duration ps, {tag: value})]} from the fixture's text alone."""
    text = open(FIXTURE).read()
    out = {}
    for plane in text.split("planes {")[1:]:
        meta = {k: (n.replace('\\"', '"'), op) for k, n, op in re.findall(
            r'event_metadata \{ key: (\d+) value \{ id: \d+ name: '
            r'"((?:[^"\\]|\\.)*)"(?: stats \{ metadata_id: 1 str_value: '
            r'"([^"]*)" \})? \} \}', plane)}
        stat = dict(re.findall(
            r'stat_metadata \{ key: (\d+) value \{ id: \d+ name: "([^"]*)"',
            plane))
        for line in plane.split("lines {")[1:]:
            name = re.search(r'name: "([^"]*)"', line).group(1)
            rows = out.setdefault(name, [])
            for mid, off, dur, stats in EVENT.findall(line):
                tags = {stat[k]: int(v) for k, v in re.findall(
                    r"metadata_id: (\d+) int64_value: (-?\d+)", stats)}
                rows.append((*meta[mid], int(off), int(dur), tags))
    return out


def _ctx(path=FIXTURE):
    from perfbench import span_reduce, trace_reduce
    data, raw = span_reduce._data(path)
    return SimpleNamespace(
        cell={"name": CELL}, trace=trace_reduce.load(path), notes=[],
        mx=(span_reduce.load(path, data), span_reduce.op_names(raw)),
        note=lambda text: None)


def _metric(name):
    with open(os.path.join(PB, "metrics", name + ".json")) as f:
        return json.load(f)


def _read(name, ctx):
    from perfbench import harness
    m = _metric(name)
    return harness.module("readers", m["reader"]).read(m, ctx)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_files_have_their_entries(name):
    """Each metric PR 31 adds is a file and an entry of
    ``BENCHMARK.json`` that agree, read by a reader that is there, and
    neither lists its cells: a listed cell whose reader finds nothing
    fails the run (``perfbench/run.py``), and the parent's program,
    which the driver runs these files over, gives these readers
    nothing."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = _metric(name)
    (entry,) = [e for e in bench["per_layer"] if e["name"] == name]
    assert entry == {k: m[k] for k in ("name", "unit", "better", "source",
                                       "layer", "moves")}
    assert "workloads" not in m and m["moves"] == "train_tokens_per_s"
    assert os.path.exists(os.path.join(PB, "readers", m["reader"] + ".py"))
    assert m["source"] == ("device_trace" if m["reader"] == "scope_time"
                           else "program_span")


@pytest.mark.parametrize("what", ["window", "spans", "tags", "threads",
                                  "op_names", "scopes", "no_program"])
def test_span_reduce_on_the_recorded_trace(what):
    from perfbench import span_reduce as sr
    assert os.path.getsize(FIXTURE) < 100_000
    hand = _by_hand()
    host = [r for line, rows in hand.items()
            if line not in ("XLA Ops", "XLA Modules") for r in rows]
    ctx = _ctx()
    spans, names = ctx.mx
    if what == "window":
        (window,) = [r for r in host if r[0] == "pb.window"]
        assert abs((spans.t1 - spans.t0) - window[3] * 1e-12) < 1e-9
        assert spans.t0 == 0.0
    elif what == "spans":
        want = sorted((n, o, d) for n, _op, o, d, _t in host
                      if n.startswith("mx."))
        got = sorted((s.name, round(s.start * 1e12),
                      round((s.end - s.start) * 1e12)) for s in spans.spans)
        assert got == want and len(want) >= 6
        assert {n for n, _o, _d in want} == {
            "mx.train.step", "mx.train.h2d", "mx.train.dispatch"}
    elif what == "tags":
        steps = spans.named("train.step")
        want = [t for n, _op, _o, _d, t in host if n == "mx.train.step"]
        assert [s.tags for s in steps] == want
        numbers = [t["step"] for t in want]
        assert numbers == list(range(numbers[0], numbers[0] + len(want)))
        assert len({t["compiles"] for t in want}) == 1
        assert all(set(s.tags) == {"step"}
                   for s in spans.named("train.h2d"))
    elif what == "threads":
        # the trainer's phases are on one thread; h2d and dispatch lie
        # inside their step, so the cover is the steps' own
        (thread,) = {s.thread for s in spans.spans}
        covered = sum(d for n, _op, _o, d, _t in host
                      if n == "mx.train.step")
        (window,) = [r for r in host if r[0] == "pb.window"]
        assert abs(sr.coverage(spans, thread) - covered / window[3]) < 1e-9
    elif what == "op_names":
        want = {n: op for n, op, _o, _d, _t in hand["XLA Ops"] if op}
        assert names == want and len(want) >= 8
        assert all(op.startswith("jit(mx_train_step)/")
                   for op in want.values())
        assert sr.scoped(names, "mx_train_step")
        assert not sr.scoped(names, "mx_serve_decode_step")
    elif what == "scopes":
        runs = hand["XLA Modules"]
        assert [n.split("(")[0] for n, *_ in runs] == ["jit_mx_train_step"] * 2
        under = lambda op, scopes: any(                     # noqa: E731
            re.search(r"(^|[/(])%s([/)]|$)" % re.escape(s), op)
            for s in scopes)
        ops = hand["XLA Ops"]
        for scopes in (["mx.optim"], ["mx.fwd", "mx.loss"]):
            inside = sum(d for _n, op, _o, d, _t in ops if under(op, scopes))
            other = sum(d for _n, op, _o, d, _t in ops
                        if not under(op, scopes))
            got = sr.scope_seconds(ctx.trace, names, "mx_train_step", scopes)
            assert got[1] == 2 and inside > 0
            assert abs(got[0] - inside * 1e-12 / 2) < 1e-9
            assert abs(got[2] - other * 1e-12 / 2) < 1e-9
    else:
        assert sr.scope_seconds(ctx.trace, names, "mx_serve_decode_step",
                                ["mx.optim"]) is None


# worked by hand from the fixture's lines: the three mx.train.step
# events last 5339.630, 4781.539 and 6107.790 us and their h2d 2002.590,
# 1769.210 and 2165.750 us (the medians); both steps' compiles tag reads
# 19; %fusion.18, the one operation under mx.optim, runs 1310.432 and
# 1310.261 us (their mean); the other 22 operations, all under mx.fwd or
# its transpose, sum to 10734.157 us over the two steps
BY_HAND = {"train.dispatch_ms": 5.33963, "train.h2d_ms": 2.00259,
           "train.compiles_in_window": 0.0,
           "train.optim_device_ms": 1.3103465,
           "train.fwd_bwd_device_ms": 5.3670785}


@pytest.mark.parametrize("name", NEW)
def test_readers_on_the_recorded_trace(name):
    hand = _by_hand()
    host = [r for line, rows in hand.items()
            if line not in ("XLA Ops", "XLA Modules") for r in rows]
    got = _read(name, _ctx())
    assert abs(got - BY_HAND[name]) < 1e-9
    if name in ("train.dispatch_ms", "train.h2d_ms"):
        span = "mx.train.step" if name == "train.dispatch_ms" \
            else "mx.train.h2d"
        want = statistics.median(d for n, _op, _o, d, _t in host
                                 if n == span) * 1e-9
        assert abs(got - want) < 1e-6 and got > 0
    elif name == "train.compiles_in_window":
        assert got == 0.0
    else:
        scope = "mx.optim" if "optim" in name else r"mx\.(fwd|loss)"
        want = sum(d for _n, op, _o, d, _t in hand["XLA Ops"]
                   if re.search(r"[/(]%s[/)]" % scope, op)) * 1e-9 / 2
        assert abs(got - want) < 1e-6 and got > 0


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_phases(name):
    """The parent's program has no ``mx.`` phase and no
    ``jit_mx_train_step``: PR 30's recorded serving trace stands in for
    it.  Each reader returns nothing and does not raise."""
    assert _read(name, _ctx(PARENT)) is None


def test_scope_time_names_the_program_it_looked_in():
    """The cache-key trap (PERF.md section 7): the program is there
    under its name and none of its operations has a scope, as when the
    compile cache serves an executable from before the scopes."""
    ctx = _ctx()
    spans, names = ctx.mx
    ctx.mx = (spans, {text: re.sub(r"mx\.\w+", "anon", op)
                      for text, op in names.items()})
    with pytest.raises(RuntimeError, match="jit_mx_train_step"):
        _read("train.optim_device_ms", ctx)
