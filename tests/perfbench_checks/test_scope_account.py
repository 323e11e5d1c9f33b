"""Checks of ``perfbench/readers/scope_account.py`` on small recorded
traces, on the CPU.  ``fixtures/account_slice.xspace.txt`` was cut by
``span_reduce.cut`` from PR 41's own traced chip run of
``lfm2-24b-a2b.causal_b1_l8192``: the window's first two whole
executions of ``jit_mx_train_step`` with the sixty longest operations
of each, under the closed taxonomy.  ``fixtures/train_slice.xspace.txt``
(PR 31) is BERT's program from before it: ``mx.fwd`` and ``mx.optim``
and no leaf under the container, which is also what a stale executable
from the compile cache looks like.  The expected values are worked out
again here from the files' text.  The eight metrics are read through
``scope_account.METRICS``, and their files (PR 43) are held to it."""
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
FIXTURE = os.path.join(PB, "fixtures", "account_slice.xspace.txt")
BEFORE = os.path.join(PB, "fixtures", "train_slice.xspace.txt")
SERVING = os.path.join(PB, "fixtures", "serve_slice.xspace.txt")
CELL = "lfm2-24b-a2b.causal_b1_l8192"
SCOPED = {"train.attn_proj_device_ms": ["mx.attn.proj"],
          "train.head_loss_device_ms": ["mx.head", "mx.loss"],
          "train.norm_embed_device_ms": ["mx.norm", "mx.embed"],
          "train.ffn_device_ms": ["mx.ffn.dense"],
          "train.attn_dense_device_ms": ["mx.attn.dense"]}
NEW = ["train.step_device_ms", *SCOPED, "train.unnamed_device_ms",
       "train.unscoped_device_ms"]
EVENT = re.compile(r"events \{ metadata_id: (\d+) offset_ps: (\d+) "
                   r"duration_ps: (\d+)")
SCOPE = re.compile(r"(?:^|[/(])(mx\.[\w.]+)(?=[/):]|$)")


def _by_hand(path):
    """([(HLO text, jax name, duration ps)] of the device's operations,
    [duration ps] of the program's executions) from the file's text."""
    device = open(path).read().split("planes {")[1]
    meta = {k: (n.replace('\\"', '"'), op) for k, n, op in re.findall(
        r'event_metadata \{ key: (\d+) value \{ id: \d+ name: '
        r'"((?:[^"\\]|\\.)*)"(?: stats \{ metadata_id: 1 str_value: '
        r'"([^"]*)" \})? \} \}', device)}
    lines = {re.search(r'name: "([^"]*)"', line).group(1): [
        (*meta[mid], int(dur)) for mid, _off, dur in EVENT.findall(line)]
        for line in device.split("lines {")[1:]}
    return lines["XLA Ops"], [d for _n, _op, d in lines["XLA Modules"]]


def _leaf(op):
    found = [s for s in SCOPE.findall(op) if s != "mx.fwd"]
    return found[-1] if found else None


def _ctx(path):
    from perfbench import span_reduce, trace_reduce
    data, raw = span_reduce._data(path)
    notes = []
    return SimpleNamespace(
        cell={"name": CELL}, trace=trace_reduce.load(path), notes=notes,
        mx=(span_reduce.load(path, data), span_reduce.op_names(raw)),
        note=notes.append)


def _metric(name):
    from perfbench.readers import scope_account
    return {"name": name, "params": {"program": scope_account.PROGRAM,
                                     **scope_account.METRICS[name]}}


def _file(name):
    with open(os.path.join(PB, "metrics", name + ".json")) as f:
        return json.load(f)


def _read(name, ctx):
    from perfbench.readers import scope_account
    return scope_account.read(_metric(name), ctx)


@pytest.mark.parametrize("name", NEW)
def test_the_readers_table_names_the_metric(name):
    """``scope_account.METRICS`` holds the ``params`` of the eight
    metrics' files: each file is there and says what the table says."""
    from perfbench.readers import scope_account
    params = scope_account.METRICS[name]
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", name)
    assert params.get("scopes") == SCOPED.get(name)
    assert params["what"] == ("scopes" if name in SCOPED
                              else name.split(".")[1].split("_")[0])
    m = _file(name)
    assert m["reader"] == "scope_account"
    assert m["params"] == {"program": scope_account.PROGRAM, **params}


def test_the_fixture_is_small_and_carries_the_taxonomy():
    assert os.path.getsize(FIXTURE) < 100_000
    ops, runs = _by_hand(FIXTURE)
    assert len(runs) == 2
    leaves = {_leaf(op) for _t, op, _d in ops if op}
    assert {"mx.ffn.dense", "mx.attn.proj", "mx.head", "mx.loss", "mx.norm",
            "mx.embed", "mx.optim", "mx.sconv.in_proj"} <= leaves
    assert "mx.attn.dense" not in leaves            # the flash kernels
    assert any(not op for _t, op, _d in ops)        # unnamed ones too


@pytest.mark.parametrize("name", NEW)
def test_readers_on_the_recorded_trace(name):
    ops, runs = _by_hand(FIXTURE)
    ctx = _ctx(FIXTURE)
    got = _read(name, ctx)
    if name == "train.step_device_ms":
        want = statistics.median(runs) * 1e-9
        assert "by leaf" in ctx.notes[-1]
    elif name == "train.attn_dense_device_ms":
        assert got is None          # the flash kernels: no such leaf
        return
    elif name in SCOPED:
        want = sum(d for _t, op, d in ops
                   if _leaf(op) in SCOPED[name]) * 1e-9 / len(runs)
        assert want > 0
    elif name == "train.unnamed_device_ms":
        want = sum(d for _t, op, d in ops
                   if not op.startswith("jit(mx_train_step)")) \
            * 1e-9 / len(runs)
        assert want > 0 and "reshape f32[67100672]" in ctx.notes[-1]
    else:
        want = sum(d for _t, op, d in ops
                   if op.startswith("jit(mx_train_step)")
                   and _leaf(op) is None) * 1e-9 / len(runs)
    assert abs(got - want) < 1e-6


def test_the_identity_closes_on_the_recorded_trace():
    """Every ``*_device_ms`` that reads a disjoint set of leaves +
    unnamed + unscoped = the summed operation time a step, here of the
    operations the fixture kept.  ``train.dense_ffn_device_ms`` reads
    the leaf ``train.ffn_device_ms`` reads, and counts once."""
    from perfbench.readers import scope_time
    ops, runs = _by_hand(FIXTURE)
    ctx = _ctx(FIXTURE)
    new = {n: _read(n, ctx) or 0.0 for n in NEW[1:]}
    old = {n: scope_time.read(_file(n), ctx) for n in (
        "train.optim_device_ms", "train.sconv_device_ms",
        "train.moe_top4_device_ms", "train.attn_d64_device_ms",
        "train.dense_ffn_device_ms", "train.fwd_bwd_device_ms")}
    assert abs(old.pop("train.dense_ffn_device_ms")
               - new["train.ffn_device_ms"]) < 1e-6
    under = old.pop("train.fwd_bwd_device_ms")      # the container
    total = sum(d for _t, _op, d in ops) * 1e-9 / len(runs)
    assert abs(sum(new.values()) + sum(old.values()) - total) < 1e-6
    assert abs(total - under - old["train.optim_device_ms"]
               - new["train.unnamed_device_ms"]
               - new["train.unscoped_device_ms"]) < 1e-6


def test_a_part_the_model_lacks_reads_nothing():
    """LFM2 has no dense attention core: nothing returned, never 0,
    and the note says what the program does carry."""
    ctx = _ctx(FIXTURE)
    assert _read("train.attn_dense_device_ms", ctx) is None
    assert "mx.attn.proj" in ctx.notes[-1]
    assert "none of ['mx.attn.dense']: a model without that part, or " \
        in ctx.notes[-1]
    assert _read("train.attn_proj_device_ms", ctx) > 0
    assert "none of" not in ctx.notes[-1]


@pytest.mark.parametrize("name", NEW)
def test_a_program_from_before_the_scopes(name):
    """The parent's program, or a stale executable from the compile
    cache: the scope metrics return nothing and say what the program
    carries and what may have served it; step, unnamed and unscoped
    read as on any program."""
    ops, runs = _by_hand(BEFORE)
    ctx = _ctx(BEFORE)
    got = _read(name, ctx)
    if name in SCOPED:
        assert got is None
        assert f"jit_mx_train_step carries ['mx.fwd', 'mx.optim'] and " \
            f"none of {SCOPED[name]}" in ctx.notes[-1]
        assert "an executable from before these scopes, served by the " \
            "compile cache?" in ctx.notes[-1]
    elif name == "train.step_device_ms":
        assert abs(got - statistics.median(runs) * 1e-9) < 1e-6
    elif name == "train.unnamed_device_ms":
        assert got == 0.0           # that cut kept named operations only
    else:
        want = sum(d for _t, op, d in ops if _leaf(op) is None) * 1e-9 / 2
        assert abs(got - want) < 1e-6 and want > 5.0


def test_each_device_and_each_program_has_its_own_account():
    """Two devices whose executions overlap in time: an operation
    counts only inside an execution of its own device.  A metric of
    another program does not read this program's account."""
    from perfbench import trace_reduce
    from perfbench.readers import scope_account
    step = "jit_mx_train_step(1)"
    trace = trace_reduce.Trace(0.0, 20.0, 2, [
        (0, "a", 1.0, 2.0), (1, "b", 6.0, 8.0), (1, "c", 1.0, 2.0),
        (0, "d", 12.0, 13.0)], [(0, step, 0.0, 10.0), (1, step, 5.0, 15.0)],
        [])
    names = {"a": "jit(mx_train_step)/mx.loss/x", "c": "",
             "b": "jit(mx_train_step)/jvp(mx.fwd)/mx.head/y",
             "d": "jit(mx_train_step)/mx.optim/z"}
    a = scope_account.account(trace, names, "mx_train_step")
    assert a.steps == 2 and a.step == 10.0
    assert dict(a.leaves) == {"mx.loss": 0.5, "mx.head": 1.0}
    assert not a.unnamed and not a.unscoped
    ctx = SimpleNamespace(trace=trace, mx=(None, names), note=print)
    assert _read("train.head_loss_device_ms", ctx) == 1500.0
    other = {"name": "x", "params": {"program": "mx_other", "what": "step"}}
    assert scope_account.read(other, ctx) is None
    assert _read("train.step_device_ms", ctx) == 10000.0


@pytest.mark.parametrize("name", NEW)
def test_a_trace_without_the_program(name):
    """PR 30's recorded serving trace has no ``jit_mx_train_step``:
    nothing returned, nothing raised."""
    assert _read(name, _ctx(SERVING)) is None
