"""Checks of ``bert-large.pretrain_dp4_b128_l128``, the benchmark's cell
across a host's chips, on the CPU at the toy size over four of the
host's forced devices: the data-parallel program against the plain
reference and against the one-device program on the same global batch,
what an adapter is handed (every device of the cell, and the mix's
``mesh``), the all-reduce's bytes by hand, the collective reader on a
slice of the chip's own four-chip trace.  The whole rehearsals (the
cell, its control, a chip's rows left out, the exchange between chips
left out) are cases of ``test_perfbench.py``'s, marked ``slow``."""
import json
import os
import re
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PB = os.path.join(REPO, "perfbench")
sys.path.insert(0, REPO)

import jax                                              # noqa: E402

from perfbench import check, harness, traffic, work     # noqa: E402

CELL = "bert-large.pretrain_dp4_b128_l128"
WORK, CFG, MIX = harness.load_cell(CELL)
TOY = CFG["toy"]
SEED = 2 ** 31 + 4321
FIXTURE = os.path.join(PB, "fixtures", "train_dp4_slice.xspace.txt")


# ------------------------------------------------------- the cell's files
def test_the_cell_is_the_one_chip_cell_over_four_chips():
    one, _cfg, one_mix = harness.load_cell("bert-large.pretrain_b32_l128")
    assert WORK["config"] == one["config"] == "bert-large"
    assert WORK["chips"] == 4 and MIX["mesh"] == {"dp": 4}
    assert "mesh" not in one_mix                    # absent: one device
    # the same rows a chip, length, masked positions and feed
    assert MIX["batch"] == 4 * one_mix["batch"] == 128
    for key in ("seqlen", "masked", "host_batches", "warmup_steps",
                "reference_rows", "use_flash"):
        assert MIX[key] == one_mix[key], key
    assert MIX["toy"]["batch"] == 8 and MIX["toy"]["mesh"] == {"dp": 4}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == [CELL]
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == {"train.collective_device_ms",
                    "train.collective_exposed_ms", "grad_allreduce_roofline"}


# ------------------------------------- the program, over four devices
def _program(mesh, devices):
    from perfbench.adapters import bert_pretrain as adapter
    batches = traffic.mlm_batches(MIX["toy"], TOY["vocab_size"], SEED)
    return adapter.build(dict(CFG, use_flash=False), TOY, batches[0],
                         devices, mesh), batches


@pytest.fixture(scope="module")
def followed():
    """Three steps from one seed: the program over ``dp=4``, the same
    global batch on one device, and the reference."""
    from perfbench.reference import bert_pretrain as ref
    from perfbench.runners import train as runner
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices (tests/conftest.py forces eight)")
    out = {}
    for name, mesh, devices in (("dp4", {"dp": 4}, jax.devices()[:4]),
                                ("dp1", {}, jax.devices()[:1])):
        program, batches = _program(mesh, devices)
        out[name] = runner.first_steps(program, ref, TOY, SEED, batches)
        if name == "dp4":
            out["mesh"] = dict(program.trainer.mesh.shape)
            out["shards"] = {
                leaf: [s.data for s in
                       program.trainer.params[pname].addressable_shards]
                for leaf, pname in list(program.names.items())[::7]}
        program.free()
    out["ref"] = ref.train_steps(TOY, CFG["optimizer"], SEED, batches[:3],
                                 MIX["toy"]["reference_rows"])
    out["sizes"] = ref.leaf_sizes(TOY)
    return out


def test_dp4_agrees_with_the_reference_inside_the_toy_limits(followed):
    numbers, _where = check.train_numbers(followed["dp4"], followed["ref"],
                                          followed["sizes"])
    limits = {k: v for k, v in WORK["toy_limits"].items()
              if k.endswith("_gap")}
    ok, table = check.verdict(numbers, limits)
    assert ok, table
    assert followed["mesh"] == {"dp": 4, "tp": 1, "sp": 1, "ep": 1}


def test_dp4_agrees_with_dp1_on_the_same_global_batch(followed):
    """The same mean over the same eight rows, summed in another order:
    losses to float32 rounding, every leaf's norms to a few 1e-6."""
    four, one = followed["dp4"], followed["dp1"]
    for a, b in zip(four["losses"], one["losses"]):
        assert abs(a - b) <= 2e-6 * abs(b)
    numbers, where = check.train_numbers(four, one, followed["sizes"])
    assert numbers["grad_gap"] < 1e-5, where
    assert numbers["change_gap"] < 1e-4, where


def test_every_chip_holds_the_same_leaves(followed):
    """The norms are read from replicated leaves: after three steps
    each of the four devices holds the same numbers, bit for bit."""
    import numpy as np
    assert followed["shards"]
    for leaf, shards in followed["shards"].items():
        assert len(shards) == 4, leaf
        first = np.asarray(shards[0])
        for other in shards[1:]:
            assert (np.asarray(other) == first).all(), leaf


# -------------------------------------------- what an adapter is handed
@pytest.mark.parametrize("name,cell", [
    ("mellum_moe", "mellum2-12b-a2.5b.causal_b1_l8192"),
    ("nemotron_h", "nemotron-3-nano-30b-a3b.causal_b1_l8192"),
    ("lfm2_moe", "lfm2-24b-a2b.causal_b1_l8192")])
def test_a_decoder_adapter_refuses_a_mesh_by_name(name, cell):
    """One signature for all four adapters; the three decoders build on
    one device and say so, by their own name, before building."""
    _work, cfg, mix = harness.load_cell(cell)
    assert cfg["adapter"] == name
    adapter = harness.module("adapters", name)
    batches = traffic.mlm_batches(mix["toy"], cfg["toy"]["vocab_size"], 1)
    with pytest.raises(ValueError, match=f"^{name} adapter: the mix asks "
                       f"for the mesh .*ep"):
        adapter.build(dict(cfg, use_flash=True), cfg["toy"], batches[0],
                      jax.devices()[:4], {"dp": 4})
    bert = harness.module("adapters", "bert_pretrain")
    with pytest.raises(ValueError, match="bert_pretrain adapter: .*dp only"):
        bert.build(dict(CFG, use_flash=False), TOY, batches[0],
                   jax.devices()[:4], {"tp": 4})


def test_an_adapter_still_takes_one_device_alone():
    """The call from before PR 43, which ``tests/test_scope_taxonomy.py``
    (not the benchmark's to edit) still makes: one device, no mesh."""
    from perfbench.adapters import mellum_moe
    one = jax.devices()[0]
    assert mellum_moe.one_device("x", one) is one
    assert mellum_moe.one_device("x", [one, jax.devices()[-1]], {}) is one
    bert = harness.module("adapters", "bert_pretrain")
    batches = traffic.mlm_batches(MIX["toy"], TOY["vocab_size"], 1)
    program = bert.build(dict(CFG, use_flash=False), TOY, batches[0], one)
    assert dict(program.trainer.mesh.shape) == {"dp": 1, "tp": 1, "sp": 1,
                                                "ep": 1}
    program.free()


# ------------------------------------------------------- counts, by hand
def test_allreduce_bytes_by_hand():
    # 8 numbers of 4 bytes over 4 chips: a ring sends 3/4 of them twice
    assert work.allreduce_bytes(8, 4, 4) == 48.0
    assert work.allreduce_bytes(8, 4, 1) == 0.0
    # BERT-large's trained leaves: the three embedding tables and their
    # norm; 24 layers of qkv, out, two norms and the feed-forward;
    # pooler, the masked-LM transform, its norm and untied decoder, nsp
    C, Hd, V, P = 1024, 4096, 30522, 512
    layer = (3 * C * C + 3 * C) + (C * C + C) + 2 * C \
        + (Hd * C + Hd) + (C * Hd + C) + 2 * C
    elements = (V * C + 2 * C + P * C + 2 * C) + 24 * layer \
        + (C * C + C) + (C * C + C) + 2 * C + (V * C + V) + (2 * C + 2)
    assert elements == 367_480_636              # 1.47 GB of gradients
    ctx = SimpleNamespace(cfg=CFG, dims=CFG["dims"], devices=[0] * 4,
                          facts={"steps": 3})
    ops, nbytes = work.grad_allreduce(ctx)
    assert ops == 0
    assert nbytes == 3 * 2_204_883_816          # 2.20 GB a chip a step
    assert nbytes / 3 == 2 * 3 / 4 * 4 * elements


# -------------------------------------------- device time over two devices
def test_scope_seconds_counts_an_operation_on_its_own_device():
    """Two devices whose executions overlap in time, as a host's chips
    do: an operation counts inside an execution of its own device."""
    from perfbench import span_reduce, trace_reduce
    step = "jit_mx_train_step(1)"
    trace = trace_reduce.Trace(0.0, 20.0, 2, [
        (0, "a", 1.0, 2.0), (1, "b", 6.0, 8.0), (1, "c", 1.0, 2.0),
        (0, "d", 12.0, 13.0), (1, "e", 14.0, 14.5)],
        [(0, step, 0.0, 10.0), (1, step, 5.0, 15.0)], [])
    names = {"a": "jit(mx_train_step)/mx.optim/x",
             "b": "jit(mx_train_step)/mx.optim/y",
             "c": "jit(mx_train_step)/mx.optim/z",
             "d": "jit(mx_train_step)/mx.optim/w",
             "e": "jit(mx_train_step)/jvp(mx.fwd)/v"}
    inside, runs, other = span_reduce.scope_seconds(
        trace, names, "mx_train_step", ["mx.optim"])
    # a on device 0 and b on device 1; c and d lie outside their own
    # device's execution though inside the other's
    assert (inside, runs, other) == (1.5, 2, 0.25)


# --------------------------------------- the collective reader, by hand
STEP = "jit_mx_train_step(1)"
SYNTHETIC = [
    (0, "%fusion.1 = f32[8] fusion(%a)", 0.0, 1.0),
    (0, "%all-reduce.3 = (f32[8]{0}, f32[4]{0}) all-reduce(%x, %y)", 1.0, 3.0),
    (0, "%all-gather-start.1 = (f32[2], f32[8]) all-gather-start(%z)",
     3.0, 3.5),
    (0, "%fusion.2 = f32[8] fusion(%b)", 3.5, 5.0),
    (0, "%all-gather-done.1 = f32[8] all-gather-done(%all-gather-start.1)",
     5.5, 6.0),
    (0, "%fusion.3 = f32[8] fusion(%c)", 6.0, 7.0),
    (1, "%fusion.1 = f32[8] fusion(%a)", 0.5, 1.5),
    (1, "%ar = (f32[8]{0:T(8)}, f32[4]{0}) all-reduce(%x, %y)", 1.5, 3.0),
    (1, "%all-reduce.9 = f32[8] all-reduce(%q)", 20.0, 21.0)]  # no execution


def _collective(what, trace, kinds=None, steps=1, devices=2):
    from perfbench.readers import collective_time as ct
    notes = []
    ctx = SimpleNamespace(
        trace=trace, note=notes.append, cfg=CFG, dims=CFG["dims"],
        devices=[0] * devices, facts={"steps": steps},
        peak={"ici_bytes_per_s": 200e9})
    metric = {"name": what, "params": {
        "program": "mx_train_step", "what": what,
        "collectives": list(kinds or ct.KINDS),
        "work": "perfbench.work:grad_allreduce"}}
    return ct.read(metric, ctx), notes


def test_collective_time_by_hand():
    from perfbench import trace_reduce
    trace = trace_reduce.Trace(0.0, 30.0, 2, SYNTHETIC,
                               [(0, STEP, 0.0, 8.0), (1, STEP, 0.5, 8.5)], [])
    # device 0: the all-reduce 1-3, the pair in flight 3-6: 5 s in
    # progress, 1.5 of them under fusion.2; device 1: 1.5 s, all exposed
    total, notes = _collective("total", trace)
    assert total == 1e3 * (5.0 + 1.5) / 2
    assert "all-gather-done x0.5, all-gather-start x0.5, all-reduce x1" \
        in notes[0]
    exposed, _ = _collective("exposed", trace)
    assert exposed == 1e3 * (3.5 + 1.5) / 2 and exposed <= total
    only, _ = _collective("total", trace, kinds=["all-reduce"])
    assert only == 1e3 * (2.0 + 1.5) / 2
    # four chips send 2,204,883,816 bytes each a step: 11.02 ms at
    # 200 GB/s, over the all-reduces' 1.75 s
    share, _ = _collective("roofline", trace, kinds=["all-reduce"],
                           devices=4)
    assert abs(share - 100 * (2_204_883_816 / 200e9) / 1.75) < 1e-9
    # one device, no collective: nothing, never 0
    alone = trace_reduce.Trace(0.0, 30.0, 1, [SYNTHETIC[0]],
                               [(0, STEP, 0.0, 8.0)], [])
    for what in ("total", "exposed", "roofline"):
        assert _collective(what, alone)[0] is None
    other = trace_reduce.Trace(0.0, 30.0, 1, SYNTHETIC, [], [])
    assert _collective("total", other)[0] is None   # the program not there


def test_collective_time_reads_nothing_on_the_one_chip_traces():
    from perfbench import trace_reduce
    for name in ("train_slice", "account_slice", "serve_slice"):
        trace = trace_reduce.load(
            os.path.join(PB, "fixtures", name + ".xspace.txt"))
        for what in ("total", "exposed", "roofline"):
            assert _collective(what, trace)[0] is None, (name, what)


# ------------------------- the reader on the chip's own four-chip trace
def _fixture_by_hand():
    """{device: (collectives' intervals with a pair joined, the other
    operations' intervals, the execution)} in picoseconds, worked out
    from the fixture's text without ``trace_reduce`` or the reader."""
    text = open(FIXTURE).read()
    out = {}
    for plane in text.split("planes {")[1:]:
        dev = re.search(r'name: "/device:TPU:(\d+)"', plane)
        if not dev:
            continue
        names = dict(re.findall(
            r'event_metadata \{ key: (\d+) value \{ id: \d+ name: '
            r'"((?:[^"\\]|\\.)*)"', plane))
        lines = {re.search(r'name: "([^"]*)"', line).group(1): [
            (names[m], int(o), int(o) + int(d)) for m, o, d in re.findall(
                r"events \{ metadata_id: (\d+) offset_ps: (\d+) "
                r"duration_ps: (\d+)", line)]
            for line in plane.split("lines {")[1:]}
        (_n, r0, r1), = lines["XLA Modules"]
        busy, others, opened = [], [], None
        for name, a, b in sorted(lines["XLA Ops"], key=lambda e: e[1]):
            assert r0 <= a and b <= r1
            if name.startswith("%async-collective-start"):
                opened = a
                busy.append((a, b))
            elif name.startswith("%async-collective-done"):
                busy.append((opened, b))
            elif name.startswith("%all-reduce"):
                busy.append((a, b))
            else:
                others.append((a, b))
        out[int(dev.group(1))] = (busy, others, (r0, r1))
    return out


def _covered(intervals, inside=None):
    """Picoseconds covered by ``intervals`` (within ``inside``'s, where
    given), counted picosecond range by picosecond range."""
    edges = sorted({x for iv in intervals + (inside or []) for x in iv})
    total = 0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in intervals) and (
                inside is None or any(s <= mid < e for s, e in inside)):
            total += b - a
    return total


def test_collective_time_on_the_recorded_four_chip_trace():
    """``fixtures/train_dp4_slice.xspace.txt``: the fourth whole step of
    PR 43's first traced run of this cell on four v5e chips (my chip
    run, PR 43, call 1), cut by ``trace_reduce.cut`` from the trace
    thinned to, on every device, the collectives, everything between
    the ``async-collective`` pair's start and done, and the other
    operations of 150 us or more.  A step has 13 plain ``all-reduce``
    operations, 2.2 ms each back to back after the backward pass, and
    one async pair (the masked rows' all-gather) in flight for 4 ms
    under the masked-LM head.  Total and exposed time are worked out
    again here from the file's text."""
    from perfbench import trace_reduce
    assert os.path.getsize(FIXTURE) < 400_000
    by_hand = _fixture_by_hand()
    assert sorted(by_hand) == [0, 1, 2, 3]
    want_total = want_exposed = 0
    for busy, others, _run in by_hand.values():
        assert len(busy) == 15              # 13 all-reduces, the pair twice
        want_total += _covered(busy)
        want_exposed += _covered(busy) - _covered(busy, others)
    trace = trace_reduce.load(FIXTURE)
    assert trace.n_devices == 4
    total, notes = _collective("total", trace, devices=4)
    exposed, _ = _collective("exposed", trace, devices=4)
    assert abs(total - want_total * 1e-9 / 4) < 1e-6
    assert abs(exposed - want_exposed * 1e-9 / 4) < 1e-6
    assert 0 < exposed < total
    assert abs(total - 27.3141) < 1e-3 and abs(exposed - 25.5550) < 1e-3
    assert "all-reduce x13, async-collective-done x1, " \
        "async-collective-start x1" in notes[0]
    only, _ = _collective("total", trace, kinds=["all-reduce"], devices=4)
    assert 25.0 < only < total
    share, notes = _collective("roofline", trace, kinds=["all-reduce"],
                               devices=4)
    assert abs(share - 100 * 11.02441908 / only) < 1e-6
    assert 40 < share < 45 and "least 11.0244 ms" in notes[0]
