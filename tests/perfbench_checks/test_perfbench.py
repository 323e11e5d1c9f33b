"""Checks of the benchmark itself (``BENCHMARK.json``, ``perfbench/``),
on the CPU.  What needs a process of its own (a rehearsal run, which
turns on JAX's persistent compile cache) runs in a child process, so
nothing here changes the worker that runs the other test files.  The
whole rehearsals (a sound run of each kind, the control, each planted
fault: ten to twenty seconds of compiling each) are marked ``slow``:
tier-1 leaves them out, so that they take no cores from the timing
tests that run beside them; ``pytest tests/perfbench_checks -m slow``
runs them."""
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PB = os.path.join(REPO, "perfbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
sys.path.insert(0, REPO)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCH = _json(REPO, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
WORKLOAD_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(PB, "workloads"))
                        if f.endswith(".json"))
TRAIN, SERVE = "bert-large.pretrain_b32_l128", "gpt2-medium.chat_closed16"
DP4 = "bert-large.pretrain_dp4_b128_l128"       # the cell on four chips
METRIC_FILES = sorted(f for f in os.listdir(os.path.join(PB, "metrics"))
                      if f.endswith(".json"))


def _child(code, timeout=600, devices=1):
    """``code`` in a child process on ``devices`` forced CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


# ------------------------------------------------------------ the data files
# Each check takes the benchmark it checks: ``BENCHMARK.json`` as loaded
# and the directory of ``perfbench``'s data files.  They run over the
# repo's own, and (``test_an_appended_entry_passes_every_data_check``)
# over a copy with one made-up configuration, cell and metric appended:
# nothing here may name a place in a list or the length of one.
def _cells(bench):
    return [w["name"] for w in bench["workloads"]]


def _listed(pb, folder):
    return sorted(f[:-5] for f in os.listdir(os.path.join(pb, folder))
                  if f.endswith(".json"))


def _loaders(pb):
    """``harness``'s two finders, reading the data files under ``pb``."""
    from perfbench import harness

    def load_cell(name):
        cell = _json(pb, "workloads", name + ".json")
        return (cell, _json(pb, "configs", cell["config"] + ".json"),
                _json(pb, "mixes", cell["traffic"] + ".json"))

    def cell_metrics(cell_name, end_to_end):
        real, harness.ROOT = harness.ROOT, pb
        try:
            return harness.cell_metrics(cell_name, end_to_end)
        finally:
            harness.ROOT = real

    return load_cell, cell_metrics


def check_names_units_and_sources(bench, pb):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace"), m
        assert 0 < m["bound"] <= 0.1, m
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4), w
        assert 0 < len(w["why"]) <= 200 and "\n" not in w["why"], w
    # of a benchmark's cells at most a quarter, and always one, on four
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    for root, _dirs, files in os.walk(pb):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (root, f)


def check_cell_files(bench, pb, cell):
    """A workload file is a cell of ``BENCHMARK.json``, letter for
    letter, or it is parked and says why (and then ``perfbench.run``
    gives no result for it).  A cell's ``chips`` is the product of its
    mix's ``mesh`` (absent: one device)."""
    import math
    work = _json(pb, "workloads", cell + ".json")
    assert work["name"] == cell and NAME.match(cell)
    assert (cell in _cells(bench)) != ("parked" in work)
    cfg = _json(pb, "configs", work["config"] + ".json")
    if cell in _cells(bench):
        w = next(w for w in bench["workloads"] if w["name"] == cell)
        assert (work["config"], work["traffic"], work["chips"],
                work["why"]) == (w["config"], w["traffic"], w["chips"],
                                 w["why"])
        entry = next(c for c in bench["configs"] if c["name"] == w["config"])
        assert entry["file"] == f"perfbench/configs/{cfg['name']}.json"
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
    else:
        assert 0 < len(work["parked"]) and "PERF.md" in work["parked"]
    assert cfg["name"] == work["config"]
    assert cfg["kind"] in ("train", "serve")
    assert "bfloat16" in cfg["precision"]["control"]
    mix = _json(pb, "mixes", work["traffic"] + ".json")
    assert mix["name"] == work["traffic"] and "toy" in mix
    for sizes in (mix, mix["toy"]):
        assert math.prod(sizes.get("mesh", {}).values()) == work["chips"]
        if "mesh" in sizes:     # rows divide over dp, blocks over rows
            assert sizes["batch"] % sizes["mesh"].get("dp", 1) == 0
    assert work["limits"] and set(work["limits"]) == set(work["toy_limits"])
    for folder, key in (("adapters", "adapter"), ("reference", "reference")):
        assert os.path.exists(os.path.join(PB, folder, cfg[key] + ".py"))


def check_metric_files(bench, pb, name):
    from perfbench import harness
    load_cell, cell_metrics = _loaders(pb)
    m = _json(pb, "metrics", name + ".json")
    assert name == m["name"]
    entry = next(e for e in bench["per_layer"] if e["name"] == m["name"])
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == m[key], key
    assert entry.get("workloads") == m.get("workloads")
    assert os.path.exists(os.path.join(PB, "readers", m["reader"] + ".py"))
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    reporting = moved.get("workloads", _cells(bench))
    for cell in m.get("workloads", reporting):
        assert cell in reporting, (m["name"], cell)
        _work, cfg, _mix = load_cell(cell)
        runner = harness.module("runners", cfg["kind"])
        assert m["moves"] in runner.END_TO_END
        assert m in cell_metrics(cell, runner.END_TO_END)
    if "roofline" in m["name"]:
        assert m["name"].endswith("_roofline") and m["unit"] == "%"
    if "mfu" in re.split(r"[._]", m["name"]):
        assert m["unit"] == "%"


def check_every_cell_reports_a_per_layer_metric(bench, pb):
    from perfbench import harness
    load_cell, cell_metrics = _loaders(pb)
    assert sorted(e["name"] for e in bench["per_layer"]) \
        == _listed(pb, "metrics")
    for cell in _cells(bench):
        _work, cfg, _mix = load_cell(cell)
        runner = harness.module("runners", cfg["kind"])
        assert cell_metrics(cell, runner.END_TO_END), cell
        for name in runner.END_TO_END:
            e = next(e for e in bench["end_to_end"] if e["name"] == name)
            assert cell in e.get("workloads", _cells(bench))


def check_all(bench, pb):
    check_names_units_and_sources(bench, pb)
    for cell in _listed(pb, "workloads"):
        check_cell_files(bench, pb, cell)
    for name in _listed(pb, "metrics"):
        check_metric_files(bench, pb, name)
    check_every_cell_reports_a_per_layer_metric(bench, pb)


def test_names_units_and_sources():
    check_names_units_and_sources(BENCH, PB)


@pytest.mark.parametrize("cell", WORKLOAD_FILES)
def test_cell_files(cell):
    check_cell_files(BENCH, PB, cell)


@pytest.mark.parametrize("fn", METRIC_FILES)
def test_metric_files(fn):
    check_metric_files(BENCH, PB, fn[:-5])


def test_every_cell_reports_a_per_layer_metric():
    check_every_cell_reports_a_per_layer_metric(BENCH, PB)


@pytest.mark.parametrize("where", ["end", "front"])
def test_an_appended_entry_passes_every_data_check(tmp_path, where):
    """What the next ``model_config``, ``perf_opt`` or ``tracing`` PR
    does: one made-up configuration, cell and metric, as new files and
    one entry each, go into a copy of the benchmark, and every data
    check above passes over the copy, wherever in its list an entry
    lands (PR 39 pinned the lists' last entries, and no PR after it
    could add one until a ``benchmark`` PR restated the asserts)."""
    import copy
    import shutil
    pb = tmp_path / "perfbench"
    for folder in ("configs", "workloads", "mixes", "metrics"):
        shutil.copytree(os.path.join(PB, folder), pb / folder)
    bench = copy.deepcopy(BENCH)

    def put(folder, name, data):
        with open(pb / folder / (name + ".json"), "w") as f:
            json.dump(data, f)

    cfg = dict(_json(PB, "configs", "bert-large.json"), name="made-up-1b",
               source="https://example.org/made-up-1b/config.json")
    mix = dict(_json(PB, "mixes", "pretrain_b32_l128.json"),
               name="pretrain_b16_l128", batch=16)
    cell = dict(_json(PB, "workloads", TRAIN + ".json"),
                name="made-up-1b.pretrain_b16_l128", config="made-up-1b",
                traffic="pretrain_b16_l128", why="a made-up cell")
    metric = dict(_json(PB, "metrics", "train.optim_device_ms.json"),
                  name="train.made_up_device_ms", workloads=[cell["name"]])
    put("configs", cfg["name"], cfg)
    put("mixes", mix["name"], mix)
    put("workloads", cell["name"], cell)
    put("metrics", metric["name"], metric)
    at = {"end": len, "front": lambda entries: 0}[where]
    for group, entry in (
            ("configs", {"name": cfg["name"], "source": cfg["source"],
                         "file": "perfbench/configs/made-up-1b.json",
                         "reduced": cfg["reduced"], "why": "made up"}),
            ("workloads", {k: cell[k] for k in
                           ("name", "config", "traffic", "chips", "why")}),
            ("per_layer", {k: metric[k] for k in
                           ("name", "unit", "better", "source", "layer",
                            "moves", "workloads")})):
        bench[group].insert(at(bench[group]), entry)
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].insert(at(m["workloads"]), cell["name"])
    check_all(bench, str(pb))
    # and the check is no empty one: the made-up metric without its file
    os.remove(pb / "metrics" / (metric["name"] + ".json"))
    with pytest.raises(AssertionError):
        check_every_cell_reports_a_per_layer_metric(bench, str(pb))


def test_peak_table_refuses_an_unknown_device():
    from perfbench import peaks
    assert peaks.peak("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    # 1,600 Gbit/s of inter-chip links a chip, the sum of its links
    assert peaks.peak("TPU v5 lite")["ici_bytes_per_s"] == 200e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")


# ------------------------------------------------------- counts, by hand
TINY = {"units": 4, "hidden_size": 8, "num_layers": 2, "vocab_size": 10}
# one block over 6 tokens (2 rows of 3) that see 3 keys each:
# qkv+out 2*6*4*16 = 768, ffn 2*6*4*8*2 = 768, attention 2*18*4*2 = 288;
# pooler 2*2*4*4 = 64, nsp 2*2*4*2 = 32, mlm 2*2*1*4*(4+10) = 224
BERT_FWD = 2 * (768 + 768 + 288) + 64 + 32 + 224


@pytest.mark.parametrize("fn,args,want", [
    ("bert_pretrain_forward_flops", (TINY, 2, 3, 1), BERT_FWD),
    ("bert_pretrain_step_flops", (TINY, 2, 3, 1), 3 * BERT_FWD),
    # decode over a context of 5: proj 2*1*4*16 = 128, ffn 128,
    # attention 2*5*4*2 = 80, per layer; vocabulary 2*4*10 = 80
    ("decoder_decode_flops", (TINY, 5), 2 * (128 + 128 + 80) + 80),
    # prefill of 3 tokens sees 1+2+3 = 6 keys: 384 + 384 + 96 per layer
    ("decoder_prefill_flops", (TINY, 3), 2 * (384 + 384 + 96) + 80),
])
def test_flops_by_hand(fn, args, want):
    from perfbench import flops
    assert getattr(flops, fn)(*args) == want


@pytest.mark.parametrize("fn,args,want", [
    # bh=2, lq=lk=4, d=8, 2 bytes: QK^T and PV are 2*2*4*4*8 each
    ("flash_attention_fwd", (2, 4, 4, 8, 2), (1024, 2 * 2 * 8 * 16)),
    ("flash_attention_bwd", (2, 4, 4, 8, 2), (2048, 2 * 2 * 8 * 32)),
    ("flash_attention_step", ({"batch": 1, "heads": 2, "seqlen": 4,
                               "head_dim": 8, "layers": 3, "itemsize": 2},),
     (3 * 3072, 3 * (512 + 1024))),
    # two live slots with 3 and 5 keys, 2 heads of 4, float32 pool
    ("paged_attention_decode", ([3, 5], 2, 4, 4),
     (2 * 8 * 8 * 2, 2 * 8 * 8 * 4 + 2 * 2 * 8 * 4)),
    ("least_seconds", (1000, 50, {"flops_per_s": 100.0,
                                  "hbm_bytes_per_s": 10.0}),
     (10.0, "compute")),
    ("least_seconds", (100, 50, {"flops_per_s": 100.0,
                                 "hbm_bytes_per_s": 10.0}), (5.0, "bytes")),
])
def test_kernel_work_by_hand(fn, args, want):
    from perfbench import kernels
    assert getattr(kernels, fn)(*args) == want


def test_traffic_is_the_same_work_for_every_seed():
    from perfbench import traffic
    mix = _json(PB, "mixes", "chat_closed16.json")
    sizes = []
    for seed in (1, 2 ** 31 + 5):
        streams = traffic.closed_loop_requests(mix, 50257, seed)
        assert len(streams) == mix["clients"]
        first = [next(streams[0]) for _ in range(mix["per_client"])]
        sizes.append(sorted((len(p), n) for p, n in first))
        assert min(len(p) for p, _ in first) == mix["prompt_len"]["lo"]
        assert max(len(p) for p, _ in first) == mix["prompt_len"]["hi"]
    assert sizes[0] == sizes[1]
    a = traffic.mlm_batches(_json(PB, "mixes", "pretrain_b32_l128.json")["toy"],
                            1024, 2 ** 31 + 5)
    b = traffic.mlm_batches(_json(PB, "mixes", "pretrain_b32_l128.json")["toy"],
                            1024, 2 ** 31 + 5)
    assert all((x == y).all() for p, q in zip(a, b) for x, y in zip(p, q))
    rows = {tuple(r) for batch in a for r in batch[0]}
    assert len(rows) == len(a) * a[0][0].shape[0]       # all rows differ


def test_check_measures():
    from perfbench import check
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    sizes = {"a": 100, "b": 100, "c": 2}
    numbers, where = check.train_numbers(
        {"losses": [1.0], "grad_norms": {"a": 1.0, "b": 2.0, "c": 5e-9},
         "change_norms": {"a": 1.0, "b": 2.2, "c": 0.0}},
        {"losses": [1.1], "grad_norms": ref, "change_norms": ref}, sizes)
    assert numbers["grad_gap"] == 0.0           # the 2-element leaf is out
    assert where["change_gap"] == "b"           # "c" has no gradient
    gap, leaf = check.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 2e-9}, ref)
    assert leaf == "a" and abs(gap - 0.1) < 1e-12
    # a leaf left unmoved reads 1; a tiny leaf is held against the median
    assert check.worst_leaf_gap({"a": 0.0, "b": 2.0, "c": 1e-9}, ref)[0] == 1.0
    assert check.still_leaves({"a": 1.0, "b": 2.0, "c": 1e-9}) == {"c"}
    ok, table = check.verdict({"x": 0.5, "y": float("nan")},
                              {"x": 1.0, "y": 1.0, "z": 0})
    assert not ok and table["x"] == [0.5, 1.0] and table["z"] == [None, 0]
    assert check.verdict({"x": 0.5}, {"x": 1.0})[0]


# ------------------------------------------- runs, each in a child process
def _run(cell, seed, trace=0, rehearsal=True, patch=""):
    code = patch + (
        "\nimport sys\nfrom perfbench import run\n"
        f"sys.exit(run.main(['--workload', {cell!r}, '--seed', '{seed}', "
        f"'--seconds', '0.5', '--trace', '{trace}'"
        + (", '--rehearsal'" if rehearsal else "") + "]))\n")
    return _child(code, devices=_json(PB, "workloads", cell + ".json")["chips"])


def _verdict(stderr):
    line = [ln for ln in stderr.splitlines()
            if ln.startswith("REHEARSAL done")][-1]
    return json.loads(line[line.index("{"):])


@pytest.mark.parametrize("cell,reason", [(TRAIN, "needs 1 TPU chip"),
                                         (DP4, "needs 4 TPU chip"),
                                         (SERVE, "is parked")])
def test_run_gives_no_result(cell, reason):
    """Without a chip; and, chip or none, for a parked cell."""
    r = _run(cell, 5, rehearsal=False)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert reason in r.stderr


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    """A metric file that names a cell has something to read there; a
    reader that finds nothing (the kernel renamed, the program split)
    must not pass in silence."""
    from types import SimpleNamespace
    from perfbench import harness, run, trace_reduce
    listed = {"name": "k_roofline", "unit": "%", "reader": "kernel_roofline",
              "workloads": [TRAIN],
              "params": {"pattern": "no_such_kernel", "work": "x:y"}}
    trace = trace_reduce.load(
        os.path.join(PB, "fixtures", "serve_slice.xspace.txt"))
    ctx = SimpleNamespace(cell={"name": TRAIN}, peak={"flops_per_s": 1.0},
                          note=print)
    result = {"profiler": SimpleNamespace(load=lambda: trace), "facts": {}}
    real = harness.cell_metrics
    harness.cell_metrics = lambda cell, end_to_end: [listed]
    try:
        with pytest.raises(RuntimeError, match="found nothing"):
            run.per_layer(ctx, result, ("train_tokens_per_s",))
        listed.pop("workloads")         # not listed by name: left out
        assert run.per_layer(ctx, result, ("train_tokens_per_s",))[0] == {}
    finally:
        harness.cell_metrics = real


def test_run_outside_a_checkout_prints_no_result(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        BENCH["command"] + ["--workload", CELLS[0], "--seed", "5",
                            "--seconds", "1", "--trace", "0", "--rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "cannot import mxnet_tpu" in r.stderr


@pytest.mark.slow
@pytest.mark.parametrize("cell", [TRAIN, SERVE, DP4])
def test_rehearsal_is_correct_and_prints_no_result(cell):
    r = _run(cell, 2 ** 31 + 77, trace=1)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "" and "REHEARSAL" in r.stderr
    v = _verdict(r.stderr)
    assert v["correct"] and v["failed"] == 0 and v["attempted"] > 0
    # the last lines on standard error give each number beside its limit
    assert any(ln.startswith("correct: true  ")
               for ln in r.stderr.strip().splitlines()[-3:])


STATE_UNCHANGED = """
import jax, jax.numpy as jnp
from perfbench.adapters import bert_pretrain as a
orig = a.Program.step
def step(self, batch):
    t = self.trainer
    saved = jax.tree_util.tree_map(jnp.copy, (t.params, t.opt_state))
    loss = orig(self, batch)
    t.params, t.opt_state = saved
    return loss
a.Program.step = step
"""
HALF_BATCH = """
import numpy as np
from perfbench.adapters import bert_pretrain as a
orig = a.Program.step
def step(self, batch):
    h = len(batch[0]) // 2
    return orig(self, tuple(np.concatenate([x[:h], x[:h]]) for x in batch))
a.Program.step = step
"""
# what every chip would hold with the exchange between chips left out:
# the step of its own rows alone.  Device 0's leaves are what the norms
# read, so the batch is the first chip's rows on every chip.
EXCHANGE_LEFT_OUT = """
import numpy as np
from perfbench.adapters import bert_pretrain as a
orig = a.Program.step
def step(self, batch):
    own = len(batch[0]) // self.trainer.mesh.shape["dp"]
    return orig(self, tuple(np.concatenate([x[:own]] * (len(x) // own))
                            for x in batch))
a.Program.step = step
"""
TOKEN_ALTERED = """
import numpy as np
from perfbench.adapters import decoder_lm as a
orig = a.Program.generate
def generate(self, prompt, new_tokens, on_token, timeout):
    seen = []
    def cb(t):
        seen.append(t)
        on_token((int(t) + 1) % 512 if len(seen) == 3 else t)
    out = np.array(orig(self, prompt, new_tokens, cb, timeout))
    if len(out) >= 3:
        out[2] = (out[2] + 1) % 512
    return out
a.Program.generate = generate
"""


@pytest.mark.slow
@pytest.mark.parametrize("fault,cell,caught_by", [
    (STATE_UNCHANGED, TRAIN, ("change_gap", "grad_gap")),
    (HALF_BATCH, TRAIN, ("grad_gap", "change_gap")),
    (TOKEN_ALTERED, SERVE, ("token_gap",)),
    (STATE_UNCHANGED, DP4, ("change_gap", "grad_gap")),
    (HALF_BATCH, DP4, ("grad_gap", "change_gap")),
    (EXCHANGE_LEFT_OUT, DP4, ("grad_gap", "change_gap")),
], ids=["state_unchanged", "half_batch", "token_altered",
        "dp4_state_unchanged", "dp4_half_batch", "dp4_exchange_left_out"])
def test_a_broken_timed_path_is_not_correct(fault, cell, caught_by):
    r = _run(cell, 2 ** 31 + 78, patch=fault)
    assert r.returncode == 1, r.stderr[-2000:]
    v = _verdict(r.stderr)
    assert v["correct"] is False
    over = [k for k, (value, limit) in v["checks"].items()
            if value is None or not value <= limit]
    assert set(over) & set(caught_by), v["checks"]
    assert "compiled_in_window" not in over


def _control_rows(cell):
    r = _child(
        "import sys\nfrom perfbench import control\n"
        f"sys.exit(control.main(['--workload', {cell!r}, '--seeds', '3', "
        "'--control-seeds', '3', '--seconds', '1', '--rehearsal', "
        "'--first-seed', '2200000001']))\n",
        devices=_json(PB, "workloads", cell + ".json")["chips"])
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("READINGS ")][-1]
    limits = {k: v for k, v in
              _json(PB, "workloads", cell + ".json")["toy_limits"].items()
              if k.endswith("_gap")}
    return json.loads(line[len("READINGS "):])["rows"], limits


@pytest.mark.slow
@pytest.mark.parametrize("cell,rows_kept", [(TRAIN, 2), (DP4, 6)])
def test_the_training_control_is_not_correct(cell, rows_kept):
    """The reference in bfloat16 (the precision below the
    configuration's float32), put in the program's place, fails a limit
    that the program keeps, and so do rows left out of the gradient:
    half of the toy's four on one device, one chip's two of the toy's
    eight over four (toy sizes and toy limits; the chip's readings at
    the cell's size are in PERF.md)."""
    from perfbench import check
    rows, limits = _control_rows(cell)
    for row in rows:
        assert row["rows_kept"] == rows_kept
        assert check.verdict(row["program"][0], limits)[0]
        assert not check.verdict(row["control"][0], limits)[0]
        assert not check.verdict(row["fault_rows_left_out"][0], limits)[0]


@pytest.mark.slow
def test_the_serving_control_reads_the_same_numbers_as_the_program():
    """The parked serving cell's control is the configuration's own,
    bfloat16; every reading carries the widest gap and the candidates
    that may come to separate the two (PERF.md, Open questions)."""
    rows, limits = _control_rows(SERVE)
    for row in rows:
        assert set(row["program"]) == set(row["control"]) >= set(limits)
        assert row["program"]["token_gap"] <= limits["token_gap"]
        assert row["control"]["gap_sum"] >= row["control"]["token_gap"] >= 0


# ------------------------------------------------------ the trace reduction
FIXTURE = os.path.join(PB, "fixtures", "serve_slice.xspace.txt")
KERNEL = 'custom_call_target="tpu_custom_call"'


def _fixture_by_hand():
    """(device events, busy picoseconds, kernel picoseconds) worked out
    from the fixture's text, without ``trace_reduce``."""
    text = open(FIXTURE).read()
    device = text[:text.index('name: "XLA Modules"')]
    names = dict(re.findall(
        r'event_metadata \{ key: (\d+) value \{ id: \d+ name: "((?:[^"\\]|\\.)*)"',
        text[text.index("event_metadata"):text.index('planes { id: 100')]))
    events = [(names[m], int(o), int(d)) for m, o, d in re.findall(
        r"events \{ metadata_id: (\d+) offset_ps: (\d+) duration_ps: (\d+)",
        device)]
    last_end, busy_ps = 0, 0
    for _n, o, d in sorted(events, key=lambda e: e[1]):
        busy_ps += max(0, o + d - max(o, last_end))
        last_end = max(last_end, o + d)
    return events, busy_ps, sum(d for n, _o, d in events
                                if "tpu_custom_call" in n)


@pytest.mark.parametrize("what", ["window", "busy", "kernel", "programs",
                                  "gaps", "top_ops"])
def test_trace_reduce_on_the_recorded_trace(what):
    """``fixtures/serve_slice.xspace.txt``: 78 ms cut from the first
    traced chip run of ``gpt2-medium.chat_closed16`` (PR 30): one
    prefill program, one decode step, 24 paged-attention kernel calls.
    The sums are worked out again here from the file's text."""
    from perfbench import trace_reduce as tr
    assert os.path.getsize(FIXTURE) < 1_000_000
    events, busy_ps, kernel_ps = _fixture_by_hand()
    t = tr.load(FIXTURE)
    if what == "window":
        assert t.n_devices == 1 and len(t.ops) == len(events) == 2988
        assert abs(t.window_s - 0.078) < 1e-12
    elif what == "busy":
        assert abs(tr.busy_seconds(t) - busy_ps * 1e-12) < 1e-9
        assert abs(tr.busy_seconds(t) - 0.068181661) < 1e-8
    elif what == "kernel":
        seconds, calls = tr.op_seconds(t, KERNEL)
        assert calls == 24 and abs(seconds - kernel_ps * 1e-12) < 1e-9
        assert abs(seconds - 0.004790186) < 1e-8
        assert tr.op_seconds(t, "no_such_kernel") == (0.0, 0)
    elif what == "programs":
        assert sorted(round(1e3 * (e - s), 3) for _d, _n, s, e in t.modules) \
            == [26.395, 41.76]
    elif what == "gaps":
        gaps = dict(tr.idle_gaps(t))
        assert set(gaps) == {"pb.generate", "unannotated"}
        assert abs(sum(gaps.values()) - (0.078 - busy_ps * 1e-12)) < 1e-9
    else:
        top = tr.top_ops(t, 3)     # summed over an operation's instances
        assert [k for k, _v in top] == [
            "copy f32[24,769,16,16,64] x8",
            "slice_bitcast_fusion f32[769,16,16,64] x48",
            "_unknown_ f32[16,16,64] x24"]      # the paged kernel's 24 calls
        assert abs(top[2][1] - kernel_ps * 1e-12) < 1e-9
        assert tr.kind_and_shape(
            "%fusion.2 = (f32[8,4]{1,0}, f32[8]{0}) fusion(f32[8] %x)") \
            == "fusion f32[8,4]"
