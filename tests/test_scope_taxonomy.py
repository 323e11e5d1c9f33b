"""The device-side scopes of the training step are a closed taxonomy
(``mxnet_tpu.tracing.SCOPES``, docs/observability.md): every operation
of ``jit_mx_train_step`` that carries a jax name lies under exactly one
leaf, and every leaf is read by a metric: a file of the benchmark's,
or one of the account's eight (``perfbench.readers.scope_account.
METRICS``, whose files a ``benchmark`` PR adds).  The toy
models are the four families' own, through the adapters and ``toy``
sizes the benchmark's rehearsals use."""
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

from mxnet_tpu import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
CELLS = ["bert-large.pretrain_b32_l128",
         "mellum2-12b-a2.5b.causal_b1_l8192",
         "nemotron-3-nano-30b-a3b.causal_b1_l8192",
         "lfm2-24b-a2b.causal_b1_l8192"]
# what each family's toy step must carry besides mx.loss and mx.optim
FAMILY = {
    CELLS[0]: {"mx.embed", "mx.norm", "mx.head", "mx.attn.proj",
               "mx.attn.dense", "mx.ffn.dense"},
    CELLS[1]: {"mx.embed", "mx.norm", "mx.head", "mx.attn.proj",
               "mx.attn.window", "mx.attn.full", "mx.rope", "mx.moe.route",
               "mx.moe.dispatch", "mx.moe.experts", "mx.moe.combine"},
    CELLS[2]: {"mx.embed", "mx.norm", "mx.head", "mx.attn.proj",
               "mx.attn.full", "mx.moe.route", "mx.moe.experts",
               "mx.moe.shared", "mx.ssm.in_proj", "mx.ssm.conv",
               "mx.ssm.scan", "mx.ssm.gate_norm", "mx.ssm.out_proj"},
    CELLS[3]: {"mx.embed", "mx.norm", "mx.head", "mx.attn.proj",
               "mx.attn.full", "mx.attn.qk_norm", "mx.rope", "mx.ffn.dense",
               "mx.moe.route", "mx.moe.experts", "mx.sconv.in_proj",
               "mx.sconv.conv", "mx.sconv.out_proj"},
}
LEAVES = set(tracing.SCOPES) - tracing.SCOPE_CONTAINERS
SCOPE = re.compile(r"(?:^|[/(])(mx\.[\w.]+)(?=[/):]|$)")


@pytest.fixture(scope="module", params=CELLS)
def lowered(request):
    """(cell, {location id: jax name}, the ids of the step's
    ``dot_general``s) of the family's toy step, lowered on the CPU."""
    from perfbench import harness, traffic
    _cell, cfg, mix = harness.load_cell(request.param)
    dims, mix = cfg["toy"], mix["toy"]
    batch = traffic.mlm_batches(mix, dims["vocab_size"], 1)[0]
    program = harness.module("adapters", cfg["adapter"]).build(
        dict(cfg, use_flash=mix.get("use_flash", False)), dims, batch,
        jax.devices()[0])
    trainer = program.trainer
    if cfg["adapter"] != "bert_pretrain":
        tokens = np.asarray(batch[0])
        batch = (tokens, tokens[:, 1:])
    text = trainer._step.lower(
        trainer.params, trainer.opt_state,
        *trainer.shard_batch(*batch)).as_text(debug_info=True)
    names = {k: v for k, v in re.findall(
        r'^#(loc\d+) = loc\("((?:[^"\\]|\\.)*)"', text, re.M)
        if v.startswith("jit(mx_train_step)")}
    dots = re.findall(r"stablehlo\.dot_general .* loc\(#(loc\d+)\)", text)
    return request.param, names, dots


def test_every_component_is_in_the_table(lowered):
    cell, names, _dots = lowered
    met = {s for name in names.values() for s in SCOPE.findall(name)}
    assert met <= set(tracing.SCOPES), met - set(tracing.SCOPES)
    assert FAMILY[cell] | {"mx.fwd", "mx.loss", "mx.optim"} <= met, \
        (FAMILY[cell] | {"mx.fwd", "mx.loss", "mx.optim"}) - met


def test_no_location_carries_two_leaves(lowered):
    """A leaf may meet itself (a ``custom_vjp``'s backward opens its
    forward's scope again under the transpose); two different leaves
    in one name would be counted twice."""
    _cell, names, _dots = lowered
    two = {name for name in names.values()
           if len(set(SCOPE.findall(name)) & LEAVES) > 1}
    assert not two, sorted(two)[:5]


def test_every_matmul_lies_under_a_leaf(lowered):
    """Forward and backward: a ``dot_general`` outside every leaf is
    time no metric would say the place of."""
    _cell, names, dots = lowered
    assert len(dots) >= 10
    bare = {names.get(d, d) for d in dots
            if not set(SCOPE.findall(names.get(d, ""))) & LEAVES}
    assert not bare, sorted(bare)[:5]


# ------------------------------------------------------------ static checks
def _literals():
    """{scope literal: [file]} of every ``named_scope("mx.`` under
    ``mxnet_tpu/`` (conditional expressions give two)."""
    found = {}
    for folder, _dirs, files in os.walk(os.path.join(REPO, "mxnet_tpu")):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(folder, fn)) as f:
                text = f.read()
            for call in re.findall(
                    r"named_scope\((\s*\"mx\.[^)]*)\)", text):
                for lit in re.findall(r'"(mx\.[\w.]+)"', call):
                    found.setdefault(lit, []).append(fn)
    return found


def _metric_scopes():
    """{leaf: [metric]} over ``perfbench/metrics/*.json`` and the
    account's table."""
    from perfbench.readers import scope_account
    out = {}
    for name, params in scope_account.METRICS.items():
        for s in params.get("scopes", []):
            out.setdefault(s, []).append(name)
    folder = os.path.join(REPO, "perfbench", "metrics")
    for fn in sorted(os.listdir(folder)):
        with open(os.path.join(folder, fn)) as f:
            m = json.load(f)
        for s in m.get("params", {}).get("scopes", []):
            if m["name"] not in out.setdefault(s, []):
                out[s].append(m["name"])
    return out


def test_every_scope_literal_is_in_the_table():
    found = _literals()
    assert set(found) == set(tracing.SCOPES), \
        set(found) ^ set(tracing.SCOPES)
    assert "mx.act" not in found and "mx.act" not in tracing.SCOPES
    assert tracing.SCOPE_CONTAINERS == {"mx.fwd"}
    assert all(line and "\n" not in line
               for line in tracing.SCOPES.values())


def test_every_leaf_is_documented_and_read_by_a_metric():
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        doc = f.read()
    rows = {s: row for row in doc.splitlines() if row.startswith("| `mx.")
            for s in re.findall(r"`(mx\.[\w.]+)`", row.split("|")[1])}
    read = _metric_scopes()
    for leaf in sorted(LEAVES):
        assert leaf in rows, f"{leaf}: no row in docs/observability.md"
        if leaf not in read:
            assert "operators only" in rows[leaf], leaf
        for metric in read.get(leaf, []):
            assert metric in doc, (leaf, metric)
    assert set(read) <= set(tracing.SCOPES), set(read) - set(tracing.SCOPES)
    assert "mx.act" not in doc


def test_the_reader_names_the_same_containers():
    from perfbench.readers import scope_account
    assert set(scope_account.CONTAINERS) == tracing.SCOPE_CONTAINERS
