"""The work a window did, counted from what the runner recorded
(``ctx.facts``) and the configuration's sizes, through the count
functions of ``flops.py`` and ``kernels.py``.  Configurations and
metric files name these functions as ``module:function``."""
import importlib

from . import flops, harness, kernels


ITEMSIZE = {"float32": 4, "bfloat16": 2}


def resolve(spec):
    mod, fn = spec.split(":")
    return getattr(importlib.import_module(mod), fn)


def bert_pretrain_flops(ctx):
    tr = ctx.facts["traffic"]
    return ctx.facts["steps"] * flops.bert_pretrain_step_flops(
        ctx.dims, tr["batch"], tr["seqlen"], tr["masked"])


def allreduce_bytes(elements, itemsize, n_devices):
    """Bytes ONE chip sends in an all-reduce of ``elements`` numbers
    over ``n_devices``: 2 (n - 1) / n of the array, the least any
    algorithm sends (a ring's reduce-scatter, then its all-gather)."""
    return 2 * (n_devices - 1) * elements * itemsize / n_devices


def grad_allreduce(ctx):
    """(operations, bytes one chip sends) of the window's gradient
    all-reduces under data parallelism: every trained leaf once a
    step, in float32, as the configuration's reference shapes them."""
    ref = harness.module("reference", ctx.cfg["reference"])
    elements = sum(ref.leaf_sizes(ctx.dims).values())
    return 0, ctx.facts["steps"] * allreduce_bytes(
        elements, ITEMSIZE["float32"], len(ctx.devices))


def _window_tokens(ctx):
    """(prompt length, index of the token within its request) of every
    token that landed in the window."""
    t0, t1 = ctx.facts["t0"], ctx.facts["t1"]
    for r in ctx.facts["records"]:
        for k, t in enumerate(r.t_tokens):
            if t0 <= t <= t1:
                yield len(r.prompt), k


def decoder_serve_flops(ctx):
    """A request's first token costs its prefill; token k after it costs
    one decode position over a context of prompt + k."""
    total = 0
    for n, k in _window_tokens(ctx):
        total += (flops.decoder_prefill_flops(ctx.dims, n) if k == 0
                  else flops.decoder_decode_flops(ctx.dims, n + k))
    return total


def flash_training(ctx):
    tr, d = ctx.facts["traffic"], ctx.dims
    ops, nbytes = kernels.flash_attention_step({
        "batch": tr["batch"], "heads": d["num_heads"], "seqlen": tr["seqlen"],
        "head_dim": d["units"] // d["num_heads"], "layers": d["num_layers"],
        "itemsize": ITEMSIZE[ctx.cfg["precision"]["params"]]})
    return ctx.facts["steps"] * ops, ctx.facts["steps"] * nbytes


def paged_decode(ctx):
    """Every decoded token of the window is one live slot of one decode
    call in each layer, reading its whole context from the pool."""
    d = ctx.dims
    item = ITEMSIZE[ctx.cfg["precision"]["kv_pool"]]
    lens = [n + k for n, k in _window_tokens(ctx) if k > 0]
    ops, nbytes = kernels.paged_attention_decode(
        lens, d["num_heads"], d["units"] // d["num_heads"], item)
    return d["num_layers"] * ops, d["num_layers"] * nbytes
