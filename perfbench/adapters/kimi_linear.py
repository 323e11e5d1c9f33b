"""Kimi-Linear next-token training through ``models.get_decoder_lm`` +
``parallel.ShardedTrainer`` on ``make_mesh(dp=1, tp=1, sp=1, ep=1)``:
the program's ordinary path, with one chip's share of the routed experts
and of the vocabulary as the configuration states it.  Of a batch of the
one generator it takes the rows of tokens; a row's labels are the row
shifted by one (Mellum's adapter's loss)."""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import kimi_linear as ref
from . import lfm2_moe, mellum_moe

# {"steps", "rows" (expert layers, experts held)}: what the device-side
# count of routed rows gained between the last two calls of
# ``programs()``, which the runner makes just before and just after its
# window
WINDOW = {}

# the reference's leaf -> the attribute of the layer's first block (its
# mixer) or of its second (its feed-forward) that holds it
_MIXER = {
    "kda": {"qkv_w": "qkv_proj", "fa_w": "f_a_proj", "fb_w": "f_b_proj",
            "b_w": "b_proj", "ga_w": "g_a_proj", "gb_w": "g_b_proj",
            "conv_w": "conv_weight", "A_log": "A_log", "dt_bias": "dt_bias",
            "gate_norm_g": "norm_gamma", "out_w": "out_proj"},
    "mla": {"q_w": "q_proj.weight", "kva_w": "kv_a_proj.weight",
            "kv_norm_g": "kv_norm.gamma", "kvb_w": "kv_b_proj.weight",
            "o_w": "out_proj.weight"},
}
_FFN = {
    "dense": {"ffn_w1": "ffn_1.weight", "ffn_w2": "ffn_2.weight"},
    "moe": {"router_w": "gate_weight", "router_bias": "route_bias",
            "w1": "expert_w1", "w2": "expert_w2", "shared_w1": "shared_w1",
            "shared_w2": "shared_w2"},
}


def next_token_loss(logits, labels):
    """Mellum's adapter's loss, the mean cross-entropy of positions 0 ..
    L-2 against the next token, with no slice of the (L, vocabulary)
    logits: every position's log-sum-exp and picked logit are taken over
    the whole row (the last position picks token 0) and the last is left
    out of the mean.  The slice of 8,191 rows is written out, a second
    640 MB array beside the logits, which the step cannot hold (PERF.md
    section 4)."""
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(
        logits, jnp.pad(labels, ((0, 0), (0, 1)))[..., None], -1)[..., 0]
    return (jax.nn.logsumexp(logits, axis=-1) - picked)[:, :-1].mean()


def _name_map(lm, dims):
    """canonical leaf name (perfbench/reference/kimi_linear.py) -> the
    program's parameter name, found by walking the blocks."""
    m = {"embed": lm.word_embed.weight, "final_norm_g": lm.final_norm.gamma,
         "head_w": lm.lm_head.weight}
    for i, (cell, kind) in enumerate(zip(lm.cells, dims["layer_types"])):
        m[f"l{i}.op_norm_g"] = cell.attn_norm.gamma
        m[f"l{i}.ffn_norm_g"] = cell.ffn_norm.gamma
        # DecoderCell keeps a two-part layer's mixer under ``attention``
        # whatever its kind
        for block, leaves in ((cell.attention, _MIXER[kind]),
                              (cell.ffn, _FFN[ref.ffn_kind(dims, i)])):
            for leaf, path in leaves.items():
                p = block
                for part in path.split("."):
                    p = getattr(p, part)
                m[f"l{i}.{leaf}"] = p
    return {k: p.name for k, p in m.items()}


class Program(lfm2_moe.Program):
    """One ``ShardedTrainer`` with its state.  Loading the weights,
    stepping, the host clock's reading and freeing are LFM2's and
    Mellum's adapters'; what builds this family, names its reference or
    its counter is written here."""

    def __init__(self, cfg, dims, example_batch, devices, mesh=None):
        import mxnet_tpu as mx
        from mxnet_tpu import models, nd, parallel
        device = mellum_moe.one_device("kimi_linear", devices, mesh)
        if not cfg["use_flash"]:
            raise ValueError("kimi_linear adapter: the model has no "
                             "attention but the flash kernels'")
        # the registry holds the model's constants (the sigmoid router
        # and its scale, the norms' epsilon); what is passed is the cut,
        # the cell's two switches and the widths that a toy shrinks
        eps = dims["rms_norm_eps"]
        lm = models.get_decoder_lm(
            cfg["model_name"], layer_types=tuple(dims["layer_types"]),
            recompute_experts=cfg["recompute_experts"],
            attention_dtype=cfg["precision"]["attention"],
            kda=dict(heads=dims["kda_heads"], head_dim=dims["kda_head_dim"],
                     conv_kernel=dims["conv_kernel"], chunk=dims["chunk"],
                     norm_eps=eps),
            mla=dict(qk_nope_dim=dims["qk_nope_dim"],
                     qk_rope_dim=dims["qk_rope_dim"],
                     v_head_dim=dims["v_head_dim"],
                     kv_lora_rank=dims["kv_lora_rank"], latent_eps=eps),
            **{k: dims[k] for k in (
                "vocab_size", "experts_held", "first_expert",
                "dense_ffn_layers", "train_router", "units", "num_heads",
                "hidden_size", "num_experts", "experts_per_token",
                "expert_hidden_size", "shared_expert_hidden_size")})
        # load_weights overwrites every leaf from the seed
        lm.initialize(mx.init.Zero())
        opt = cfg["optimizer"]
        tokens = example_batch[0]
        mesh = parallel.make_mesh(dp=1, tp=1, sp=1, ep=1, devices=[device])
        self.beta1 = opt["beta1"]
        self.dims = dims
        self.trainer = parallel.ShardedTrainer(
            lm, next_token_loss, mesh, optimizer=opt["name"],
            optimizer_params={k: opt[k] for k in
                              ("learning_rate", "beta1", "beta2", "eps",
                               "weight_decay")},
            example_inputs=(nd.array(tokens),), n_labels=1,
            take_block_params=True)
        self.names = _name_map(lm, dims)
        self.counters = [c.ffn.rows_routed.name
                         for i, c in enumerate(lm.cells)
                         if ref.ffn_kind(dims, i) == "moe"]
        missing = (set(self.trainer.params) - set(self.names.values())
                   - set(self.counters))
        if missing:
            raise RuntimeError(f"kimi_linear adapter: parameters the "
                               f"reference does not know: {sorted(missing)}")
        self._readings = []
        self._calls = []        # the host's clock at each step()'s start

    def first_grad_norms(self):
        """Leaf norms of the gradient the optimizer got in step 1, from
        its first-moment state after that one step (m = (1 - beta1) g)."""
        scale = 1.0 / (1.0 - self.beta1)
        norms = jax.jit(lambda m: ref.leaf_norms(m, self.dims))(
            self._canonical(self.trainer.opt_state["mean"]))
        return {n: scale * float(x) for n, x in jax.device_get(norms).items()}

    def change_norms(self, weights0):
        """Leaf norms of (parameters now - ``weights0``)."""
        norms = jax.jit(lambda p, q: ref.leaf_norms(
            {n: p[n] - q[n] for n in p}, self.dims))(
            self._canonical(self.trainer.params), weights0)
        return {n: float(x) for n, x in jax.device_get(norms).items()}

    def programs(self):
        """The step's compiled programs so far.  The runner calls this
        just before and just after its window, never inside it: the one
        place where the device-side count of routed rows is read."""
        t = self.trainer
        rows = np.stack(jax.device_get([t.params[n] for n in self.counters]))
        self._readings.append((t._step_no, rows))
        if len(self._readings) > 1:
            (s0, r0), (s1, r1) = self._readings[-2:]
            WINDOW.update(steps=s1 - s0, rows=r1 - r0)
            if s1 > s0:
                per = (r1 - r0) / (s1 - s0)
                load = per.max(1) / per.mean(1)
                print(f"kimi_linear: {s1 - s0} steps; rows routed to held "
                      f"experts a step, by expert layer: "
                      f"{[round(float(x), 1) for x in per.sum(1)]}; the "
                      f"largest expert's load over the mean, by layer: "
                      f"{[round(float(x), 3) for x in load]}",
                      file=sys.stderr, flush=True)
                print(self._host_clock(s1 - s0).replace(
                    "lfm2_moe:", "kimi_linear:", 1), file=sys.stderr,
                    flush=True)
        return t._step._cache_size()


def build(cfg, dims, example_batch, devices, mesh=None):
    """``devices``: every device of the cell (one device alone, as
    ``tests/test_scope_taxonomy.py`` hands it, is a cell of one);
    ``mesh``: the mix's, absent = one device."""
    return Program(cfg, dims, example_batch, devices, mesh)
