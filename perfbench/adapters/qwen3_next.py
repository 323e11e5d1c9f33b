"""Qwen3-Next next-token training through ``models.get_decoder_lm`` +
``parallel.ShardedTrainer`` on ``make_mesh(dp=1, tp=1, sp=1, ep=1)``:
the program's ordinary path, with one chip's share of the routed experts
and of the vocabulary as the configuration states it.  Of a batch of the
one generator it takes the rows of tokens; a row's labels are the row
shifted by one (Mellum's adapter's loss)."""
import sys

import jax
import numpy as np

from ..reference import qwen3_next as ref
from . import lfm2_moe, mellum_moe

# {"steps", "rows" (layers, experts held)}: what the device-side count of
# routed rows gained between the last two calls of ``programs()``, which
# the runner makes just before and just after its window
WINDOW = {}

# the reference's leaf -> the attribute of the layer's first block (its
# mixer) or of its second (its feed-forward) that holds it
_MIXER = {
    "linear_attention": {"qkvz_w": "in_proj.weight", "ba_w": "ba_proj.weight",
                         "conv_w": "conv_weight", "dt_bias": "dt_bias",
                         "A_log": "A_log", "gate_norm_g": "norm_gamma",
                         "out_w": "out_proj.weight"},
    "full_attention": {"q_w": "q_proj.weight", "kv_w": "kv_proj.weight",
                       "o_w": "out_proj.weight", "q_norm_g": "q_norm.gamma",
                       "k_norm_g": "k_norm.gamma"},
}
_FFN = {"router_w": "gate_weight", "w1": "expert_w1", "w2": "expert_w2",
        "shared_w1": "shared_w1", "shared_w2": "shared_w2",
        "shared_gate_w": "shared_gate_weight"}


def _name_map(lm, dims):
    """canonical leaf name (perfbench/reference/qwen3_next.py) -> the
    program's parameter name, found by walking the blocks."""
    m = {"embed": lm.word_embed.weight, "final_norm_g": lm.final_norm.gamma,
         "head_w": lm.lm_head.weight}
    for i, (cell, kind) in enumerate(zip(lm.cells, dims["layer_types"])):
        m[f"l{i}.op_norm_g"] = cell.attn_norm.gamma
        m[f"l{i}.ffn_norm_g"] = cell.ffn_norm.gamma
        # DecoderCell keeps a two-part layer's mixer under ``attention``
        # whatever its kind
        for block, leaves in ((cell.attention, _MIXER[kind]),
                              (cell.ffn, _FFN)):
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
        device = mellum_moe.one_device("qwen3_next", devices, mesh)
        if not cfg["use_flash"]:
            raise ValueError("qwen3_next adapter: the model has no "
                             "attention but the flash kernels'")
        # the registry holds the model's constants (the gated attention's
        # gate, partial rotary and theta, the norms' epsilon, the shared
        # expert's gate); what is passed is the cut, the cell's two
        # switches and the widths that a toy shrinks
        lm = models.get_decoder_lm(
            cfg["model_name"], layer_types=tuple(dims["layer_types"]),
            recompute_experts=cfg["recompute_experts"],
            attention_dtype=cfg["precision"]["attention"],
            gdn=dict(key_heads=dims["linear_key_heads"],
                     value_heads=dims["linear_value_heads"],
                     key_dim=dims["linear_key_dim"],
                     value_dim=dims["linear_value_dim"],
                     conv_kernel=dims["conv_kernel"], chunk=dims["chunk"],
                     norm_eps=dims["rms_norm_eps"]),
            **{k: dims[k] for k in (
                "vocab_size", "experts_held", "first_expert", "train_router",
                "units", "num_heads", "num_kv_heads", "head_dim",
                "rotary_dim", "num_experts", "experts_per_token",
                "expert_hidden_size", "shared_expert_hidden_size")})
        # load_weights overwrites every leaf from the seed
        lm.initialize(mx.init.Zero())
        opt = cfg["optimizer"]
        tokens = example_batch[0]
        mesh = parallel.make_mesh(dp=1, tp=1, sp=1, ep=1, devices=[device])
        self.beta1 = opt["beta1"]
        self.dims = dims
        self.trainer = parallel.ShardedTrainer(
            lm, mellum_moe.next_token_loss, mesh, optimizer=opt["name"],
            optimizer_params={k: opt[k] for k in
                              ("learning_rate", "beta1", "beta2", "eps",
                               "weight_decay")},
            example_inputs=(nd.array(tokens),), n_labels=1,
            take_block_params=True)
        self.names = _name_map(lm, dims)
        self.counters = [c.ffn.rows_routed.name for c in lm.cells]
        missing = (set(self.trainer.params) - set(self.names.values())
                   - set(self.counters))
        if missing:
            raise RuntimeError(f"qwen3_next adapter: parameters the "
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
                print(f"qwen3_next: {s1 - s0} steps; rows routed to held "
                      f"experts a step, by layer: "
                      f"{[round(float(x), 1) for x in per.sum(1)]}; the "
                      f"largest expert's load over the mean, by layer: "
                      f"{[round(float(x), 3) for x in per.max(1) / per.mean(1)]}",
                      file=sys.stderr, flush=True)
                print(self._host_clock(s1 - s0).replace(
                    "lfm2_moe:", "qwen3_next:", 1), file=sys.stderr,
                    flush=True)
        return t._step._cache_size()


def build(cfg, dims, example_batch, devices, mesh=None):
    """``devices``: every device of the cell (one device alone, as
    ``tests/test_scope_taxonomy.py`` hands it, is a cell of one);
    ``mesh``: the mix's, absent = one device."""
    return Program(cfg, dims, example_batch, devices, mesh)
