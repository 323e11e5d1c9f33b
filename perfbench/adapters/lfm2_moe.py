"""LFM2 next-token training through ``models.get_decoder_lm`` +
``parallel.ShardedTrainer`` on ``make_mesh(dp=1, tp=1, sp=1, ep=1)``:
the program's ordinary path, with one chip's share of the routed experts
and of the vocabulary as the configuration states it.  Of a batch of the
one generator it takes the rows of tokens; a row's labels are the row
shifted by one (Mellum's adapter's loss)."""
import sys
import time

import jax
import numpy as np

from ..reference import lfm2_moe as ref
from . import mellum_moe

# {"steps", "rows" (expert layers, experts held)}: what the device-side
# count of routed rows gained between the last two calls of
# ``programs()``, which the runner makes just before and just after its
# window
WINDOW = {}

# the reference's leaf -> the attribute of the layer's first block (its
# operator) or of its second (its feed-forward) that holds it
_OPERATOR = {
    "conv": {"in_w": "in_proj.weight", "conv_w": "conv_weight",
             "out_w": "out_proj.weight"},
    "full_attention": {"q_w": "q_proj.weight", "kv_w": "kv_proj.weight",
                       "o_w": "out_proj.weight", "q_norm_g": "q_norm.gamma",
                       "k_norm_g": "k_norm.gamma"},
}
_FFN = {
    "dense": {"ffn_w1": "ffn_1.weight", "ffn_w2": "ffn_2.weight"},
    "moe": {"router_w": "gate_weight", "router_bias": "route_bias",
            "w1": "expert_w1", "w2": "expert_w2"},
}


def _name_map(lm, dims):
    """canonical leaf name (perfbench/reference/lfm2_moe.py) -> the
    program's parameter name, found by walking the blocks.  The head has
    no leaf: it reads the embedding's."""
    m = {"embed": lm.word_embed.weight, "final_norm_g": lm.final_norm.gamma}
    for i, (cell, kind) in enumerate(zip(lm.cells, dims["layer_types"])):
        m[f"l{i}.op_norm_g"] = cell.attn_norm.gamma
        m[f"l{i}.ffn_norm_g"] = cell.ffn_norm.gamma
        # DecoderCell keeps a two-part layer's operator under
        # ``attention`` whatever its kind
        for block, leaves in ((cell.attention, _OPERATOR[kind]),
                              (cell.ffn, _FFN[ref.ffn_kind(dims, i)])):
            for leaf, path in leaves.items():
                p = block
                for part in path.split("."):
                    p = getattr(p, part)
                m[f"l{i}.{leaf}"] = p
    return {k: p.name for k, p in m.items()}


class Program(mellum_moe.Program):
    """One ``ShardedTrainer`` with its state: the object the set-up
    drives through its first steps and the window goes on stepping.
    Loading the weights, stepping and freeing are Mellum's adapter's;
    what names this family's reference or its counter is written here."""

    def __init__(self, cfg, dims, example_batch, devices, mesh=None):
        import mxnet_tpu as mx
        from mxnet_tpu import models, nd, parallel
        device = mellum_moe.one_device("lfm2_moe", devices, mesh)
        if not cfg["use_flash"]:
            raise ValueError("lfm2_moe adapter: the model has no "
                             "attention but the flash kernels'")
        # the registry holds the model's constants (the tied head, the
        # sigmoid router and its epsilon, the q/k norm, rotary, the
        # taps); what is passed is the cut, the cell's two switches and
        # the widths that a toy shrinks
        lm = models.get_decoder_lm(
            cfg["model_name"], layer_types=tuple(dims["layer_types"]),
            recompute_experts=cfg["recompute_experts"],
            attention_dtype=cfg["precision"]["attention"],
            **{k: dims[k] for k in (
                "vocab_size", "experts_held", "first_expert",
                "dense_ffn_layers", "train_router",
                "units", "num_heads", "num_kv_heads", "head_dim",
                "hidden_size", "num_experts", "experts_per_token",
                "expert_hidden_size")})
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
        self.counters = [c.ffn.rows_routed.name
                         for i, c in enumerate(lm.cells)
                         if ref.ffn_kind(dims, i) == "moe"]
        missing = (set(self.trainer.params) - set(self.names.values())
                   - set(self.counters))
        if missing:
            raise RuntimeError(f"lfm2_moe adapter: parameters the "
                               f"reference does not know: {sorted(missing)}")
        self._readings = []
        self._calls = []        # the host's clock at each step()'s start

    def step(self, batch):
        self._calls.append(time.perf_counter())
        return super().step(batch)

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
                print(f"lfm2_moe: {s1 - s0} steps; rows routed to held "
                      f"experts a step, by expert layer: "
                      f"{[round(float(x), 1) for x in per.sum(1)]}; the "
                      f"largest expert's load over the mean, by layer: "
                      f"{[round(float(x), 3) for x in per.max(1) / per.mean(1)]}",
                      file=sys.stderr, flush=True)
                print(self._host_clock(s1 - s0), file=sys.stderr, flush=True)
        return t._step._cache_size()

    def _host_clock(self, steps):
        """Where the last ``steps`` calls of ``step()`` stood on the
        host's clock.  With two steps in flight a call starts when the
        loss of the call three before it is ready, one step's device
        time after the call before: a call that starts late is a stall
        of the window (PERF.md section 7 has the other cells')."""
        every = np.diff(self._calls[-steps:])
        late = {int(k) + 1: round(1e3 * float(every[k]), 1)
                for k in np.flatnonzero(every > 1.5 * np.median(every))}
        return (f"lfm2_moe: host clock: a call of step() every "
                f"{1e3 * np.median(every):.1f} ms (median, longest "
                f"{1e3 * every.max():.1f}); calls over 1.5 times the median "
                f"after the one before, call: ms: {late}")


def build(cfg, dims, example_batch, devices, mesh=None):
    """``devices``: every device of the cell (one device alone, as
    ``tests/test_scope_taxonomy.py`` hands it, is a cell of one);
    ``mesh``: the mix's, absent = one device."""
    return Program(cfg, dims, example_batch, devices, mesh)
