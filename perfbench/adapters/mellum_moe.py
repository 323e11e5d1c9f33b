"""Mellum2 next-token training through ``models.get_decoder_lm`` +
``parallel.ShardedTrainer`` on ``make_mesh(dp=1, tp=1, sp=1, ep=1)``:
the program's ordinary path, with one chip's share of the experts and
of the vocabulary as the configuration states it.  Of a batch of the
one generator it takes the rows of tokens; a row's labels are the row
shifted by one."""
import gc
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import mellum_moe as ref

# {"steps", "rows" (layers, experts held)}: what the device-side count
# of routed rows gained between the last two calls of ``programs()``,
# which the runner makes just before and just after its window
WINDOW = {}


def next_token_loss(logits, labels):
    """Mean cross-entropy of positions 0 .. L-2 against the next token."""
    logits = logits[:, :-1].astype(jnp.float32)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return (jax.nn.logsumexp(logits, axis=-1) - picked).mean()


def rope_kwargs(rope):
    """The ``rope`` op's keyword arguments from a ``rope_parameters``
    entry of the model's own config.json."""
    kw = {"theta": float(rope["rope_theta"])}
    if rope.get("rope_type") == "yarn":
        kw.update(yarn_factor=float(rope["factor"]),
                  yarn_original_max=rope["original_max_position_embeddings"],
                  yarn_beta_fast=float(rope["beta_fast"]),
                  yarn_beta_slow=float(rope["beta_slow"]),
                  attention_factor=rope["attention_factor"])
    return kw


def _name_map(lm):
    """canonical leaf name (perfbench/reference/mellum_moe.py) -> the
    program's parameter name, found by walking the blocks."""
    m = {"embed": lm.word_embed.weight, "final_norm_g": lm.final_norm.gamma,
         "head_w": lm.lm_head.weight}
    for i, cell in enumerate(lm.cells):
        att, moe = cell.attention, cell.ffn
        for leaf, p in (("attn_norm_g", cell.attn_norm.gamma),
                        ("q_w", att.q_proj.weight),
                        ("kv_w", att.kv_proj.weight),
                        ("o_w", att.out_proj.weight),
                        ("ffn_norm_g", cell.ffn_norm.gamma),
                        ("router_w", moe.gate_weight),
                        ("w1", moe.expert_w1), ("w2", moe.expert_w2)):
            m[f"l{i}.{leaf}"] = p
    return {k: p.name for k, p in m.items()}


def one_device(adapter, devices, mesh=None):
    """The one device a decoder adapter builds on: the cell's first (a
    device alone, as a caller from before PR 43 hands it, is a cell of
    one).  A mix whose ``mesh`` asks for more is refused by the
    adapter's name: the expert leaves carry ``ep``, and no adapter
    places them yet."""
    if math.prod((mesh or {}).values()) != 1:
        raise ValueError(
            f"{adapter} adapter: the mix asks for the mesh {mesh}; this "
            f"adapter builds on one device until a PR gives it ep")
    return devices[0] if isinstance(devices, (list, tuple)) else devices


class Program:
    """One ``ShardedTrainer`` with its state: the object the set-up
    drives through its first steps and the window goes on stepping."""

    def __init__(self, cfg, dims, example_batch, devices, mesh=None):
        import mxnet_tpu as mx
        from mxnet_tpu import models, nd, parallel
        device = one_device("mellum_moe", devices, mesh)
        if not cfg["use_flash"]:
            raise ValueError("mellum_moe adapter: the model has no "
                             "attention but the flash kernels'")
        lm = models.get_decoder_lm(
            cfg["model_name"], layer_types=tuple(dims["layer_types"]),
            rope={kind: rope_kwargs(r)
                  for kind, r in dims["rope_parameters"].items()},
            recompute_experts=cfg["recompute_experts"],
            attention_dtype=cfg["precision"]["attention"],
            **{k: dims[k] for k in (
                "vocab_size", "units", "num_heads", "num_kv_heads",
                "head_dim", "window", "num_experts", "experts_per_token",
                "expert_hidden_size", "experts_held", "first_expert",
                "rms_norm_eps", "train_router")})
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
        self.names = _name_map(lm)
        self.counters = [c.ffn.rows_routed.name for c in lm.cells]
        missing = (set(self.trainer.params) - set(self.names.values())
                   - set(self.counters))
        if missing:
            raise RuntimeError(f"mellum_moe adapter: parameters the "
                               f"reference does not know: {sorted(missing)}")
        self._readings = []

    def load_weights(self, weights):
        """Hand the benchmark's weights to the trainer, leaf by leaf (a
        leaf's old buffer is freed as the new one takes its place), and
        fresh optimizer state."""
        t = self.trainer
        for leaf, name in self.names.items():
            old = t.params[name]
            t.params[name] = jax.device_put(
                weights[leaf].astype(old.dtype), t.param_shardings[name])
            old.delete()
        t.opt_state = jax.tree_util.tree_map(
            lambda a: jnp.zeros_like(a), t.opt_state)

    def step(self, batch):
        tokens = batch[0]
        return self.trainer.step(tokens, tokens[:, 1:])

    def _canonical(self, tree):
        return {leaf: tree[name] for leaf, name in self.names.items()}

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
                print(f"mellum_moe: {s1 - s0} steps; rows routed to held "
                      f"experts a step, by layer: "
                      f"{[round(float(x), 1) for x in per.sum(1)]}; the "
                      f"largest expert's load over the mean, by layer: "
                      f"{[round(float(x), 3) for x in per.max(1) / per.mean(1)]}",
                      file=sys.stderr, flush=True)
        return t._step._cache_size()

    def free(self):
        self.trainer = None
        gc.collect()


def build(cfg, dims, example_batch, devices, mesh=None):
    """``devices``: every device of the cell (one device alone, as
    ``tests/test_scope_taxonomy.py`` hands it, is a cell of one);
    ``mesh``: the mix's, absent = one device."""
    return Program(cfg, dims, example_batch, devices, mesh)
