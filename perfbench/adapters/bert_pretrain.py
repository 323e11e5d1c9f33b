"""BERT pretraining through ``models.get_bert_model`` +
``BERTForPretrain`` + ``parallel.ShardedTrainer`` (a copy of
``chip_smoke.py``'s construction, the path PR 21 proved on the chip).
The mix's ``mesh`` (``{"dp": 4}``; absent: one device) says how the
cell's devices are used: data-parallel, every leaf replicated, a host
batch's rows placed over ``dp`` by ``ShardedTrainer.shard_batch``."""
import gc

import jax
import jax.numpy as jnp

from ..reference import bert_pretrain as ref


def pretrain_loss(outputs, mlm_y, nsp_y):
    mlm_scores, nsp_scores = outputs
    mlm_logp = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), -1)
    nsp_logp = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), -1)
    return (-jnp.take_along_axis(mlm_logp, mlm_y[..., None], -1).mean()
            - jnp.take_along_axis(nsp_logp, nsp_y[:, None], -1).mean())


def _name_map(head):
    """canonical leaf name (perfbench/reference/bert_pretrain.py) ->
    the program's parameter name, found by walking the blocks."""
    bert, enc = head.bert, head.bert.encoder
    m = {"word": bert.word_embed.weight, "type": bert.token_type_embed.weight,
         "pos": enc.position_weight,
         "emb_ln_g": enc.layer_norm.gamma, "emb_ln_b": enc.layer_norm.beta,
         "pool_w": bert.pooler.weight, "pool_b": bert.pooler.bias,
         "mlm_w": head.mlm_dense.weight, "mlm_b": head.mlm_dense.bias,
         "mlm_ln_g": head.mlm_norm.gamma, "mlm_ln_b": head.mlm_norm.beta,
         "dec_w": head.mlm_decoder.weight, "dec_b": head.mlm_decoder.bias,
         "nsp_w": head.nsp_classifier.weight,
         "nsp_b": head.nsp_classifier.bias}
    for i, cell in enumerate(enc.transformer_cells):
        att, ffn = cell.attention, cell.ffn
        for leaf, p in (("qkv_w", att.qkv.weight), ("qkv_b", att.qkv.bias),
                        ("o_w", att.out_proj.weight),
                        ("o_b", att.out_proj.bias),
                        ("ln1_g", cell.attn_norm.gamma),
                        ("ln1_b", cell.attn_norm.beta),
                        ("f1_w", ffn.ffn_1.weight), ("f1_b", ffn.ffn_1.bias),
                        ("f2_w", ffn.ffn_2.weight), ("f2_b", ffn.ffn_2.bias),
                        ("ln2_g", ffn.layer_norm.gamma),
                        ("ln2_b", ffn.layer_norm.beta)):
            m[f"l{i}.{leaf}"] = p
    return {k: p.name for k, p in m.items()}


class Program:
    """One ``ShardedTrainer`` with its state: the object the set-up
    drives through its first steps and the window goes on stepping."""

    def __init__(self, cfg, dims, example_batch, devices, mesh=None):
        import mxnet_tpu as mx
        from mxnet_tpu import models, nd, parallel
        # load_weights overwrites every leaf from the seed, so the
        # blocks start from zeros: no 367 M random numbers on the host
        zeros = mx.init.Zero()
        model = models.get_bert_model(
            cfg["model_name"], vocab_size=dims["vocab_size"], dropout=0.0,
            max_length=dims["max_length"], use_flash=cfg["use_flash"],
            **{k: dims[k] for k in ("units", "hidden_size", "num_layers",
                                    "num_heads")})
        model.initialize(zeros)
        head = models.BERTForPretrain(model, vocab_size=dims["vocab_size"])
        head.initialize(zeros)
        opt = cfg["optimizer"]
        feats = tuple(nd.array(a) for a in example_batch[:4])
        mesh = mesh or {}
        if not isinstance(devices, (list, tuple)):
            devices = [devices]
        if set(mesh) - {"dp"}:
            raise ValueError(f"bert_pretrain adapter: the mix asks for the "
                             f"mesh {mesh}; this adapter builds dp only")
        mesh = parallel.make_mesh(dp=mesh.get("dp", 1), tp=1, sp=1,
                                  devices=devices)
        params = cfg["precision"]["params"]
        self.beta1 = opt["beta1"]
        self.heads = dims["num_heads"]
        self.trainer = parallel.ShardedTrainer(
            head, pretrain_loss, mesh, optimizer=opt["name"],
            optimizer_params={k: opt[k] for k in
                              ("learning_rate", "beta1", "beta2", "eps",
                               "weight_decay")},
            example_inputs=feats, n_labels=2,
            dtype=None if params == "float32" else jnp.dtype(params))
        self.names = _name_map(head)
        missing = set(self.trainer.params) - set(self.names.values())
        if missing:
            raise RuntimeError(f"bert_pretrain adapter: parameters the "
                               f"reference does not know: {sorted(missing)}")

    def load_weights(self, weights):
        """Hand the benchmark's weights to the trainer (and fresh
        optimizer state), in the trainer's own type and placement."""
        t = self.trainer
        for leaf, name in self.names.items():
            old = t.params[name]
            t.params[name] = jax.device_put(
                weights[leaf].astype(old.dtype), t.param_shardings[name])
        t.opt_state = jax.tree_util.tree_map(
            lambda a: jnp.zeros_like(a), t.opt_state)

    def step(self, batch):
        return self.trainer.step(*batch)

    def _canonical(self, tree):
        return {leaf: tree[name] for leaf, name in self.names.items()}

    def first_grad_norms(self):
        """Leaf norms of the gradient the optimizer got in step 1, from
        its first-moment state after that one step (m = (1 - beta1) g)."""
        scale = 1.0 / (1.0 - self.beta1)
        norms = jax.jit(lambda m: ref.leaf_norms(m, self.heads))(
            self._canonical(self.trainer.opt_state["mean"]))
        return {n: scale * float(x) for n, x in jax.device_get(norms).items()}

    def change_norms(self, weights0):
        """Leaf norms of (parameters now - ``weights0``)."""
        norms = jax.jit(lambda p, q: ref.leaf_norms(
            {n: p[n].astype(jnp.float32) - q[n].astype(jnp.float32)
             for n in p}, self.heads))(
            self._canonical(self.trainer.params), weights0)
        return {n: float(x) for n, x in jax.device_get(norms).items()}

    def programs(self):
        return self.trainer._step._cache_size()

    def free(self):
        self.trainer = None
        gc.collect()


def build(cfg, dims, example_batch, devices, mesh=None):
    """``devices``: every device of the cell (one device alone, as
    ``tests/test_scope_taxonomy.py`` hands it, is a cell of one);
    ``mesh``: the mix's, absent = one device."""
    return Program(cfg, dims, example_batch, devices, mesh)
