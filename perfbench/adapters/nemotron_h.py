"""Nemotron-3-Nano next-token training through ``models.get_decoder_lm``
+ ``parallel.ShardedTrainer`` on ``make_mesh(dp=1, tp=1, sp=1, ep=1)``:
the program's ordinary path, with one chip's share of the routed experts
and of the vocabulary as the configuration states it.  Of a batch of the
one generator it takes the rows of tokens; a row's labels are the row
shifted by one (Mellum's adapter's loss)."""
import sys
import threading
import time

import jax
import numpy as np

from ..reference import nemotron_h as ref
from . import mellum_moe

# {"steps", "rows" (expert layers, experts held)}: what the device-side
# count of routed rows gained between the last two calls of
# ``programs()``, which the runner makes just before and just after its
# window
WINDOW = {}

# the reference's leaf -> the block's attribute that holds it, by kind
_LEAVES = {
    "mamba2": {"in_w": "in_proj.weight", "conv_w": "conv_weight",
               "conv_b": "conv_bias", "dt_bias": "dt_bias", "A_log": "A_log",
               "D": "D", "gate_norm_g": "norm_gamma",
               "out_w": "out_proj.weight"},
    "attention": {"q_w": "q_proj.weight", "kv_w": "kv_proj.weight",
                  "o_w": "out_proj.weight"},
    "moe": {"router_w": "gate_weight", "router_bias": "route_bias",
            "w1": "expert_w1", "w2": "expert_w2", "shared_w1": "shared_w1",
            "shared_w2": "shared_w2"},
}


def _name_map(lm, layer_types):
    """canonical leaf name (perfbench/reference/nemotron_h.py) -> the
    program's parameter name, found by walking the blocks."""
    m = {"embed": lm.word_embed.weight, "final_norm_g": lm.final_norm.gamma,
         "head_w": lm.lm_head.weight}
    for i, (cell, kind) in enumerate(zip(lm.cells, layer_types)):
        m[f"l{i}.norm_g"] = cell.norm.gamma
        for leaf, path in _LEAVES[kind].items():
            p = cell.mixer
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
        device = mellum_moe.one_device("nemotron_h", devices, mesh)
        if not cfg["use_flash"]:
            raise ValueError("nemotron_h adapter: the model has no "
                             "attention but the flash kernels'")
        kinds = tuple(dims["layer_types"])
        lm = models.get_decoder_lm(
            cfg["model_name"], layer_types=kinds,
            mamba=dict(num_heads=dims["mamba_num_heads"],
                       head_dim=dims["mamba_head_dim"],
                       state_size=dims["ssm_state_size"],
                       n_groups=dims["n_groups"],
                       conv_kernel=dims["conv_kernel"],
                       chunk=dims["chunk_size"],
                       norm_eps=dims["rms_norm_eps"]),
            router=dict(scoring="sigmoid",
                        route_scale=dims["routed_scaling_factor"]),
            recompute_experts=cfg["recompute_experts"],
            attention_dtype=cfg["precision"]["attention"],
            **{k: dims[k] for k in (
                "vocab_size", "units", "num_heads", "num_kv_heads",
                "head_dim", "num_experts", "experts_per_token",
                "expert_hidden_size", "shared_expert_hidden_size",
                "experts_held", "first_expert", "rms_norm_eps",
                "train_router")})
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
        self.names = _name_map(lm, kinds)
        self.counters = [c.mixer.rows_routed.name
                         for c, kind in zip(lm.cells, kinds) if kind == "moe"]
        missing = (set(self.trainer.params) - set(self.names.values())
                   - set(self.counters))
        if missing:
            raise RuntimeError(f"nemotron_h adapter: parameters the "
                               f"reference does not know: {sorted(missing)}")
        self._readings = []
        # what _host_clock reports: (host clock at step()'s start, at
        # its end), each step's loss, and a second thread's view of both
        self._calls, self._losses = [], []
        self._ticks, self._ready_at = [], {}
        self._stop = threading.Event()  # the second thread lives from the
                                        # first programs() to the second

    def step(self, batch):
        t0 = time.perf_counter()
        loss = super().step(batch)
        self._calls.append((t0, time.perf_counter()))
        self._losses.append(loss)
        return loss

    def _tick(self, every=0.02):
        """A second thread's clock, and when IT first saw each step's
        loss ready: tells a process that stood still from a main thread
        woken late, and both from a device that finished late."""
        seen = 0
        while not self._stop.wait(every):
            now = time.perf_counter()
            self._ticks.append(now)
            while seen < len(self._losses) and self._losses[seen].is_ready():
                self._ready_at[seen] = now
                seen += 1

    def free(self):
        self._stop.set()
        self._losses.clear()
        super().free()

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
        if len(self._readings) == 1:
            threading.Thread(target=self._tick, daemon=True).start()
        else:
            self._stop.set()
            (s0, r0), (s1, r1) = self._readings[-2:]
            WINDOW.update(steps=s1 - s0, rows=r1 - r0)
            if s1 > s0:
                per = (r1 - r0) / (s1 - s0)
                print(f"nemotron_h: {s1 - s0} steps; rows routed to held "
                      f"experts a step, by expert layer: "
                      f"{[round(float(x), 1) for x in per.sum(1)]}; the "
                      f"largest expert's load over the mean, by layer: "
                      f"{[round(float(x), 3) for x in per.max(1) / per.mean(1)]}",
                      file=sys.stderr, flush=True)
                print(self._host_clock(s1 - s0), file=sys.stderr, flush=True)
        return t._step._cache_size()

    def _host_clock(self, steps):
        """Where the last ``steps`` calls of ``step()`` stood on the
        host's clock.  With two steps in flight, call j starts when the
        loss of call j - 3 is ready, 363 ms after the call before: a
        call that starts late is a stall of the window (one untraced
        run in six loses 1 to 2 s to one: PERF.md section 7).  Each
        late call is given with the next two (calls that follow at once
        found their steps already done: the device went on while the
        host stood), the second thread's longest silence around it, and
        how long before the call that thread saw the awaited loss
        ready."""
        first = len(self._calls) - steps
        starts, ends = np.asarray(self._calls[first:]).T
        every, inside = np.diff(starts), ends - starts
        ticks = np.asarray(self._ticks)
        late = []
        for k in np.flatnonzero(every > 1.5 * np.median(every)):
            j = int(k) + 1
            around = ticks[(ticks > starts[k]) & (ticks < starts[j] + 1.0)]
            ready = self._ready_at.get(first + j - 3)
            late.append({
                "call": j, "ms_after_the_one_before": [
                    round(1e3 * float(x), 1) for x in every[k:k + 3]],
                "ms_inside": round(1e3 * float(inside[j]), 1),
                "second_thread_longest_silence_ms": round(1e3 * float(
                    np.diff(around).max()), 1) if len(around) > 1 else None,
                "awaited_loss_seen_ready_ms_before_the_call": None
                if ready is None else round(1e3 * float(starts[j] - ready), 1)})
        return (f"nemotron_h: host clock: a call of step() every "
                f"{1e3 * np.median(every):.1f} ms (median), "
                f"{1e3 * np.median(inside):.2f} ms inside it (longest "
                f"{1e3 * inside.max():.1f}); calls over 1.5 times the median "
                f"after the one before: {late}")


def build(cfg, dims, example_batch, devices, mesh=None):
    """``devices``: every device of the cell (one device alone, as
    ``tests/test_scope_taxonomy.py`` hands it, is a cell of one);
    ``mesh``: the mix's, absent = one device."""
    return Program(cfg, dims, example_batch, devices, mesh)
