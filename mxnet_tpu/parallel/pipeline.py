"""Pipeline parallelism: GPipe microbatch schedule over a ``pp`` axis.

New TPU-first capability (SURVEY.md §2.4: upstream has NO pipeline
parallelism — its closest construct, BucketingModule, is dynamic-shape
handling).  Stages live on different devices along a mesh axis; micro-
batches flow stage-to-stage via ``lax.ppermute`` on ICI neighbors inside
one compiled program.  The schedule is the classic GPipe fill-drain:
``T = n_micro + n_stages - 1`` ticks, stage ``p`` processing microbatch
``t - p`` at tick ``t``; expressed as ``lax.scan`` (static shapes, no
data-dependent python control flow), so it jits, differentiates
(reverse-mode replays the schedule backwards — the cotangent ppermutes
ride the reverse ring), and composes with dp/tp on the other mesh axes.

Uniform-stage contract: every stage maps activations of one fixed
(shape, dtype) to the same (shape, dtype) — the hand-off buffer between
neighbors is a single static aval.  (Megatron-style transformer stacks
satisfy this by construction.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["pipeline_apply", "make_pipeline_mesh"]


def make_pipeline_mesh(n_stages, devices=None) -> Mesh:
    """A 1-D mesh whose single axis is the pipeline (``pp``)."""
    import numpy as np
    if devices is None:
        devices = jax.devices()
    if len(devices) < n_stages:
        raise MXNetError(f"pipeline of {n_stages} stages needs "
                         f"{n_stages} devices, have {len(devices)}")
    return Mesh(np.array(devices[:n_stages]), axis_names=("pp",))


def pipeline_apply(stage_fn, stage_params, micro_inputs, mesh: Mesh,
                   axis: str = "pp"):
    """Run ``micro_inputs`` through the stage pipeline.

    stage_fn(params, x) -> y with ``y.shape == x.shape`` and same dtype
    (uniform-stage contract).  ``stage_params``: pytree whose leaves have
    a leading stage dimension of size ``mesh.shape[axis]`` (sharded over
    ``axis``).  ``micro_inputs``: (n_micro, micro_batch, ...).  Returns
    (n_micro, micro_batch, ...) outputs of the LAST stage, replicated.
    """
    n_stages = mesh.shape[axis]
    n_micro = micro_inputs.shape[0]
    T = n_micro + n_stages - 1

    def _varying(x):
        # shard_map tracks varying-manual-axes: scan carries that
        # BECOME pp-varying must start pp-varying
        return lax.pcast(x, (axis,), to="varying")

    def per_device(params_stage, xs):
        # params_stage leaves: (1, ...) — this device's stage slice
        params_local = jax.tree_util.tree_map(lambda a: a[0],
                                              params_stage)
        p = lax.axis_index(axis)
        buf0 = _varying(jnp.zeros(xs.shape[1:], xs.dtype))
        outs0 = _varying(jnp.zeros_like(xs))

        def tick(state, t):
            buf, outs = state
            m = t - p                       # microbatch this stage sees
            active = (m >= 0) & (m < n_micro)
            x_in = jnp.where(p == 0,
                             xs[jnp.clip(t, 0, n_micro - 1)], buf)
            y = stage_fn(params_local, x_in)
            # zero inactive ticks so garbage never propagates
            y = jnp.where(active, y, jnp.zeros_like(y))
            if n_stages > 1:
                # mxlint: disable=collective-soundness (deliberately
                # non-total: the GPipe hand-off sends stage i -> i+1 and
                # must NOT wrap the last stage back to 0 — stage 0 reads
                # fresh microbatches from xs, and ppermute zero-fills
                # un-received buffers, which `active` masking discards)
                sent = lax.ppermute(
                    y, axis,
                    perm=[(i, i + 1) for i in range(n_stages - 1)])
            else:
                sent = y
            m_out = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            take = active & (p == n_stages - 1)
            outs = outs.at[m_out].set(
                jnp.where(take, y, outs[m_out]))
            return (buf if n_stages == 1 else sent, outs), None

        (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(T))
        # only the last stage holds real outputs: replicate via psum of
        # the masked buffer (identity when n_stages == 1)
        mask = (p == n_stages - 1).astype(outs.dtype)
        return lax.psum(outs * mask, axis)

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(pspec, P()), out_specs=P())
    return fn(stage_params, micro_inputs)
