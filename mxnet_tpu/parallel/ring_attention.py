"""Ring attention: context/sequence parallelism over the ICI ring.

New capability beyond reference parity (SURVEY.md §5.7: the reference's
attention is O(L^2) single-device).  Sequence is sharded over a mesh axis;
each device holds a Q block and rotates K/V blocks around the ring with
``lax.ppermute``, accumulating softmax online (flash-attention style), so
memory is O(L_local) and the KV transfers overlap compute on ICI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "ring_self_attention"]


def _ring_attention_local(q, k, v, q_pos, k_pos, axis_name, causal, scale,
                          window=None):
    """Per-device body under shard_map.

    q (B, H, Lq, D); k/v (B, H, Lk, D); *_pos (Lq,)/(Lk,) global token
    positions (positions travel with the rotating kv so causal masking
    stays correct on every hop).

    ``window``: causal sliding window — key positions in
    ``(q_pos - window, q_pos]`` attend.  Ring hops whose rotating KV
    block lies entirely outside every local query's band SKIP their
    attention compute via ``lax.cond`` (the rotation itself still runs:
    the ring schedule is fixed); with S shards and window W, each
    device pays for ~``ceil(W / L_loc) + 1`` hops of compute instead
    of S.
    """
    axis_size = lax.psum(1, axis_name)
    B, H, Lq, D = q.shape
    neg_inf = jnp.asarray(-1e30, dtype=jnp.float32)

    m0 = jnp.full((B, H, Lq), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((B, H, Lq), dtype=jnp.float32)
    acc0 = jnp.zeros((B, H, Lq, D), dtype=jnp.float32)
    # constants start axis-unvarying under shard_map's vma typing;
    # the loop carry becomes varying, so pre-cast the initial carry
    m0, l0, acc0 = (lax.pcast(x, (axis_name,), to="varying")
                    for x in (m0, l0, acc0))

    def attend(m, l, acc, k, v, k_pos):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = k_pos[None, :] > q_pos[:, None]        # (Lq, Lk)
            if window is not None:
                mask = mask | (k_pos[None, :] <= q_pos[:, None] - window)
            s = jnp.where(mask[None, None], neg_inf, s)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
        return m_new, l_new, acc_new

    def body(i, carry):
        m, l, acc, k, v, k_pos = carry
        if window is None:
            m, l, acc = attend(m, l, acc, k, v, k_pos)
        else:
            # band-overlap test for THIS hop's kv block: any (q, k)
            # with q - window < k_pos <= q_pos?
            needed = (jnp.min(k_pos) <= jnp.max(q_pos)) & \
                (jnp.max(k_pos) > jnp.min(q_pos) - window)
            m, l, acc = lax.cond(
                needed,
                lambda args: attend(*args, k, v, k_pos),
                lambda args: args,
                (m, l, acc))
        # rotate kv (and its positions) one hop around the ring
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        k_pos = lax.ppermute(k_pos, axis_name, perm)
        return m, l, acc, k, v, k_pos

    m, l, acc, _, _, _ = lax.fori_loop(
        0, axis_size, body, (m0, l0, acc0, k, v, k_pos))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis_name="sp", causal=False,
                   window=None):
    """Sharded attention over sequence: q/k/v (B, H, L, D) with L sharded
    on ``axis_name``.  Returns (B, H, L, D) with the same sharding.

    ``window``: causal sliding-window width (key positions in
    ``(q - window, q]``); requires ``causal=True``.  Out-of-band ring
    hops skip their attention compute, so cost scales with the window,
    not the full context."""
    if window is not None:
        from ..base import MXNetError
        if not causal:
            raise MXNetError("ring_attention: window= requires "
                             "causal=True (sliding-window attention is "
                             "causal)")
        if int(window) < 1:
            raise MXNetError("ring_attention: window must be >= 1")
    n = mesh.shape[axis_name]
    B, H, L, D = q.shape
    scale = 1.0 / (D ** 0.5)
    L_loc = L // n

    qkv_spec = P(None, None, axis_name, None)
    pos = jnp.arange(L, dtype=jnp.int32)

    def local_fn(q, k, v, q_pos, k_pos):
        return _ring_attention_local(q, k, v, q_pos, k_pos, axis_name,
                                     causal, scale, window=window)

    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, P(axis_name), P(axis_name)),
        out_specs=qkv_spec)
    return fn(q, k, v, pos, pos)


def ring_self_attention(x, w_qkv, w_out, num_heads, mesh, axis_name="sp",
                        causal=True, window=None):
    """x (B, L, C) sequence-sharded -> same; projections computed locally
    (they're pointwise over sequence)."""
    B, L, C = x.shape
    D = C // num_heads
    qkv = jnp.einsum("blc,oc->blo", x, w_qkv)      # (B, L, 3C)
    qkv = qkv.reshape(B, L, 3, num_heads, D)
    q = qkv[:, :, 0].transpose(0, 2, 1, 3)
    k = qkv[:, :, 1].transpose(0, 2, 1, 3)
    v = qkv[:, :, 2].transpose(0, 2, 1, 3)
    out = ring_attention(q, k, v, mesh, axis_name, causal, window=window)
    out = out.transpose(0, 2, 1, 3).reshape(B, L, C)
    return jnp.einsum("blc,oc->blo", out, w_out)
