"""What each kernel has to do at the least, from its shapes: the
operations and the bytes the algorithm needs for one call, and the
least time a chip with the given peaks could take for them (the larger
of operations over peak FLOP/s and bytes over peak bytes/s).
Recomputed operations do not count."""


def least_seconds(ops, nbytes, peak):
    by_compute = ops / peak["flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_compute, by_bytes), ("compute" if by_compute >= by_bytes
                                       else "bytes")


def flash_attention_fwd(bh, lq, lk, d, itemsize):
    """softmax(Q K^T) V over (bh, l, d) tensors."""
    ops = 2 * bh * lq * lk * d * 2
    nbytes = itemsize * bh * d * (2 * lq + 2 * lk)        # q, o; k, v
    return ops, nbytes


def flash_attention_bwd(bh, lq, lk, d, itemsize):
    """dQ, dK, dV from dO: four products (dV = P^T dO, dP = dO V^T,
    dQ = dS K, dK = dS^T Q); the scores' recomputation is not counted."""
    ops = 2 * bh * lq * lk * d * 4
    nbytes = itemsize * bh * d * (4 * lq + 4 * lk)   # q o do dq; k v dk dv
    return ops, nbytes


def flash_attention_step(p):
    """Forward and backward of every layer of one training step
    (``p``: batch, heads, seqlen, head_dim, layers, itemsize)."""
    bh, l, d = p["batch"] * p["heads"], p["seqlen"], p["head_dim"]
    fo, fb = flash_attention_fwd(bh, l, l, d, p["itemsize"])
    bo, bb = flash_attention_bwd(bh, l, l, d, p["itemsize"])
    return p["layers"] * (fo + bo), p["layers"] * (fb + bb)


def paged_attention_decode(context_lens, heads, head_dim, itemsize,
                           q_itemsize=4):
    """One decode call over a ragged batch: each live slot reads its
    context's K and V once at the pool's item size, plus its query row
    and its output row."""
    ctx = sum(context_lens)
    n = len(context_lens)
    ops = 2 * ctx * heads * head_dim * 2
    nbytes = (2 * ctx * heads * head_dim * itemsize
              + 2 * n * heads * head_dim * q_itemsize)
    return ops, nbytes
