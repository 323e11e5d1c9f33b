"""The one traffic generator.  A cell's traffic is its mix,
``mixes/<traffic>.json``: parameters only.  Every seed gets the same set
of sizes in another order, with other token ids, so that the work in a
window does not depend on the seed."""
import numpy as np

# pairs prompt sizes with output sizes, the same for every seed and mix
PAIRING_SEED = 0


def rng_for(seed, stream):
    """An independent numpy generator for (seed, stream name)."""
    return np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32,
         sum(map(ord, stream)), len(stream)])


def _spread(spec, n):
    """``n`` sizes that cover the distribution evenly, ends included."""
    q = np.linspace(0.0, 1.0, n)
    lo, hi = spec["lo"], spec["hi"]
    if spec["dist"] == "log_uniform":
        x = lo * (hi / lo) ** q
    elif spec["dist"] == "uniform":
        x = lo + (hi - lo) * q
    else:
        raise ValueError(f"traffic: unknown dist {spec['dist']!r}")
    return np.rint(x).astype(int)


def mlm_batches(traffic, vocab_size, seed):
    """``host_batches`` masked-LM batches on the host, all rows
    different: (tokens, token types, valid length, masked positions,
    masked-LM labels, next-sentence labels)."""
    B, L, M = traffic["batch"], traffic["seqlen"], traffic["masked"]
    rng = rng_for(seed, "mlm_batches")
    out = []
    for _ in range(traffic["host_batches"]):
        tokens = rng.integers(0, vocab_size, (B, L), dtype=np.int32)
        split = rng.integers(L // 4, 3 * L // 4, (B, 1))
        types = (np.arange(L)[None, :] >= split).astype(np.int32)
        valid = np.full((B,), L, np.float32)
        masked = np.sort(np.stack([rng.permutation(L)[:M]
                                   for _ in range(B)]), -1).astype(np.int32)
        mlm_y = rng.integers(0, vocab_size, (B, M), dtype=np.int32)
        nsp_y = rng.integers(0, 2, (B,), dtype=np.int32)
        out.append((tokens, types, valid, masked, mlm_y, nsp_y))
    return out


def closed_loop_requests(traffic, vocab_size, seed):
    """For each of ``clients`` clients, an endless stream of (prompt,
    new_tokens).  Every client walks the same ``per_client`` sizes (the
    prompt and output distributions covered evenly, paired by one fixed
    shuffle), each round in a new order drawn from the seed and
    with new token ids, so no prompt shares a prefix with another."""
    K = traffic["per_client"]
    prompts = _spread(traffic["prompt_len"], K)
    outs = _spread(traffic["new_tokens"], K)
    outs = outs[np.random.default_rng(PAIRING_SEED).permutation(K)]

    def stream(rng):
        while True:
            for j in rng.permutation(K):
                yield (rng.integers(1, vocab_size, (int(prompts[j]),),
                                    dtype=np.int32), int(outs[j]))

    return [stream(rng_for(seed, f"closed_loop.client{c}"))
            for c in range(traffic["clients"])]


def warmup_requests(traffic, vocab_size, seed):
    """One request at the longest prompt of every prefill bucket the
    mix can hit, so that each program compiles before the window."""
    rng = rng_for(seed, "warmup")
    lens = sorted({min(traffic["prompt_len"]["hi"], b)
                   for b in traffic["prefill_buckets"]})
    return [(rng.integers(1, vocab_size, (n,), dtype=np.int32),
             traffic["new_tokens"]["lo"]) for n in lens]
