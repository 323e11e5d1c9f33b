"""Model FLOPs, counted from shapes: every matrix multiplication the
forward pass needs (2 FLOPs a multiply-add), attention scores
included; no embedding lookup, no element-wise work, no recomputation.
A training step is three forwards' worth (the backward pass multiplies
each matmul's operands twice more)."""


def _block_flops(tokens, keys, units, hidden):
    """One transformer block over ``tokens`` positions that attend to
    ``keys`` positions in total (sum over the tokens)."""
    proj = 2 * tokens * units * (3 * units + units)       # qkv + out
    ffn = 2 * tokens * units * hidden * 2
    attn = 2 * keys * units * 2                           # QK^T and PV
    return proj + ffn + attn


def bert_pretrain_forward_flops(dims, batch, seqlen, n_masked):
    C, Hd, V = dims["units"], dims["hidden_size"], dims["vocab_size"]
    tokens = batch * seqlen
    blocks = dims["num_layers"] * _block_flops(
        tokens, tokens * seqlen, C, Hd)
    pooler = 2 * batch * C * C
    nsp = 2 * batch * C * 2
    mlm = 2 * batch * n_masked * C * (C + V)              # dense + decoder
    return blocks + pooler + nsp + mlm


def bert_pretrain_step_flops(dims, batch, seqlen, n_masked):
    return 3 * bert_pretrain_forward_flops(dims, batch, seqlen, n_masked)


def decoder_prefill_flops(dims, prompt_len):
    """A causal prefill of ``prompt_len`` tokens: position i attends to
    i + 1 keys; the vocabulary projection runs for the last position
    only."""
    C, Hd, V = dims["units"], dims["hidden_size"], dims["vocab_size"]
    keys = prompt_len * (prompt_len + 1) // 2
    return (dims["num_layers"] * _block_flops(prompt_len, keys, C, Hd)
            + 2 * C * V)


def decoder_decode_flops(dims, context_len):
    """One decode position that attends to ``context_len`` keys (itself
    included), with its vocabulary projection."""
    C, Hd, V = dims["units"], dims["hidden_size"], dims["vocab_size"]
    return (dims["num_layers"] * _block_flops(1, context_len, C, Hd)
            + 2 * C * V)
