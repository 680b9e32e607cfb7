"""Operations the forward and backward passes of a dense GPT require per
trained token (recomputed operations do not count; a causal mask needs
half of the attention products)."""

TRACE_NAMES = ()


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """6 x the matmul parameters + the causal attention products."""
    L, h = sizes["num_layers"], sizes["hidden_size"]
    f, v = sizes["ffn_hidden_size"], sizes["vocab_size"]
    matmul_params = L * (4 * h * h + 2 * h * f) + v * h
    attention = L * 2 * 2 * (seq / 2) * h        # QK^T and PV, causal
    return 3 * (2 * matmul_params + attention)     # backward = 2 x forward
