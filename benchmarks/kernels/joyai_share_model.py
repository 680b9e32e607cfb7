"""Operations the forward and backward passes of the latent-attention
sparse-expert decoder require per trained token, for the share of the
model that ``sizes`` holds (recomputed operations do not count; a causal
mask needs half of the attention products; each held expert at its
expected load of ``experts per token x held / routed`` rows a token)."""

TRACE_NAMES = ()


def forward_macs_per_token(sizes: dict, seq: int) -> dict:
    """Multiply-adds a token, forward, by part."""
    h, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    nv = sizes["qk_nope_head_dim"] + sizes["v_head_dim"]
    projections = (h * sizes["q_lora_rank"] + sizes["q_lora_rank"] * heads * qk
                   + h * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
                   + sizes["kv_lora_rank"] * heads * nv
                   + heads * sizes["v_head_dim"] * h)
    products = heads * (seq / 2) * (qk + sizes["v_head_dim"])
    expert = 3 * h * sizes["moe_intermediate_size"]
    held_per_token = sizes["num_experts_per_tok"] * sizes["n_routed_experts"] \
        / sizes["router_experts"]
    return {"attention_projections": projections,
            "attention_products": products,
            "dense_mlp": 3 * h * sizes["intermediate_size"],
            "router": h * sizes["router_experts"],
            "shared_expert": sizes["n_shared_experts"] * expert,
            "held_experts": held_per_token * expert,
            "head": sizes["vocab_size"] * h,
            "mtp_join": 2 * h * h}


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """3 x 2 x the forward multiply-adds (backward = 2 x forward)."""
    m = forward_macs_per_token(sizes, seq)
    attention = m["attention_projections"] + m["attention_products"]
    expert_layer = attention + m["router"] + m["shared_expert"] \
        + m["held_experts"]
    n_dense = sizes["first_k_dense_replace"]
    n_expert = sizes["num_hidden_layers"] - n_dense
    macs = (n_dense * (attention + m["dense_mlp"]) + n_expert * expert_layer
            + m["head"] + sizes.get("num_nextn_predict_layers", 0)
            * (m["mtp_join"] + expert_layer + m["head"]))
    return 3 * 2 * macs
