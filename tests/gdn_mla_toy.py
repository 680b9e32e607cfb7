"""The hybrid family (``models/gdn_mla``) at toy widths for the tests: the
published keys, the recipe's ``Model:`` section, the reference's names
mapped onto the program's tree, seeded weights."""

from __future__ import annotations

import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the published keys at toy widths (a reference ``sizes``): one leading
#: dense layer, then a period of a latent layer and three linear ones
PUBLISHED = {
    "vocab_size": 96, "max_position_embeddings": 4096, "hidden_size": 32,
    "intermediate_size": 48, "moe_intermediate_size": 24,
    "num_hidden_layers": 5, "num_attention_heads": 8, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 16, "q_lora_rank": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16,
    "n_group": 1, "topk_group": 1, "num_experts_per_tok": 3,
    "first_k_dense_replace": 1, "norm_topk_prob": True,
    "rope_interleave": True, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post",
    "layernorm_gating_weight": 2, "gated_attention": True,
    "use_shared_expert_sigmoid": False, "use_mla_scaling_factor": True,
    "linear_attention_type": "GigaChat35GatedDeltaNet",
    "full_attention_layers": [1], "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-6,
    # small enough to act on N(0, 0.02) weights: the clamp is exercised
    "swiglu_limit": 0.02, "num_nextn_predict_layers": 0,
    # the share: the router is 16 wide, 8 experts from the 4th are held
    "n_routed_experts": 8, "router_experts": 16, "first_expert_held": 4,
}


def model_section(**over) -> dict:
    """The recipe's ``Model:`` section at toy widths (float32)."""
    model = {k: v for k, v in PUBLISHED.items() if k != "router_experts"}
    model.update(module="GDNMLAModule", n_routed_experts=16, experts_held=8,
                 dtype="float32", param_dtype="float32")
    model.update(over)
    return model


_LEAVES = {
    "norm_a_pre": "attn_norm/w", "norm_a_post": "attn_post_norm/w",
    "norm_f_pre": "mlp_norm/w", "norm_f_post": "mlp_post_norm/w",
    "o": "mixer/out", "mlp_gate": "mlp/gate", "mlp_up": "mlp/up",
    "mlp_down": "mlp/down", "router": "moe/router",
    "bias": "moe/selection_bias", "e_gate": "moe/experts_gate",
    "e_up": "moe/experts_up", "e_down": "moe/experts_down",
    "s_gate": "moe/shared_gate", "s_up": "moe/shared_up",
    "s_down": "moe/shared_down"}
_KINDS = {"ld": "linear_dense", "lm": "linear_moe", "ad": "latent_dense",
          "am": "latent_moe"}


def param_paths(spec: dict) -> dict:
    """Reference weight name -> path in the program's tree."""
    paths = {"emb": "embed/tokens", "head": "head/kernel",
             "norm_f": "final_norm/w"}
    for name in spec:
        if name in paths:
            continue
        prefix, leaf = name.split("_", 1)
        paths[name] = _KINDS[prefix] + "/" + _LEAVES.get(leaf,
                                                         "mixer/" + leaf)
    return paths


def zero_expert_counters() -> None:
    """The metrics registry is the process's, and the benchmark's
    ``moe_serve_passes_per_layer`` divides ALL its passes by ALL its steps
    and by ONE configuration's expert layers (4 in this family's toys, 8 in
    the other families'): a test that ran this family's engine leaves zero
    behind, for whichever rehearsal the worker runs next — and no
    preemptions either (the cell's rehearsal reads that counter whole)."""
    from fleetx_tpu.observability.metrics import get_registry

    reg = get_registry()
    reg.counter("serving_moe_passes_total").reset()
    reg.counter("serving_requests_preempted").reset()
    hist = reg.histogram("serving_moe_load_max_over_mean")
    hist.reset()
    hist.total_count, hist.total_sum = 0, 0.0
