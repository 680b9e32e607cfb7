"""The short-convolution family (``models/conv_moe``) at toy widths for the
tests: the published keys, the recipe's ``Model:`` section, the reference's
names mapped onto the program's tree."""

from __future__ import annotations

import os

# the metrics registry is the process's: a test that ran this family's
# engine leaves zero behind (``tests/gdn_mla_toy.py`` has the reasons)
from gdn_mla_toy import zero_expert_counters  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the published keys at toy widths (a reference ``sizes``): the cut's own
#: pattern — a leading convolution layer with the dense MLP, then two
#: periods of an attention layer and three convolution layers, each with
#: experts; 4 query heads to each of 2 key-value heads of 64 (half a lane
#: tile, as published), 8 experts of which a token takes 2, 3 taps
PUBLISHED = {
    "vocab_size": 96, "max_position_embeddings": 4096, "hidden_size": 512,
    "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_hidden_layers": 9,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv"],
    "num_dense_layers": 1, "num_attention_heads": 8,
    "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-5, "norm_topk_prob": True, "num_experts": 8,
    "num_experts_per_tok": 2,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
}


def model_section(**over) -> dict:
    """The recipe's ``Model:`` section at toy widths (float32)."""
    model = dict(PUBLISHED, module="ConvMoEModule", dtype="float32",
                 param_dtype="float32")
    model.update(over)
    return model


_LEAVES = {
    "norm_op": "operator_norm/scale", "norm_ffn": "ffn_norm/scale",
    "in": "conv/in", "taps": "conv/taps", "out": "conv/out",
    "q": "attn/q", "k": "attn/k", "v": "attn/v", "o": "attn/out",
    "q_norm": "attn/q_norm", "k_norm": "attn/k_norm",
    "mlp_gate": "mlp/gate", "mlp_up": "mlp/up", "mlp_down": "mlp/down",
    "router": "moe/router", "bias": "moe/expert_bias",
    "e_gate": "moe/experts_gate", "e_up": "moe/experts_up",
    "e_down": "moe/experts_down"}
_KINDS = {"cd": "conv_dense", "cm": "conv_moe", "fd": "full_dense",
          "fm": "full_moe"}


def param_paths(spec: dict) -> dict:
    """Reference weight name -> path in the program's tree."""
    paths = {"emb": "embed/tokens", "norm_f": "final_norm/scale"}
    for name in spec:
        if name not in paths:
            prefix, leaf = name.split("_", 1)
            paths[name] = _KINDS[prefix] + "/" + _LEAVES[leaf]
    return paths
