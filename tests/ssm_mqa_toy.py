"""The scan / multi-query family (``models/ssm_mqa``) at toy widths for the
tests: the published keys, the recipe's ``Model:`` section, the reference's
names mapped onto the program's tree."""

from __future__ import annotations

import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the published keys at toy widths (a reference ``sizes``): 8 layers at
#: period 4, offset 1 — layers 1 and 5 attend, the other six scan in runs of
#: 1, 3 and 2 (a single layer and two loops, as the real map's 7, 13, 6
#: are) — 4 query heads over ONE key-value head of 16
PUBLISHED = {
    "vocab_size": 96, "max_position_embeddings": 4096, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "attn_layer_period": 4, "attn_layer_offset": 1,
    "expert_layer_period": 2, "expert_layer_offset": 1, "num_experts": 1,
    "num_experts_per_tok": 1, "mamba_d_state": 8, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 4, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "tie_word_embeddings": True,
}
#: a geometry every kernel admits (interpret mode): 2 query heads over one
#: key-value head of 128 — one lane tile — and 512 scan channels
KERNEL_WIDTHS = {"hidden_size": 256, "num_attention_heads": 2}


def sizes(**over) -> dict:
    """A reference ``sizes`` at toy widths."""
    out = dict(PUBLISHED)
    out.update(over)
    return out


def model_section(**over) -> dict:
    """The recipe's ``Model:`` section at toy widths (float32)."""
    model = dict(PUBLISHED, module="SSMMQAModule", dtype="float32",
                 param_dtype="float32")
    model.update(over)
    return model


_LEAVES = {
    "norm1_w": "norm1/scale", "norm2_w": "norm2/scale",
    "mlp_gate": "mlp/gate", "mlp_up": "mlp/up", "mlp_down": "mlp/down",
    "in": "ssm/in", "taps": "ssm/taps", "conv_b": "ssm/conv_bias",
    "x": "ssm/x", "dt_norm_w": "ssm/dt_norm", "b_norm_w": "ssm/b_norm",
    "c_norm_w": "ssm/c_norm", "dt": "ssm/dt", "dt_b": "ssm/dt_bias",
    "A_log": "ssm/A_log", "D": "ssm/D", "out": "ssm/out",
    "qkv": "attn/qkv", "o": "attn/out"}
_KINDS = {"sc": "scan", "at": "full"}


def param_paths(spec: dict) -> dict:
    """Reference weight name -> path in the program's tree."""
    paths = {"emb": "embed/tokens", "norm_f_w": "final_norm/scale"}
    for name in spec:
        if name not in paths:
            prefix, leaf = name.split("_", 1)
            paths[name] = _KINDS[prefix] + "/" + _LEAVES[leaf]
    return paths
