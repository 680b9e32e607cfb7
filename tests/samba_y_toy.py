"""The decoder-hybrid-decoder family (``models/samba_y``) at toy widths for
the tests: the published keys, the recipe's ``Model:`` section, the
reference's names mapped onto the program's tree."""

from __future__ import annotations

import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the published keys at toy widths (a reference ``sizes``): 8 layers, so
#: the map is whole with ``N/2 = 4`` — scan, window, scan, window, scan
#: (hands ``m`` on), full, memory unit, cross; 8 query heads over 4
#: key-value heads of 8 (two key-value pairs, two query pairs to each); a
#: window of 8 tokens
PUBLISHED = {
    "vocab_size": 96, "max_position_embeddings": 4096, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "sliding_window": 8, "mb_per_layer": 2, "layer_norm_eps": 1e-5,
    "hidden_act": "silu", "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False,
}
#: the ASSUMED sizes at toy widths (``sizes["assumed"]`` of the reference,
#: keys of the recipe's ``Model:``)
ASSUMED = {"d_state": 8, "d_conv": 4, "expand": 2, "dt_rank": 4}
#: a geometry every kernel admits (interpret mode): 8 query heads over 4
#: key-value heads of 32 — pairs of 64 lanes, half a lane tile — and 256
#: scan channels
KERNEL_WIDTHS = {"hidden_size": 256, "intermediate_size": 128}


def sizes(**over) -> dict:
    """A reference ``sizes`` at toy widths."""
    out = dict(PUBLISHED, assumed=dict(ASSUMED))
    out.update(over)
    return out


def model_section(**over) -> dict:
    """The recipe's ``Model:`` section at toy widths (float32)."""
    model = dict(PUBLISHED, **ASSUMED, module="SambaYModule",
                 dtype="float32", param_dtype="float32")
    model.update(over)
    return model


_LEAVES = {
    "norm1_w": "norm1/scale", "norm1_b": "norm1/bias",
    "norm2_w": "norm2/scale", "norm2_b": "norm2/bias",
    "mlp_gate_up": "mlp/gate_up", "mlp_down": "mlp/down",
    "taps": "ssm/taps", "conv_b": "ssm/conv_bias", "x": "ssm/x",
    "dt": "ssm/dt", "dt_b": "ssm/dt_bias", "A_log": "ssm/A_log",
    "D": "ssm/D",
    "qkv": "attn/qkv", "qkv_b": "attn/qkv_bias", "o": "attn/out",
    "o_b": "attn/out_bias", "lq1": "attn/lambda_q1", "lk1": "attn/lambda_k1",
    "lq2": "attn/lambda_q2", "lk2": "attn/lambda_k2", "subln": "attn/subln"}
_KINDS = {"sc": "scan", "wn": "window", "fl": "full", "gm": "gmu",
          "cr": "cross"}
#: ``in`` / ``out`` name a scan layer's products and a memory unit's
_GROUP = {"sc": "ssm", "gm": "gmu"}


def param_paths(spec: dict) -> dict:
    """Reference weight name -> path in the program's tree."""
    paths = {"emb": "embed/tokens", "norm_f_w": "final_norm/scale",
             "norm_f_b": "final_norm/bias"}
    for name in spec:
        if name not in paths:
            prefix, leaf = name.split("_", 1)
            where = _LEAVES.get(leaf) or f"{_GROUP[prefix]}/{leaf}"
            paths[name] = _KINDS[prefix] + "/" + where
    return paths
