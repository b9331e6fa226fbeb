"""Operations that the work needs, from the configuration's shapes.

The model FLOPs count what the forward and backward passes require: three
times the forward pass, and never the forward pass that full remat runs a
second time.
"""
from __future__ import annotations


def lm_forward_flops_per_token(model: dict, seq_len: int) -> float:
    """Dense decoder, per token: the output head, then per layer the q/k/v/o
    projections, the attention core at an average causal context of
    seq/2, and the MLP (the formulas of the program's
    ``launch/analytic.py``)."""
    d, h, kv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // h
    mats = 3 if model.get("gated_mlp", True) else 2
    s_eff = seq_len / 2.0
    attn = 2 * d * hd * (h + 2 * kv) + 2 * h * hd * d + 4 * h * hd * s_eff
    ffn = 2 * d * model["d_ff"] * mats
    return 2.0 * d * model["vocab"] + model["n_layers"] * (attn + ffn)


def train_flops_per_item(config: dict, data: dict) -> float:
    """Forward and backward FLOPs per token: 3 × forward."""
    if config["reference"] != "lm_transformer":
        raise ValueError(f"no FLOP count for {config['reference']!r}")
    return 3.0 * lm_forward_flops_per_token(config["model"], data["seq_len"])
