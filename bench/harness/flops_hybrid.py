"""Operations that a Mamba-2 / attention hybrid's work needs, from the
configuration's shapes (the `lm_hybrid` family).

As in ``flops.py``: training counts three times the forward pass, and never
the forward pass that full remat runs a second time.  Per token, the
forward pass is the output head, then per layer of the pattern its mixer
and its dense SwiGLU MLP:

* attention: the q/k/v/o projections and the core at an average causal
  context of seq/2;
* Mamba-2: the in and out projections, the depthwise conv, and the SSD
  core by chunk of Q: within the chunk (2N + 2P)·H·Q/2 (the C·B scores and
  their product with x, at an average causal context of Q/2), the chunk's
  state 2·N·P·H, and the inter-chunk output 2·N·P·H.
"""
from __future__ import annotations


def attention_flops_per_token(model: dict, seq_len: int) -> float:
    d, h, kv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // h
    return (2 * d * hd * (h + 2 * kv) + 2 * h * hd * d
            + 4 * h * hd * seq_len / 2.0)


def mamba_flops_per_token(model: dict, seq_len: int) -> float:
    d, n, p = model["d_model"], model["ssm_state"], model["ssm_headdim"]
    d_inner = model["ssm_expand"] * d
    heads = d_inner // p
    conv_dim = d_inner + 2 * n
    chunk = min(model["ssm_chunk"], seq_len)
    proj = 2 * d * (d_inner + conv_dim + heads) + 2 * d_inner * d
    conv = 2 * 4 * conv_dim
    ssd = (2 * n + 2 * p) * heads * chunk / 2.0 + 2 * 2 * n * p * heads
    return proj + conv + ssd


def forward_flops_per_token(model: dict, seq_len: int) -> float:
    d = model["d_model"]
    mixer = {"attn": attention_flops_per_token,
             "mamba": mamba_flops_per_token}
    per_period = 0.0
    for kind, ffn in model["pattern"]:
        if ffn != "dense":
            raise ValueError(f"no FLOP count for ffn {ffn!r}")
        per_period += mixer[kind](model, seq_len) + 2 * d * model["d_ff"] * 3
    repeats = model["n_layers"] // len(model["pattern"])
    return 2.0 * d * model["vocab"] + repeats * per_period


def train_flops_per_item(config: dict, data: dict) -> float:
    """Forward and backward FLOPs per token: 3 × forward."""
    if config["reference"] != "lm_hybrid":
        raise ValueError(f"no FLOP count for {config['reference']!r}")
    return 3.0 * forward_flops_per_token(config["model"], data["seq_len"])
