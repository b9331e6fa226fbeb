"""granite-4.0-h-micro — IBM Granite 4.0-H Micro
[huggingface.co/ibm-granite/granite-4.0-h-micro, config.json].

40L, d_model 2048, a Mamba-2 / GQA hybrid in a fixed 9 : 1 ratio: the
attention layers sit at indices 5, 15, 25 and 35, so the pattern is a
period of 10 (mamba × 5, attn, mamba × 4).  Attention: 32 heads, 8 KV
heads, head_dim 64, no positional embedding (NoPE), softmax scale
``attention_multiplier`` 1/64.  Mamba-2: d_state 128, 64 heads of 64,
one group, expand 2, conv 4 with bias, no projection bias, chunk 256.
Every layer has a dense SwiGLU MLP of 8192 (``shared_intermediate_size``,
no experts).  Vocab 100352, tied head, RMSNorm eps 1e-5, and μP-style
multipliers: embedding × 12, residual branches × 0.22, logits ÷ 8.
"""
from repro.configs.base import LayerSpec, ModelCfg, OptimCfg, ParallelCfg, RunCfg

# one period of the published ``layer_types``: layers 0-9
_PATTERN = tuple(LayerSpec("attn" if i == 5 else "mamba", "dense")
                 for i in range(10))


def config() -> RunCfg:
    model = ModelCfg(
        name="granite-4.0-h-micro", arch_type="hybrid",
        n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        d_ff=8192, vocab=100352, norm="rmsnorm", norm_eps=1e-5,
        gated_mlp=True, tie_embeddings=True,
        attn_positions="none", attn_scale=1 / 64,
        embed_scale=12.0, residual_scale=0.22, logits_scale=8.0,
        pattern=_PATTERN,
        ssm_state=128, ssm_headdim=64, ssm_expand=2, ssm_chunk=256,
        param_dtype="bfloat16", compute_dtype="bfloat16",
        source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/"
               "blob/main/config.json",
    )
    return RunCfg(model=model, parallel=ParallelCfg(profile="A"),
                  optim=OptimCfg())
