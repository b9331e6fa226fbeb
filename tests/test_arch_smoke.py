"""Per-assigned-architecture smoke tests (deliverable f).

Each instantiates the REDUCED same-family variant (2 layers, d_model ≤ 512,
≤ 4 experts) and runs one forward + one PD-SGDM train step on CPU, asserting
output shapes and the absence of NaNs.  The FULL configs are exercised by
the multi-pod dry-run (ShapeDtypeStruct only).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ASSIGNED, get_config, get_smoke_config
from repro.configs.shapes import train_batch_arrays
from repro.core import PDSGDM, PDSGDMConfig
from repro.core.gossip import DenseComm
from repro.core.topology import ring
from repro.models import make_model


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_forward_and_train_step(arch):
    run = get_smoke_config(arch)
    mcfg = run.model
    assert mcfg.n_layers <= max(2, len(mcfg.pattern))
    assert mcfg.d_model <= 512
    assert mcfg.n_experts <= 4

    model = make_model(mcfg)
    K, b, s = 2, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(0), K)
    params = jax.vmap(lambda k: model.init(jax.random.PRNGKey(0)))(keys)
    batch = train_batch_arrays(mcfg, K, b, s, jax.random.PRNGKey(1))

    # forward: logits shape + finite
    logits, aux = model.apply(
        params and jax.tree_util.tree_map(lambda x: x[0], params),
        {k: v[0] for k, v in batch.items() if k != "labels"})
    assert logits.shape[0] == b and logits.shape[-1] == mcfg.vocab
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"

    # one decentralized train step across K=2 workers
    opt = PDSGDM(PDSGDMConfig(eta=0.05, mu=0.9, p=1), DenseComm(ring(K)))
    state = opt.init(params)
    lossf = jax.vmap(jax.value_and_grad(
        lambda p, bb: model.loss(p, bb)[0]))
    losses, grads = lossf(params, batch)
    new_params, state = opt.step(state, params, grads)
    assert bool(jnp.isfinite(losses).all()), f"{arch}: NaN loss"
    for leaf in jax.tree_util.tree_leaves(new_params):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all()), arch
    # params actually moved
    moved = any(
        float(jnp.abs(a.astype(jnp.float32)
                      - b_.astype(jnp.float32)).max()) > 0
        for a, b_ in zip(jax.tree_util.tree_leaves(new_params),
                         jax.tree_util.tree_leaves(params)))
    assert moved, f"{arch}: train step was a no-op"


@pytest.mark.parametrize("arch", ASSIGNED)
def test_full_config_matches_assignment(arch):
    """The full (dry-run) configs carry the exact assigned dimensions."""
    spec = {
        "arctic-480b": dict(n_layers=35, d_model=7168, n_heads=56,
                            n_kv_heads=8, d_ff=4864, vocab=32000,
                            n_experts=128),
        "mixtral-8x7b": dict(n_layers=32, d_model=4096, n_heads=32,
                             n_kv_heads=8, d_ff=14336, vocab=32000,
                             n_experts=8),
        "stablelm-12b": dict(n_layers=40, d_model=5120, n_heads=32,
                             n_kv_heads=8, d_ff=13824, vocab=100352),
        "olmo-1b": dict(n_layers=16, d_model=2048, n_heads=16,
                        n_kv_heads=16, d_ff=8192, vocab=50304,
                        norm="nonparametric"),
        "qwen2-72b": dict(n_layers=80, d_model=8192, n_heads=64,
                          n_kv_heads=8, d_ff=29568, vocab=152064,
                          qkv_bias=True),
        "musicgen-medium": dict(n_layers=48, d_model=1536, n_heads=24,
                                n_kv_heads=24, d_ff=6144, vocab=2048,
                                input_mode="embeds"),
        "minicpm3-4b": dict(n_layers=62, d_model=2560, n_heads=40,
                            n_kv_heads=40, d_ff=6400, vocab=73448,
                            use_mla=True),
        "internvl2-76b": dict(n_layers=80, d_model=8192, n_heads=64,
                              n_kv_heads=8, d_ff=28672, vocab=128256,
                              input_mode="vlm"),
        "jamba-1.5-large-398b": dict(n_layers=72, d_model=8192, n_heads=64,
                                     n_kv_heads=8, d_ff=24576, vocab=65536,
                                     n_experts=16),
        "mamba2-1.3b": dict(n_layers=48, d_model=2048, d_ff=0,
                            vocab=50280, ssm_state=128),
        "granite-4.0-h-micro": dict(n_layers=40, d_model=2048, n_heads=32,
                                    n_kv_heads=8, head_dim=64, d_ff=8192,
                                    vocab=100352, ssm_state=128,
                                    ssm_headdim=64, ssm_chunk=256,
                                    n_experts=0, tie_embeddings=True,
                                    attn_positions="none",
                                    attn_scale=1 / 64, embed_scale=12.0,
                                    residual_scale=0.22, logits_scale=8.0,
                                    norm_eps=1e-5),
    }[arch]
    m = get_config(arch).model
    for k, v in spec.items():
        assert getattr(m, k) == v, (arch, k, getattr(m, k), v)
    assert m.source, arch


def test_jamba_interleave_ratio():
    m = get_config("jamba-1.5-large-398b").model
    mixers = [s.mixer for s in m.pattern]
    assert mixers.count("attn") == 1 and mixers.count("mamba") == 7
    ffns = [s.ffn for s in m.pattern]
    assert ffns.count("moe") == 4  # every other layer


def test_granite_interleave_ratio():
    m = get_config("granite-4.0-h-micro").model
    mixers = [s.mixer for s in m.pattern] * m.n_repeats
    assert [i for i, k in enumerate(mixers) if k == "attn"] == [5, 15, 25, 35]
    assert mixers.count("mamba") == 36
    assert {s.ffn for s in m.pattern} == {"dense"}


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b",
                                  "granite-4.0-h-micro"])
def test_smoke_config_keeps_every_layer_kind_of_a_hybrid(arch):
    full, small = get_config(arch).model, get_smoke_config(arch).model
    assert {s.mixer for s in small.pattern} == {s.mixer for s in full.pattern}
    assert {s.ffn for s in small.pattern} == {s.ffn for s in full.pattern}
    assert small.n_experts == min(full.n_experts, 4)
