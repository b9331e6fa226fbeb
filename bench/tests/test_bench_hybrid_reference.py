"""The program's Mamba-2 / attention hybrid against the plain reference
(``references/lm_hybrid.py``) on the CPU, on seeded random weights at a
small size: one 10-layer period of the Granite pattern, d_model 64, 4
heads and 1 KV head of 16, d_state 16, Mamba heads of 16, chunk 8, seq 32,
vocab 256, float32 weights and compute.

Tolerances, and why: both sides compute in float32 on the CPU, where a
matrix product is exact float32, so what is left is the order of the
arithmetic: the chunked SSD (a cumulative sum of log-decays, then
differences of it under ``exp``) against the reference's step-by-step
recurrence, and the fused against the blocked attention and head.  Ten
layers of that read 9e-8 on the loss and 4e-6 on the worst leaf's gradient
(relative), so the limits are 1e-6 and 1e-4: ten and twenty-five times
room.  A change of the model moves them by 1e-3 and more (a multiplier
removed reads 2.5e-3 on the loss and 13 on the gradient; RoPE put back
0.72 on the gradient)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import sharded
from references import lm_hybrid
from repro.configs.base import ModelCfg
from repro.models.mamba2 import Mamba2Cfg, mamba2_apply
from repro.models.transformer import make_model

PATTERN = [["mamba", "dense"]] * 5 + [["attn", "dense"]] + \
    [["mamba", "dense"]] * 4
MODEL = dict(n_layers=10, d_model=64, n_heads=4, n_kv_heads=1, head_dim=16,
             d_ff=128, vocab=256, norm="rmsnorm", norm_eps=1e-5,
             gated_mlp=True, tie_embeddings=True, qkv_bias=False,
             window=None, attn_positions="none", attn_scale=1 / 64,
             embed_scale=12.0, residual_scale=0.22, logits_scale=8.0,
             pattern=PATTERN, ssm_state=16, ssm_headdim=16, ssm_expand=2,
             ssm_chunk=8, param_dtype="float32", compute_dtype="float32")
SEQ = 32
LOSS_TOL, GRAD_TOL = 1e-6, 1e-4


def _weights(model: dict):
    struct = jax.eval_shape(make_model(ModelCfg(
        name="small", arch_type="hybrid", **model)).init,
        jax.random.PRNGKey(0))
    return sharded.param_maker(lm_hybrid, struct, stacked=False)(
        jax.random.PRNGKey(3))


def _batch():
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, SEQ + 1), 0,
                              MODEL["vocab"])
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _gaps(program_model: dict):
    """(loss gap, worst leaf's gradient gap), both relative, of the
    program built from ``program_model`` against the reference of
    ``MODEL``, on the same weights."""
    params, batch = _weights(MODEL), _batch()
    model = make_model(ModelCfg(name="small", arch_type="hybrid",
                                **program_model))
    loss, grads = jax.value_and_grad(
        lambda p: model.loss(p, batch, remat="full")[0])(params)
    # with μ = 0, η = 0 and no weight decay the momentum after one step is
    # the reference's gradient
    step = lm_hybrid.make_step(MODEL, {"eta": 0.0, "mu": 0.0,
                                       "weight_decay": 0.0})
    zeros = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        _, ref_grads, ref_loss = step(params, zeros, batch["tokens"],
                                      batch["labels"])
    loss_gap = abs(float(loss) - float(ref_loss)) / abs(float(ref_loss))
    grad_gap = max(
        float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        for a, b in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(ref_grads)))
    return loss_gap, grad_gap


def test_the_program_loss_and_gradient_match_the_reference():
    loss_gap, grad_gap = _gaps(MODEL)
    assert loss_gap < LOSS_TOL and grad_gap < GRAD_TOL, (loss_gap, grad_gap)


@pytest.mark.parametrize("change", [{"residual_scale": 1.0},
                                    {"attn_positions": "rope"}],
                         ids=["residual_multiplier_removed", "rope_put_back"])
def test_a_changed_model_fails_the_comparison(change):
    loss_gap, grad_gap = _gaps({**MODEL, **change})
    assert loss_gap > LOSS_TOL or grad_gap > GRAD_TOL, (loss_gap, grad_gap)


@pytest.mark.parametrize("chunk", [4, 16])
def test_the_chunked_ssd_matches_the_recurrence(chunk):
    """``mamba2_apply`` (chunks of ``chunk``) against the reference's
    Mamba-2 mixer, which steps the recurrence token by token; one layer,
    f32, so the limit is 1e-5 relative (it reads under 1e-6)."""
    params = _weights(MODEL)["blocks"]["pos0"]["mamba"]
    mp = jax.tree_util.tree_map(lambda a: a[0].astype(jnp.float32), params)
    u = jax.random.normal(jax.random.PRNGKey(11), (2, SEQ, MODEL["d_model"]))
    cfg = Mamba2Cfg(d_model=MODEL["d_model"], d_state=MODEL["ssm_state"],
                    headdim=MODEL["ssm_headdim"], expand=MODEL["ssm_expand"],
                    chunk=chunk, norm_eps=MODEL["norm_eps"])
    got = mamba2_apply(mp, u, cfg)
    with jax.default_matmul_precision("highest"):
        want = lm_hybrid.make_layers(MODEL)["ssm"](mp, u)
    gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert gap < 1e-5, gap
    assert np.isfinite(np.asarray(got)).all()
