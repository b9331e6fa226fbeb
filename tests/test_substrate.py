"""Substrate layers: data pipeline, checkpointing, serving, gossip backends."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gossip import DenseComm
from repro.core.topology import ring, torus


# --------------------------------------------------------------------- gossip
def test_dense_mix_equals_matmul():
    top = ring(8)
    comm = DenseComm(top)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 5, 3))
    got = comm.mix({"w": x})["w"]
    want = jnp.einsum("kj,jab->kab", jnp.asarray(top.W, jnp.float32), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("wire_dtype", ["float32", "bfloat16"])
def test_dense_mix_matmul_is_full_precision(wire_dtype):
    """The W-matmul must not run at a TPU's default f32 precision, which
    rounds W to bf16 (1/3 → 0.33398) and drifts from the sharded AXPY."""
    comm = DenseComm(ring(8), wire_dtype=wire_dtype)
    jx = jax.make_jaxpr(comm.mix)({"w": jnp.zeros((8, 5, 3))})
    dots = [e for e in jx.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert dots
    for e in dots:
        assert e.params["precision"] in (
            jax.lax.Precision.HIGHEST,
            (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)), e


def test_dense_shift_views_roll():
    comm = DenseComm(ring(4))
    x = jnp.arange(4.0)[:, None]
    views = comm.shift_views({"w": x})
    np.testing.assert_allclose(np.asarray(views[(0, 1)]["w"][:, 0]),
                               [1, 2, 3, 0])
    np.testing.assert_allclose(np.asarray(views[(0, -1)]["w"][:, 0]),
                               [3, 0, 1, 2])


def test_torus_mix_factorizes():
    top = torus((2, 4))
    comm = DenseComm(top)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 3))
    got = comm.mix({"w": x})["w"]
    want = jnp.einsum("kj,ja->ka", jnp.asarray(top.W, jnp.float32), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ----------------------------------------------------------------------- data
def test_lm_batch_deterministic_and_aligned():
    from repro.data.synthetic import LMStreamCfg, lm_batch
    cfg = LMStreamCfg(vocab=128, seq_len=16, batch=2, n_workers=4)
    b1 = lm_batch(cfg, 3)
    b2 = lm_batch(cfg, 3)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert b1["tokens"].shape == (4, 2, 16)
    # labels are next-token shifted
    np.testing.assert_array_equal(np.asarray(b1["tokens"][..., 1:]),
                                  np.asarray(b1["labels"][..., :-1]))
    b3 = lm_batch(cfg, 4)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_class_batch_noniid():
    from repro.data.synthetic import ClassStreamCfg, class_batch
    iid = class_batch(ClassStreamCfg(batch=64, n_workers=4), 0)
    non = class_batch(ClassStreamCfg(batch=64, n_workers=4,
                                     dirichlet_alpha=0.1), 0)
    assert iid["images"].shape == (4, 64, 32, 32, 3)
    # non-IID: per-worker label histograms diverge more than IID
    def spread(b):
        h = np.stack([np.bincount(np.asarray(b["labels"][k]), minlength=10)
                      for k in range(4)])
        return np.abs(h / 64.0 - 0.1).mean()
    assert spread(non) > spread(iid)


# ----------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import checkpoint as ckpt
    params = {"a": jnp.arange(6.0).reshape(2, 3),
              "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    state = {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
             "step": jnp.int32(7)}
    ckpt.save(str(tmp_path), 7, params=params, opt_state=state)
    assert ckpt.latest_step(str(tmp_path)) == 7
    out = ckpt.restore(str(tmp_path), 7,
                       {"params": params, "opt_state": state})
    np.testing.assert_array_equal(np.asarray(out["params"]["a"]),
                                  np.asarray(params["a"]))
    assert int(out["opt_state"]["step"]) == 7
    # shape mismatch is rejected
    bad = {"a": jnp.zeros((3, 3)), "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    with pytest.raises(ValueError):
        ckpt.restore(str(tmp_path), 7, {"params": bad})


# -------------------------------------------------------------------- serving
def test_generate_greedy_deterministic():
    from repro.configs.base import ModelCfg
    from repro.models import make_model
    from repro.serve.serving import generate
    mcfg = ModelCfg(name="tiny", arch_type="dense", n_layers=2, d_model=32,
                    n_heads=4, n_kv_heads=2, d_ff=64, vocab=64)
    model = make_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    o1 = generate(model, params, prompts, 6)
    o2 = generate(model, params, prompts, 6)
    assert o1.shape == (2, 14)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(o1[:, :8]), np.asarray(prompts))


# ------------------------------------------------------------------ schedules
def test_warmup_cosine():
    from repro.core.schedules import warmup_cosine
    f = warmup_cosine(10, 100, min_factor=0.1)
    assert float(f(jnp.int32(0))) == pytest.approx(0.0)
    assert float(f(jnp.int32(10))) == pytest.approx(1.0)
    assert float(f(jnp.int32(100))) == pytest.approx(0.1, abs=1e-6)


# -------------------------------------------------------------- hlo analysis
def test_collective_parse_units():
    from repro.launch.hlo_analysis import parse_collectives
    txt = """
ENTRY %main (a: f32[16]) -> f32[16] {
  %ar = f32[1024,8]{1,0} all-reduce(%x), replica_groups=[8,8]<=[64]
  %cp = bf16[512]{0} collective-permute(%y), channel_id=3
  %ag = f32[64,32]{1,0} all-gather(%z), replica_groups=[4,16]<=[64]
}
"""
    st = parse_collectives(txt)
    assert st.counts == {"all-reduce": 1, "collective-permute": 1,
                         "all-gather": 1}
    assert st.result_bytes["all-reduce"] == 1024 * 8 * 4
    assert st.result_bytes["collective-permute"] == 512 * 2
    # all-reduce wire = 2(n-1)/n * size, n=8
    assert st.wire_bytes["all-reduce"] == pytest.approx(
        2 * 7 / 8 * 1024 * 8 * 4)


def test_collective_parse_loop_multiplicity():
    from repro.launch.hlo_analysis import parse_collectives
    txt = """
%body (p: f32[8]) -> f32[8] {
  %ar = f32[256]{0} all-reduce(%x), replica_groups=[8,8]<=[64]
}
%cond (p: f32[8]) -> pred[] {
  %lt = pred[] compare(%i, %n)
}
ENTRY %main (a: f32[8]) -> f32[8] {
  %w = f32[8] while(%a), condition=%cond, body=%body
  %cp = f32[128]{0} collective-permute(%y)
}
"""
    st = parse_collectives(txt, loop_trips=(4,))
    assert st.counts["all-reduce"] == 4          # ×4 inside the loop
    assert st.counts["collective-permute"] == 1  # top level
