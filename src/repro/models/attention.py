"""Attention: GQA (+bias), sliding-window, MLA, blockwise long-seq, KV-cache decode.

Three execution paths per variant:
  * ``attend_full``      — O(s²) einsum + causal mask (short sequences).
  * ``attend_blockwise`` — scan over query chunks; memory O(s·chunk) instead
    of O(s²).  Sliding-window attention additionally slices the KV band, so
    flops drop to O(s·window).
  * ``decode``           — one new token against a KV cache (full-length
    cache, or ring-buffer cache for sliding-window).

GQA layout: q (b, s, n_heads, hd); k/v (b, s, n_kv, hd); heads grouped as
(n_kv, group) for the score einsums so XLA sees the kv-head dim it can shard.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense, dense_init, rmsnorm, rmsnorm_init

__all__ = ["AttnCfg", "attention_init", "attention_apply", "attention_decode",
           "init_kv_cache", "mla_init", "mla_apply", "mla_decode",
           "init_mla_cache", "NEG_INF"]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False          # qwen2
    window: Optional[int] = None    # sliding-window size (None = full causal)
    q_chunk: int = 1024      # blockwise query-chunk length  # lint: allow
    blockwise_threshold: int = 8192  # use blockwise when seq >= this
    rope_theta: float = 10000.0
    rope: bool = True               # False: no positional embedding (NoPE)
    scale: Optional[float] = None   # softmax scale; None = head_dim**-0.5
    # MLA dims (minicpm3 / deepseek-v2 style); used only by the mla_* path
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_dim: int = 64
    qk_rope_dim: int = 32
    v_head_dim: int = 64


# ============================================================================ GQA
def attention_init(key, cfg: AttnCfg, dtype):
    kq, kk, kv, ko = jax.random.split(key, 4)
    h, kvh, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    return {
        "wq": dense_init(kq, d, h * hd, dtype, use_bias=cfg.qkv_bias),
        "wk": dense_init(kk, d, kvh * hd, dtype, use_bias=cfg.qkv_bias),
        "wv": dense_init(kv, d, kvh * hd, dtype, use_bias=cfg.qkv_bias),
        "wo": dense_init(ko, h * hd, d, dtype),
    }


def _qkv(params, x, cfg: AttnCfg, cos, sin, positions):
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(params["wq"], x).reshape(b, s, h, hd)
    k = dense(params["wk"], x).reshape(b, s, kvh, hd)
    v = dense(params["wv"], x).reshape(b, s, kvh, hd)
    if cfg.rope:
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    return q, k, v


def _scale(cfg: AttnCfg, head_dim: int) -> float:
    return cfg.scale if cfg.scale is not None else head_dim ** -0.5


def _scores_to_out(q, k, v, mask, scale):
    """q: (b,sq,kv,g,hd); k/v: (b,sk,kv,hd); mask: (b|1,1|kv?,sq,sk) bool."""
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    # cast the fill explicitly: a bare python float is a weak f64 scalar
    # under jax_enable_x64 and would promote the whole softmax to f64
    # (repro.analysis.jaxpr_check's no-f64 contract)
    logits = jnp.where(mask[:, None, None, :, :], logits,
                       jnp.float32(NEG_INF))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def _group(q, cfg: AttnCfg):
    b, s, h, hd = q.shape
    return q.reshape(b, s, cfg.n_kv_heads, h // cfg.n_kv_heads, hd)


def attend_full(q, k, v, cfg: AttnCfg, q_positions, k_positions):
    """Materialized causal (+optional sliding-window) attention."""
    scale = _scale(cfg, cfg.head_dim)
    qg = _group(q, cfg)
    caus = q_positions[:, :, None] >= k_positions[:, None, :]
    if cfg.window is not None:
        caus &= (q_positions[:, :, None] - k_positions[:, None, :]) < cfg.window
    out = _scores_to_out(qg, k, v, caus, scale)
    b, s = q.shape[0], q.shape[1]
    # v head dim may differ from qk head dim (MLA)
    return out.reshape(b, s, cfg.n_heads, v.shape[-1])


def attend_blockwise(q, k, v, cfg: AttnCfg, q_positions, k_positions):
    """Scan over query chunks; SWA slices a static-size KV band per chunk."""
    b, s, h, hd = q.shape
    cq = min(cfg.q_chunk, s)
    assert s % cq == 0, f"seq {s} not divisible by q_chunk {cq}"
    nchunks = s // cq
    scale = _scale(cfg, hd)
    qg = _group(q, cfg)

    if cfg.window is not None:
        band = cq + ((cfg.window + cq - 1) // cq) * cq  # static KV band length
        pad = band - cq
        kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        posp = jnp.pad(k_positions, ((0, 0), (pad, 0)), constant_values=-1)

        def chunk_fn(_, i):
            qs = jax.lax.dynamic_slice_in_dim(qg, i * cq, cq, axis=1)
            qpos = jax.lax.dynamic_slice_in_dim(q_positions, i * cq, cq, axis=1)
            ks = jax.lax.dynamic_slice_in_dim(kp, i * cq, band, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vp, i * cq, band, axis=1)
            kpos = jax.lax.dynamic_slice_in_dim(posp, i * cq, band, axis=1)
            m = (qpos[:, :, None] >= kpos[:, None, :]) & (kpos[:, None, :] >= 0)
            m &= (qpos[:, :, None] - kpos[:, None, :]) < cfg.window
            return None, _scores_to_out(qs, ks, vs, m, scale)
    else:
        def chunk_fn(_, i):
            qs = jax.lax.dynamic_slice_in_dim(qg, i * cq, cq, axis=1)
            qpos = jax.lax.dynamic_slice_in_dim(q_positions, i * cq, cq, axis=1)
            m = qpos[:, :, None] >= k_positions[:, None, :]
            return None, _scores_to_out(qs, k, v, m, scale)

    _, outs = jax.lax.scan(chunk_fn, None, jnp.arange(nchunks))
    # outs: (nchunks, b, cq, kv, g, vd) -> (b, s, h, vd)
    outs = jnp.moveaxis(outs, 0, 1).reshape(b, s, cfg.n_heads, v.shape[-1])
    return outs


def _noshd(x, *names):
    return x


def attention_apply(params, x, cfg: AttnCfg, cos, sin, positions=None,
                    force_blockwise: Optional[bool] = None, shd=_noshd):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q, k, v = _qkv(params, x, cfg, cos, sin, positions)
    # perf lever (attn_ctx_shard): queries seq-sharded over the tp axis,
    # k/v replicated -> the s² score tensors partition over query chunks
    # with no sharded-contraction psum.
    q = shd(q, "batch", "seq_q", "heads", "head")
    k = shd(k, "batch", "seq_kv", "kv", "head")
    v = shd(v, "batch", "seq_kv", "kv", "head")
    blockwise = (s >= cfg.blockwise_threshold if force_blockwise is None
                 else force_blockwise)
    attend = attend_blockwise if blockwise else attend_full
    out = attend(q, k, v, cfg, positions, positions)
    out = shd(out, "batch", "seq_q", "heads", "head")
    return dense(params["wo"], out.reshape(b, s, -1))


def _prompt_cache(cfg: AttnCfg, k, v, positions, max_len: int):
    """Pack a full-prompt K/V into the decode cache layout.

    Full cache: positions 0..s-1 at slots 0..s-1, rest invalid.
    Ring (SWA) cache: position p lives at slot p % slots — for the
    consecutive prompt tail this is a roll by (s mod slots).
    """
    b, s = positions.shape
    slots = max_len if cfg.window is None else min(cfg.window, max_len)
    if cfg.window is not None and s > slots:
        k_t, v_t = k[:, s - slots:], v[:, s - slots:]
        p_t = positions[:, s - slots:]
        sh = s % slots
        return {"k": jnp.roll(k_t, sh, axis=1),
                "v": jnp.roll(v_t, sh, axis=1),
                "pos": jnp.roll(p_t, sh, axis=1)}
    pad = slots - s
    return {
        "k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
        "pos": jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1),
    }


def attention_prefill(params, x, cfg: AttnCfg, cos, sin, max_len: int,
                      positions=None, shd=_noshd):
    """Full-sequence forward that also emits the decode cache."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q, k, v = _qkv(params, x, cfg, cos, sin, positions)
    q = shd(q, "batch", "seq_q", "heads", "head")
    k = shd(k, "batch", "seq_kv", "kv", "head")
    v = shd(v, "batch", "seq_kv", "kv", "head")
    blockwise = s >= cfg.blockwise_threshold
    attend = attend_blockwise if blockwise else attend_full
    out = attend(q, k, v, cfg, positions, positions)
    out = shd(out, "batch", "seq_q", "heads", "head")
    y = dense(params["wo"], out.reshape(b, s, -1))
    return y, _prompt_cache(cfg, k, v, positions, max_len)


# ---------------------------------------------------------------------------- decode
def init_kv_cache(cfg: AttnCfg, batch: int, max_len: int, dtype):
    """Full cache, or ring buffer of ``window`` slots for sliding-window."""
    slots = max_len if cfg.window is None else min(cfg.window, max_len)
    return {
        "k": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.full((batch, slots), -1, jnp.int32),
    }


def attention_decode(params, x, cache, pos, cfg: AttnCfg, cos, sin):
    """One-step decode.  x: (b, 1, d); pos: scalar int32 current position."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _qkv(params, x, cfg, cos, sin, positions)

    slots = cache["k"].shape[1]
    slot = pos % slots if cfg.window is not None else pos
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], positions, slot, axis=1)

    scale = _scale(cfg, cfg.head_dim)
    qg = _group(q, cfg)
    mask = (cpos >= 0) & (cpos <= pos)
    if cfg.window is not None:
        mask &= cpos > pos - cfg.window
    out = _scores_to_out(qg, k, v, mask[:, None, :], scale)
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    y = dense(params["wo"], out)
    return y, {"k": k, "v": v, "pos": cpos}


# ============================================================================ MLA
def mla_init(key, cfg: AttnCfg, dtype):
    ks = jax.random.split(key, 7)
    d, h = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wdq": dense_init(ks[0], d, cfg.q_lora_rank, dtype),
        "q_norm": rmsnorm_init(cfg.q_lora_rank, dtype),
        "wuq": dense_init(ks[1], cfg.q_lora_rank, h * qk, dtype),
        "wdkv": dense_init(ks[2], d, cfg.kv_lora_rank, dtype),
        "kv_norm": rmsnorm_init(cfg.kv_lora_rank, dtype),
        "wkr": dense_init(ks[3], d, cfg.qk_rope_dim, dtype),
        "wuk": dense_init(ks[4], cfg.kv_lora_rank, h * cfg.qk_nope_dim, dtype),
        "wuv": dense_init(ks[5], cfg.kv_lora_rank, h * cfg.v_head_dim, dtype),
        "wo": dense_init(ks[6], h * cfg.v_head_dim, d, dtype),
    }


def _mla_qkv(params, x, cfg: AttnCfg, cos, sin, positions):
    b, s, _ = x.shape
    h = cfg.n_heads
    cq = rmsnorm(params["q_norm"], dense(params["wdq"], x))
    q = dense(params["wuq"], cq).reshape(b, s, h, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, cos, sin, positions)

    ckv = rmsnorm(params["kv_norm"], dense(params["wdkv"], x))  # (b,s,r)
    k_rope = dense(params["wkr"], x)[:, :, None, :]             # shared head
    k_rope = apply_rope(k_rope, cos, sin, positions)
    return q_nope, q_rope, ckv, k_rope


def _mla_expand(params, ckv, k_rope, cfg: AttnCfg):
    b, s, _ = ckv.shape
    h = cfg.n_heads
    k_nope = dense(params["wuk"], ckv).reshape(b, s, h, cfg.qk_nope_dim)
    v = dense(params["wuv"], ckv).reshape(b, s, h, cfg.v_head_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, cfg.qk_rope_dim))], axis=-1)
    return k, v


def mla_apply(params, x, cfg: AttnCfg, cos, sin, positions=None):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q_nope, q_rope, ckv, k_rope = _mla_qkv(params, x, cfg, cos, sin, positions)
    k, v = _mla_expand(params, ckv, k_rope, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # MLA is MHA (n_kv == n_heads) over qk = nope+rope dims
    mcfg = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads,
                               head_dim=cfg.qk_nope_dim + cfg.qk_rope_dim)
    blockwise = s >= cfg.blockwise_threshold
    attend = attend_blockwise if blockwise else attend_full
    out = attend(q, k, v, mcfg, positions, positions)
    return dense(params["wo"], out.reshape(b, s, -1))


def mla_prefill(params, x, cfg: AttnCfg, cos, sin, max_len: int,
                positions=None):
    """MLA full-sequence forward that also emits the compressed cache."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q_nope, q_rope, ckv, k_rope = _mla_qkv(params, x, cfg, cos, sin, positions)
    k, v = _mla_expand(params, ckv, k_rope, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    mcfg = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads,
                               head_dim=cfg.qk_nope_dim + cfg.qk_rope_dim)
    attend = attend_blockwise if s >= cfg.blockwise_threshold else attend_full
    out = attend(q, k, v, mcfg, positions, positions)
    y = dense(params["wo"], out.reshape(b, s, -1))
    pad = max_len - s
    cache = {
        "ckv": jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
        "krope": jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0), (0, 0))),
        "pos": jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1),
    }
    return y, cache


def init_mla_cache(cfg: AttnCfg, batch: int, max_len: int, dtype):
    """Compressed cache: latent c_kv + shared rotary key — the MLA win."""
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, 1, cfg.qk_rope_dim), dtype),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def mla_decode(params, x, cache, pos, cfg: AttnCfg, cos, sin):
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv(
        params, x, cfg, cos, sin, positions)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_new, pos, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(
        cache["krope"], krope_new, pos, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(cache["pos"], positions, pos, axis=1)

    k, v = _mla_expand(params, ckv, krope, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    mcfg = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads,
                               head_dim=cfg.qk_nope_dim + cfg.qk_rope_dim)
    mask = (cpos >= 0) & (cpos <= pos)
    out = _scores_to_out(_group(q, mcfg), k, v, mask[:, None, :],
                         mcfg.head_dim ** -0.5)
    y = dense(params["wo"], out.reshape(b, 1, -1))
    return y, {"ckv": ckv, "krope": krope, "pos": cpos}
