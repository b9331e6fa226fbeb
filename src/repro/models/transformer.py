"""Unified decoder-only model covering all assigned architecture families.

A model is a repeating *block pattern* (``ModelCfg.pattern``) of layers, each
``LayerSpec(mixer, ffn)`` with mixer ∈ {attn, mla, mamba} and ffn ∈ {dense,
moe, dense+moe, none}.  The pattern is repeated ``n_repeats`` times and the
repeats are ``lax.scan``-ned with stacked params — this keeps the HLO size
O(pattern) instead of O(n_layers), which matters for the 80-layer configs in
the multi-pod dry-run.

Input modalities (per the assignment's stub carve-out): ``tokens`` (LM),
``embeds`` (audio: precomputed codec-frame embeddings), ``vlm`` (precomputed
patch embeddings prefix + text tokens).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelCfg
from repro.models import attention as attn_lib
from repro.models import mamba2 as mamba_lib
from repro.models import moe as moe_lib
from repro.models.attention import AttnCfg
from repro.models.layers import (dense, dense_init, embed, embedding_init,
                                 layernorm, layernorm_init, mlp, mlp_init,
                                 nonparametric_layernorm, rmsnorm,
                                 rmsnorm_init, rope_freqs)
from repro.models.mamba2 import Mamba2Cfg
from repro.models.moe import MoECfg

__all__ = ["Model", "make_model"]

# Named scopes (``jax.named_scope``) of the attention sub-layers, in every
# pass: the compiled HLO's op metadata carries them, so a device trace can
# pick out attention.  Each is scoped by its mixer kind, its pre-norm
# included.
ATTENTION_SCOPES = ("attn", "mla")


def _noshd(x, *names):
    return x


# ---------------------------------------------------------------------------- norms
def _norm_init(cfg: ModelCfg, dtype):
    if cfg.norm == "rmsnorm":
        return lambda: rmsnorm_init(cfg.d_model, dtype)
    if cfg.norm == "layernorm":
        return lambda: layernorm_init(cfg.d_model, dtype)
    if cfg.norm == "nonparametric":
        return lambda: {}
    raise ValueError(cfg.norm)


def _norm_apply(cfg: ModelCfg):
    if cfg.norm == "rmsnorm":
        return rmsnorm
    if cfg.norm == "layernorm":
        return layernorm
    if cfg.norm == "nonparametric":
        return lambda p, x: nonparametric_layernorm(x)
    raise ValueError(cfg.norm)


class Model:
    """Functional model: ``init``, ``apply`` (logits), ``loss``, serving ops."""

    def __init__(self, cfg: ModelCfg, shd: Callable = _noshd):
        self.cfg = cfg
        self.shd = shd
        self.param_dtype = jnp.dtype(cfg.param_dtype)
        self.compute_dtype = jnp.dtype(cfg.compute_dtype)
        self.attn_cfg = AttnCfg(
            d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
            qkv_bias=cfg.qkv_bias, window=cfg.window,
            rope_theta=cfg.rope_theta,
            q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
            v_head_dim=cfg.v_head_dim)
        self.mamba_cfg = Mamba2Cfg(
            d_model=cfg.d_model, d_state=cfg.ssm_state,
            headdim=cfg.ssm_headdim, expand=cfg.ssm_expand,
            chunk=cfg.ssm_chunk, bcast_groups=cfg.ssm_bcast_groups)
        self.moe_cfg = MoECfg(
            d_model=cfg.d_model, d_ff=cfg.d_ff, n_experts=cfg.n_experts,
            top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
            router_aux_weight=cfg.router_aux_weight, gated=cfg.gated_mlp,
            n_groups=cfg.moe_groups)

    # ------------------------------------------------------------------ init
    def _layer_init(self, key, spec: LayerSpec):
        cfg = self.cfg
        dtype = self.param_dtype
        kmix, kffn, _ = jax.random.split(key, 3)
        ninit = _norm_init(cfg, dtype)
        p: Dict = {"norm_mix": ninit()}
        if spec.mixer == "attn":
            p["attn"] = attn_lib.attention_init(kmix, self.attn_cfg, dtype)
        elif spec.mixer == "mla":
            p["attn"] = attn_lib.mla_init(kmix, self.attn_cfg, dtype)
        elif spec.mixer == "mamba":
            p["mamba"] = mamba_lib.mamba2_init(kmix, self.mamba_cfg, dtype)
        else:
            raise ValueError(spec.mixer)
        if spec.ffn != "none":
            p["norm_ffn"] = ninit()
        if spec.ffn in ("dense", "dense+moe"):
            p["mlp"] = mlp_init(kffn, cfg.d_model, cfg.d_ff, dtype,
                                gated=cfg.gated_mlp)
        if spec.ffn in ("moe", "dense+moe"):
            kmoe = jax.random.fold_in(kffn, 1)
            p["moe"] = moe_lib.moe_init(kmoe, self.moe_cfg, dtype)
        return p

    def init(self, key) -> Dict:
        cfg = self.cfg
        dtype = self.param_dtype
        kemb, khead, kblocks, knorm = jax.random.split(key, 4)
        params: Dict = {}
        params["embed"] = embedding_init(kemb, cfg.vocab, cfg.d_model, dtype)
        # stacked block params: one stack per pattern position
        blocks = {}
        for pos, spec in enumerate(cfg.pattern):
            keys = jax.random.split(
                jax.random.fold_in(kblocks, pos), cfg.n_repeats)
            blocks[f"pos{pos}"] = jax.vmap(
                partial(self._layer_init, spec=spec))(keys)
        params["blocks"] = blocks
        params["final_norm"] = _norm_init(cfg, dtype)()
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(khead, cfg.d_model, cfg.vocab, dtype)
        return params

    # ------------------------------------------------------------------ layers
    def _rope(self, max_len: int):
        return rope_freqs(self.attn_cfg.head_dim
                          if not self.cfg.use_mla else self.cfg.qk_rope_dim,
                          max_len, self.cfg.rope_theta)

    def _apply_layer(self, spec: LayerSpec, lp, x, cos, sin, positions):
        cfg = self.cfg
        nap = _norm_apply(cfg)
        if spec.mixer in ATTENTION_SCOPES:
            with jax.named_scope(spec.mixer):
                h = nap(lp["norm_mix"], x)
                if spec.mixer == "attn":
                    mix = attn_lib.attention_apply(lp["attn"], h,
                                                   self.attn_cfg, cos, sin,
                                                   positions, shd=self.shd)
                else:
                    mix = attn_lib.mla_apply(lp["attn"], h, self.attn_cfg,
                                             cos, sin, positions)
        else:
            mix = mamba_lib.mamba2_apply(lp["mamba"], nap(lp["norm_mix"], x),
                                         self.mamba_cfg)
        x = x + mix
        aux = jnp.zeros((), jnp.float32)
        if spec.ffn == "none":
            return x, aux
        h = nap(lp["norm_ffn"], x)
        out = 0.0
        if spec.ffn in ("dense", "dense+moe"):
            out = out + mlp(lp["mlp"], h)
        if spec.ffn in ("moe", "dense+moe"):
            mo, aux = moe_lib.moe_apply(lp["moe"], h, self.moe_cfg, self.shd)
            out = out + mo
        x = self.shd(x + out, "batch", "seq", "embed")
        return x, aux

    def _block(self, x, block_params, cos, sin, positions):
        aux_total = jnp.zeros((), jnp.float32)
        for pos, spec in enumerate(self.cfg.pattern):
            x, aux = self._apply_layer(spec, block_params[f"pos{pos}"],
                                       x, cos, sin, positions)
            aux_total = aux_total + aux
        return x, aux_total

    # ------------------------------------------------------------------ embed in
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        cd = self.compute_dtype
        if cfg.input_mode == "tokens":
            x = embed(params["embed"], batch["tokens"]).astype(cd)
        elif cfg.input_mode == "embeds":
            x = batch["embeds"].astype(cd)     # stub frontend output
        elif cfg.input_mode == "vlm":
            tok = embed(params["embed"], batch["tokens"]).astype(cd)
            x = jnp.concatenate([batch["patch_embeds"].astype(cd), tok],
                                axis=1)
        else:
            raise ValueError(cfg.input_mode)
        return self.shd(x, "batch", "seq", "embed")

    # ------------------------------------------------------------------ forward
    def apply(self, params, batch, remat: str = "none"):
        """Full-sequence forward.  Returns (logits_f32, aux_loss)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        b, s, _ = x.shape
        cos, sin = self._rope(s)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        def block_fn(carry, block_params):
            x, aux = carry
            x, a = self._block(x, block_params, cos, sin, positions)
            return (x, aux + a), None

        if remat == "full":
            block_fn = jax.checkpoint(block_fn, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(
            block_fn, (x, jnp.zeros((), jnp.float32)), params["blocks"])

        x = _norm_apply(cfg)(params["final_norm"], x)
        head = (params["embed"]["table"].T if cfg.tie_embeddings
                else params["lm_head"]["w"])
        logits = jnp.einsum("bsd,dv->bsv", x, head,
                            preferred_element_type=jnp.float32)
        return self.shd(logits, "batch", "seq", "vocab"), aux

    def loss(self, params, batch, remat: str = "none"):
        """Next-token cross entropy over ``labels`` (-1 = masked)."""
        logits, aux = self.apply(params, batch, remat=remat)
        labels = batch["labels"]
        if self.cfg.input_mode == "vlm":
            # image-prefix positions carry no labels
            pad = jnp.full(
                (labels.shape[0], logits.shape[1] - labels.shape[1]),
                -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return ce + aux, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------------ serving
    def _layer_cache(self, spec: LayerSpec, batch: int, max_len: int):
        cd = self.compute_dtype
        if spec.mixer == "attn":
            return attn_lib.init_kv_cache(self.attn_cfg, batch, max_len, cd)
        if spec.mixer == "mla":
            return attn_lib.init_mla_cache(self.attn_cfg, batch, max_len, cd)
        return mamba_lib.init_mamba_cache(self.mamba_cfg, batch, cd)

    def init_cache(self, batch: int, max_len: int):
        """Stacked (over repeats) cache per pattern position."""
        out = {}
        for pos, spec in enumerate(self.cfg.pattern):
            one = self._layer_cache(spec, batch, max_len)
            out[f"pos{pos}"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(
                    a[None], (self.cfg.n_repeats,) + a.shape), one)
        return out

    def _prefill_layer(self, spec: LayerSpec, lp, x, cos, sin, positions,
                       max_len: int):
        cfg = self.cfg
        nap = _norm_apply(cfg)
        h = nap(lp["norm_mix"], x)
        if spec.mixer == "attn":
            mix, cache = attn_lib.attention_prefill(
                lp["attn"], h, self.attn_cfg, cos, sin, max_len, positions,
                shd=self.shd)
        elif spec.mixer == "mla":
            mix, cache = attn_lib.mla_prefill(
                lp["attn"], h, self.attn_cfg, cos, sin, max_len, positions)
        else:
            mix, cache = mamba_lib.mamba2_apply(
                lp["mamba"], h, self.mamba_cfg, return_state=True)
        x = x + mix
        if spec.ffn == "none":
            return x, cache
        h = nap(lp["norm_ffn"], x)
        out = 0.0
        if spec.ffn in ("dense", "dense+moe"):
            out = out + mlp(lp["mlp"], h)
        if spec.ffn in ("moe", "dense+moe"):
            mo, _ = moe_lib.moe_apply(lp["moe"], h, self.moe_cfg, self.shd)
            out = out + mo
        return x + out, cache

    def prefill_fast(self, params, batch, max_len: Optional[int] = None):
        """One-pass prompt processing: last-token logits + populated cache.

        Unlike :meth:`prefill` (sequential, example-scale), this runs the
        normal full-sequence forward and packs each layer's K/V (or SSM
        state) into the decode-cache layout — the production prefill path.
        """
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        b, s, _ = x.shape
        max_len = max_len or s
        cos, sin = self._rope(max_len)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        def block_fn(x, block_params):
            caches = {}
            for pos_i, spec in enumerate(cfg.pattern):
                x, c = self._prefill_layer(
                    spec, block_params[f"pos{pos_i}"], x, cos, sin,
                    positions, max_len)
                caches[f"pos{pos_i}"] = c
            return x, caches

        x, cache = jax.lax.scan(block_fn, x, params["blocks"])
        x = _norm_apply(cfg)(params["final_norm"], x[:, -1:, :])
        head = (params["embed"]["table"].T if cfg.tie_embeddings
                else params["lm_head"]["w"])
        logits = jnp.einsum("bsd,dv->bsv", x, head,
                            preferred_element_type=jnp.float32)
        return logits[:, 0, :], cache

    def _decode_layer(self, spec: LayerSpec, lp, x, cache, pos, cos, sin):
        cfg = self.cfg
        nap = _norm_apply(cfg)
        h = nap(lp["norm_mix"], x)
        if spec.mixer == "attn":
            mix, cache = attn_lib.attention_decode(
                lp["attn"], h, cache, pos, self.attn_cfg, cos, sin)
        elif spec.mixer == "mla":
            mix, cache = attn_lib.mla_decode(
                lp["attn"], h, cache, pos, self.attn_cfg, cos, sin)
        else:
            mix, cache = mamba_lib.mamba2_decode(
                lp["mamba"], h, cache, self.mamba_cfg)
        x = x + mix
        if spec.ffn == "none":
            return x, cache
        h = nap(lp["norm_ffn"], x)
        out = 0.0
        if spec.ffn in ("dense", "dense+moe"):
            out = out + mlp(lp["mlp"], h)
        if spec.ffn in ("moe", "dense+moe"):
            mo, _ = moe_lib.moe_apply(lp["moe"], h, self.moe_cfg, self.shd)
            out = out + mo
        return x + out, cache

    def decode_step(self, params, cache, tokens_or_embeds, pos,
                    max_positions: Optional[int] = None):
        """One new token for every sequence in the batch.

        ``tokens_or_embeds``: (b,) int32 tokens, or (b, 1, d) embeds.
        ``pos``: scalar int32 — current position (same for whole batch).
        ``max_positions``: static bound on positions (RoPE table size);
        defaults to the cache length — must be passed explicitly for
        sliding-window caches whose ring is shorter than the sequence.
        Returns (logits (b, vocab) f32, new cache).
        """
        cfg = self.cfg
        cd = self.compute_dtype
        if jnp.issubdtype(tokens_or_embeds.dtype, jnp.integer):
            x = embed(params["embed"], tokens_or_embeds[:, None]).astype(cd)
        else:
            x = tokens_or_embeds.astype(cd)
        max_len = max_positions or self._cache_len(cache)
        cos, sin = self._rope(max_len)

        def block_fn(x, scanned):
            block_params, blk_cache = scanned
            new_cache = {}
            for p_i, spec in enumerate(cfg.pattern):
                x, c = self._decode_layer(
                    spec, block_params[f"pos{p_i}"], x,
                    blk_cache[f"pos{p_i}"], pos, cos, sin)
                new_cache[f"pos{p_i}"] = c
            return x, new_cache

        x, new_cache = jax.lax.scan(block_fn, x, (params["blocks"], cache))
        x = _norm_apply(cfg)(params["final_norm"], x)
        head = (params["embed"]["table"].T if cfg.tie_embeddings
                else params["lm_head"]["w"])
        logits = jnp.einsum("bsd,dv->bsv", x, head,
                            preferred_element_type=jnp.float32)
        return logits[:, 0, :], new_cache

    def _cache_len(self, cache) -> int:
        for pos, spec in enumerate(self.cfg.pattern):
            if spec.mixer == "attn":
                return cache[f"pos{pos}"]["k"].shape[2]
            if spec.mixer == "mla":
                return cache[f"pos{pos}"]["ckv"].shape[2]
        return 1  # pure-SSM: rope tables unused

    def prefill(self, params, batch, max_len: Optional[int] = None):
        """Run the prompt, build a cache, return last-position logits.

        Simple implementation: full forward for logits + per-layer cache
        writes via teacher-forced decode of the K/V projections.  Attention
        caches hold the prompt; SSM caches hold the final state (computed by
        stepping the recurrence — adequate for the example serving loop).
        """
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        b, s, _ = x.shape
        max_len = max_len or s
        cache = self.init_cache(b, max_len)
        logits = None

        def step(i, carry):
            cache, last_logits = carry
            tok_x = jax.lax.dynamic_slice_in_dim(x, i, 1, axis=1)
            lg, cache = self._decode_embeds(params, cache, tok_x, i)
            return cache, lg

        # sequential prefill (example-scale only; training uses apply()).
        cache, logits = jax.lax.fori_loop(
            0, s, step, (cache, jnp.zeros((b, cfg.vocab), jnp.float32)))
        return logits, cache

    def _decode_embeds(self, params, cache, x, pos):
        cfg = self.cfg
        max_len = self._cache_len(cache)
        cos, sin = self._rope(max_len)

        def block_fn(x, scanned):
            block_params, blk_cache = scanned
            new_cache = {}
            for p_i, spec in enumerate(cfg.pattern):
                x, c = self._decode_layer(
                    spec, block_params[f"pos{p_i}"], x,
                    blk_cache[f"pos{p_i}"], pos, cos, sin)
                new_cache[f"pos{p_i}"] = c
            return x, new_cache

        x, new_cache = jax.lax.scan(block_fn, x, (params["blocks"], cache))
        x = _norm_apply(cfg)(params["final_norm"], x)
        head = (params["embed"]["table"].T if cfg.tie_embeddings
                else params["lm_head"]["w"])
        logits = jnp.einsum("bsd,dv->bsv", x, head,
                            preferred_element_type=jnp.float32)
        return logits[:, 0, :], new_cache


def make_model(cfg: ModelCfg, shd: Callable = _noshd) -> Model:
    return Model(cfg, shd)
