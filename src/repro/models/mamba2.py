"""Mamba-2 SSD (state-space duality) mixer — arXiv:2405.21060.

Training/prefill uses the chunked SSD algorithm: within a chunk the output is
a masked (decay-weighted) attention-like quadratic form; across chunks a
linear recurrence carries the (heads, headdim, d_state) SSM state.  Chunking
makes the op O(s·Q) with MXU-friendly matmuls instead of an O(s) sequential
scan.  Decode is the O(1) recurrent step on the cached state.

Layout notes (TPU adaptation): head/p/n dims are kept as explicit trailing
dims (multiples of 64/128) so every einsum maps onto the MXU; the chunk scan
is a ``lax.scan`` whose carry is the SSM state (small), so XLA keeps the big
intra-chunk tensors out of the loop carry.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.layers import dense, dense_init, rmsnorm, rmsnorm_init

__all__ = ["Mamba2Cfg", "mamba2_init", "mamba2_apply", "mamba2_decode",
           "init_mamba_cache"]


@dataclasses.dataclass(frozen=True)
class Mamba2Cfg:
    d_model: int
    d_state: int = 128
    headdim: int = 64
    expand: int = 2
    chunk: int = 256
    conv_kernel: int = 4
    n_groups: int = 1
    bcast_groups: bool = False  # broadcast (not gather) group->head expand
    norm_eps: float = 1e-6      # the gated RMSNorm's eps

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.headdim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def in_proj_dim(self) -> int:
        # z, xBC, dt
        return self.d_inner + self.conv_dim + self.n_heads


def mamba2_init(key, cfg: Mamba2Cfg, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    h = cfg.n_heads
    return {
        "in_proj": dense_init(k1, cfg.d_model, cfg.in_proj_dim, dtype),
        "conv_w": (jax.random.normal(k2, (cfg.conv_kernel, cfg.conv_dim),
                                     jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((cfg.conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "norm": rmsnorm_init(cfg.d_inner, dtype),
        "out_proj": dense_init(k3, cfg.d_inner, cfg.d_model, dtype),
    }


def _split_zxbcdt(cfg: Mamba2Cfg, zxbcdt):
    z, xBC, dt = jnp.split(
        zxbcdt, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
    return z, xBC, dt


def _causal_conv(xBC, w, b):
    """Depthwise causal conv1d.  xBC: (b, s, c); w: (k, c)."""
    k = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * w[i][None, None, :]
              for i in range(k))
    return jax.nn.silu((out + b[None, None, :]).astype(jnp.float32)
                       ).astype(xBC.dtype)


def _split_xbc(cfg: Mamba2Cfg, xBC, bsz, s):
    gn = cfg.n_groups * cfg.d_state
    x, B, C = jnp.split(xBC, [cfg.d_inner, cfg.d_inner + gn], axis=-1)
    x = x.reshape(bsz, s, cfg.n_heads, cfg.headdim)
    B = B.reshape(bsz, s, cfg.n_groups, cfg.d_state)
    C = C.reshape(bsz, s, cfg.n_groups, cfg.d_state)
    # groups -> heads
    rep = cfg.n_heads // cfg.n_groups
    if cfg.bcast_groups:
        # broadcast+reshape lowers to an HLO broadcast; jnp.repeat lowers to
        # a gather, which the SPMD partitioner resolves with a full
        # all-reduce of the expanded (b,s,h,n) tensor per layer (§Perf).
        B = jnp.broadcast_to(B[:, :, :, None, :],
                             (bsz, s, cfg.n_groups, rep, cfg.d_state)
                             ).reshape(bsz, s, cfg.n_heads, cfg.d_state)
        C = jnp.broadcast_to(C[:, :, :, None, :],
                             (bsz, s, cfg.n_groups, rep, cfg.d_state)
                             ).reshape(bsz, s, cfg.n_heads, cfg.d_state)
    else:
        B = jnp.repeat(B, rep, axis=2)
        C = jnp.repeat(C, rep, axis=2)
    return x, B, C


def mamba2_apply(params, u, cfg: Mamba2Cfg, return_state: bool = False):
    """u: (b, s, d_model) -> (b, s, d_model) [, decode cache].  Chunked SSD."""
    bsz, s, _ = u.shape
    Q = min(cfg.chunk, s)
    assert s % Q == 0, f"seq {s} % chunk {Q} != 0"
    nc = s // Q
    h, p, n = cfg.n_heads, cfg.headdim, cfg.d_state

    zxbcdt = dense(params["in_proj"], u)
    z, xBC_raw, dt_raw = _split_zxbcdt(cfg, zxbcdt)
    xBC = _causal_conv(xBC_raw, params["conv_w"], params["conv_b"])
    x, B, C = _split_xbc(cfg, xBC, bsz, s)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"][None, None, :])       # (b,s,h)
    A = -jnp.exp(params["A_log"])                                  # (h,)
    dA = dt * A[None, None, :]                                     # (b,s,h) ≤ 0

    # chunked views
    xc = x.reshape(bsz, nc, Q, h, p).astype(jnp.float32)
    Bc = B.reshape(bsz, nc, Q, h, n).astype(jnp.float32)
    Cc = C.reshape(bsz, nc, Q, h, n).astype(jnp.float32)
    dtc = dt.reshape(bsz, nc, Q, h)
    dAc = dA.reshape(bsz, nc, Q, h)
    cum = jnp.cumsum(dAc, axis=2)                                  # (b,nc,Q,h)

    # ---- intra-chunk (quadratic, attention-like with decay mask)
    # L[q, j] = exp(cum_q - cum_j) for j <= q
    rel = cum[:, :, :, None, :] - cum[:, :, None, :, :]            # (b,nc,Q,Q,h)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    # mask *before* exp: exp of the (positive) upper-triangular part would
    # overflow and poison gradients through the where.
    rel = jnp.where(mask[None, None, :, :, None], rel, -1e9)
    L = jnp.exp(rel)
    att = jnp.einsum("bcqhn,bcjhn->bcqjh", Cc, Bc) * L
    y_intra = jnp.einsum("bcqjh,bcjh,bcjhp->bcqhp", att, dtc, xc)

    # ---- chunk states:  S_c = Σ_j exp(cum_Q - cum_j) dt_j B_j ⊗ x_j
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)                # (b,nc,Q,h)
    states = jnp.einsum("bcjh,bcjh,bcjhn,bcjhp->bchnp",
                        decay_to_end, dtc, Bc, xc)                 # (b,nc,h,n,p)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                        # (b,nc,h)

    def scan_fn(S, inp):
        st, dec = inp            # (b,h,n,p), (b,h)
        S_new = S * dec[:, :, None, None] + st
        return S_new, S          # emit state *entering* the chunk

    S0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    S_final, S_in = jax.lax.scan(
        scan_fn, S0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    S_in = jnp.moveaxis(S_in, 0, 1)                                # (b,nc,h,n,p)

    # ---- inter-chunk:  y_q += exp(cum_q) C_q · S_in
    y_inter = jnp.einsum("bcqh,bcqhn,bchnp->bcqhp",
                         jnp.exp(cum), Cc, S_in)

    y = (y_intra + y_inter).reshape(bsz, s, h, p)
    y = y + params["D"][None, None, :, None] * x.astype(jnp.float32)
    y = y.reshape(bsz, s, cfg.d_inner).astype(u.dtype)

    # gated RMSNorm then output projection
    y = rmsnorm(params["norm"],
                (y.astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype),
                eps=cfg.norm_eps)
    out = dense(params["out_proj"], y)
    if not return_state:
        return out
    # decode cache: final SSM state + last (k-1) raw conv inputs
    kk = cfg.conv_kernel - 1
    conv_tail = xBC_raw[:, s - kk:, :] if s >= kk else jnp.pad(
        xBC_raw, ((0, 0), (kk - s, 0), (0, 0)))
    return out, {"ssm": S_final, "conv": conv_tail}


# ---------------------------------------------------------------------------- decode
def init_mamba_cache(cfg: Mamba2Cfg, batch: int, dtype):
    return {
        "ssm": jnp.zeros((batch, cfg.n_heads, cfg.d_state, cfg.headdim),
                         jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, cfg.conv_dim), dtype),
    }


def mamba2_decode(params, u, cache, cfg: Mamba2Cfg):
    """One token.  u: (b, 1, d_model)."""
    bsz = u.shape[0]
    h, p, n = cfg.n_heads, cfg.headdim, cfg.d_state

    zxbcdt = dense(params["in_proj"], u)
    z, xBC_new, dt_raw = _split_zxbcdt(cfg, zxbcdt)

    # rolling conv state
    conv_in = jnp.concatenate([cache["conv"], xBC_new], axis=1)  # (b, k, c)
    w = params["conv_w"]
    out = jnp.einsum("bkc,kc->bc", conv_in.astype(jnp.float32),
                     w.astype(jnp.float32)) + params["conv_b"].astype(jnp.float32)
    xBC = jax.nn.silu(out)[:, None, :].astype(u.dtype)
    new_conv = conv_in[:, 1:, :]

    x, B, C = _split_xbc(cfg, xBC, bsz, 1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"][None, None, :])[:, 0]   # (b,h)
    A = -jnp.exp(params["A_log"])
    dA = jnp.exp(dt * A[None, :])                                    # (b,h)

    x0 = x[:, 0].astype(jnp.float32)      # (b,h,p)
    B0 = B[:, 0].astype(jnp.float32)      # (b,h,n)
    C0 = C[:, 0].astype(jnp.float32)
    S = cache["ssm"] * dA[:, :, None, None] + jnp.einsum(
        "bh,bhn,bhp->bhnp", dt, B0, x0)
    y = jnp.einsum("bhn,bhnp->bhp", C0, S)
    y = y + params["D"][None, :, None] * x0
    y = y.reshape(bsz, 1, cfg.d_inner).astype(u.dtype)
    y = rmsnorm(params["norm"],
                (y.astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype),
                eps=cfg.norm_eps)
    return dense(params["out_proj"], y), {"ssm": S, "conv": new_conv}
