"""QSGD quantization kernels: blockwise s-level quantize + bit-pack on the
flatten-once (rows, 1024) layout.

  * ``qsgd_quant_kernel``   — x (rows, 1024) f32 → packed levels
                              (rows, 1024·bits/8) uint8 + norms (rows, 1) f32.
  * ``qsgd_dequant_kernel`` — inverse: Q(x) = (u − s)/s · norm.

One *row* is one quantization block: ``norm = max |x|`` over the row, then
``u = round(x / norm · s) + s`` ∈ [0, 2s] packed ``8/bits`` elements per
byte with ``bits = qsgd_bits(levels)`` ∈ {2, 4, 8} (the same exact
MXU bit-gather as the sign kernel, ``repro.kernels.bitpack``).
Deterministic nearest rounding keeps the operator a δ-contraction; the
jnp oracle is ``repro.core.wire.qsgd_rows``.

Padding contract: the ``KernelPlan`` zero-pads tail rows, and 0 quantizes
to the center level u = s which dequantizes back to exactly 0, so no
counts operand is needed (unlike sign, whose *scale* depends on the true
length).  All-padding rows carry norm 0 and dequantize to 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# the bit-width rule is owned by the wire codec (one source of truth for
# the kernel, the jnp oracle, and the byte accounting)
from repro.core.wire import qsgd_bits as _bits
from repro.kernels import LANE, bitpack, default_interpret

__all__ = ["qsgd_quant_pallas", "qsgd_dequant_pallas", "LANE", "BLOCK_ROWS"]

BLOCK_ROWS = 256


def _quant_kernel(x_ref, w_ref, packed_ref, norm_ref, *, levels, bits):
    x = x_ref[...]                                    # (BR, 1024) f32
    s = jnp.float32(levels)
    norm = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    norm_ref[...] = norm
    # scale-first, single elementwise multiply — mirrors the jnp oracle so
    # no lowering can reassociate the div/mul chain (see wire.qsgd_rows)
    qscale = s / jnp.maximum(norm, 1e-30)
    u = jnp.round(x * qscale) + s                     # integers in [0, 2s]
    packed_ref[...] = bitpack.pack_fields(u, w_ref[...], bits=bits)


def _dequant_kernel(packed_ref, norm_ref, s_ref, out_ref, *, levels, bits):
    u = bitpack.unpack_fields(packed_ref[...], s_ref[...], bits=bits)
    s = jnp.float32(levels)
    # mirrors wire.qsgd_rows_unpack's bit-determinism contract: reciprocal
    # constant (no constant division), scale formed first (single
    # multiply), and the norm>0 select (empty rows → exact +0)
    inv_s = jnp.float32(np.float32(1.0) / np.float32(levels))
    norm = norm_ref[...]
    scale = inv_s * norm
    vals = (u.astype(jnp.float32) - s) * scale
    out_ref[...] = jnp.where(norm > 0, vals, jnp.float32(0.0))


@functools.partial(jax.jit, static_argnames=("levels", "interpret"))
def qsgd_quant_pallas(x, *, levels: int, interpret: bool | None = None):
    """x: (rows, 1024) f32 → (packed (rows, 1024·bits/8) u8,
    norms (rows, 1) f32)."""
    if interpret is None:
        interpret = default_interpret()
    rows, lane = x.shape
    assert lane == LANE and rows % BLOCK_ROWS == 0, (rows, lane)
    bits = _bits(levels)
    packed_w = LANE * bits // 8
    grid = (rows // BLOCK_ROWS,)
    kernel = functools.partial(_quant_kernel, levels=levels, bits=bits)
    packed, norms = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((LANE, packed_w), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((BLOCK_ROWS, packed_w), lambda i: (i, 0)),
                   pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, packed_w), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=interpret,
    )(x.astype(jnp.float32), bitpack.pack_matrix(bits))
    return bitpack.to_wire(packed), norms


@functools.partial(jax.jit, static_argnames=("levels", "interpret"))
def qsgd_dequant_pallas(packed, norms, *, levels: int,
                        interpret: bool | None = None):
    """(rows, 1024·bits/8) u8 + (rows, 1) f32 → Q(x) (rows, 1024) f32."""
    if interpret is None:
        interpret = default_interpret()
    rows = packed.shape[0]
    bits = _bits(levels)
    packed_w = LANE * bits // 8
    assert packed.shape[1] == packed_w and rows % BLOCK_ROWS == 0
    grid = (rows // BLOCK_ROWS,)
    kernel = functools.partial(_dequant_kernel, levels=levels, bits=bits)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, packed_w), lambda i: (i, 0)),
                  pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0)),
                  pl.BlockSpec((packed_w, LANE), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), jnp.float32)],
        interpret=interpret,
    )(bitpack.from_wire(packed), norms.reshape(rows, 1).astype(jnp.float32),
      bitpack.spread_matrix(bits))[0]
