"""Sign compression kernels: blockwise scaled-sign + 8-signs/byte bit-pack.

CPD-SGDM's per-round hot spot.  Two kernels:

  * ``sign_pack_kernel``   — x (rows, 1024) f32 → packed (rows, 128) uint8
                             + scales (rows, 1) f32 (mean |x| per row).
  * ``sign_unpack_kernel`` — inverse: Q(x) = scale · sign(x).

One *row* is one scale block (= ``compression.SIGN_BLOCK`` = 1024 elements =
8 f32 vregs), so the kernel's row dim maps directly onto the pure-jnp
oracle's block dim and the packed row is exactly one 128-lane uint8 vreg.

Padding contract: the flatten-once layout (``ops.KernelPlan``) zero-pads
each leaf's tail row, so a row may hold fewer than 1024 *valid* elements.
The per-row true length is threaded in as the ``counts`` operand ((rows, 1)
f32) and divides the |x| sum — giving exactly the padding-masked scale the
jnp oracle (``repro.core.compression.sign_pack``) computes.  Without it the
tail block's scale would be deflated by ``n_valid/1024``.  Rows that are
pure alignment padding carry count 0 and produce scale 0.

The bit-gather is an exact MXU product with a constant (1024, 128)
weight matrix, and the unpack its transpose plus a per-lane shift
(``repro.kernels.bitpack``): Mosaic has no u8 arithmetic or lane-splitting
reshape.  Bit-exact against ``repro.core.compression`` and the matrix
oracle ``repro.kernels.ref.sign_pack_rows_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import LANE, bitpack, default_interpret

__all__ = ["sign_pack_pallas", "sign_unpack_pallas", "LANE", "PACKED",
           "BLOCK_ROWS"]

# LANE elements per scale block (== compression.SIGN_BLOCK)
PACKED = LANE // 8   # bytes per packed row
BLOCK_ROWS = 256


def _pack_kernel(x_ref, cnt_ref, w_ref, packed_ref, scale_ref):
    x = x_ref[...]                                   # (BR, 1024) f32
    cnt = cnt_ref[...]                               # (BR, 1) f32 valid count
    # padded entries are exactly 0, so the |x| row sum already excludes
    # them; only the divisor needs the true length (bit-exact vs the
    # padding-masked oracle)
    scale_ref[...] = (jnp.sum(jnp.abs(x), axis=1, keepdims=True)
                      / jnp.maximum(cnt, 1.0))
    packed_ref[...] = bitpack.pack_fields(
        (x >= 0).astype(jnp.float32), w_ref[...], bits=1)


def _unpack_kernel(packed_ref, scale_ref, s_ref, out_ref):
    bits = bitpack.unpack_fields(packed_ref[...], s_ref[...], bits=1)
    signs = bits.astype(jnp.float32) * 2.0 - 1.0
    out_ref[...] = signs * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_pack_pallas(x, counts=None, *, interpret: bool | None = None):
    """x: (rows, 1024) f32 → (packed (rows,128) u8, scales (rows,1) f32).

    ``counts`` ((rows,) or (rows, 1) f32) is the number of *valid* (non-
    padding) elements per row; omitted means every row is full.
    """
    if interpret is None:
        interpret = default_interpret()
    rows, lane = x.shape
    assert lane == LANE and rows % BLOCK_ROWS == 0, (rows, lane)
    if counts is None:
        counts = jnp.full((rows, 1), float(LANE), jnp.float32)
    grid = (rows // BLOCK_ROWS,)
    packed, scales = pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0)),
                  pl.BlockSpec((LANE, PACKED), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((BLOCK_ROWS, PACKED), lambda i: (i, 0)),
                   pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, PACKED), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=interpret,
    )(x.astype(jnp.float32), counts.reshape(rows, 1).astype(jnp.float32),
      bitpack.pack_matrix(1))
    return bitpack.to_wire(packed), scales


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_unpack_pallas(packed, scales, *, interpret: bool | None = None):
    """(rows,128) u8 + (rows,1) f32 → Q(x) (rows, 1024) f32."""
    if interpret is None:
        interpret = default_interpret()
    rows = packed.shape[0]
    assert packed.shape[1] == PACKED and rows % BLOCK_ROWS == 0
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_ROWS, PACKED), lambda i: (i, 0)),
                  pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0)),
                  pl.BlockSpec((PACKED, LANE), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), jnp.float32)],
        interpret=interpret,
    )(bitpack.from_wire(packed), scales.reshape(rows, 1).astype(jnp.float32),
      bitpack.spread_matrix(1))[0]
