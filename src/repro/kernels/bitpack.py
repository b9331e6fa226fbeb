"""Byte packing of small integer fields inside a Pallas TPU kernel.

The sign and QSGD kernels pack ``8/bits`` fields of ``bits`` bits into one
byte: byte ``j`` of a row holds elements ``vpb·j … vpb·j + vpb − 1``, the
first in the low bits (``docs/WIRE_FORMATS.md``).  Mosaic has no unsigned
reductions, no u8 ⇄ f32 casts and no lane-splitting reshape, so the
packing runs as two exact MXU products and int32 lane arithmetic:

  * pack   — ``fields (BR, LANE) @ P (LANE, LANE/vpb)`` with
             ``P[vpb·j + t, j] = 2^(bits·t)``: each output lane is the byte's
             weighted sum.  Fields, weights and sums are integers ≤ 255, so
             bf16 operands with f32 accumulation are exact.
  * unpack — ``bytes (BR, LANE/vpb) @ S (LANE/vpb, LANE)`` with
             ``S[j, vpb·j + t] = 1`` copies each byte onto its ``vpb``
             lanes; a per-lane shift and mask then extracts the field.

Kernels exchange the packed bytes as ``int8`` (which Mosaic loads, stores
and casts); the wrappers bitcast to and from the ``uint8`` wire dtype,
which moves no data.  The payload shipped is the same u8 array.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import LANE

__all__ = ["pack_matrix", "spread_matrix", "pack_fields", "unpack_fields",
           "to_wire", "from_wire"]


def pack_matrix(bits: int) -> jnp.ndarray:
    """(LANE, LANE·bits/8) bf16: lane ``l`` → byte ``l // vpb`` at weight
    ``2^(bits · (l % vpb))``."""
    vpb = 8 // bits
    lanes = np.arange(LANE)
    w = np.zeros((LANE, LANE // vpb), np.float32)
    w[lanes, lanes // vpb] = 2.0 ** (bits * (lanes % vpb))
    return jnp.asarray(w, jnp.bfloat16)


def spread_matrix(bits: int) -> jnp.ndarray:
    """(LANE·bits/8, LANE) bf16 0/1: byte ``j`` → lanes ``vpb·j …``."""
    vpb = 8 // bits
    lanes = np.arange(LANE)
    s = np.zeros((LANE // vpb, LANE), np.float32)
    s[lanes // vpb, lanes] = 1.0
    return jnp.asarray(s, jnp.bfloat16)


def pack_fields(fields, w, *, bits: int):
    """In-kernel: (BR, LANE) f32 integer fields in [0, 2^bits) → (BR,
    LANE·bits/8) int8 bytes (two's-complement view of the u8 byte)."""
    if bits == 8:
        byte = fields.astype(jnp.int32)
    else:
        byte = jnp.dot(fields.astype(jnp.bfloat16), w,
                       preferred_element_type=jnp.float32).astype(jnp.int32)
    # 0..255 → −128..127 before the narrowing cast, so it is exact
    return (byte - ((byte >> 7) << 8)).astype(jnp.int8)


def unpack_fields(packed, s, *, bits: int):
    """In-kernel inverse of :func:`pack_fields`: (BR, LANE·bits/8) int8 →
    (BR, LANE) int32 fields."""
    byte = packed.astype(jnp.int32) & 0xFF
    if bits == 8:
        return byte
    vpb = 8 // bits
    spread = jnp.dot(byte.astype(jnp.bfloat16), s,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    lanes = jax.lax.broadcasted_iota(jnp.int32, spread.shape, 1)
    return (spread >> (bits * (lanes % vpb))) & ((1 << bits) - 1)


def to_wire(packed_i8):
    """Kernel int8 bytes → the u8 wire payload (a bitcast, no data moves)."""
    return jax.lax.bitcast_convert_type(packed_i8, jnp.uint8)


def from_wire(packed_u8):
    """u8 wire payload → the kernel's int8 view (a bitcast)."""
    return jax.lax.bitcast_convert_type(packed_u8, jnp.int8)
