"""Sparse-row gather/scatter kernels: the data movers of the sparse wire.

The sparse-rows codec (``repro.core.wire.SparseRowsCodec``) ships only the
*touched* rows of the flatten-once ``(rows, LANE)`` layout: an index vector
plus a compact ``(S, LANE)`` payload matrix, ``S`` = the static per-leaf
row budget summed over leaves.  These two kernels are its hot spots:

  * ``row_gather_pallas``  — x (rows, LANE) f32 + idx (S,) i32 →
                             payload (S, LANE) f32, ``payload[j] =
                             x[idx[j]]`` with lanes ≥ the row's true
                             length (``counts``) zeroed (counts-aware: a
                             gathered tail row ships exactly its valid
                             prefix even if the source held junk).
  * ``row_scatter_pallas`` — inverse: out (rows, LANE) f32 with
                             ``out[idx[j]] += payload[j]`` and every
                             untouched row exactly 0.

Both are scalar-prefetch kernels (``pltpu.PrefetchScalarGridSpec``): the
index vector is prefetched to SMEM and drives the ``BlockSpec`` index_map,
so each grid step DMAs exactly one touched row — the canonical TPU sparse
gather idiom.  The kernels see the matrix as a ``(rows, 1, LANE)`` view
with a squeezed leading block dim: a ``(1, LANE)`` block of a 2-D array
breaks Mosaic's (8, 128) tiling rule, a ``(1, LANE)`` trailing slab of the
3-D view does not.  The gather's row lengths ride along as a second
prefetched scalar vector.  The scatter accumulates into a
zero-initialized output via ``input_output_aliases`` (the zeros operand
*is* the output buffer), so rows no grid step visits stay exactly 0.

Contract: within one payload the indices are **distinct** (the codec
selects per-leaf top-norm rows — distinct within a leaf, disjoint row
segments across leaves) and sorted ascending, so the scatter is a pure
permutation write and bit-exact against the jnp oracle
(``repro.kernels.ref.row_gather_ref`` / ``row_scatter_ref``); duplicate
indices would make the read-accumulate-write order visible and are not
supported.  Kernels move bytes, they never transform values — which is
what makes the kernel wire bit-identical to the per-leaf jnp codec.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANE, default_interpret

__all__ = ["row_gather_pallas", "row_scatter_pallas", "LANE"]


def _gather_kernel(idx_ref, cnt_ref, x_ref, out_ref):
    del idx_ref  # consumed by the BlockSpec index_map (scalar prefetch)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    valid = lanes < cnt_ref[pl.program_id(0)]
    out_ref[...] = jnp.where(valid, x_ref[...], jnp.float32(0.0))


def _scatter_kernel(idx_ref, base_ref, val_ref, out_ref):
    del idx_ref  # consumed by the BlockSpec index_maps (scalar prefetch)
    out_ref[...] = base_ref[...] + val_ref[...]


def _row_block(index_map):
    """One (1, LANE) row of a (rows, 1, LANE) view, leading dim squeezed."""
    return pl.BlockSpec((None, 1, LANE), index_map)


@functools.partial(jax.jit, static_argnames=("interpret",))
def row_gather_pallas(x, idx, counts=None, *,
                      interpret: bool | None = None):
    """x (rows, LANE) f32 + idx (S,) i32 → gathered (S, LANE) f32.

    ``counts``: per-row true lengths (``KernelPlan.row_counts``); the
    gathered row keeps only its valid prefix.  None = full rows.
    """
    if interpret is None:
        interpret = default_interpret()
    rows, lane = x.shape
    assert lane == LANE, (rows, lane)
    (s,) = idx.shape
    idx = idx.astype(jnp.int32)
    if counts is None:
        cnt_g = jnp.full((s,), LANE, jnp.int32)
    else:
        cnt_g = jnp.take(jnp.asarray(counts, jnp.float32).reshape(rows),
                         idx, axis=0).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[_row_block(lambda j, idx_ref, cnt_ref: (idx_ref[j], 0, 0))],
        out_specs=_row_block(lambda j, idx_ref, cnt_ref: (j, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, 1, LANE), jnp.float32),
        interpret=interpret,
    )(idx, cnt_g, x.astype(jnp.float32).reshape(rows, 1, LANE))
    return out.reshape(s, LANE)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def row_scatter_pallas(idx, vals, *, rows: int,
                       interpret: bool | None = None):
    """idx (S,) i32 + vals (S, LANE) f32 → out (rows, LANE) f32 with
    ``out[idx[j]] += vals[j]`` and untouched rows exactly 0."""
    if interpret is None:
        interpret = default_interpret()
    s, lane = vals.shape
    assert lane == LANE and idx.shape == (s,), (idx.shape, vals.shape)
    idx = idx.astype(jnp.int32)
    base = jnp.zeros((rows, 1, LANE), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s,),
        in_specs=[_row_block(lambda j, idx_ref: (idx_ref[j], 0, 0)),
                  _row_block(lambda j, idx_ref: (j, 0, 0))],
        out_specs=_row_block(lambda j, idx_ref: (idx_ref[j], 0, 0)),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1, LANE), jnp.float32),
        # the zeros operand is the output buffer: unvisited rows stay 0
        input_output_aliases={1: 0},
        interpret=interpret,
    )(idx, base, vals.astype(jnp.float32).reshape(s, 1, LANE))
    return out.reshape(rows, LANE)
