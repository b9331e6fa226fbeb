"""The flatten-once kernel layout (``KernelPlan``) + jit'd Pallas wrappers.

Every Pallas kernel in this package operates on one canonical layout: an
f32 matrix of shape ``(rows, 1024)`` (optionally with a leading worker dim,
``(K, rows, 1024)``).  ``KernelPlan`` is the bidirectional mapping between
an arbitrary pytree and that layout:

  * **per-leaf row alignment** — every leaf starts on a fresh row and its
    tail row is zero-padded, so a 1024-row never spans two leaves.  This
    makes the kernel sign-compression *blocks* identical to the per-leaf
    jnp oracle's blocks (``repro.core.compression``, block = 1024), and the
    zero tail keeps elementwise kernels (momentum, gossip AXPY) exact.
  * **flatten once per round** — the fused round engine flattens the
    param/momentum trees at the round boundary, runs the ``lax.scan`` of p
    momentum updates, the gossip mix, and CPD-SGDM's sign pack/unpack all
    on the matrix, and unflattens once at the end (``PDSGDM.kernel_round``).
  * ``row_counts()`` carries each row's true (non-padding) length into the
    sign kernel so tail-block scales match the padding-masked oracle.

``interpret`` defaults to :func:`repro.kernels.default_interpret` —
lazily evaluated: compiled kernels on a TPU, interpret mode (the CPU
correctness harness) anywhere else.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import default_interpret
from repro.kernels import gossip_mix as gm
from repro.kernels import momentum as mom
from repro.kernels import qsgd_quant as qq
from repro.kernels import sign_compress as sc
from repro.kernels import topk_select as tk

__all__ = ["KernelPlan", "PLAN_BLOCK_ROWS", "LANE", "default_interpret",
           "momentum_update_mat", "gossip_mix_mat", "sign_pack",
           "sign_unpack", "topk_pack", "topk_unpack", "qsgd_pack",
           "qsgd_unpack", "row_gather", "row_scatter",
           "momentum_update_tree", "gossip_mix_tree"]

from repro.kernels import LANE  # noqa: E402  (the single lane definition)

# one layout serves every kernel: lcm of the kernels' BLOCK_ROWS
PLAN_BLOCK_ROWS = int(np.lcm.reduce(
    [mom.BLOCK_ROWS, gm.BLOCK_ROWS, sc.BLOCK_ROWS, tk.BLOCK_ROWS,
     qq.BLOCK_ROWS]))


@dataclasses.dataclass(frozen=True)
class _Slot:
    """Where one leaf lives in the (rows, 1024) matrix."""
    shape: Tuple[int, ...]     # per-worker shape (worker dim stripped)
    dtype: object
    size: int                  # prod(shape)
    row_start: int
    n_rows: int                # ceil(size / 1024)


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Flatten-once mapping: pytree ⇄ zero-padded (rows, 1024) f32 matrix.

    ``worker_dim=True`` treats each leaf's leading axis as a stacked worker
    dim that is preserved: ``flatten`` returns ``(K, rows, 1024)`` and the
    per-worker row layout is identical for every worker (this is what the
    DenseComm simulation and the GSPMD-level sharded round both use; inside
    ``shard_map`` the same plan sees K = 1).
    """
    treedef: object
    slots: Tuple[_Slot, ...]
    rows: int
    block_rows: int
    worker_dim: bool

    @classmethod
    def for_tree(cls, tree, *, worker_dim: bool = False,
                 block_rows: int = PLAN_BLOCK_ROWS) -> "KernelPlan":
        """Build a plan from a concrete tree or a ShapeDtypeStruct tree."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        slots = []
        row = 0
        for leaf in leaves:
            shape = tuple(leaf.shape[1:] if worker_dim else leaf.shape)
            size = int(np.prod(shape)) if shape else 1
            assert size > 0, f"empty leaf {leaf.shape} has no kernel rows"
            n_rows = -(-size // LANE)
            slots.append(_Slot(shape, jnp.dtype(leaf.dtype), size, row,
                               n_rows))
            row += n_rows
        rows = -(-row // block_rows) * block_rows
        return cls(treedef, tuple(slots), rows, block_rows, worker_dim)

    # -- geometry ----------------------------------------------------------
    @property
    def n_valid(self) -> int:
        """Total real (non-padding) elements per worker."""
        return sum(s.size for s in self.slots)

    @property
    def used_rows(self) -> int:
        """Rows that carry leaf data (excludes the block-alignment tail).
        This is the wire extent: payloads are sliced to ``used_rows`` before
        a neighbour exchange so alignment padding never ships, keeping the
        actual ppermute bytes equal to the accounted
        ``Σ ceil(size/1024)`` blocks."""
        last = self.slots[-1]
        return last.row_start + last.n_rows

    def pad_wire(self, mat) -> jnp.ndarray:
        """Re-pad a wire-sliced (..., used_rows, d) payload back to the
        kernel row extent (..., rows, d) for the unpack kernel."""
        width = [(0, 0)] * mat.ndim
        width[-2] = (0, self.rows - mat.shape[-2])
        return jnp.pad(mat, width)

    def wire(self, mat) -> jnp.ndarray:
        """Slice a kernel matrix to the ``used_rows`` wire extent before a
        neighbour exchange (inverse of :meth:`pad_wire`).  Identity when
        the block-alignment tail is empty.  The tail is zero on every
        worker and row-local mixing keeps it zero, so the slice is exact —
        overlapped rounds ship their in-flight payload through the same
        extent, keeping stale and synchronous bytes identical."""
        if self.used_rows >= self.rows:
            return mat
        return mat[..., :self.used_rows, :]

    def row_counts(self) -> jnp.ndarray:
        """(rows, 1) f32: valid elements per row (the sign-scale divisor)."""
        c = np.zeros((self.rows,), np.float32)
        for s in self.slots:
            c[s.row_start:s.row_start + s.n_rows] = float(LANE)
            c[s.row_start + s.n_rows - 1] = float(
                s.size - (s.n_rows - 1) * LANE)
        return jnp.asarray(c).reshape(self.rows, 1)

    # -- tree ⇄ matrix -----------------------------------------------------
    def flatten(self, tree) -> jnp.ndarray:
        """(rows, 1024) f32 — or (K, rows, 1024) when ``worker_dim``."""
        leaves = self.treedef.flatten_up_to(tree)
        axis = 1 if self.worker_dim else 0
        parts = []
        for slot, leaf in zip(self.slots, leaves):
            pad = slot.n_rows * LANE - slot.size
            if self.worker_dim:
                flat = jnp.reshape(leaf, (leaf.shape[0], -1)).astype(
                    jnp.float32)
                flat = jnp.pad(flat, ((0, 0), (0, pad)))
                parts.append(flat.reshape(leaf.shape[0], slot.n_rows, LANE))
            else:
                flat = jnp.reshape(leaf, (-1,)).astype(jnp.float32)
                flat = jnp.pad(flat, (0, pad))
                parts.append(flat.reshape(slot.n_rows, LANE))
        mat = jnp.concatenate(parts, axis=axis) if len(parts) > 1 else parts[0]
        tail = self.rows - mat.shape[axis]
        if tail:
            width = [(0, 0)] * mat.ndim
            width[axis] = (0, tail)
            mat = jnp.pad(mat, width)
        return mat

    def unflatten(self, mat, dtype=None):
        """Inverse of :meth:`flatten`; ``dtype`` overrides the recorded
        per-leaf dtypes (e.g. force f32 for momentum/x̂ state trees)."""
        leaves = []
        for slot in self.slots:
            if self.worker_dim:
                block = mat[:, slot.row_start:slot.row_start + slot.n_rows]
                flat = block.reshape(mat.shape[0], -1)[:, :slot.size]
                shape = (mat.shape[0],) + slot.shape
            else:
                block = mat[slot.row_start:slot.row_start + slot.n_rows]
                flat = block.reshape(-1)[:slot.size]
                shape = slot.shape
            leaves.append(flat.reshape(shape).astype(dtype or slot.dtype))
        return self.treedef.unflatten(leaves)


def _rows2d(mat) -> jnp.ndarray:
    """Collapse any leading worker dims onto the row axis: (..., R, 1024) →
    (N·R, 1024).  Valid because R is a multiple of every kernel's
    BLOCK_ROWS, so blocks never straddle two workers."""
    return mat.reshape(-1, LANE)


# --------------------------------------------------------------------- mat ops
def momentum_update_mat(x_mat, m_mat, g_mat, *, mu: float, lr,
                        weight_decay: float = 0.0, nesterov: bool = False,
                        interpret: bool | None = None):
    """Fused SGDM on the kernel layout; accepts (..., rows, 1024)."""
    shape = x_mat.shape
    x_new, m_new = mom.momentum_update(
        _rows2d(x_mat), _rows2d(m_mat), _rows2d(g_mat), lr, mu=mu,
        wd=weight_decay, nesterov=nesterov, interpret=interpret)
    return x_new.reshape(shape), m_new.reshape(shape)


def gossip_mix_mat(mats, weights, *, interpret: bool | None = None):
    """Fused W-row AXPY of n aligned matrices; accepts (..., rows, 1024)."""
    shape = mats[0].shape
    out = gm.gossip_mix(tuple(_rows2d(m) for m in mats),
                        weights=tuple(float(w) for w in weights),
                        interpret=interpret)
    return out.reshape(shape)


def delayed_mix_mat(x_mat, dx_mat, *, interpret: bool | None = None):
    """Land an overlapped round's one-round-stale gossip correction
    matrix-to-matrix on the flatten-once layout: ``x + dx`` as the fused
    W-row AXPY, where ``dx = gate·(W̃·buf − buf)`` was formed at round
    start from the in-flight payload.  The staleness gate is folded into
    ``dx`` by an elementwise multiply because the AXPY kernel's weights
    must stay static floats."""
    return gossip_mix_mat((x_mat, dx_mat), (1.0, 1.0), interpret=interpret)


def sign_pack(x_mat, counts=None, *, interpret: bool | None = None):
    """(..., rows, 1024) → (packed (..., rows, 128) u8, scales (..., rows, 1)).

    ``counts``: per-row valid lengths from :meth:`KernelPlan.row_counts`,
    tiled across any leading worker dims automatically.
    """
    lead, rows = x_mat.shape[:-2], x_mat.shape[-2]
    packed, scales = sc.sign_pack_pallas(_rows2d(x_mat),
                                         _tile_counts(counts, rows, lead),
                                         interpret=interpret)
    return (packed.reshape(lead + (rows, sc.PACKED)),
            scales.reshape(lead + (rows, 1)))


def sign_unpack(packed, scales, *, interpret: bool | None = None):
    """Inverse of :func:`sign_pack`: (..., rows, 1024) f32 = scale·sign."""
    lead, rows = packed.shape[:-2], packed.shape[-2]
    out = sc.sign_unpack_pallas(packed.reshape(-1, sc.PACKED),
                                scales.reshape(-1, 1), interpret=interpret)
    return out.reshape(lead + (rows, LANE))


def _tile_counts(counts, rows, lead):
    """Normalize a (rows,)/(rows, 1) counts operand and tile it across any
    leading worker dims (the per-row layout is identical per worker)."""
    if counts is None:
        return None
    c = jnp.asarray(counts, jnp.float32).reshape(rows, 1)
    if lead:
        c = jnp.tile(c, (int(np.prod(lead)), 1))
    return c


def topk_pack(x_mat, counts=None, *, fraction: float,
              interpret: bool | None = None):
    """(..., rows, 1024) → (idx (..., rows, W) i32, vals (..., rows, W) f32)
    with W = ceil(fraction·1024) — the blockwise top-k wire payload.

    ``counts``: per-row valid lengths (:meth:`KernelPlan.row_counts`); the
    active slot count per row is ``ceil(fraction · count)``.
    """
    lead, rows = x_mat.shape[:-2], x_mat.shape[-2]
    idx, vals = tk.topk_select_pallas(
        _rows2d(x_mat), _tile_counts(counts, rows, lead),
        fraction=fraction, interpret=interpret)
    w = idx.shape[-1]
    return (idx.reshape(lead + (rows, w)), vals.reshape(lead + (rows, w)))


def topk_unpack(idx, vals, *, interpret: bool | None = None):
    """Inverse scatter of :func:`topk_pack` → (..., rows, 1024) f32."""
    lead, rows, w = idx.shape[:-2], idx.shape[-2], idx.shape[-1]
    out = tk.topk_scatter_pallas(idx.reshape(-1, w), vals.reshape(-1, w),
                                 interpret=interpret)
    return out.reshape(lead + (rows, LANE))


def qsgd_pack(x_mat, *, levels: int, interpret: bool | None = None):
    """(..., rows, 1024) → (levels (..., rows, 1024·bits/8) u8,
    norms (..., rows, 1) f32) — the blockwise QSGD wire payload."""
    lead, rows = x_mat.shape[:-2], x_mat.shape[-2]
    packed, norms = qq.qsgd_quant_pallas(_rows2d(x_mat), levels=levels,
                                         interpret=interpret)
    return (packed.reshape(lead + (rows, packed.shape[-1])),
            norms.reshape(lead + (rows, 1)))


def qsgd_unpack(packed, norms, *, levels: int,
                interpret: bool | None = None):
    """Inverse of :func:`qsgd_pack`: (..., rows, 1024) f32."""
    lead, rows = packed.shape[:-2], packed.shape[-2]
    out = qq.qsgd_dequant_pallas(packed.reshape(-1, packed.shape[-1]),
                                 norms.reshape(-1, 1), levels=levels,
                                 interpret=interpret)
    return out.reshape(lead + (rows, LANE))


def row_gather(x_mat, idx, counts=None, *, interpret: bool | None = None):
    """(..., rows, 1024) + idx (..., S) i32 → gathered (..., S, 1024) f32 —
    the sparse wire's payload builder (``repro.kernels.row_gather``).

    ``counts``: per-row valid lengths (:meth:`KernelPlan.row_counts`,
    shared across workers); gathered rows keep only their valid prefix.
    Scalar-prefetch grids cannot be vmapped, so leading worker dims run as
    a static Python loop — K kernel launches, one per simulated worker
    (the sharded production path has no lead dim).
    """
    from repro.kernels import row_gather as rg
    if counts is not None:
        counts = jnp.asarray(counts, jnp.float32).reshape(x_mat.shape[-2])
    lead = x_mat.shape[:-2]
    if not lead:
        return rg.row_gather_pallas(x_mat, idx, counts, interpret=interpret)
    k = int(np.prod(lead))
    xs = x_mat.reshape((k,) + x_mat.shape[-2:])
    ids = idx.reshape(k, idx.shape[-1])
    out = jnp.stack([rg.row_gather_pallas(xs[i], ids[i], counts,
                                          interpret=interpret)
                     for i in range(k)])
    return out.reshape(lead + out.shape[-2:])


def row_scatter(idx, vals, *, rows: int, interpret: bool | None = None):
    """Inverse of :func:`row_gather`: idx (..., S) + vals (..., S, 1024) →
    (..., rows, 1024) f32 with ``out[idx[j]] += vals[j]`` per worker and
    untouched rows exactly 0."""
    from repro.kernels import row_gather as rg
    lead = vals.shape[:-2]
    if not lead:
        return rg.row_scatter_pallas(idx, vals, rows=rows,
                                     interpret=interpret)
    k = int(np.prod(lead))
    ids = idx.reshape(k, idx.shape[-1])
    vs = vals.reshape((k,) + vals.shape[-2:])
    out = jnp.stack([rg.row_scatter_pallas(ids[i], vs[i], rows=rows,
                                           interpret=interpret)
                     for i in range(k)])
    return out.reshape(lead + out.shape[-2:])


# -------------------------------------------------------------------- tree ops
def momentum_update_tree(params, m, grads, *, mu: float, lr,
                         weight_decay: float = 0.0, nesterov: bool = False,
                         interpret: bool | None = None):
    """Fused SGDM over a whole pytree (one kernel launch).

    Per-call flatten/unflatten — the per-step debugging path.  The fused
    round (``PDSGDM.kernel_round``) flattens once per *round* instead.
    """
    plan = KernelPlan.for_tree(params)
    x_new, m_new = momentum_update_mat(
        plan.flatten(params), plan.flatten(m), plan.flatten(grads),
        mu=mu, lr=lr, weight_decay=weight_decay, nesterov=nesterov,
        interpret=interpret)
    return plan.unflatten(x_new), plan.unflatten(m_new, dtype=jnp.float32)


def gossip_mix_tree(trees, weights, *, interpret: bool | None = None):
    """Fused W-row mixing of n aligned pytrees (self + neighbours)."""
    plan = KernelPlan.for_tree(trees[0])
    out = gossip_mix_mat(tuple(plan.flatten(t) for t in trees), weights,
                         interpret=interpret)
    return plan.unflatten(out)
