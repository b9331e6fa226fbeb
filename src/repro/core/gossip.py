"""Gossip communication backends.

Two implementations of the same mixing semantics ``x⁽ᵏ⁾ ← Σⱼ w_kj x⁽ʲ⁾``:

* :class:`DenseComm` — single-process simulation.  Every pytree leaf carries a
  leading worker dimension of size K and mixing is an einsum with the dense
  mixing matrix ``W``.  This is the mathematically-literal form of the paper's
  Eq. (4)/(17) and is what the convergence experiments and unit tests run on
  (CPU, any K).

* :class:`ShardedComm` — production backend, used *inside* ``shard_map``.
  Each device holds its worker's (model-parallel shard of the) parameters
  without a worker dimension; neighbour exchange is ``jax.lax.ppermute``
  (HLO ``collective-permute``) along the named worker mesh axes.  Circulant
  (ring) and Kronecker-of-circulant (torus) topologies map each weighted
  shift to one ppermute; the fully-connected topology maps to ``pmean``.

Both expose::

    mix(tree, r=None)        -> tree            # Σⱼ w_kj x⁽ʲ⁾ (round r's W)
    shift_views(tree)        -> {(axis,shift): tree}   # raw neighbour tensors
    weights()                -> {(axis,shift): w}

``shift_views`` / ``receive_payload`` are what CPD-SGDM uses to move the
*compressed* wire-codec payload (``repro.core.wire``) between neighbours:
a payload is a plain dict of arrays, and each array crosses the wire as
one ``ppermute`` — uint8 sign bits, int32 top-k indices, f32 values —
so the HLO collective carries exactly the codec's bytes, for every
compressor, not just sign.

Either backend can be built from a single :class:`Topology` (static graph)
or from a :class:`TopologySchedule` (time-varying graph): ``mix`` then
selects round ``r``'s mixing matrix *inside* the jitted computation —
DenseComm indexes a stacked ``(T, K, K)`` weight tensor with the traced
round index; ShardedComm precomputes every round's ppermute program and
selects it with ``lax.switch`` — so the fused round engine never retraces
as the graph changes.  ``backend.topology`` remains the round-0 topology
(shapes / worker count); per-round structure is ``backend.topology_at(r)``.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topology import (MembershipSchedule, Topology,
                                 TopologySchedule, active_edge_count,
                                 hierarchical_inter_shifts,
                                 hierarchical_self_weight, masked_matrix)

__all__ = ["DenseComm", "ShardedComm", "HierarchicalComm", "CommBackend",
           "gossip_bytes_per_round", "hier_bytes_per_round",
           "worker_mask_like"]

ShiftKey = Tuple[int, int]  # (topology axis, shift)

# dtypes the gossip wire can ship the uncompressed payload in; decoding is
# always an f32 upcast before the weighted accumulation
_WIRE_DTYPES = ("float32", "bfloat16")


def worker_mask_like(mask, leaf):
    """Reshape a (K,) worker mask so it broadcasts against a worker-stacked
    leaf of shape (K, ...)."""
    return mask.reshape((mask.shape[0],) + (1,) * (leaf.ndim - 1))


def _inter_factor(top: Topology) -> np.ndarray:
    """The (n_nodes, n_nodes) inter-level factor of a hierarchical
    topology: W_hier = R ⊗ (1/m)11ᵀ, rebuilt from the axis-0 shifts."""
    n = int(top.axis_sizes[0])
    R = np.eye(n) * hierarchical_self_weight(top)
    for (sh, w) in hierarchical_inter_shifts(top):
        for i in range(n):
            R[i, (i + sh) % n] += w
    return R


class CommBackend:
    topology: Topology
    schedule: Optional[TopologySchedule] = None
    membership: Optional[MembershipSchedule] = None
    wire_dtype: str = "float32"

    @property
    def wire_itemsize(self) -> int:
        """Bytes per element of the uncompressed gossip payload."""
        return 2 if self.wire_dtype == "bfloat16" else 4

    def _check_wire_dtype(self):
        if self.wire_dtype not in _WIRE_DTYPES:
            raise ValueError(
                f"wire_dtype {self.wire_dtype!r} not in {_WIRE_DTYPES}")

    @property
    def period(self) -> int:
        """Schedule period T (1 for a static topology)."""
        return self.schedule.period if self.schedule is not None else 1

    @property
    def round_cycle(self) -> int:
        """Joint period of the topology schedule and the membership
        schedule — the number of rounds after which both the graph and the
        liveness pattern repeat.  Byte accounting and per-round mixing
        programs cycle over this, not ``period``."""
        M = self.membership.period if self.membership is not None else 1
        return math.lcm(self.period, M)

    def topology_at(self, r: int) -> Topology:
        """Topology of round ``r`` (python int; wraps modulo the period)."""
        if self.schedule is not None:
            return self.schedule.at(r)
        return self.topology

    def active_at(self, r: int) -> np.ndarray:
        """(K,) bool — workers exchanging in round ``r`` (all True without
        a membership schedule)."""
        if self.membership is None:
            return np.ones(self.topology.n_workers, dtype=bool)
        return self.membership.active_at(r)

    def effective_matrix(self, r: int) -> np.ndarray:
        """The K×K mixing matrix this backend executes in round ``r``,
        membership mask applied — what chaos tests and the jaxpr contract
        checker assert row-stochasticity / dead-column-zero against."""
        top = self.topology_at(r)
        act = self.active_at(r)
        if act.all():
            return np.asarray(top.W)   # host: introspection  # lint: allow
        return masked_matrix(top, act)

    def effective_stale_matrix(self, r: int) -> np.ndarray:
        """The K×K matrix the *overlapped* delivery of round ``r``'s payload
        executes: round ``r``'s topology masked by the liveness of the
        delivery round ``r+1`` — a payload from a worker that died while in
        flight is dropped and its mass renormalized back to the receivers'
        self-weight (dead receivers keep the identity row).  Equal to
        :meth:`effective_matrix` without a membership schedule."""
        top = self.topology_at(r)
        act = self.active_at(r + 1)
        if act.all():
            return np.asarray(top.W)   # host: introspection  # lint: allow
        return masked_matrix(top, act)

    def edges_per_worker(self, r: int = 0):
        """Mean directed exchanges per worker in round ``r``: the topology
        degree without membership (int — exact legacy accounting), else
        ``active_edge_count / K`` (float; dead edges ship zero bytes)."""
        top = self.topology_at(r)
        if self.membership is None:
            return top.degree
        act = self.active_at(r)
        if act.all():
            return top.degree
        return active_edge_count(top, act) / top.n_workers

    def mix(self, tree, r=None):
        raise NotImplementedError

    def stale_mix(self, tree, r=None):
        """Mix of a one-round-stale snapshot under round ``r``'s topology
        and the *delivery* round's (``r+1``) liveness — the overlapped-round
        counterpart of :meth:`mix` (see :meth:`effective_stale_matrix`).
        Identical to ``mix`` without a membership schedule."""
        raise NotImplementedError

    def shift_views(self, tree) -> Dict[ShiftKey, object]:
        raise NotImplementedError

    def weights(self) -> Dict[ShiftKey, float]:
        return {(ax, sh): w for (ax, sh, w) in self.topology.shifts}

    def nonself_shifts(self):
        return [(ax, sh, w) for (ax, sh, w) in self.topology.shifts if sh != 0]

    def self_weight(self) -> float:
        return float(sum(w for (_, sh, w) in self.topology.shifts if sh == 0))

    def _resolve(self, first):
        """Normalize the first constructor arg: a schedule sets both the
        schedule and the round-0 ``topology`` (shape/worker-count anchor)."""
        if isinstance(first, TopologySchedule):
            self.schedule = first
            self.topology = first.at(0)
        else:
            self.schedule = None
            self.topology = first


def _mm(w, x):
    """f32 mixing product at full precision: a TPU's default f32 matmul
    rounds its operands to bf16 (W = 1/3 becomes 0.33398), which would
    make the simulation drift from the sharded AXPY gossip."""
    return jnp.matmul(w, x, precision=jax.lax.Precision.HIGHEST)


@dataclasses.dataclass
class DenseComm(CommBackend):
    """Simulation backend: leaves are worker-stacked, leading dim K.

    Accepts a ``Topology`` or a ``TopologySchedule``; with a schedule the
    per-round W is selected by indexing the stacked ``(T, K, K)`` weight
    tensor with the (traced) round index — one trace serves every round.
    """

    topology: Topology  # or a TopologySchedule at construction
    membership: Optional[MembershipSchedule] = None
    wire_dtype: str = "float32"

    def __post_init__(self):
        self._resolve(self.topology)
        self._check_wire_dtype()
        self._W = jnp.asarray(self.topology.W, dtype=jnp.float32)
        self._Ws = (jnp.asarray(self.schedule.stacked_W(), dtype=jnp.float32)
                    if self.schedule is not None else None)
        # Hierarchical rounds mix through the factored form — exact intra
        # mean, then the (n, n) inter factor — mirroring the sharded
        # execution (and its bf16 wire point) instead of the flat W matmul.
        tops = (self.schedule.topologies if self.schedule is not None
                else (self.topology,))
        if (all(t.name == "hierarchical" for t in tops)
                and self.membership is None):
            self._hier_m = int(self.topology.axis_sizes[1])
            self._hier_R = jnp.asarray(
                np.stack([_inter_factor(t) for t in tops]), jnp.float32)
        else:
            self._hier_m = 0
            self._hier_R = None
        if self.membership is not None:
            self.membership.validate()
            if self.membership.n_workers != self.topology.n_workers:
                raise ValueError(
                    f"membership K={self.membership.n_workers} != topology "
                    f"K={self.topology.n_workers}")
            # Stack the masked matrix of every round in the joint cycle so
            # a traced round index selects it — one trace serves every
            # liveness pattern.  All-active rounds reuse the topology's own
            # W bit-for-bit.
            Lc = self.round_cycle
            Wm, act = [], []
            for l in range(Lc):
                a = self.membership.active_at(l)
                top = self.topology_at(l)
                Wm.append(np.asarray(top.W) if a.all()   # lint: allow
                          else masked_matrix(top, a))
                act.append(a)
            self._Wm = jnp.asarray(np.stack(Wm), dtype=jnp.float32)
            self._act = jnp.asarray(np.stack(act))
            # Overlapped delivery: round l's payload exchanged under the
            # *next* round's liveness (a worker that died with a payload in
            # flight drops out of the mix, renormalized) — same joint cycle.
            self._Wov = jnp.asarray(
                np.stack([self.effective_stale_matrix(l)
                          for l in range(Lc)]), dtype=jnp.float32)
        else:
            self._Wm = None
            self._act = None
            self._Wov = None

    def _W_at(self, r):
        if self.membership is not None:
            if self._Wm.shape[0] == 1:
                return self._Wm[0]
            if r is None:
                raise ValueError(
                    "DenseComm with a MembershipSchedule needs the round "
                    "index: mix(tree, r=...)")
            return self._Wm[jnp.mod(jnp.asarray(r), self._Wm.shape[0])]
        if self.schedule is None or self.schedule.period == 1:
            return self._W
        if r is None:
            raise ValueError(
                "DenseComm with a TopologySchedule needs the round index: "
                "mix(tree, r=...)")
        return self._Ws[jnp.mod(jnp.asarray(r), self.schedule.period)]

    def active_mask(self, r):
        """(K,) bool under a traced round index; None without membership.
        Optimizers use it to pin a straggler's auxiliary state (e.g. MT's
        tracking variable) instead of applying a phantom self-exchange."""
        if self.membership is None:
            return None
        if self._act.shape[0] == 1:
            return self._act[0]
        if r is None:
            raise ValueError(
                "DenseComm with a MembershipSchedule needs the round "
                "index: active_mask(r=...)")
        return self._act[jnp.mod(jnp.asarray(r), self._act.shape[0])]

    def mix(self, tree, r=None):
        if self._hier_R is not None:
            return self._apply_hier(self._hier_R_at(r), tree)
        return self._apply_W(self._W_at(r), tree)

    def _hier_R_at(self, r):
        if self._hier_R.shape[0] == 1:
            return self._hier_R[0]
        if r is None:
            raise ValueError(
                "DenseComm with a TopologySchedule needs the round index: "
                "mix(tree, r=...)")
        return self._hier_R[jnp.mod(jnp.asarray(r), self._hier_R.shape[0])]

    def _apply_hier(self, R, tree):
        """Factored hierarchical round: exact intra mean, inter factor on
        the node means, result rebroadcast in-node — the same program the
        sharded backend executes (``pmean`` → leader gossip → ``psum``),
        so the bf16 wire point sits exactly where the slow link is."""
        m = self._hier_m

        def _mix(leaf):
            K = leaf.shape[0]
            assert K == self.topology.n_workers, (
                f"leaf worker dim {K} != K={self.topology.n_workers}")
            flat = leaf.reshape(K // m, m, -1).astype(jnp.float32)
            xa = flat.mean(axis=1)
            if self.wire_dtype == "bfloat16":
                diag = jnp.diagonal(R)
                wire = xa.astype(jnp.bfloat16).astype(jnp.float32)
                mixed = diag[:, None] * xa + _mm(R - jnp.diag(diag), wire)
            else:
                mixed = _mm(R, xa)
            out = jnp.broadcast_to(mixed[:, None, :], flat.shape)
            return out.astype(leaf.dtype).reshape(leaf.shape)

        return jax.tree_util.tree_map(_mix, tree)

    def stale_mix(self, tree, r=None):
        if self.membership is None:
            return self.mix(tree, r=r)
        if self._Wov.shape[0] == 1:
            return self._apply_W(self._Wov[0], tree)
        if r is None:
            raise ValueError(
                "DenseComm with a MembershipSchedule needs the round "
                "index: stale_mix(tree, r=...)")
        W = self._Wov[jnp.mod(jnp.asarray(r), self._Wov.shape[0])]
        return self._apply_W(W, tree)

    def _apply_W(self, W, tree):
        def _mix(leaf):
            K = leaf.shape[0]
            assert K == self.topology.n_workers, (
                f"leaf worker dim {K} != K={self.topology.n_workers}")
            flat = leaf.reshape(K, -1).astype(jnp.float32)
            if self.wire_dtype == "bfloat16":
                # what ships is the off-diagonal payload: each worker keeps
                # its own value at full precision and receives neighbours'
                # values bf16-rounded, accumulating in f32 — the sharded
                # backend's wire semantics, simulated
                diag = jnp.diagonal(W)
                wire = flat.astype(jnp.bfloat16).astype(jnp.float32)
                out = diag[:, None] * flat + _mm(W - jnp.diag(diag), wire)
            else:
                out = _mm(W, flat)
            return out.astype(leaf.dtype).reshape(leaf.shape)

        return jax.tree_util.tree_map(_mix, tree)

    def _roll(self, leaf, axis: int, shift: int):
        """Return the view where worker k sees worker (k+shift)'s value."""
        grid = self.topology.axis_sizes
        K = leaf.shape[0]
        g = leaf.reshape(grid + leaf.shape[1:])
        # worker index along `axis` receives from (idx + shift) -> roll by -shift
        g = jnp.roll(g, -shift, axis=axis)
        return g.reshape((K,) + leaf.shape[1:])

    def shift_views(self, tree) -> Dict[ShiftKey, object]:
        out = {}
        for (ax, sh, _w) in self.nonself_shifts():
            out[(ax, sh)] = jax.tree_util.tree_map(
                lambda leaf: self._roll(leaf, ax, sh), tree)
        return out


@dataclasses.dataclass
class ShardedComm(CommBackend):
    """Production backend: ppermute along named mesh axes, inside shard_map.

    ``axis_names[i]`` is the mesh axis carrying topology axis ``i``.

    Accepts a ``Topology`` or a ``TopologySchedule``.  With a schedule every
    round's ppermute program (source→dest pairs per weighted exchange) is
    precomputed at construction; ``mix(tree, r)`` selects the round's
    program with ``lax.switch`` on the traced round index, so all T
    collective patterns live in one compiled executable — no retracing as
    the graph changes round to round.
    """

    topology: Topology  # or a TopologySchedule at construction
    axis_names: Tuple[str, ...]
    membership: Optional[MembershipSchedule] = None
    wire_dtype: str = "float32"

    def __post_init__(self):
        self._resolve(self.topology)
        self._check_wire_dtype()
        for top in (self.schedule.topologies if self.schedule is not None
                    else (self.topology,)):
            # 'complete' mixes via pmean over all named axes — grid unused.
            if top.name != "complete" and (
                    len(self.axis_names) != len(top.axis_sizes)):
                raise ValueError(
                    f"axis_names {self.axis_names} vs grid {top.axis_sizes}")
        if self.membership is not None:
            self.membership.validate()
            if self.membership.n_workers != self.topology.n_workers:
                raise ValueError(
                    f"membership K={self.membership.n_workers} != topology "
                    f"K={self.topology.n_workers}")
            if len(self.axis_names) != 1:
                # a multi-axis ppermute applies one perm across every slice
                # of the other axes — per-worker edge pruning is not
                # expressible there.  Flatten the grid to one worker axis
                # to combine elastic membership with the sharded backend.
                raise ValueError(
                    "elastic membership on ShardedComm needs a single "
                    f"worker axis; got axis_names {self.axis_names}")

    def _receive_from(self, x, axis: int, shift: int):
        """Each worker receives the value held by worker (k+shift) on `axis`."""
        n = self.topology.axis_sizes[axis]
        name = self.axis_names[axis]
        perm = [(j, (j - shift) % n) for j in range(n)]
        return jax.lax.ppermute(x, name, perm)

    def _receive_perm(self, x, axis: int, recv_from):
        """Each worker ``j`` on `axis` receives the value of ``recv_from[j]``."""
        name = self.axis_names[axis]
        perm = [(int(src), j) for j, src in enumerate(recv_from)]
        return jax.lax.ppermute(x, name, perm)

    def receive_tree(self, tree, axis: int, shift: int):
        return jax.tree_util.tree_map(
            partial(self._receive_from, axis=axis, shift=shift), tree)

    def receive_payload(self, payload: Dict[str, object], axis: int,
                        shift: int) -> Dict[str, object]:
        """Ship one wire-codec payload from the (axis, shift) neighbour:
        one ``ppermute`` per payload array, dtypes preserved (this is
        where compression becomes real bytes on the interconnect)."""
        return {k: self._receive_from(v, axis, shift)
                for k, v in payload.items()}

    def _receive_from_committed(self, x, axis: int, shift: int, source_ok):
        """``ppermute`` pruned to sources with ``source_ok[s]`` (a static
        numpy bool mask).  Destinations whose source did not commit receive
        zeros — which every wire codec decodes to exactly 0, so a stored
        neighbour copy updated with the decoded payload stays put."""
        n = self.topology.axis_sizes[axis]
        name = self.axis_names[axis]
        ok = np.asarray(source_ok, dtype=bool)   # host: pair list  # lint: allow
        pairs = [(s, (s - shift) % n) for s in range(n) if ok[s]]
        if not pairs:
            return jnp.zeros_like(x)
        return jax.lax.ppermute(x, name, pairs)

    def receive_payload_committed(self, payload: Dict[str, object],
                                  axis: int, shift: int,
                                  source_ok) -> Dict[str, object]:
        """Like :meth:`receive_payload`, but edges from non-committing
        sources are pruned from the collective (dead edges ship zero
        bytes); their receivers get all-zero payload arrays."""
        return {k: self._receive_from_committed(v, axis, shift, source_ok)
                for k, v in payload.items()}

    def _mix_with(self, top: Topology, tree):
        """One gossip round under a specific topology (static trace)."""
        if top.name == "complete":
            return jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, self.axis_names), tree)
        if top.name == "disconnected":
            return tree

        # Kronecker factorization: apply the per-axis exchanges sequentially.
        per_axis: Dict[int, list] = {}
        for (ax, sh, w) in top.shifts:
            per_axis.setdefault(ax, []).append(("shift", sh, w))
        for (ax, recv, w) in top.perms:
            per_axis.setdefault(ax, []).append(("perm", recv, w))

        def mix_leaf(x):
            y = x
            for ax in sorted(per_axis):
                acc = None
                payload = self._wire_cast(y)
                for (kind, arg, w) in per_axis[ax]:
                    if kind == "shift" and arg == 0:
                        v = y.astype(jnp.float32)       # self term: no wire
                    elif kind == "shift":
                        v = self._unwire_cast(
                            self._receive_from(payload, ax, arg))
                    else:
                        v = self._unwire_cast(
                            self._receive_perm(payload, ax, arg))
                    term = v * jnp.float32(w)
                    acc = term if acc is None else acc + term
                y = acc.astype(x.dtype)
            return y

        return jax.tree_util.tree_map(mix_leaf, tree)

    def _wire_cast(self, x):
        """What actually ships: the neighbour payload in the wire dtype
        (the self term never crosses the wire and stays full precision).
        The bf16 payload ships bitcast to u16: XLA's convert mover happily
        slides a float down-cast past the ppermute (re-widening the wire
        to 4 B/elem), but never commutes converts across integer bitcasts,
        so the 2 B/elem wire is pinned on every backend."""
        if self.wire_dtype == "bfloat16":
            return jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16),
                                                jnp.uint16)
        return x

    def _unwire_cast(self, v):
        """Received payload back to f32 for the mixing accumulation
        (inverse of :meth:`_wire_cast`)."""
        if self.wire_dtype == "bfloat16":
            return jax.lax.bitcast_convert_type(
                v, jnp.bfloat16).astype(jnp.float32)
        return v.astype(jnp.float32)

    def _mix_with_masked(self, top: Topology, act, tree):
        """One gossip round under a specific topology with only ``act``
        workers exchanging.  Each weighted shift/perm becomes a ppermute
        pruned to edges with both endpoints active; per-worker receive
        coefficients and the renormalized self-weight come from
        :func:`masked_matrix`'s factors, gathered at ``axis_index`` — so
        the executed matrix equals the dense backend's masked W exactly.
        """
        if act.all():
            return self._mix_with(top, tree)
        if top.name == "disconnected":
            return tree

        name = self.axis_names[0]
        n = top.axis_sizes[0]
        idx = jax.lax.axis_index(name)
        act = np.asarray(act, dtype=bool)   # host: program build  # lint: allow
        ks = np.arange(n)

        # Per-exchange pruned perms + per-receiver coefficient vectors.
        # Coefficients come from each (shift, w) entry directly — never
        # from reading the masked matrix, whose aliased entries (e.g. the
        # ±K/2 shifts of `exponential`) collapse into one cell.
        entries = []  # (coeff (n,) f32, pairs)
        off_diag = np.zeros(n)
        for (_ax, sh, w) in top.shifts:
            if sh % n == 0:  # self (possibly aliased) — absorbed in diag
                continue
            src = (ks + sh) % n
            coeff = np.where(act & act[src], w, 0.0)
            pairs = [(int(s), int((s - sh) % n)) for s in range(n)
                     if act[s] and act[(s - sh) % n]]
            off_diag += coeff
            entries.append((coeff.astype(np.float32), pairs))
        for (_ax, recv, w) in top.perms:
            src = np.asarray(recv)   # host: program build  # lint: allow
            coeff = np.where((src != ks) & act & act[src], w, 0.0)
            pairs = [(int(src[j]), int(j)) for j in range(n)
                     if src[j] != j and act[j] and act[src[j]]]
            off_diag += coeff
            entries.append((coeff.astype(np.float32), pairs))
        # Lost neighbour mass flows back to self: rows stay stochastic.
        diag = jnp.asarray((1.0 - off_diag).astype(np.float32))[idx]
        coeffs = [jnp.asarray(c)[idx] for (c, _p) in entries]

        def mix_leaf(x):
            acc = x.astype(jnp.float32) * diag
            payload = self._wire_cast(x)
            for c, (_coeff, pairs) in zip(coeffs, entries):
                if not pairs:
                    continue
                v = self._unwire_cast(jax.lax.ppermute(payload, name, pairs))
                acc = acc + v * c
            return acc.astype(x.dtype)

        return jax.tree_util.tree_map(mix_leaf, tree)

    def mix(self, tree, r=None):
        if self.membership is not None:
            Lc = self.round_cycle
            if Lc == 1:
                return self._mix_with_masked(
                    self.topology_at(0), self.active_at(0), tree)
            if r is None:
                raise ValueError(
                    "ShardedComm with a MembershipSchedule needs the round "
                    "index: mix(tree, r=...)")
            branches = [partial(self._mix_with_masked, self.topology_at(l),
                                self.active_at(l)) for l in range(Lc)]
            idx = jnp.mod(jnp.asarray(r, jnp.int32), Lc)
            return jax.lax.switch(idx, branches, tree)
        if self.schedule is None or self.period == 1:
            return self._mix_with(self.topology_at(0), tree)
        if r is None:
            raise ValueError(
                "ShardedComm with a TopologySchedule needs the round index: "
                "mix(tree, r=...)")
        branches = [partial(self._mix_with, top)
                    for top in self.schedule.topologies]
        idx = jnp.mod(jnp.asarray(r, jnp.int32), self.period)
        return jax.lax.switch(idx, branches, tree)

    def stale_mix(self, tree, r=None):
        if self.membership is None:
            return self.mix(tree, r=r)
        Lc = self.round_cycle
        if Lc == 1:
            return self._mix_with_masked(
                self.topology_at(0), self.active_at(1), tree)
        if r is None:
            raise ValueError(
                "ShardedComm with a MembershipSchedule needs the round "
                "index: stale_mix(tree, r=...)")
        branches = [partial(self._mix_with_masked, self.topology_at(l),
                            self.active_at(l + 1)) for l in range(Lc)]
        idx = jnp.mod(jnp.asarray(r, jnp.int32), Lc)
        return jax.lax.switch(idx, branches, tree)

    def shift_views(self, tree) -> Dict[ShiftKey, object]:
        out = {}
        for (ax, sh, _w) in self.nonself_shifts():
            out[(ax, sh)] = self.receive_tree(tree, ax, sh)
        return out


@dataclasses.dataclass
class HierarchicalComm(ShardedComm):
    """Two-level sharded backend: exact intra-node average + inter-node
    gossip between node leaders.

    Workers live on the ``(n_nodes, node_size)`` grid of a
    ``"hierarchical"`` topology (or a schedule of them — e.g.
    ``hierarchical_schedule``'s one-peer-exp inter rounds).  Each round
    executes the factored matrix ``W_inter ⊗ (1/m)11ᵀ`` as:

    1. **intra** — grouped ``pmean`` over the node's ``m`` workers (the
       only non-ppermute collective the round contract allows), on the
       fast in-host links;
    2. **inter** — ``ppermute`` of the node mean between node *leaders*
       only (pruned source→dest pairs), optionally bf16
       (``wire_dtype``) or codec-compressed (``inter_codec``) — the slow
       cross-host wire, amortized over the node's ``m`` workers;
    3. **rebroadcast** — grouped ``psum`` of the leader's mixed value
       back to its node (intra links again).

    Two mesh layouts are supported:

    * ``axis_names = (name,)`` — one flat worker axis of size
      ``n_nodes × node_size``; worker ``i·m + j`` is node ``i`` member
      ``j`` and member 0 is the leader.  Intra steps are
      ``axis_index_groups`` collectives, the inter ppermute is pruned to
      leaders.
    * ``axis_names = (inter, intra)`` — the node boundary *is* a mesh
      axis (e.g. ``("pod", "data")``); ``node_size`` must equal the
      intra-axis size.  Every device holds its node mean after the full-
      axis ``pmean``, so the inter ppermute runs unpruned (per-device
      bytes are the same; there is no leader amortization) and no
      rebroadcast is needed.

    ``inter_codec`` compresses the inter wire with any keyless
    :class:`repro.core.wire.WireCodec` (identity/sign/qsgd/topk; randk
    needs a shared key and is rejected).  The self term stays full
    precision, so a lossy codec makes this standard *biased* compressed
    gossip — identity is bit-exact with no codec.  Elastic membership is
    dense-only (a masked two-level program is not expressible as pruned
    grouped collectives); use ``DenseComm`` with a hierarchical topology
    to simulate churn.
    """

    inter_codec: Optional[object] = None   # keyless WireCodec or None

    def __post_init__(self):
        self._resolve(self.topology)
        self._check_wire_dtype()
        for top in (self.schedule.topologies if self.schedule is not None
                    else (self.topology,)):
            if top.name != "hierarchical" or len(top.axis_sizes) != 2:
                raise ValueError(
                    "HierarchicalComm needs hierarchical (n_nodes, "
                    f"node_size) topologies; got {top.name!r} with grid "
                    f"{top.axis_sizes}")
        if len(self.axis_names) not in (1, 2):
            raise ValueError(
                "HierarchicalComm maps onto one flat worker axis or an "
                f"(inter, intra) axis pair; got {self.axis_names}")
        if self.membership is not None:
            raise ValueError(
                "elastic membership on HierarchicalComm is not supported: "
                "masked two-level rounds are not expressible as pruned "
                "grouped collectives — run hierarchical churn on DenseComm")
        if self.inter_codec is not None:
            if getattr(self.inter_codec, "name", "") == "randk":
                raise ValueError(
                    "randk inter_codec needs a shared per-round key; use "
                    "identity/sign/qsgd/topk on the inter wire")
            if self.wire_dtype != "float32":
                raise ValueError(
                    "inter_codec already defines the wire encoding; "
                    "combine it with wire_dtype='float32'")

    @property
    def n_nodes(self) -> int:
        return int(self.topology.axis_sizes[0])

    @property
    def node_size(self) -> int:
        return int(self.topology.axis_sizes[1])

    @property
    def hier_leader_pruned(self) -> bool:
        """True when only node leaders ship the inter wire (flat-axis
        layout) — per-worker inter bytes amortize over ``node_size``."""
        return len(self.axis_names) == 1

    def inter_degree(self, r: int = 0) -> int:
        return len(hierarchical_inter_shifts(self.topology_at(r)))

    def _node_groups(self):
        m, n = self.node_size, self.n_nodes
        return [[i * m + j for j in range(m)] for i in range(n)]

    def _level_ops(self, top: Topology):
        """The three per-layout primitives of one two-level round:
        ``node_avg`` (exact intra mean, f32), ``recv(payload, shift)``
        (inter-node exchange of an arbitrary payload array) and
        ``rebroadcast`` (mixed leader value back to its node)."""
        n, m = int(top.axis_sizes[0]), int(top.axis_sizes[1])
        if len(self.axis_names) == 2:
            inter_name, intra_name = self.axis_names

            def node_avg(x):
                if m == 1:
                    return x.astype(jnp.float32)
                return jax.lax.pmean(x.astype(jnp.float32), intra_name)

            def recv(payload, sh):
                perm = [(j, (j - sh) % n) for j in range(n)]
                return jax.lax.ppermute(payload, inter_name, perm)

            # every device already holds its node mean post-pmean, so the
            # unpruned ppermute leaves all of them consistent — no step 3
            def rebroadcast(acc):
                return acc

            return node_avg, recv, rebroadcast

        name = self.axis_names[0]
        groups = self._node_groups()

        def node_avg(x):
            if m == 1:
                return x.astype(jnp.float32)
            return jax.lax.pmean(x.astype(jnp.float32), name,
                                 axis_index_groups=groups)

        def recv(payload, sh):
            # leaders only: non-paired destinations receive zeros, which
            # the rebroadcast below overwrites
            pairs = [(s * m, ((s - sh) % n) * m) for s in range(n)]
            return jax.lax.ppermute(payload, name, pairs)

        if m == 1:
            def rebroadcast(acc):
                return acc
        else:
            def rebroadcast(acc):
                is_leader = jnp.equal(
                    jnp.mod(jax.lax.axis_index(name), m), 0)
                only_leader = jnp.where(is_leader, acc,
                                        jnp.zeros_like(acc))
                return jax.lax.psum(only_leader, name,
                                    axis_index_groups=groups)

        return node_avg, recv, rebroadcast

    def _inter_mix(self, xa, top, recv, *, wire=None, unwire=None):
        """Weighted inter-node accumulation on a node mean ``xa`` (f32).
        ``wire``/``unwire`` optionally restrict what ships to a payload
        slice (kernel used_rows) and pad it back after decode."""
        inter = hierarchical_inter_shifts(top)
        ws = hierarchical_self_weight(top)
        if not inter:
            return xa
        if wire is None:
            wire = unwire = lambda v: v
        acc = xa * jnp.float32(ws)
        src = wire(xa)
        if self.inter_codec is not None:
            pay = self.inter_codec.pack(src)
            for (sh, w) in inter:
                got = {k: recv(v, sh) for k, v in pay.items()}
                dec = self.inter_codec.unpack(got, src.size, src.shape,
                                              jnp.float32)
                acc = acc + unwire(dec) * jnp.float32(w)
        else:
            payload = self._wire_cast(src)
            for (sh, w) in inter:
                v = self._unwire_cast(recv(payload, sh))
                acc = acc + unwire(v) * jnp.float32(w)
        return acc

    def _mix_with(self, top: Topology, tree):
        """One two-level round under a specific hierarchical topology."""
        node_avg, recv, rebroadcast = self._level_ops(top)

        def mix_leaf(x):
            xa = node_avg(x)
            acc = self._inter_mix(xa, top, recv)
            return rebroadcast(acc).astype(x.dtype)

        return jax.tree_util.tree_map(mix_leaf, tree)

    def mix_mat(self, x_mat, *, plan=None, r: int = 0):
        """Kernel-path round on the flatten-once ``(rows, LANE)`` matrix:
        the intra levels run on the full matrix (alignment-tail zeros
        average to zero and stay zero), while the inter wire ships only
        the plan's ``used_rows`` slice — accounted ≡ shipped.  Static
        topologies only (schedules go through :meth:`mix`)."""
        top = self.topology_at(r)
        node_avg, recv, rebroadcast = self._level_ops(top)
        u = None if plan is None else int(plan.used_rows)
        if u is None or u >= x_mat.shape[-2]:
            wire = unwire = None
        else:
            def wire(v):
                return v[..., :u, :]
            unwire = plan.pad_wire
        xa = node_avg(x_mat)
        acc = self._inter_mix(xa, top, recv, wire=wire, unwire=unwire)
        return rebroadcast(acc).astype(x_mat.dtype)

    def shift_views(self, tree):
        raise NotImplementedError(
            "HierarchicalComm has no flat per-shift views: the inter wire "
            "moves node means between leaders, not raw worker tensors")


def _wire_leaf_bytes(tree, backend: CommBackend) -> int:
    """Σ leaf bytes as they ship on the wire: leaf dtype, downshifted to
    the backend's wire dtype when that is narrower (bf16 x-wire)."""
    wi = getattr(backend, "wire_itemsize", 4)
    return sum(int(np.prod(l.shape)) * min(int(l.dtype.itemsize), wi)
               for l in jax.tree_util.tree_leaves(tree))


def gossip_bytes_per_round(tree, backend: CommBackend,
                           bits_per_element: float | None = None,
                           r: int = 0) -> int:
    """Per-worker bytes sent in communication round ``r`` (comm-cost model).

    Full precision: round-r degree × Σ leaf bytes (at the backend's wire
    dtype — bf16 halves the uncompressed payload).  With compression, pass
    the compressor's ``wire_bits_per_element``.  Under a time-varying
    schedule the degree — and hence the bytes — varies by round; under a
    membership schedule dead edges ship zero bytes, so the multiplier is
    the round's active-edge count averaged over workers (a float).
    Hierarchical topologies charge the slow-link level only (the headline
    figure): see :func:`hier_bytes_per_round` for the per-level split.
    The optimizer's ``bytes_per_round_cycle`` collects the joint cycle.
    """
    top = backend.topology_at(r)
    if top.name == "hierarchical" and backend.membership is None:
        return hier_bytes_per_round(tree, backend, r=r)["inter"]
    total_elems = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))
    deg = top.degree
    if backend.membership is not None:
        epw = backend.edges_per_worker(r)
        if bits_per_element is None:
            return epw * _wire_leaf_bytes(tree, backend)
        return float(epw * total_elems * bits_per_element / 8.0)
    if bits_per_element is None:
        return deg * _wire_leaf_bytes(tree, backend)
    return int(deg * total_elems * bits_per_element / 8.0)


def hier_bytes_per_round(tree, backend: CommBackend, r: int = 0) -> dict:
    """Per-level comm-cost split of one hierarchical round.

    Returns a dict of per-worker byte figures for round ``r``:

    * ``"inter"`` — slow-link bytes per *worker*: inter-degree × payload
      (codec wire bytes when ``inter_codec`` is set, else leaf bytes at
      the wire dtype), divided by ``node_size`` when only leaders ship
      (flat-axis layout / dense simulation) — the headline accounting.
    * ``"inter_site"`` — slow-link bytes at the collective-permute op
      site per participating device (no leader amortization): what the
      HLO byte check reads off the compiled program.
    * ``"intra_wire"`` — fast-link bytes per worker: ring all-reduce
      wire cost ``2(m−1)/m × f32 bytes`` per intra collective (average +
      rebroadcast on the flat-axis layout; average only on the two-axis
      layout, where no rebroadcast ships).
    * ``"intra_result"`` — Σ all-reduce *result* bytes (what the HLO
      parser reports per op), for accounted ≡ shipped per level.
    """
    top = backend.topology_at(r)
    if top.name != "hierarchical":
        raise ValueError(f"not a hierarchical topology: {top.name!r}")
    m = int(top.axis_sizes[1])
    leaves = jax.tree_util.tree_leaves(tree)
    elems = sum(int(np.prod(l.shape)) for l in leaves)
    ideg = len(hierarchical_inter_shifts(top))
    codec = getattr(backend, "inter_codec", None)
    if codec is not None:
        payload = sum(codec.wire_bytes(int(np.prod(l.shape)))
                      for l in leaves)
    else:
        payload = _wire_leaf_bytes(tree, backend)
    pruned = bool(getattr(backend, "hier_leader_pruned", True))
    site = ideg * payload
    n_intra = 0 if m == 1 else (2 if pruned else 1)
    return {
        "inter": site / m if pruned else float(site),
        "inter_site": site,
        "intra_wire": n_intra * (2.0 * (m - 1) / m) * 4 * elems,
        "intra_result": n_intra * 4 * elems,
    }
