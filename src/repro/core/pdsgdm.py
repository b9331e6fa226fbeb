"""PD-SGDM — Periodic Decentralized Momentum SGD (paper Algorithm 1).

Per worker k, per iteration t::

    m⁽ᵏ⁾ₜ   = μ m⁽ᵏ⁾ₜ₋₁ + ∇F(x⁽ᵏ⁾ₜ; ξ⁽ᵏ⁾ₜ)
    x⁽ᵏ⁾ₜ₊½ = x⁽ᵏ⁾ₜ − η m⁽ᵏ⁾ₜ
    x⁽ᵏ⁾ₜ₊₁ = Σⱼ w_kj x⁽ʲ⁾ₜ₊½      if mod(t+1, p) == 0   (gossip)
            = x⁽ᵏ⁾ₜ₊½              otherwise

The optimizer is backend-agnostic: with :class:`~repro.core.gossip.DenseComm`
leaves carry a leading worker dim (simulation / paper-faithful experiments);
with :class:`~repro.core.gossip.ShardedComm` it runs inside ``shard_map`` on
per-worker shards and gossip lowers to ``collective-permute``.

Weight decay follows the paper's experimental setup (PyTorch SGD semantics:
decay folded into the gradient before the momentum update).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core.gossip import CommBackend, DenseComm, HierarchicalComm

__all__ = ["PDSGDMConfig", "PDSGDM"]

# Named scopes (``jax.named_scope``) of the fused round, set in ``round`` and
# ``kernel_round`` so every optimizer and both trainers carry them: the
# compiled HLO's op metadata holds them, so a device trace can split a round.
SCOPE_GRAD = "grad"              # the loss and its gradient (fwd, bwd, remat)
SCOPE_LOCAL_STEP = "local_step"  # the momentum and weight update
SCOPE_GOSSIP = "gossip"          # wire casts, codec, the exchange, the mix


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _keep_arg_layout(tree):
    """Pin every leaf of two or more dims (fewer have one layout) to the
    default major-to-minor layout, the one the jitted round takes and
    returns its arrays in.  On a TPU that holds where a leaf's last dim fills
    whole 128-lane tiles; a narrower leaf whose default layout there differs
    pays a copy into and out of the loop.

    Applied to the round's scan carry.  Left free, the TPU compiler may carry
    the loop in another layout: on one chip, where nothing after the loop
    pins it, it transposes the last two dims of the stacked block weights and
    momentum, relayouts the whole model into and out of the loop, and holds
    both copies at once.  Only a TPU compile gets the pin: the CPU's layout
    assignment keeps the default anyway, and its partitioner would gather
    the constraint's sharded operand in full."""
    def pin(tree):
        return _tree_map(lambda x: x if x.ndim < 2 else with_layout_constraint(
            x, Layout(major_to_minor=tuple(range(x.ndim)))), tree)
    return jax.lax.platform_dependent(tree, tpu=pin, default=lambda t: t)


@dataclasses.dataclass(frozen=True)
class PDSGDMConfig:
    eta: float = 0.1                 # step size η (peak LR if schedule given)
    mu: float = 0.9                  # momentum coefficient μ ∈ (0, 1)
    p: int = 4                       # communication period (p > 1 in paper)
    weight_decay: float = 0.0
    nesterov: bool = False           # beyond-paper option (off by default)
    lr_schedule: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None
    # Pallas execution path: the fused round runs on the flatten-once
    # (rows, 1024) kernel layout (momentum scan + gossip mix + CPD's sign
    # wire all on one matrix) — the recommended production configuration.
    use_kernel: bool = False
    # None → repro.kernels.default_interpret() (interpret off-TPU); tests
    # and benchmarks may force it either way.
    kernel_interpret: Optional[bool] = None
    # Communication-hiding overlapped rounds: the gossip payload of round r
    # is snapshotted at the end of round r's local scan, its exchange is
    # issued at the *start* of round r+1 (the collective has no data
    # dependence on round r+1's compute, so the interconnect transfer hides
    # behind the local scan), and the mixing correction lands one round
    # late — x ← x + (W·x̃ − x̃) applied to the drifted params at the end
    # of round r+1.  The in-flight snapshot + staleness phase ride the
    # optimizer state as ``DelayedMixState`` (state["mix"]), so checkpoint
    # resume mid-overlap is bit-identical.  Bytes per round are unchanged:
    # still exactly one payload exchange per round.
    overlap: bool = False

    def lr(self, step):
        if self.lr_schedule is None:
            return jnp.asarray(self.eta, jnp.float32)
        return self.eta * self.lr_schedule(step)


class PDSGDM:
    """Algorithm 1.

    ``step = local_step ∘ maybe_communicate`` is the per-iteration form;
    ``round`` is the fused form (p local steps + one unconditional gossip in
    a single ``lax.scan``) that the trainers execute.
    """

    def __init__(self, config: PDSGDMConfig, comm: CommBackend):
        if not (0.0 <= config.mu < 1.0):
            raise ValueError("momentum μ must be in [0, 1)")
        if config.p < 1:
            raise ValueError("communication period p must be ≥ 1")
        self.config = config
        self.comm = comm

    # -- state ---------------------------------------------------------------
    def init(self, params):
        state = {
            "m": _tree_map(lambda x: jnp.zeros_like(x, dtype=jnp.float32), params),
            "step": jnp.zeros((), jnp.int32),
        }
        if self.config.overlap:
            state["mix"] = self._delayed_mix_init(params)
        return state

    # -- DelayedMixState (overlap=True) ---------------------------------------
    # The in-flight gossip payload: ``buf`` is the f32 snapshot taken at the
    # end of the previous round's local scan (what the neighbours are
    # receiving *now*), ``phase`` is the staleness phase — 0 before any
    # payload has been cut (round 0 executes the exchange but gates the
    # correction to an exact no-op), 1 once a payload is in flight.
    def _delayed_mix_init(self, params):
        return {
            "buf": _tree_map(lambda x: x.astype(jnp.float32), params),
            "phase": jnp.zeros((), jnp.int32),
        }

    # delta-tree keys produced by overlap_begin (MT adds the tracking
    # correction "dc"); the runtime builds shard_map specs from these
    overlap_delta_keys: tuple = ("dx",)
    # whether overlap_step_refresh does anything (MT drips the stale
    # tracking correction into every local step; everyone else skips the
    # per-step hook entirely)
    overlap_refreshes: bool = False

    # -- local computation (Alg. 1 lines 2-4) ---------------------------------
    def local_step(self, state, params, grads):
        cfg = self.config
        lr = cfg.lr(state["step"]).astype(jnp.float32)
        mu = jnp.float32(cfg.mu)
        wd = jnp.float32(cfg.weight_decay)

        if cfg.use_kernel:
            from repro.kernels import ops as kops
            new_params, new_m = kops.momentum_update_tree(
                params, state["m"], grads, mu=cfg.mu, lr=lr,
                weight_decay=cfg.weight_decay, nesterov=cfg.nesterov,
                interpret=cfg.kernel_interpret)
        else:
            def upd(x, m, g):
                g32 = g.astype(jnp.float32) + wd * x.astype(jnp.float32)
                m_new = mu * m + g32
                d = (g32 + mu * m_new) if cfg.nesterov else m_new
                x_new = x.astype(jnp.float32) - lr * d
                return x_new.astype(x.dtype), m_new

            xs, treedef = jax.tree_util.tree_flatten(params)
            ms = treedef.flatten_up_to(state["m"])
            gs = treedef.flatten_up_to(grads)
            pairs = [upd(x, m, g) for x, m, g in zip(xs, ms, gs)]
            new_params = treedef.unflatten([x for x, _ in pairs])
            new_m = treedef.unflatten([m for _, m in pairs])

        new_state = dict(state)   # preserve subclass state (e.g. CPD's x̂)
        new_state["m"] = new_m
        new_state["step"] = state["step"] + 1
        return new_params, new_state

    # -- communication (Alg. 1 lines 5-9) --------------------------------------
    def round_index(self, state):
        """0-based index of the gossip round being applied.

        ``comm_round`` runs after the local step(s) advanced the counter to
        ``t+1 = (r+1)·p``, so ``r = step // p − 1``.  Time-varying topology
        schedules key on this — and because it is derived from the
        checkpointed step counter, resume restores the schedule phase
        bit-identically with no extra persisted cursor.
        """
        return state["step"] // self.config.p - 1

    def comm_round(self, state, params):
        """One gossip round (unconditional), with round ``r``'s topology."""
        return self.comm.mix(params, r=self.round_index(state)), state

    def is_comm_step(self, state):
        """mod(t+1, p) == 0, evaluated *after* the local step incremented t."""
        return (state["step"] % self.config.p) == 0

    def maybe_communicate(self, state, params):
        do = self.is_comm_step(state)
        params, state = jax.lax.cond(
            do,
            lambda s, p: self.comm_round(s, p),
            lambda s, p: (p, s),
            state, params)
        return params, state

    # -- overlapped rounds: one-round-stale delayed mixing ----------------------
    def overlap_begin(self, state):
        """Issue the in-flight payload's exchange and form the delayed-mix
        correction — the only collectives in an overlapped round, with no
        data dependence on the round's local scan (communication hiding).

        Evaluated at round start, ``round_index(state)`` *is* the payload's
        round r (step = (r+1)·p), so time-varying topologies key on the
        payload round while the membership mask keys on the delivery round
        r+1 inside ``stale_mix``.  ``phase == 0`` (nothing in flight yet)
        gates the correction to exact zero; the exchange still runs so one
        trace and one byte pattern serve every round.
        """
        mix = state["mix"]
        r = self.round_index(state)
        gate = (mix["phase"] > 0).astype(jnp.float32)
        mixed = self.comm.stale_mix(mix["buf"], r=r)
        dx = _tree_map(lambda mb, b: (mb - b) * gate, mixed, mix["buf"])
        return {"dx": dx}

    def overlap_step_refresh(self, state, delta):
        """Per-local-step refresh from the in-flight payload (no-op here;
        MT-DSGDm drips its stale tracking correction through this hook)."""
        return state

    def overlap_apply(self, state, params, delta):
        """Land the one-round-stale correction on the drifted params at the
        round's end, then cut the next payload (snapshot + phase=1)."""
        params_new = _tree_map(
            lambda x, d: (x.astype(jnp.float32) + d).astype(x.dtype),
            params, delta["dx"])
        new_state = dict(state)
        new_state["mix"] = self._snapshot_mix(new_state, params_new)
        return params_new, new_state

    def _snapshot_mix(self, state, params):
        return {
            "buf": _tree_map(lambda x: x.astype(jnp.float32), params),
            "phase": jnp.ones((), jnp.int32),
        }

    # -- full iteration ---------------------------------------------------------
    def step(self, state, params, grads):
        if self.config.overlap:
            # Per-step form of the overlapped round (debugging / off-round
            # resume).  The correction depends only on the in-flight buf,
            # so recomputing it each step is value-identical to the fused
            # round's single round-start computation — the per-step path
            # continues a mid-overlap checkpoint bit-identically.
            delta = self.overlap_begin(state)
            params, state = self.local_step(state, params, grads)
            state = self.overlap_step_refresh(state, delta)
            params, state = jax.lax.cond(
                self.is_comm_step(state),
                lambda s, p: self.overlap_apply(s, p, delta),
                lambda s, p: (p, s),
                state, params)
            return params, state
        params, state = self.local_step(state, params, grads)
        params, state = self.maybe_communicate(state, params)
        return params, state

    # -- fused round (the canonical hot path) -----------------------------------
    def round(self, state, params, grads_fn, batches, *,
              local_step=None, comm_round=None, gossip=True,
              overlap_begin=None, overlap_apply=None, overlap_refresh=None):
        """One whole round, fused: ``lax.scan`` of p local steps then exactly
        one unconditional gossip round — no per-step ``lax.cond``, no per-step
        Python dispatch.

        ``grads_fn(params, batch) -> (loss, grads)``; ``batches`` carries a
        leading scan dim of length p.  ``local_step``/``comm_round`` default
        to the optimizer's own methods (DenseComm simulation); the sharded
        runtime passes ``shard_map``-wrapped versions so the identical scan
        structure drives both backends.  ``gossip=False`` runs a fused tail
        of local steps only (a run whose length is not a multiple of p).

        With ``use_kernel`` and no injected overrides the round executes on
        the flatten-once Pallas layout instead (:meth:`kernel_round`).

        With ``overlap`` the round takes the delayed-mixing form instead:
        the in-flight payload's exchange is issued at round *start*
        (``overlap_begin``), the p-step scan runs with no data dependence
        on it (MT's per-step refresh excepted), and the stale correction
        lands after the scan (``overlap_apply``), which also cuts the next
        round's payload.  ``overlap_begin``/``overlap_refresh``/
        ``overlap_apply`` are injectable exactly like ``local_step``/
        ``comm_round`` (the sharded runtime passes shard_mapped versions).

        Returns ``(params, state, losses)`` with ``losses`` stacked over the
        p local steps.
        """
        if (self.config.use_kernel and local_step is None
                and comm_round is None and overlap_begin is None
                and overlap_apply is None):
            return self.kernel_round(state, params, grads_fn, batches,
                                     gossip=gossip)
        if local_step is None:
            local_step = self.local_step
        if comm_round is None:
            comm_round = self.comm_round

        if self.config.overlap:
            if overlap_begin is None:
                overlap_begin = self.overlap_begin
            if overlap_apply is None:
                overlap_apply = self.overlap_apply
            if overlap_refresh is None and self.overlap_refreshes:
                overlap_refresh = self.overlap_step_refresh
            delta = None
            if gossip or overlap_refresh:
                with jax.named_scope(SCOPE_GOSSIP):
                    delta = overlap_begin(state)

            def body(carry, batch):
                params, state = carry
                with jax.named_scope(SCOPE_GRAD):
                    loss, grads = grads_fn(params, batch)
                with jax.named_scope(SCOPE_LOCAL_STEP):
                    params, state = local_step(state, params, grads)
                    if overlap_refresh is not None:
                        state = overlap_refresh(state, delta)
                return _keep_arg_layout((params, state)), loss

            (params, state), losses = jax.lax.scan(body, (params, state),
                                                   batches)
            if gossip:
                with jax.named_scope(SCOPE_GOSSIP):
                    params, state = overlap_apply(state, params, delta)
            return params, state, losses

        def body(carry, batch):
            params, state = carry
            with jax.named_scope(SCOPE_GRAD):
                loss, grads = grads_fn(params, batch)
            with jax.named_scope(SCOPE_LOCAL_STEP):
                params, state = local_step(state, params, grads)
            return _keep_arg_layout((params, state)), loss

        (params, state), losses = jax.lax.scan(body, (params, state), batches)
        if gossip:
            with jax.named_scope(SCOPE_GOSSIP):
                params, state = comm_round(state, params)
        return params, state, losses

    # -- kernel round: flatten once, scan + gossip on the (rows, 1024) layout --
    @property
    def kernel_comm_supported(self) -> bool:
        """Whether ``comm_round_mat`` can run this optimizer's gossip on the
        kernel matrix (PD-SGDM: always — worst case it falls back to
        ``comm.mix`` *on the matrix*, still flatten-once)."""
        return True

    def mat_state(self, plan, state) -> dict:
        """Flatten the per-element optimizer state trees into kernel mats."""
        mats = {"m": plan.flatten(state["m"])}
        if self.config.overlap:
            mats["mix_buf"] = plan.flatten(state["mix"]["buf"])
        return mats

    def unmat_state(self, plan, mats, state, step) -> dict:
        new_state = dict(state)
        new_state["m"] = plan.unflatten(mats["m"], dtype=jnp.float32)
        new_state["step"] = step
        if self.config.overlap:
            new_state["mix"] = {
                **state["mix"],
                "buf": plan.unflatten(mats["mix_buf"], dtype=jnp.float32),
            }
        return new_state

    def local_step_mat(self, x_mat, mats, g_mat, step):
        """One fused momentum update on the kernel layout (Alg. 1 lines 2-4)."""
        from repro.kernels import ops as kops
        cfg = self.config
        x_new, m_new = kops.momentum_update_mat(
            x_mat, mats["m"], g_mat, mu=cfg.mu,
            lr=cfg.lr(step).astype(jnp.float32),
            weight_decay=cfg.weight_decay, nesterov=cfg.nesterov,
            interpret=cfg.kernel_interpret)
        return x_new, {**mats, "m": m_new}

    def _shift_view_mat(self, mat, ax: int, sh: int):
        """The matrix each worker receives from its (ax, sh) neighbour."""
        if isinstance(self.comm, DenseComm):
            return self.comm._roll(mat, ax, sh)
        return self.comm._receive_from(mat, ax, sh)

    def _mat_wire_static(self) -> bool:
        """Whether ``_gossip_mat`` runs the shift-structured AXPY wire:
        static graph, full membership, no perms, not complete — the path
        whose neighbour exchanges slice to ``plan.used_rows`` (block-exact
        accounting).  Elastic membership routes through ``comm.mix`` on
        the matrix, which owns the per-round edge pruning."""
        top = self.comm.topology
        return ((self.comm.schedule is None or self.comm.period == 1)
                and self.comm.membership is None
                and not top.perms
                and top.name not in ("complete", "disconnected",
                                     "hierarchical"))

    def _gossip_mat(self, x_mat, r, *, plan=None):
        """Gossip mix on the kernel layout.  Static shift-structured graphs
        run the fused Pallas AXPY per topology axis (mirroring
        ``ShardedComm._mix_with``'s Kronecker factorization); everything
        else (schedules, ``complete``, perm graphs) falls back to
        ``comm.mix`` applied to the matrix — still flatten-once.

        With a ``plan``, each neighbour exchange ships only the
        ``plan.used_rows`` wire extent: the block-alignment tail is zero
        on every worker and row-local mixing keeps it zero, so slicing is
        exact and the ppermute bytes equal ``bytes_per_comm_round``.
        """
        from repro.kernels import ops as kops
        if not self._mat_wire_static():
            comm = self.comm
            if (isinstance(comm, HierarchicalComm)
                    and (comm.schedule is None or comm.period == 1)):
                # two-level round on the matrix: intra pmean on the full
                # rows, inter wire sliced to used_rows (accounted ≡ shipped)
                return comm.mix_mat(x_mat, plan=plan)
            return self.comm.mix(x_mat, r=r)
        top = self.comm.topology
        u = plan.used_rows if plan is not None else None
        per_axis: dict = {}
        for (ax, sh, w) in top.shifts:
            per_axis.setdefault(ax, []).append((sh, w))
        y = x_mat
        for ax in sorted(per_axis):
            views, weights = [], []
            payload = self._wire_cast_mat(y)
            for (sh, w) in per_axis[ax]:
                if sh == 0:
                    views.append(y)
                elif u is not None and u < y.shape[-2]:
                    views.append(plan.pad_wire(self._unwire_cast_mat(
                        self._shift_view_mat(plan.wire(payload), ax, sh))))
                else:
                    views.append(self._unwire_cast_mat(
                        self._shift_view_mat(payload, ax, sh)))
                weights.append(w)
            y = kops.gossip_mix_mat(tuple(views), tuple(weights),
                                    interpret=self.config.kernel_interpret)
        return y

    def _wire_cast_mat(self, v):
        """The neighbour payload in the backend's wire dtype (bf16 halves
        the kernel-path bytes; the self view stays f32).  Bitcast to u16
        so the down-cast cannot slide past the ppermute (see
        ``CommBackend._wire_cast``)."""
        if getattr(self.comm, "wire_dtype", "float32") == "bfloat16":
            return jax.lax.bitcast_convert_type(v.astype(jnp.bfloat16),
                                                jnp.uint16)
        return v

    def _unwire_cast_mat(self, v):
        """Received kernel payload back to f32 (inverse of
        ``_wire_cast_mat``)."""
        if getattr(self.comm, "wire_dtype", "float32") == "bfloat16":
            return jax.lax.bitcast_convert_type(
                v, jnp.bfloat16).astype(jnp.float32)
        return v.astype(jnp.float32)

    def comm_round_mat(self, x_mat, mats, counts, r, *, plan=None):
        """One gossip round on the kernel layout (``counts`` unused here;
        CPD-SGDM's override feeds it to the sign kernel)."""
        return self._gossip_mat(x_mat, r, plan=plan), mats

    # -- overlapped rounds on the kernel layout ---------------------------------
    def _stale_gossip_mat(self, x_mat, r, *, plan=None):
        """Stale mix on the kernel matrix.  Static full-membership graphs
        reuse the shift-structured AXPY wire (stale ≡ regular there: no
        membership mask to shift by one round); hierarchical comms carry
        no membership either, so stale ≡ regular and the plan-sliced wire
        applies too; elastic/scheduled comms route through
        ``comm.stale_mix`` on the matrix, which keys the membership mask
        on the delivery round r+1."""
        if self._mat_wire_static() or isinstance(self.comm,
                                                 HierarchicalComm):
            return self._gossip_mat(x_mat, r, plan=plan)
        return self.comm.stale_mix(x_mat, r=r)

    def overlap_begin_mat(self, mats, r, gate, *, plan=None):
        """Matrix-domain ``overlap_begin``: issue the in-flight payload's
        exchange and form the stale correction, gated by the staleness
        phase (``gate`` is a traced f32 scalar, folded by multiply because
        the fused AXPY kernel takes static weights)."""
        buf = mats["mix_buf"]
        mixed = self._stale_gossip_mat(buf, r, plan=plan)
        return {"dx": (mixed - buf) * gate}

    def overlap_refresh_mat(self, mats, delta):
        """Per-local-step refresh on the kernel layout (no-op here; MT's
        override drips the stale tracking correction)."""
        return mats

    def overlap_apply_mat(self, x_mat, mats, delta, r):
        """Land the stale correction matrix-to-matrix (fused AXPY), then
        cut the next payload by snapshotting the mixed matrix.  ``r`` is
        the landing round (QG's override keys its LR normalizer on it)."""
        from repro.kernels import ops as kops
        x_new = kops.delayed_mix_mat(x_mat, delta["dx"],
                                     interpret=self.config.kernel_interpret)
        return x_new, {**mats, "mix_buf": x_new}

    def kernel_round(self, state, params, grads_fn, batches, *, gossip=True,
                     local_step_mat=None, comm_round_mat=None,
                     overlap_begin_mat=None, overlap_apply_mat=None,
                     overlap_refresh_mat=None):
        """The fused round on the flatten-once kernel layout.

        Params and the per-element state trees are flattened into the
        canonical (rows, 1024) matrices **once**, the ``lax.scan`` of p
        momentum updates runs matrix-to-matrix (the tree form is only
        rematerialized to evaluate ``grads_fn``), the gossip mix — and
        CPD-SGDM's sign pack/unpack — operate on the same layout, and the
        trees are rebuilt once at the round boundary.  Master copies stay
        f32 across the round (leaf dtypes are restored at unflatten).

        ``local_step_mat``/``comm_round_mat`` default to the optimizer's own
        matrix methods (DenseComm simulation); the sharded runtime passes
        ``shard_map``-wrapped versions, exactly like :meth:`round`.
        """
        from repro.kernels import ops as kops
        plan = kops.KernelPlan.for_tree(params, worker_dim=True)
        if local_step_mat is None:
            local_step_mat = self.local_step_mat
        if comm_round_mat is None:
            comm_round_mat = functools.partial(self.comm_round_mat,
                                               plan=plan)
        x_mat = plan.flatten(params)
        mats = self.mat_state(plan, state)

        if self.config.overlap:
            if not self.kernel_comm_supported:
                raise ValueError(
                    "overlap=True on the kernel path requires matrix-domain "
                    "gossip (kernel_comm_supported)")
            if overlap_begin_mat is None:
                overlap_begin_mat = functools.partial(self.overlap_begin_mat,
                                                      plan=plan)
            if overlap_apply_mat is None:
                overlap_apply_mat = self.overlap_apply_mat
            if overlap_refresh_mat is None and self.overlap_refreshes:
                overlap_refresh_mat = self.overlap_refresh_mat
            # round start: step = (r+1)·p, so r below is the payload round
            r = state["step"] // self.config.p - 1
            gate = (state["mix"]["phase"] > 0).astype(jnp.float32)
            with jax.named_scope(SCOPE_GOSSIP):
                delta = overlap_begin_mat(mats, r, gate)

            def body(carry, batch):
                x_mat, mats, step = carry
                with jax.named_scope(SCOPE_GRAD):
                    loss, grads = grads_fn(plan.unflatten(x_mat), batch)
                with jax.named_scope(SCOPE_LOCAL_STEP):
                    x_mat, mats = local_step_mat(x_mat, mats,
                                                 plan.flatten(grads), step)
                    if overlap_refresh_mat is not None:
                        mats = overlap_refresh_mat(mats, delta)
                return (x_mat, mats, step + 1), loss

            (x_mat, mats, step), losses = jax.lax.scan(
                body, (x_mat, mats, state["step"]), batches)
            if gossip:
                with jax.named_scope(SCOPE_GOSSIP):
                    x_mat, mats = overlap_apply_mat(x_mat, mats, delta,
                                                    step // self.config.p - 1)
            params = plan.unflatten(x_mat)
            state = self.unmat_state(plan, mats, state, step)
            if gossip:
                state = dict(state)
                state["mix"] = {**state["mix"],
                                "phase": jnp.ones((), jnp.int32)}
            return params, state, losses

        def body(carry, batch):
            x_mat, mats, step = carry
            with jax.named_scope(SCOPE_GRAD):
                loss, grads = grads_fn(plan.unflatten(x_mat), batch)
            with jax.named_scope(SCOPE_LOCAL_STEP):
                x_mat, mats = local_step_mat(x_mat, mats, plan.flatten(grads),
                                             step)
            return (x_mat, mats, step + 1), loss

        (x_mat, mats, step), losses = jax.lax.scan(
            body, (x_mat, mats, state["step"]), batches)

        if gossip and self.kernel_comm_supported:
            r = step // self.config.p - 1
            with jax.named_scope(SCOPE_GOSSIP):
                x_mat, mats = comm_round_mat(x_mat, mats, plan.row_counts(),
                                             r)
        params = plan.unflatten(x_mat)
        state = self.unmat_state(plan, mats, state, step)
        if gossip and not self.kernel_comm_supported:
            # e.g. CPD with a non-kernel compressor: tree comm at the boundary
            with jax.named_scope(SCOPE_GOSSIP):
                params, state = self.comm_round(state, params)
        return params, state, losses

    # -- comm-cost model ----------------------------------------------------------
    def _mat_wire_rows(self, params) -> int:
        """``used_rows`` wire extent of the kernel layout: Σ per-leaf
        ceil(size/1024) rows."""
        import numpy as np
        from repro.kernels import LANE
        return sum(-(-int(np.prod(l.shape, dtype=np.int64)) // LANE)
                   for l in jax.tree_util.tree_leaves(params))

    def _mat_wire_bytes(self, params) -> int:
        """Bytes of one neighbour exchange on the kernel layout: the
        ``used_rows`` wire extent (Σ per-leaf ceil(size/1024) rows × 1024)
        at the wire dtype — master copies stay f32 across the round, but a
        bf16 wire ships the neighbour payload at 2 B/elem."""
        from repro.kernels import LANE
        item = min(4, getattr(self.comm, "wire_itemsize", 4))
        return self._mat_wire_rows(params) * LANE * item

    def _kernel_wire_active(self) -> bool:
        return (self.config.use_kernel and self.kernel_comm_supported
                and self._mat_wire_static())

    def _kernel_hier_active(self) -> bool:
        """Whether the round gossips through ``HierarchicalComm.mix_mat``
        (kernel layout, static hierarchical graph) — the inter payload is
        then the ``(used_rows, 1024)`` matrix, not the leaf tree."""
        return (self.config.use_kernel and self.kernel_comm_supported
                and isinstance(self.comm, HierarchicalComm)
                and (self.comm.schedule is None or self.comm.period == 1))

    def hier_bytes_per_level(self, params, r: int = 0) -> dict:
        """Per-level byte split of one hierarchical round (see
        :func:`repro.core.gossip.hier_bytes_per_round`); on the kernel
        path the payload is the flatten-once ``used_rows × 1024`` matrix."""
        from repro.core.gossip import hier_bytes_per_round
        from repro.kernels import LANE
        payload = params
        if self._kernel_hier_active():
            payload = [jax.ShapeDtypeStruct(
                (self._mat_wire_rows(params) * LANE,), jnp.float32)]
        return hier_bytes_per_round(payload, self.comm, r=r)

    def bytes_per_comm_round(self, params, r: int = 0) -> int:
        from repro.core.gossip import gossip_bytes_per_round
        top = self.comm.topology_at(r)
        if top.name == "hierarchical" and self.comm.membership is None:
            return self.hier_bytes_per_level(params, r=r)["inter"]
        if self._kernel_wire_active():
            deg = self.comm.topology_at(r).degree
            return deg * self._mat_wire_bytes(params)
        return gossip_bytes_per_round(params, self.comm, r=r)

    def bytes_per_round_cycle(self, params) -> tuple:
        """Per-round bytes over one joint schedule × membership cycle
        (1-tuple when both static); the trainers accumulate these
        round-robin for comm-MB accounting.  Rounds where a worker is dead
        or straggling ship fewer bytes — dead edges count zero."""
        return tuple(self.bytes_per_comm_round(params, r=r)
                     for r in range(self.comm.round_cycle))
