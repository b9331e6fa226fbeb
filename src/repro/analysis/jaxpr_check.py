"""jaxpr-level round-contract checks: structural invariants on traces.

The fused round (``PDSGDM.round`` / ``kernel_round`` / the runtime's
``train_round``) promises: p local steps inside one ``lax.scan``, exactly
one gossip exchange at the round boundary, no host callbacks, no float64
operands (``core.topology``'s f64 spectral math must stay on the host), a
single flatten at the kernel-path boundary, and — under a topology
schedule — one ``lax.switch`` whose branch count is the schedule period.
Every check here walks a ``jax.make_jaxpr`` trace; nothing executes.

All checks return a list of human-readable violation strings (empty =
contract holds) so the CLI driver can aggregate across the optimizer ×
backend × codec grid; ``require`` turns them into an exception for tests.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["ContractViolation", "require", "iter_eqns", "collective_eqns",
           "check_no_host_callbacks", "check_no_f64", "check_round_scan",
           "check_gossip_boundary", "check_overlap_boundary",
           "check_schedule_switch",
           "check_kernel_flatten_once", "check_membership_mask",
           "traced_mixing_matrix", "trace_round", "check_round_contract"]

# primitives that move data across workers inside shard_map.  (GSPMD-domain
# collectives never appear in a jaxpr — XLA inserts them at partitioning —
# so any collective eqn here is an explicit gossip/exchange op.)
COLLECTIVE_PRIMS = frozenset({
    "ppermute", "pshuffle", "psum", "pmax", "pmin", "pmean", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter",
})
# host-callback primitives: a round containing one cannot be async-dispatched
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
})


class ContractViolation(AssertionError):
    """One or more round-contract checks failed."""

    def __init__(self, violations: List[str]):
        self.violations = list(violations)
        super().__init__("\n".join(self.violations))


def require(violations: List[str]) -> None:
    """Raise :class:`ContractViolation` unless ``violations`` is empty."""
    if violations:
        raise ContractViolation(violations)


# --------------------------------------------------------------------- walking
def _sub_jaxprs(eqn):
    """The jaxprs nested in an eqn's params (scan/cond/pjit/shard_map/...)."""
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if hasattr(x, "jaxpr"):      # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):     # raw Jaxpr
                yield x


def iter_eqns(jaxpr, _scan_depth: int = 0):
    """Yield ``(eqn, scan_depth)`` for every eqn, recursing into sub-jaxprs.

    ``scan_depth`` counts enclosing ``scan`` bodies — the round contract
    distinguishes "inside the p-step scan" from "at the round boundary".
    """
    for eqn in jaxpr.eqns:
        yield eqn, _scan_depth
        inner = _scan_depth + (1 if eqn.primitive.name == "scan" else 0)
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, inner)


def _closed(fn_or_jaxpr):
    return getattr(fn_or_jaxpr, "jaxpr", fn_or_jaxpr)


def _where(eqn) -> str:
    """Best-effort user source location of an eqn."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        pass
    return "<unknown>"


def collective_eqns(jaxpr) -> List[Tuple[object, int]]:
    """All cross-worker collective eqns with their scan depth."""
    return [(eqn, d) for eqn, d in iter_eqns(_closed(jaxpr))
            if eqn.primitive.name in COLLECTIVE_PRIMS]


# ---------------------------------------------------------------------- checks
def check_no_host_callbacks(jaxpr) -> List[str]:
    """Zero host callbacks anywhere in the round (a callback in the scan
    body forces a device→host sync every local step)."""
    out = []
    for eqn, depth in iter_eqns(_closed(jaxpr)):
        if eqn.primitive.name in CALLBACK_PRIMS:
            out.append(f"host callback `{eqn.primitive.name}` in the round "
                       f"(scan depth {depth}) at {_where(eqn)}")
    return out


def check_no_f64(jaxpr) -> List[str]:
    """Zero float64 operands or outputs in the traced round.

    Trace the round under ``jax_enable_x64`` before calling this: the
    default config silently truncates f64 leaks (e.g. a numpy float64
    mixing weight, or an ambient-precision python scalar) to f32, hiding
    the bug until someone flips x64 on — tracing with x64 enabled makes
    the leak visible as a genuine f64 aval.
    """
    out = []
    for eqn, _ in iter_eqns(_closed(jaxpr)):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and dtype == jnp.float64:
                out.append(f"float64 operand {aval.str_short()} in "
                           f"`{eqn.primitive.name}` at {_where(eqn)}")
                break
    return out


def check_round_scan(jaxpr, p: int) -> List[str]:
    """Exactly one top-level ``lax.scan`` of length p (the fused local
    loop) — no per-step python dispatch, no nested accidental scans of p."""
    closed = _closed(jaxpr)
    tops = []

    def top_scans(jxp):
        # descend through non-scan wrappers (pjit/shard_map/cond) so the
        # "top level" is the round body regardless of jit nesting; pallas
        # internals (interpret-mode grid loops) are not round structure
        for eqn in jxp.eqns:
            if eqn.primitive.name == "scan":
                tops.append(eqn)
            elif "pallas" not in eqn.primitive.name:
                for sub in _sub_jaxprs(eqn):
                    top_scans(sub)

    top_scans(closed)
    lengths = [int(e.params.get("length", -1)) for e in tops]
    if lengths.count(p) != 1:
        return [f"expected exactly one round scan of length p={p}, found "
                f"scan lengths {lengths or 'none'}"]
    return []


def check_gossip_boundary(jaxpr, *, expected: Optional[int] = None,
                          allowed=("ppermute", "pmean", "psum")) -> List[str]:
    """Every collective sits at the round boundary (scan depth 0) — the
    paper's one-exchange-per-round structure — and only expected kinds
    appear.  ``expected`` additionally pins the exact ppermute count
    (degree × wire arrays per exchange)."""
    out = []
    colls = collective_eqns(jaxpr)
    for eqn, depth in colls:
        if depth > 0:
            out.append(f"collective `{eqn.primitive.name}` inside the round "
                       f"scan (depth {depth}) at {_where(eqn)} — gossip must "
                       "happen once at the round boundary")
        if eqn.primitive.name not in allowed:
            out.append(f"unexpected collective `{eqn.primitive.name}` at "
                       f"{_where(eqn)} (allowed: {sorted(allowed)})")
    if expected is not None:
        n_perm = sum(1 for eqn, _ in colls
                     if eqn.primitive.name == "ppermute")
        if n_perm != expected:
            out.append(f"expected {expected} ppermute(s) per round, "
                       f"found {n_perm}")
    return out


def check_overlap_boundary(jaxpr, *, p: int,
                           expected: Optional[int] = None,
                           allowed=("ppermute", "pmean", "psum")) -> List[str]:
    """Overlapped-round contract: every collective is *issued before* the
    p-step local scan — in program order the exchange precedes the first
    scan of length p, proving the stale payload has no data dependence on
    the round's local steps (the transfer can hide behind compute).  As
    in the sync contract, collectives must sit at scan depth 0, only
    expected kinds appear, and ``expected`` pins the ppermute count (the
    wire is byte-identical to a sync round — only its timing moves)."""
    out = []
    seen_scan = False
    n_perm = 0
    for eqn, depth in iter_eqns(_closed(jaxpr)):
        name = eqn.primitive.name
        if name == "scan" and int(eqn.params.get("length", -1)) == p:
            seen_scan = True
        if name not in COLLECTIVE_PRIMS:
            continue
        if depth > 0:
            out.append(f"collective `{name}` inside the round scan (depth "
                       f"{depth}) at {_where(eqn)} — overlap gossip must be "
                       "issued once at the round start")
        elif seen_scan:
            out.append(f"collective `{name}` after the local scan at "
                       f"{_where(eqn)} — overlap requires every exchange "
                       "issued before the p-step scan (scan-independent "
                       "payload)")
        if name not in allowed:
            out.append(f"unexpected collective `{name}` at {_where(eqn)} "
                       f"(allowed: {sorted(allowed)})")
        if name == "ppermute":
            n_perm += 1
    if expected is not None and n_perm != expected:
        out.append(f"expected {expected} ppermute(s) per overlap round, "
                   f"found {n_perm}")
    return out


def traced_mixing_matrix(comm, r: int):
    """The (K, K) matrix the dense round-``r`` gossip *actually applies*,
    extracted by pushing identity probe leaves through ``comm.mix`` —
    reading the executed computation, not the backend's weight tables, so
    a table/trace mismatch is visible."""
    import numpy as np
    K = comm.topology_at(r).n_workers
    probe = {"e": jnp.eye(K, dtype=jnp.float32)}
    return np.asarray(jax.jit(lambda t: comm.mix(t, r=r))(probe)["e"])


def check_membership_mask(comm, rounds=None) -> List[str]:
    """Elastic-membership mask semantics on the *traced* dense mixing.

    For every round in the membership cycle (or ``rounds``): the applied
    matrix must be row-stochastic, a masked-out worker must hold exactly
    the identity row e_k (its exchange skipped, self-weight 1), and no
    active worker may read from a masked-out peer (zero dead columns) —
    a round gossiping with a dead worker is a contract violation.
    """
    import numpy as np
    ms = comm.membership
    if ms is None:
        return []
    out = []
    for r in (range(comm.round_cycle) if rounds is None else rounds):
        W = traced_mixing_matrix(comm, r)
        act = np.asarray(comm.active_at(r), dtype=bool)
        K = W.shape[0]
        bad_rows = np.flatnonzero(np.abs(W.sum(axis=1) - 1.0) > 1e-5)
        for k in bad_rows:
            out.append(f"round {r}: row {k} of the applied mixing matrix "
                       f"sums to {W[k].sum():.6f}, not 1 (renormalization "
                       "over live peers broken)")
        for k in np.flatnonzero(~act):
            if np.abs(W[k] - np.eye(K)[k]).max() > 1e-6:
                out.append(f"round {r}: masked-out worker {k} still "
                           "gossips (row != e_k)")
        dead_cols = W[np.ix_(act, ~act)]
        if dead_cols.size and np.abs(dead_cols).max() > 1e-6:
            i, j = np.unravel_index(np.abs(dead_cols).argmax(),
                                    dead_cols.shape)
            src = np.flatnonzero(~act)[j]
            dst = np.flatnonzero(act)[i]
            out.append(f"round {r}: active worker {dst} reads weight "
                       f"{dead_cols[i, j]:.6f} from masked-out worker "
                       f"{src} (dead column must be zero)")
    return out


def check_dense_no_collectives(jaxpr) -> List[str]:
    """The DenseComm simulation backend must trace to zero collectives —
    its gossip is a W-matmul over the stacked worker dim."""
    return [f"collective `{eqn.primitive.name}` in a DenseComm round at "
            f"{_where(eqn)}" for eqn, _ in collective_eqns(jaxpr)]


def check_schedule_switch(jaxpr, period: int) -> List[str]:
    """Under a topology schedule the per-round ppermute program is selected
    by one ``lax.switch`` whose branch count equals the schedule period —
    all T collective patterns live in a single trace (no retracing)."""
    branch_counts = [len(eqn.params["branches"])
                     for eqn, _ in iter_eqns(_closed(jaxpr))
                     if eqn.primitive.name == "cond"
                     and len(eqn.params.get("branches", ())) > 2]
    if period <= 2:
        return []     # a 2-branch switch is indistinguishable from lax.cond
    if period not in branch_counts:
        return [f"no lax.switch with {period} branches (schedule period); "
                f"found multi-way branch counts {branch_counts or 'none'}"]
    return []


def check_kernel_flatten_once(jaxpr, plan, p: int) -> List[str]:
    """The kernel path flattens the pytree into the (rows, LANE) matrix
    once at the round boundary: the round scan's carry must hold the plan
    matrix (params + every per-element state mat ride the carry in matrix
    form, not as leaf trees)."""
    from repro.kernels import LANE
    closed = _closed(jaxpr)
    scan = next((eqn for eqn, d in iter_eqns(closed)
                 if eqn.primitive.name == "scan"
                 and int(eqn.params.get("length", -1)) == p), None)
    if scan is None:
        return [f"kernel round: no scan of length p={p} found"]
    n_carry = int(scan.params.get("num_carry", 0))
    carry_avals = [v.aval for v in scan.invars[:n_carry]
                   if hasattr(v, "aval")]
    mat_shapes = [a.shape for a in carry_avals
                  if getattr(a, "ndim", 0) >= 2 and a.shape[-1] == LANE
                  and a.shape[-2] == plan.rows]
    if not mat_shapes:
        return [f"kernel round scan carry holds no (…, {plan.rows}, {LANE}) "
                "plan matrix — the flatten-once layout is not riding the "
                "scan carry"]
    return []


# ---------------------------------------------------------------- round tracing
def toy_params(n_workers: int, sizes=(1500, 96), dense: bool = True):
    """A tiny worker-stacked param tree (f32, explicit dtypes)."""
    shape = (lambda s: (n_workers, s)) if dense else (lambda s: (s,))
    return {f"w{i}": jnp.zeros(shape(s), jnp.float32)
            for i, s in enumerate(sizes)}


def toy_grads_fn(params, batch):
    """loss, grads ≡ something cheap and f32-pure for tracing."""
    loss = sum(jnp.sum(l * l) for l in jax.tree_util.tree_leaves(params))
    grads = jax.tree_util.tree_map(lambda l: l + batch.mean(), params)
    return loss.astype(jnp.float32), grads


def trace_round(opt, params, p: int, *, kernel: bool = False, x64: bool = False,
                grads_fn: Callable = toy_grads_fn):
    """``jax.make_jaxpr`` of one fused round (no execution, no devices).

    ``x64=True`` traces under ``jax_enable_x64`` so latent f64 operands
    surface as real f64 avals (see :func:`check_no_f64`).
    """
    state = opt.init(params)
    n_w = next(iter(jax.tree_util.tree_leaves(params))).shape[0]
    batches = jnp.zeros((p, n_w, 4), jnp.float32)

    def one_round(params, state, batches):
        if kernel:
            return opt.kernel_round(state, params, grads_fn, batches)
        return opt.round(state, params, grads_fn, batches)

    with jax.enable_x64(x64):
        return jax.make_jaxpr(one_round)(params, state, batches)


# ------------------------------------------------------------------ aggregate
def check_round_contract(opt, params, *, kernel: bool = False,
                         schedule_period: Optional[int] = None,
                         expected_ppermutes: Optional[int] = None,
                         dense: bool = True,
                         overlap: bool = False) -> List[str]:
    """Run every applicable jaxpr check on one optimizer round trace.

    ``dense=True`` (the DenseComm simulation) additionally requires zero
    collectives; sharded traces (built elsewhere, inside shard_map) pass
    ``dense=False`` with an ``expected_ppermutes`` count instead.
    ``overlap=True`` swaps the boundary check for the overlapped-round
    variant: collectives precede the p-step scan instead of following it
    (dense overlap still requires zero collectives — stricter).
    """
    p = opt.config.p
    jx = trace_round(opt, params, p, kernel=kernel)
    out = []
    out += check_no_host_callbacks(jx)
    out += check_round_scan(jx, p)
    if dense:
        out += check_dense_no_collectives(jx)
    elif overlap:
        out += check_overlap_boundary(jx, p=p, expected=expected_ppermutes)
    else:
        out += check_gossip_boundary(jx, expected=expected_ppermutes)
    if schedule_period is not None:
        out += check_schedule_switch(jx, schedule_period)
    if kernel:
        from repro.kernels import ops as kops
        plan = kops.KernelPlan.for_tree(params, worker_dim=True)
        out += check_kernel_flatten_once(jx, plan, p)
    if dense and getattr(opt.comm, "membership", None) is not None:
        out += check_membership_mask(opt.comm)
    # f64 needs its own trace under the x64 config
    out += check_no_f64(trace_round(opt, params, p, kernel=kernel, x64=True))
    return out
