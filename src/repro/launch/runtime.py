"""Distributed runtime: builds jit-able train / prefill / decode steps.

Structure of one training iteration (docs/ARCHITECTURE.md):

  1. per-worker forward+backward — ``vmap`` over the stacked worker dim, in
     the pjit/GSPMD domain (XLA inserts the tensor-parallel collectives and,
     in profile B, the FSDP all-gathers + within-worker gradient psums);
  2. the PD/CPD-SGDM optimizer step — wrapped in ``jax.shard_map`` so the
     gossip round lowers to explicit ``ppermute`` (collective-permute) over
     the worker axes, with the compressed payload bit-packed on the wire.

``TrainPack.train_round`` is the **canonical hot path**: one jitted call =
``lax.scan`` of p local steps + exactly one gossip round (``opt.round``
with the optimizer calls shard_mapped), buffers donated.  It is what
``repro.train.trainer.ShardedTrainer`` executes, and the honest unit for
the dry-run roofline: compute of p steps, communication of exactly one
gossip round.  ``train_step`` remains for per-step debugging and for runs
whose tail is shorter than a round.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelCfg, RunCfg
from repro.configs.shapes import InputShape, train_batch_specs
from repro.core import make_compressor, make_optimizer
from repro.core.gossip import DenseComm, HierarchicalComm, ShardedComm
from repro.core.topology import (disconnected, hierarchical, make_schedule,
                                 make_topology, torus)
from repro.launch.sharding import (Layout, batch_spec_tree, cache_spec_tree,
                                   make_layout, param_spec_tree, to_shardings)
from repro.models import make_model

__all__ = ["build_comm", "build_train", "build_serve", "TrainPack",
           "ServePack", "make_shd"]


def _smap(mesh):
    return functools.partial(jax.shard_map, mesh=mesh, check_vma=False)


def make_shd(layout: Layout, parallel):
    """Logical-axis sharding-constraint hook for the model (perf levers).

    Only active when a perf flag requests it — the baseline model runs with
    GSPMD propagation alone.  Names present in the rule table force a
    constraint (a None mapping = explicit replication over that dim).
    """
    rules = {}       # name -> (axis, priority); higher priority wins an axis
    if getattr(parallel, "attn_ctx_shard", False) and layout.tp_axis:
        # attention core: prefer head-sharded q (blockwise-safe: the chunk
        # scan slices seq, so a seq shard would reshard every chunk); fall
        # back to seq-sharded q when heads don't divide the tp axis
        # (e.g. arctic's 56 heads on 16).  k/v explicitly replicated.
        rules["heads"] = (layout.tp_axis, 2)
        rules["seq_q"] = (layout.tp_axis, 1)
        rules["seq_kv"] = (None, 0)
    if getattr(parallel, "moe_token_shard", False):
        if layout.fsdp_axis:
            rules["tokens"] = (layout.fsdp_axis, 2)
            rules["expert"] = (layout.fsdp_axis, 2)
            rules["group"] = (layout.fsdp_axis, 2)
        if layout.tp_axis:
            rules["mlp"] = (layout.tp_axis, 1)
    if not rules:
        return lambda x, *names: x
    mesh = layout.mesh

    def shd(x, *names):
        if not any(n in rules for n in names):
            return x
        spec = [None] * len(names)
        used = set()
        order = sorted(range(len(names)),
                       key=lambda i: -(rules.get(names[i], (None, -1))[1]))
        for i in order:
            n = names[i]
            if n not in rules:
                continue
            ax = rules[n][0]
            if (ax is None or ax in used or i >= x.ndim
                    or x.shape[i] % mesh.shape[ax] != 0):
                continue
            spec[i] = ax
            used.add(ax)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))

    return shd


# --------------------------------------------------------------------------- comm
def build_comm(run: RunCfg, layout: Layout, membership=None):
    """Topology (or topology schedule) + comm backend for the worker layout.

    ``parallel.topology_schedule != "static"`` selects a time-varying gossip
    graph: the ShardedComm precomputes every round's ppermute program and
    the fused round engine switches between them on the traced round index.
    ``membership`` (a ``MembershipSchedule``) masks dead/straggling workers
    out of each round's mixing matrix (elastic fleets).

    ``parallel.node_size > 0`` selects two-level gossip
    (:class:`HierarchicalComm`): exact intra-node averaging over groups of
    ``node_size`` workers + ``parallel.topology`` between node leaders
    (optionally codec-compressed via ``parallel.inter_codec``).  On a
    two-axis worker layout the inner axis is the node.
    """
    waxes = layout.worker_axes
    sizes = layout.worker_sizes
    wd = getattr(run.optim, "wire_dtype", "float32")
    if not waxes:
        return DenseComm(disconnected(1), membership=membership,
                         wire_dtype=wd)
    sched_name = getattr(run.parallel, "topology_schedule", "static")
    node_size = int(getattr(run.parallel, "node_size", 0) or 0)
    if node_size:
        K = int(layout.n_workers)
        if len(waxes) == 2:
            if node_size != sizes[1]:
                raise ValueError(
                    f"node_size {node_size} must equal the inner worker "
                    f"axis size {sizes[1]} on a two-axis layout "
                    f"{waxes}: the node boundary is the mesh axis")
        elif K % node_size != 0:
            raise ValueError(
                f"node_size {node_size} does not divide the worker count "
                f"{K}")
        n_nodes = K // node_size
        if sched_name in ("hier_one_peer", "hierarchical_one_peer"):
            first = make_schedule("hier_one_peer", (n_nodes, node_size))
        elif sched_name == "static":
            first = hierarchical(n_nodes, node_size,
                                 inter=run.parallel.topology)
        else:
            raise ValueError(
                f"topology_schedule {sched_name!r} does not compose with "
                "node_size (hierarchical rounds support 'static' and "
                "'hier_one_peer')")
        return HierarchicalComm(first, axis_names=waxes,
                                membership=membership, wire_dtype=wd,
                                inter_codec=_make_inter_codec(run))
    if sched_name != "static":
        sched = make_schedule(
            sched_name, sizes, base_topology=run.parallel.topology,
            rounds=run.parallel.schedule_rounds,
            seed=run.parallel.schedule_seed)
        return ShardedComm(sched, axis_names=waxes, membership=membership,
                           wire_dtype=wd)
    if len(waxes) == 1:
        topo = make_topology(run.parallel.topology, sizes)
    else:
        topo = torus(sizes)  # hierarchical pod×ring mixing
    return ShardedComm(topo, axis_names=waxes, membership=membership,
                       wire_dtype=wd)


def _make_inter_codec(run: RunCfg):
    """The keyless WireCodec for the hierarchical inter-node wire, from
    ``parallel.inter_codec`` (shape knobs shared with the compressor)."""
    from repro.core.wire import make_codec
    name = str(getattr(run.parallel, "inter_codec", "none")).lower()
    if name in ("none", ""):
        return None
    o = run.optim
    comp = make_compressor(
        name, **_compressor_kwargs(dataclasses.replace(o, compressor=name)))
    return make_codec(comp)


def _compressor_kwargs(o) -> dict:
    """OptimCfg knobs → the named compressor's constructor args."""
    name = o.compressor.lower()
    if name == "sign":
        return {"block": o.compressor_block}
    if name == "topk":
        return {"fraction": o.compressor_fraction,
                "block": o.compressor_block}
    if name == "randk":
        return {"fraction": o.compressor_fraction}
    if name == "qsgd":
        return {"levels": o.compressor_levels,
                "block": o.compressor_block}
    if name in ("sparse", "sparse_rows") or name.startswith("sparse+"):
        return {"max_rows": o.compressor_rows,
                "levels": o.compressor_levels,
                "block": o.compressor_block}
    return {}


def _make_optimizer(run: RunCfg, comm):
    o = run.optim
    # cpd/choco always ship a codec payload; mt ships the correction wire
    # compressed only when explicitly opted in (track_compressed)
    wants_comp = (o.name.startswith(("cpd", "choco"))
                  or (o.name.startswith("mt") and o.track_compressed))
    comp = make_compressor(o.compressor, **_compressor_kwargs(o)) if \
        wants_comp else None
    return make_optimizer(
        o.name, comm, eta=o.eta, mu=o.mu, p=o.p, gamma=o.gamma,
        weight_decay=o.weight_decay, compressor=comp,
        use_kernel=o.use_kernel, kernel_interpret=o.kernel_interpret,
        overlap=o.overlap)


# --------------------------------------------------------------------------- train
@dataclasses.dataclass
class TrainPack:
    model: object
    opt: object
    layout: Layout
    params_struct: object
    state_struct: object
    batch_struct: object
    params_sharding: object
    state_sharding: object
    batch_sharding: object
    init_fn: Callable             # (key) -> (params, opt_state)  [jit, sharded]
    train_step: Callable          # (params, state, batch) -> (params, state, loss)
    train_round: Callable         # (params, state, batches[p]) -> (..., losses)
    round_batch_struct: object
    round_batch_sharding: object


def build_train(run: RunCfg, mesh, shape: InputShape,
                model_cfg: Optional[ModelCfg] = None,
                membership=None) -> TrainPack:
    mcfg = model_cfg or run.model
    layout = make_layout(run.parallel, mesh)
    model = make_model(mcfg, shd=make_shd(layout, run.parallel))
    n_w = layout.n_workers
    comm = build_comm(run, layout, membership=membership)
    opt = _make_optimizer(run, comm)
    remat = run.parallel.remat
    p_round = run.optim.p

    # ---- structs
    def init_stacked(key):
        keys = jax.random.split(key, n_w)
        # all workers start from x0 (paper: x₀ identical) — fold_in worker id
        # only for data; params use the same key.
        return jax.vmap(lambda k: model.init(key))(keys)

    params_struct = jax.eval_shape(init_stacked, jax.random.PRNGKey(0))
    state_struct = jax.eval_shape(opt.init, params_struct)
    batch_struct = train_batch_specs(mcfg, shape, n_w)

    # ---- spec trees
    pspec = param_spec_tree(params_struct, layout, stacked_worker=True)
    sspec = _state_spec(state_struct, pspec)
    bspec = batch_spec_tree(batch_struct, layout)
    params_sh = to_shardings(pspec, mesh)
    state_sh = to_shardings(sspec, mesh)
    batch_sh = to_shardings(bspec, mesh)

    # ---- loss / grads (GSPMD domain)
    def loss_fn(p, b):
        loss, met = model.loss(p, b, remat=remat)
        return loss, met

    grad_fn = jax.vmap(jax.value_and_grad(loss_fn, has_aux=True))

    # ---- optimizer (manual / shard_map domain)
    def opt_full(p, s, g):
        return opt.step(s, p, g)

    def opt_local(p, s, g):
        return opt.local_step(s, p, g)

    def opt_comm(p, s):
        return opt.comm_round(s, p)

    smap = _smap(mesh)
    opt_full_sh = smap(opt_full, in_specs=(pspec, sspec, pspec),
                       out_specs=(pspec, sspec))
    opt_local_sh = smap(opt_local, in_specs=(pspec, sspec, pspec),
                        out_specs=(pspec, sspec))
    opt_comm_sh = smap(opt_comm, in_specs=(pspec, sspec),
                       out_specs=(pspec, sspec))

    def train_step(params, state, batch):
        (losses, mets), grads = grad_fn(params, batch)
        params, state = opt_full_sh(params, state, grads)
        return params, state, losses.mean()

    def gfn(p_, b):
        (losses, _mets), grads = grad_fn(p_, b)
        return losses.mean(), grads

    if run.optim.use_kernel and opt.kernel_comm_supported:
        # kernel execution path: the whole round runs on the flatten-once
        # (n_workers, rows, 1024) matrix — flatten/unflatten happen in the
        # GSPMD domain (the worker dim stays sharded over the worker axes;
        # inside shard_map each device sees its (1, rows, 1024) shard), and
        # only the matrix-domain optimizer calls enter the manual domain.
        from repro.kernels import ops as kops
        plan = kops.KernelPlan.for_tree(params_struct, worker_dim=True)
        mspec = P(layout.worker_axes or None, None, None)
        opt_local_mat_sh = smap(opt.local_step_mat,
                                in_specs=(mspec, mspec, mspec, P()),
                                out_specs=(mspec, mspec))
        opt_comm_mat_sh = smap(functools.partial(opt.comm_round_mat,
                                                 plan=plan),
                               in_specs=(mspec, mspec, P(), P()),
                               out_specs=(mspec, mspec))

        if run.optim.overlap:
            # overlapped rounds: the in-flight payload's exchange (the only
            # collective) is shard_mapped at round *start*; the stale
            # correction lands matrix-to-matrix after the scan.
            ob_mat_sh = smap(functools.partial(opt.overlap_begin_mat,
                                               plan=plan),
                             in_specs=(mspec, P(), P()), out_specs=mspec)
            oa_mat_sh = smap(opt.overlap_apply_mat,
                             in_specs=(mspec, mspec, mspec, P()),
                             out_specs=(mspec, mspec))
            orf_mat_sh = (smap(opt.overlap_refresh_mat,
                               in_specs=(mspec, mspec), out_specs=mspec)
                          if opt.overlap_refreshes else None)

            def train_round(params, state, batches):
                """Overlapped round on the kernel layout: exchange issued
                at round start, p momentum steps, stale mix landed."""
                return opt.kernel_round(
                    state, params, gfn, batches,
                    local_step_mat=opt_local_mat_sh,
                    comm_round_mat=opt_comm_mat_sh,
                    overlap_begin_mat=ob_mat_sh,
                    overlap_apply_mat=oa_mat_sh,
                    overlap_refresh_mat=orf_mat_sh)
        else:
            def train_round(params, state, batches):
                """p momentum steps + one gossip, all on the kernel
                layout."""
                return opt.kernel_round(
                    state, params, gfn, batches,
                    local_step_mat=opt_local_mat_sh,
                    comm_round_mat=opt_comm_mat_sh)
    elif run.optim.overlap:
        dspec = {k: pspec for k in opt.overlap_delta_keys}
        ob_sh = smap(opt.overlap_begin, in_specs=(sspec,), out_specs=dspec)
        oa_sh = smap(opt.overlap_apply,
                     in_specs=(sspec, pspec, dspec),
                     out_specs=(pspec, sspec))
        orf_sh = (smap(opt.overlap_step_refresh, in_specs=(sspec, dspec),
                       out_specs=sspec)
                  if opt.overlap_refreshes else None)

        def train_round(params, state, batches):
            """Overlapped round: the in-flight payload's gossip (the only
            ppermutes) issues at round start with no data dependence on
            the p-step scan; the one-round-stale correction lands at the
            round's end (``opt.round`` owns the structure, the optimizer
            calls are shard_mapped exactly like the synchronous path)."""
            return opt.round(
                state, params, gfn, batches,
                local_step=lambda s, p_, g: opt_local_sh(p_, s, g),
                overlap_begin=ob_sh, overlap_apply=oa_sh,
                overlap_refresh=orf_sh)
    else:
        def train_round(params, state, batches):
            """p local momentum steps then exactly one gossip round.

            The scan structure lives in ``opt.round``; only the optimizer
            calls are shard_mapped into the manual domain (the forward/
            backward stays in the GSPMD domain).
            """
            return opt.round(
                state, params, gfn, batches,
                local_step=lambda s, p_, g: opt_local_sh(p_, s, g),
                comm_round=lambda s, p_: opt_comm_sh(p_, s))

    round_batch_struct = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((p_round,) + s.shape, s.dtype),
        batch_struct)
    round_batch_sh = jax.tree_util.tree_map(
        lambda sh: NamedSharding(mesh, P(None, *sh.spec)), batch_sh)

    def init_fn(key):
        params = init_stacked(key)
        return params, opt.init(params)

    jit_init = jax.jit(init_fn, out_shardings=(params_sh, state_sh))
    jit_step = jax.jit(train_step,
                       in_shardings=(params_sh, state_sh, batch_sh),
                       out_shardings=(params_sh, state_sh, None),
                       donate_argnums=(0, 1))
    jit_round = jax.jit(train_round,
                        in_shardings=(params_sh, state_sh, round_batch_sh),
                        out_shardings=(params_sh, state_sh, None),
                        donate_argnums=(0, 1))

    return TrainPack(
        model=model, opt=opt, layout=layout,
        params_struct=params_struct, state_struct=state_struct,
        batch_struct=batch_struct,
        params_sharding=params_sh, state_sharding=state_sh,
        batch_sharding=batch_sh,
        init_fn=jit_init, train_step=jit_step, train_round=jit_round,
        round_batch_struct=round_batch_struct,
        round_batch_sharding=round_batch_sh)


def _state_spec(state_struct, pspec):
    """Optimizer-state specs: per-element trees (momentum, CPD's x̂,
    MT's tracking c / ĝ_prev, QG's xprev) mirror params; step replicated."""
    def build(struct, like):
        out = {}
        for k, v in struct.items():
            if k == "step":
                out[k] = P()
            elif k in ("m", "xhat", "c", "g_prev", "xprev"):
                out[k] = like
            elif k == "xhat_nbrs":
                out[k] = {kk: like for kk in v}
            elif k == "mix":
                # DelayedMixState (overlap=True): in-flight payload trees
                # (buf, MT's buf_c) mirror params; the staleness phase is a
                # replicated scalar
                out[k] = {kk: (P() if kk == "phase" else like)
                          for kk in v}
            else:
                raise KeyError(k)
        return out

    return build(state_struct, pspec)


# --------------------------------------------------------------------------- serve
@dataclasses.dataclass
class ServePack:
    model: object
    layout: Layout
    params_struct: object
    cache_struct: object
    pre_struct: object
    params_sharding: object
    cache_sharding: object
    prefill_step: Callable
    decode_step: Callable
    batch: int
    max_len: int


def build_serve(run: RunCfg, mesh, shape: InputShape,
                model_cfg: Optional[ModelCfg] = None) -> ServePack:
    mcfg = model_cfg or run.model
    layout = make_layout(run.parallel, mesh, serving=True)
    model = make_model(mcfg, shd=make_shd(layout, run.parallel))
    b, s = shape.global_batch, shape.seq_len

    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache_struct = jax.eval_shape(
        functools.partial(model.init_cache, b, s))
    pspec = param_spec_tree(params_struct, layout, stacked_worker=False)
    cspec = cache_spec_tree(cache_struct, layout, b)
    params_sh = to_shardings(pspec, mesh)
    cache_sh = to_shardings(cspec, mesh)

    from repro.configs.shapes import _batch_struct
    pre_struct = _batch_struct(mcfg, b, s, with_labels=False)
    pre_spec = {k: P(layout.batch_axes or None,
                     *([None] * (len(v.shape) - 1)))
                for k, v in pre_struct.items()}
    if b % max(1, math.prod(layout.axis_size(a)
                            for a in layout.batch_axes)) != 0:
        pre_spec = {k: P(*([None] * len(v.shape)))
                    for k, v in pre_struct.items()}
    pre_sh = to_shardings(pre_spec, mesh)

    def prefill_step(params, batch):
        return model.prefill_fast(params, batch, max_len=s)

    def decode_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos,
                                 max_positions=s)

    tok_spec = P(layout.batch_axes or None)
    if b % max(1, math.prod(layout.axis_size(a)
                            for a in layout.batch_axes)) != 0:
        tok_spec = P()
    tok_sh = NamedSharding(mesh, tok_spec)
    scalar_sh = NamedSharding(mesh, P())

    jit_prefill = jax.jit(prefill_step,
                          in_shardings=(params_sh, pre_sh),
                          out_shardings=(None, cache_sh))
    jit_decode = jax.jit(decode_step,
                         in_shardings=(params_sh, cache_sh, tok_sh,
                                       scalar_sh),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(1,))

    return ServePack(
        model=model, layout=layout,
        params_struct=params_struct, cache_struct=cache_struct,
        pre_struct=pre_struct,
        params_sharding=params_sh, cache_sharding=cache_sh,
        prefill_step=jit_prefill, decode_step=jit_decode,
        batch=b, max_len=s)
