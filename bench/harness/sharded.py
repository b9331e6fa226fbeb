"""Driver ``sharded``: the launcher's path, ``TrainPack.train_round``.

The run config comes from ``repro.launch.train.run_config`` with the
configuration's model and deployment and the traffic's optimizer; the pack
from ``repro.launch.runtime.build_train`` on a (workers, 1) mesh, one
worker per chip.  Set-up compiles ``train_round`` alone, makes the weights
from the seed, and drives that compiled round through the first three
rounds on the window's own feed; the window continues it.  The host loop
is ``ShardedTrainer.train``'s: the round's batch, dispatch, no sync until
the window ends.
"""
from __future__ import annotations

import dataclasses
import time

from . import common, traffic

CHECK_ROUNDS = 3          # rounds the reference follows


def build(cell: dict, devices):
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.shapes import InputShape
    from repro.launch import train as launch
    from repro.launch.runtime import build_train

    cfg, tr = cell["config_spec"], cell["traffic_spec"]
    dep, data, opt = cfg["deployment"], tr["data"], tr["optimizer"]
    k = int(dep["workers"])
    argv = ["--arch", cfg["program"]["arch"], "--optimizer", opt["name"],
            "--p", str(opt["p"]), "--eta", str(opt["eta"]),
            "--topology", dep["topology"], "--wire-dtype", dep["wire_dtype"],
            "--seq-len", str(data["seq_len"]),
            "--global-batch", str(k * int(data["per_worker_batch"]))]
    if opt.get("use_kernel"):
        argv.append("--use-kernel")
    run = launch.run_config(launch.parse_args(argv))
    run = dataclasses.replace(
        run,
        model=dataclasses.replace(run.model, **cfg["model"]),
        optim=dataclasses.replace(run.optim, mu=float(opt["mu"]),
                                  weight_decay=float(opt["weight_decay"])),
        parallel=dataclasses.replace(run.parallel,
                                     remat=cfg["program"]["remat"]))
    mesh = Mesh(np.asarray(devices[:k]).reshape(k, 1), ("data", "model"))
    pack = build_train(run, mesh, InputShape(
        cell["name"], int(data["seq_len"]), k * int(data["per_worker_batch"]),
        "train"))
    return run, mesh, pack


def param_maker(ref, struct, stacked: bool):
    """``make(key)``: the benchmark's weights in the program's tree, every
    worker starting from the same x0 when ``stacked``."""
    import jax
    import jax.numpy as jnp
    paths, treedef = jax.tree_util.tree_flatten_with_path(struct)

    def make(key):
        leaves = []
        for i, (path, s) in enumerate(paths):
            names = tuple(getattr(p, "key", getattr(p, "idx", None))
                          for p in path)
            shape = s.shape[1:] if stacked else s.shape
            leaf = ref.init_leaf(names, shape, jax.random.fold_in(key, i),
                                 s.dtype)
            leaves.append(jnp.broadcast_to(leaf[None], s.shape)
                          if stacked else leaf)
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return make


def run(cell: dict, seed: int, seconds: float, trace_on: bool, devices,
        on_window=None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from references import optim as ref_optim

    ref = common.reference_module(cell)
    cfg, tr = cell["config_spec"], cell["traffic_spec"]
    data, opt = tr["data"], tr["optimizer"]
    k, p = int(cfg["deployment"]["workers"]), int(opt["p"])
    vocab = int(cfg["model"]["vocab"])
    devices = devices[:k]
    _run, _mesh, pack = build(cell, devices)
    key = common.seed_key(seed)
    wkey = jax.random.fold_in(key, 1)

    make_params = param_maker(ref, pack.params_struct, stacked=True)
    params = jax.jit(make_params, out_shardings=pack.params_sharding)(wkey)
    state = jax.jit(pack.opt.init, out_shardings=pack.state_sharding)(params)
    feed = jax.jit(lambda kk, r: traffic.round_batch(data, kk, r, p, k,
                                                     vocab),
                   out_shardings=pack.round_batch_sharding)
    batch = feed(key, 0)
    compiled = pack.train_round.lower(params, state, batch).compile()
    norms = jax.jit(ref_optim.worker_leaf_norms)
    dx_norms = jax.jit(lambda x, kk: ref_optim.worker_leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a.astype(jnp.float32)
                               - b.astype(jnp.float32), x, make_params(kk))))

    # the first rounds, through the window's own call and feed: what the
    # check compares, and the round time that sizes the window
    losses, round_s = [], []
    for r in range(CHECK_ROUNDS):
        t0 = time.perf_counter()
        params, state, lv = compiled(params, state, feed(key, r))
        losses.append(lv)
        if r == 0:
            m_first = jax.device_get(norms(state["m"]))
        else:
            jax.block_until_ready(params)
            round_s.append(time.perf_counter() - t0)
    dx = jax.device_get(dx_norms(params, wkey))
    prog = {"losses": [float(v) for v in jax.device_get(
        jnp.concatenate(losses))], "m_first": list(m_first), "dx": list(dx)}
    n_rounds = max(2, round(seconds / min(round_s)))

    def window():
        nonlocal params, state
        out = []
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for r in range(CHECK_ROUNDS, CHECK_ROUNDS + n_rounds):
                with jax.profiler.TraceAnnotation("bench.feed"):
                    b = feed(key, r)
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    params, state, lv = compiled(params, state, b)
                out.append(lv)
            with jax.profiler.TraceAnnotation("bench.sync"):
                jax.block_until_ready((params, state, out))
        return out, time.perf_counter() - t0

    setup_s = time.perf_counter() - common.T_START
    (win_losses, window_s), rec = on_window(window) if trace_on \
        else (window(), None)
    per_round = jax.device_get(jnp.stack(win_losses))
    failed = sum(1 for row in per_round if not all(common.finite(row)))
    device = common.device_info(devices)

    # free the program's state before the reference runs
    pstruct = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
        pack.params_struct)
    for leaf in jax.tree_util.tree_leaves((params, state, batch)):
        leaf.delete()
    del compiled, pack

    spec = {"model": cfg["model"], "optimizer": opt, "workers": k,
            "topology": cfg["deployment"]["topology"]}
    one = param_maker(ref, pstruct, stacked=False)
    gen = jax.jit(lambda kk, r: traffic.round_batch(data, kk, r, p, k,
                                                    vocab))

    def x0_fn(dev):
        return jax.jit(one, out_shardings=SingleDeviceSharding(dev))(wkey)

    return {
        "items": n_rounds * p * traffic.items_per_step(data, k),
        "rounds": n_rounds, "failed": failed, "window_s": window_s,
        "setup_s": setup_s, "device": device, "prog": prog,
        "trace": rec,
        "reference": lambda variant=None: ref.run(
            spec, x0_fn, lambda r: gen(key, r), devices,
            calls=CHECK_ROUNDS, rounds_per_call=1, variant=variant),
    }
