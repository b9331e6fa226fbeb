"""Smoke run of the decentralized trainer on a TPU.

    python chip_smoke.py            # one chip: phases `train` and `kernels`
    python chip_smoke.py --chips 4  # four chips: phase `multichip` only

Phases:

* ``train`` — the launcher's path (``repro.launch.train``: ``run_config``
  → ``build_train`` → ``ShardedTrainer``) for OLMo-1B at its published
  widths (16 layers, d_model 2048, bf16) on a 1×1 mesh: PD-SGDM, p = 4,
  seq 2048, one sequence per step, a warm-up round plus three timed ones.
* ``kernels`` — (a) every ``repro.kernels.ops`` matrix op run natively on
  the (rows, 1024) layout of one full-width OLMo-1B block, against its
  ``repro.kernels.ref`` oracle; (b) the README quickstart's ``SimTrainer`` +
  ``DenseComm(ring(8))`` on the paper's ResNet-20, eight workers stacked on
  the chip, each optimizer and codec on the kernel path against the same
  run on the tree path.
* ``multichip`` — ``build_train`` on a (4, 1) mesh, one worker per chip,
  gossip as ``collective-permute``, against the dense simulation of the
  same rounds (``DenseComm``, W-matmul) from the same seed and batches.

Times printed are smoke timings, not benchmarks.  Nothing catches a
phase's exception: any failure exits non-zero.  A run that finds no TPU
exits non-zero at once and never falls back to the CPU.  The last line of
stdout is one JSON object: ``{"ok": true, "device": {...}}``.
"""
import argparse
import dataclasses
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

SEED = 0
P = 4                     # local steps per round
OLMO_SEQ = 2048
RESNET_WORKERS = 8
RESNET_BATCH = 16         # per worker (the paper's CIFAR-10 setting)
RESNET_ROUNDS = 2
# ResNet-20's first rounds on a TPU amplify rounding: its f32 convolutions
# run on bf16 passes, so one ulp of x₀ moves the parameters after 2 rounds
# by 7.7e-5 at η = 0.001 and 0.057 at the paper's η = 0.1 (CPU, batch 2).
# The kernel-vs-tree comparison runs at the small η, and its tolerance is
# that one-ulp spread, measured in the same run, times a margin.
RESNET_ETA = 1e-3
ULP_MARGIN = 2.0
MULTICHIP_LAYERS = 2      # depth cut of the four-chip phase
MULTICHIP_SEQ = 2048
MULTICHIP_ROUNDS = 2
# sharded ≡ dense simulation (the slow tier, tests/test_sharded.py)
TOL_SHARDED = {"pd_sgdm": 5e-4, "cpd_sgdm": 8e-3}


def log(msg):
    print(msg, flush=True)


def _start():
    """Import the repository's package and JAX; refuse to run off-TPU."""
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke: no src/repro beside {__file__}: run it from "
                 "a checkout of the repository")
    sys.path.insert(0, src)
    import jax
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX backend is "
                 f"{jax.default_backend()!r}); this smoke run needs a TPU "
                 "and does not fall back to the CPU")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    return jax


class Checks:
    """Every comparison of the run is printed; any that fails makes the
    run exit non-zero at its end, before the result line."""

    def __init__(self):
        self.failed = []

    def expect(self, ok, name, detail=""):
        log(f"  {name}: {detail or 'yes'}{'' if ok else '  <- FAILED'}")
        if not ok:
            self.failed.append(name)

    def within(self, name, diff, tol):
        self.expect(diff <= tol, name, f"max |diff| {diff!r} (tol {tol!r})")

    def kernel_lowered(self, compiled, what):
        self.expect("tpu_custom_call" in compiled.as_text(),
                    f"{what} contains tpu_custom_call")


def _max_abs_diff(a, b):
    import jax
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ------------------------------------------------------------------ train
def phase_train(jax, checks):
    from repro.configs.shapes import InputShape
    from repro.launch import train as launch
    from repro.launch.mesh import make_mesh
    from repro.launch.runtime import build_train
    from repro.train.trainer import ShardedTrainer

    rounds = 4                                   # warm-up + 3 timed
    args = launch.parse_args([
        "--arch", "olmo-1b", "--optimizer", "pd_sgdm", "--p", str(P),
        "--seq-len", str(OLMO_SEQ), "--global-batch", "1",
        "--steps", str(rounds * P)])
    run = launch.run_config(args)
    m = run.model
    log(f"[train] {m.name}: {m.n_layers} layers, d_model {m.d_model}, "
        f"{m.n_heads} heads, d_ff {m.d_ff}, vocab {m.vocab}, "
        f"{m.param_dtype}; {run.optim.name} p={run.optim.p} "
        f"seq {args.seq_len}, {args.global_batch} sequence/step")
    mesh = make_mesh((1, 1), ("data", "model"))
    pack = build_train(run, mesh, InputShape("smoke", args.seq_len,
                                             args.global_batch, "train"))
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    round_s = []
    inner = pack.train_round

    def timed_round(params, state, batches):
        t0 = time.perf_counter()
        out = jax.block_until_ready(inner(params, state, batches))
        round_s.append(time.perf_counter() - t0)
        return out

    trainer = ShardedTrainer(dataclasses.replace(pack,
                                                 train_round=timed_round))
    with mesh:
        out = trainer.train(jax.random.PRNGKey(SEED),
                            launch.batch_fn_for(run, 1, args), args.steps,
                            log_every=1, verbose=False)
    loss = out["history"].loss
    log(f"[train] loss step 0 {loss[0]!r}, step {len(loss) - 1} "
        f"{loss[-1]!r}")
    checks.expect(math.isfinite(loss[0]) and math.isfinite(loss[-1]),
                  "train losses finite")
    log(f"[train] backend compile seconds (all programs): "
        f"{sum(compile_s)!r}")
    log(f"[train] warm-up round incl. compile {round_s[0]!r} s; smoke "
        f"timing (not a benchmark) of the next {len(round_s) - 1} rounds, "
        f"each ended with block_until_ready: {round_s[1:]!r} s")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[train] peak_bytes_in_use {stats.get('peak_bytes_in_use')!r}")


# ---------------------------------------------------------------- kernels
def _olmo_block_struct(jax):
    """One full-width OLMo-1B block's parameters (layer 0 of the stack)."""
    from repro.configs.registry import get_config
    from repro.models import make_model
    model = make_model(get_config("olmo-1b").model)
    full = jax.eval_shape(model.init, jax.random.PRNGKey(SEED))
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], jax.numpy.float32),
        full["blocks"])


def phase_kernel_ops(jax, checks):
    """Each ops matrix op, natively, against its jnp oracle."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    plan = ops.KernelPlan.for_tree(_olmo_block_struct(jax))
    log(f"[kernels] OLMo-1B block: {plan.n_valid} params, "
        f"{plan.rows} x {ops.LANE} rows")
    key = jax.random.PRNGKey(SEED)

    def rand_mat(i, scale=1.0):
        leaves = [scale * jax.random.normal(
            jax.random.fold_in(key, 10 * i + j), s.shape, jnp.float32)
            for j, s in enumerate(plan.slots)]
        return plan.flatten(plan.treedef.unflatten(leaves))

    x, m, g = rand_mat(1), rand_mat(2), rand_mat(3, 1e-2)
    counts = plan.row_counts()
    off = dict(interpret=False)

    def same(name, got, want):
        got, want = np.asarray(got), np.asarray(want)
        bad = int(np.sum(got != want))
        checks.expect(bad == 0, name,
                      f"{bad} of {got.size} elements differ (bit-exact)")

    def close(name, got, want, *, atol=0.0, rtol=0.0):
        got, want = np.asarray(got), np.asarray(want)
        err = np.abs(got - want)
        checks.expect(bool((err <= atol + rtol * np.abs(want)).all()), name,
                      f"max |diff| {float(err.max())!r} (atol {atol!r}, "
                      f"rtol {rtol!r})")

    xn, mn = ops.momentum_update_mat(x, m, g, mu=0.9, lr=0.1,
                                     weight_decay=1e-4, **off)
    xr, mr = ref.momentum_update_ref(x, m, g, 0.1, mu=0.9, wd=1e-4)
    close("momentum x", xn, xr, atol=1e-5)
    close("momentum m", mn, mr, atol=1e-5)
    w = (0.5, 0.25, 0.25)
    close("gossip_mix", ops.gossip_mix_mat((x, m, g), w, **off),
          ref.gossip_mix_ref((x, m, g), w), atol=1e-6)

    pk, sc = ops.sign_pack(x, counts, **off)
    pr, sr = ref.sign_pack_rows_ref(x, counts)
    same("sign pack bits", pk, pr)
    close("sign pack scales", sc, sr, rtol=1e-6)
    close("sign unpack", ops.sign_unpack(pk, sc, **off),
          ref.sign_unpack_ref(pk, sc), rtol=1e-6)

    ik, vk = ops.topk_pack(x, counts, fraction=0.01, **off)
    ir, vr = ref.topk_rows_ref(x, counts, fraction=0.01)
    same("topk select idx", ik, ir)
    same("topk select vals", vk, vr)
    same("topk scatter", ops.topk_unpack(ik, vk, **off),
         ref.topk_rows_unpack_ref(ir, vr, ops.LANE))

    for levels in (1, 7, 16):                    # 2-, 4- and 8-bit wires
        qk, nk = ops.qsgd_pack(x, levels=levels, **off)
        qr, nr = ref.qsgd_rows_ref(x, levels=levels)
        same(f"qsgd{levels} levels", qk, qr)
        same(f"qsgd{levels} norms", nk[:, 0], nr)
        same(f"qsgd{levels} unpack", ops.qsgd_unpack(qk, nk, levels=levels,
                                                     **off),
             ref.qsgd_rows_unpack_ref(qr, nr, levels=levels,
                                      block=ops.LANE))

    rng = np.random.default_rng(SEED)
    idx = jnp.asarray(np.sort(rng.choice(plan.rows, plan.rows // 100,
                                         replace=False)), jnp.int32)
    rows_g = ops.row_gather(x, idx, counts, **off)
    same("row gather", rows_g, ref.row_gather_ref(x, idx, counts))
    same("row scatter", ops.row_scatter(idx, rows_g, rows=plan.rows, **off),
         ref.row_scatter_ref(idx, rows_g, rows=plan.rows))


def phase_kernel_rounds(jax, checks):
    """ResNet-20, 8 workers on a ring: kernel path ≡ tree path, as far
    as one ulp of x₀ moves the tree path itself."""
    import jax.numpy as jnp

    from repro.core import make_compressor, make_optimizer
    from repro.core.gossip import DenseComm
    from repro.core.topology import ring
    from repro.data.synthetic import ClassStreamCfg, class_batch
    from repro.models.resnet import resnet20_init, resnet20_loss
    from repro.train.trainer import SimTrainer

    K = RESNET_WORKERS
    params0 = jax.vmap(lambda _: resnet20_init(jax.random.PRNGKey(SEED)))(
        jnp.arange(K))
    params0_ulp = jax.tree_util.tree_map(
        lambda x: jnp.nextafter(x, jnp.inf), params0)
    data = ClassStreamCfg(batch=RESNET_BATCH, n_workers=K, seed=SEED)
    steps = RESNET_ROUNDS * P
    log(f"[kernels] paper-resnet20 x {K} workers (ring), "
        f"{RESNET_ROUNDS} rounds of p={P}, batch {RESNET_BATCH}/worker, "
        f"eta {RESNET_ETA}")

    def run(name, codec, use_kernel, init=params0):
        opt = make_optimizer(
            name, DenseComm(ring(K)), eta=RESNET_ETA, mu=0.9, p=P, gamma=0.4,
            weight_decay=1e-4,
            compressor=make_compressor(codec) if codec else None,
            use_kernel=use_kernel, kernel_interpret=False)
        trainer = SimTrainer(resnet20_loss, opt, rounds_per_log=RESNET_ROUNDS)
        if use_kernel:
            batches = jax.tree_util.tree_map(
                lambda *b: jnp.stack(b).reshape(
                    (RESNET_ROUNDS, P) + b[0].shape),
                *[class_batch(data, t) for t in range(steps)])
            checks.kernel_lowered(trainer._block.lower(
                params0, opt.init(params0), batches).compile(),
                f"{name}/{codec} kernel round")
        params, _, hist = trainer.train(
            init, lambda t: class_batch(data, t), steps, log_every=steps)
        return params, hist.loss

    for name, codec in (("pd_sgdm", None), ("cpd_sgdm", "sign"),
                        ("cpd_sgdm", "qsgd"), ("cpd_sgdm", "topk")):
        p_kernel, loss_k = run(name, codec, True)
        p_tree, loss_t = run(name, codec, False)
        p_ulp, _ = run(name, codec, False, params0_ulp)
        label = name + (f"/{codec}" if codec else "")
        spread = _max_abs_diff(p_tree, p_ulp)
        log(f"  {label}: loss {loss_k[0]!r} -> {loss_k[-1]!r} (kernel), "
            f"{loss_t[0]!r} -> {loss_t[-1]!r} (tree); tree moved by one "
            f"ulp of x0: {spread!r}")
        checks.within(f"{label} kernel vs tree params",
                      _max_abs_diff(p_kernel, p_tree), ULP_MARGIN * spread)


# -------------------------------------------------------------- multichip
def phase_multichip(jax, checks):
    """One worker per chip (ppermute gossip) ≡ the dense simulation."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as Pspec

    from repro.configs.shapes import InputShape
    from repro.core import (CPDSGDM, CPDSGDMConfig, SignCompressor,
                            make_optimizer)
    from repro.core.gossip import DenseComm
    from repro.core.topology import ring
    from repro.launch import train as launch
    from repro.launch.mesh import make_mesh
    from repro.launch.runtime import build_train
    from repro.models import make_model

    K = 4
    devices = jax.devices()
    if len(devices) < K:
        raise AssertionError(f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    mesh = make_mesh((K, 1), ("data", "model"))

    for name, extra in (("pd_sgdm", []),
                        ("cpd_sgdm", ["--use-kernel", "--compressor",
                                      "sign"])):
        args = launch.parse_args([
            "--arch", "olmo-1b", "--optimizer", name, "--p", str(P),
            "--seq-len", str(MULTICHIP_SEQ), "--global-batch", str(K),
            "--steps", str(MULTICHIP_ROUNDS * P)] + extra)
        run = launch.run_config(args)
        # f32 parameter storage (bf16 compute stays): the tolerance below
        # is an f32 one, and a bf16 store turns f32-ulp differences in the
        # mix into whole bf16 ulps
        model_cfg = dataclasses.replace(run.model,
                                        n_layers=MULTICHIP_LAYERS,
                                        param_dtype="float32")
        run = dataclasses.replace(
            run, model=model_cfg,
            optim=dataclasses.replace(run.optim, kernel_interpret=False))
        log(f"[multichip] {name}{' ' + ' '.join(extra) if extra else ''}: "
            f"{model_cfg.name} widths, depth cut to {model_cfg.n_layers} "
            f"layers, f32 params; K={K} ring, seq {MULTICHIP_SEQ}, "
            f"{MULTICHIP_ROUNDS} rounds of p={P}")
        pack = build_train(run, mesh, InputShape(
            "smoke", MULTICHIP_SEQ, K, "train"))
        compiled = pack.train_round.lower(
            pack.params_struct, pack.state_struct,
            pack.round_batch_struct).compile()
        checks.expect("collective-permute" in compiled.as_text(),
                      f"{name} sharded round gossips by collective-permute")
        if run.optim.use_kernel:
            checks.kernel_lowered(compiled, f"{name} sharded kernel round")
        mem = compiled.memory_analysis()
        log(f"  sharded round per chip: arguments "
            f"{mem.argument_size_in_bytes!r} B, temporaries "
            f"{mem.temp_size_in_bytes!r} B")

        batch_fn = launch.batch_fn_for(run, K, args)
        rounds = [jax.tree_util.tree_map(
            lambda *b: jnp.stack(b), *[batch_fn(r * P + i)
                                       for i in range(P)])
            for r in range(MULTICHIP_ROUNDS)]
        params, state = pack.init_fn(jax.random.PRNGKey(SEED))
        # (worker index, device id) of every shard of every leaf
        placements = {tuple(sorted((sh.index[0].start or 0, sh.device.id)
                                   for sh in leaf.addressable_shards))
                      for leaf in jax.tree_util.tree_leaves(params)}
        checks.expect(
            all([w for w, _ in pl] == list(range(K))
                and len({d for _, d in pl}) == K for pl in placements),
            f"{name} one worker shard per chip",
            f"(worker, device id) per leaf: {sorted(placements)!r}")
        for rb in rounds:
            params, state, losses = pack.train_round(params, state, rb)
        del state
        log(f"  sharded losses, last round: "
            f"{[float(v) for v in losses]!r}")

        # the same rounds as the dense simulation, partitioned by XLA
        # over the same chips (worker dim sharded): K stacked copies of
        # the model do not fit on one chip (see the ``multichip`` notes)
        model = make_model(model_cfg)
        comm = DenseComm(ring(K))
        o = run.optim
        if name == "cpd_sgdm":
            dense = CPDSGDM(CPDSGDMConfig(eta=o.eta, mu=o.mu, p=o.p,
                                          gamma=o.gamma,
                                          weight_decay=o.weight_decay,
                                          packed_wire=False),
                            comm, SignCompressor())
        else:
            dense = make_optimizer(name, comm, eta=o.eta, mu=o.mu, p=o.p,
                                   weight_decay=o.weight_decay)
        remat = run.parallel.remat
        grad = jax.vmap(jax.value_and_grad(
            lambda p_, b: model.loss(p_, b, remat=remat)[0]))

        def grads_fn(p_, b):
            losses_, g = grad(p_, b)
            return losses_.mean(), g

        def shard_workers(tree, lead=()):
            """Worker dim (after ``lead`` leading dims) over the chips."""
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, NamedSharding(
                    mesh, Pspec(*lead, "data") if x.ndim else Pspec())),
                tree)

        init_workers = jax.jit(jax.vmap(
            lambda _: model.init(jax.random.PRNGKey(SEED))))
        dparams = shard_workers(init_workers(jnp.arange(K)))
        # from a second copy of x₀: CPD's x̂₀ = x₀ must not alias the
        # donated params
        dstate = shard_workers(dense.init(init_workers(jnp.arange(K))))
        dround = jax.jit(lambda s, p_, b: dense.round(s, p_, grads_fn, b),
                         donate_argnums=(0, 1))
        for rb in rounds:
            dparams, dstate, dlosses = dround(dstate, dparams,
                                              shard_workers(rb, (None,)))
        log(f"  dense losses, last round: "
            f"{[float(v) for v in dlosses]!r}")
        checks.within(f"{name} sharded vs dense params",
                      _max_abs_diff(params, dparams), TOL_SHARDED[name])
        del params, dparams, dstate


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args()
    jax = _start()
    devices = jax.devices()
    dev = devices[0]
    log(f"devices: {len(devices)} x {dev.device_kind} ({dev.platform})")
    checks = Checks()
    if args.chips == 4:
        phase_multichip(jax, checks)
    else:
        phase_train(jax, checks)
        phase_kernel_ops(jax, checks)
        phase_kernel_rounds(jax, checks)
    if checks.failed:
        sys.exit(f"chip_smoke: {len(checks.failed)} check(s) failed: "
                 + "; ".join(checks.failed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
