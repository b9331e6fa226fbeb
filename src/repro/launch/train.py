"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --optimizer pd_sgdm --steps 50 --devices 8

On a TPU the mesh covers the attached chips.  On the CPU, ``--devices N``
forces N host devices for a debug mesh (the same code as on a real mesh).
``--smoke`` selects the reduced config; without it the published widths
run.  ``chip_smoke.py`` drives the same :func:`run_config` →
``build_train`` → ``ShardedTrainer`` path on the chip.
"""
import argparse
import sys


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--optimizer", default=None,
                    help="pd_sgdm|cpd_sgdm|mt_dsgdm|qg_dsgdm|c_sgdm|"
                         "d_sgd|pd_sgd|choco_sgd")
    ap.add_argument("--p", type=int, default=None)
    ap.add_argument("--eta", type=float, default=None)
    ap.add_argument("--topology", default=None,
                    help="ring|torus|complete|exponential|disconnected")
    ap.add_argument("--topology-schedule", default=None,
                    help="static|one_peer_exp|alt_axes|random_matching "
                         "(time-varying gossip graph)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="run the fused round on the flatten-once Pallas "
                         "kernel layout (recommended on TPU; interpret "
                         "mode — the correctness harness — on CPU)")
    ap.add_argument("--overlap", action="store_true",
                    help="communication-hiding overlapped rounds: exchange "
                         "round r's gossip payload during round r+1's "
                         "local scan and mix it one round late (stale "
                         "delayed mixing; unsupported optimizer combos "
                         "raise at construction)")
    ap.add_argument("--node-size", type=int, default=None,
                    help="hierarchical two-level gossip: exact intra-node "
                         "averaging over groups of this many workers, "
                         "--topology between node leaders only")
    ap.add_argument("--wire-dtype", default=None,
                    choices=("float32", "bfloat16"),
                    help="dtype of the gossip payload on the wire "
                         "(bfloat16 halves it; accumulation stays f32)")
    ap.add_argument("--inter-codec", default=None,
                    help="compress the hierarchical inter-node wire "
                         "(identity|sign|topk|qsgd; needs --node-size)")
    ap.add_argument("--compressor", default=None,
                    help="cpd_sgdm/choco wire codec: "
                         "identity|sign|topk|randk|qsgd|sparse|"
                         "sparse+sign|sparse+qsgd")
    ap.add_argument("--compressor-fraction", type=float, default=None,
                    help="topk/randk kept fraction")
    ap.add_argument("--compressor-levels", type=int, default=None,
                    help="qsgd quantization levels (7 = 4-bit wire)")
    ap.add_argument("--compressor-block", type=int, default=None,
                    help="sign/topk/qsgd/sparse block width (1024 = kernel "
                         "lane; other widths use the per-leaf jnp wire)")
    ap.add_argument("--compressor-rows", type=int, default=None,
                    help="sparse wire: shipped-row budget per leaf "
                         "(bytes/round scale with it, not with table size)")
    ap.add_argument("--track-compressed", action="store_true",
                    help="mt_dsgdm: ship the gradient-tracking correction "
                         "through the --compressor wire codec instead of "
                         "full precision")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU debug)")
    ap.add_argument("--data-axis", type=int, default=4)
    ap.add_argument("--model-axis", type=int, default=2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in --ckpt-dir")
    return ap.parse_args(argv)


def run_config(args):
    """The ``RunCfg`` the command line asks for."""
    import dataclasses

    from repro.configs.registry import get_config, get_smoke_config

    run = (get_smoke_config if args.smoke else get_config)(args.arch)
    optim = run.optim
    if args.optimizer:
        optim = dataclasses.replace(optim, name=args.optimizer)
    if args.p:
        optim = dataclasses.replace(optim, p=args.p)
    if args.eta is not None:
        optim = dataclasses.replace(optim, eta=args.eta)
    if args.use_kernel:
        optim = dataclasses.replace(optim, use_kernel=True)
    if args.overlap:
        optim = dataclasses.replace(optim, overlap=True)
    if args.compressor:
        optim = dataclasses.replace(optim, compressor=args.compressor)
    if args.compressor_fraction is not None:
        optim = dataclasses.replace(
            optim, compressor_fraction=args.compressor_fraction)
    if args.compressor_levels is not None:
        optim = dataclasses.replace(
            optim, compressor_levels=args.compressor_levels)
    if args.compressor_block is not None:
        optim = dataclasses.replace(
            optim, compressor_block=args.compressor_block)
    if args.compressor_rows is not None:
        optim = dataclasses.replace(
            optim, compressor_rows=args.compressor_rows)
    if args.track_compressed:
        optim = dataclasses.replace(optim, track_compressed=True)
    if args.wire_dtype:
        optim = dataclasses.replace(optim, wire_dtype=args.wire_dtype)
    parallel = run.parallel
    if args.topology:
        parallel = dataclasses.replace(parallel, topology=args.topology)
    if args.topology_schedule:
        parallel = dataclasses.replace(
            parallel, topology_schedule=args.topology_schedule)
    if args.node_size is not None:
        parallel = dataclasses.replace(parallel, node_size=args.node_size)
    if args.inter_codec:
        parallel = dataclasses.replace(parallel,
                                       inter_codec=args.inter_codec)
    return dataclasses.replace(run, optim=optim, parallel=parallel)


def batch_fn_for(run, n_workers: int, args):
    """Step ``t`` → the seeded random token batch of every worker."""
    import jax

    from repro.configs.shapes import train_batch_arrays

    def batch_fn(t):
        return train_batch_arrays(
            run.model, n_workers, args.global_batch // n_workers,
            args.seq_len, jax.random.fold_in(jax.random.PRNGKey(1), t))
    return batch_fn


def main(argv=None):
    args = parse_args(argv)
    if args.devices:
        from repro.launch.mesh import force_host_devices
        force_host_devices(args.devices)

    import jax

    from repro.configs.shapes import InputShape
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import worker_mesh
    from repro.launch.runtime import build_train
    from repro.train.trainer import ShardedTrainer

    enable_compile_cache()
    run = run_config(args)
    optim = run.optim
    mesh = worker_mesh(args.data_axis, args.model_axis)
    shape = InputShape("cli", args.seq_len, args.global_batch, "train")
    pack = build_train(run, mesh, shape)
    n_w = pack.layout.n_workers
    print(f"arch={args.arch} optimizer={optim.name} p={optim.p} "
          f"workers={n_w} kernel={optim.use_kernel} "
          f"overlap={optim.overlap} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")
    batch_fn = batch_fn_for(run, n_w, args)

    trainer = ShardedTrainer(pack, ckpt_dir=args.ckpt_dir,
                             ckpt_every=args.ckpt_every)
    with mesh:
        out = trainer.train(jax.random.PRNGKey(0), batch_fn, args.steps,
                            log_every=max(args.steps // 10, 1),
                            resume=args.resume)
    h = out["history"]
    if not h.loss:      # e.g. --resume with a checkpoint at/past --steps
        print("no steps run")
        return
    print(f"final loss {h.loss[-1]:.4f} (start {h.loss[0]:.4f})")
    if out["steps_run"] == args.steps and h.loss[-1] >= h.loss[0]:
        # a short resumed tail is too noisy to judge — only warn on full runs
        print("WARNING: loss did not decrease", file=sys.stderr)


if __name__ == "__main__":
    main()
