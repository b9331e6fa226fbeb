"""End-to-end driver: decentralized LM pretraining on the sharded runtime.

Trains an OLMo-family model with PD-SGDM over a (data × model) mesh —
gossip lowers to collective-permute, exactly the production path the
dry-run compiles for 256/512 chips.  The mesh covers the attached
devices; on the CPU ``--devices N`` forces N host devices.
Execution runs through ``TrainPack.train_round`` (fused p-step rounds,
donated buffers); checkpoints carry the full optimizer state so
``--resume`` continues bit-identically.

``--node-size m`` switches the flat gossip graph to the two-level
hierarchical round (exact intra-node average + ``--topology`` between
node leaders), ``--wire-dtype bfloat16`` halves the inter wire, and
``--inter-codec`` compresses it; ``--json-out`` writes the run record
(loss curve endpoints, tokens/sec, comm-MB) that
``benchmarks/pretrain_sweep.py`` consumes — the sweep calls :func:`main`
in its own process, so the two share this one driver path.

Default is a ~100M-param model for a few hundred steps (the deliverable's
end-to-end scale); ``--quick`` shrinks it for a smoke pass.

  PYTHONPATH=src python examples/pretrain_decentralized.py --quick --devices 8
  PYTHONPATH=src python examples/pretrain_decentralized.py \
      --steps 300 --devices 8      # ~100M params, the full driver
  PYTHONPATH=src python examples/pretrain_decentralized.py --devices 8 \
      --quick --node-size 2 --wire-dtype bfloat16   # two-level gossip
"""
import argparse
import json
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="force N CPU host devices (0 = the attached "
                         "devices)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--optimizer", default="pd_sgdm")
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--topology", default="ring",
                    help="gossip graph between workers (flat), or between "
                         "node leaders when --node-size is set")
    ap.add_argument("--node-size", type=int, default=0,
                    help="two-level gossip: exact intra-node averaging "
                         "over groups of this many workers (0 = flat)")
    ap.add_argument("--wire-dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="dtype of the gossip payload on the wire")
    ap.add_argument("--inter-codec", default="none",
                    help="compress the hierarchical inter-node wire "
                         "(identity/sign/topk/qsgd; needs --node-size)")
    ap.add_argument("--json-out", default=None,
                    help="write the run record (losses, tokens/sec, "
                         "comm-MB) to this JSON file")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in "
                         "--ckpt-dir")
    return ap.parse_args(argv)


def main(argv=None):
    """Run the driver; returns the run record (None if no step ran)."""
    args = parse_args(argv)
    if args.devices:
        from repro.launch.mesh import force_host_devices
        force_host_devices(args.devices)

    import jax

    from repro.configs.base import ModelCfg, OptimCfg, ParallelCfg, RunCfg
    from repro.configs.shapes import InputShape
    from repro.data.synthetic import LMStreamCfg, lm_batch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import worker_mesh
    from repro.launch.runtime import build_train
    from repro.train.trainer import ShardedTrainer

    enable_compile_cache()
    if args.quick:
        mcfg = ModelCfg(name="lm-5m", arch_type="dense", n_layers=4,
                        d_model=128, n_heads=4, n_kv_heads=2, d_ff=512,
                        vocab=4096)
        seq, gbatch, steps = 64, 16, min(args.steps, 30)
    else:
        # ~100M params: 12L × d768 (GPT-2-small-ish), 32k vocab
        mcfg = ModelCfg(name="lm-100m", arch_type="dense", n_layers=12,
                        d_model=768, n_heads=12, n_kv_heads=4, d_ff=3072,
                        vocab=32768)
        seq, gbatch, steps = 256, 16, args.steps

    run = RunCfg(model=mcfg,
                 parallel=ParallelCfg(profile="A", remat="none",
                                      topology=args.topology,
                                      node_size=args.node_size,
                                      inter_codec=args.inter_codec),
                 optim=OptimCfg(name=args.optimizer, eta=0.25, mu=0.9,
                                p=args.p, weight_decay=1e-4,
                                wire_dtype=args.wire_dtype))

    # workers × TP2 where the devices allow it, else one worker per device
    n_dev = len(jax.devices())
    mesh = worker_mesh(max(n_dev // 2, 1), 2)
    shape = InputShape("pretrain", seq, gbatch, "train")
    pack = build_train(run, mesh, shape)
    K = pack.layout.n_workers
    n_params = mcfg.params_count()
    print(f"model={mcfg.name} params={n_params/1e6:.1f}M workers={K} "
          f"optimizer={run.optim.name} p={run.optim.p} seq={seq} "
          f"global_batch={gbatch} topology={args.topology} "
          f"node_size={args.node_size} wire_dtype={args.wire_dtype}")

    data = LMStreamCfg(vocab=mcfg.vocab, seq_len=seq, batch=gbatch // K,
                       n_workers=K)
    trainer = ShardedTrainer(pack, ckpt_dir=args.ckpt_dir,
                             ckpt_every=100 if args.ckpt_dir else 0)
    wall0 = time.time()
    with mesh:
        out = trainer.train(jax.random.PRNGKey(0),
                            lambda t: lm_batch(data, t), steps,
                            log_every=max(steps // 20, 1),
                            resume=args.resume)
    elapsed = time.time() - wall0
    h = out["history"]
    if not h.loss:          # --resume with a checkpoint at/past --steps
        print("no steps run")
        return None
    ran = out["steps_run"]
    tokens_per_s = ran * gbatch * seq / max(elapsed, 1e-9)
    comm_mb = h.comm_mb[-1] if h.comm_mb else 0.0
    print(f"loss: {h.loss[0]:.4f} -> {h.loss[-1]:.4f} over {ran} steps "
          f"({tokens_per_s:.0f} tok/s, {comm_mb:.1f} comm-MB/worker)")

    record = {
        "model": mcfg.name, "params": n_params, "workers": K,
        "optimizer": run.optim.name, "p": run.optim.p,
        "topology": args.topology, "node_size": args.node_size,
        "wire_dtype": args.wire_dtype, "inter_codec": args.inter_codec,
        "steps": ran, "seq": seq, "global_batch": gbatch,
        "first_loss": h.loss[0], "final_loss": h.loss[-1],
        "tokens_per_s": tokens_per_s, "comm_mb": comm_mb,
        "bytes_per_comm_round": trainer.bytes_per_round(),
        "wall_s": elapsed,
    }
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(record, f, indent=1)
        print(f"wrote {args.json_out}")

    if ran == steps:        # a short resumed tail is too noisy to judge
        assert h.loss[-1] < h.loss[0], "training failed to reduce loss"
    return record


if __name__ == "__main__":
    main()
