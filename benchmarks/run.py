"""Benchmark orchestrator — one section per paper table/figure or subsystem.

  PYTHONPATH=src python -m benchmarks.run             # everything
  PYTHONPATH=src python -m benchmarks.run fig1 fig3   # a subset

Sections
--------
  fig1      PD-SGDM vs C-SGDM/D-SGD/PD-SGD loss trajectories (paper Fig. 1)
  fig2      communication-cost model: bytes on the wire per method (Fig. 2)
  fig3      CPD-SGDM compressed gossip vs full precision (Fig. 3)
  speedup   steps/sec scaling over worker count K
  round     per-step dispatch vs fused-round scan (the round engine)
  toposweep static ring vs time-varying topologies at equal bytes-on-wire
  kernels   Pallas kernel microbenchmarks (interpret mode) vs jnp references
  kernel_path  per-leaf jnp round vs per-step kernel vs flatten-once fused
               round (interpret-parity layout comparison)
  wire      bytes/round and round-time per wire codec on the fused path
            (also writes its own BENCH_wire_codecs.json when standalone)
  noniid    heterogeneity sweep: Dirichlet-α × p × optimizer, judged on
            the global loss of the averaged model (MT-DSGDm vs PD-SGDM
            vs QG vs D-PSGD; standalone writes BENCH_noniid.json)
  elastic   churn sweep: survivor loss / consensus / wire bytes vs. the
            kill+straggle rate under seeded chaos scripts (standalone
            writes BENCH_elastic.json)
  pretrain  hierarchical two-level gossip vs. flat ring on the LM
            pretraining driver: analytic comm rows for the ~100M model
            plus end-to-end runs of examples/pretrain_decentralized.py
            (standalone writes BENCH_pretrain.json; env knobs
            PRETRAIN_STEPS / PRETRAIN_MODEL)
  embedding sparse embedding-row wire on the power-law (Zipf) lookup
            workload: bytes/round vs rows touched (batch sweep), flat in
            table size (table sweep), plus a fused sparse round timing
            (standalone writes BENCH_embedding.json)
  roofline  dry-run HLO analysis against TPU v5e hardware ceilings

Output formats
--------------
Human-readable: every section prints ``name,us_per_call,derived`` CSV rows
to stdout, where ``derived`` is a ``k1=v1;k2=v2`` string of
section-specific metrics (steps/sec, speedups, final losses, ...).

Machine-readable: after the selected sections run, the same rows are
written to ``benchmarks/BENCH_<tag>.json`` (tag from ``$BENCH_TAG``,
default ``latest``) so later PRs can diff perf trajectories without
scraping stdout.  Schema (version 1)::

    {
      "schema": 1,
      "created_unix": <int>,          # stamp of the run
      "sections": ["fig1", ...],      # what was executed — any subset of
                                      # SECTIONS below, kernel_path /
                                      # noniid / elastic included
      "jax": "0.4.37",                # toolchain provenance
      "backend": "cpu",               # jax.default_backend()
      "wall_s": <float>,              # total wall clock
      "rows": [                       # csv rows, structured
        {"name": "round_engine/fused_round_p4",
         "us_per_call": 123.4,
         "derived": {"steps_per_s": 8100.0, "speedup_vs_per_step": 1.5}},
        {"name": "kernel_path/speedup_p4",   # flatten-once layout win
         "us_per_call": 0.0,
         "derived": {"fused_vs_perstep_parity": 1.5, "fused_vs_jnp": 1.2}},
        {"name": "noniid/claim_alpha0.1",    # heterogeneity claim row
         "us_per_call": 0.0,
         "derived": {"mt_minus_pd_best": -0.01, "mt_le_pd": 1.0}},
        {"name": "elastic/claim_survivors",  # chaos-sweep claim row
         "us_per_call": 0.0,
         "derived": {"survivors_bounded": 1.0, "cells": 12.0}},
        {"name": "pretrain/claim_inter_reduction",  # two-level comm claim
         "us_per_call": 0.0,
         "derived": {"inter_reduction_f32": 8.0,
                     "inter_reduction_bf16": 16.0, "reduction_ok": 1.0}},
        {"name": "pretrain/claim_equal_loss",  # end-to-end LM driver claim
         "us_per_call": 0.0,
         "derived": {"hier_loss_ok": 1.0, "train_comm_reduction": 8.0}},
        {"name": "embedding/claim_bytes_scale",  # sparse-wire scaling claim
         "us_per_call": 0.0,
         "derived": {"bytes_scale_with_touched": 1.0,
                     "sparse_vs_dense_x": 99.0,
                     "bytes_flat_in_table": 1.0}},
        ...
      ]
    }

Standalone section runs also write their own committed baselines
(``BENCH_kernel_path.json``, ``BENCH_wire_codecs.json``,
``BENCH_noniid.json``, ``BENCH_elastic.json``, ``BENCH_pretrain.json``,
``BENCH_embedding.json``) which ``tools/bench_compare.py`` gates fresh
runs against.

``derived`` values parse to floats where possible; free-form fragments are
kept under ``"note"``.  Rows are append-only within a run; compare runs by
joining on ``name``.  The fused-round rows (``round_engine/*``) are the
regression gate: new execution-path work must not lower their
``steps_per_s``.
"""
import json
import os
import sys
import time

SECTIONS = ["fig1", "fig2", "fig3", "speedup", "round", "toposweep",
            "kernels", "kernel_path", "wire", "noniid", "elastic",
            "pretrain", "embedding", "roofline"]


def _write_bench_json(sections, wall_s) -> str:
    """Persist the collected rows as benchmarks/BENCH_<tag>.json."""
    import jax

    from benchmarks.common import collected_rows
    tag = os.environ.get("BENCH_TAG", "latest")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_{tag}.json")
    doc = {
        "schema": 1,
        "created_unix": int(time.time()),
        "sections": sections,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "wall_s": wall_s,
        "rows": collected_rows(),
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


def main() -> None:
    want = [a for a in sys.argv[1:] if a in SECTIONS] or SECTIONS
    if "pretrain" in want:
        # its training rows run on a multi-device mesh; on the CPU that
        # takes forced host devices, set before any section starts JAX
        from repro.launch.mesh import force_host_devices
        force_host_devices(8)
    print("name,us_per_call,derived")
    t0 = time.time()
    if "fig1" in want:
        from benchmarks import fig1_pdsgdm
        fig1_pdsgdm.main()
    if "fig2" in want:
        from benchmarks import fig2_comm_cost
        fig2_comm_cost.main()
    if "fig3" in want:
        from benchmarks import fig3_cpdsgdm
        fig3_cpdsgdm.main()
    if "speedup" in want:
        from benchmarks import speedup
        speedup.main()
    if "round" in want:
        from benchmarks import round_engine
        round_engine.main()
    if "toposweep" in want:
        from benchmarks import topology_sweep
        topology_sweep.main()
    if "kernels" in want:
        from benchmarks import kernels_micro
        kernels_micro.main()
    if "kernel_path" in want:
        from benchmarks import kernel_path
        kernel_path.main()
    if "wire" in want:
        from benchmarks import wire_codecs
        wire_codecs.main()
    if "noniid" in want:
        from benchmarks import noniid_sweep
        noniid_sweep.main()
    if "elastic" in want:
        from benchmarks import elastic_sweep
        elastic_sweep.main()
    if "pretrain" in want:
        from benchmarks import pretrain_sweep
        pretrain_sweep.main()
    if "embedding" in want:
        from benchmarks import embedding_wire
        embedding_wire.main()
    if "roofline" in want:
        from benchmarks import roofline
        roofline.main()
    wall = time.time() - t0
    path = _write_bench_json(want, wall)
    print(f"bench_json,0.0,path={os.path.relpath(path)}")
    print(f"total_wall_s,{wall*1e6:.0f},sections={want}")


if __name__ == '__main__':
    main()
