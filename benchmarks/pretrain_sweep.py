"""Pretraining sweep: hierarchical two-level gossip vs. the flat ring.

Two row families, one claim row each:

**Analytic comm rows** price one gossip round of the ~100M-param LM
(the ``examples/pretrain_decentralized.py`` full model: 12L × d768,
32k vocab) on K = 8 workers — flat ring(8) vs. the two-level round
(2 nodes × 4 workers, ring between node leaders) at f32 and bf16 inter
wires.  Pure byte accounting through the same
``bytes_per_comm_round`` / ``hier_bytes_per_level`` code the HLO gate
checks against compiled programs, so the numbers are exact on any host:

* flat ring(8): degree 2 × 4 B × N          = 8 N bytes/worker/round
* hier f32: 1 leader edge × 4 B × N ÷ m=4   = 1 N  (8× less inter)
* hier bf16: 1 × 2 B × N ÷ 4                = 0.5 N (16× less inter)

``pretrain/claim_inter_reduction`` pins both ratios (``rel_tol`` 0.02)
and ``reduction_ok`` = 1 iff both are ≥ 2× (``min_frac`` 1.0) — the
deliverable's headline: ≥ 2× inter-node comm reduction.

**Training rows** actually run ``examples/pretrain_decentralized.py``
(its ``main``, in this process: the sweep and the example share one
driver path, and one process holds the devices) twice — flat ring vs.
``--node-size 2 --wire-dtype bfloat16`` — and record tokens/sec,
comm-MB/worker, and the loss-curve endpoints.  They need at least four
devices (two-level rounds of 2-worker nodes over a TP2 mesh); run on the
CPU, the sweep asks for 8 host devices before JAX starts.
``pretrain/claim_equal_loss`` gates ``hier_loss_ok`` = 1 iff the
hierarchical final loss is within 5% of the flat run's (``min_frac``
1.0: equal-or-better final loss at a fraction of the comm volume);
``train_comm_reduction`` reports the measured accounted-MB ratio.
Tokens/sec is recorded but not gated (host-dependent).

Env knobs: ``PRETRAIN_STEPS`` (default 8) trims the training runs;
``PRETRAIN_MODEL=full`` switches them from the quick ~5M model to the
full ~100M one (CI smoke uses quick — the analytic rows always price
the 100M model).

Standalone runs write ``benchmarks/BENCH_pretrain.json``; under
``python -m benchmarks.run pretrain`` the rows land in the main
``BENCH_<tag>.json``.
"""
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row

K = 8            # analytic mesh: 8 workers, 2 nodes × 4
NODE_SIZE = 4
STEPS = int(os.environ.get("PRETRAIN_STEPS", "8"))
MODEL = os.environ.get("PRETRAIN_MODEL", "quick")   # quick | full

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lm100m():
    from repro.configs.base import ModelCfg
    return ModelCfg(name="lm-100m", arch_type="dense", n_layers=12,
                    d_model=768, n_heads=12, n_kv_heads=4, d_ff=3072,
                    vocab=32768)


def analytic_rows() -> dict:
    """Byte-accounting rows for one gossip round of the 100M model."""
    from repro.core import DenseComm, make_optimizer
    from repro.core.topology import hierarchical, ring

    n_params = _lm100m().params_count()
    # accounting only reads leaf sizes — one flat leaf prices the model
    params = [jax.ShapeDtypeStruct((n_params,), jnp.float32)]

    flat = make_optimizer("pd_sgdm", DenseComm(ring(K)), p=4)
    flat_b = float(flat.bytes_per_comm_round(params))
    csv_row("pretrain/comm_flat_ring", 0.0,
            f"mb_per_round={flat_b / 2**20:.4f};workers={K};"
            f"params={n_params}")

    inter = {}
    for wdt in ("float32", "bfloat16"):
        comm = DenseComm(hierarchical(K // NODE_SIZE, NODE_SIZE),
                         wire_dtype=wdt)
        opt = make_optimizer("pd_sgdm", comm, p=4)
        lv = opt.hier_bytes_per_level(params)
        inter[wdt] = lv["inter"]
        tag = "f32" if wdt == "float32" else "bf16"
        csv_row(f"pretrain/comm_hier_{tag}", 0.0,
                f"inter_mb={lv['inter'] / 2**20:.4f};"
                f"intra_mb={lv['intra_wire'] / 2**20:.4f};"
                f"node_size={NODE_SIZE};wire_dtype={wdt}")

    red_f32 = flat_b / inter["float32"]
    red_bf16 = flat_b / inter["bfloat16"]
    ok = int(red_f32 >= 2.0 and red_bf16 >= 2.0)
    csv_row("pretrain/claim_inter_reduction", 0.0,
            f"inter_reduction_f32={red_f32:.4f};"
            f"inter_reduction_bf16={red_bf16:.4f};reduction_ok={ok}")
    return {"flat": flat_b, "inter": inter}


def _driver():
    """``examples/pretrain_decentralized.py`` as a module (examples/ is not
    a package)."""
    path = os.path.join(_REPO, "examples", "pretrain_decentralized.py")
    spec = importlib.util.spec_from_file_location("pretrain_decentralized",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_driver(extra: list) -> dict:
    argv = ["--steps", str(STEPS)] + (["--quick"] if MODEL != "full"
                                      else []) + extra
    return _driver().main(argv)


def train_rows() -> dict:
    """Drive the shared example end-to-end: flat ring vs. two-level."""
    runs = {
        "flat": [],
        "hier": ["--node-size", "2", "--wire-dtype", "bfloat16"],
    }
    if len(jax.devices()) < 4:
        raise RuntimeError(
            f"the training rows need >= 4 devices, found "
            f"{len(jax.devices())}: on the CPU set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 before JAX starts")
    recs = {}
    for tag, extra in runs.items():
        r = _run_driver(extra)
        recs[tag] = r
        us = r["wall_s"] / max(r["steps"], 1) * 1e6
        csv_row(f"pretrain/train_{tag}", us,
                f"final_loss={r['final_loss']:.4f};"
                f"first_loss={r['first_loss']:.4f};"
                f"tokens_per_s={r['tokens_per_s']:.1f};"
                f"comm_mb={r['comm_mb']:.4f};"
                f"bytes_per_comm_round={r['bytes_per_comm_round']:.0f};"
                f"model={r['model']};workers={r['workers']};"
                f"steps={r['steps']}")

    flat, hier = recs["flat"], recs["hier"]
    loss_ok = int(hier["final_loss"] <= 1.05 * flat["final_loss"])
    comm_red = flat["comm_mb"] / max(hier["comm_mb"], 1e-12)
    csv_row("pretrain/claim_equal_loss", 0.0,
            f"hier_loss_ok={loss_ok};"
            f"train_comm_reduction={comm_red:.4f};"
            f"flat_final={flat['final_loss']:.4f};"
            f"hier_final={hier['final_loss']:.4f}")
    return recs


def main() -> dict:
    out = {"analytic": analytic_rows(), "train": train_rows()}
    return out


def _write_json(results) -> str:
    from benchmarks.common import collected_rows
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_pretrain.json")
    rows = [r for r in collected_rows() if r["name"].startswith("pretrain/")]
    doc = {
        "schema": 1,
        "created_unix": int(time.time()),
        "sections": ["pretrain"],
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "steps": STEPS,
        "model": MODEL,
        "rows": rows,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


if __name__ == "__main__":
    from repro.launch.mesh import force_host_devices
    force_host_devices(K)
    print("name,us_per_call,derived")
    res = main()
    print(f"bench_json,0.0,path={os.path.relpath(_write_json(res))}")
