import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The lines above MUST run before jax imports: the sharded HLO checks need
# 8 forced host devices (4 workers × TP2 debug mesh / 8 workers × TP1),
# and jax locks the device count at first init.  Run this module in its
# own process (python -m repro.analysis.run), never import it from tests.

import argparse      # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402

"""Static-analysis driver: the round contract, checked across the grid.

    python -m repro.analysis.run               # fast grid (CI push)
    python -m repro.analysis.run --grid full   # optimizer × codec ×
                                               # schedule sweep (nightly)

Phases (nothing trains; jaxpr tracing + AOT compiles only):

1. dense jaxpr grid      — optimizer × {tree, kernel} on DenseComm:
                           one p-scan, zero collectives, zero callbacks,
                           zero f64 (traced under x64), flatten-once carry
2. sharded jaxpr + HLO   — build_train on the debug mesh per optimizer ×
                           codec: gossip at the boundary only, expected
                           ppermute counts, switch branches ≡ schedule
                           period, donation aliased, collective allowlist,
                           collective-permute bytes ≡ bytes_per_comm_round
3. retrace guard         — full schedule sweep + mid-cycle resume must
                           compile the fused round exactly once

Exit 0 = contract holds, 1 = violations (printed per combo).
"""


def _dense_grid(full: bool):
    from repro.core import make_compressor
    # (optimizer, codec, use_kernel, overlap)
    grid = [
        ("pd_sgdm", None, False, False),
        ("pd_sgdm", None, True, False),
        ("cpd_sgdm", "sign", True, False),
        ("cpd_sgdm", "qsgd", False, False),
        ("cpd_sgdm", "sparse", True, False),
        ("mt_dsgdm", None, False, False),
        ("pd_sgdm", None, False, True),
        ("mt_dsgdm", None, True, True),
    ]
    if full:
        grid += [
            ("cpd_sgdm", "sign", False, False),
            ("cpd_sgdm", "qsgd", True, False),
            ("cpd_sgdm", "topk", False, False),
            ("cpd_sgdm", "randk", False, False),
            ("cpd_sgdm", "identity", False, False),
            ("cpd_sgdm", "sparse+sign", False, False),
            ("qg_dsgdm", None, False, False),
            ("mt_dsgdm", None, True, False),
            ("pd_sgdm", None, True, True),
            ("mt_dsgdm", None, False, True),
            ("qg_dsgdm", None, True, True),
            ("cpd_sgdm", "sign", False, True),
        ]
    return grid


def phase_dense(full: bool) -> list:
    from repro.analysis import jaxpr_check as jc
    from repro.core import make_compressor, make_optimizer
    from repro.core.gossip import DenseComm
    from repro.core.topology import make_schedule, ring

    K = 8
    params = jc.toy_params(K)
    failures = []
    for name, comp, kernel, overlap in _dense_grid(full):
        compressor = make_compressor(comp) if comp else None
        opt = make_optimizer(name, DenseComm(ring(K)), eta=0.05, mu=0.9,
                             p=3, compressor=compressor, use_kernel=kernel,
                             kernel_interpret=True, overlap=overlap)
        kern = kernel and opt.kernel_comm_supported
        label = (f"dense/{name}/{comp or 'none'}/"
                 f"{'kernel' if kern else 'tree'}"
                 + ("/overlap" if overlap else ""))
        v = jc.check_round_contract(opt, params, kernel=kern, overlap=overlap)
        _report(label, v, failures)

    # scheduled dense rounds (stacked-W indexing; still zero collectives)
    for sched_name in (["one_peer_exp"] if not full else
                       ["one_peer_exp", "random_matching"]):
        sched = make_schedule(sched_name, (K,))
        opt = make_optimizer("pd_sgdm", DenseComm(sched), eta=0.05, mu=0.9,
                             p=2)
        v = jc.check_round_contract(opt, params)
        _report(f"dense/pd_sgdm/{sched_name}", v, failures)

    # hierarchical two-level rounds: dense simulation factors the round
    # through node means (W = R ⊗ C) — still one p-scan, zero collectives
    from repro.core.topology import hierarchical, hierarchical_schedule
    hier_grid = [("pd_sgdm", False, False), ("pd_sgdm", True, False)]
    if full:
        hier_grid += [("mt_dsgdm", False, False), ("pd_sgdm", False, True),
                      ("mt_dsgdm", True, True)]
    for name, kernel, overlap in hier_grid:
        opt = make_optimizer(name, DenseComm(hierarchical(2, 4)), eta=0.05,
                             mu=0.9, p=3, use_kernel=kernel,
                             kernel_interpret=True, overlap=overlap)
        kern = kernel and opt.kernel_comm_supported
        v = jc.check_round_contract(opt, params, kernel=kern, overlap=overlap)
        _report(f"dense/{name}/hier-m4/{'kernel' if kern else 'tree'}"
                + ("/overlap" if overlap else ""), v, failures)
    opt = make_optimizer("pd_sgdm", DenseComm(hierarchical_schedule(4, 2)),
                         eta=0.05, mu=0.9, p=2)
    v = jc.check_round_contract(opt, params)
    _report("dense/pd_sgdm/hier_one_peer", v, failures)

    # elastic membership: the masked matrices must honour the liveness
    # contract every round (check_membership_mask runs inside the
    # aggregate when the backend carries a membership schedule)
    from repro.testing import chaos_script, membership_for
    ms = membership_for(K, 6, chaos_script(K, 6, seed=7))
    for name, comp, overlap in (
            [("pd_sgdm", None, False), ("pd_sgdm", None, True)] if not full
            else [("pd_sgdm", None, False), ("cpd_sgdm", "sign", False),
                  ("mt_dsgdm", None, False), ("pd_sgdm", None, True),
                  ("mt_dsgdm", None, True)]):
        compressor = make_compressor(comp) if comp else None
        opt = make_optimizer(name, DenseComm(ring(K), membership=ms),
                             eta=0.05, mu=0.9, p=3, compressor=compressor,
                             overlap=overlap)
        v = jc.check_round_contract(opt, params, overlap=overlap)
        _report(f"dense/{name}/{comp or 'none'}/membership"
                + ("/overlap" if overlap else ""), v, failures)
    # elastic hierarchical rounds are dense-only (masked factored matrix)
    opt = make_optimizer("pd_sgdm", DenseComm(hierarchical(2, 4),
                                              membership=ms),
                         eta=0.05, mu=0.9, p=3)
    v = jc.check_round_contract(opt, params)
    _report("dense/pd_sgdm/hier-m4/membership", v, failures)
    return failures


def _sharded_grid(full: bool):
    # (optimizer, codec, use_kernel, topology_schedule, overlap)
    grid = [
        ("pd_sgdm", "sign", False, "static", False),
        ("pd_sgdm", "sign", True, "static", False),
        ("cpd_sgdm", "sign", False, "static", False),
        ("cpd_sgdm", "sparse", True, "static", False),
        ("pd_sgdm", "sign", False, "one_peer_exp", False),
        ("pd_sgdm", "sign", False, "static", True),
        ("pd_sgdm", "sign", True, "static", True),
    ]
    if full:
        grid += [
            ("cpd_sgdm", "sign", True, "static", False),
            ("cpd_sgdm", "qsgd", False, "static", False),
            ("cpd_sgdm", "topk", False, "static", False),
            ("cpd_sgdm", "randk", False, "static", False),
            ("cpd_sgdm", "sparse+qsgd", False, "static", False),
            ("mt_dsgdm", "sign", False, "static", False),
            ("pd_sgdm", "sign", False, "random_matching", False),
            ("pd_sgdm", "sign", True, "one_peer_exp", False),
            ("mt_dsgdm", "sign", False, "static", True),
            ("mt_dsgdm", "sign", True, "static", True),
            ("qg_dsgdm", "sign", False, "static", True),
            ("pd_sgdm", "sign", False, "one_peer_exp", True),
            ("cpd_sgdm", "sign", False, "static", True),   # must skip
        ]
    return grid


def _build_pack(opt_name, codec, use_kernel, schedule, overlap=False,
                node_size=0, wire_dtype="float32", inter_codec="none"):
    from repro.configs.base import ModelCfg, OptimCfg, ParallelCfg, RunCfg
    from repro.configs.shapes import InputShape
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.runtime import build_train

    mcfg = ModelCfg(name="tiny", arch_type="dense", n_layers=2, d_model=32,
                    n_heads=4, n_kv_heads=2, d_ff=64, vocab=128)
    run = RunCfg(model=mcfg,
                 parallel=ParallelCfg(profile="A", remat="none",
                                      topology_schedule=schedule,
                                      node_size=node_size,
                                      inter_codec=inter_codec),
                 optim=OptimCfg(name=opt_name, p=2, compressor=codec,
                                use_kernel=use_kernel,
                                kernel_interpret=True, overlap=overlap,
                                wire_dtype=wire_dtype))
    mesh = make_debug_mesh(8, 1)   # 8 workers × TP1: per-device ≡ per-worker
    return build_train(run, mesh, InputShape("t", 16, 8, "train"))


def phase_sharded(full: bool) -> list:
    from repro.analysis import hlo_check as hc
    from repro.analysis import jaxpr_check as jc

    failures = []
    for opt_name, codec, use_kernel, schedule, overlap in _sharded_grid(full):
        label = (f"sharded/{opt_name}/{codec}/"
                 f"{'kernel' if use_kernel else 'tree'}/{schedule}"
                 + ("/overlap" if overlap else ""))
        try:
            pack = _build_pack(opt_name, codec, use_kernel, schedule, overlap)
        except ValueError as e:      # unsupported combo (e.g. CPD+schedule)
            print(f"  skip {label}: {e}")
            continue
        args = (pack.params_struct, pack.state_struct,
                pack.round_batch_struct)
        jx = jax.make_jaxpr(pack.train_round)(*args)
        v = []
        v += jc.check_no_host_callbacks(jx)
        v += jc.check_round_scan(jx, pack.opt.config.p)
        expected = None
        if opt_name == "pd_sgdm" and schedule == "static":
            deg = pack.opt.comm.topology.degree
            n_arrays = (1 if (use_kernel and pack.opt.kernel_comm_supported)
                        else len(jax.tree_util.tree_leaves(
                            pack.params_struct)))
            expected = deg * n_arrays
        if overlap:
            # same wire, moved to the round start: the exchange must
            # precede the p-step scan (scan-independent payload), with
            # the ppermute count unchanged from the sync contract
            v += jc.check_overlap_boundary(jx, p=pack.opt.config.p,
                                           expected=expected)
        else:
            v += jc.check_gossip_boundary(jx, expected=expected)
        if schedule != "static":
            v += jc.check_schedule_switch(jx, pack.opt.comm.period)
        with jax.enable_x64(True):
            jx64 = jax.make_jaxpr(pack.train_round)(*args)
        v += jc.check_no_f64(jx64)
        # schedules vary wire bytes by round; byte equality is round-0 only
        v += hc.check_sharded_round(pack, check_bytes=(schedule == "static"),
                                    label=label)
        _report(label, v, failures)

    # hierarchical two-level rounds: psum inside the node, ppermute between
    # node leaders — per-level accounted ≡ shipped on static graphs
    from repro.core.topology import hierarchical_inter_shifts
    # (optimizer, use_kernel, schedule, overlap, wire_dtype, inter_codec)
    hier_grid = [
        ("pd_sgdm", False, "static", False, "float32", "none"),
        ("pd_sgdm", True, "static", False, "float32", "none"),
        ("pd_sgdm", False, "static", False, "bfloat16", "none"),
    ]
    if full:
        hier_grid += [
            ("mt_dsgdm", False, "static", False, "float32", "none"),
            ("pd_sgdm", True, "static", False, "bfloat16", "none"),
            ("pd_sgdm", False, "hier_one_peer", False, "float32", "none"),
            ("pd_sgdm", False, "static", True, "float32", "none"),
            ("pd_sgdm", True, "static", True, "float32", "none"),
            ("pd_sgdm", False, "static", False, "float32", "identity"),
            ("cpd_sgdm", False, "static", False, "float32", "none"),  # skip
        ]
    for opt_name, use_kernel, schedule, overlap, wdt, icodec in hier_grid:
        label = (f"sharded/{opt_name}/hier-m4/"
                 f"{'kernel' if use_kernel else 'tree'}/{schedule}"
                 + (f"/{wdt}" if wdt != "float32" else "")
                 + (f"/codec-{icodec}" if icodec != "none" else "")
                 + ("/overlap" if overlap else ""))
        try:
            pack = _build_pack(opt_name, "sign", use_kernel, schedule,
                               overlap, node_size=4, wire_dtype=wdt,
                               inter_codec=icodec)
        except ValueError as e:      # unsupported combo (e.g. CPD+hier)
            print(f"  skip {label}: {e}")
            continue
        args = (pack.params_struct, pack.state_struct,
                pack.round_batch_struct)
        jx = jax.make_jaxpr(pack.train_round)(*args)
        v = []
        v += jc.check_no_host_callbacks(jx)
        v += jc.check_round_scan(jx, pack.opt.config.p)
        expected = None
        if opt_name == "pd_sgdm" and schedule == "static":
            ideg = len(hierarchical_inter_shifts(pack.opt.comm.topology))
            n_arrays = (1 if (use_kernel and pack.opt.kernel_comm_supported)
                        else len(jax.tree_util.tree_leaves(
                            pack.params_struct)))
            expected = ideg * n_arrays
        if overlap:
            v += jc.check_overlap_boundary(jx, p=pack.opt.config.p,
                                           expected=expected)
        else:
            v += jc.check_gossip_boundary(jx, expected=expected)
        if schedule != "static":
            v += jc.check_schedule_switch(jx, pack.opt.comm.period)
        with jax.enable_x64(True):
            jx64 = jax.make_jaxpr(pack.train_round)(*args)
        v += jc.check_no_f64(jx64)
        v += hc.check_sharded_round(pack, check_bytes=(schedule == "static"),
                                    label=label)
        _report(label, v, failures)
    return failures


def phase_retrace() -> list:
    from repro.analysis.retrace import check_schedule_no_retrace

    failures = []
    v = check_schedule_no_retrace()
    _report("retrace/one_peer_exp-sweep+resume", v, failures)
    return failures


def _report(label: str, violations: list, failures: list):
    status = "ok" if not violations else "FAIL"
    print(f"  {status:4s} {label}")
    for msg in violations:
        print(f"       - {msg}")
    if violations:
        failures.append((label, violations))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="round-contract static checks")
    ap.add_argument("--grid", choices=("fast", "full"), default="fast")
    ap.add_argument("--phase", choices=("all", "dense", "sharded", "retrace"),
                    default="all")
    args = ap.parse_args(argv)
    full = args.grid == "full"

    failures = []
    t0 = time.time()
    if args.phase in ("all", "dense"):
        print("[1/3] dense jaxpr contract grid")
        failures += phase_dense(full)
    if args.phase in ("all", "sharded"):
        print("[2/3] sharded jaxpr + HLO contract grid")
        failures += phase_sharded(full)
    if args.phase in ("all", "retrace"):
        print("[3/3] retrace guard")
        failures += phase_retrace()

    dt = time.time() - t0
    if failures:
        print(f"\nstatic-analysis: {len(failures)} combo(s) violated the "
              f"round contract ({dt:.0f}s)", file=sys.stderr)
        return 1
    print(f"\nstatic-analysis: round contract holds ({dt:.0f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
