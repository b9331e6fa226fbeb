"""Sharded static-analysis checks on the real 8-device mesh (subprocess,
slow tier): the contract holds at HEAD, and each seeded violation — an
injected all-gather in the round, a dropped donation — is caught."""
import os
import subprocess
import sys
import textwrap

import pytest

_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.analysis import hlo_check as hc
    from repro.analysis import jaxpr_check as jc
    from repro.analysis.hlo_parse import parse_collectives
    from repro.configs.base import ModelCfg, OptimCfg, ParallelCfg, RunCfg
    from repro.configs.shapes import InputShape
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.runtime import build_train

    mcfg = ModelCfg(name="tiny", arch_type="dense", n_layers=2, d_model=32,
                    n_heads=4, n_kv_heads=2, d_ff=64, vocab=128)

    def pack_for(opt_name, use_kernel=False, compressor="sign"):
        run = RunCfg(model=mcfg,
                     parallel=ParallelCfg(profile="A", remat="none"),
                     optim=OptimCfg(name=opt_name, p=2,
                                    compressor=compressor,
                                    use_kernel=use_kernel,
                                    kernel_interpret=True))
        mesh = make_debug_mesh(8, 1)
        return build_train(run, mesh, InputShape("t", 16, 8, "train"))
""")

_SCRIPT_GREEN = _PRELUDE + textwrap.dedent("""
    for opt_name, use_kernel in [("pd_sgdm", False), ("pd_sgdm", True),
                                 ("cpd_sgdm", False)]:
        pack = pack_for(opt_name, use_kernel)
        v = hc.check_sharded_round(pack, label=opt_name)
        jx = jax.make_jaxpr(pack.train_round)(
            pack.params_struct, pack.state_struct, pack.round_batch_struct)
        v += jc.check_no_host_callbacks(jx)
        v += jc.check_round_scan(jx, 2)
        v += jc.check_gossip_boundary(jx)
        assert v == [], (opt_name, use_kernel, v)
    print("SHARDED_CONTRACT_OK")
""")

_SCRIPT_SEEDED_ALLGATHER = _PRELUDE + textwrap.dedent("""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    pack = pack_for("pd_sgdm")
    mesh = pack.layout.mesh
    ax = pack.layout.worker_axes[0]
    inner = pack.train_round

    def sabotaged(params, state, batches):
        params, state, losses = inner(params, state, batches)
        # the regression the allowlist exists for: an accidental
        # full-param all-gather riding the round
        leaf = jax.tree_util.tree_leaves(params)[0]
        extra = shard_map(
            lambda s: jax.lax.all_gather(s, ax),
            mesh=mesh, in_specs=P(ax),
            out_specs=P(None, ax))(leaf)
        losses = losses + extra.sum() * 0.0
        return params, state, losses

    txt = jax.jit(sabotaged).lower(
        pack.params_struct, pack.state_struct,
        pack.round_batch_struct).compile().as_text()
    stats = parse_collectives(txt)
    v = hc.check_collectives_allowed(stats)
    assert v, "seeded all-gather was not caught"
    assert any("all-gather" in s for s in v), v
    print("SEEDED_ALLGATHER_CAUGHT")
""")

_SCRIPT_SEEDED_NO_DONATE = _PRELUDE + textwrap.dedent("""
    pack = pack_for("pd_sgdm")
    # recompile the same round WITHOUT donate_argnums: the alias map
    # disappears and check_donation must flag it
    bare = jax.jit(pack.train_round.__wrapped__
                   if hasattr(pack.train_round, "__wrapped__")
                   else lambda p, s, b: pack.train_round(p, s, b))
    txt = bare.lower(pack.params_struct, pack.state_struct,
                     pack.round_batch_struct).compile().as_text()
    n = sum(len(jax.tree_util.tree_leaves(t))
            for t in (pack.params_struct, pack.state_struct))
    v = hc.check_donation(txt, n)
    assert v, "dropped donation was not caught"
    assert "donation" in v[0], v
    # and the donating executable passes
    good = hc.compile_round_text(pack)
    assert hc.check_donation(good, n) == []
    print("SEEDED_NO_DONATE_CAUGHT")
""")


def _run(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_sharded_contract_green_at_head():
    assert "SHARDED_CONTRACT_OK" in _run(_SCRIPT_GREEN)


@pytest.mark.slow
def test_seeded_allgather_caught():
    assert "SEEDED_ALLGATHER_CAUGHT" in _run(_SCRIPT_SEEDED_ALLGATHER)


@pytest.mark.slow
def test_seeded_dropped_donation_caught():
    assert "SEEDED_NO_DONATE_CAUGHT" in _run(_SCRIPT_SEEDED_NO_DONATE)
