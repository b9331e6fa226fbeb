"""Small copies of the cells and the faults planted under their timed path,
shared by the fault tests."""
import dataclasses
import importlib.util
import os

import jax

from harness import common

RUN = os.path.join(common.BENCH, "run.py")
# the limits of the small copies, read on the CPU at that size: sound runs
# (12 seeds, max) 1.0e-3, 2.0e-3, 1.9e-3; the fp8 control (min) 0.018,
# 0.17, 0.21; half the batch (min) 0.045, 0.36, 0.37.  The cells' own
# limits are read on the chip at the cells' size.
TINY_CHECK = {"loss_gap": 0.005, "grad_gap": 0.05, "update_gap": 0.1}


def bench_run():
    spec = importlib.util.spec_from_file_location("bench_run_main", RUN)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny(name: str) -> dict:
    """The cell at a size a test can hold: its driver, optimizer and batch
    kept; the widths, depth, vocabulary and sequence cut, and the limits
    those sizes read."""
    cell = common.load_cell(name)
    cell["check"] = dict(TINY_CHECK)
    cell["config_spec"]["model"].update(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab=256)
    cell["traffic_spec"]["data"].update(seq_len=32)
    return cell


def run_tiny(monkeypatch, name, variants=()):
    monkeypatch.setattr(common, "enable_compile_cache", lambda: "off")
    return bench_run().run_cell(name, 2 ** 31 + 99, 0.2, False,
                                cell=tiny(name), require_accelerator=False,
                                variants=variants)


def freeze_sharded_round(monkeypatch):
    """A round that returns its params and state unchanged."""
    from harness import sharded
    build = sharded.build

    def broken(cell, devices):
        run, mesh, pack = build(cell, devices)
        real = pack.train_round
        return run, mesh, dataclasses.replace(pack, train_round=jax.jit(
            lambda p, s, b: (p, s, real(p, s, b)[2])))
    monkeypatch.setattr(sharded, "build", broken)


def half_batch_lm(monkeypatch):
    """The model's loss over the first half of each worker's rows."""
    from repro.models.transformer import Model
    loss = Model.loss

    def half(self, params, batch, remat="none"):
        return loss(self, params, {k: v[:v.shape[0] // 2]
                                   for k, v in batch.items()}, remat=remat)
    monkeypatch.setattr(Model, "loss", half)


def no_exchange(monkeypatch):
    """Gossip between chips that leaves every worker's values as they were."""
    from repro.core.gossip import ShardedComm
    monkeypatch.setattr(ShardedComm, "mix", lambda self, tree, r=None: tree)
