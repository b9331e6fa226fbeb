"""A small copy of the Granite cell that a CPU test can hold, shared by the
hybrid's tests: the cell's driver, optimizer, batch and pattern kept; the
widths, vocabulary and sequence cut."""
import faults
from harness import common

CELL = "granite4h-micro-pd-p4-s4096"
SMALL = dict(d_model=64, n_heads=4, n_kv_heads=1, head_dim=16, d_ff=128,
             vocab=256, ssm_state=16, ssm_headdim=16, ssm_chunk=8)
SEQ = 32
# the limits of the small copy, read on the CPU at that size: sound runs
# (8 seeds, max) 2.2e-3, 2.4e-3, 2.9e-3; the fp8 control (min) 0.031, 0.48,
# 0.45; half the tokens (min) 0.14, 0.43, 0.46.  The cell's own limits are
# read on the chip at the cell's size.
SMALL_CHECK = {"loss_gap": 0.01, "grad_gap": 0.05, "update_gap": 0.1}


def cell() -> dict:
    c = common.load_cell(CELL)
    c["check"] = dict(SMALL_CHECK)
    c["config_spec"]["model"].update(SMALL)
    c["traffic_spec"]["data"].update(seq_len=SEQ)
    return c


def run(monkeypatch, seed=2 ** 31 + 99, variants=()):
    monkeypatch.setattr(common, "enable_compile_cache", lambda: "off")
    return faults.bench_run().run_cell(CELL, seed, 0.2, False, cell=cell(),
                                       require_accelerator=False,
                                       variants=variants)
