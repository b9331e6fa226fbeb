"""The harness's own rules: unknown chips, seeds, traffic, the comparison."""
import math

import jax
import numpy as np
import pytest

from harness import check, common, traffic


def test_a_device_kind_not_in_the_peaks_table_is_refused():
    with pytest.raises(common.BenchError, match="not in bench/peaks.json"):
        common.peaks_for("TPU v9 imaginary")
    assert common.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_no_accelerator_no_result():
    with pytest.raises(common.BenchError, match="no accelerator"):
        common.find_devices(1)


def test_large_seeds_give_distinct_repeatable_keys():
    big = 2 ** 31 + 7
    k = [jax.random.key_data(common.seed_key(s)) for s in (7, big, big)]
    assert not np.array_equal(k[0], k[1])
    assert np.array_equal(k[1], k[2])


def test_every_row_of_every_step_differs_and_repeats_from_the_seed():
    data = common.load_json("traffic", "lm-s2048-b2-pd-p4.json")["data"]
    data = dict(data, seq_len=16)
    key = common.seed_key(3)
    a = traffic.round_batch(data, key, 0, 4, 2, vocab=50304)
    b = traffic.round_batch(data, key, 0, 4, 2, vocab=50304)
    c = traffic.round_batch(data, key, 1, 4, 2, vocab=50304)
    assert a["tokens"].shape == (4, 2, 2, 16)
    assert np.array_equal(a["tokens"], b["tokens"])
    rows = np.concatenate([a["tokens"].reshape(-1, 16),
                           c["tokens"].reshape(-1, 16)])
    assert len({r.tobytes() for r in rows}) == len(rows)
    np.testing.assert_array_equal(a["labels"][..., :-1], a["tokens"][..., 1:])


def test_a_cell_reads_only_the_per_layer_metrics_listed_for_it():
    one = common.per_layer_metrics("olmo1b-pd-p4")
    ring = common.per_layer_metrics("olmo1b-ring4-pd-p4")
    assert "mfu.lm" in one and "mfu.lm" in ring
    assert "gossip_ms.ring4" in ring and "gossip_ms.ring4" not in one
    for name in ring:
        assert common.load_module("metrics", f"{name}.py").UNIT


def _readings(scale=1.0, loss=2.0):
    return {"losses": [loss, loss], "m_first": [[1.0 * scale, 2.0, 3.0]],
            "dx": [[0.5, 1.0 * scale, 4.0]]}


def test_gaps_by_the_worst_leaf_against_the_larger_of_leaf_and_median():
    ref = _readings()
    prog = {"losses": [2.0, 2.2], "m_first": [[1.1, 2.0, 3.0]],
            "dx": [[0.6, 1.0, 4.0]]}
    got = check.numbers(prog, ref)
    assert got["loss_gap"] == pytest.approx(0.1)
    # leaf 0: |1.1 − 1| over max(1, median 2)
    assert got["grad_gap"] == pytest.approx(0.05)
    # leaf 0 of the change: |0.6 − 0.5| over max(0.5, median 1)
    assert got["update_gap"] == pytest.approx(0.1)


def test_a_quiet_leaf_is_left_out_of_the_update_gap():
    ref = {"losses": [1.0], "m_first": [[1e-6, 2.0, 3.0]],
           "dx": [[1e-7, 1.0, 4.0]]}
    prog = {"losses": [1.0], "m_first": [[1e-6, 2.0, 3.0]],
            "dx": [[5.0, 1.0, 4.0]]}
    assert check.numbers(prog, ref)["update_gap"] == 0.0


def test_an_unchanged_state_reads_one():
    ref = _readings()
    prog = dict(ref, dx=[[0.0, 0.0, 0.0]])
    assert check.numbers(prog, ref)["update_gap"] == pytest.approx(1.0)


def test_a_nan_fails_every_limit():
    ref = _readings()
    prog = dict(ref, losses=[float("nan"), 2.0])
    got = check.numbers(prog, ref)
    assert got["loss_gap"] == math.inf
    ok, rows = check.judge(got, {"loss_gap": 1e9})
    assert not ok and rows[0]["value"] == math.inf
