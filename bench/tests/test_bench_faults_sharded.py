"""The check of the one-chip ``sharded`` cell on a small copy run on the
CPU: a sound run is correct; the control and each fault the cell can have
are not.  (The exchange between chips is the ring cell's:
``test_bench_faults_ring.py``.)"""
import faults

CELL = "olmo1b-pd-p4"


def test_sound_run_is_correct_and_the_control_is_not(monkeypatch):
    res = faults.run_tiny(monkeypatch, CELL,
                          variants=[{"compute": "float8_e4m3fn"}])
    assert res["correct"], res["checks"]
    limits = faults.tiny(CELL)["check"]
    control = res["variants"]["compute=float8_e4m3fn"]
    assert any(control[n] > lim for n, lim in limits.items()), control


def test_a_round_that_leaves_its_state_unchanged_is_caught(monkeypatch):
    faults.freeze_sharded_round(monkeypatch)
    res = faults.run_tiny(monkeypatch, CELL)
    assert not res["correct"]
    assert res["values"]["update_gap"] > 0.9


def test_half_of_the_batch_left_out_is_caught(monkeypatch):
    faults.half_batch_lm(monkeypatch)
    res = faults.run_tiny(monkeypatch, CELL)
    assert not res["correct"], res["values"]
