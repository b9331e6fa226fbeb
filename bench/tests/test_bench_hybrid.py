"""The Granite cell's pieces on the CPU: its FLOP count against a hand
count, the metrics it reads, its two readers on a hand-made trace, and its
check on a small copy of the cell (``hybrid_small``): a sound run is
correct, and the control and half of the tokens left out are not."""
import pytest

import hybrid_small
from harness import common, flops_hybrid, scopes

GRANITE = hybrid_small.CELL


def test_granite_forward_is_1_968_gflop_per_token():
    model = common.load_cell(GRANITE)["config_spec"]["model"]
    d, v = 2048, 100352
    # the tied head; per Mamba-2 layer the in projection to z, xBC and dt
    # (4096 + 4352 + 64), the out projection, the conv (4 taps of 4352) and
    # the SSD by chunk of 256: (2·128 + 2·64)·64·128 within the chunk, and
    # 2·128·64·64 each for the chunk's state and the inter-chunk output;
    # the attention layer's q/k/v/o (32 + 2·8 heads of 64, then 2048·2048)
    # and its core at an average context of 2048; every layer's SwiGLU MLP
    head = 2 * d * v
    mamba = (2 * d * 8512 + 2 * 4096 * d + 2 * 4 * 4352
             + 384 * 64 * 128 + 2 * 2 * 128 * 64 * 64)
    attn = 2 * d * 64 * 48 + 2 * 2048 * d + 4 * 32 * 64 * 2048
    mlp = 6 * d * 8192
    assert mamba == 56_920_064 and attn == 37_748_736
    got = flops_hybrid.forward_flops_per_token(model, 4096)
    assert got == head + 9 * (mamba + mlp) + attn + mlp
    assert round(got / 1e9, 3) == 1.968


def test_training_counts_three_forward_passes_of_the_hybrid_only():
    cell = common.load_cell(GRANITE)
    cfg, data = cell["config_spec"], cell["traffic_spec"]["data"]
    assert flops_hybrid.train_flops_per_item(cfg, data) == \
        3 * flops_hybrid.forward_flops_per_token(cfg["model"], 4096)
    with pytest.raises(ValueError):
        flops_hybrid.train_flops_per_item(
            common.load_json("configs", "olmo-1b.json"), data)


def test_the_granite_cell_reads_its_listed_metrics():
    granite = common.per_layer_metrics(GRANITE)
    assert {"mfu.hybrid", "ssm_ms.hybrid", "attention_ms.lm",
            "compute_ms.lm"} <= set(granite)
    assert "mfu.lm" not in granite and "gossip_ms.ring4" not in granite
    for name in granite:
        assert common.load_module("metrics", f"{name}.py").UNIT


def _ctx(monkeypatch):
    """A two-round window of 1 s on one chip: a loop around two ssm ops
    (forward and backward), an attention op and a collective that carries
    the ssm scope, with the instruction table they would have."""
    table = {
        "%fusion.1": ("fusion", "jit(f)/grad/vmap(jvp(ssm))/dot_general"),
        "%fusion.2": ("fusion",
                      "jit(f)/grad/vmap(transpose(jvp(ssm)))/mul"),
        "%fusion.3": ("fusion", "jit(f)/grad/attn/dot_general"),
        "%collective-permute.1": ("collective-permute",
                                  "jit(f)/gossip/ssm/permute"),
    }
    monkeypatch.setattr(scopes, "table", lambda cell: table)
    ops = [["%while.3", 0, 9e8], ["fusion.1 = f32[2]{0}", 1e8, 2e8],
           ["%fusion.2", 9.5e8, 1e8], ["%fusion.3", 0, 1e8],
           ["%collective-permute.1", 0, 5e7]]
    return {"cell": common.load_cell(GRANITE), "rounds": 2,
            "items": 2 * 4 * 4096, "peaks": {"bf16_flops_per_s": 197e12},
            "trace": {"window": [0, 1e9], "host": [],
                      "devices": [{"name": "/device:TPU:0", "ops": ops}]}}


def test_ssm_time_sums_the_ssm_scope_in_every_pass(monkeypatch):
    # fusion.1 whole (200 ms), fusion.2 clipped at the window's end (50
    # ms); the loop, the attention op and the collective left out
    ctx = _ctx(monkeypatch)
    read = common.load_module("metrics", "ssm_ms.hybrid.py").read
    assert read(ctx) == pytest.approx((200 + 50) / 2)


def test_mfu_of_the_hybrid_is_its_flops_over_the_peak(monkeypatch):
    ctx = _ctx(monkeypatch)
    read = common.load_module("metrics", "mfu.hybrid.py").read
    per_token = 3 * 1_967_704_064
    assert read(ctx) == pytest.approx(
        100 * per_token * 2 * 4 * 4096 / 1.0 / 197e12)


CONTROL = {"compute": "float8_e4m3fn"}
HALF = {"half_batch": True}


@pytest.fixture(scope="module")
def small_run():
    with pytest.MonkeyPatch.context() as mp:
        return hybrid_small.run(mp, variants=[CONTROL, HALF])


def test_a_sound_small_run_is_correct(small_run):
    assert small_run["correct"], small_run["checks"]


@pytest.mark.parametrize("variant", ["compute=float8_e4m3fn",
                                     "half_batch=True"])
def test_the_control_and_half_the_tokens_fail_the_check(small_run, variant):
    values = small_run["variants"][variant]
    limits = hybrid_small.SMALL_CHECK
    assert any(values[n] > lim for n, lim in limits.items()), values


def test_half_of_the_tokens_left_out_of_the_program_is_caught(monkeypatch):
    """The program's loss over the first half of the sequence only (the
    cell has one row per step), put under the timed path."""
    from repro.models.transformer import Model
    loss = Model.loss

    def half(self, params, batch, remat="none"):
        labels = batch["labels"]
        keep = labels.shape[-1] // 2
        return loss(self, params, dict(batch, labels=labels.at[
            ..., keep:].set(-1)), remat=remat)
    monkeypatch.setattr(Model, "loss", half)
    res = hybrid_small.run(monkeypatch)
    assert not res["correct"], res["values"]
