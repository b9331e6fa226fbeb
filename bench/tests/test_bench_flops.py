"""The FLOP counts the benchmark keeps, against hand counts."""
import pytest

from harness import common, flops


def test_olmo_1b_forward_is_2_488_gflop_per_token():
    model = common.load_json("configs", "olmo-1b.json")["model"]
    # the tied head 2·2048·50304; per layer q/k/v/o 8·2048², attention
    # core at an average context of 1024: 4·2048·1024, the SwiGLU MLP's
    # gate, up and down projections 6·2048·8192
    head = 2 * 2048 * 50304
    layer = 8 * 2048 ** 2 + 4 * 2048 * 1024 + 6 * 2048 * 8192
    assert head == 206_045_184 and layer == 142_606_336
    got = flops.lm_forward_flops_per_token(model, 2048)
    assert got == head + 16 * layer
    assert round(got / 1e9, 3) == 2.488


def test_olmo_1b_forward_is_1_951_gflop_per_token():
    # the program's own olmo-1b preset: a GELU MLP of two matrices
    model = dict(common.load_json("configs", "olmo-1b.json")["model"],
                 gated_mlp=False)
    head = 2 * 2048 * 50304
    layer = 8 * 2048 ** 2 + 4 * 2048 * 1024 + 4 * 2048 * 8192
    assert layer == 109_051_904
    got = flops.lm_forward_flops_per_token(model, 2048)
    assert got == head + 16 * layer
    assert round(got / 1e9, 3) == 1.951


@pytest.mark.parametrize("gated,mats", [(True, 3), (False, 2)])
def test_the_mlp_counts_its_matrices(gated, mats):
    model = dict(common.load_json("configs", "olmo-1b.json")["model"],
                 gated_mlp=gated, n_layers=1, vocab=0)
    attn = 8 * 2048 ** 2 + 4 * 2048 * 1024
    assert flops.lm_forward_flops_per_token(model, 2048) == \
        attn + 2 * mats * 2048 * 8192


def test_training_counts_three_forward_passes():
    cfg = common.load_json("configs", "olmo-1b.json")
    data = common.load_json("traffic", "lm-s2048-b2-pd-p4.json")["data"]
    assert flops.train_flops_per_item(cfg, data) == \
        3 * flops.lm_forward_flops_per_token(cfg["model"], 2048)
