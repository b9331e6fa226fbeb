"""The reduction from a trace to the per-layer metrics, on traces whose
numbers are worked out by hand."""
import json
import os

import pytest

from harness import common, readers, trace

# a window of 100 ns on one device:
#   fusion.1 [10, 30) and fusion.2 [20, 40) overlap, a collective-permute
#   [50, 70) with convolution.3 [60, 65) inside it, and custom-call.4
#   [80, 90); the host was in feed [0, 12), dispatch [40, 55) and sync
#   [70, 100)
REC = {
    "window": [0.0, 100.0],
    "devices": [{"name": "/device:TPU:0", "ops": [
        ["fusion.1", 10.0, 20.0], ["fusion.2", 20.0, 20.0],
        ["collective-permute-done.1", 50.0, 20.0],
        ["convolution.3", 60.0, 5.0], ["custom-call.4", 80.0, 10.0]]}],
    "host": [["bench.window", 0.0, 100.0], ["bench.feed", 0.0, 12.0],
             ["bench.dispatch", 40.0, 15.0], ["bench.sync", 70.0, 30.0]],
}
DEV, WIN = REC["devices"][0], REC["window"]


def test_busy_union_and_idle_share():
    # [10, 40) + [50, 70) + [80, 90) = 30 + 20 + 10
    assert trace.busy_ns(DEV, WIN) == 60.0
    assert trace.idle_share(DEV, WIN) == pytest.approx(0.4)


def test_collective_time_and_exposed_part():
    assert trace.time_ns(DEV, readers.COLLECTIVE, WIN) == 20.0
    # 20 ns of collective, 5 of them under convolution.3
    assert trace.exposed_ns(DEV, readers.COLLECTIVE, WIN) == 15.0


def test_idle_gaps_are_named_by_the_host_span_open_over_them():
    gaps = trace.idle_gaps(DEV, WIN, REC["host"])
    assert sorted(gaps) == sorted([["bench.feed", 10e-9],
                                   ["bench.dispatch", 10e-9],
                                   ["bench.sync", 10e-9],
                                   ["bench.sync", 10e-9]])


def test_a_gap_no_span_covers_is_named_so():
    host = [h for h in REC["host"] if h[0] != "bench.feed"]
    gaps = trace.idle_gaps(DEV, WIN, host)
    assert ["no span", 10e-9] in gaps


def test_ops_clipped_to_the_window():
    assert trace.busy_ns(DEV, [15.0, 55.0]) == 25.0 + 5.0
    assert trace.top_ops(DEV, WIN)[0] == ["fusion.1", 20e-9]


def test_a_loop_that_holds_ops_hides_nothing():
    # the chip's trace puts a while loop [5, 95) on the line of its body's
    # ops: the device is busy while it runs, but it is no top op, adds
    # nothing to compute time, and covers no collective
    ops = [["%while.7 = (s32[]) while(s32[] %t), body=%b", 5.0, 90.0]]
    dev = dict(DEV, ops=DEV["ops"] + ops)
    assert trace.busy_ns(dev, WIN) == 90.0
    assert trace.top_ops(dev, WIN)[0] == ["fusion.1", 20e-9]
    assert trace.exposed_ns(dev, readers.COLLECTIVE, WIN) == 15.0
    ctx = dict(_ctx("olmo1b-pd-p4"), trace=dict(REC, devices=[dev]))
    assert readers.compute_ms_per_round(ctx) == pytest.approx(45e-6)


def _ctx(cell_name):
    cell = common.load_cell(cell_name)
    peaks = common.load_json("peaks.json")["devices"]["TPU v5 lite"]
    return {"cell": cell, "trace": REC, "peaks": peaks, "rounds": 1,
            "items": 1000}


def test_compute_time_leaves_out_the_collectives():
    # fusion.1 ∪ fusion.2 = [10, 40), convolution.3 = [60, 65),
    # custom-call.4 = [80, 90)
    assert readers.compute_ms_per_round(_ctx("olmo1b-pd-p4")) == \
        pytest.approx(45e-6)


def test_the_readers_of_the_lm_cells():
    lm = _ctx("olmo1b-ring4-pd-p4")
    idle = common.load_module("metrics", "device_idle_share.lm.py")
    assert idle.read(lm) == pytest.approx(40.0)
    host = common.load_module("metrics", "host_ms_per_round.lm.py")
    # feed 12 ns + dispatch 15 ns in one round
    assert host.read(lm) == pytest.approx(27e-6)
    gossip = common.load_module("metrics", "gossip_ms.ring4.py")
    assert gossip.read(lm) == pytest.approx(20e-6)
    exposed = common.load_module("metrics", "gossip_exposed_ms.ring4.py")
    assert exposed.read(lm) == pytest.approx(15e-6)
    quiet = dict(lm, trace=dict(REC, devices=[{"name": "d", "ops": [
        ["fusion.1", 10.0, 20.0]]}]))
    assert gossip.read(quiet) is None and exposed.read(quiet) is None


def test_mfu_is_model_flops_over_the_peak():
    ctx = _ctx("olmo1b-pd-p4")
    mfu = common.load_module("metrics", "mfu.lm.py")
    per_token = 3 * 2_487_746_560
    want = 100 * per_token * 1000 / 100e-9 / 197e12
    assert mfu.read(ctx) == pytest.approx(want)


# 50 us recorded on a TPU v5 lite (bench/testdata): 45 ops, none
# overlapping another, the host in bench.sync throughout
CHIP = os.path.join(common.BENCH, "testdata",
                    "olmo1b-pd-p4.round-boundary.json")


@pytest.fixture(scope="module")
def chip():
    with open(CHIP, encoding="utf-8") as f:
        return json.load(f)


def test_busy_union_and_idle_share_of_a_chip_slice(chip):
    dev, win = chip["devices"][0], chip["window"]
    # the 45 ops' lengths inside the slice sum to 23656 ns (the first op,
    # %copy.376, began 3.27 ms earlier and ends 4438 ns in; the last,
    # %copy.349, starts at 46520 and runs past the end)
    assert trace.busy_ns(dev, win) == 23656.0
    assert trace.idle_share(dev, win) == pytest.approx(26344 / 50000)


def test_idle_gaps_of_a_chip_slice(chip):
    dev, win = chip["devices"][0], chip["window"]
    gaps = trace.idle_gaps(dev, win, chip["host"], n=3)
    # [4438, 14464) before the feed's first op, [39876, 46518) between the
    # feed's last op and the round's first, [15102, 18998) inside the feed
    assert [g[0] for g in gaps] == ["bench.sync"] * 3
    assert [g[1] for g in gaps] == pytest.approx([10026e-9, 6642e-9,
                                                  3896e-9])
    assert len(trace.gaps(trace.spans_of(dev["ops"]), *win)) == 36


# 2.91 ms of chip 0 recorded on four TPU v5 lite (bench/testdata): the end
# of the round's scan (%while.198 to 15032, %fusion.613 to 9145), then the
# gossip's collective-permute starts and dones
RING = os.path.join(common.BENCH, "testdata",
                    "olmo1b-ring4-pd-p4.gossip.json")


def test_gossip_time_and_exposed_part_of_a_chip_slice():
    with open(RING, encoding="utf-8") as f:
        rec = json.load(f)
    dev, win = rec["devices"][0], rec["window"]
    # starts 25 + 2348 + 2 + 5 + 2580 + 3, done.1 2874277, and the second
    # done clipped at the slice's end, 2914277 - 2894282 = 19995
    assert trace.time_ns(dev, readers.COLLECTIVE, win) == 2899235.0
    # nothing else runs beside them: all of it is exposed
    assert trace.exposed_ns(dev, readers.COLLECTIVE, win) == 2899235.0
    # busy: [0, 15032) and the collectives; six gaps of 1 or 2 ns, 10 in all
    assert trace.busy_ns(dev, win) == 2914267.0
    assert trace.top_ops(dev, win, 1) == [["%collective-permute-done.1",
                                           pytest.approx(2874277e-9)]]
