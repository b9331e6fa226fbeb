"""The benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips this machine holds: set-up
(build, weights from the seed, compile or cache load, the first rounds
that the check compares), then a window of ``--seconds``, then the check
against the plain reference.  The last line of stdout is one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and ``breakdown`` with ``--trace 1``); the numbers compared, each beside
its limit, close stderr and the line.  With ``--trace 0`` the metrics are
the cell's end-to-end ones, with ``--trace 1`` its per-layer ones, read
from a profiler trace of the window.  Off an accelerator, or with fewer
chips than the cell needs, it prints no result and exits non-zero.
"""
import argparse
import importlib
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from harness import common  # noqa: E402  (starts the set-up clock)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def traced(window):
    """Run the window under the profiler: its result and the reduced trace."""
    from harness import trace
    box = {}

    def fn():
        box["out"] = window()
    rec = trace.capture(fn, os.path.join(common.ROOT, ".bench_trace"))
    return box["out"], rec


def per_layer(cell, out, peaks) -> dict:
    """The readers ``metrics/<metric>.py`` of the per-layer metrics that
    ``BENCHMARK.json`` lists for this cell; a reader that finds nothing to
    read returns None and its metric is left out."""
    ctx = {"cell": cell, "trace": out["trace"], "peaks": peaks,
           "rounds": out["rounds"], "items": out["items"]}
    metrics = {}
    for name in common.per_layer_metrics(cell["name"]):
        reader = common.load_module("metrics", f"{name}.py")
        value = reader.read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    return metrics


def run_cell(name: str, seed: int, seconds: float, trace_on: bool, *,
             cell: dict | None = None, require_accelerator: bool = True,
             variants=()) -> dict:
    """One run of a cell; returns the fields of the result line.  ``cell``
    replaces the cell's files (tests run small copies), ``variants``
    are reference variants put in the program's place (the control and
    the faults): each gets its own numbers."""
    from harness import check
    cell = cell or common.load_cell(name)
    devices = common.find_devices(int(cell["chips"]), require_accelerator)
    peaks = common.peaks_for(devices[0].device_kind) \
        if require_accelerator else None
    common.log(f"{name}: compile cache {common.enable_compile_cache()}")
    driver = importlib.import_module(f"harness.{cell['driver']}")
    out = driver.run(cell, seed, seconds, trace_on, devices,
                     on_window=traced)
    common.log(f"{name}: set-up {out['setup_s']:.2f} s, window "
               f"{out['window_s']:.2f} s, {out['rounds']} rounds, memory "
               f"peak {out['device']['memory_peak_bytes']} bytes")
    t0 = time.perf_counter()
    ref = out["reference"]()
    common.log(f"{name}: reference {time.perf_counter() - t0:.2f} s")
    values = check.numbers(out["prog"], ref)
    ok, rows = check.judge(values, cell["check"])
    extra = {}
    for v in variants:
        extra[json_name(v)] = check.numbers(out["reference"](v), ref)
    if trace_on:
        metrics = per_layer(cell, out, peaks)
        from harness import trace
        rec = out["trace"]
        dev = max(rec["devices"], key=lambda d: trace.idle_share(
            d, rec["window"]))
        window = rec["window"]
        out["device"]["busy_s"] = sum(
            trace.busy_ns(d, window) for d in rec["devices"]) * 1e-9 / len(
            rec["devices"])
        out["device"]["window_s"] = (window[1] - window[0]) * 1e-9
        breakdown = {"device_ops": trace.top_ops(dev, window),
                     "idle_gaps": trace.idle_gaps(dev, window, rec["host"])}
    else:
        unit = cell["config_spec"]["item_unit"]
        metrics = {f"{unit}_per_s": {"value": out["items"] / out["window_s"],
                                     "unit": f"{unit}/s"},
                   "setup_s": {"value": out["setup_s"], "unit": "s"}}
        breakdown = None
    return {"correct": ok and out["failed"] == 0,
            "attempted": out["rounds"], "failed": out["failed"],
            "metrics": metrics, "device": out["device"], "checks": rows,
            "breakdown": breakdown, "variants": extra, "values": values,
            "trace": out["trace"]}


def json_name(variant: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in sorted(variant.items()))


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, os.path.join(common.ROOT, "src"))
    if not os.path.isdir(os.path.join(common.ROOT, "src", "repro")):
        common.log("bench: no src/repro in this checkout: nothing to run")
        return 2
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except common.BenchError as e:
        common.log(f"bench: {e}")
        return 2
    common.print_result(correct=res["correct"], attempted=res["attempted"],
                        failed=res["failed"], metrics=res["metrics"],
                        device=res["device"], checks=res["checks"],
                        breakdown=res["breakdown"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
