"""Readings that set a cell's limits: the program, its control and its faults.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--variants '[{"compute": "float8_e4m3fn"}, {"half_batch": true}]'] \
        [--seconds 2] [--trace 0] [--out <readings>.jsonl]

For each seed, in one process: one run of the cell (set-up, a short window,
the program's numbers against the plain reference), then each variant of
the reference put in the program's place — the control
(``{"compute": <lower dtype>}``) and the faults (``{"half_batch": true}``:
half of each batch left out, the mean over the rest; ``{"topology":
"none"}``: the exchange between workers left out) — compared with the same
reference.  A state left unchanged reads 1 on ``update_gap`` by
construction and needs no run.  One JSON line per seed goes to ``--out``
and to stdout.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as bench  # noqa: E402
from harness import common  # noqa: E402


def describe(pd, per_line: int = 25) -> str:
    """Planes, lines and the first events of a raw xplane, with stats."""
    rows = []
    for plane in pd.planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            rows.append(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                stats = {k: str(v)[:120] for k, v in ev.stats}
                rows.append(f"    {ev.name[:100]!r} start {ev.start_ns} "
                            f"dur {ev.duration_ns} {stats}")
    return "\n".join(rows) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="[]")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--save-trace", default=None,
                    help="keep each run's reduced trace here, as "
                         "<path>.<seed>.json.gz")
    ap.add_argument("--describe", default=None,
                    help="write the planes, lines and first events of each "
                         "raw trace here (to map op names by hand)")
    args = ap.parse_args(argv)
    if args.describe:
        from harness import trace
        reduce = trace.reduce_xplane

        def describing(pd):
            with open(args.describe, "a", encoding="utf-8") as f:
                f.write(describe(pd))
            return reduce(pd)
        trace.reduce_xplane = describing
    sys.path.insert(0, os.path.join(common.ROOT, "src"))
    variants = json.loads(args.variants)
    out = open(args.out, "a", encoding="utf-8") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        res = bench.run_cell(args.workload, seed, args.seconds,
                             bool(args.trace), variants=variants)
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "trace": args.trace, "correct": res["correct"],
                           "values": res["values"],
                           "variants": res["variants"],
                           "metrics": res["metrics"],
                           "device": res["device"],
                           "breakdown": res["breakdown"]})
        print(line, flush=True)
        if args.save_trace and res["trace"] is not None:
            from harness import trace
            trace.save(res["trace"], f"{args.save_trace}.{seed}.json.gz")
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
