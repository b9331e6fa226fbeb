"""From a profiler trace to the intervals the per-layer readers use.

``capture`` runs a callable under ``jax.profiler`` and reduces the xplane
file to a small, plain record, which is all that the readers and the
tests see::

    {"window": [start_ns, end_ns],
     "devices": [{"name": "/device:TPU:0", "ops": [[name, start, dur], ...]}],
     "host": [[span name, start, dur], ...]}

Device ops come from each device plane's ``XLA Ops`` line, host spans are
the benchmark's own ``TraceAnnotation``s (names starting ``bench.``), and
the window is the ``bench.window`` span.  The functions below reduce
intervals; none of them knows a cell.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# control flow: its body's ops lie on the same line, inside its interval
CONTAINER = re.compile(r"^%?(while|conditional|call)(\.\d+)?(\s|$)")


def is_container(name: str) -> bool:
    return bool(CONTAINER.match(name))


# ------------------------------------------------------------ intervals
def merge(intervals):
    """Sorted, disjoint [start, end) intervals covering the same points."""
    out = []
    for s, e in sorted((float(a), float(b)) for a, b in intervals if b > a):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def overlap(a, b) -> float:
    """Length of the points that lie in both sets of intervals."""
    a, b = merge(a), merge(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(intervals, lo, hi):
    """The stretches of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def spans_of(ops):
    return [(s, s + d) for _n, s, d in ops]


# ------------------------------------------------------------ per device
def busy_ns(dev: dict, window) -> float:
    """Union of the device's op intervals inside the window."""
    return length(clip(spans_of(dev["ops"]), *window))


def idle_share(dev: dict, window) -> float:
    lo, hi = window
    return 1.0 - busy_ns(dev, window) / (hi - lo)


def matching(dev: dict, pattern: str, window):
    rx = re.compile(pattern)
    return [(s, s + d) for n, s, d in dev["ops"] if rx.search(n)
            and min(s + d, window[1]) > max(s, window[0])]


def time_ns(dev: dict, pattern: str, window) -> float:
    """Summed duration of the ops whose name matches, inside the window."""
    return sum(e - s for s, e in clip(matching(dev, pattern, window),
                                      *window))


def exposed_ns(dev: dict, pattern: str, window) -> float:
    """The part of the matching ops' time during which no other op runs
    on that device (a loop that holds them is not another op)."""
    rx = re.compile(pattern)
    mine = clip(matching(dev, pattern, window), *window)
    others = clip([(s, s + d) for n, s, d in dev["ops"]
                   if not rx.search(n) and not is_container(n)], *window)
    return length(mine) - overlap(mine, others)


def top_ops(dev: dict, window, n: int = 10):
    """[[op name, seconds]] of the ops that took most device time; loops
    and calls are left out, since their bodies' ops are counted."""
    tot: dict = {}
    for name, s, d in dev["ops"]:
        if is_container(name):
            continue
        c = clip([(s, s + d)], *window)
        if c:
            tot[name] = tot.get(name, 0.0) + (c[0][1] - c[0][0])
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(dev: dict, window, host, n: int = 10):
    """[[host span, seconds]] of the longest device idle gaps, each named by
    the innermost benchmark span open over most of it (``no span`` when
    none was)."""
    out = []
    for s, e in gaps(spans_of(dev["ops"]), *window):
        best, best_cover = "no span", 0.0
        for name, hs, hd in host:
            if name == WINDOW_SPAN:
                continue
            cover = overlap([(s, e)], [(hs, hs + hd)])
            if cover > best_cover or (cover == best_cover and cover > 0
                                      and hd < _dur(host, best)):
                best, best_cover = name, cover
        out.append([best, (e - s) * 1e-9])
    out.sort(key=lambda g: -g[1])
    return out[:n]


def _dur(host, name):
    return min((d for n, _s, d in host if n == name), default=float("inf"))


def span_ns(host, name: str, window) -> float:
    return length(clip([(s, s + d) for n, s, d in host if n == name],
                       *window))


# ------------------------------------------------------------ capture
def capture(fn, out_dir: str) -> dict:
    """Run ``fn`` under the profiler; return the reduced record and remove
    the raw trace."""
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no xplane file in {out_dir}")
    rec = reduce_xplane(jax.profiler.ProfileData.from_file(files[0]))
    shutil.rmtree(out_dir, ignore_errors=True)
    return rec


def reduce_xplane(pd) -> dict:
    devices, host = [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:[A-Z]+:\d+", plane.name):
            ops = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                   for line in plane.lines if line.name == "XLA Ops"
                   for ev in line.events]
            devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            host.extend([ev.name, float(ev.start_ns), float(ev.duration_ns)]
                        for line in plane.lines for ev in line.events
                        if ev.name.startswith(SPAN_PREFIX))
    devices.sort(key=lambda d: d["name"])
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if not win:
        raise RuntimeError("the trace holds no bench.window span")
    window = [win[0][1], win[0][1] + win[0][2]]
    return {"window": window, "devices": devices, "host": host}


def save(rec: dict, path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(rec, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt", encoding="utf-8") as f:
        return json.load(f)
