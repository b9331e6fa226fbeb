"""What the per-layer readers under ``metrics/`` share.

A reader's ``read(ctx)`` returns one number, or None where it finds nothing
to read.  ``ctx`` holds the cell, the reduced trace of the window
(``harness.trace``), the chip's peaks and the window's rounds and tokens.
The run loop calls the readers of the metrics that ``BENCHMARK.json``
lists for the cell, and no others.
"""
from __future__ import annotations

import re

from . import flops, trace

COLLECTIVE = r"^%?collective-permute"


def window_s(ctx) -> float:
    lo, hi = ctx["trace"]["window"]
    return (hi - lo) * 1e-9


def mfu_percent(ctx) -> float:
    """Model FLOPs of the window's work over the chips' bf16 peak."""
    cell = ctx["cell"]
    per_item = flops.train_flops_per_item(cell["config_spec"],
                                          cell["traffic_spec"]["data"])
    chips = len(ctx["trace"]["devices"])
    return 100.0 * per_item * ctx["items"] / window_s(ctx) / (
        chips * ctx["peaks"]["bf16_flops_per_s"])


def per_round_ms(ns: float, ctx) -> float:
    return ns * 1e-6 / ctx["rounds"]


def mean_over_devices(fn, ctx) -> float:
    devs = ctx["trace"]["devices"]
    return sum(fn(d) for d in devs) / len(devs)


def idle_percent(ctx) -> float:
    rec = ctx["trace"]
    return 100.0 * max(trace.idle_share(d, rec["window"])
                       for d in rec["devices"])


def host_ms_per_round(ctx) -> float:
    rec = ctx["trace"]
    ns = sum(trace.span_ns(rec["host"], s, rec["window"])
             for s in ("bench.feed", "bench.dispatch"))
    return per_round_ms(ns, ctx)


def compute_ms_per_round(ctx) -> float:
    """Device busy time less the collective-permutes (and less the loops
    that hold them)."""
    rec = ctx["trace"]
    rx = re.compile(COLLECTIVE)

    def one(dev):
        kept = [(s, s + d) for n, s, d in dev["ops"]
                if not rx.search(n) and not trace.is_container(n)]
        return trace.length(trace.clip(kept, *rec["window"]))
    return per_round_ms(mean_over_devices(one, ctx), ctx)


def collective_ms_per_round(ctx, exposed: bool) -> float | None:
    """Device time of the collective-permutes per round, averaged over the
    chips, or only the part no other op on that chip overlaps; None where
    the window has no collective."""
    rec = ctx["trace"]
    if not any(trace.matching(d, COLLECTIVE, rec["window"])
               for d in rec["devices"]):
        return None
    fn = trace.exposed_ns if exposed else trace.time_ns
    return per_round_ms(mean_over_devices(
        lambda d: fn(d, COLLECTIVE, rec["window"]), ctx), ctx)
