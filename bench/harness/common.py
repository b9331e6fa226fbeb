"""What every cell shares: its files, the seed, the device and the result line.

A cell is ``workloads/<cell>.json``.  It names a configuration
(``configs/<config>.json``: the model and the deployment) and a traffic mix
(``traffic/<traffic>.json``: the data and the optimizer job), the driver
that runs them, the chips it needs, and the limits of its check.  Nothing
here is keyed on a cell's name.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
# process start, as near as the harness can see it: ``setup_s`` runs from here
T_START = time.perf_counter()


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, bad spec)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(BENCH, *parts)
    if not os.path.isfile(path):
        raise BenchError(f"no such file: {os.path.relpath(path, ROOT)}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell with its configuration and traffic resolved by name."""
    cell = load_json("workloads", f"{name}.json")
    cell["name"] = name
    cell["config_spec"] = load_json("configs", f"{cell['config']}.json")
    cell["traffic_spec"] = load_json("traffic", f"{cell['traffic']}.json")
    return cell


def per_layer_metrics(cell_name: str) -> list:
    """The per-layer metrics ``BENCHMARK.json`` lists for a cell: those
    whose ``workloads`` name it, and those with no ``workloads`` key."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    return [m["name"] for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def load_module(*parts: str):
    """Import a file under ``bench/`` by path (metric readers have dots in
    their names, so they are not importable as packages)."""
    path = os.path.join(BENCH, *parts)
    if not os.path.isfile(path):
        raise BenchError(f"no such file: {os.path.relpath(path, ROOT)}")
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cell: dict):
    """``references/<name>.py``, the plain reference the configuration
    names."""
    return importlib.import_module(
        f"references.{cell['config_spec']['reference']}")


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 31 bits seed the key and
    the rest is folded in, so seeds past 2**31 stay distinct."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed % 2 ** 31)
    return jax.random.fold_in(key, (seed // 2 ** 31) % 2 ** 31)


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in ``peaks.json`` is an error."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def find_devices(chips: int, require_accelerator: bool = True):
    """The first ``chips`` devices.  Off an accelerator, or with fewer
    chips than the cell asks for, the run ends with no result."""
    import jax
    devices = jax.devices()
    if require_accelerator and devices[0].platform == "cpu":
        raise BenchError("JAX finds no accelerator (platform 'cpu'); the "
                         "benchmark does not fall back to the CPU")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """The program's persistent compile cache (a fixed path in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), with every program cached,
    however quick it was to compile, so a second run compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable
    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def finite(values) -> list:
    return [bool(math.isfinite(float(v))) for v in values]


def print_result(*, correct: bool, attempted: int, failed: int,
                 metrics: dict, device: dict, checks: list,
                 breakdown: dict | None = None) -> None:
    """The result line, last on stdout; the compared numbers, each beside
    its limit, last on stderr and last in the line."""
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})"
            f"{'' if c['value'] <= c['limit'] else '  <- over the limit'}")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # a reading that is missing or not finite prints as 1e300
    out["checks"] = {c["name"]: {"value": min(c["value"], 1e300),
                                 "limit": c["limit"]} for c in checks}
    print(json.dumps(out), flush=True)
