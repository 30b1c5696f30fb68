"""Paths, file lookup by name, the device gate and the result line.

Nothing here imports JAX at import time: the train runner's parent must
stay off the chip (a chip belongs to one process)."""
from __future__ import annotations

import importlib
import json
import os
import sys
import time

T_PROCESS_START = time.perf_counter()      # set-up is counted from here

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)          # the checkout
# everything the benchmark writes stays inside the checkout, at fixed
# paths (the compile cache's path is part of its key): git-ignored
WORK = os.path.join(ROOT, ".bench_work")
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
WEIGHT_SEEDS = 2    # weights are drawn from seed % WEIGHT_SEEDS (see
                    # fixture_seed): bounds what the HF-layout fixtures
                    # of gpt2-xl (6.2 GB each) take on disk


class BenchError(Exception):
    """Anything that must end the run with a non-zero code and NO
    result line (no chip, a missing file, a program that died)."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def named(kind: str, name: str, search=()) -> dict:
    """The file `<kind>/<name>.json` — under benchmark/ or, for the
    throw-away cells of a demonstration, under an extra directory."""
    for base in (*search, BENCH_DIR):
        p = os.path.join(base, kind, f"{name}.json")
        if os.path.exists(p):
            d = load_json(p)
            d.setdefault("name", name)
            return d
    raise BenchError(f"no {kind}/{name}.json under {[*search, BENCH_DIR]}")


def load_cell(workload: str, search=()) -> dict:
    """A cell = workloads/<name>.json, which names a configuration file,
    a traffic file and the runner (`kind`)."""
    cell = named("workloads", workload, search)
    cell["config_file"] = named("configs", cell["config"], search)
    cell["traffic_file"] = named("traffic", cell["traffic"], search)
    cell["search"] = list(search)
    return cell


def benchmark_json() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def is_listed(workload: str) -> bool:
    return workload in {w["name"] for w in benchmark_json()["workloads"]}


def metrics_for(workload: str, group: str) -> list:
    """BENCHMARK.json's metrics of one group that this cell reports. A
    cell BENCHMARK.json does not list yet (a throw-away cell made of new
    files) reports whatever its runner and the readers give a value."""
    return [m for m in benchmark_json()[group]
            if not is_listed(workload) or "workloads" not in m
            or workload in m["workloads"]]


def resolve(spec: str):
    """`pkg.module:function`, relative to benchmark/ (so a later PR adds
    a reader or a runner as a new file, never by editing one)."""
    mod, _, fn = spec.partition(":")
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    return getattr(importlib.import_module(mod), fn)


def fixture_seed(seed: int) -> int:
    return int(seed) % WEIGHT_SEEDS


def child_env() -> dict:
    """The environment of every process the benchmark starts: the
    compile cache at a fixed path inside the checkout unless the
    machine's owner already placed it, every program cached however
    quick its compile, and the program importable."""
    env = dict(os.environ)
    env.setdefault(CACHE_ENV, os.path.join(ROOT, ".jax_cache"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    env.setdefault("TPU_LOG_DIR", "disabled")
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    return env


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    if kind not in table or kind.startswith("_"):
        raise BenchError(f"device kind {kind!r} is not in harness/peaks.json"
                         f" ({[k for k in table if k[0] != '_']}): an "
                         f"unknown chip is an error, not a default")
    return table[kind]


def require_chip(chips: int, rehearse: bool = False) -> dict:
    """The device as JAX reports it — or BenchError. No CPU fallback:
    a measurement path that finds no TPU, an unknown kind or fewer
    chips than the cell asks for prints no result."""
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    if rehearse:
        return info
    if d.platform != "tpu":
        raise BenchError(f"JAX found platform {d.platform!r}, not a TPU")
    peaks_for(d.device_kind)
    if len(devs) < chips:
        raise BenchError(f"{len(devs)} chip(s), the cell needs {chips}")
    if len(devs) != chips:
        raise BenchError(f"{len(devs)} chips visible, the cell is for "
                         f"{chips}: run it on a machine of that size")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip of this process."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def say(msg: str, **fields) -> None:
    """A progress line (never the result line: it goes to stderr)."""
    rec = {"t": round(time.perf_counter() - T_PROCESS_START, 2),
           "msg": msg, **fields}
    print(json.dumps(rec), file=sys.stderr, flush=True)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, units: dict, device: dict,
                breakdown=None) -> str:
    """The contract's last line. Values as measured, all digits."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": float(v), "unit": units[k]}
                       for k, v in metrics.items() if v is not None},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    return json.dumps(out)
