#!/usr/bin/env python3
"""One cell, one run:

  python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell by name (benchmark/workloads/<name>.json -> a
configuration file, a traffic file and a runner), finds the chip or
fails, sets up, warms up the cell's own shapes, measures for --seconds
and prints the contract's line last on stdout: the end-to-end metrics
with --trace 0, the per-layer metrics, busy/window seconds and the
breakdown with --trace 1. Everything else it says goes to stderr.
No TPU, an unknown device kind, fewer chips than the cell asks for, a
missing file or a program that died: exit code != 0 and NO result line.
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

from harness import common  # noqa: E402  (starts the set-up clock)

RUNNERS = {"train_cli": "harness.train_cli",
           "serve_engine": "harness.serve_engine"}


def layer_values(cell, obs, e2e):
    """Per-layer metrics of this cell: BENCHMARK.json says which, each
    metric's own file says how. Also the loaded trace (or None)."""
    from harness import xplane
    trace = None
    if obs.get("trace_dir"):
        path = xplane.find_xplane(obs["trace_dir"])
        if path:
            trace = xplane.load(path)
    ctx = {"peaks": common.peaks_for(obs["device"]["kind"]),
           "config": cell["config_file"], "traffic": cell["traffic_file"],
           "e2e": e2e}
    values, units = {}, {}
    for m in common.metrics_for(cell["name"], "per_layer"):
        spec = common.named("layer_metrics", m["name"], cell["search"])
        if m["moves"] not in e2e:
            continue            # reported only where what it moves is
        v = common.resolve(spec["reader"])(obs, trace, ctx,
                                           **spec.get("args", {}))
        if v is not None:
            values[m["name"]], units[m["name"]] = v, m["unit"]
    return values, units, trace


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--search", action="append", default=[],
                    help="extra directory holding workloads/ configs/ "
                         "traffic/ layer_metrics/ (a throw-away cell)")
    args = ap.parse_args(argv)
    # before JAX is imported anywhere: the compile cache at a fixed path
    # inside the checkout unless the machine's owner placed it
    os.environ.update(common.child_env())
    try:
        cell = common.load_cell(args.workload, args.search)
        mod = importlib.import_module(RUNNERS.get(cell["kind"],
                                                  cell["kind"]))
        obs = mod.observe(cell, args.seed, args.seconds, bool(args.trace))
        listed = common.metrics_for(cell["name"], "end_to_end")
        e2e_all = mod.end_to_end(obs)
        e2e = {m["name"]: e2e_all[m["name"]] for m in listed
               if m["name"] in e2e_all}
        missing = [m["name"] for m in listed if m["name"] not in e2e]
        if missing and common.is_listed(cell["name"]):
            raise common.BenchError(f"no value for {missing}")
        inwin = mod.in_window_correct(obs)
        common.say("in-window checks", **inwin)
        device = dict(obs["device"],
                      memory_peak_bytes=obs["memory_peak_bytes"])
        ok = bool(obs["verdict"]["ok"]) and inwin["ok"]
        if not args.trace:
            units = {m["name"]: m["unit"] for m in listed
                     if m["name"] in e2e}
            line = common.result_line(
                correct=ok, attempted=inwin["attempted"],
                failed=inwin["failed"], metrics=e2e, units=units,
                device=device)
        else:
            from harness import xplane
            values, units, trace = layer_values(cell, obs, e2e)
            b = xplane.busy(trace) if trace is not None else {}
            if not b or b["busy_s"] <= 0:
                raise common.BenchError(
                    "the traced run shows no operation on the device")
            device.update(busy_s=b["busy_s"], window_s=b["window_s"])
            common.say("notes", **obs.get("notes", {}))
            line = common.result_line(
                correct=ok, attempted=inwin["attempted"],
                failed=inwin["failed"], metrics=values, units=units,
                device=device, breakdown=xplane.breakdown(trace))
        common.say("setup phases (s since process start)",
                   **obs["setup_phases"])
    except common.BenchError as e:
        common.say("FAILED", error=str(e))
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
