#!/usr/bin/env python3
"""Find the knee of an open-loop serve cell ONCE, on the chip: the
highest arrival rate the engine sustains. The cell's traffic file then
freezes 0.8 x that rate as a number; nothing searches at run time.

  python benchmark/tools/knee_sweep.py --workload <open-loop cell> \
      [--start 2] [--run_seconds 40] [--seed 0]

One engine, one process. First a single client (closed loop, one request
at a time) gives the medians a lone user sees. Then open-loop runs of
`run_seconds` at rates doubling from --start until one fails, then
bisected (--bisections halvings; three give about +-10 %). A rate is sustained when the
queue depth at the end of the run (mean over its last 2 s) is no greater
than at 10 s (mean over 9-11 s; +1 for a queue that hovers at 0 or 1)
and at least 90 % of the requests due at least 15 s before its end met
TTFT <= 5 x and TPOT <= 2 x the single client's medians (unfinished =
missed; 15 s is about what the mix's longest request takes alone).
Writes chiprun_out/knee_sweep_<workload>.json.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import numpy as np  # noqa: E402

from harness import common, serve_engine as se, stats  # noqa: E402
from harness import traffic as traffic_mod  # noqa: E402


def one_rate(eng, names, tr, rate, seconds, seed, limits, settle_s=15.0):
    t = copy.deepcopy(tr)
    t["arrivals"] = {"process": "poisson", "rate_per_s": rate}
    n = int(rate * seconds * 1.4) + 64
    sched = traffic_mod.serve_schedule(t, eng.config.vocab_size, seed, n)
    drv = se.Driver(eng, names, sched)
    drv.start()
    drv.run_until(t_stop=drv.t_origin + seconds)
    t_end = time.perf_counter()
    q = lambda lo, hi: float(np.mean([s[6] for s in drv.steps
                                      if lo <= s[1] - drv.t_origin <= hi]
                                     or [0.0]))
    due = drv.records
    ttft = [se.ttft_ms(r) for r in due if r["first"] is not None]
    tpot = [se.tpot_ms(r) for r in due if se.tpot_ms(r) is not None]
    # requests still in flight at the end were not given their chance:
    # judge only those due at least settle_s before the end (about what
    # the longest request of the mix takes alone)
    judged = [r for r in due if r["due"] <= t_end - settle_s]
    ok_j = sum(1 for r in judged if r["done"] is not None
               and r["req"].state == "finished"
               and se.ttft_ms(r) <= limits[0]
               and (se.tpot_ms(r) or 0.0) <= limits[1])
    eng.drain()
    row = {"rate_per_s": rate, "submitted": len(due),
           "queue_at_10s": q(9, 11), "queue_at_end": q(seconds - 2, seconds),
           "attainment": ok_j / max(len(judged), 1),
           "ttft_p50_ms": stats.median(ttft) if ttft else None,
           "ttft_p90_ms": stats.tail(ttft, 90),
           "tpot_p50_ms": stats.median(tpot) if tpot else None,
           "tpot_p90_ms": stats.tail(tpot, 90),
           "tokens_per_s": sum(len(r["req"].tokens) for r in due) / seconds}
    row["sustained"] = bool(row["queue_at_end"] <= row["queue_at_10s"] + 1
                            and row["attainment"] >= 0.9)
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--start", type=float, default=2.0)
    ap.add_argument("--run_seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max_rate", type=float, default=256.0)
    ap.add_argument("--bisections", type=int, default=3)
    ap.add_argument("--search", action="append", default=[])
    ap.add_argument("--rehearse", type=int, default=0,
                    help="CPU rehearsal of the control flow (no chip gate)")
    args = ap.parse_args()
    os.environ.update(common.child_env())      # before JAX is imported
    cell = common.load_cell(args.workload, args.search)
    device = common.require_chip(cell["chips"], bool(args.rehearse))
    tr = cell["traffic_file"]
    eng, names, trees, params = se.build_engine(cell, args.seed)
    # a single client: one request at a time
    t = copy.deepcopy(tr)
    t["arrivals"] = {"process": "backlog"}
    sched = traffic_mod.serve_schedule(t, eng.config.vocab_size,
                                       args.seed + 1, 12)
    ttft, tpot = [], []
    for i in range(12):
        a = sched["adapter"][i]
        t0 = time.perf_counter()
        r = eng.submit(sched["prompts"][i], max_new_tokens=sched["max_new"][i],
                       adapter=names[a] if a >= 0 else None)
        first = None
        while not r.done:
            eng.step()
            if first is None and r.tokens:
                first = time.perf_counter()
        t1 = time.perf_counter()
        if i >= 4:                       # the first few warm the programs
            ttft.append((first - t0) * 1e3)
            if len(r.tokens) > 1:
                tpot.append((t1 - first) / (len(r.tokens) - 1) * 1e3)
    single = {"ttft_p50_ms": stats.median(ttft),
              "tpot_p50_ms": stats.median(tpot), "requests": len(ttft)}
    limits = (5 * single["ttft_p50_ms"], 2 * single["tpot_p50_ms"])
    print(json.dumps({"single_client": single, "limits_ms": limits,
                      "device": device}), flush=True)
    rows = []
    rate, lo, hi = args.start, None, None
    while rate <= args.max_rate:
        row = one_rate(eng, names, tr, rate, args.run_seconds, args.seed,
                       limits)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if row["sustained"]:
            lo, rate = rate, rate * 2
            if hi is not None:
                break
        elif lo is None and rate > 0.3:
            hi, rate = rate, rate / 2      # the start was past the knee
        else:
            hi = rate
            break
    if lo is not None and hi is not None:
        for _ in range(args.bisections):
            mid = (lo + hi) / 2
            row = one_rate(eng, names, tr, mid, args.run_seconds, args.seed,
                           limits)
            rows.append(row)
            print(json.dumps(row), flush=True)
            if row["sustained"]:
                lo = mid
            else:
                hi = mid
    out = {"workload": args.workload, "rehearsal": bool(args.rehearse),
           "single_client": single,
           "limits_ms": {"ttft": limits[0], "tpot": limits[1]},
           "rows": rows, "knee_per_s": lo, "first_unsustained_per_s": hi,
           "frozen_rate_per_s": None if lo is None else round(0.8 * lo, 2),
           "device": device, "run_seconds": args.run_seconds}
    os.makedirs(os.path.join(common.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(common.ROOT, "chiprun_out",
                           f"knee_sweep_{args.workload}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("knee_per_s",
                                          "first_unsustained_per_s",
                                          "frozen_rate_per_s")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
