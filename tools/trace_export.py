"""Telemetry stream(s) -> ONE Perfetto/Chrome-trace-event timeline.

A fleet run (or serve session) leaves per-host JSONL telemetry shards;
this tool converts a coordinator stream — plus every `.host<k>` shard
found next to it — into a single trace-event JSON file that opens in ui.perfetto.dev (or
chrome://tracing): one PROCESS row per host, one THREAD row per track
("phase" for the goodput buckets, "ckpt" for the async writer thread,
"prefetch" for the producer, "req:<id>" per serve request), counter
tracks for loss/tok_s/queue depth, and instant markers for every
incident event (anomaly, straggler, hang, rollback, degrade, preempt,
profile_capture, over-capacity mem_check).

Clock discipline: `span` events carry a MONOTONIC t0 (time.perf_counter,
the envelope's `t_mono` clock). Each host's monotonic clock is placed
on the wall timeline via the median (t - t_mono) offset over its own
records — NTP steps move wall time, never a span's duration or its
position relative to its host's other spans. Streams that predate
`t_mono` still convert (instants and counters use wall `t`; they carry
no spans to place).

Reconciliation: with `--trace_spans` the goodput meter emits one span
per phase segment from the SAME transitions that charge the run_end
buckets, so per-phase span sums match `run_end.goodput` by
construction — the tool prints the check (and `phase_reconcile` is the
test's oracle).

Device traces are not merged here: every `Tracer.span` is also a
`jax.profiler.TraceAnnotation` (core/trace.py), so a profiler trace
(--profile_dir, --auto_profile) already holds the program's spans on
the device's own clock — open its `.xplane.pb` directory with the
profiler's viewer, or reduce it with benchmark/harness/xplane.py. The
stream's `profile_capture` events say where each trace of a run lies.

Usage:
  python tools/trace_export.py run.jsonl -o trace.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root
sys.path.insert(0, _HERE)                   # sibling tools

from fleet_report import discover_shards          # noqa: E402
from telemetry_report import load_events          # noqa: E402

# incident events rendered as instant markers (name rule per event)
_INSTANT_EVENTS = ("anomaly", "straggler", "hang", "preempt", "rollback",
                   "degrade", "mem_check", "ckpt_verify",
                   "profile_capture", "throttle", "ckpt_dropped",
                   "route")

# step_stats fields rendered as counter tracks
_COUNTERS = ("loss", "tok_s", "queue_depth", "hbm_mb", "step_time_ms")


def latest_run(events):
    """Slice one shard's events to its LATEST run (from the last
    run_start onward; the whole stream when none). A resumed stream
    appends runs from different processes, whose perf_counter epochs
    share nothing — one median (t - t_mono) offset over both would
    misplace the minority run's spans by the epoch gap, and summing
    both runs' phase spans against the final run_end's buckets would
    report the by-construction identity as violated on a healthy
    resumed run. One timeline = one run (the same latest-run scoping
    rule the report tools apply to truncated streams)."""
    idx = max((i for i, e in enumerate(events)
               if e.get("event") == "run_start"), default=-1)
    return events[idx:] if idx > 0 else events


def mono_offset(events):
    """Median wall-minus-monotonic offset for one host's records: maps
    a span's monotonic t0 onto the wall timeline. None when the stream
    predates t_mono."""
    ds = [e["t"] - e["t_mono"] for e in events
          if isinstance(e.get("t_mono"), (int, float))
          and isinstance(e.get("t"), (int, float))]
    return statistics.median(ds) if ds else None


def _instant_name(e) -> str:
    ev = e["event"]
    if ev == "anomaly":
        return f"anomaly:{e.get('kind')}"
    if ev == "straggler":
        return f"straggler:host{e.get('slow_host')}"
    if ev == "mem_check":
        return f"mem_check:{e.get('verdict')}"
    if ev == "ckpt_verify":
        return ("ckpt_verify:ok" if e.get("ok")
                else "ckpt_verify:REJECTED")
    if ev == "rollback":
        return f"rollback:{e.get('reason')}"
    if ev == "degrade":
        return f"degrade:{e.get('rung')}"
    if ev == "profile_capture":
        return f"profile_capture:{e.get('trigger')}"
    if ev == "route":
        repl = e.get("replica")
        return (f"route:rid{e.get('rid')}->r{repl}" if repl is not None
                else f"route:rid{e.get('rid')}->REJECT")
    return ev


def _span_args(e) -> dict:
    skip = {"event", "seq", "t", "t_mono", "host", "name", "track",
            "t0", "dur_ms"}
    return {k: v for k, v in e.items() if k not in skip}


def host_trace_events(host, events, t_base):
    """One host's trace events (ts in us relative to t_base). Returns
    (trace_events, track_names_seen)."""
    out = []
    off = mono_offset(events)
    tracks = {}  # track name -> tid

    def tid_for(track):
        if track not in tracks:
            # stable, readable ordering: phase first, then the engine
            # threads, request tracks in arrival order after
            tracks[track] = len(tracks) + 1
        return tracks[track]

    spans = [e for e in events if e["event"] == "span"]
    have = {e.get("track", "") for e in spans}
    for e in spans:
        if off is None:
            continue  # no clock bridge: a pre-t_mono stream has no
            # spans anyway (same round introduced both)
        wall = e["t0"] + off
        out.append({
            "ph": "X", "pid": host, "tid": tid_for(e["track"]),
            "ts": round((wall - t_base) * 1e6, 3),
            "dur": round(e["dur_ms"] * 1000.0, 3),
            "name": e["name"], "cat": "span", "args": _span_args(e),
        })
    # requests: if the engine did not trace spans (trace_spans off),
    # synthesize queue/decode spans from the request lifecycle events
    # the stream always carries — wall-clock precision, same tracks
    if not any(t.startswith("req:") for t in have):
        reqs = {}
        for e in events:
            if e["event"] == "request":
                reqs.setdefault(e["id"], []).append(e)
        for rid, recs in sorted(reqs.items()):
            by_phase = {r["phase"]: r for r in recs}
            enq = by_phase.get("enqueue")
            admit = by_phase.get("admit")
            term = next((r for r in recs
                         if r["phase"] in ("finish", "cancel", "reject",
                                           "timeout", "error")), None)
            track = f"req:{rid}"
            if enq and admit:
                out.append({
                    "ph": "X", "pid": host, "tid": tid_for(track),
                    "ts": round((enq["t"] - t_base) * 1e6, 3),
                    "dur": round(max(admit["t"] - enq["t"], 0) * 1e6, 3),
                    "name": "queue", "cat": "request",
                    "args": {"id": rid}})
            if admit and term:
                out.append({
                    "ph": "X", "pid": host, "tid": tid_for(track),
                    "ts": round((admit["t"] - t_base) * 1e6, 3),
                    "dur": round(max(term["t"] - admit["t"], 0) * 1e6, 3),
                    "name": "decode", "cat": "request",
                    "args": {"id": rid, "outcome": term["phase"],
                             "new_tokens": term.get("new_tokens")}})
    # checkpoint writes: derive write spans from the checkpoint events
    # (emitted at write END with write_ms) when the writer wasn't traced
    if "ckpt" not in have:
        for e in events:
            if e["event"] == "checkpoint" and e.get("write_ms"):
                t_end = e["t"]
                out.append({
                    "ph": "X", "pid": host, "tid": tid_for("ckpt"),
                    "ts": round((t_end - e["write_ms"] / 1000.0
                                 - t_base) * 1e6, 3),
                    "dur": round(e["write_ms"] * 1000.0, 3),
                    "name": "ckpt.write",
                    "cat": "checkpoint",
                    "args": {"step": e["step"], "bytes": e.get("bytes"),
                             "async": e.get("async")}})
    # instants: every incident event is a marker on its host's row
    for e in events:
        if e["event"] in _INSTANT_EVENTS:
            if e["event"] == "mem_check" and e.get("verdict") == "ok":
                continue  # a clean preflight is not an incident
            out.append({
                "ph": "i", "pid": host, "tid": tid_for("events"),
                "ts": round((e["t"] - t_base) * 1e6, 3), "s": "p",
                "name": _instant_name(e), "cat": e["event"],
                "args": {k: v for k, v in e.items()
                         if k not in ("event", "seq", "t", "t_mono",
                                      "host")}})
    # counters: the step_stats trend lines, drawable next to the spans
    for e in events:
        if e["event"] == "step_stats":
            ts = round((e["t"] - t_base) * 1e6, 3)
            for f in _COUNTERS:
                v = e.get(f)
                if isinstance(v, (int, float)):
                    out.append({"ph": "C", "pid": host, "tid": 0,
                                "ts": ts, "name": f,
                                "args": {f: round(float(v), 4)}})
        elif e["event"] == "serve_stats":
            ts = round((e["t"] - t_base) * 1e6, 3)
            for f in ("queue_depth", "active", "free_blocks"):
                v = e.get(f)
                if isinstance(v, (int, float)):
                    out.append({"ph": "C", "pid": host, "tid": 0,
                                "ts": ts, "name": f"serve_{f}",
                                "args": {f: round(float(v), 4)}})
    # metadata: name the process and thread rows
    meta = [{"ph": "M", "pid": host, "name": "process_name",
             "args": {"name": f"host {host}"
                      + (" (coordinator)" if host == 0 else "")}},
            {"ph": "M", "pid": host, "name": "process_sort_index",
             "args": {"sort_index": host}}]
    for track, tid in tracks.items():
        meta.append({"ph": "M", "pid": host, "tid": tid,
                     "name": "thread_name", "args": {"name": track}})
    return meta + out, set(tracks)


def phase_sums(trace, pid: int = 0) -> dict:
    """Per-name sums (seconds) over ONE host's goodput-phase spans —
    the reconciliation oracle the acceptance test compares against
    that host's run_end.goodput. Scoped to a single pid: each host
    runs its own GoodputMeter, so summing phase spans across a fleet
    against one host's buckets would report the by-construction
    identity as violated on a perfectly healthy run."""
    sums = {}
    for e in trace["traceEvents"]:
        if e.get("ph") == "X" and e.get("cat") == "span" \
                and e.get("pid") == pid \
                and e.get("name") and "dur" in e:
            # phase spans carry bucket names; other span tracks carry
            # names outside the bucket set, so keying by name is safe
            sums[e["name"]] = sums.get(e["name"], 0.0) \
                + e["dur"] / 1e6
    return sums


def phase_reconcile(trace, goodput, pid: int = 0) -> dict:
    """{bucket: (span_sum_s, bucket_s, abs_delta_s)} for every goodput
    bucket the trace carries spans for, scoped to `pid`'s host."""
    sums = phase_sums(trace, pid=pid)
    out = {}
    for k, v in (goodput or {}).items():
        if not k.endswith("_s") or k == "total_s":
            continue
        b = k[:-2]
        if b in sums:
            out[b] = (round(sums[b], 4), v, round(abs(sums[b] - v), 4))
    return out


def router_reconcile(shards) -> dict | None:
    """Router-vs-replica span reconciliation for a serve-fleet stream
    (shard 0 = router, shard k = replica k). The merged timeline is
    only trustworthy across process rows if each process's spans —
    placed on the wall axis via that host's mono_offset — land where
    that SAME process's wall-stamped events say the instant occurred.
    Two anchors exist per routed rid, one on each side of the handoff:

      router side:   the `route` span's END (t0 + offset + dur) is the
                     ack instant the `route` EVENT stamps with wall t;
      replica side:  the rid-tagged `queue` span's START (t0 + offset)
                     is the submit instant the request phase=enqueue
                     EVENT stamps with wall t.

    |placed - stamped| per anchor bounds how far a span can be
    misplaced relative to any other process's row (events share one
    wall clock; queueing delay between route and enqueue is real time,
    not error, and is deliberately NOT measured here). Returns None
    when the stream carries no route events (not a router run); rids
    missing an anchor (replica killed pre-flush, tracer off) are
    counted, not matched — settlement handled them off-stream."""
    shards = {h: latest_run(evs) for h, evs in shards.items()}
    offs = {h: mono_offset(evs) for h, evs in shards.items()}
    routes = {}
    for e in shards.get(0, ()):
        if e.get("event") == "route" and isinstance(e.get("rid"), int) \
                and e.get("replica") is not None:
            routes[e["rid"]] = e  # last route per rid wins (failover)
    if not routes:
        return None
    gaps, unmatched = [], 0

    def anchor(host, rid, span_name, span_end, event_t):
        """Gap between a placed span edge and the wall stamp of the
        event emitted at the same instant. None when either half is
        missing on `host` for `rid`."""
        off = offs.get(host)
        if off is None:
            return None
        span = next((e for e in shards.get(host, ())
                     if e.get("event") == "span"
                     and e.get("name") == span_name
                     and e.get("rid") == rid), None)
        if span is None or event_t is None:
            return None
        placed = span["t0"] + off \
            + (span["dur_ms"] / 1000.0 if span_end else 0.0)
        return abs(placed - event_t)

    enq = {}  # (host, rid) -> wall t of the last enqueue event
    for h, evs in shards.items():
        if h == 0:
            continue
        for e in evs:
            if e.get("event") == "request" \
                    and e.get("phase") == "enqueue" \
                    and isinstance(e.get("rid"), int):
                enq[(h, e["rid"])] = e["t"]
    for rid, r in routes.items():
        pair = (anchor(0, rid, "route", True, r["t"]),
                anchor(r["replica"], rid, "queue", False,
                       enq.get((r["replica"], rid))))
        got = [g for g in pair if g is not None]
        if len(got) < 2:
            unmatched += 1
        gaps.extend(got)
    ts = [e["t"] for evs in shards.values() for e in evs
          if isinstance(e.get("t"), (int, float))]
    wall = (max(ts) - min(ts)) if ts else 0.0
    worst = max(gaps) if gaps else 0.0
    return {"rids": len(routes),
            "matched": len(routes) - unmatched,
            "unmatched": unmatched,
            "anchors": len(gaps),
            "max_gap_ms": round(worst * 1000.0, 3),
            "wall_s": round(wall, 3),
            "max_gap_frac": (worst / wall) if wall else 0.0}


def export(shards, router=False) -> dict:
    """shards: {host: events}. Returns the trace-event JSON dict.
    Each shard is scoped to its latest run first (see latest_run).
    With router=True the process rows are named for the serve-fleet
    layout (host 0 is the router front door, host k replica k)."""
    shards = {h: latest_run(evs) for h, evs in shards.items()}
    all_events = [e for evs in shards.values() for e in evs]
    t_base = min((e["t"] for e in all_events
                  if isinstance(e.get("t"), (int, float))), default=0.0)
    trace_events = []
    for host, events in sorted(shards.items()):
        evs, _tracks = host_trace_events(host, events, t_base)
        trace_events.extend(evs)
    if router:
        for e in trace_events:
            if e.get("ph") == "M" and e.get("name") == "process_name":
                pid = e.get("pid")
                e["args"]["name"] = ("router" if pid == 0
                                     else f"replica {pid}")
    return {"traceEvents": trace_events, "displayTimeUnit": "ms",
            "otherData": {"source": "mobilefinetuner_tpu trace_export",
                          "hosts": len(shards),
                          "t_base_unix": t_base}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="telemetry stream(s) -> Perfetto trace-event JSON")
    ap.add_argument("jsonl", help="telemetry stream (--telemetry_out "
                                  "base path; .host<k> shards are "
                                  "discovered and merged)")
    ap.add_argument("-o", "--out", default="",
                    help="output file (default: <stream>.trace.json)")
    ap.add_argument("--router", action="store_true",
                    help="serve-fleet stream: name host 0 'router' and "
                         "host k 'replica k', and check the per-rid "
                         "route->enqueue clock gap across processes "
                         "(fails when it exceeds 1%% of wall)")
    args = ap.parse_args(argv)
    paths = discover_shards(args.jsonl)
    if not paths:
        print(f"error: no telemetry shards at {args.jsonl}",
              file=sys.stderr)
        return 1
    shards, n_bad = {}, 0
    for h, p in sorted(paths.items()):
        try:
            events, bad = load_events(p)
        except OSError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        n_bad += bad
        if events:
            shards[h] = events
    if not shards:
        print(f"error: no valid telemetry events in "
              f"{sorted(paths.values())}", file=sys.stderr)
        return 1
    trace = export(shards, router=args.router)
    out = args.out or (args.jsonl + ".trace.json")
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(trace, f)
    os.replace(tmp, out)
    n_span = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
    print(f"{out}: {len(trace['traceEvents'])} trace events "
          f"({n_span} spans) from {len(shards)} host shard(s)"
          + (f", {n_bad} invalid lines skipped" if n_bad else "")
          + " — open in ui.perfetto.dev")
    # reconciliation check: the COORDINATOR's phase-span sums vs its
    # run_end goodput buckets (the acceptance identity; per-host by
    # construction, so the comparison is scoped to pid 0)
    ends = [e for e in shards.get(0, []) if e["event"] == "run_end"
            and isinstance(e.get("goodput"), dict)]
    if ends:
        rec = phase_reconcile(trace, ends[-1]["goodput"], pid=0)
        if rec:
            total = ends[-1]["goodput"].get("total_s") or 0.0
            worst = max(d for _, _, d in rec.values())
            print(f"goodput reconciliation over {len(rec)} bucket(s): "
                  f"max |span_sum - bucket| = {worst:.4f}s"
                  + (f" ({100 * worst / total:.2f}% of total)"
                     if total else ""))
    if args.router:
        rr = router_reconcile(shards)
        if rr is None:
            print("error: --router but no route events in the stream",
                  file=sys.stderr)
            return 1
        print(f"router reconciliation: {rr['matched']}/{rr['rids']} "
              f"rids fully anchored"
              + (f" ({rr['unmatched']} settled off-stream)"
                 if rr["unmatched"] else "")
              + f", max span-placement gap over {rr['anchors']} "
              f"anchor(s) = {rr['max_gap_ms']}ms "
              f"({100 * rr['max_gap_frac']:.3f}% of {rr['wall_s']}s "
              f"wall)")
        if rr["max_gap_frac"] > 0.01:
            print("error: router/replica span reconciliation gap "
                  "exceeds 1% of wall clock", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
