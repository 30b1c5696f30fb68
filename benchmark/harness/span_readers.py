"""Readers of the per-layer metrics that read what the PROGRAM records
about itself (mobilefinetuner_tpu/core/trace.py): the flush counter in
`step_stats` (`drained_ms`, `drained_parts_ms`) and the `serve.*` spans
the serve loop writes into the profiler's trace, on the device trace's
clock. Same contract as harness/readers.py: `read(obs, trace, ctx)`
returns the value, or None where the program wrote no such field or
span (the parent of the PR that added them) — the metric is then left
out of the line. What a reader learns beside its value goes to
`obs["notes"]`, which a traced run prints on stderr.
"""
from __future__ import annotations

import bisect

from . import stats

FLUSH_PARTS = ("sensors", "emit", "log", "loop_tail", "input_wait",
               "dispatch")
SERVE_STEP = "serve.step"
# the phases of one `serve.step`, in the order the loop runs them
# (`serve.prefill` lies inside `serve.admit`)
SERVE_CHILDREN = ("serve.expire", "serve.admit", "serve.prefill_chunk",
                  "serve.page_append", "serve.upload", "serve.dispatch",
                  "serve.sync", "serve.emit", "serve.stats")


# --------------------------- train loop: the flush counter ------------------

def window_drains(obs):
    """[(step_stats record, its whole interval in s)] of the window's
    flushes that carry a drained period. The line of flush F carries
    the period that followed the flush before it, which lies inside F's
    interval — so a flush whose interval the profiler's start or stop
    falls into is left out, by the rule of `readers.train_stall_share`:
    it was drained for the trace, not by the program."""
    if obs["kind"] != "train_cli":
        return []
    every, prof = obs["log_interval"], obs.get("profile_steps")
    return [(f, d) for d, f in zip(obs["interval_s"], obs["flushes"])
            if f.get("drained_ms") is not None
            and not (prof and f["step"] - every <= prof[1]
                     and f["step"] - 1 >= prof[0])]


def train_drained_share(obs, trace, ctx):
    """Share of the window's whole-interval time in which the device
    had nothing queued: from a flush's `device_get` return to the
    return of the next dispatch, as the train loop stamped it."""
    rows = window_drains(obs)
    if not rows:
        return None
    return 100.0 * sum(f["drained_ms"] for f, _ in rows) / (
        1e3 * sum(d for _, d in rows))


def train_drained_max_ms(obs, trace, ctx):
    """The longest drained period of the window, with its split by
    phase in the notes: a stalled run names the phase that stalled.
    Also noted: the median of each part, and the period that followed
    the flush the profiler's trace holds (left out of the metric,
    because the profiler's stop may fall into it)."""
    rows = window_drains(obs)
    if not rows:
        return None
    worst = max((f for f, _ in rows), key=lambda f: f["drained_ms"])
    notes = obs.setdefault("notes", {})
    notes["train_drained_max"] = _drain_note(worst)
    notes["train_drained_median_ms"] = {
        "whole": stats.median([f["drained_ms"] for f, _ in rows]),
        **{k: stats.median([f["drained_parts_ms"][k] for f, _ in rows])
           for k in FLUSH_PARTS}}
    notes["train_drained_parts_close_to"] = max(
        abs(sum(f["drained_parts_ms"].values()) / f["drained_ms"] - 1.0)
        for f, _ in rows if f["drained_ms"] > 0)
    every, prof = obs["log_interval"], obs.get("profile_steps")
    if prof:
        notes["train_drained_traced_flushes"] = [
            _drain_note(f) for f in obs["flushes"]
            if f.get("drained_ms") is not None
            and prof[0] < f["step"] - every <= prof[1]]
    return worst["drained_ms"]


def _drain_note(f: dict) -> dict:
    return {"reported_at_step": f["step"], "drained_ms": f["drained_ms"],
            "parts_ms": f["drained_parts_ms"]}


# --------------------------- serve loop: spans of the trace -----------------

def main_line_events(trace, prefix: str = "serve."):
    """The program's spans on the line of the thread that ran the serve
    loop: the host plane has one line per thread, and the loop's is the
    one with the most `serve.step` events. Sorted by start."""
    best, n_best = None, 0
    for p in trace.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            n = sum(1 for e in line.events if e.name == SERVE_STEP)
            if n > n_best:
                best, n_best = line, n
    if best is None:
        return []
    return sorted((e for e in best.events if e.name.startswith(prefix)),
                  key=lambda e: (e.start, -e.dur))


def serve_steps(trace):
    """One dict per `serve.step` of the traced span, in order:
    {"span": (start, end), "children": {name: [(start, end), ...]}} with
    the spans that lie inside it (ns, the trace's clock)."""
    evs = main_line_events(trace)
    starts = [e.start for e in evs]
    out = []
    for e in evs:
        if e.name != SERVE_STEP:
            continue
        lo, hi = e.start, e.start + e.dur
        kids = {}
        for c in evs[bisect.bisect_left(starts, lo):
                     bisect.bisect_right(starts, hi)]:
            if c is not e and c.start + c.dur <= hi:
                kids.setdefault(c.name, []).append(
                    (c.start, c.start + c.dur))
        out.append({"span": (lo, hi), "children": kids})
    return out


def _is_decode(step: dict) -> bool:
    k = step["children"]
    return len(k.get("serve.dispatch", ())) == 1 \
        and len(k.get("serve.sync", ())) == 1


def serve_host_turn_ms(obs, trace, ctx):
    """Median host turn between consecutive decode steps of the traced
    span: from the end of step k's `serve.sync` (the step's tokens are
    on the host, nothing is queued on the device) to the start of step
    k+1's `serve.dispatch`, over the turns that admitted nothing (no
    `serve.admit` in step k+1 — the rule of `decode_step_ms_batch`).
    Noted beside it: the median of each phase, of the part of a turn
    outside `serve.step` (the driver's own bookkeeping) and the least
    share of a `serve.step` its phases cover."""
    if obs["kind"] != "serve_engine" or trace is None:
        return None
    steps = serve_steps(trace)
    decode = [s for s in steps if _is_decode(s)]
    turns, outside = [], []
    for a, b in zip(steps, steps[1:]):
        if not (_is_decode(a) and _is_decode(b)) \
                or b["children"].get("serve.admit"):
            continue
        turns.append(b["children"]["serve.dispatch"][0][0]
                     - a["children"]["serve.sync"][0][1])
        outside.append(b["span"][0] - a["span"][1])
    if not turns:
        return None
    ms = lambda ns: ns / 1e6
    total = lambda s, name: sum(e1 - e0 for e0, e1
                                in s["children"].get(name, ()))
    notes = obs.setdefault("notes", {})
    notes["serve_turn"] = {
        "turns": len(turns), "decode_steps": len(decode),
        "outside_serve_step_ms": ms(stats.median(outside)),
        "phase_median_ms": {
            name: ms(stats.median([total(s, name) for s in decode]))
            for name in SERVE_CHILDREN
            if any(name in s["children"] for s in decode)},
        "phases_cover_least": min(
            sum(total(s, name) for name in SERVE_CHILDREN)
            / max(s["span"][1] - s["span"][0], 1) for s in decode)}
    return ms(stats.median(turns))
