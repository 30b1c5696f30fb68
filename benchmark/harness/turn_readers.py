"""Readers that itemise a TURNOVER of the serve loop: the turn in which
a finished request's slot is given to the next one, the one turn of
`ServeEngine.step` left in which the device waits for the host.

Every `serve.step` of the traced span is sorted by what its children
show (`kind_of`), each idle gap of the first device is cut at the
boundaries of the loop's `serve.*` spans and put to the innermost one,
and the device's module line says what the prefill, the page write and
the decode step take there. Five metrics read the result; the rest goes
to `obs["notes"]["serve_turnover"]`, which a traced run prints on
stderr.

The host's spans and the device's ops are NOT on one clock to better
than a millisecond or two: a profiler session places the device's line
against the host's by a constant that differs from session to session
(the device 1.36 ms early in one traced run, 0.32 ms in the next: my
chip runs, PR 35), which is the length of the phases a turnover is made
of. So the offset is measured from the trace itself (`clock_offset`)
and the device's line is moved by it before any gap is put to a span;
the notes say what was applied and check it (`shared_clock`).

Same contract as harness/readers.py: `read(obs, trace, ctx)` returns
the value, or None where there is nothing to read — no traced run, no
`serve.step` in the trace, for `serve_first_token_wait_ms` no
`serve.prefill.first_token` (a program older than that span), for the
two device metrics no device plane.
"""
from __future__ import annotations

import bisect
import collections

from . import span_readers, stats, xplane

ADMIT, PREFILL = "serve.admit", "serve.prefill"
DISPATCH, SYNC = "serve.dispatch", "serve.sync"
# the one-shot prefill, cut where the host blocks, in the order it runs
PREFILL_CHILDREN = ("serve.prefill.dispatch", "serve.prefill.page_write",
                    "serve.prefill.first_token")
FIRST_TOKEN = PREFILL_CHILDREN[2]
# phases whose time in a turnover turn is noted, a total per turn
TURN_PHASES = (SYNC, "serve.emit", "serve.expire", "serve.upload", DISPATCH)
# the serve programs as the device's "XLA Modules" line names them
MODULES = {"prefill": "jit_prefill_py", "page_write": "jit_write_py",
           "decode_step": "jit_step_py"}
# the TPU runtime's host event for handing a program to the device
ENQUEUE = "DoEnqueueProgram"
OUTSIDE = "(outside serve.step)"
KINDS = ("steady", "stale", "fill", "turnover", "no_dispatch")


def kind_of(step: dict) -> str:
    """What one `serve.step` was, by its children alone:
    turnover     at least one `serve.admit` and a dispatch;
    steady       the one dispatch ENDS before the one sync starts, no
                 admission: step k+1 queued, then step k read;
    fill         a dispatch and no sync: nothing was in flight;
    stale        any other turn with a dispatch (the sync came first,
                 or there are two);
    no_dispatch  nothing was live to decode."""
    k = step["children"]
    dispatch, sync = k.get(DISPATCH, ()), k.get(SYNC, ())
    if len(dispatch) != 1:
        return "no_dispatch"
    if k.get(ADMIT):
        return "turnover"
    if not sync:
        return "fill"
    if len(sync) == 1 and dispatch[0][1] <= sync[0][0]:
        return "steady"
    return "stale"


def innermost(events) -> list:
    """[(start, end, name)], disjoint and ascending: the innermost of
    one thread's nested spans at every instant some span covers.
    `events` sorted by (start, -duration); a child is clipped to its
    parent."""
    out, stack, cur = [], [], 0

    def advance(to):
        nonlocal cur
        if to > cur:
            out.append((cur, to, stack[-1][1]))
            cur = to

    for e in events:
        while stack and stack[-1][0] <= e.start:
            advance(stack[-1][0])
            stack.pop()
        if stack:
            advance(e.start)
        cur = max(cur, e.start)
        end = e.start + e.dur
        stack.append((min(end, stack[-1][0]) if stack else end, e.name))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return out


class Gaps:
    """The idle intervals of one device inside its window."""

    def __init__(self, merged, window):
        self.iv = xplane.subtract([list(window)], merged)
        self.starts = [s for s, _ in self.iv]
        self.window = window

    def idle(self, lo: int, hi: int) -> int:
        """ns of the gaps inside [lo, hi]."""
        lo, hi = max(lo, self.window[0]), min(hi, self.window[1])
        ns = 0
        for k in range(max(bisect.bisect_right(self.starts, lo) - 1, 0),
                       len(self.iv)):
            s, e = self.iv[k]
            if s >= hi:
                break
            ns += max(min(e, hi) - max(s, lo), 0)
        return ns


def idle_by_phase(gaps: Gaps, segments) -> dict:
    """{innermost `serve.*` span: ns} over the device's idle gaps of at
    least `xplane.GAP_FLOOR_NS`, each cut at the spans' boundaries;
    what no span covers goes under OUTSIDE."""
    acc = collections.Counter()
    starts = [s for s, _, _ in segments]
    for gs, ge in gaps.iv:
        if ge - gs < xplane.GAP_FLOOR_NS:
            continue
        left = ge - gs
        for k in range(max(bisect.bisect_right(starts, gs) - 1, 0),
                       len(segments)):
            s, e, name = segments[k]
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                acc[name] += ov
                left -= ov
        acc[OUTSIDE] += left
    return dict(acc)


def module_events(plane, prefix: str) -> list:
    """Executions of one program on a device, in order."""
    return sorted((e for l in plane.lines if l.name == xplane.MODULES_LINE
                   for e in l.events if e.name.startswith(prefix)),
                  key=lambda e: e.start)


def _span_total(step: dict, name: str) -> int:
    return sum(e - s for s, e in step["children"].get(name, ()))


def _median_ms(ns: list):
    return stats.median(ns) / 1e6 if ns else None


def clock_offset(trace, turnovers, prefills):
    """(ns to add to the device's times, [least, most] over the turns)
    or (None, None). A turnover turn begins by reading the step in
    flight, so the device is idle when the turn's first prefill is
    handed to it, and the program starts as the runtime enqueues it:
    the offset is the end of the first ENQUEUE event (any host thread)
    after the turn's first `serve.prefill` opens, less the start of the
    prefill module nearest to that span — the median over turnover
    turns. None where the trace holds no such event or module."""
    enq = sorted((e.start, e.start + e.dur)
                 for e in xplane.host_events(trace) if e.name == ENQUEUE)
    if not enq or not prefills:
        return None, None
    enq_starts = [s for s, _ in enq]
    mod_starts = [m.start for m in prefills]
    found = []
    for turn in turnovers:
        span = turn["children"].get(PREFILL)
        if not span:
            continue
        t = span[0][0]
        i = bisect.bisect_left(enq_starts, t)
        j = bisect.bisect_left(mod_starts, t)
        near = min(mod_starts[max(j - 1, 0):j + 1], key=lambda m: abs(m - t))
        if i < len(enq):
            found.append(enq[i][1] - near)
    if not found:
        return None, None
    return round(stats.median(found)), [min(found), max(found)]


def dispatched_modules(steps, kinds, decode) -> list:
    """[(turn index, the decode-step module that turn dispatched)]. The
    step a turn dispatches is read by the first `serve.sync` of a LATER
    turn, and its module is the last decode-step module to end before
    that sync does: matched by the sync's end alone, so that the
    dispatch's side of the clock is left to be checked."""
    ends = [e.start + e.dur for e in decode]
    pending, pairs = None, []
    for i, s in enumerate(steps):
        sync = s["children"].get(SYNC)
        if sync and pending is not None:
            j = bisect.bisect_right(ends, sync[0][1]) - 1
            if j >= 0:
                pairs.append((pending, decode[j]))
        if kinds[i] != "no_dispatch":
            pending = i
    return pairs


def shared_clock(steps, kinds, decode, shift: int) -> dict:
    """The host's spans against the device's modules: the share of
    steady turns whose module starts after their `serve.dispatch`
    started (must be 100 %) — on the clock as the readers use it, and
    as the trace gave it (`shift` taken back); and, at turnover turns
    (the device has nothing queued), how long after."""
    lags = [(kinds[i], m.start - steps[i]["children"][DISPATCH][0][0])
            for i, m in dispatched_modules(steps, kinds, decode)]
    steady = [x for k, x in lags if k == "steady"]
    share = lambda back: 100.0 * sum(x - back >= 0 for x in steady) \
        / len(steady) if steady else None
    return {"module_starts_after_its_dispatch_share": share(0),
            "as_the_trace_gave_it_share": share(shift),
            "turnover_dispatch_to_module_start_ms": _median_ms(
                [x for k, x in lags if k == "turnover"])}


def turnover_edges(steps, kinds, decode) -> list:
    """[(start, end)] of every turnover turn as the DEVICE lives it:
    from the start of its `serve.step` to the start of the decode-step
    module it dispatched — the first to start after the turn's
    `serve.dispatch` did, the device having nothing queued. The runtime
    hands a program to the device AFTER the dispatching call returned
    (0.10-0.15 ms: my chip runs, PR 35), so the end of `serve.step`
    would cut the turn's last gap and leave its tail to the steady
    turn that follows. Never later than the next turn's dispatch; the
    end of the `serve.step` where no such module is in the trace."""
    starts = [m.start for m in decode]
    out = []
    for i, s in enumerate(steps):
        if kinds[i] != "turnover":
            continue
        lo, hi = s["span"]
        limit = next((steps[j]["children"][DISPATCH][0][0]
                      for j in range(i + 1, len(steps))
                      if steps[j]["children"].get(DISPATCH)), hi)
        j = bisect.bisect_left(starts, s["children"][DISPATCH][0][0])
        if j < len(starts) and starts[j] <= limit:
            hi = starts[j]
        out.append((lo, hi))
    return out


def device_account(trace, steps, kinds) -> dict:
    """The two device metrics and the notes that need a device plane;
    {} where the trace holds none."""
    b = xplane.busy(trace)
    if not b:
        return {}
    first = sorted(b["merged"])[0]
    plane = next(p for p in xplane.device_planes(trace) if p.name == first)
    turnovers = [s for s, k in zip(steps, kinds) if k == "turnover"]
    off, spread = clock_offset(trace, turnovers,
                               module_events(plane, MODULES["prefill"]))
    shift = off or 0
    mods = {k: [e._replace(start=e.start + shift)
                for e in module_events(plane, v)]
            for k, v in MODULES.items()}
    window = tuple(t + shift for t in b["window_ns"])
    gaps = Gaps([[s + shift, e + shift] for s, e in b["merged"][first]],
                window)
    idle = [gaps.idle(lo, hi) for lo, hi
            in turnover_edges(steps, kinds, mods["decode_step"])]
    whole, win = xplane.total(gaps.iv), window[1] - window[0]
    by_phase = idle_by_phase(
        gaps, innermost(span_readers.main_line_events(trace)))
    return {
        "serve_turnover_idle_ms": _median_ms(idle),
        "device_idle_turnover_share": 100.0 * sum(idle) / win,
        "notes": {
            "turnovers_a_second": len(turnovers) / (win / 1e9),
            "idle_s": {"whole": whole / 1e9,
                       "in_turnover_turns": sum(idle) / 1e9,
                       "outside_turnover_turns": (whole - sum(idle)) / 1e9,
                       "outside_turnover_turns_share":
                           100.0 * (whole - sum(idle)) / win,
                       "gaps_of_50_us_or_more": sum(
                           e - s for s, e in gaps.iv
                           if e - s >= xplane.GAP_FLOOR_NS) / 1e9},
            "idle_by_phase_s": {k: v / 1e9 for k, v in sorted(
                by_phase.items(), key=lambda kv: -kv[1])},
            "module_median_ms": {
                k: {"ms": _median_ms([e.dur for e in evs]),
                    "runs": len(evs)} for k, evs in mods.items() if evs},
            "clock_offset_ms": None if off is None else {
                "added_to_the_device": off / 1e6,
                "least_most_over_turnovers": [x / 1e6 for x in spread]},
            "shared_clock": shared_clock(steps, kinds, mods["decode_step"],
                                         shift)}}


def account(obs, trace):
    """Everything the five metrics and the notes are read from, once a
    run: {"values": {...}, "notes": {...}} or None."""
    if obs.get("kind") != "serve_engine" or trace is None:
        return None
    if obs.get("_turn_account", (None,))[0] is trace:
        return obs["_turn_account"][1]
    steps = span_readers.serve_steps(trace)
    if not steps:
        return None
    kinds = [kind_of(s) for s in steps]
    count = collections.Counter(kinds)
    n_dispatch = len(steps) - count["no_dispatch"]
    turnovers = [s for s, k in zip(steps, kinds) if k == "turnover"]
    steady = [s for s, k in zip(steps, kinds) if k == "steady"]
    spans = lambda name: [e - s for st in steps
                          for s, e in st["children"].get(name, ())]
    admits, prefills = spans(ADMIT), spans(PREFILL)
    cover = [sum(_span_total(s, n) for n in span_readers.SERVE_CHILDREN)
             / max(s["span"][1] - s["span"][0], 1) for s in steady]
    notes = {
        "turns": {k: count[k] for k in KINDS},
        "admissions_a_turnover_turn": sum(
            len(s["children"][ADMIT]) for s in turnovers) / len(turnovers)
        if turnovers else None,
        # a total per turnover turn
        "turnover_turn_median_ms": {
            n: _median_ms([_span_total(s, n) for s in turnovers])
            for n in TURN_PHASES},
        # per admission (a turn may hold several)
        "admission_median_ms": {
            ADMIT: _median_ms(admits), PREFILL: _median_ms(prefills),
            **{n: _median_ms(spans(n)) for n in PREFILL_CHILDREN},
            "serve.admit less serve.prefill": _median_ms(
                [a - p for a, p in zip(admits, prefills)])
            if len(admits) == len(prefills) else None},
        "steady_step_covered_by_children": {
            "median": stats.median(cover), "least": min(cover)}
        if cover else None}
    device = device_account(trace, steps, kinds)
    notes.update(device.pop("notes", {}))
    values = {
        "serve_turnover_ms": _median_ms(
            [s["children"][DISPATCH][0][1] - s["span"][0]
             for s in turnovers]),
        "serve_first_token_wait_ms": _median_ms(spans(FIRST_TOKEN)),
        "serve_carried_share": 100.0 * len(steady) / n_dispatch
        if n_dispatch else None,
        "serve_turnover_idle_ms": device.get("serve_turnover_idle_ms"),
        "device_idle_turnover_share":
            device.get("device_idle_turnover_share")}
    out = {"values": values, "notes": notes}
    obs["_turn_account"] = (trace, out)
    obs.setdefault("notes", {})["serve_turnover"] = notes
    return out


def _value(obs, trace, name: str):
    a = account(obs, trace)
    return a["values"][name] if a else None


def serve_turnover_ms(obs, trace, ctx):
    """Median over turnover turns: start of the turn's `serve.step` ->
    end of its `serve.dispatch` (the host's whole exposed turn)."""
    return _value(obs, trace, "serve_turnover_ms")


def serve_first_token_wait_ms(obs, trace, ctx):
    """Median `serve.prefill.first_token`: the wait of `int(tok0)`."""
    return _value(obs, trace, "serve_first_token_wait_ms")


def serve_turnover_idle_ms(obs, trace, ctx):
    """Median over turnover turns of the first device's idle time (the
    complement of the union of its "XLA Ops") inside the turn as the
    device lives it (`turnover_edges`: the edge is moved from the end
    of `serve.step` to the start of the module the turn dispatched),
    on the measured clock (`clock_offset`), clipped to the busy
    window."""
    return _value(obs, trace, "serve_turnover_idle_ms")


def device_idle_turnover_share(obs, trace, ctx):
    """The sum of that idle time over all turnover turns / the traced
    window of `xplane.busy`: the turnovers' part of
    `device_idle_share`."""
    return _value(obs, trace, "device_idle_turnover_share")


def serve_carried_share(obs, trace, ctx):
    """Steady turns / turns with a dispatch: what `steps_carried /
    step` says inside the engine, read where the benchmark can."""
    return _value(obs, trace, "serve_carried_share")
