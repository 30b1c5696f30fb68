"""The readers that itemise a turnover of the serve loop
(harness/turn_readers.py) on a hand-made `Trace`: one host line of
nested `serve.*` spans and one device plane whose idle gaps straddle
the spans' boundaries. Run by hand with the other rehearsal tests
(`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`); tier-1 runs
the same CASES through tests/test_trace_spans.py."""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

from harness import turn_readers, xplane  # noqa: E402

US = 1_000          # ns
ns = lambda ms: round(ms * 1000) * US


def span(name, dur_ms, *kids):
    return (name, dur_ms, kids)


def lay(evs, t_ms, node) -> float:
    """The node at t_ms, its children back to back from its start."""
    name, dur_ms, kids = node
    evs.append(xplane.Event(name, ns(t_ms), ns(dur_ms), ""))
    c = t_ms
    for kid in kids:
        c = lay(evs, c, kid)
    return t_ms + dur_ms


HEAD = (span("serve.expire", 0.1), span("serve.page_append", 0.1),
        span("serve.upload", 0.3), span("serve.dispatch", 0.4))
STEADY = span("serve.step", 3.0, *HEAD, span("serve.sync", 2.0),
              span("serve.emit", 0.1))
STALE = span("serve.step", 3.0, span("serve.sync", 2.0),
             span("serve.emit", 0.1), *HEAD)
FILL = span("serve.step", 1.0, *HEAD)
TURNOVER = span(
    "serve.step", 10.0, span("serve.sync", 1.0), span("serve.emit", 0.2),
    span("serve.expire", 0.1),
    span("serve.admit", 7.0, span(
        "serve.prefill", 6.5, span("serve.prefill.dispatch", 1.0),
        span("serve.prefill.page_write", 0.5),
        span("serve.prefill.first_token", 5.0))),
    span("serve.page_append", 0.1), span("serve.upload", 1.0),
    span("serve.dispatch", 0.5))
# (start ms, turn): A steady, B turnover, C steady, D stale, 0.5 ms of
# the driver's own, E steady, F fill
TURNS = ((0.0, STEADY), (3.0, TURNOVER), (13.0, STEADY), (16.0, STALE),
         (19.5, STEADY), (22.5, FILL))
# the device: (start ms, end ms, module). Idle: 3.9-5.0 (sync .1, emit
# .2, expire .1, prefill.dispatch .7), 9.0-9.02 (under the floor),
# 9.5-13.1 (first_token 1.3, admit's tail .5, page_append .1, upload 1.0,
# dispatch .5, the turn's own tail .1 and — the step it dispatched
# starts 0.1 ms AFTER its `serve.step` closed — .1 under the next
# turn's expire) — 4.72 ms that are the turnover's — and 17.9-19.2 (the
# stale turn's sync .1, emit .1, expire .1, page_append .1, upload .3,
# dispatch .4, and .2 outside any serve.step)
DEVICE = ((0.0, 0.95, "jit_step_py(1)"), (0.95, 3.9, "jit_step_py(1)"),
          (5.0, 9.0, "jit_prefill_py(2)"), (9.02, 9.5, "jit_write_py(3)"),
          (13.1, 15.8, "jit_step_py(1)"), (15.8, 17.9, "jit_step_py(1)"),
          (19.2, 22.3, "jit_step_py(1)"), (22.3, 24.0, "jit_step_py(1)"))
BY_PHASE_MS = {"serve.sync": 0.2, "serve.emit": 0.3, "serve.expire": 0.3,
               "serve.prefill.dispatch": 0.7,
               "serve.prefill.first_token": 1.3, "serve.admit": 0.5,
               "serve.page_append": 0.2, "serve.upload": 1.3,
               "serve.dispatch": 0.9, "serve.step": 0.1,
               turn_readers.OUTSIDE: 0.2}


# the runtime hands a program to the device: every module of DEVICE
# that starts on an idle device was enqueued in the 0.1 ms before
ENQUEUED_MS = (4.9, 8.9, 13.0, 19.1)


def host_plane(keep=lambda name: True, runtime: bool = True):
    evs = []
    for t, turn in TURNS:
        lay(evs, t, turn)
        evs.append(xplane.Event("bench.engine_step", ns(t) - US,
                                ns(turn[1]) + 2 * US, ""))
    other = xplane.Line("python", [xplane.Event(
        "prefetch.produce", ns(i), ns(0.5), "") for i in range(40)])
    queue = xplane.Line("tfrt-non-blocking-queue", [xplane.Event(
        turn_readers.ENQUEUE, ns(t), ns(0.1), "") for t in ENQUEUED_MS])
    return xplane.Plane("/host:CPU", [
        xplane.Line("python", [e for e in evs if keep(e.name)]), other,
        *([queue] if runtime else [])])


def device_plane(shift_ms: float = 0.0):
    ops = [xplane.Event("%fusion.1 = f32[] fusion()", ns(a + shift_ms),
                        ns(b - a), "") for a, b, _ in DEVICE]
    mods = [xplane.Event(name, ns(a + shift_ms), ns(b - a), "")
            for a, b, name in DEVICE]
    return xplane.Plane("/device:TPU:0", [
        xplane.Line(xplane.OPS_LINE, ops),
        xplane.Line(xplane.MODULES_LINE, mods)])


WHOLE = xplane.Trace([host_plane(), device_plane()])
PARENT = xplane.Trace([host_plane(
    lambda n: not n.startswith("serve.prefill.")), device_plane()])
NO_DEVICE = xplane.Trace([host_plane()])
# the device's line 3 ms early, as a profiler session may place it:
# measured and undone where the runtime's enqueue events say by how much
EARLY = xplane.Trace([host_plane(), device_plane(shift_ms=-3.0)])
EARLY_UNTOLD = xplane.Trace([host_plane(runtime=False),
                             device_plane(shift_ms=-3.0)])
NO_STEP = xplane.Trace([host_plane(lambda n: n.startswith("bench.")),
                        device_plane()])
SERVE = {"kind": "serve_engine"}
TURNOVER_IDLE_MS = 4.72
WINDOW_MS = 24.0
close = lambda a, b: abs(a - b) < 1e-9


def notes_of_the_whole_trace(n) -> bool:
    t = n["serve_turnover"]
    by = t["idle_by_phase_s"]
    adm, idle = t["admission_median_ms"], t["idle_s"]
    kids = [adm[k] for k in turn_readers.PREFILL_CHILDREN]
    return (
        t["turns"] == {"steady": 3, "stale": 1, "fill": 1, "turnover": 1,
                       "no_dispatch": 0}
        and t["admissions_a_turnover_turn"] == 1.0
        and close(t["turnovers_a_second"], 1 / (WINDOW_MS / 1e3))
        and set(by) == set(BY_PHASE_MS)
        and all(close(by[k] * 1e3, v) for k, v in BY_PHASE_MS.items())
        # the pieces sum to the idle time of the gaps of 50 us or more
        and close(sum(by.values()), idle["gaps_of_50_us_or_more"])
        and close(idle["gaps_of_50_us_or_more"] * 1e3, 6.0)
        and close(idle["whole"] * 1e3, 6.02)
        and close(idle["in_turnover_turns"] * 1e3, TURNOVER_IDLE_MS)
        and close(idle["in_turnover_turns"]
                  + idle["outside_turnover_turns"], idle["whole"])
        and kids == [1.0, 0.5, 5.0] and adm["serve.prefill"] == 6.5
        and adm["serve.admit"] == 7.0
        and close(adm["serve.admit less serve.prefill"], 0.5)
        and t["turnover_turn_median_ms"] == {
            "serve.sync": 1.0, "serve.emit": 0.2, "serve.expire": 0.1,
            "serve.upload": 1.0, "serve.dispatch": 0.5}
        and close(t["steady_step_covered_by_children"]["median"], 1.0)
        and close(t["module_median_ms"]["prefill"]["ms"], 4.0)
        and close(t["module_median_ms"]["page_write"]["ms"], 0.48)
        and t["module_median_ms"]["decode_step"]["runs"] == 6
        and t["clock_offset_ms"] == {
            "added_to_the_device": 0.0,
            "least_most_over_turnovers": [0.0, 0.0]}
        and t["shared_clock"] == {
            "module_starts_after_its_dispatch_share": 100.0,
            "as_the_trace_gave_it_share": 100.0,
            "turnover_dispatch_to_module_start_ms": 0.7})


# (id, reader, obs, trace, value or None, a check of obs["notes"] or None)
CASES = [
    ("turnover_is_step_start_to_dispatch_end", "serve_turnover_ms",
     SERVE, WHOLE, 9.9, notes_of_the_whole_trace),
    ("first_token_wait_is_the_child_alone", "serve_first_token_wait_ms",
     SERVE, WHOLE, 5.0, None),
    # to the start of the step the turn dispatched, 0.1 ms past the end
    # of its `serve.step`
    ("turnover_idle_is_the_device_s_gaps_until_its_step_starts",
     "serve_turnover_idle_ms", SERVE, WHOLE, TURNOVER_IDLE_MS, None),
    ("turnover_share_is_of_the_busy_window",
     "device_idle_turnover_share", SERVE, WHOLE,
     100.0 * TURNOVER_IDLE_MS / WINDOW_MS, None),
    # steady A, C, E of the six turns with a dispatch: a stale turn
    # without an admission and a fill turn are not carried
    ("carried_share_counts_steady_turns_alone", "serve_carried_share",
     SERVE, WHOLE, 50.0, None),
    # a program without the three children (the parent): b is silent,
    # the others answer, and the prefill's idle time stays with it
    ("parent_has_no_first_token_span", "serve_first_token_wait_ms",
     SERVE, PARENT, None, None),
    ("parent_still_gives_the_turnover", "serve_turnover_ms", SERVE,
     PARENT, 9.9, lambda n: close(
         n["serve_turnover"]["idle_by_phase_s"]["serve.prefill"] * 1e3,
         2.0)),
    ("parent_still_gives_the_idle", "serve_turnover_idle_ms", SERVE,
     PARENT, TURNOVER_IDLE_MS, None),
    ("parent_still_gives_the_carried_share", "serve_carried_share",
     SERVE, PARENT, 50.0, None),
    # a trace of the CPU backend holds no device plane
    ("no_device_plane_no_idle", "serve_turnover_idle_ms", SERVE,
     NO_DEVICE, None, None),
    ("no_device_plane_no_share", "device_idle_turnover_share", SERVE,
     NO_DEVICE, None, None),
    ("no_device_plane_spans_still_answer", "serve_first_token_wait_ms",
     SERVE, NO_DEVICE, 5.0,
     lambda n: "idle_by_phase_s" not in n["serve_turnover"]),
    # the device's line 3 ms early: the offset is measured from the
    # runtime's enqueue and every reading is the whole trace's
    ("an_early_device_line_is_moved_back", "serve_turnover_idle_ms",
     SERVE, EARLY, TURNOVER_IDLE_MS,
     lambda n: notes_of_the_whole_trace({"serve_turnover": dict(
         n["serve_turnover"],
         clock_offset_ms={"added_to_the_device": 0.0,
                          "least_most_over_turnovers": [0.0, 0.0]},
         shared_clock=dict(n["serve_turnover"]["shared_clock"],
                           as_the_trace_gave_it_share=100.0))})
     and n["serve_turnover"]["clock_offset_ms"]["added_to_the_device"]
     == 3.0 and n["serve_turnover"]["shared_clock"][
         "as_the_trace_gave_it_share"] < 100.0),
    ("an_early_device_line_same_share", "device_idle_turnover_share",
     SERVE, EARLY, 100.0 * TURNOVER_IDLE_MS / WINDOW_MS, None),
    # no enqueue event to measure it by: nothing is moved, a module
    # starts before the dispatch that queued it, and the sanity line
    # says so
    ("an_unmeasured_offset_shows_in_the_sanity_line",
     "serve_carried_share", SERVE, EARLY_UNTOLD, 50.0,
     lambda n: n["serve_turnover"]["clock_offset_ms"] is None
     and n["serve_turnover"]["shared_clock"][
         "module_starts_after_its_dispatch_share"] < 100.0),
    ("no_serve_step_gives_none", "serve_turnover_ms", SERVE, NO_STEP,
     None, None),
    ("no_serve_step_gives_none_share", "device_idle_turnover_share",
     SERVE, NO_STEP, None, None),
    ("an_untraced_run_gives_none", "serve_carried_share", SERVE, None,
     None, None),
    ("a_train_run_has_no_turn", "serve_turnover_ms",
     {"kind": "train_cli"}, WHOLE, None, None),
]


def check(reader, obs, trace, want, notes_ok) -> None:
    obs = dict(obs)
    got = getattr(turn_readers, reader)(obs, trace, {})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
    if notes_ok is not None:
        assert notes_ok(obs["notes"]), obs["notes"]


@pytest.mark.parametrize("reader,obs,trace,want,notes_ok",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_turn_reader(reader, obs, trace, want, notes_ok):
    check(reader, obs, trace, want, notes_ok)


def test_innermost_cuts_nested_spans_at_their_boundaries():
    evs = []
    lay(evs, 0.0, span("a", 10.0, span("b", 4.0, span("c", 1.0)),
                       span("d", 2.0)))
    lay(evs, 12.0, span("a", 1.0))
    evs.sort(key=lambda e: (e.start, -e.dur))
    assert turn_readers.innermost(evs) == [
        (ns(0), ns(1), "c"), (ns(1), ns(4), "b"), (ns(4), ns(6), "d"),
        (ns(6), ns(10), "a"), (ns(12), ns(13), "a")]
