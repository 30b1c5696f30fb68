"""The readers of the program's own spans and counters
(harness/span_readers.py) on hand-made `Trace` tuples and on flush
records of the shape `run_training` writes. Run by hand with the other
rehearsal tests (`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`);
tier-1 runs the same CASES through tests/test_trace_spans.py."""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

from harness import span_readers, xplane  # noqa: E402

MS = 1_000_000      # ns


def flush(step: int, drained_ms=None, **parts) -> dict:
    """A step_stats record as the window keeps it; the parts not given
    share what is left of `drained_ms` evenly."""
    rec = {"event": "step_stats", "step": step, "drained_ms": drained_ms,
           "drained_parts_ms": None}
    if drained_ms is not None:
        rest = [k for k in span_readers.FLUSH_PARTS if k not in parts]
        left = (drained_ms - sum(parts.values())) / len(rest)
        rec["drained_parts_ms"] = {
            k: parts.get(k, left) for k in span_readers.FLUSH_PARTS}
    return rec


def train_obs(flushes, prof=None, every: int = 5, interval_s=3.0) -> dict:
    return {"kind": "train_cli", "log_interval": every,
            "profile_steps": prof, "flushes": flushes,
            "interval_s": [interval_s] * len(flushes)}


def serve_trace(steps, extra_lines=()):
    """A host plane whose first line is the serve loop's thread. A step
    is (start_ms, [(phase, dur_ms), ...]): its phases run back to back
    from 0.01 ms after `serve.step` opens, which closes 0.01 ms after
    the last; `bench.engine_step` wraps each, as the driver's does."""
    evs = []
    for start_ms, phases in steps:
        t = t0 = int(start_ms * MS)
        t += MS // 100
        for name, dur_ms in phases:
            evs.append(xplane.Event(name, t, int(dur_ms * MS), ""))
            if name == "serve.admit":       # its prefill lies inside it
                evs.append(xplane.Event("serve.prefill", t + 1000,
                                        int(dur_ms * MS) - 2000, ""))
            t += int(dur_ms * MS)
        t += MS // 100
        evs.append(xplane.Event("serve.step", t0, t - t0, ""))
        evs.append(xplane.Event("bench.engine_step", t0 - 1000,
                                t - t0 + 2000, ""))
    return xplane.Trace([xplane.Plane("/host:CPU", [
        xplane.Line("python", evs), *extra_lines])])


DECODE = [("serve.expire", 0.1), ("serve.page_append", 0.1),
          ("serve.upload", 0.5), ("serve.dispatch", 1.0),
          ("serve.sync", 290.0), ("serve.emit", 0.8)]
# dispatch opens 0.71 ms into a step; 0.81 ms of a step follow its sync
ADMIT = DECODE[:1] + [("serve.admit", 60.0)] + DECODE[1:]
SERVE = {"kind": "serve_engine"}

# (id, reader, obs, trace, value or None, a check of obs["notes"] or None)
CASES = [
    ("drained_share_over_whole_intervals", "train_drained_share",
     train_obs([flush(10, 3.0), flush(15, 6.0), flush(20)]), None,
     100.0 * (3.0 + 6.0) / (2 * 3000.0), None),
    ("drained_max_names_the_stalled_phase", "train_drained_max_ms",
     train_obs([flush(10, 3.0), flush(15, 403.0, emit=400.0),
                flush(20, 2.0)]), None, 403.0,
     lambda n: max(n["train_drained_max"]["parts_ms"],
                   key=n["train_drained_max"]["parts_ms"].get) == "emit"
     and n["train_drained_max"]["reported_at_step"] == 15
     and n["train_drained_parts_close_to"] < 1e-9),
    # the profiler runs over steps 15..20: the flushes at 20 and 25
    # cover steps it started or stopped in (2.3 s of drain for the
    # trace, not by the program) and are left out, as in
    # readers.train_stall_share; the one at 25 followed the traced flush
    ("profiler_touched_flushes_are_left_out", "train_drained_max_ms",
     train_obs([flush(15, 3.0), flush(20, 900.0), flush(25, 2300.0),
                flush(30, 4.0)], prof=(15, 20)), None, 4.0,
     lambda n: [f["reported_at_step"] for f
                in n["train_drained_traced_flushes"]] == [25]),
    ("no_drained_field_gives_none", "train_drained_share",
     train_obs([flush(10), flush(15)]), None, None, None),
    ("no_drained_field_gives_none_max", "train_drained_max_ms",
     train_obs([flush(10), flush(15)]), None, None, None),
    ("a_serve_run_has_no_flush", "train_drained_share",
     dict(SERVE), None, None, None),
    # turns: 1->2 = 0.81 + 5.0 outside + 0.71 = 6.52 ms; 2->3 admits and
    # is left out; 3->4 = 0.81 + 1.0 + 0.71 = 2.52 ms; median 4.52
    ("host_turn_is_sync_end_to_next_dispatch", "serve_host_turn_ms",
     dict(SERVE), serve_trace([(0.0, DECODE), (297.52, DECODE),
                               (600.0, ADMIT), (953.52, DECODE)]),
     4.52, lambda n: n["serve_turn"]["turns"] == 2
     and n["serve_turn"]["decode_steps"] == 4
     and abs(n["serve_turn"]["outside_serve_step_ms"] - 3.0) < 1e-6
     and abs(n["serve_turn"]["phase_median_ms"]["serve.upload"] - 0.5)
     < 1e-6 and n["serve_turn"]["phases_cover_least"] > 0.95),
    # another thread's line with more events must not be taken for the
    # loop's: the loop's line is the one that holds `serve.step`
    ("the_loop_line_is_found_among_threads", "serve_host_turn_ms",
     dict(SERVE), serve_trace(
         [(0.0, DECODE), (293.52, DECODE)],
         [xplane.Line("python", [xplane.Event("prefetch.produce", i * MS,
                                              MS // 2, "")
                                 for i in range(50)])]),
     0.81 + 1.0 + 0.71, None),
    ("parent_trace_without_program_spans_gives_none",
     "serve_host_turn_ms", dict(SERVE), xplane.Trace([xplane.Plane(
         "/host:CPU", [xplane.Line("python", [
             xplane.Event("bench.engine_step", 0, 296 * MS, ""),
             xplane.Event("bench.engine_step", 297 * MS, 296 * MS,
                          "")])])]), None, None),
    ("a_step_without_its_sync_is_no_decode_step", "serve_host_turn_ms",
     dict(SERVE), serve_trace([(0.0, DECODE[:4]), (10.0, DECODE[:4])]),
     None, None),
    ("an_untraced_run_gives_none", "serve_host_turn_ms", dict(SERVE),
     None, None, None),
]


def check(reader, obs, trace, want, notes_ok) -> None:
    obs = dict(obs)
    got = getattr(span_readers, reader)(obs, trace, {})
    if want is None:
        assert got is None
        assert "notes" not in obs
        return
    assert got == pytest.approx(want, rel=1e-9)
    if notes_ok is not None:
        assert notes_ok(obs["notes"]), obs["notes"]


@pytest.mark.parametrize("reader,obs,trace,want,notes_ok",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_span_reader(reader, obs, trace, want, notes_ok):
    check(reader, obs, trace, want, notes_ok)


def test_new_metrics_are_listed_with_their_readers():
    """Each new metric: a file that names its reader, and an entry of
    BENCHMARK.json appended after everything the benchmark had."""
    from harness import common
    listed = [m["name"] for m in common.benchmark_json()["per_layer"]]
    new = ["train_drained_share", "train_drained_max_ms",
           "serve_host_turn_ms_batch"]
    assert listed[-3:] == new
    for name in new:
        spec = common.named("layer_metrics", name)
        assert callable(common.resolve(spec["reader"]))
        entry = common.benchmark_json()["per_layer"][listed.index(name)]
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert entry[key] == spec[key], (name, key)
