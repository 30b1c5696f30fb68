"""Program spans + anomaly-triggered profiler capture (DESIGN.md §22).

The telemetry stream (core/telemetry.py) records WHAT happened; this
module records WHEN. `Tracer.span` is the repo's ONE span primitive and
it has two sinks:

  - the profiler's own trace. Every lexical span is also a
    `jax.profiler.TraceAnnotation`, so whenever a profiler session is
    live (--profile_dir, the AutoProfiler below, the benchmark's
    --trace 1) the span sits in the `.xplane.pb` host plane, on the
    line of the thread that ran it and on the DEVICE trace's clock:
    an idle gap on the chip is attributed to the phase the host was in
    by reading one file. With no session the annotation is TraceMe's
    inactive check (a fraction of a microsecond), so the spans stay in
    the train loop and the serve loop always.
  - the telemetry JSONL, OPT-IN (--trace_spans /
    ServeConfig.trace_spans): one `span` record per completed span,
    emitted at span END on a named TRACK, which tools/trace_export.py
    converts to one Perfetto timeline. `Tracer.enabled` gates this sink
    ONLY; while it is off a span formats no string, builds no record
    and reads no clock.

`emit_span` stays for spans that are pure bookkeeping over stamps the
caller already holds (the GoodputMeter's phase track, the serve
engine's `req:<id>` lifecycle): those are JSONL-only. Spans ride the
SAME crash-durable stream as every other event, so a killed run keeps
every span that finished before the kill.

Clock discipline: a JSONL span's `t0` is time.perf_counter() — the
clock of the envelope's `t_mono` — so the exporter places spans on the
wall timeline via the per-host (t - t_mono) offset without NTP-step
jitter corrupting durations.

Everything here is host-side: no device access, no top-level jax
import (core/static_checks.py pins it); `jax.profiler` is bound on the
first span.

The second half is the flight recorder (`AutoProfiler`, --auto_profile):
a manually pre-scheduled --profile_dir window is useless for the
anomalies the sensors actually catch, so this arms a ONE-SHOT
jax.profiler capture when a sensor fires — slow-step multiple over the
rolling median, loss-spike/divergence anomaly, straggler attribution,
hang watchdog pre-exit — saving the device trace of the BAD step next
to the stack dumps, under a capture budget and a cooldown so a
persistently sick run produces a few traces, not a disk full of them.
Every capture decision is a `profile_capture` telemetry event.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional

_annotation: Optional[Callable] = None  # bound by _bind_annotation


def _bind_annotation() -> Callable:
    """`jax.profiler.TraceAnnotation`, bound on the first span: this
    module may not import jax at load (core/static_checks.py), and an
    annotation touches no device."""
    global _annotation
    import jax
    _annotation = jax.profiler.TraceAnnotation
    return _annotation


class _Span:
    """One lexical span (`Tracer.span`): a TraceAnnotation always, a
    `span` record on exit while the tracer's JSONL sink is on."""

    __slots__ = ("_tracer", "_name", "_track", "_extra", "_ann", "_t0")

    def __init__(self, tracer, name, track, extra):
        self._tracer, self._name = tracer, name
        self._track, self._extra = track, extra

    def __enter__(self):
        self._ann = (_annotation or _bind_annotation())(self._name)
        self._ann.__enter__()
        if self._tracer.enabled:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)    # first: writing the record is not
        if self._tracer.enabled:    # part of what the span measures
            self._tracer.emit_span(
                self._name, self._track, self._t0,
                (time.perf_counter() - self._t0) * 1000.0, **self._extra)
        return False


class Tracer:
    """The span primitive over a telemetry sink (`Telemetry.emit`
    signature).

    One emit site for the whole repo: every producer — the train loop,
    the serve loop, the goodput meter, the checkpoint writer, the
    prefetch producer — routes through `span` / `emit_span`, so the
    `span` event shape cannot fork between threads or subsystems.
    Thread-safety is the sink's problem (Telemetry.emit is
    lock-serialized), which is what lets the checkpoint writer and
    prefetch producer threads trace through the same stream as the
    step loop. `enabled` gates the JSONL sink only: a Tracer with no
    sink still annotates the profiler's trace.
    """

    def __init__(self, sink: Optional[Callable] = None,
                 enabled: bool = True):
        self._sink = sink
        self.enabled = bool(sink) and enabled

    def emit_span(self, name: str, track: str, t0: float, dur_ms: float,
                  **extra) -> None:
        """Record one completed span: `t0` is a time.perf_counter()
        stamp (the envelope's t_mono clock), dur_ms its length. Extra
        fields ride along (the schema is a floor)."""
        if not self.enabled:
            return
        self._sink(event="span", name=name, track=track,
                   t0=round(t0, 6), dur_ms=round(dur_ms, 3), **extra)

    def span(self, name: str, track: str = "main", **extra) -> _Span:
        """Lexical span: in the profiler's trace whenever a session is
        live, and a `span` record on exit while `enabled` — exception
        or not (the span that raised is exactly the one a post-mortem
        wants on the timeline)."""
        return _Span(self, name, track, extra)


#: the default of every component that takes a tracer: no JSONL sink,
#: so its spans reach only the profiler's trace (it holds no state)
NULL_TRACER = Tracer()


class AutoProfiler:
    """One-shot anomaly-triggered jax.profiler capture (--auto_profile).

    State machine: IDLE -> (sensor trigger, budget left, cooldown
    elapsed) -> CAPTURING (jax.profiler trace started into its own
    subdirectory; the step loop calls `tick` after each step and the
    capture stops after `steps` of them, syncing the device first so
    the trace actually contains the dispatched work) -> COOLDOWN.
    A `profile_capture` event records every completed capture — step,
    trigger kind, path, budget left — so the stream says where the
    trace of the bad step lives.

    The hang path is different: when the watchdog fires, the step loop
    is by definition not ticking, so `capture_now` takes a bounded
    capture on the CALLER's thread (start, hold, stop) — whatever the
    device is doing while wedged lands in the trace, before a
    --watchdog 2 abort can os._exit.

    `profiler_start`/`profiler_stop` are injectable so tests never
    depend on jax.profiler internals; the default binds lazily (no jax
    import at module load). Failures inside the profiler NEVER
    propagate — a broken capture must not kill the training run it was
    meant to diagnose.

    Thread-safety: `capture_now` runs on the WATCHDOG thread while
    `trigger`/`tick` run on the step loop, so every state transition is
    lock-serialized — without it, a loop that unwedges during a hang
    capture's hold could tick the hold capture to a premature stop and
    double-finish it (and two threads could double-start the one
    profiler). The lock is held across `capture_now`'s bounded hold:
    blocking a just-unwedged loop for the hold is noise next to the
    stall that fired the watchdog, and it is what keeps the profiler
    single-owner.
    """

    def __init__(self, out_dir: str, sink: Optional[Callable] = None,
                 steps: int = 2, cooldown_s: float = 300.0,
                 budget: int = 2,
                 profiler_start: Optional[Callable[[str], None]] = None,
                 profiler_stop: Optional[Callable[[], None]] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.out_dir = out_dir
        self._sink = sink
        self.steps = max(int(steps), 1)
        self.cooldown_s = float(cooldown_s)
        self.budget = max(int(budget), 0)
        self._clock = clock
        self._lock = threading.Lock()
        self._start = profiler_start or self._jax_start
        self._stop = profiler_stop or self._jax_stop
        self._last_capture_t: Optional[float] = None
        self._active_path: Optional[str] = None
        self._steps_left = 0
        self._trigger: Optional[str] = None
        self._n = 0
        self.captured = 0  # completed captures (test observable)

    @staticmethod
    def _jax_start(path: str) -> None:
        import jax
        jax.profiler.start_trace(path)

    @staticmethod
    def _jax_stop() -> None:
        import jax
        jax.profiler.stop_trace()

    @property
    def active(self) -> bool:
        return self._active_path is not None

    def _ready(self) -> bool:
        if self.active or self.budget <= 0:
            return False
        if self._last_capture_t is not None and \
                self._clock() - self._last_capture_t < self.cooldown_s:
            return False
        return True

    def _capture_path(self, trigger: str, step: int) -> str:
        path = os.path.join(self.out_dir,
                            f"cap{self._n}_{trigger}_step{step}")
        self._n += 1
        return path

    def trigger(self, kind: str, step: int) -> bool:
        """A sensor fired: start a capture unless one is active, the
        budget is spent, or the cooldown has not elapsed. Returns True
        exactly when a capture STARTED."""
        with self._lock:
            if not self._ready():
                return False
            path = self._capture_path(kind, step)
            try:
                os.makedirs(path, exist_ok=True)
                self._start(path)
            except Exception:
                return False  # a broken profiler must not kill the run
            self._active_path = path
            self._trigger = kind
            self._steps_left = self.steps
            return True

    def tick(self, step: int, sync: Optional[Callable] = None) -> bool:
        """One step completed under an active capture; stops the trace
        after `steps` ticks (running `sync` first so the async-
        dispatched device work is actually IN the window). Returns True
        when the capture completed on this tick."""
        with self._lock:
            if not self.active:
                return False
            self._steps_left -= 1
            if self._steps_left > 0:
                return False
            if sync is not None:
                try:
                    sync()
                except Exception:
                    pass
            return self._finish(step, steps=self.steps)

    def capture_now(self, kind: str, step: int,
                    hold_s: float = 1.0) -> bool:
        """Bounded immediate capture for callers with no step loop to
        tick — the hang watchdog's pre-exit hook: start, hold while the
        wedged device does whatever it is doing, stop, record. Never
        raises. Holds the lock for the whole start-hold-stop so the
        step loop can never tick this capture to a premature stop."""
        with self._lock:
            if not self._ready():
                return False
            path = self._capture_path(kind, step)
            try:
                os.makedirs(path, exist_ok=True)
                self._start(path)
            except Exception:
                return False
            self._active_path, self._trigger = path, kind
            time.sleep(max(hold_s, 0.0))
            # steps=None: a bounded hold, not a counted step window
            # (the schema documents exactly this)
            return self._finish(step, steps=None)

    def _finish(self, step: int, steps) -> bool:
        # caller holds self._lock; `steps` is what the capture ACTUALLY
        # covered (None for the hang path's bounded hold), not the
        # configured window — a close() mid-capture reports the steps
        # that ran, so post-mortem tooling never overstates the trace
        path, trigger = self._active_path, self._trigger
        try:
            self._stop()
        except Exception:
            self._active_path = None
            return False
        self._active_path = None
        self._last_capture_t = self._clock()
        self.budget -= 1
        self.captured += 1
        if self._sink is not None:
            self._sink(event="profile_capture", step=step,
                       trigger=trigger, path=path, steps=steps,
                       budget_left=self.budget)
        return True

    def close(self) -> None:
        """Stop a capture left open by an exiting loop (the trace of
        the steps that DID run is still worth keeping — and reported
        with the tick count that actually elapsed)."""
        with self._lock:
            if self.active:
                self._finish(-1, steps=self.steps - self._steps_left)
