"""Async double-buffered input pipeline: bounded-queue background batch
producer + device-placement lookahead.

The optimizer step on TPU dispatches asynchronously, so the only thing
that can stall the device between steps is the HOST: tokenization (the
streaming-window refetch in data/wikitext2.py re-encodes lines on a
window miss), step-batch assembly across grad-accum micro-batches, and
the blocking shard/`device_put` before the compiled step can be fed.
This module takes all of that off the step loop's critical path:

  stage 1 — producer thread: runs the existing host-side batch generator
      (`cli/common.micro_batches`, `WikiText2Dataset.epoch`) into a
      bounded FIFO queue (`depth` items). ONE thread consumes the
      generator, so the queue order IS the generator order — the
      determinism contract below costs nothing.
  stage 2 — device lookahead: `place_fn` (shard_batch /
      `device_put_global`) is issued for batch k+1 while the caller still
      computes step k, so the host->HBM transfer overlaps device compute
      (classic double buffering; `lookahead` placed batches in flight).

Determinism contract: the prefetched stream yields the BYTE-IDENTICAL
batch sequence of the synchronous path — same generator, consumed in
order, placed in order. Resume (`skip_steps` fast-forward), per-epoch
shuffle, and multi-host per-process sharding therefore behave exactly as
without prefetch (every process still runs the same seeded pipeline and
feeds only its addressable shards; nothing about placement changes, only
WHEN it happens). `depth=0` is the kill-switch: no thread, no lookahead,
the caller pulls the generator synchronously through the same interface.

Shutdown: `close()` (also wired through `__exit__`/`__del__`) stops the
producer promptly even when it is blocked on a full queue — the producer
only ever waits on the queue with a timeout and re-checks a stop event —
and a generator that RAISES in the producer thread re-raises the same
exception at the consumer's next `__next__`. A consumer that dies
mid-epoch just calls `close()`; no thread outlives it.
"""

from __future__ import annotations

import collections
import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional

from mobilefinetuner_tpu.core.trace import NULL_TRACER

_DONE = object()

# lock-discipline declaration (core/static_checks.py, DESIGN.md §24):
# this module deliberately has NO lock — producer<->consumer state rides
# self-synchronizing primitives, and everything else is single-thread.
GRAFT_SHARED_STATE = {
    "Prefetcher": {
        "lock": None,
        "guarded": [],
        "channels": ["_q", "_stop"],  # bounded Queue + stop Event
        "note": "_buf/_exhausted/_error/_closed are consumer-thread-"
                "only; _rss_limit/_rss_logged producer-only after "
                "__init__ (construction happens-before thread start); "
                "rss_sheds is a monotonic int gauge (benign race by "
                "design, documented observable)",
    },
}


class _Failure:
    """Producer-side exception, carried through the queue to the consumer."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class Prefetcher:
    """Iterator over `source` with a background producer and placement
    lookahead.

    Args:
      source: any iterable of batches (host-side work happens in its
        `__next__` — that is what moves off the critical path).
      depth: bounded queue size (max host batches buffered ahead of the
        consumer). 0 disables BOTH the thread and the lookahead — the
        synchronous reference path, same interface.
      place_fn: optional per-item placement (shard_batch/device_put);
        applied in order, `lookahead` items ahead of the consumer.
      lookahead: placed items in flight beyond the one being returned
        (1 = classic double buffering).

    Consumers that want the host/device breakdown time their own
    `next()` calls (cli/common.run_training's host_wait_ms does): that
    covers queue wait AND lookahead placement with one mechanism, and
    reads the same for the depth=0 synchronous path.
    """

    def __init__(self, source: Iterable, depth: int = 2,
                 place_fn: Optional[Callable[[Any], Any]] = None,
                 lookahead: int = 1, rss_limit_mb: float = 0,
                 rss_fn: Optional[Callable[[], Optional[float]]] = None,
                 tracer=NULL_TRACER):
        if depth < 0:
            raise ValueError(f"depth must be >= 0, got {depth}")
        # each batch the producer thread assembles is a
        # `prefetch.produce` span (core/trace.py) on the producer's own
        # line of a profiler trace — and on the "prefetch" track of the
        # stream under --trace_spans — so host batch assembly is SEEN
        # overlapping device steps (the overlap is this module's claim)
        # instead of inferred from host_wait_ms
        self._tracer = tracer
        self._place = place_fn if place_fn is not None else (lambda x: x)
        self._lookahead = max(lookahead, 0) if depth > 0 else 0
        self._buf: collections.deque = collections.deque()
        self._exhausted = False
        self._closed = False
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._q = None
        # host-RSS shed guard (core/memory_guard.host_rss_mb): while the
        # process RSS sits above rss_limit_mb the producer stops
        # assembling lookahead batches until the consumer drains the
        # queue — the pipeline degrades toward depth-1 instead of the
        # OS OOM-killer picking a victim. 0 = off. rss_fn is injectable
        # for tests; a backend whose RSS cannot be read disables the
        # guard (never block on a sensor that cannot answer).
        self._rss_limit = max(float(rss_limit_mb), 0.0)
        if rss_fn is None:
            from mobilefinetuner_tpu.core.memory_guard import host_rss_mb
            rss_fn = host_rss_mb
        self._rss_fn = rss_fn
        self._rss_logged = False
        self.rss_sheds = 0  # lookahead batches deferred under pressure
        if depth > 0:
            self._q = queue.Queue(maxsize=depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._produce, args=(source,),
                name="batch-producer", daemon=True)
            self._thread.start()
        else:
            self._it = iter(source)

    # -- producer thread -----------------------------------------------------

    def _put(self, item) -> bool:
        """Queue-put that stays responsive to close(); False = stopping."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _shed_on_rss(self) -> None:
        """Hold the producer BEFORE it assembles the next batch while
        host RSS exceeds the limit and the consumer still has queued
        batches to drain: under memory pressure the lookahead is the
        one host allocation this pipeline controls, so it is the first
        thing to give back. Resumes as soon as RSS drops below the
        limit or the queue empties (a starved consumer always wins —
        shedding must degrade throughput, never deadlock it)."""
        if not self._rss_limit:
            return
        rss = self._rss_fn()
        if rss is None:
            self._rss_limit = 0  # unreadable sensor: guard off, once
            return
        if rss <= self._rss_limit:
            return
        self.rss_sheds += 1
        if not self._rss_logged:
            self._rss_logged = True
            from mobilefinetuner_tpu.core.logging import get_logger
            get_logger().warning(
                f"host RSS {rss:.0f} MB over the {self._rss_limit:.0f} "
                f"MB prefetch guard: shedding lookahead depth until "
                f"pressure clears")
        while not self._stop.is_set() and self._q.qsize() > 0:
            rss = self._rss_fn()
            if rss is None or rss <= self._rss_limit:
                break
            self._stop.wait(0.02)

    def _produce(self, source):
        try:
            it = iter(source)
            while True:
                self._shed_on_rss()
                if self._stop.is_set():
                    return
                try:
                    with self._tracer.span("prefetch.produce", "prefetch"):
                        item = next(it)
                except StopIteration:
                    self._put(_DONE)
                    return
                if not self._put(item):
                    return
        except BaseException as e:  # noqa: BLE001 — carried to the consumer
            self._put(_Failure(e))

    # -- consumer side -------------------------------------------------------

    def _get(self):
        """Next raw item, or the _DONE / _Failure terminal marker."""
        if self._thread is None:
            try:
                return next(self._it)
            except StopIteration:
                return _DONE
            except BaseException as e:  # sync path: same deferral contract
                return _Failure(e)
        return self._q.get()

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        # keep `lookahead + 1` placed items in flight: before item k is
        # returned, items k+1..k+lookahead are already placed (their
        # host->device transfers overlap the caller's step k)
        while not self._exhausted and len(self._buf) < self._lookahead + 1:
            item = self._get()
            if item is _DONE:
                self._exhausted = True
            elif isinstance(item, _Failure):
                # surface the generator's exception only once everything
                # produced BEFORE it has been consumed — the exact point
                # the synchronous path would raise at
                self._exhausted = True
                self._error = item.exc
            else:
                self._buf.append(self._place(item))
        if self._buf:
            return self._buf.popleft()
        err, self._error = self._error, None
        self.close()
        if err is not None:
            raise err
        raise StopIteration

    def queue_depth(self) -> int:
        """Instantaneous gauge: host batches buffered ahead of the
        consumer (producer queue + placed lookahead buffer). A healthy
        pipeline sits near its depth; a gauge stuck at 0 means the
        producer is the bottleneck — the telemetry step_stats field that
        tells a host-bound run from a device-bound one without a
        profiler. 0 on the synchronous (depth=0) path."""
        q = self._q.qsize() if self._q is not None else 0
        return q + len(self._buf)

    # -- lifecycle -----------------------------------------------------------

    def close(self, join_timeout: float = 5.0):
        """Stop the producer and release the queue. Idempotent; safe from
        any consumer error path (use as a context manager or try/finally).
        """
        if self._closed:
            return
        self._closed = True
        self._buf.clear()
        if self._thread is not None:
            self._stop.set()
            # unblock a producer sitting in a full-queue put (it re-checks
            # the stop event on its put timeout anyway; draining just
            # shortens the join)
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=join_timeout)
            self._thread = None

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # best-effort: never leak the producer thread
        try:
            self.close(join_timeout=0.1)
        except Exception:
            pass
