"""Async overlapped checkpointing: snapshot-then-write (DESIGN.md §15).

The reference is built around interrupted training — the energy governor
suspends runs on battery/thermal signals, so frequent checkpoints are a
first-class workload — yet a naive save stalls the step loop for the
device→host pull PLUS the disk write. This module splits the two:

  - **snapshot** (`snapshot`/`timed_snapshot`): the step loop's ONLY
    blocking work. Phase 1 issues `copy_to_host_async` on every
    addressable shard of every device leaf in ONE batched pass (the
    transfers overlap each other and any in-flight device compute);
    phase 2 is one bounded wait that materializes the host numpy tree.
    The wait is NOT optional — it is the donation-hazard guard: the
    caller's next dispatched train step donates the trainable/optimizer
    buffers (`make_train_step(donate=True)`), and an un-awaited D2H copy
    would race the donated buffers' reuse and snapshot garbage. After
    `snapshot` returns, the host tree is immutable numpy and the step
    loop may dispatch freely (regression-pinned by
    tests/test_async_ckpt.py's donation test).

  - **write** (`AsyncCheckpointer`): HF key-mapping, bf16 encode, and
    the safetensors write run on a single background thread, off the
    step loop. Crash safety belongs to the writers themselves
    (`safetensors_io.atomic_publish`: tmp + fsync + atomic rename — a
    kill mid-write can never corrupt the checkpoint `--resume_from`
    loads). Backpressure is a bounded depth-1 queue: a save request
    landing while one is in flight COALESCES to the newest snapshot
    (the superseded snapshot is dropped with a `ckpt_dropped` telemetry
    event — checkpoints are recovery points, only the newest matters);
    `final=True` saves drain the queue and block until everything is on
    disk. Background write failures are stored and re-raised at the
    next save()/drain()/close(raise_errors=True), so a disk-full writer
    surfaces instead of silently losing checkpoints.

Telemetry: the `checkpoint` event is emitted HERE (not by the step
loop), carrying the split the goodput accounting needs — `wall_s` and
`snapshot_ms` are the blocking cost charged to the loop (what the
goodput `checkpoint` bucket counts), `write_ms`/`bytes`/`mb_s` the
background cost that now overlaps `step` time. The sync oracle path
(`--async_save 0`, enabled=False) runs the same write_fn inline and
emits the same event shape with `async: false` and `wall_s` covering
snapshot + write — the two paths produce byte-identical files
(tests/test_async_ckpt.py pins the parity for both model families).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Iterable, Optional

import numpy as np

from mobilefinetuner_tpu.core.trace import NULL_TRACER

# lock-discipline declaration (core/static_checks.py, DESIGN.md §24):
# the cross-thread contract graftlint enforces mechanically — every
# guarded field may be touched only under the declared lock.
GRAFT_SHARED_STATE = {
    "AsyncCheckpointer": {
        "lock": "_lock",
        "guarded": ["_pending", "_inflight", "_error", "_stop"],
        "locked_helpers": [],
        "channels": ["_work"],  # Condition BUILT ON _lock
        "note": "dropped is written under _lock on the step-loop side; "
                "written is writer-thread-only; _thread is started "
                "under _lock and joined only by the step-loop thread",
    },
}


# ----------------------------- snapshot -------------------------------------

def snapshot(tree):
    """Batched device→host pull of a pytree: issue `copy_to_host_async`
    on EVERY device leaf first (one batched issue — the transfers
    overlap instead of serializing), then one bounded wait materializing
    numpy. Host/numpy leaves pass through untouched, so the function is
    idempotent and safe on already-gathered (multi-host) trees.

    The returned tree is plain numpy: safe to hand to a background
    writer while the step loop keeps training — including steps that
    DONATE the source buffers (the wait in phase 2 completes before any
    such dispatch can happen; see the module docstring's donation
    hazard)."""
    import jax
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            try:
                leaf.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass  # committed-to-host or deleted arrays: asarray below
    return jax.tree.map(np.asarray, tree)


def timed_snapshot(tree):
    """(host_tree, blocking_ms) — the number the step loop charges to
    the checkpoint goodput bucket and `checkpoint.snapshot_ms`."""
    t0 = time.perf_counter()
    host = snapshot(tree)
    return host, (time.perf_counter() - t0) * 1000.0


def tree_bytes(host_tree) -> int:
    """Total nbytes of a host snapshot (telemetry/bench accounting)."""
    import jax
    return int(sum(np.asarray(x).nbytes for x in jax.tree.leaves(host_tree)))


# ----------------------------- background writer -----------------------------

class CheckpointDrainTimeout(TimeoutError):
    """A bounded drain/close expired with a checkpoint write still in
    flight — the writer thread is wedged (dead filesystem, hung NFS).
    Carries the stuck step in the message so the operator knows WHICH
    recovery point never landed; atomic publication guarantees the
    unfinished write left no corrupt file behind. A NAMED type so
    cleanup paths can distinguish 'writer wedged, abandon it' from a
    real write error (which close() re-raises as RuntimeError)."""

    def __init__(self, step: int, timeout: float):
        self.step = step
        self.timeout = timeout
        super().__init__(
            f"checkpoint write for step {step} still in flight after "
            f"{timeout:.1f}s drain timeout (writer thread wedged; the "
            f"unfinished write cannot corrupt any published checkpoint)")


class _SaveItem:
    __slots__ = ("step", "write_fn", "final", "snapshot_ms", "done")

    def __init__(self, step, write_fn, final, snapshot_ms):
        self.step = step
        self.write_fn = write_fn
        self.final = final
        self.snapshot_ms = snapshot_ms
        self.done = threading.Event()


class AsyncCheckpointer:
    """Snapshot-then-write checkpoint pipeline (one per training run).

    `save(step, write_fn, final=..., snapshot_ms=...)` hands a
    zero-argument `write_fn` — closing over an already-snapshotted HOST
    tree — to a single background writer thread. `write_fn` must return
    the paths it wrote (for the bytes/MB-s accounting) and must go
    through atomically-publishing writers (every safetensors writer in
    this repo does — `safetensors_io.atomic_publish`).

    enabled=False is the synchronous oracle (`--async_save 0`): save()
    runs write_fn inline and returns after the write — same event
    shape, same bytes on disk, no thread.

    event_sink has `Telemetry.emit`'s signature (event, **fields) and
    may be None; emission is serialized by Telemetry's own lock, so the
    writer thread and the step loop share one stream safely.
    """

    def __init__(self, enabled: bool = True,
                 event_sink: Optional[Callable] = None,
                 tracer=NULL_TRACER):
        self.enabled = bool(enabled)
        self._sink = event_sink
        # each disk write is a `ckpt.write` span (core/trace.py) taken
        # on the writer THREAD: on that thread's line of a profiler
        # trace, and on the "ckpt" track of the stream under
        # --trace_spans, the background write is drawn overlapping
        # `step` time (the overlap is this module's whole point)
        self._tracer = tracer
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._pending: Optional[_SaveItem] = None
        self._inflight: Optional[_SaveItem] = None
        self._error: Optional[BaseException] = None
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self.dropped = 0   # coalesced-away snapshots (observable in tests)
        self.written = 0   # completed writes

    # -- step-loop side -------------------------------------------------------

    def save(self, step: int, write_fn: Callable[[], Iterable[str]], *,
             final: bool = False, snapshot_ms: float = 0.0) -> None:
        """Queue (async) or perform (sync) one checkpoint write. Blocking
        time for the caller: ~0 async (enqueue + possible coalesce), the
        full write when sync or final=True (final drains — the run must
        not end before its last checkpoint is durable). Raises a stored
        background-write error instead of enqueueing more work onto a
        broken writer."""
        self._raise_pending_error()
        if not self.enabled:
            self._write(_SaveItem(step, write_fn, final, snapshot_ms))
            self._raise_pending_error()
            return
        item = _SaveItem(step, write_fn, final, snapshot_ms)
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="ckpt-writer")
                self._thread.start()
            if self._pending is not None:
                # depth-1 backpressure: coalesce to the newest snapshot.
                # A checkpoint is a recovery point — when the writer
                # falls behind, writing every intermediate one buys
                # nothing but queue growth (unbounded host copies of the
                # whole tree); the superseded snapshot is dropped and
                # recorded.
                old = self._pending
                self._pending = item
                self.dropped += 1
                old.done.set()  # nobody will write it; unblock waiters
                self._emit(event="ckpt_dropped", step=old.step,
                           superseded_by=item.step)
            else:
                self._pending = item
            self._work.notify()
        if final:
            self.drain()

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until the queue is empty and the in-flight write (if
        any) completed; re-raise any background-write error. timeout
        (per outstanding item) bounds the wait for cleanup paths — a
        final=True save drains WITHOUT one (the run must not end before
        its last checkpoint is durable). A bounded drain that expires
        raises CheckpointDrainTimeout NAMING the in-flight step (it
        used to return silently, which let a hung write stall shutdown
        indefinitely downstream — the caller had no way to know the
        drain gave up)."""
        while True:
            with self._lock:
                item = self._inflight or self._pending
            if item is None:
                break
            if not item.done.wait(timeout):
                raise CheckpointDrainTimeout(item.step, timeout or 0.0)
        self._raise_pending_error()

    def close(self, raise_errors: bool = True,
              drain_timeout: float = 600.0) -> None:
        """Drain outstanding writes (a snapshot already taken is a
        checkpoint worth finishing, even when the training loop died)
        and stop the writer thread. raise_errors=False swallows write
        errors — for exception-path cleanup where re-raising would mask
        the original failure. The drain is BOUNDED (generously — any
        real write finishes in minutes; a dead filesystem never does)
        so a wedged writer cannot hang cleanup forever: on
        CheckpointDrainTimeout the daemon thread is ABANDONED — no
        30-second join against a thread known to be stuck (atomic
        publication means the unfinished write leaves no corrupt file
        behind) — and with raise_errors the named timeout propagates so
        shutdown reports WHICH step's recovery point was lost. The
        writer thread is stopped/joined on every other path, including
        when the drain re-raises a stored write error (no thread
        leak)."""
        wedged = False
        try:
            self.drain(timeout=drain_timeout)
        except CheckpointDrainTimeout:
            wedged = True
            if raise_errors:
                raise
        except BaseException:
            if raise_errors:
                raise
        finally:
            with self._lock:
                self._stop = True
                self._work.notify()
            if self._thread is not None:
                self._thread.join(timeout=0.2 if wedged else 30.0)
                self._thread = None

    # -- writer side ----------------------------------------------------------

    def _raise_pending_error(self):
        with self._lock:
            err, self._error = self._error, None
        if err is not None:
            raise RuntimeError("background checkpoint write failed") from err

    def _emit(self, event: str, **fields):
        if self._sink is not None:
            self._sink(event, **fields)

    def _write(self, item: _SaveItem) -> None:
        t0 = time.perf_counter()
        try:
            with self._tracer.span("ckpt.write", "ckpt", step=item.step):
                paths = list(item.write_fn() or ())
        except BaseException as e:  # surfaced at the next save()/drain()
            with self._lock:
                self._error = e
            return
        finally:
            item.done.set()
        write_ms = (time.perf_counter() - t0) * 1000.0
        nbytes = 0
        for p in paths:
            try:
                nbytes += os.path.getsize(p)
            except OSError:
                pass
        self.written += 1
        # wall_s = the BLOCKING cost this save charged to the step loop
        # (snapshot only under async; snapshot + write sync) — the same
        # number the goodput `checkpoint` bucket and partial_goodput
        # count, so the stream's checkpoint accounting matches the meter
        blocking_ms = item.snapshot_ms + (0.0 if self.enabled else write_ms)
        self._emit(event="checkpoint", step=item.step, final=item.final,
                   wall_s=round(blocking_ms / 1000.0, 4),
                   snapshot_ms=round(item.snapshot_ms, 3),
                   write_ms=round(write_ms, 3),
                   bytes=nbytes,
                   mb_s=(round(nbytes / 2**20 / (write_ms / 1000.0), 2)
                         if write_ms > 0 and nbytes else None),
                   **{"async": self.enabled})

    def _run(self) -> None:
        while True:
            with self._lock:
                while self._pending is None and not self._stop:
                    self._work.wait()
                if self._pending is None and self._stop:
                    return
                self._inflight, self._pending = self._pending, None
                item = self._inflight
            try:
                self._write(item)
            finally:
                with self._lock:
                    self._inflight = None


def submit(ckpt: Optional[AsyncCheckpointer], step: int,
           write_fn: Callable[[], Iterable[str]], *, final: bool = False,
           snapshot_ms: float = 0.0) -> None:
    """Save-hook helper: route through the run's checkpointer when the
    loop passed one, else write inline (direct/legacy callers — align
    dumps, tests driving a save hook by hand)."""
    if ckpt is None:
        write_fn()
        return
    ckpt.save(step, write_fn, final=final, snapshot_ms=snapshot_ms)
