"""Serve-layer robustness (round 14, DESIGN.md §19): bounded admission
and load shedding, per-request deadlines, step-dispatch crash
containment, SIGTERM graceful drain, and the fault-injection harness —
the serve-side mirror of r13's injected-failure fleet tests.

Two invariants anchor everything here:

  TERMINAL ACCOUNTING — every request reaching a terminal state
  (finished | cancelled | rejected | timeout | error) emits exactly ONE
  terminal `request` phase and releases exactly the pages it allocated
  (`assert_terminal_accounting`, run after every fault e2e);

  COMPILE STABILITY — rejects, sheds, timeouts, containment, and drain
  are host-side bookkeeping: ≤2 post-warmup traces (0 expected) across
  every fault path, and surviving requests' greedy outputs stay
  token-identical to the batch-at-a-time generate() oracle.
"""

import dataclasses
import json
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))

from mobilefinetuner_tpu.core.config import GPT2Config
from mobilefinetuner_tpu.core.preempt import PreemptionGuard
from mobilefinetuner_tpu.core.telemetry import (HangWatchdog, Telemetry,
                                                validate_event)
from mobilefinetuner_tpu.models import gpt2
from mobilefinetuner_tpu.models.generate import SampleConfig, gpt2_generate
from mobilefinetuner_tpu.serve import Request, ServeConfig, ServeEngine

CFG = dataclasses.replace(
    GPT2Config.tiny(vocab_size=211), n_embd=64, n_head=4, n_positions=64,
    n_layer=2, embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(CFG, jax.random.PRNGKey(0))


def make_engine(params, tmp_path=None, stream="r.jsonl", **cfg_kw):
    kw = dict(num_slots=2, block_T=8, num_blocks=32, max_prompt=16,
              max_new_tokens=8)
    kw.update(cfg_kw)
    tel = Telemetry(str(tmp_path / stream)) if tmp_path is not None \
        else Telemetry("")
    return ServeEngine("gpt2", CFG, params, ServeConfig(**kw),
                       telemetry=tel)


def oracle(params, req):
    """Batch-at-a-time generate() with a contiguous cache — the serve
    loop's parity target (same convention as tests/test_serve.py)."""
    ids = jnp.asarray([req.prompt], jnp.int32)
    cfg = SampleConfig(max_new_tokens=req.max_new_tokens, greedy=True,
                      eos_id=None, pad_id=0)
    return np.asarray(gpt2_generate(CFG, params, ids, jnp.ones_like(ids),
                                    cfg))[0].tolist()


def read_events(path):
    with open(path) as f:
        return [json.loads(l) for l in f.read().splitlines() if l.strip()]


def assert_terminal_accounting(recs, reqs, engine):
    """THE leak/accounting invariant: every request terminal, exactly
    one terminal `request` phase per id (matching its state), and the
    allocator holds zero pages. Round 21 extends it to REFCOUNTED
    pages: with every request terminal, every shared page's refcount
    must have returned to zero exactly once (`refcounts == {}` — a
    page still carrying a count is a leak, a count going negative
    raised at free time), and a prefix cache's registered/parked sets
    must agree with the allocator's."""
    terminal_phase = {"finished": "finish", "cancelled": "cancel",
                      "rejected": "reject", "timeout": "timeout",
                      "error": "error"}
    by_id = {}
    for r in recs:
        if r.get("event") == "request":
            by_id.setdefault(r["id"], []).append(r["phase"])
    for req in reqs:
        assert req.state in Request.TERMINAL, \
            f"req {req.id} non-terminal: {req.state}"
        assert not req.blocks, f"req {req.id} still holds pages"
        terms = [p for p in by_id.get(req.id, ())
                 if p in terminal_phase.values()]
        assert terms == [terminal_phase[req.state]], \
            f"req {req.id} ({req.state}): terminal phases {terms}"
    assert engine.alloc.in_use == 0, \
        f"allocator leaked {engine.alloc.in_use} pages"
    assert engine.alloc.refcounts == {}, \
        f"pages still refcounted: {engine.alloc.refcounts}"
    if engine.prefix is not None:
        engine.prefix.check_consistent()
    assert not engine.active and not engine.queue


# --------------------------- bounded admission -------------------------------

def test_queue_full_rejects_newest(params, tmp_path):
    eng = make_engine(params, tmp_path, num_slots=1, max_queue=2)
    a = eng.submit([1, 2, 3])
    eng.step()                                  # a -> active
    assert a.state == "active"
    q = [eng.submit([4, 5]), eng.submit([6, 7])]
    over = eng.submit([8, 9])                   # queue at cap: rejected
    assert over.state == "rejected" and over.reason == "queue_full"
    assert [r.state for r in q] == ["queued", "queued"]
    eng.cancel(a)
    for r in q:
        eng.cancel(r)
    eng.close()
    recs = read_events(eng.telemetry.path)
    ev = {(r["id"], r["phase"]): r for r in recs
          if r["event"] == "request"}
    assert ev[(over.id, "reject")]["reason"] == "queue_full"
    assert_terminal_accounting(recs, [a, over] + q, eng)


def test_shed_policy_drops_nearest_deadline(params, tmp_path):
    """shed_policy="deadline": a full queue sheds the queued request
    closest to blowing its own deadline, not the newest arrival;
    with no deadline-carrying queued request it degrades to
    reject-newest."""
    eng = make_engine(params, tmp_path, num_slots=1, max_queue=2,
                      shed_policy="deadline")
    a = eng.submit([1, 2, 3])
    eng.step()
    urgent = eng.submit([4, 5], deadline_ms=50.0)
    lax = eng.submit([5, 6], deadline_ms=60_000.0)
    newcomer = eng.submit([6, 7])               # sheds `urgent`
    assert urgent.state == "rejected" and urgent.reason == "shed"
    assert newcomer.state == "queued" and lax.state == "queued"
    # no deadline-carrying queued request left that is sheddable ->
    # the next over-limit arrival... `lax` still has one; drop it too
    newcomer2 = eng.submit([7, 8])
    assert lax.state == "rejected" and lax.reason == "shed"
    # queue now holds only deadline-less requests: reject the newest
    newcomer3 = eng.submit([8, 9])
    assert newcomer3.state == "rejected" and \
        newcomer3.reason == "queue_full"
    eng.cancel(a)
    eng.cancel(newcomer)
    eng.cancel(newcomer2)
    eng.close()
    assert_terminal_accounting(
        read_events(eng.telemetry.path),
        [a, urgent, lax, newcomer, newcomer2, newcomer3], eng)


# --------------------------- deadlines ---------------------------------------

def test_queued_deadline_times_out_without_prefill(params, tmp_path):
    """A queued request past its deadline is dropped BEFORE admission:
    no prefill trace, no pages, partial-output-free timeout."""
    eng = make_engine(params, tmp_path)
    req = eng.submit([1, 2, 3], deadline_ms=1.0)
    time.sleep(0.01)
    eng.step()
    assert req.state == "timeout" and req.reason == "deadline"
    assert req.tokens == [] and eng.trace_counts["prefill"] == 0
    eng.close()
    assert_terminal_accounting(read_events(eng.telemetry.path),
                               [req], eng)


def test_active_deadline_returns_partial_output(params, tmp_path):
    """An active request past its deadline is cancelled at the next
    step boundary: partial tokens kept, slot + pages released, the
    OTHER slot's request unaffected and still oracle-equal."""
    eng = make_engine(params, tmp_path)
    rng = np.random.default_rng(3)
    doomed = eng.submit(list(rng.integers(1, 200, 5)), max_new_tokens=8,
                        deadline_ms=60_000.0)
    healthy = eng.submit(list(rng.integers(1, 200, 7)), max_new_tokens=8)
    eng.step()                      # admit (first token) + one decode
    eng.step()                      # ...folded in behind the second
    assert doomed.state == "active" and len(doomed.tokens) == 2
    # force the deadline into the past at a known boundary — the
    # wall-clock version of "the budget ran out mid-generation",
    # without a timing-dependent sleep
    doomed.deadline_t = time.perf_counter() - 1e-3
    done = eng.step()
    assert doomed in done
    assert doomed.state == "timeout" and doomed.reason == "deadline"
    partial = list(doomed.tokens)
    assert len(partial) == 3        # output up to the boundary survives
    assert partial == oracle(params, doomed)[:3]
    eng.drain()
    assert healthy.state == "finished"
    assert healthy.tokens == oracle(params, healthy)
    eng.close()
    assert_terminal_accounting(read_events(eng.telemetry.path),
                               [doomed, healthy], eng)


# --------------------------- crash containment -------------------------------

def test_step_error_fails_active_queue_survives(params, tmp_path):
    """The containment acceptance: an exception out of the decode-step
    dispatch fails ONLY the in-flight requests (phase=error, reason =
    the exception type), the pool resets leak-free, the queue survives,
    and serving resumes — queued survivors finish oracle-equal with
    ZERO new traces."""
    eng = make_engine(params, tmp_path, num_slots=2, stats_every=3)
    rng = np.random.default_rng(11)
    warm = eng.submit(list(rng.integers(1, 200, 4)), max_new_tokens=2)
    eng.drain()
    traces0 = eng.total_traces()
    reqs = [eng.submit(list(rng.integers(1, 200, int(n))),
                       max_new_tokens=6) for n in (5, 9, 3, 7)]
    eng.step()                      # admit the first two
    inflight = [r for r in reqs if r.state == "active"]
    queued = [r for r in reqs if r.state == "queued"]
    assert len(inflight) == 2 and len(queued) == 2

    class BoomError(RuntimeError):
        pass

    def boom(step):
        eng.step_hook = None        # one-shot
        raise BoomError("injected")
    eng.step_hook = boom
    done = eng.step()
    assert sorted(r.id for r in done) == sorted(r.id for r in inflight)
    for r in inflight:
        assert r.state == "error" and r.reason == "BoomError"
        assert len(r.tokens) >= 1   # partial output survives the crash
    assert eng.alloc.in_use == 0    # pool reset clean
    assert [r.state for r in queued] == ["queued", "queued"]
    # serving resumes: the survivors prefill into the reset pool and
    # stay oracle-equal — the fault never reached the compiled programs
    eng.drain()
    for r in queued:
        assert r.state == "finished"
        assert r.tokens == oracle(params, r), f"req {r.id}"
    assert eng.total_traces() - traces0 == 0
    eng.close()
    recs = read_events(eng.telemetry.path)
    for rec in recs:
        assert validate_event(rec) is None, (rec, validate_event(rec))
    assert any(r.get("event") == "serve_stats" for r in recs)
    assert_terminal_accounting(recs, [warm] + reqs, eng)


@pytest.mark.parametrize("fault", ["cancel", "deadline", "step_error"])
def test_fault_while_a_step_is_in_flight(params, tmp_path, fault):
    """The loop runs one step ahead (PR 34), so a cancel, a deadline and
    a dispatch failure each arrive while a decode step is queued on the
    device: the victim keeps exactly the tokens folded in (a prefix of
    its oracle), the in-flight step's other rows lose nothing, the
    allocator balances, and whoever survives — the neighbour, or after
    containment rebuilt the pools the queue — stays oracle-equal."""
    eng = make_engine(params, tmp_path, num_slots=2, block_T=4)
    rng = np.random.default_rng(17)
    reqs = [eng.submit(list(rng.integers(1, 200, int(n))),
                       max_new_tokens=8) for n in (5, 9, 3, 7)]
    for _ in range(3):
        eng.step()
    victim, neighbour = reqs[0], reqs[1]
    assert eng._inflight is not None and not eng._stale
    assert victim.state == neighbour.state == "active"
    seen = len(victim.tokens)
    if fault == "cancel":
        eng.cancel(victim)
        assert len(victim.tokens) == seen and eng._inflight is not None
        done = eng.step()
    elif fault == "deadline":
        victim.deadline_t = time.perf_counter() - 1e-3
        done = eng.step()
        assert victim in done and victim.reason == "deadline"
    else:
        def boom(step):
            eng.step_hook = None    # one-shot
            raise RuntimeError("injected")
        eng.step_hook = boom
        done = eng.step()
        assert victim in done and neighbour in done
        assert neighbour.state == "error" and eng._inflight is None
    want = {"cancel": "cancelled", "deadline": "timeout",
            "step_error": "error"}[fault]
    assert victim.state == want and not victim.blocks
    assert victim.tokens == oracle(params, victim)[:len(victim.tokens)]
    assert seen <= len(victim.tokens) <= seen + 1
    eng.drain()
    for r in reqs:
        if r.state == "finished":
            assert r.tokens == oracle(params, r), f"req {r.id}"
        else:
            assert r.tokens == oracle(params, r)[:len(r.tokens)]
    assert [r.state for r in reqs[2:]] == ["finished", "finished"]
    assert (neighbour.state == "finished") == (fault != "step_error")
    h = eng.health()
    assert eng._inflight is None and h["eos_discarded_tokens"] == 0
    assert h["steps_carried"] + h["steps_uploaded"] == h["decode_steps"]
    assert eng.trace_counts["decode_step"] == 1
    eng.close()
    assert_terminal_accounting(read_events(eng.telemetry.path), reqs, eng)


def test_on_step_error_raise_policy(params, tmp_path):
    """on_step_error="raise": containment still runs (actives failed,
    pool clean) but the exception propagates to the caller."""
    eng = make_engine(params, tmp_path, on_step_error="raise")
    req = eng.submit([1, 2, 3, 4])
    eng.step()

    def boom(step):
        eng.step_hook = None
        raise ValueError("injected dispatch failure")
    eng.step_hook = boom
    with pytest.raises(ValueError, match="injected"):
        eng.step()
    assert req.state == "error" and req.reason == "ValueError"
    assert eng.alloc.in_use == 0
    # the engine object is still serviceable after the raise
    ok = eng.submit([5, 6, 7])
    eng.drain()
    assert ok.state == "finished" and ok.tokens == oracle(params, ok)
    eng.close()
    assert_terminal_accounting(read_events(eng.telemetry.path),
                               [req, ok], eng)


def test_prefill_error_fails_one_request_not_neighbors(params, tmp_path):
    """A failed PREFILL kills one request; the other slot's in-flight
    request keeps its cache (no pool reset on the admission path) and
    finishes oracle-equal."""
    eng = make_engine(params, tmp_path)
    rng = np.random.default_rng(7)
    healthy = eng.submit(list(rng.integers(1, 200, 6)), max_new_tokens=6)
    eng.step()                      # healthy active
    victim = eng.submit(list(rng.integers(1, 200, 4)), max_new_tokens=6)
    real_prefill, calls = eng._prefill, []

    def flaky_prefill(*a, **k):
        if not calls:
            calls.append(1)
            raise RuntimeError("prefill died")
        return real_prefill(*a, **k)
    eng._prefill = flaky_prefill
    done = eng.step()
    assert victim in done
    assert victim.state == "error" and victim.reason == "RuntimeError"
    eng.drain()
    assert healthy.state == "finished"
    assert healthy.tokens == oracle(params, healthy)
    eng.close()
    assert_terminal_accounting(read_events(eng.telemetry.path),
                               [healthy, victim], eng)


# --------------------------- graceful drain ----------------------------------

def test_sigterm_drain(params, tmp_path):
    """SIGTERM at a step boundary: admissions stop, the queued
    remainder rejects with reason=shutdown, in-flight requests FINISH
    (oracle-equal), and the stream ends run_end{exit=preempted,
    reason=preempted} with a preempt event marking the drain."""
    eng = make_engine(params, tmp_path, num_slots=2)
    eng.install_preemption()
    rng = np.random.default_rng(5)
    reqs = [eng.submit(list(rng.integers(1, 200, int(n))),
                       max_new_tokens=6) for n in (4, 8, 5, 3)]
    eng.step()
    inflight = [r for r in reqs if r.state == "active"]
    queued = [r for r in reqs if r.state == "queued"]
    assert len(inflight) == 2 and len(queued) == 2
    os.kill(os.getpid(), signal.SIGTERM)
    time.sleep(0.01)                # let the handler run
    assert eng.guard.triggered
    eng.drain()
    assert eng.draining
    for r in queued:
        assert r.state == "rejected" and r.reason == "shutdown"
    for r in inflight:
        assert r.state == "finished"
        assert r.tokens == oracle(params, r), f"req {r.id}"
    # post-drain submissions are turned away, not queued into a corpse
    late = eng.submit([9, 9, 9])
    assert late.state == "rejected" and late.reason == "shutdown"
    eng.close()
    recs = read_events(eng.telemetry.path)
    for rec in recs:
        assert validate_event(rec) is None, (rec, validate_event(rec))
    assert any(r["event"] == "preempt" for r in recs)
    end = recs[-1]
    assert end["event"] == "run_end" and end["exit"] == "preempted" \
        and end["reason"] == "preempted"
    assert_terminal_accounting(recs, reqs + [late], eng)


def test_second_signal_cancels_inflight(params, tmp_path):
    """The escalation contract: a second SIGTERM mid-drain raises
    KeyboardInterrupt (the operator outranks a slow drain) — the
    caller cancels in-flight and still gets a terminal-complete,
    schema-valid stream."""
    eng = make_engine(params, tmp_path)
    guard = eng.install_preemption()
    req = eng.submit([1, 2, 3, 4], max_new_tokens=8)
    eng.step()
    os.kill(os.getpid(), signal.SIGTERM)
    time.sleep(0.01)
    assert guard.triggered
    with pytest.raises(KeyboardInterrupt):
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.2)
    for r in list(eng.active):
        eng.cancel(r)
    assert req.state == "cancelled" and len(req.tokens) >= 1
    eng.close()
    recs = read_events(eng.telemetry.path)
    assert recs[-1]["exit"] == "preempted"
    assert_terminal_accounting(recs, [req], eng)


# --------------------------- lifecycle hygiene -------------------------------

def test_submit_after_close_raises_and_close_idempotent(params):
    eng = make_engine(params)
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit([1, 2])
    eng.close()                     # second close is a no-op


def test_exit_unwinds_as_error_with_exception_name(params, tmp_path):
    """__exit__ on an exception records run_end{exit=error,
    reason=<type>} — not a clean run_end wearing the type as exit."""
    eng = make_engine(params, tmp_path)
    with pytest.raises(ValueError):
        with eng:
            raise ValueError("user code blew up")
    recs = read_events(eng.telemetry.path)
    end = recs[-1]
    assert end["event"] == "run_end"
    assert end["exit"] == "error" and end["reason"] == "ValueError"
    # and the clean path still records exit=ok
    eng2 = make_engine(params, tmp_path, stream="r2.jsonl")
    with eng2:
        pass
    assert read_events(eng2.telemetry.path)[-1]["exit"] == "ok"


def test_health_and_serve_stats_cadence(params, tmp_path):
    eng = make_engine(params, tmp_path, stats_every=2)
    h = eng.health()
    assert h["queue_depth"] == 0 and h["active"] == 0
    assert h["blocks_in_use"] == 0 and h["p95_step_ms"] is None
    eng.submit([1, 2, 3], max_new_tokens=6)
    eng.step()
    h = eng.health()
    assert h["active"] == 1 and h["occupancy"] == 0.5
    assert h["blocks_in_use"] >= 1
    eng.drain()
    eng.close()
    recs = read_events(eng.telemetry.path)
    stats = [r for r in recs if r["event"] == "serve_stats"]
    # max_new=6 = prefill token + 5 decode steps; cadence 2 -> 2, 4
    assert [s["step"] for s in stats] == [2, 4]
    for s in stats:
        assert validate_event(s) is None
        assert s["p95_step_ms"] > 0
        assert s["active"] == 1     # mid-flight at both snapshots
    # the request finishes at decode step 5, after the last snapshot —
    # the cumulative counter lives in health()
    assert eng.health()["counts"]["finished"] == 1


# --------------------------- watchdog over the serve loop --------------------

def test_watchdog_fires_on_injected_hang(params, tmp_path):
    """--inject hang: a wedged step dispatch trips the engine-level
    HangWatchdog (a `hang` event lands in the SAME stream) while the
    run still completes — report-only mode, serve-side mirror of the
    r09 injected-stall test."""
    import serve_bench
    stream = str(tmp_path / "wd.jsonl")
    wd = HangWatchdog(mult=2.0, min_deadline_s=0.25, grace_s=5.0,
                      stacks_file=str(tmp_path / "stacks.txt"),
                      abort=False)
    eng = ServeEngine("gpt2", CFG, params,
                      ServeConfig(num_slots=2, block_T=8, num_blocks=32,
                                  max_prompt=16, max_new_tokens=8),
                      telemetry=Telemetry(stream), watchdog=wd)
    wd.on_hang = lambda p: eng.telemetry.emit("hang", **p)
    wd.start()
    try:
        warm = eng.submit([1, 2, 3], max_new_tokens=2)
        eng.drain()
        # the third dispatch of the request: the loop runs one step
        # ahead, so by then two of its steps have been folded in and
        # the watchdog's median is a warm step's, not the compile's
        serve_bench.install_inject(
            eng, f"hang:{eng.decode_steps + 3}:1.2")
        req = eng.submit([4, 5, 6, 7], max_new_tokens=6)
        eng.drain()
    finally:
        wd.stop()
    assert wd.fired >= 1
    assert req.state == "finished" and req.tokens == oracle(params, req)
    eng.close()
    recs = read_events(eng.telemetry.path)
    hangs = [r for r in recs if r["event"] == "hang"]
    assert hangs and hangs[0]["action"] == "continue"
    for rec in recs:
        assert validate_event(rec) is None, (rec, validate_event(rec))
    assert_terminal_accounting(recs, [warm, req], eng)


def test_write_failure_escalates_to_full_containment(params, tmp_path):
    """The prompt-page WRITE donates the pools (non-CPU backends): a
    failure there may have consumed every resident's cache, so —
    unlike a failed prefill — containment must escalate: the victim
    AND the in-flight requests fail, the pools rebuild, and serving
    resumes clean (uniform semantics on every backend, because the CPU
    tests are the only ones that run in CI)."""
    eng = make_engine(params, tmp_path)
    rng = np.random.default_rng(13)
    resident = eng.submit(list(rng.integers(1, 200, 6)), max_new_tokens=8)
    eng.step()                      # resident active, cache populated
    victim = eng.submit(list(rng.integers(1, 200, 4)), max_new_tokens=6)
    real_write, calls = eng._write, []

    def flaky_write(*a, **k):
        if not calls:
            calls.append(1)
            raise RuntimeError("write died post-donation")
        return real_write(*a, **k)
    eng._write = flaky_write
    eng.step()
    assert victim.state == "error" and victim.reason == "RuntimeError"
    assert resident.state == "error"    # cache suspect -> failed too
    assert eng.alloc.in_use == 0 and not eng._pools_at_risk
    fresh = eng.submit(list(rng.integers(1, 200, 5)), max_new_tokens=6)
    eng.drain()
    assert fresh.state == "finished"
    assert fresh.tokens == oracle(params, fresh)
    eng.close()
    assert_terminal_accounting(read_events(eng.telemetry.path),
                               [resident, victim, fresh], eng)


def test_cache_on_fault_matrix_refcounts_return_to_zero(params, tmp_path):
    """Round 21: the r14 fault matrix re-run with shared-prefix reuse
    and chunked admission engaged — step-error containment while two
    residents SHARE refcounted prefix pages, a cancel mid-chunk, and a
    queued deadline timeout. After every path: every shared page's
    refcount back to zero exactly once (refcounts == {}), the prefix
    cache consistent with the allocator, survivors oracle-equal, and
    zero new traces once every bucket + the COW program are warm."""
    eng = make_engine(params, tmp_path, num_slots=2, num_blocks=64,
                      prefix_cache=True, max_prompt_chunked=40)
    rng = np.random.default_rng(21)
    common = list(rng.integers(1, 200, 16))      # two full pages
    all_reqs = []

    def run(prompt, max_new=2, **kw):
        r = eng.submit(prompt, max_new_tokens=max_new, **kw)
        eng.drain()
        all_reqs.append(r)
        return r

    # warm EVERY executable: classic prefill+write+step, both chunk
    # buckets (8, 16), and the COW full-hit re-feed
    run(common[:8])                              # classic one-shot
    run(common + list(rng.integers(1, 200, 10)))  # chunked, bucket 16
    run(common + list(rng.integers(1, 200, 5)))   # prefix hit, bucket 8
    run(common)                                   # full hit -> COW
    assert eng.cow_copies >= 1
    traces0 = eng.total_traces()

    # --- step_error containment while prefix pages are SHARED --------
    rA = eng.submit(common + list(rng.integers(1, 200, 8)),
                    max_new_tokens=6)
    rB = eng.submit(common + list(rng.integers(1, 200, 8)),
                    max_new_tokens=6)
    all_reqs += [rA, rB]
    eng.step()                      # admit both; rA's final chunk
    eng.step()                      # rB's final chunk; rA decodes
    assert not rA.prefilling and not rB.prefilling
    shared = rA.blocks[:2]
    assert shared == rB.blocks[:2], "prefix pages not shared"
    assert all(eng.alloc.refcounts[b] == 2 for b in shared)

    class BoomError(RuntimeError):
        pass

    def boom(step):
        eng.step_hook = None
        raise BoomError("injected")
    eng.step_hook = boom
    done = eng.step()
    assert sorted(r.id for r in done) == sorted([rA.id, rB.id])
    for r in (rA, rB):
        assert r.state == "error" and r.reason == "BoomError"
    # containment rebuilt the pools: refcounts cleared ONCE, and the
    # cache flushed alongside (its contents no longer exist)
    assert eng.alloc.in_use == 0 and eng.alloc.refcounts == {}
    assert eng.alloc.parked_blocks == 0
    eng.prefix.check_consistent()

    # --- cancel mid-chunk --------------------------------------------
    midway = eng.submit(list(rng.integers(1, 200, 35)),
                        max_new_tokens=6)
    all_reqs.append(midway)
    eng.step()                      # first 16-wide chunk only
    assert midway.state == "active" and midway.prefilling
    assert 0 < midway.prefill_pos < len(midway.prompt)
    eng.cancel(midway)
    assert midway.state == "cancelled" and not midway.blocks
    assert eng.alloc.in_use == 0 and eng.alloc.refcounts == {}
    eng.prefix.check_consistent()

    # --- queued deadline timeout with the cache engaged --------------
    late = eng.submit(common + [3, 3, 3], deadline_ms=1.0)
    all_reqs.append(late)
    time.sleep(0.01)
    eng.step()
    assert late.state == "timeout" and late.reason == "deadline"
    assert eng.alloc.refcounts == {}

    # serving resumes post-flush: a fresh chunked admission finishes
    # oracle-equal on the SAME executables (no retrace paid anywhere)
    fresh = run(common + list(rng.integers(1, 200, 9)), max_new=6)
    assert fresh.state == "finished"
    assert fresh.tokens == oracle(params, fresh)
    assert eng.total_traces() - traces0 == 0, dict(eng.trace_counts)
    eng.close()
    recs = read_events(eng.telemetry.path)
    for rec in recs:
        assert validate_event(rec) is None, (rec, validate_event(rec))
    assert_terminal_accounting(recs, all_reqs, eng)


def test_inject_never_fired_fails_the_harness(tmp_path):
    """An armed --inject fault that never fires (step out of the run's
    reach) must FAIL the harness run — CI keys on the exit status, and
    a no-op injection proving nothing must not read as a pass."""
    import serve_bench
    with pytest.raises(SystemExit, match="never fired"):
        serve_bench.run_rows(
            "tiny-gpt2", [200.0], n_requests=2, adapters=0, num_slots=2,
            block_T=8, num_blocks=32, max_prompt=16, max_new=4,
            dtype="float32", seed=0, prompt_lo=2,
            inject="step_error:100000", drain=False)


def test_run_load_census_includes_submit_time_terminals(params, tmp_path):
    """run_load's returned list must cover submit-time terminals too:
    queue_full rejects and SHED VICTIMS reach their terminal state
    inside a LATER request's submit() call and never come back from
    step() — the bench row's census has to union submitted with
    step-returned or it undercounts exactly the failures the harness
    exists to measure."""
    import serve_bench
    eng = make_engine(params, tmp_path, num_slots=1, max_queue=2,
                      shed_policy="deadline")
    done, _ = serve_bench.run_load(eng, [], rate=1e6, n_requests=8,
                                   seed=2, prompt_lo=2, prompt_hi=6,
                                   max_new=4, deadline_ms=60_000.0)
    assert len(done) == 8                    # every request accounted for
    assert all(r.done for r in done)
    by_state = {}
    for r in done:
        by_state[r.state] = by_state.get(r.state, 0) + 1
    # 8 near-simultaneous arrivals into 1 slot + a 2-deep queue MUST
    # overflow; with every request carrying a deadline the victims are
    # shed (reason=shed), not reject-newest
    assert by_state.get("rejected", 0) >= 1
    assert any(r.reason == "shed" for r in done)
    assert by_state.get("finished", 0) >= 1
    assert sum(by_state.values()) == 8
    row = serve_bench.row_from("census", eng, done, 1.0, 1e6, 0)
    assert row["terminal"]["rejected"] == by_state.get("rejected", 0)
    eng.close()
    assert_terminal_accounting(read_events(eng.telemetry.path), done, eng)


# --------------------------- the merged fault e2e ----------------------------

def test_injected_fault_poisson_e2e(params, tmp_path):
    """THE acceptance e2e: seeded Poisson open-loop load through the
    real engine (tools/serve_bench.py load generator) with an injected
    step_error, a bounded queue, per-request deadlines, and a SIGTERM
    drain — ONE stream, asserted schema-valid end to end, surviving
    greedy outputs oracle-identical, zero post-warmup retraces, and
    terminal accounting across every fault path."""
    import serve_bench
    stream = str(tmp_path / "e2e.jsonl")
    eng = ServeEngine(
        "gpt2", CFG, params,
        # max_queue ABOVE the offered burst: bounded admission is
        # configured (the production shape) but the cap/shed behavior
        # itself is pinned by its own deterministic tests — a
        # timing-dependent shed here would make the terminal census
        # nondeterministic
        ServeConfig(num_slots=2, block_T=8, num_blocks=32, max_prompt=16,
                    max_new_tokens=8, max_queue=16,
                    shed_policy="deadline", stats_every=5),
        telemetry=Telemetry(stream))
    eng.install_preemption()
    all_reqs = []
    # warmup outside the measured window (r11 convention)
    warm = eng.submit([1, 1, 1], max_new_tokens=2)
    eng.drain()
    all_reqs.append(warm)
    traces0 = eng.total_traces()

    # phase A: Poisson load with a step_error injected mid-flight —
    # generous deadline so only the injection (never CI timing) decides
    # who fails
    serve_bench.install_inject(eng, f"step_error:{eng.decode_steps + 2}")
    done, _ = serve_bench.run_load(eng, [], rate=500.0, n_requests=10,
                                   seed=4, prompt_lo=2, prompt_hi=9,
                                   max_new=5, deadline_ms=120_000.0)
    all_reqs.extend(done)
    assert len(done) == 10
    errored = [r for r in done if r.state == "error"]
    finished = [r for r in done if r.state == "finished"]
    assert errored, "the injection never fired"
    assert all(r.reason == "InjectedStepError" for r in errored)
    assert finished, "containment killed the queue too"
    for r in finished:
        assert r.tokens == oracle(params, r), f"req {r.id}"

    # phase B: a deterministic deadline blow (queued, never prefills)
    late = eng.submit([2, 2, 2], deadline_ms=1.0)
    time.sleep(0.01)
    prefills = eng.trace_counts["prefill"]
    eng.step()
    assert late.state == "timeout" and late.reason == "deadline"
    assert eng.trace_counts["prefill"] == prefills
    all_reqs.append(late)

    # phase C: SIGTERM drain — in-flight finish, queue rejects
    rng = np.random.default_rng(9)
    tail = [eng.submit(list(rng.integers(1, 200, int(n))),
                       max_new_tokens=5) for n in (4, 6, 3, 7)]
    all_reqs.extend(tail)
    eng.step()
    os.kill(os.getpid(), signal.SIGTERM)
    time.sleep(0.01)
    eng.drain()
    survivors = [r for r in tail if r.state == "finished"]
    shut = [r for r in tail if r.state == "rejected"]
    assert survivors and shut
    assert all(r.reason == "shutdown" for r in shut)
    for r in survivors:
        assert r.tokens == oracle(params, r), f"req {r.id}"

    # the compile-stability invariant held across EVERY fault path
    assert eng.total_traces() - traces0 <= 2
    assert eng.total_traces() - traces0 == 0    # the design target
    eng.close()

    recs = read_events(stream)
    for rec in recs:
        assert validate_event(rec) is None, (rec, validate_event(rec))
    assert recs[0]["event"] == "run_start"
    end = recs[-1]
    assert end["event"] == "run_end" and end["exit"] == "preempted" \
        and end["reason"] == "preempted"
    assert any(r["event"] == "preempt" for r in recs)
    assert any(r["event"] == "serve_stats" for r in recs)
    assert_terminal_accounting(recs, all_reqs, eng)

    # the report renders the failure-mode rates from the same stream
    import telemetry_report
    s = telemetry_report.summarize(recs)
    rq = s["requests"]
    census = lambda st: sum(1 for r in all_reqs if r.state == st)
    assert rq["errors"] == census("error") == len(errored)
    assert rq["rejected"] == census("rejected") == len(shut)
    assert rq["timeout"] == census("timeout") == 1
    assert rq["error_rate"] > 0 and rq["reject_rate"] > 0 \
        and rq["timeout_rate"] > 0
    assert rq["fail_reasons"]["shutdown"] == len(shut)
    assert rq["fail_reasons"]["deadline"] == 1
    assert rq["fail_reasons"]["InjectedStepError"] == len(errored)
    assert s["serve"]["snapshots"] >= 1
    assert s["serve"]["counts"]["error"] == len(errored)
    assert telemetry_report.main([stream]) == 0
