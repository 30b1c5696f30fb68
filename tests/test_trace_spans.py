"""Program spans on the device trace's clock (core/trace.py, PR 24).

`Tracer.span` is one primitive with two sinks: the opt-in telemetry
JSONL (pinned in test_observability.py) and, always, a
`jax.profiler.TraceAnnotation` — so a profiler session holds the
program's spans in its `.xplane.pb` host plane. The host plane is
recorded on the CPU backend as well, so everything here runs under a
CPU profiler session of a second or two and is read back with the
benchmark's own loader (benchmark/harness/xplane.load)."""

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)
sys.path[:0] = [TESTS, os.path.join(REPO, "tools"),
                os.path.join(REPO, "benchmark", "tests")]

import test_span_readers as bench_cases  # noqa: E402  (benchmark/tests)
import test_turn_readers as turn_cases  # noqa: E402  (benchmark/tests)
from fixtures import write_tiny_gpt2_dir, write_wikitext_dir  # noqa: E402
from harness import span_readers, turn_readers, xplane  # noqa: E402  (benchmark/)

from mobilefinetuner_tpu.core.config import GPT2Config  # noqa: E402
from mobilefinetuner_tpu.core.telemetry import (Telemetry,  # noqa: E402
                                                validate_event)
from mobilefinetuner_tpu.core.trace import NULL_TRACER, Tracer  # noqa: E402
from mobilefinetuner_tpu.models import gpt2  # noqa: E402
from mobilefinetuner_tpu.serve import ServeConfig, ServeEngine  # noqa: E402


class profiled:
    """A CPU profiler session without the Python tracer (its frames
    would bury the spans); `.trace` is the loaded trace afterwards."""

    def __init__(self, path):
        self.path, self.trace = str(path), None

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.path, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        if exc[0] is None:
            self.trace = xplane.load(xplane.find_xplane(self.path))


def lines_with(trace, name):
    """Host-plane lines (one per thread) that hold a span `name`."""
    return [l for p in trace.planes if p.name.startswith("/host:")
            for l in p.lines if any(e.name == name for e in l.events)]


def inside(inner, outer) -> bool:
    return outer.start <= inner.start \
        and inner.start + inner.dur <= outer.start + outer.dur


# --------------------------- the primitive -----------------------------------

def test_nested_spans_land_in_the_profilers_trace(tmp_path):
    """Under a live session a span nested in another is in the xplane
    under both names, on one thread's line, the inner inside the outer
    — with NO JSONL sink at all."""
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(8)).block_until_ready()
    with profiled(tmp_path) as prof:
        with NULL_TRACER.span("t24.outer", "loop"):
            with NULL_TRACER.span("t24.inner", "loop", step=3):
                f(jnp.ones(8)).block_until_ready()
    (line,) = lines_with(prof.trace, "t24.outer")
    (outer,) = [e for e in line.events if e.name == "t24.outer"]
    (inner,) = [e for e in line.events if e.name == "t24.inner"]
    assert inside(inner, outer) and inner.dur > 0


def test_span_without_session_or_sink_is_silent_and_sink_is_opt_in(
        tmp_path):
    """No session, no sink: no error, nothing emitted. `enabled` gates
    the JSONL sink only, and a span's record is the one `emit_span`
    writes — same fields in the same order."""
    seen = []
    off = Tracer(lambda **rec: seen.append(rec), enabled=False)
    assert not off.enabled and not NULL_TRACER.enabled
    for tr in (NULL_TRACER, off):
        with tr.span("quiet", "loop", step=1):
            pass
        with pytest.raises(ZeroDivisionError):
            with tr.span("raises", "loop"):
                1 / 0
    assert seen == []
    path = str(tmp_path / "t.jsonl")
    with Telemetry(path) as tel:
        on = Tracer(tel.emit)
        t0 = time.perf_counter()
        with on.span("work", "loop", step=3):
            time.sleep(0.002)
        on.emit_span("work", "loop", t0, 2.0, step=3)
    lexical, manual = [json.loads(l) for l in open(path)]
    assert validate_event(lexical) is None
    assert list(lexical) == list(manual)
    assert lexical["dur_ms"] >= 2.0 and lexical["step"] == 3
    assert abs(lexical["t0"] - t0) < 0.05


# --------------------------- the serve loop ----------------------------------

SERVE_CFG = dataclasses.replace(
    GPT2Config.tiny(vocab_size=211), n_embd=64, n_head=4, n_positions=64,
    n_layer=2, embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)


# what a steady turn enters, before and after the prefill was cut
STEADY_SPANS = {"serve.expire", "serve.page_append", "serve.upload",
                "serve.dispatch", "serve.sync", "serve.emit"}


def test_serve_step_is_split_into_phases_and_causes_no_retrace(tmp_path):
    """A tiny ServeEngine under a session: every decode turn is exactly
    one `serve.step` holding one `serve.dispatch`; a STEADY turn
    dispatches step k+1 and then holds the one `serve.sync` and
    `serve.emit` of step k, a stale turn folds k in before it touches
    slot state and dispatches, a fill turn has nothing to fold; every
    admission is a `serve.admit` holding its `serve.prefill`, which is
    cut into its two dispatches and its first-token wait (PR 35) while
    a steady turn enters the spans it entered before the cut; the spans
    cause no retrace; and the benchmark's readers find the host turn,
    the turnover and the carried share the engine counted."""
    params = gpt2.init_params(SERVE_CFG, jax.random.PRNGKey(0))
    eng = ServeEngine("gpt2", SERVE_CFG, params, ServeConfig(
        num_slots=2, block_T=8, num_blocks=16, max_prompt=16,
        max_new_tokens=8))
    try:
        rng = np.random.default_rng(0)
        prompt = lambda n: [int(t) for t in rng.integers(1, 200, n)]
        eng.submit(prompt(5), max_new_tokens=3)
        eng.drain()                      # every program has compiled
        traces0, steps0 = sum(eng.trace_counts.values()), eng.decode_steps
        carried0 = eng.steps_carried
        with profiled(tmp_path) as prof:
            for n in (4, 9, 6):
                eng.submit(prompt(n), max_new_tokens=6)
            eng.drain()
        assert sum(eng.trace_counts.values()) == traces0
        n_decode = eng.decode_steps - steps0
        n_carried = eng.steps_carried - carried0
    finally:
        eng.close()
    steps = span_readers.serve_steps(prof.trace)
    decode = [s for s in steps if "serve.dispatch" in s["children"]]
    assert len(decode) == n_decode > 0
    steady = []
    for s in decode:
        k = s["children"]
        (dispatch,) = k["serve.dispatch"]
        for name in ("serve.expire", "serve.page_append", "serve.upload"):
            assert len(k[name]) == 1, (name, k)
        assert k["serve.upload"][0][1] <= dispatch[0]
        syncs, emits = k.get("serve.sync", ()), k.get("serve.emit", ())
        assert len(syncs) == len(emits) <= 1, k
        if not syncs:
            continue                     # fill: nothing was in flight
        (sync,), (emit,) = syncs, emits
        assert sync[1] <= emit[0]
        if dispatch[1] <= sync[0]:       # dispatch of k+1, then sync of k
            steady.append(s)
        else:                            # stale: k folded in first
            assert emit[1] <= k["serve.expire"][0][0]
    assert len(steady) == n_carried >= 4
    admits = [a for s in steps for a in s["children"].get("serve.admit", ())]
    prefills = [p for s in steps
                for p in s["children"].get("serve.prefill", ())]
    assert len(admits) == len(prefills) == 3
    assert all(a[0] <= p[0] and p[1] <= a[1]
               for a, p in zip(admits, prefills))
    # every one-shot prefill: one of each child, in the order the code
    # runs them, disjoint, and nothing of the prefill left between them
    kids = [[c for s in steps for c in s["children"].get(name, ())]
            for name in turn_readers.PREFILL_CHILDREN]
    assert [len(k) for k in kids] == [3, 3, 3]
    for p, *cut in zip(prefills, *kids):
        assert p[0] <= cut[0][0] and cut[-1][1] <= p[1]
        assert all(a[1] <= b[0] for a, b in zip(cut, cut[1:]))
        assert sum(c[1] - c[0] for c in cut) >= 0.9 * (p[1] - p[0])
    for s in steady:
        assert set(s["children"]) == STEADY_SPANS
    obs = {"kind": "serve_engine"}
    turn = span_readers.serve_host_turn_ms(obs, prof.trace, {})
    assert turn is not None and turn > 0
    assert obs["notes"]["serve_turn"]["turns"] >= 2
    # the new readers on the same trace: what the engine counted as
    # carried is what the spans show as steady; no device plane here
    assert turn_readers.serve_carried_share(obs, prof.trace, {}) \
        == pytest.approx(100.0 * n_carried / n_decode)
    counts = obs["notes"]["serve_turnover"]["turns"]
    assert counts["steady"] == n_carried and counts["turnover"] >= 1
    assert counts["steady"] + counts["stale"] + counts["fill"] \
        + counts["turnover"] == n_decode
    assert turn_readers.serve_turnover_ms(obs, prof.trace, {}) \
        >= turn_readers.serve_first_token_wait_ms(obs, prof.trace, {}) > 0
    assert turn_readers.serve_turnover_idle_ms(obs, prof.trace, {}) is None


# --------------------------- the train loop ----------------------------------

PROF = (4, 8)       # --profile_start 4 --profile_steps 4
EVERY = 2           # --log_interval


@pytest.fixture(scope="module")
def train_run(tmp_path_factory):
    """One tiny LoRA run with a profiler window and a telemetry sink
    that takes 200 ms to write a `step_stats` line — the stall PR 22's
    finding 6(b) suspects. -> (events of the stream, loaded trace)."""
    from mobilefinetuner_tpu.cli.gpt2_lora_finetune import main
    d = tmp_path_factory.mktemp("t24train")
    gdir, wdir = str(d / "gpt2"), str(d / "wt2")
    write_tiny_gpt2_dir(gdir)
    write_wikitext_dir(wdir)
    stream, prof = str(d / "run.jsonl"), str(d / "prof")
    emit = Telemetry.emit

    def slow_emit(self, event, **fields):
        if event == "step_stats":
            time.sleep(0.2)
        return emit(self, event, **fields)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Telemetry, "emit", slow_emit)
        rc = main(["--pretrained_dir", gdir, "--data_dir", wdir,
                   "--steps", "14", "--batch_size", "2", "--seq_len", "32",
                   "--lora_out", str(d / "a.safetensors"),
                   "--telemetry_out", stream, "--log_interval", str(EVERY),
                   "--profile_dir", prof, "--profile_start", str(PROF[0]),
                   "--profile_steps", str(PROF[1] - PROF[0])])
    assert rc == 0
    with open(stream) as f:
        events = [json.loads(l) for l in f if l.strip()]
    return events, xplane.load(xplane.find_xplane(prof))


def test_flush_counter_closes_and_names_the_slow_phase(train_run):
    """Every `step_stats` after the first flush carries the drained
    period that followed the flush before it; its parts sum to it; and
    the sink's injected 200 ms are under `emit` and nowhere else."""
    events, _ = train_run
    flushes = [e for e in events if e["event"] == "step_stats"]
    assert [f["step"] for f in flushes] == list(range(2, 15, 2))
    assert all(validate_event(f) is None for f in flushes)
    assert flushes[0]["drained_ms"] is None
    assert flushes[0]["drained_parts_ms"] is None
    for f in flushes[1:]:
        parts = f["drained_parts_ms"]
        assert set(parts) == set(span_readers.FLUSH_PARTS)
        assert f["drained_ms"] > 0
        assert abs(sum(parts.values()) - f["drained_ms"]) \
            <= 0.05 * f["drained_ms"]
        assert parts["emit"] >= 200.0
    # the profiler's start and stop are host time too (in `loop_tail`):
    # the benchmark's reader leaves those flushes out, and so does this
    obs = {"kind": "train_cli", "log_interval": EVERY,
           "profile_steps": PROF, "flushes": flushes,
           "interval_s": [1.0] * len(flushes)}
    clean = [f for f, _ in span_readers.window_drains(obs)]
    assert [f["step"] for f in clean] == [4, 12, 14]
    for f in clean:
        others = {k: v for k, v in f["drained_parts_ms"].items()
                  if k != "emit"}
        assert max(others.values()) < 100.0, f
    assert span_readers.train_drained_max_ms(obs, None, {}) >= 200.0
    worst = obs["notes"]["train_drained_max"]["parts_ms"]
    assert max(worst, key=worst.get) == "emit"


def test_manual_profiler_window_is_named_in_the_stream(train_run):
    events, _ = train_run
    (cap,) = [e for e in events if e["event"] == "profile_capture"]
    assert validate_event(cap) is None
    assert cap["trigger"] == "manual" and cap["step"] == PROF[1]
    assert cap["steps"] == PROF[1] - PROF[0]
    assert xplane.find_xplane(cap["path"])


def test_train_loop_spans_are_in_the_trace_on_their_threads(train_run):
    """The profiler's window holds the loop's spans on the main
    thread's line, the flush's children inside it in order, and the
    producer's spans on the producer thread's own line."""
    _, trace = train_run
    (main,) = lines_with(trace, "train.dispatch")
    by = lambda name: sorted((e for e in main.events if e.name == name),
                             key=lambda e: e.start)
    # steps 4..7 were dispatched inside the window, flushed at 6 and 8
    assert len(by("train.dispatch")) == PROF[1] - PROF[0]
    assert len(by("train.input_wait")) >= PROF[1] - PROF[0] - 1
    flushes = by("train.flush")
    assert len(flushes) == 2
    order = ("train.flush.sync", "train.flush.sensors", "train.flush.emit",
             "train.flush.log")
    for k, fl in enumerate(flushes):
        kids = [by(name)[k] for name in order]
        assert all(inside(c, fl) for c in kids)
        assert all(a.start + a.dur <= b.start
                   for a, b in zip(kids, kids[1:]))
        assert kids[2].dur >= 200e6          # the slow sink, in ns
    produce = lines_with(trace, "prefetch.produce")
    assert produce and all(l is not main for l in produce)


# --------------------------- the benchmark's readers -------------------------

@pytest.mark.parametrize("reader,obs,trace,want,notes_ok",
                         [c[1:] for c in bench_cases.CASES],
                         ids=[c[0] for c in bench_cases.CASES])
def test_span_reader(reader, obs, trace, want, notes_ok):
    """benchmark/tests/test_span_readers.py's cases, in tier-1: hand-made
    traces and recorded flush records, the profiler-touched flushes left
    out, a missing span or field giving None."""
    bench_cases.check(reader, obs, trace, want, notes_ok)


@pytest.mark.parametrize("reader,obs,trace,want,notes_ok",
                         [c[1:] for c in turn_cases.CASES],
                         ids=[c[0] for c in turn_cases.CASES])
def test_turn_reader(reader, obs, trace, want, notes_ok):
    """benchmark/tests/test_turn_readers.py's cases, in tier-1: the four
    kinds of turn, a device plane whose gaps straddle span boundaries,
    a trace without the prefill's children, without a device plane,
    without a `serve.step`."""
    turn_cases.check(reader, obs, trace, want, notes_ok)


def listed_with_its_reader(name: str) -> None:
    """A per-layer metric of BENCHMARK.json, wherever it stands in the
    list: its file names a callable reader and agrees with the entry."""
    from harness import common
    entries = {m["name"]: m for m in common.benchmark_json()["per_layer"]}
    assert name in entries
    spec = common.named("layer_metrics", name)
    assert callable(common.resolve(spec["reader"]))
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert entries[name][key] == spec[key], (name, key)


@pytest.mark.parametrize("name", ["train_drained_share",
                                  "train_drained_max_ms",
                                  "serve_host_turn_ms_batch"])
def test_new_layer_metrics_are_appended_with_their_readers(name):
    """PR 24's three metrics (the benchmark's own hand-run test of this,
    `benchmark/tests/test_span_readers.py`, also asks that they be the
    LAST three, which no later metric can leave true)."""
    listed_with_its_reader(name)


@pytest.mark.parametrize("name", ["mfu_required_afmoe", "moe_share_of_step",
                                  "expert_gmm_roofline",
                                  "flash_mixed_roofline",
                                  "moe_load_max_over_mean"])
def test_the_expert_cell_s_layer_metrics_are_listed_with_their_readers(name):
    """PR 27's five, each reported in `trinity_lora_s8192` alone."""
    listed_with_its_reader(name)
    from harness import common
    assert common.named("layer_metrics", name)["workloads"] \
        == ["trinity_lora_s8192"]


@pytest.mark.parametrize("name", ["serve_turnover_ms_batch",
                                  "serve_first_token_wait_ms_batch",
                                  "serve_turnover_idle_ms_batch",
                                  "device_idle_turnover_share_batch",
                                  "serve_carried_share_batch"])
def test_the_turnover_s_layer_metrics_are_listed_with_their_readers(name):
    """PR 35's five, each reported in `md_batchgen_long` alone."""
    listed_with_its_reader(name)
    from harness import common
    spec = common.named("layer_metrics", name)
    assert spec["workloads"] == ["md_batchgen_long"]
    assert spec["moves"] == "serve_tokens_per_s"


# --------------------------- what the spans replaced -------------------------

def test_trace_export_no_longer_merges_a_device_trace(tmp_path, capsys):
    """The xplane carries the program's spans itself: the exporter's
    guessed-offset merge (`--profile`) is gone."""
    import trace_export
    stream = str(tmp_path / "s.jsonl")
    with Telemetry(stream) as tel:
        Tracer(tel.emit).emit_span("step", "phase", time.perf_counter(),
                                   1.0)
    with pytest.raises(SystemExit) as e:
        trace_export.main([stream, "--profile", str(tmp_path)])
    assert e.value.code == 2
    capsys.readouterr()
    assert trace_export.main([stream, "-o", str(tmp_path / "o.json")]) == 0
    assert not hasattr(trace_export, "merge_profiler")
