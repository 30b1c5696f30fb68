"""Round-17 live-observability contracts (DESIGN.md §22): span tracing
with monotonic stamps, the Perfetto exporter's goodput reconciliation,
anomaly-triggered profiler capture (budget/cooldown state machine + the
slow-step e2e), and the OpenMetrics /metrics endpoint — scraped LIVE
during a serve run with zero added retraces, and structurally pinned to
never touch jax (the zero-sync invariant extended to the scraper)."""

import json
import os
import re
import socket
import sys
import threading
import time
import urllib.request

import pytest

from mobilefinetuner_tpu.core.telemetry import (GoodputMeter, Telemetry,
                                                validate_event)
from mobilefinetuner_tpu.core.trace import AutoProfiler, Tracer

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from fixtures import write_tiny_gpt2_dir, write_wikitext_dir  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def read_events(path):
    with open(path) as f:
        return [json.loads(l) for l in f.read().splitlines() if l.strip()]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# --------------------------- span layer --------------------------------------

def test_tracer_emits_schema_valid_spans_and_noops_disabled(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with Telemetry(path) as tel:
        tr = Tracer(tel.emit)
        with tr.span("work", track="phase", step=3):
            time.sleep(0.005)
        tr.emit_span("write", "ckpt", time.perf_counter(), 12.5)
        off = Tracer(None)  # no sink: hard no-op
        assert not off.enabled
        off.emit_span("x", "y", 0.0, 1.0)
        with off.span("z"):
            pass
    recs = read_events(path)
    assert [r["event"] for r in recs] == ["span", "span"]
    for r in recs:
        assert validate_event(r) is None, (r, validate_event(r))
    assert recs[0]["name"] == "work" and recs[0]["track"] == "phase"
    assert recs[0]["dur_ms"] >= 4.0
    assert recs[0]["step"] == 3  # extras ride along


def test_envelope_t_mono_monotonic_and_optional_on_read(tmp_path):
    """Round-17 satellite: every record carries a monotonic t_mono next
    to wall t (span alignment never jitters across NTP steps) — and
    records WITHOUT it (pre-round-17 streams) still validate."""
    path = str(tmp_path / "t.jsonl")
    with Telemetry(path) as tel:
        tel.emit("eval", step=1, loss=1.0, ppl=2.0, tokens=3)
        tel.emit("eval", step=2, loss=1.0, ppl=2.0, tokens=3)
    recs = read_events(path)
    assert all(isinstance(r["t_mono"], float) for r in recs)
    assert recs[0]["t_mono"] < recs[1]["t_mono"]
    old = {k: v for k, v in recs[0].items() if k != "t_mono"}
    assert validate_event(old) is None          # old streams still parse
    assert validate_event({**recs[0], "t_mono": "x"}) is not None


def test_goodput_meter_spans_reconcile_with_buckets(tmp_path):
    """The acceptance identity, unit-sized: phase spans come from the
    SAME transitions that charge the buckets, so per-bucket span sums
    equal the summary's bucket totals."""
    path = str(tmp_path / "t.jsonl")
    with Telemetry(path) as tel:
        m = GoodputMeter(tracer=Tracer(tel.emit))
        time.sleep(0.01)
        m.enter("compile")
        time.sleep(0.01)
        m.enter("step")
        time.sleep(0.01)
        m.enter("input_wait")
        time.sleep(0.005)
        m.enter("step")
        time.sleep(0.01)
        s = m.summary()
    sums = {}
    for r in read_events(path):
        assert r["event"] == "span" and r["track"] == "phase"
        sums[r["name"]] = sums.get(r["name"], 0.0) + r["dur_ms"] / 1e3
    for bucket, total in sums.items():
        assert abs(total - s[f"{bucket}_s"]) < 5e-3, (bucket, total, s)
    # every nonzero bucket has spans backing it
    for k, v in s.items():
        if k.endswith("_s") and k != "total_s" and v > 0:
            assert k[:-2] in sums


def test_telemetry_observers_see_records_and_close_is_hard_noop(tmp_path):
    seen = []
    tel = Telemetry(str(tmp_path / "t.jsonl"))
    tel.add_observer(seen.append)
    tel.add_observer(lambda r: 1 / 0)  # a broken observer is swallowed
    rec = tel.emit("eval", step=1, loss=1.0, ppl=2.0, tokens=3)
    assert rec is not None and seen and seen[0]["event"] == "eval"
    tel.close()
    assert tel.emit("eval", step=2, loss=1.0, ppl=2.0, tokens=3) is None
    assert len(seen) == 1  # closed stream: observers muted too
    # observers work WITHOUT a file (metrics without --telemetry_out)
    seen2 = []
    tel2 = Telemetry("")
    tel2.add_observer(seen2.append)
    assert tel2.emit("eval", step=1, loss=1.0, ppl=2.0,
                     tokens=3) is None  # not durably written...
    assert seen2 and seen2[0]["step"] == 1  # ...but observed


# --------------------------- auto profiler -----------------------------------

def test_autoprofiler_budget_cooldown_state_machine(tmp_path):
    starts, stops = [], []
    now = {"t": 0.0}
    path = str(tmp_path / "t.jsonl")
    with Telemetry(path) as tel:
        ap = AutoProfiler(str(tmp_path / "prof"), sink=tel.emit,
                          steps=2, cooldown_s=100.0, budget=2,
                          profiler_start=starts.append,
                          profiler_stop=lambda: stops.append(1),
                          clock=lambda: now["t"])
        assert ap.trigger("slow_step", 5)
        assert ap.active and len(starts) == 1
        assert not ap.trigger("slow_step", 6)   # already capturing
        assert not ap.tick(6)                   # 1 of 2
        assert ap.tick(7)                       # capture completes
        assert ap.captured == 1 and ap.budget == 1 and stops
        assert not ap.trigger("divergence", 8)  # cooldown holds
        now["t"] = 200.0
        assert ap.trigger("divergence", 9)      # cooldown elapsed
        ap.tick(10)
        assert ap.tick(11) and ap.budget == 0
        now["t"] = 999.0
        assert not ap.trigger("slow_step", 12)  # budget exhausted
        # hang path: bounded immediate capture needs no ticks (budget
        # gone here, so it refuses — fresh instance proves the path)
        ap2 = AutoProfiler(str(tmp_path / "prof2"), sink=tel.emit,
                           steps=2, cooldown_s=0.0, budget=1,
                           profiler_start=starts.append,
                           profiler_stop=lambda: stops.append(1))
        assert ap2.capture_now("hang", 42, hold_s=0.0)
        assert ap2.captured == 1
    caps = [r for r in read_events(path)
            if r["event"] == "profile_capture"]
    assert [c["trigger"] for c in caps] == ["slow_step", "divergence",
                                           "hang"]
    for c in caps:
        assert validate_event(c) is None, (c, validate_event(c))
        assert os.path.isdir(c["path"])
    assert caps[0]["step"] == 7 and caps[0]["budget_left"] == 1


def test_autoprofiler_close_stops_open_capture(tmp_path):
    starts, stops = [], []
    ap = AutoProfiler(str(tmp_path), steps=5,
                      profiler_start=starts.append,
                      profiler_stop=lambda: stops.append(1))
    ap.trigger("slow_step", 1)
    ap.close()
    assert stops and not ap.active
    ap.close()  # idempotent
    assert len(stops) == 1


def test_autoprofiler_swallows_profiler_failures(tmp_path):
    def boom(*a):
        raise RuntimeError("no profiler here")
    ap = AutoProfiler(str(tmp_path), profiler_start=boom)
    assert not ap.trigger("slow_step", 1)   # failure contained
    assert not ap.active and ap.captured == 0


# --------------------------- OpenMetrics endpoint ----------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(-?[0-9.eE+-]+|\+Inf|NaN)$")


def parse_openmetrics(text):
    """Mini OpenMetrics parser: the scrape contract the test enforces —
    TYPE-declared families, well-formed samples, `# EOF` framing.
    Returns (families, samples)."""
    assert text.endswith("# EOF\n"), text[-60:]
    families, samples = {}, {}
    for line in text.splitlines():
        if line == "# EOF":
            break
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ")
            assert typ in ("counter", "gauge", "histogram"), line
            families[name] = typ
        elif line.startswith("#"):
            continue
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"malformed sample line: {line!r}"
            v = float("inf") if m.group(3) == "+Inf" else float(m.group(3))
            samples[m.group(1) + (m.group(2) or "")] = v
            # every sample belongs to a declared family
            base = m.group(1)
            for suffix in ("_bucket", "_count", "_sum", "_total"):
                if base.endswith(suffix):
                    base = base[: -len(suffix)]
                    break
            assert base in families, f"undeclared family for {line!r}"
    return families, samples


def test_registry_renders_parseable_openmetrics_from_representative():
    """Feed one of every schema event through the registry: the render
    must parse, with counters/gauges/histograms all represented."""
    from test_telemetry import REPRESENTATIVE
    from mobilefinetuner_tpu.core.metrics_http import MetricsRegistry
    reg = MetricsRegistry()
    for ev, fields in REPRESENTATIVE.items():
        reg.observe(dict(event=ev, seq=0, t=1.0, **fields))
    reg.observe({"event": "not_a_real_event", "x": 1})  # ignored, safe
    fams, samples = parse_openmetrics(reg.render())
    assert fams["mft_steps"] == "counter"
    assert samples["mft_steps_total"] == 1.0
    assert fams["mft_loss"] == "gauge" and samples["mft_loss"] == 3.2
    assert fams["mft_step_time_ms"] == "histogram"
    assert samples["mft_step_time_ms_count"] == 1.0
    assert samples['mft_requests_total{phase="finish"}'] == 1.0
    assert samples['mft_anomalies_total{kind="loss_spike"}'] == 1.0
    assert samples['mft_runs_total{exit="ok"}'] == 1.0
    assert samples["mft_goodput_productive_frac"] == 0.83
    h = reg.health()
    assert h["status"] == "ok" and h["events_observed"] >= len(
        REPRESENTATIVE)


def test_metrics_server_serves_metrics_and_healthz():
    from mobilefinetuner_tpu.core.metrics_http import (MetricsRegistry,
                                                       MetricsServer)
    reg = MetricsRegistry()
    reg.observe({"event": "step_stats", "step": 3, "loss": 2.0,
                 "step_time_ms": 12.0, "tok_s": 100.0})
    srv = MetricsServer(reg, port=0)  # ephemeral bind: the test path
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            assert r.status == 200
            assert "openmetrics-text" in r.headers["Content-Type"]
            fams, samples = parse_openmetrics(r.read().decode())
        assert samples["mft_loss"] == 2.0
        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            assert r.status == 200
            h = json.loads(r.read())
        assert h["status"] == "ok" and h["last_step"] == 3
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/nope", timeout=5)
        assert ei.value.code == 404
    finally:
        srv.close()


def test_healthz_503_when_engine_draining():
    """Round-22 satellite: a draining engine's /healthz flips to 503
    with draining:true in the body — the router's scrape keys replica
    eligibility on exactly this status code, so a draining replica
    stops taking placements without any router-side special casing.
    Fake engine: the contract is the (health_fn -> HTTP) mapping, not
    the engine."""
    from mobilefinetuner_tpu.core.metrics_http import (MetricsRegistry,
                                                       MetricsServer)
    state = {"draining": False}

    def health():
        return {"status": ("draining" if state["draining"] else "ok"),
                "draining": state["draining"], "queue_depth": 0}

    srv = MetricsServer(MetricsRegistry(), port=0, health_fn=health)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            assert r.status == 200
            assert json.loads(r.read())["draining"] is False
        state["draining"] = True
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz", timeout=5)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        # the full health payload rides the 503 — a scraper sees WHY
        assert body["status"] == "draining"
        assert body["draining"] is True
    finally:
        srv.close()


def test_serve_stats_cache_gauges_render_and_parse():
    """Round-22 satellite: the r21 cache vitals (prefix_hit_rate,
    cow_copies, blocks_in_use) surface as engine /metrics gauges, plus
    the derived pool-occupancy gauge — pinned through the mini parser
    so the router's affinity/least-loaded scoring has a stable scrape
    contract to read."""
    from mobilefinetuner_tpu.core.metrics_http import MetricsRegistry
    reg = MetricsRegistry()
    reg.observe({"event": "serve_stats", "seq": 0, "t": 1.0, "step": 5,
                 "queue_depth": 2, "active": 3, "occupancy": 0.75,
                 "free_blocks": 40, "p95_step_ms": 12.0, "finished": 7,
                 "cancelled": 0, "rejected": 1, "timeout": 0,
                 "error": 0, "prefix_hit_rate": 0.42, "cow_copies": 3,
                 "blocks_in_use": 24})
    fams, samples = parse_openmetrics(reg.render())
    for name in ("mft_serve_prefix_hit_rate", "mft_serve_cow_copies",
                 "mft_serve_blocks_in_use", "mft_serve_pool_occupancy"):
        assert fams[name] == "gauge", name
    assert samples["mft_serve_prefix_hit_rate"] == 0.42
    assert samples["mft_serve_cow_copies"] == 3.0
    assert samples["mft_serve_blocks_in_use"] == 24.0
    # 24 live of 64 allocatable (parked cache pages count as free)
    assert samples["mft_serve_pool_occupancy"] == 0.375
    # a cache-off snapshot (None vitals) must not poison the render
    reg.observe({"event": "serve_stats", "seq": 1, "t": 2.0, "step": 6,
                 "queue_depth": 0, "active": 0, "occupancy": 0.0,
                 "free_blocks": 64, "p95_step_ms": None, "finished": 7,
                 "cancelled": 0, "rejected": 1, "timeout": 0,
                 "error": 0, "prefix_hit_rate": None, "cow_copies": None,
                 "blocks_in_use": None})
    parse_openmetrics(reg.render())


def test_route_events_and_fleet_registry_helpers_render():
    """Round-22: `route` decisions land as a (policy, replica)-labeled
    counter + a scrape-age histogram, and the public set_gauge /
    observe_hist / inc helpers (the router's per-replica gauges and
    fleet SLO histograms) render through the same parser."""
    from mobilefinetuner_tpu.core.metrics_http import MetricsRegistry
    reg = MetricsRegistry()
    reg.observe({"event": "route", "seq": 0, "t": 1.0, "rid": 7,
                 "replica": 1, "policy": "affinity",
                 "adapter": "tenant0", "queue_depth": 2,
                 "occupancy": 0.5, "scrape_age_ms": 35.0,
                 "candidates": 2})
    reg.observe({"event": "route", "seq": 1, "t": 2.0, "rid": 8,
                 "replica": None, "policy": "reject", "adapter": None,
                 "queue_depth": None, "occupancy": None,
                 "scrape_age_ms": None, "candidates": 0})
    reg.set_gauge("mft_fleet_queue_depth", 3, replica="1")
    reg.set_gauge("mft_fleet_queue_depth", 1, replica="2")
    reg.observe_hist("mft_fleet_ttft_ms", 12.5)
    reg.inc("mft_fleet_requests", state="finished")
    fams, samples = parse_openmetrics(reg.render())
    assert fams["mft_route_decisions"] == "counter"
    assert samples[
        'mft_route_decisions_total{policy="affinity",replica="1"}'] == 1.0
    assert samples[
        'mft_route_decisions_total{policy="reject",replica="None"}'] == 1.0
    assert fams["mft_route_scrape_age_ms"] == "histogram"
    assert samples["mft_route_scrape_age_ms_count"] == 1.0
    assert samples['mft_fleet_queue_depth{replica="1"}'] == 3.0
    assert samples['mft_fleet_queue_depth{replica="2"}'] == 1.0
    assert samples["mft_fleet_ttft_ms_count"] == 1.0
    assert samples['mft_fleet_requests_total{state="finished"}'] == 1.0


def test_observability_modules_never_import_jax_at_module_level():
    """The zero-sync pin, structurally (migrated r19): graftlint's
    `no-jax-import` rule — metrics_http must not import jax AT ALL
    (policy "never"), trace.py/telemetry.py must keep module level
    jax-free (policy "toplevel"; AutoProfiler binds jax.profiler lazily
    inside the capture functions only). The rule is AST-based, so a
    lazy in-function import in metrics_http fails it too."""
    from mobilefinetuner_tpu.core.static_checks import (NO_JAX_MODULES,
                                                        run_lint)
    res = run_lint([os.path.join(REPO, "mobilefinetuner_tpu")],
                   rules=["no-jax-import"])
    bad = res.findings + res.suppressed  # this rule is never suppressed
    assert not bad, [f.render() for f in bad]
    # the policy table still covers the three observability modules
    assert {s.rsplit("/", 1)[-1] for s in NO_JAX_MODULES} >= {
        "metrics_http.py", "trace.py", "telemetry.py"}


# --------------------------- train e2e ---------------------------------------

@pytest.fixture(scope="module")
def gpt2_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("gpt2obs")
    write_tiny_gpt2_dir(str(d))
    return str(d)


@pytest.fixture(scope="module")
def wiki_dir(tmp_path_factory):
    return write_wikitext_dir(str(tmp_path_factory.mktemp("wt2obs")))


def test_train_e2e_spans_export_and_goodput_reconcile(gpt2_dir, wiki_dir,
                                                      tmp_path):
    """Acceptance: a traced tiny train run exports to ONE Perfetto
    trace whose phase-span sums reconcile with run_end's goodput
    buckets to <1% of total, with ckpt-writer and prefetch-producer
    tracks present; both report tools render the stream and the shared
    --format json serializer returns the same sections."""
    from mobilefinetuner_tpu.cli.gpt2_lora_finetune import main
    stream = str(tmp_path / "run.jsonl")
    rc = main(["--pretrained_dir", gpt2_dir, "--data_dir", wiki_dir,
               "--steps", "4", "--batch_size", "2", "--seq_len", "32",
               "--lora_out", str(tmp_path / "a.safetensors"),
               "--telemetry_out", stream, "--trace_spans", "1",
               "--save_every", "2", "--eval_interval", "4",
               "--eval_batches", "1", "--log_interval", "2"])
    assert rc == 0
    recs = read_events(stream)
    for r in recs:
        assert validate_event(r) is None, (r, validate_event(r))
    spans = [r for r in recs if r["event"] == "span"]
    tracks = {s["track"] for s in spans}
    assert "phase" in tracks and "ckpt" in tracks \
        and "prefetch" in tracks, tracks
    goodput = [r for r in recs if r["event"] == "run_end"][-1]["goodput"]

    import trace_export
    out = str(tmp_path / "trace.json")
    assert trace_export.main([stream, "-o", out]) == 0
    trace = json.load(open(out))
    assert trace["traceEvents"], "empty trace"
    rec_check = trace_export.phase_reconcile(trace, goodput)
    assert rec_check, "no phase spans reconciled"
    total = goodput["total_s"]
    for bucket, (span_s, bucket_s, delta) in rec_check.items():
        assert delta <= max(0.01 * total, 0.005), \
            (bucket, span_s, bucket_s, total)
    # every trace event is structurally drawable
    for e in trace["traceEvents"]:
        assert e["ph"] in ("X", "i", "C", "M")
        if e["ph"] == "X":
            assert e["dur"] >= 0 and isinstance(e["name"], str)
    # report tools: text renders the span/track rollup, json carries it
    import telemetry_report
    events, bad = telemetry_report.load_events(stream)
    s = telemetry_report.summarize(events, bad)
    obs = s["observability"]
    assert obs["spans"] == len(spans)
    assert set(obs["span_tracks"]) == tracks
    assert telemetry_report.main([stream, "--format", "json"]) == 0
    assert telemetry_report.main([stream]) == 0


def test_train_e2e_auto_profile_slow_step_captures_once(gpt2_dir,
                                                        wiki_dir,
                                                        tmp_path):
    """Satellite e2e: an injected slow step trips the flight recorder
    exactly once — the capture lands on disk with a profile_capture
    event pointing at it — and the cooldown holds through the later
    slow steps (budget intact for a future incident)."""
    from mobilefinetuner_tpu.cli.gpt2_lora_finetune import main
    stream = str(tmp_path / "run.jsonl")
    prof_dir = str(tmp_path / "profiles")
    rc = main(["--pretrained_dir", gpt2_dir, "--data_dir", wiki_dir,
               "--steps", "10", "--batch_size", "2", "--seq_len", "32",
               "--lora_out", str(tmp_path / "a.safetensors"),
               "--telemetry_out", stream, "--log_interval", "1",
               "--inject", "slow_step:6:400:2",
               "--auto_profile", "1", "--auto_profile_dir", prof_dir,
               "--auto_profile_steps", "1",
               "--auto_profile_budget", "2",
               "--auto_profile_cooldown", "3600",
               "--auto_profile_slow_mult", "3"])
    assert rc == 0
    recs = read_events(stream)
    for r in recs:
        assert validate_event(r) is None, (r, validate_event(r))
    caps = [r for r in recs if r["event"] == "profile_capture"]
    assert len(caps) == 1, [c["step"] for c in caps]  # cooldown held
    cap = caps[0]
    assert cap["trigger"] == "slow_step" and cap["budget_left"] == 1
    assert os.path.isdir(cap["path"])
    # the capture actually wrote a device trace (jax.profiler output)
    dumped = [os.path.join(r, f) for r, _, fs in os.walk(cap["path"])
              for f in fs]
    assert dumped, "profiler capture directory is empty"
    assert recs[-1]["event"] == "run_end" and recs[-1]["exit"] == "ok"


# --------------------------- serve e2e ---------------------------------------

def test_serve_e2e_spans_and_live_metrics_scrape(tmp_path):
    """Acceptance: /metrics scraped CONCURRENTLY during a live tiny
    serve run returns parseable OpenMetrics with nonzero request
    histograms, the run's post-warmup retrace count stays ZERO while
    being scraped (trace_counts pin), per-request spans land on
    req:<id> tracks, and the exported trace carries them."""
    import serve_bench
    stream = str(tmp_path / "serve.jsonl")
    port = _free_port()
    eng, names = serve_bench.build_engine(
        "tiny-gpt2", num_slots=2, block_T=4, num_blocks=32,
        max_prompt=8, max_new=4, adapters=0, dtype="float32",
        telemetry_out=stream, stats_every=2, trace_spans=True,
        metrics_port=port)
    try:
        eng.submit([1, 2, 3], max_new_tokens=2)
        eng.drain()                       # warmup: compile both programs
        warm = eng.total_traces()
        base = f"http://127.0.0.1:{port}"
        scrapes, stop = [], threading.Event()

        def scraper():
            while not stop.is_set():
                with urllib.request.urlopen(base + "/metrics",
                                            timeout=5) as r:
                    scrapes.append((r.status, r.read().decode()))
                time.sleep(0.005)

        th = threading.Thread(target=scraper, daemon=True)
        th.start()
        done, elapsed = serve_bench.run_load(
            eng, names, rate=200.0, n_requests=10, seed=0,
            prompt_lo=3, prompt_hi=6, max_new=4)
        stop.set()
        th.join(timeout=5)
        assert eng.total_traces() == warm, \
            "scraping the metrics endpoint cost a retrace"
        assert scrapes and all(st == 200 for st, _ in scrapes)
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            final = r.read().decode()
        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            hz = json.loads(r.read())
    finally:
        eng.metrics_server.close()
        eng.close()
    fams, samples = parse_openmetrics(final)
    assert fams["mft_ttft_ms"] == "histogram"
    assert samples["mft_ttft_ms_count"] > 0        # nonzero histograms
    assert samples["mft_tpot_ms_count"] > 0
    assert samples['mft_requests_total{phase="finish"}'] >= 10
    assert "queue_depth" in hz and "counts" in hz  # engine.health()
    # mid-run scrapes already carried data (live, not post-hoc)
    assert any("mft_requests_total" in body for _, body in scrapes)
    # spans: every admitted request got queue/prefill/decode on its track
    recs = read_events(stream)
    for r in recs:
        assert validate_event(r) is None, (r, validate_event(r))
    spans = [r for r in recs if r["event"] == "span"]
    req_tracks = {s["track"] for s in spans if s["track"].startswith("req:")}
    assert len(req_tracks) >= 10
    names_on_track = {s["name"] for s in spans
                      if s["track"] == sorted(req_tracks)[0]}
    assert {"queue", "prefill", "decode"} <= names_on_track
    # ONE command renders the serve session (request tracks included)
    import trace_export
    out = str(tmp_path / "serve.trace.json")
    assert trace_export.main([stream, "-o", out]) == 0
    trace = json.load(open(out))
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert any(e["name"] == "decode" for e in xs)
    assert any(e["name"] == "prefill" for e in xs)


def test_trace_export_synthesizes_request_spans_without_tracing():
    """A stream recorded WITHOUT --trace_spans still exports: request
    lifecycle spans are synthesized from the request events' wall
    stamps (queue = enqueue->admit, decode = admit->terminal)."""
    import trace_export
    t0 = 1000.0
    evs = []

    def ev(seq, event, dt, **f):
        evs.append({"event": event, "seq": seq, "t": t0 + dt,
                    "t_mono": 50.0 + dt, "host": 0, **f})

    req = dict(prompt_tokens=3, adapter=None, queue_ms=None,
               new_tokens=None, ttft_ms=None, tpot_ms=None, reason=None)
    ev(0, "request", 0.0, id=7, phase="enqueue", **req)
    ev(1, "request", 0.5, id=7, phase="admit", **req)
    ev(2, "request", 0.6, id=7, phase="first_token", **req)
    ev(3, "request", 2.0, id=7, phase="finish",
       **{**req, "new_tokens": 8})
    ev(4, "checkpoint", 3.0, step=2, final=False, wall_s=0.1,
       snapshot_ms=1.0, write_ms=500.0, bytes=1 << 20, mb_s=2.0,
       **{"async": True})
    for e in evs:
        assert validate_event(e) is None, (e, validate_event(e))
    trace = trace_export.export({0: evs})
    xs = {e["name"]: e for e in trace["traceEvents"] if e["ph"] == "X"}
    assert "queue" in xs and "decode" in xs
    assert xs["queue"]["dur"] == pytest.approx(0.5e6, rel=1e-6)
    assert xs["decode"]["dur"] == pytest.approx(1.5e6, rel=1e-6)
    assert xs["decode"]["args"]["outcome"] == "finish"
    # checkpoint write span derived from the write_ms on the event
    ck = next(e for e in trace["traceEvents"]
              if e["ph"] == "X" and e["name"] == "ckpt.write")
    assert ck["dur"] == pytest.approx(500e3, rel=1e-6)


def test_trace_export_scopes_resumed_stream_to_latest_run():
    """A resumed stream appends runs whose perf_counter epochs share
    nothing: the exporter renders only the LATEST run, so one clock
    offset places every span and the reconciliation never mixes a
    prior run's phase spans into the final run_end's buckets."""
    import trace_export
    mk = lambda seq, dt, tm, ev, **f: {"event": ev, "seq": seq,
                                       "t": 1000.0 + dt,
                                       "t_mono": tm, "host": 0, **f}
    run_start = dict(jax_version="x", mesh_shape=None, process_count=1,
                     process_index=0, device_kind="cpu", device_count=1,
                     config={})
    evs = [
        mk(0, 0.0, 5000.0, "run_start", **run_start),
        mk(1, 1.0, 5001.0, "span", name="step", track="phase",
           t0=5000.0, dur_ms=1000.0),
        mk(2, 2.0, 5002.0, "run_end", steps=1, wall_s=2.0, exit="ok",
           goodput={"step_s": 1.0, "total_s": 2.0,
                    "productive_frac": 0.5}),
        # resumed run: fresh process, fresh (much smaller) mono epoch
        mk(3, 100.0, 7.0, "run_start", **run_start),
        mk(4, 103.0, 10.0, "span", name="step", track="phase",
           t0=7.0, dur_ms=3000.0),
        mk(5, 104.0, 11.0, "run_end", steps=2, wall_s=4.0, exit="ok",
           goodput={"step_s": 3.0, "total_s": 4.0,
                    "productive_frac": 0.75}),
    ]
    for e in evs:
        assert validate_event(e) is None, (e, validate_event(e))
    trace = trace_export.export({0: evs})
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 1  # the prior run's span is NOT on this timeline
    assert spans[0]["dur"] == pytest.approx(3000e3)
    rec = trace_export.phase_reconcile(
        trace, evs[-1]["goodput"], pid=0)
    assert rec["step"][2] == pytest.approx(0.0, abs=1e-6)


# --------------------------- bench_compare -----------------------------------

def test_bench_compare_rows_deltas_and_regression_gate(tmp_path):
    """Satellite contract: shared-row matching, per-metric % delta with
    direction awareness (nested percentile dicts flattened), threshold
    gating — on two synthetic rows."""
    import bench_compare
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps({"rows": [
        {"config": "a", "tokens_per_sec_per_chip": 100.0,
         "ttft_ms": {"p50": 50.0}, "peak_hbm_mb": 800.0},
        {"config": "gone", "tokens_per_sec_per_chip": 9.0},
    ]}))
    # the other artifact shape: plain JSONL rows (bench.py stdout)
    new.write_text(
        json.dumps({"config": "a", "tokens_per_sec_per_chip": 80.0,
                    "ttft_ms": {"p50": 40.0}, "peak_hbm_mb": 820.0})
        + "\n" + json.dumps({"config": "fresh",
                             "tokens_per_sec_per_chip": 1.0}) + "\n")
    o = bench_compare.load_rows(str(old))
    n = bench_compare.load_rows(str(new))
    assert set(o) == {"a", "gone"} and set(n) == {"a", "fresh"}
    assert o["a"]["ttft_ms.p50"] == 50.0  # nested dict flattened
    c = bench_compare.compare(o, n, threshold=5.0)
    assert c["shared_rows"] == ["a"]
    assert c["only_old"] == ["gone"] and c["only_new"] == ["fresh"]
    by = {m["metric"]: m for m in c["metrics"]}
    tok = by["tokens_per_sec_per_chip"]
    assert tok["delta_pct"] == pytest.approx(-20.0)
    assert tok["regressed"]                      # throughput down 20%
    assert not by["ttft_ms.p50"]["regressed"]    # latency IMPROVED
    assert by["peak_hbm_mb"]["delta_pct"] == pytest.approx(2.5)
    assert not by["peak_hbm_mb"]["regressed"]    # 2.5% < 5% threshold
    assert [m["metric"] for m in c["regressions"]] == \
        ["tokens_per_sec_per_chip"]
    # direction heuristics
    assert bench_compare.direction("tok_s") == 1
    assert bench_compare.direction("tpot_ms.p99") == -1
    assert bench_compare.direction("loss") == 0
    # no threshold -> nothing gates
    assert not bench_compare.compare(o, n, threshold=0.0)["regressions"]
