"""Round-16 resource-exhaustion robustness (DESIGN.md §21): the HBM
admission preflight (core/memory_guard.py), the remat -> accum_x2 ->
offload degradation ladder in cli/common.run_training, the
RESOURCE_EXHAUSTED-at-dispatch retry, the serve engine's build-time
refusal naming max feasible num_blocks/num_slots, the prefetch
host-RSS shed guard, and the report tools' memory section."""

import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from fixtures import write_tiny_gpt2_dir, write_wikitext_dir

from mobilefinetuner_tpu.core import memory_guard as mg
from mobilefinetuner_tpu.core.telemetry import validate_event


def read_events(path):
    with open(path) as f:
        return [json.loads(l) for l in f.read().splitlines() if l.strip()]


def assert_stream_valid(evs):
    for e in evs:
        assert validate_event(e) is None, (e, validate_event(e))
    seqs = [e["seq"] for e in evs]
    assert all(a < b for a, b in zip(seqs, seqs[1:]))


# --------------------------- unit: capacity + verdicts ----------------------

class _Dev:
    def __init__(self, kind="cpu", limit=0):
        self.device_kind = kind
        self._limit = limit

    def memory_stats(self):
        return {"bytes_limit": self._limit} if self._limit else {}


def test_device_capacity_sources_in_precedence_order():
    """--hbm_cap_mb override > memory_stats bytes_limit > device-kind
    table > unknown (None — admission never refuses on a guess)."""
    cap, src = mg.device_capacity_mb(override_mb=123, device=_Dev())
    assert (cap, src) == (123.0, "flag")
    cap, src = mg.device_capacity_mb(device=_Dev(limit=4 * 2 ** 30))
    assert (cap, src) == (4096.0, "memory_stats")
    cap, src = mg.device_capacity_mb(device=_Dev(kind="TPU v5 lite"))
    assert (cap, src) == (16 * 1024.0, "device_table")
    # longest-substring-first: "v5p" must not match the "v5 lite" row
    cap, src = mg.device_capacity_mb(device=_Dev(kind="TPU v5p"))
    assert (cap, src) == (95 * 1024.0, "device_table")
    cap, src = mg.device_capacity_mb(device=_Dev(kind="weird accel"))
    assert (cap, src) == (None, "unknown")


def test_analytic_check_verdicts_and_headroom():
    over = mg.analytic_check(95.0, cap_mb=100, headroom=0.1)
    assert over.verdict == "over" and over.cap_frac == 0.95
    ok = mg.analytic_check(89.0, cap_mb=100, headroom=0.1)
    assert ok.verdict == "ok"
    unk = mg.analytic_check(89.0, cap_mb=0, headroom=0.1,
                            phase="serve_build")
    # no flag cap: falls back to the real device; on CPU (empty
    # memory_stats, kind not in the table) that is unknown
    if jax.local_devices()[0].platform == "cpu":
        assert unk.verdict == "unknown" and unk.cap_mb is None
    # the event payload carries the schema's required trio
    ev = over.event()
    assert ev["verdict"] == "over" and ev["est_mb"] == 95.0
    assert ev["cap_mb"] == 100.0 and ev["phase"] == "serve_build"


def test_is_resource_exhausted_matches_status_text():
    assert mg.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating"))
    assert not mg.is_resource_exhausted(ValueError("shape mismatch"))


def test_host_rss_mb_reads_this_process():
    rss = mg.host_rss_mb()
    if rss is None:
        pytest.skip("no /proc/self/statm on this platform")
    assert 1.0 < rss < 10 * 1024 * 1024


def test_parse_train_inject_hbm_pressure_grammar():
    from mobilefinetuner_tpu.cli.common import parse_train_inject
    assert parse_train_inject("hbm_pressure:64") == \
        ("hbm_pressure", None, 64)
    with pytest.raises(SystemExit):
        parse_train_inject("hbm_pressure")
    with pytest.raises(SystemExit):
        parse_train_inject("hbm_meltdown:1")


# --------------------------- unit: prefetch RSS shed ------------------------

def test_prefetch_rss_shed_guard():
    """The producer defers lookahead under injected pressure (sheds
    counted, queue drains), recovers to full depth after, and the
    consumed sequence is untouched — the tool's proof run in-process
    (tools/check_stream_memory.check_rss_shed)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from check_stream_memory import check_rss_shed
    r = check_rss_shed()
    assert r["ok"], r
    assert r["sheds"] > 0 and r["sequence_intact"]
    assert r["max_depth_under_pressure"] <= 2


def test_prefetch_unreadable_rss_disables_guard():
    """A sensor that cannot answer must never block the pipeline."""
    from mobilefinetuner_tpu.data.prefetch import Prefetcher
    with Prefetcher(iter(range(20)), depth=2, rss_limit_mb=1,
                    rss_fn=lambda: None) as s:
        assert list(s) == list(range(20))
        assert s.rss_sheds == 0


# --------------------------- serve build admission --------------------------

def test_serve_infeasible_config_refused_naming_max_feasible():
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.serve.engine import ServeConfig, ServeEngine
    cfg = GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(num_slots=2, num_blocks=4096, max_prompt=16,
                       max_new_tokens=16, hbm_cap_mb=8)
    with pytest.raises(mg.MemoryAdmissionError) as ei:
        ServeEngine("gpt2", cfg, params, scfg)
    msg = str(ei.value)
    assert "num_blocks=" in msg and "num_slots=" in msg
    max_blocks = int(msg.split("num_blocks=")[1].split()[0])
    assert 0 < max_blocks < 4096
    # the refusal happened BEFORE any pool allocation
    assert ei.value.check.verdict == "over"


def test_serve_feasible_config_emits_mem_check_and_hbm_stats(tmp_path):
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.core.telemetry import Telemetry
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.serve.engine import ServeConfig, ServeEngine
    cfg = GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    stream = str(tmp_path / "serve.jsonl")
    eng = ServeEngine(
        "gpt2", cfg, params,
        ServeConfig(num_slots=2, num_blocks=64, max_prompt=16,
                    max_new_tokens=16, hbm_cap_mb=1000, stats_every=1),
        telemetry=Telemetry(stream))
    h = eng.health()
    assert h["pool_mb"] == pytest.approx(eng.pool_mb)
    assert "hbm_mb" in h  # None on backends without memory_stats
    eng.emit_stats()
    eng.close()
    evs = read_events(stream)
    assert_stream_valid(evs)
    mc = [e for e in evs if e["event"] == "mem_check"]
    assert len(mc) == 1 and mc[0]["verdict"] == "ok"
    ss = [e for e in evs if e["event"] == "serve_stats"]
    assert ss and ss[0]["pool_mb"] == pytest.approx(eng.pool_mb)


# --------------------------- e2e: preflight + ladder ------------------------
# Calibrated on the tiny GPT-2 fixture at B=8, S=64: compiled peak is
# ~8.5 MB naive, ~3.7 MB with remat, ~1.5 MB with remat + accum_x2 —
# so cap 3 MB (threshold 2.7) forces exactly the remat AND accum rungs,
# and cap 1 MB (threshold 0.9) exhausts the whole ladder.

@pytest.fixture(scope="module")
def gpt2_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("gpt2ckpt")
    write_tiny_gpt2_dir(str(d))
    return str(d)


@pytest.fixture(scope="module")
def wiki_dir(tmp_path_factory):
    return write_wikitext_dir(str(tmp_path_factory.mktemp("wt2")))


@pytest.fixture(scope="module")
def big_wiki_dir(tmp_path_factory, wiki_dir):
    """The stock fixture corpus x4: a 6-step run consumes 48 chunks,
    and under drop_last the per-epoch chunk count depends on batch
    size — the naive (b=8) and degraded (b=4) streams must BOTH stay
    inside epoch 0 or their row sequences diverge at the boundary and
    the loss-parity oracle compares different data."""
    d = str(tmp_path_factory.mktemp("wt2big"))
    for split in ("train", "valid", "test"):
        with open(os.path.join(wiki_dir, f"wiki.{split}.tokens")) as f:
            txt = f.read()
        with open(os.path.join(d, f"wiki.{split}.tokens"), "w") as f:
            f.write(txt * 4)
    return d


def _base_argv(gpt2_dir, wiki_dir, tmp_path, name, steps=6):
    return ["--pretrained_dir", gpt2_dir, "--data_dir", wiki_dir,
            "--steps", str(steps), "--seq_len", "64",
            "--lora_out", str(tmp_path / f"{name}.safetensors"),
            "--telemetry_out", str(tmp_path / f"{name}.jsonl")]


def test_e2e_on_oom_risk_fail_raises_before_data_loading(
        gpt2_dir, wiki_dir, tmp_path):
    """Acceptance: an over-capacity config under --on_oom_risk fail
    dies with the named error immediately after compile — the stream
    is run_start, compile, mem_check{verdict=over}, run_end; no
    stream/step/checkpoint activity ever started."""
    from mobilefinetuner_tpu.cli.gpt2_lora_finetune import main
    with pytest.raises(mg.MemoryAdmissionError):
        main(_base_argv(gpt2_dir, wiki_dir, tmp_path, "fail")
             + ["--batch_size", "8", "--hbm_cap_mb", "3",
                "--on_oom_risk", "fail"])
    evs = read_events(str(tmp_path / "fail.jsonl"))
    assert_stream_valid(evs)
    assert [e["event"] for e in evs] == \
        ["run_start", "compile", "mem_check", "run_end"]
    assert evs[2]["verdict"] == "over"
    assert evs[-1]["exit"] == "MemoryAdmissionError"


@pytest.fixture(scope="module")
def degrade_run(gpt2_dir, big_wiki_dir, tmp_path_factory):
    """ONE degraded run + its directly-degraded oracle, shared by the
    ladder acceptance test and the report-rendering test (each CLI run
    costs 1-3 tiny compiles; tier-1 rides a wall-clock budget)."""
    from mobilefinetuner_tpu.cli.gpt2_lora_finetune import main
    tmp_path = tmp_path_factory.mktemp("degrun")
    # cap 2 MB (admits <= 1.8 under the 10% headroom): on the installed
    # jaxlib the CPU compiler's peak for this tiny step is 6.94 MB
    # naive, 2.25 with remat, 1.26 with remat + accum x2 — so BOTH
    # rungs are needed (the jax 0.4 estimates sat higher, and the old
    # cap of 3 now admits after remat alone)
    rc = main(_base_argv(gpt2_dir, big_wiki_dir, tmp_path, "deg")
              + ["--batch_size", "8", "--hbm_cap_mb", "2",
                 "--on_oom_risk", "degrade"])
    assert rc == 0
    rc = main(_base_argv(gpt2_dir, big_wiki_dir, tmp_path, "direct")
              + ["--batch_size", "4", "--grad_accum_steps", "2",
                 "--remat"])
    assert rc == 0
    return (read_events(str(tmp_path / "deg.jsonl")),
            read_events(str(tmp_path / "direct.jsonl")))


def test_e2e_degrade_ladder_walks_remat_then_accum_with_loss_parity(
        degrade_run):
    """THE acceptance e2e: with --hbm_cap_mb below the naive estimate
    the run emits mem_check{verdict=over}, walks degrade rungs
    (remat -> accum_x2) — each rung RECOMPILES (one compile event per
    attempt) and re-preflights — completes with run_end{exit=ok} in
    one schema-valid stream, and the final loss matches (<=1e-5) a run
    launched directly at the degraded config. The run finishing at all
    pins the donation/AOT sharding invariants: a drifted output
    sharding would reject its own donated outputs at step 2."""
    evs, direct = degrade_run
    assert_stream_valid(evs)
    mcs = [e for e in evs if e["event"] == "mem_check"]
    assert [m["verdict"] for m in mcs] == ["over", "over", "ok"]
    rungs = [e for e in evs if e["event"] == "degrade"]
    assert [r["rung"] for r in rungs] == ["remat", "accum_x2"]
    assert rungs[0]["from"] == "remat=off" and rungs[0]["to"] == "remat=on"
    assert rungs[1]["from"] == "accum=1" and rungs[1]["to"] == "accum=2"
    # each rung recompiled: 1 + len(rungs) compile events, est strictly
    # decreasing down the ladder
    compiles = [e for e in evs if e["event"] == "compile"]
    assert len(compiles) == 1 + len(rungs)
    ests = [m["est_mb"] for m in mcs]
    assert ests[0] > ests[1] > ests[2]
    ends = [e for e in evs if e["event"] == "run_end"]
    assert len(ends) == 1 and ends[0]["exit"] == "ok"
    deg_losses = [e["loss"] for e in evs if e["event"] == "step_stats"]
    # the oracle: launched DIRECTLY at the degraded config (remat on,
    # half micro-batch, doubled accum — same global batch)
    direct_losses = [e["loss"] for e in direct
                     if e["event"] == "step_stats"]
    assert len(deg_losses) == len(direct_losses) == 6
    np.testing.assert_allclose(deg_losses, direct_losses, atol=1e-5)


def test_e2e_ladder_exhausted_raises_with_attempted_rungs(
        gpt2_dir, wiki_dir, tmp_path):
    """When the LAST rung still does not fit, the named error carries
    the full attempted ladder and the stream records every rung. The
    run starts AT --remat, so this also pins the skip rule: a rung
    already enabled is skipped, not re-applied — the ladder goes
    straight to accum_x2 (then offload, via the CLI's builder)."""
    from mobilefinetuner_tpu.cli.gpt2_lora_finetune import main
    registry = str(tmp_path / "exh_runs.jsonl")
    with pytest.raises(mg.MemoryAdmissionError) as ei:
        main(_base_argv(gpt2_dir, wiki_dir, tmp_path, "exh", steps=2)
             + ["--batch_size", "8", "--remat", "--hbm_cap_mb", "1",
                "--on_oom_risk", "degrade", "--run_registry", registry])
    assert "remat" not in ei.value.ladder      # already on: skipped
    assert "accum_x2" in ei.value.ladder and "offload" in ei.value.ladder
    evs = read_events(str(tmp_path / "exh.jsonl"))
    assert_stream_valid(evs)
    assert [e["rung"] for e in evs if e["event"] == "degrade"] == \
        ["accum_x2", "offload"]
    assert all(m["verdict"] == "over" for m in evs
               if m["event"] == "mem_check")
    assert evs[-1]["event"] == "run_end" \
        and evs[-1]["exit"] == "MemoryAdmissionError"
    # the admission reject still leaves exactly ONE finalized registry
    # record, carrying the exception name (DESIGN.md §28)
    from mobilefinetuner_tpu.core.run_registry import RunRegistry
    (rec,) = RunRegistry(registry).records()
    assert rec["status"] == "MemoryAdmissionError"


def test_e2e_dispatch_oom_retries_next_rung_lineage_untouched(
        gpt2_dir, wiki_dir, tmp_path):
    """Acceptance: an injected RESOURCE_EXHAUSTED at dispatch is
    retried at the next rung IN PROCESS — mem_check{phase=dispatch} +
    a degrade event land in the stream, the run completes, checkpoint
    lineage stays verifiable, and the rollback machinery is never
    falsely triggered."""
    from mobilefinetuner_tpu.cli.gpt2_lora_finetune import main
    from mobilefinetuner_tpu.io.checkpoints import resolve_checkpoint
    out = str(tmp_path / "oom.safetensors")
    rc = main(_base_argv(gpt2_dir, wiki_dir, tmp_path, "oom", steps=4)
              + ["--batch_size", "8", "--save_every", "2",
                 "--inject", "hbm_pressure:8"])
    assert rc == 0
    evs = read_events(str(tmp_path / "oom.jsonl"))
    assert_stream_valid(evs)
    dispatch = [e for e in evs if e["event"] == "mem_check"
                and e.get("phase") == "dispatch"]
    assert len(dispatch) == 1 and dispatch[0]["verdict"] == "over"
    rungs = [e for e in evs if e["event"] == "degrade"]
    assert rungs and rungs[0]["rung"] == "remat" \
        and rungs[0]["step"] == 0
    assert not [e for e in evs if e["event"] == "rollback"]
    ends = [e for e in evs if e["event"] == "run_end"]
    assert len(ends) == 1 and ends[0]["exit"] == "ok"
    # every step trained exactly once despite the retry
    stats = [e for e in evs if e["event"] == "step_stats"]
    assert stats[-1]["step"] == 4
    # the lineage the run wrote verifies clean end to end
    resolved, step, verdicts = resolve_checkpoint(out, verify=True)
    assert resolved == out and all(v["ok"] for v in verdicts)


# --------------------------- e2e: eval preflight ----------------------------

def test_eval_ppl_preflight_fail_and_warn(gpt2_dir, wiki_dir, tmp_path):
    """Satellite: the compiled eval fn gets the same preflight — fail
    raises the named error before the data loop (stream ends with a
    schema-valid run_end), warn proceeds and completes."""
    from mobilefinetuner_tpu.cli.eval_ppl import main
    # B=32 puts the compiled eval peak (~7 MB logits+activations)
    # decisively over the 1 MB cap; the valid split's real batches are
    # short (drop_last=False) and ride the jit-cache fallback
    argv = ["--pretrained_dir", gpt2_dir, "--data_root", wiki_dir,
            "--split", "valid", "--batch_size", "32", "--seq_len", "64",
            "--max_batches", "2"]
    telem = str(tmp_path / "evalfail.jsonl")
    with pytest.raises(mg.MemoryAdmissionError):
        main(argv + ["--hbm_cap_mb", "1", "--on_oom_risk", "fail",
                     "--telemetry_out", telem])
    evs = read_events(telem)
    assert_stream_valid(evs)
    mcs = [e for e in evs if e["event"] == "mem_check"]
    assert mcs and mcs[0]["verdict"] == "over"
    assert evs[-1]["event"] == "run_end" \
        and evs[-1]["exit"] == "MemoryAdmissionError"
    assert not [e for e in evs if e["event"] == "eval"]

    telem2 = str(tmp_path / "evalwarn.jsonl")
    rc = main(argv + ["--hbm_cap_mb", "1", "--on_oom_risk", "warn",
                      "--telemetry_out", telem2])
    assert rc == 0
    evs = read_events(telem2)
    assert [e for e in evs if e["event"] == "mem_check"]
    assert evs[-1]["event"] == "run_end" and evs[-1]["exit"] == "ok"


# --------------------------- report rendering -------------------------------

def test_reports_render_memory_section(degrade_run):
    """Both report tools render est-vs-cap + ladder decisions from the
    ONE shared builder (telemetry_report.memory_summary)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import fleet_report
    import telemetry_report
    events, _direct = degrade_run
    assert all(telemetry_report.validate_event(e) is None
               for e in events)
    s = telemetry_report.summarize(events)
    m = s["memory"]
    assert m and m["over"] == 2 and len(m["degrades"]) == 2
    assert m["final"]["verdict"] == "ok"
    assert m["final"]["cap_frac"] == pytest.approx(
        m["final"]["est_mb"] / m["final"]["cap_mb"], abs=5e-3)
    lines = telemetry_report.memory_lines(m)
    assert any("DEGRADE remat" in l for l in lines)
    assert any("DEGRADE accum_x2" in l for l in lines)
    # fleet_report: the same builder feeds the per-host rollup
    fs = fleet_report.fleet_summary({0: (events, 0)})
    assert fs["per_host"][0]["memory"]["over"] == 2
    # a memory-less stream renders nothing
    assert telemetry_report.memory_summary(
        [e for e in events if e["event"] == "run_end"]) is None


def test_preflight_eval_compile_names_compile_oom(tmp_path):
    """A RESOURCE_EXHAUSTED from the eval compile ITSELF must land as
    mem_check{verdict=over, phase=compile} + a schema-valid run_end +
    the named error — not an unnamed crash with a truncated stream —
    while any other compile exception passes through untouched."""
    from types import SimpleNamespace

    from mobilefinetuner_tpu.cli.common import preflight_eval_compile
    from mobilefinetuner_tpu.core.telemetry import Telemetry
    args = SimpleNamespace(hbm_cap_mb=8, hbm_headroom=0.1,
                           on_oom_risk="fail")

    def boom():
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory "
                           "allocating 123 bytes")

    tel = Telemetry(str(tmp_path / "e.jsonl"))
    with pytest.raises(mg.MemoryAdmissionError):
        preflight_eval_compile(boom, args, tel, what="test step")
    evs = read_events(str(tmp_path / "e.jsonl"))
    assert [e["event"] for e in evs] == ["mem_check", "run_end"]
    assert evs[0]["verdict"] == "over" and evs[0]["phase"] == "compile"
    assert evs[-1]["exit"] == "MemoryAdmissionError"

    def other():
        raise ValueError("not an OOM")

    tel2 = Telemetry(str(tmp_path / "e2.jsonl"))
    with pytest.raises(ValueError):
        preflight_eval_compile(other, args, tel2, what="test step")
    assert read_events(str(tmp_path / "e2.jsonl")) == []


def test_fleet_controller_gives_up_on_inadmissible_config(tmp_path):
    """The r13 controller must read run_end{exit=MemoryAdmissionError}
    as an INADMISSIBLE CONFIG — give up with the restart budget
    intact, never re-launch a config that deterministically re-fails
    the same preflight (both the dry-run decision function and the
    live ShardTail carry the verdict)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import fleet_controller as fc
    evs = [{"event": "run_start", "seq": 0, "t": 1.0},
           {"event": "run_end", "exit": "MemoryAdmissionError",
            "steps": 0, "wall_s": 0.1, "goodput": None, "seq": 1,
            "t": 2.0}]
    d = fc.decide_worker(evs)
    assert d["decision"] == "give_up"
    assert d["reason"] == "inadmissible_config"
    # a plain crash still restarts (the new branch must not widen)
    evs[1]["exit"] = "ValueError"
    assert fc.decide_worker(evs)["decision"] == "restart"
    # the live tail tracks the latest run_end exit name
    p = str(tmp_path / "w.jsonl")
    tail = fc.ShardTail(p)
    with open(p, "w") as f:
        for e in evs[:1] + [dict(evs[1], exit="MemoryAdmissionError")]:
            f.write(json.dumps(e) + "\n")
    tail.poll()
    assert tail.last_exit == "MemoryAdmissionError"


# --------------------------- partial memory_stats() dicts -------------------
# Some backends return PARTIAL dicts (bytes_in_use without bytes_limit,
# or vice versa), None, or raise outright. Round 23 routes every
# memory_stats read through xla_stats.memory_stat so no consumer
# KeyErrors on those platforms.

class _WeirdDev:
    device_kind = "weird accel"
    platform = "weird"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_memory_stat_guards_every_degenerate_shape():
    from mobilefinetuner_tpu.core.xla_stats import memory_stat
    assert memory_stat(_WeirdDev({"bytes_in_use": 7}), "bytes_in_use") == 7
    # partial dict: the missing key is default, not a KeyError
    assert memory_stat(_WeirdDev({"bytes_in_use": 7}), "bytes_limit") is None
    assert memory_stat(_WeirdDev({"bytes_in_use": 7}), "bytes_limit",
                       default=0) == 0
    assert memory_stat(_WeirdDev(None), "bytes_in_use") is None
    assert memory_stat(_WeirdDev("not a dict"), "bytes_in_use") is None
    assert memory_stat(_WeirdDev(RuntimeError("no stats")),
                       "bytes_in_use") is None
    # a bool is not a byte count even though bool subclasses int
    assert memory_stat(_WeirdDev({"bytes_in_use": True}),
                       "bytes_in_use") is None
    assert memory_stat(_WeirdDev({"bytes_in_use": "123"}),
                       "bytes_in_use") is None


def test_live_hbm_mb_survives_partial_stats_dicts():
    from mobilefinetuner_tpu.core.xla_stats import live_hbm_mb
    # bytes_in_use present WITHOUT bytes_limit: still reported
    devs = [_WeirdDev({"bytes_in_use": 300 * 2 ** 20}),
            _WeirdDev({"bytes_limit": 16 * 2 ** 30})]  # in_use missing
    assert live_hbm_mb(devices=devs) == pytest.approx(300.0)
    # nothing reports: None (not 0.0), and no exception
    assert live_hbm_mb(devices=[_WeirdDev(RuntimeError("boom")),
                                _WeirdDev({})]) is None


def test_device_capacity_falls_through_partial_stats_to_table():
    # bytes_in_use present but bytes_limit ABSENT: the capacity probe
    # must fall through (to the device table / unknown), not KeyError
    dev = _WeirdDev({"bytes_in_use": 123})
    cap, src = mg.device_capacity_mb(device=dev)
    assert (cap, src) == (None, "unknown")
    dev = _WeirdDev({"bytes_in_use": 123})
    dev.device_kind = "TPU v4"
    cap, src = mg.device_capacity_mb(device=dev)
    assert src == "device_table" and cap == 32 * 1024.0
    dev = _WeirdDev(RuntimeError("no stats"))
    dev.device_kind = "TPU v4"
    cap, src = mg.device_capacity_mb(device=dev)
    assert src == "device_table"
