"""Runner `harness.train_cli_xing4` (a workload file's `kind` names it
by module path): harness/train_cli.py's runner for the train cell of the
Xing4.0 family, as harness/train_cli_afmoe.py and train_cli_ling.py are
for theirs. The timed child is the user's CLI by argv
(`cli/train_lora_xing4` over `run_training`); the window, the rate
(`end_to_end`), the in-window checks and every helper are train_cli's
and train_cli_afmoe's own, imported (`write_word_corpus` among them: the
same corpus draw, one token a word). Only `observe` is restated, because
the runners before it name their fixture/correct child and their fixture
directory inline: here the child is harness/train_child_xing4.py, the
one weight fixture of the configuration lies under `<config>-w<seed>/xing4_0`
whatever the seed (8.0 GB), and the run_start keys kept are the
family's. `obs["kind"]` stays "train_cli": the readers of
harness/readers.py and span_readers.py ask for that.

`setup_s` leaves out the seconds the child spent on the float32
REFERENCE (`reference_s` of its verdict: computing it, or reading the
copy kept per cell and seed), as train_cli_ling does: the reference is
the yardstick's own work, not the program's start-up, and a second run
of one seed reads it back in a fraction of a second. The seconds stay
visible in `setup_phases.reference_excluded`.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import time

from . import common, stats
from .common import BenchError, say
from .traffic import argv_value
from .train_cli import (STARTUP_LIMIT_S, WARMUP_FLUSHES, POLL_S, Tail,  # noqa: F401
                        _trace_written, end_to_end, in_window_correct,
                        prune_corpora, run_child, stop)
from .train_cli_afmoe import write_word_corpus

CHILD = "train_child_xing4.py"
RUN_START = ("attention_impl_resolved", "attn_latent_bwd_resolved",
             "hc_mult", "mtp_loss_weight", "lora_impl_resolved")


def fixture_dirs(cell: dict, seed: int) -> dict:
    root = os.path.join(common.WORK, "fixtures")
    return {"root": root,
            "model": os.path.join(
                root, f"{cell['config']}-w"
                f"{cell['config_file']['fixture'].get('weight_seed', 0)}",
                "xing4_0"),
            "corpus": os.path.join(root,
                                   f"corpus-{cell['traffic']}-seed{seed}"),
            "run": os.path.join(common.WORK, "runs",
                                f"{cell['name']}-seed{seed}")}


def observe(cell: dict, seed: int, seconds: float, trace: bool,
            rehearse: bool = False) -> dict:
    tr = cell["traffic_file"]
    argv = list(tr["argv"])
    B = int(argv_value(argv, "--batch_size"))
    S = int(argv_value(argv, "--seq_len"))
    accum = int(argv_value(argv, "--grad_accum_steps", 1))
    every = int(tr.get("log_interval", 5))
    warm_flushes = WARMUP_FLUSHES
    d = fixture_dirs(cell, seed)
    os.makedirs(d["run"], exist_ok=True)
    phases = {}
    # ---- corpus (numpy only) -------------------------------------------
    digest = write_word_corpus(d["corpus"], tr["corpus"], seed)
    prune_corpora(d["root"], d["corpus"])
    phases["corpus"] = time.perf_counter() - common.T_PROCESS_START
    say("corpus", sha256=digest)
    # ---- fixture + correct: one short child of its own ------------------
    cmd = [sys.executable,
           os.path.join(common.BENCH_DIR, "harness", CHILD),
           "--workload", cell["name"], "--seed", str(seed),
           "--model_dir", d["model"], "--search", os.pathsep.join(cell["search"]),
           "--rehearse", str(int(rehearse))]
    rc, recs = run_child(cmd, os.path.join(d["run"], "correct_child.log"),
                         timeout=1400)
    by = {r.get("phase"): r for r in recs}
    for r in recs:
        say("train_child", **r)
    if rc not in (0, 1) or "correct" not in by:
        raise BenchError(f"the fixture/correct child ended rc={rc}: "
                         f"{recs[-1:] or 'no output'} (log: "
                         f"{d['run']}/correct_child.log)")
    device = by["device"]["device"]
    verdict = by["correct"]
    reference_s = float(verdict.get("reference_s") or 0.0)
    phases["fixture_and_correct"] = time.perf_counter() \
        - common.T_PROCESS_START
    phases["reference_excluded"] = reference_s
    # ---- the timed child: the user's CLI --------------------------------
    tel = os.path.join(d["run"], "telemetry.jsonl")
    if os.path.exists(tel):
        os.remove(tel)          # a stream is appended to, never reset
    cmd = [sys.executable, "-m", tr["module"],
           "--pretrained_dir", d["model"], "--data_dir", d["corpus"],
           *argv, "--seed", str(seed), "--steps", "10000000",
           "--lr_schedule", "constant", "--log_interval", str(every),
           "--telemetry_out", tel, "--on_preempt", "off",
           tr.get("out_flag", "--lora_out"),
           os.path.join(d["run"], "out.safetensors")]
    prof_dir = prof_steps = None
    if trace:
        prof_dir = os.path.join(d["run"], "profile")
        shutil.rmtree(prof_dir, ignore_errors=True)
        # inside the window: after warm-up and one more flush interval
        p0 = (warm_flushes + 1) * every
        prof_steps = (p0, p0 + int(tr.get("profile_steps", 5)))
        cmd += ["--profile_dir", prof_dir, "--profile_start", str(p0),
                "--profile_steps", str(prof_steps[1] - p0)]
    log = open(os.path.join(d["run"], "cli.log"), "w")
    proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                            cwd=common.ROOT, env=common.child_env())
    tail = Tail(tel)
    events, flushes = [], []      # all records; step_stats after compile
    t_open = None
    compiled = False
    deadline = time.perf_counter() + STARTUP_LIMIT_S
    try:
        while True:
            new = tail.poll()
            for rec in new:
                events.append(rec)
                ev = rec.get("event")
                if ev == "compile":
                    compiled = True
                elif ev == "step_stats" and compiled:
                    flushes.append(rec)
                    if len(flushes) == warm_flushes:
                        t_open = rec["_seen"]
                        phases["cli_start_to_window"] = t_open \
                            - common.T_PROCESS_START
                        say("window open", step=rec["step"])
            now = time.perf_counter()
            if t_open is not None and now > t_open + seconds:
                if not trace or _trace_written(prof_dir):
                    break
                if now > t_open + seconds + 120:
                    raise BenchError("no profiler trace appeared")
            if proc.poll() is not None:
                raise BenchError(
                    f"the CLI ended rc={proc.returncode} before the "
                    f"window closed (log: {d['run']}/cli.log)")
            if t_open is None and now > deadline:
                raise BenchError("the CLI never reached its window")
            if not new:
                time.sleep(POLL_S)
    finally:
        stop(proc)
        log.close()
        tail.close()
    seen = [f["_seen"] for f in flushes]
    idx = stats.whole_intervals(seen, t_open, seconds)
    win = [flushes[k] for k in idx]
    interval_s = [seen[k] - seen[k - 1] for k in idx]
    if interval_s:
        say("flush intervals (s, this process's clock)", n=len(interval_s),
            median=stats.median(interval_s),
            mean=sum(interval_s) / len(interval_s),
            first_64=[round(x, 4) for x in interval_s[:64]])
    first_seq = flushes[warm_flushes - 1]["seq"]
    by_event = lambda name: [e for e in events if e.get("event") == name]
    start = (by_event("run_start") or [{}])[0]
    run_dev = {"platform": start.get("platform"),
               "kind": start.get("device_kind"),
               "count": start.get("device_count")}
    if not rehearse and run_dev != device:
        raise BenchError(f"the CLI ran on {run_dev}, the check on {device}")
    mem = (by_event("mem_check") or [{}])[-1]
    comp = by_event("compile")
    est_mb = max([x for x in (mem.get("est_mb"),
                              *(c.get("peak_hbm_mb") for c in comp),
                              *(f.get("hbm_mb") for f in flushes))
                  if isinstance(x, (int, float))] or [0.0])
    return {
        "kind": "train_cli", "device": device, "cell": cell, "seed": seed,
        "setup_s": t_open - common.T_PROCESS_START - reference_s,
        "setup_phases": phases,
        "B": B * accum, "S": S, "log_interval": every,
        "chips": cell["chips"], "flushes": win,
        "interval_s": interval_s, "profile_steps": prof_steps,
        "tokens_per_flush": every * B * accum * S,
        "window_compiles": sum(1 for c in comp if c["seq"] > first_seq),
        "compile": comp[0] if comp else {}, "mem_check": mem,
        "memory_peak_bytes": int(est_mb * 2 ** 20), "verdict": verdict,
        "run_start": {k: start.get("config", {}).get(k) for k in RUN_START},
        "mesh_shape": start.get("mesh_shape"),
        "trace_dir": prof_dir, "run_dir": d["run"],
    }
