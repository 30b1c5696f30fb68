"""Runner `train_cli`: the user's training CLI as a child process, by
argv — `run_training`, the prefetch thread, the flush cadence, memory
admission and (on four chips) `build_mesh` / `shard_params` are inside
the measurement. This parent never imports JAX (a chip belongs to one
process); it tails the child's telemetry stream and stamps every line
with its OWN clock as the line appears.

Warm-up ends at the second `step_stats` flush (WARMUP_FLUSHES) after the
`compile` event; the window is the run of whole flush intervals that end
inside the next `--seconds` seconds. Tokens per flush are
log_interval x global batch x seq_len, and the rate is tokens per flush
over the MEDIAN interval (`end_to_end`: why not the mean). Then SIGTERM,
a short grace, SIGKILL: the exit is outside the window and outside
`setup_s`.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

from . import common, stats, traffic as traffic_mod
from .common import BenchError, say
from .traffic import argv_value

POLL_S = 0.001
GRACE_S = 5.0
WARMUP_FLUSHES = 2          # the window opens at the second flush
STARTUP_LIMIT_S = 1100.0    # a first run may take 1200 s, compiling


def fixture_dirs(cell: dict, seed: int) -> dict:
    root = os.path.join(common.WORK, "fixtures")
    w = common.fixture_seed(seed)
    return {"root": root,
            "model": os.path.join(root, f"{cell['config']}-w{w}", "gpt2"),
            "corpus": os.path.join(root,
                                   f"corpus-{cell['traffic']}-seed{seed}"),
            "run": os.path.join(common.WORK, "runs",
                                f"{cell['name']}-seed{seed}")}


def prune_corpora(root: str, keep: str, limit: int = 8) -> None:
    """Corpora are per seed and small; keep the newest few."""
    try:
        ds = sorted((os.path.join(root, d) for d in os.listdir(root)
                     if d.startswith("corpus-")), key=os.path.getmtime)
    except OSError:
        return
    for d in ds[:-limit]:
        if os.path.abspath(d) != os.path.abspath(keep):
            shutil.rmtree(d, ignore_errors=True)


def run_child(cmd, log_path: str, timeout: float) -> tuple:
    """A child run to its end; (rc, its JSON lines)."""
    with open(log_path, "w") as log:
        try:
            rc = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=log,
                                cwd=common.ROOT, env=common.child_env(),
                                timeout=timeout, text=True)
            lines, rc = rc.stdout.splitlines(), rc.returncode
        except subprocess.TimeoutExpired as e:
            lines, rc = (e.stdout or "").splitlines() \
                if isinstance(e.stdout, str) else [], 124
    recs = []
    for ln in lines:
        if ln.startswith("{"):
            try:
                recs.append(json.loads(ln))
            except ValueError:
                pass
    return rc, recs


class Tail:
    """Follow a JSONL file; every complete line gets this process's
    perf_counter reading at the moment it was first seen."""

    def __init__(self, path: str):
        self.path, self.f, self.buf = path, None, ""

    def poll(self) -> list:
        if self.f is None:
            if not os.path.exists(self.path):
                return []
            self.f = open(self.path)
        chunk = self.f.read()
        if not chunk:
            return []
        now = time.perf_counter()
        self.buf += chunk
        *lines, self.buf = self.buf.split("\n")
        out = []
        for ln in lines:
            try:
                rec = json.loads(ln)
            except ValueError:
                continue
            rec["_seen"] = now
            out.append(rec)
        return out

    def close(self) -> None:
        if self.f is not None:
            self.f.close()


def stop(proc: subprocess.Popen) -> None:
    """SIGTERM, a short grace, SIGKILL — and wait until it has ended."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(GRACE_S)
        except subprocess.TimeoutExpired:
            proc.kill()
    proc.wait()


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
    digest = traffic_mod.write_corpus(d["corpus"], tr["corpus"], seed)
    prune_corpora(d["root"], d["corpus"])
    phases["corpus"] = time.perf_counter() - common.T_PROCESS_START
    say("corpus", sha256=digest)
    # ---- fixture + correct: one short child of its own ------------------
    cmd = [sys.executable,
           os.path.join(common.BENCH_DIR, "harness", "train_child.py"),
           "--workload", cell["name"], "--seed", str(seed),
           "--model_dir", d["model"], "--search", os.pathsep.join(cell["search"]),
           "--rehearse", str(int(rehearse))]
    rc, recs = run_child(cmd, os.path.join(d["run"], "correct_child.log"),
                         timeout=1000)
    by = {r.get("phase"): r for r in recs}
    for r in recs:
        say("train_child", **r)
    if rc not in (0, 1) or "correct" not in by:
        raise BenchError(f"the fixture/correct child ended rc={rc}: "
                         f"{recs[-1:] or 'no output'} (log: "
                         f"{d['run']}/correct_child.log)")
    device = by["device"]["device"]
    verdict = by["correct"]
    phases["fixture_and_correct"] = time.perf_counter() \
        - common.T_PROCESS_START
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
        "setup_s": t_open - common.T_PROCESS_START, "setup_phases": phases,
        "B": B * accum, "S": S, "log_interval": every,
        "chips": cell["chips"], "flushes": win,
        "interval_s": interval_s, "profile_steps": prof_steps,
        "tokens_per_flush": every * B * accum * S,
        "window_compiles": sum(1 for c in comp if c["seq"] > first_seq),
        "compile": comp[0] if comp else {}, "mem_check": mem,
        "memory_peak_bytes": int(est_mb * 2 ** 20), "verdict": verdict,
        "run_start": {k: start.get("config", {}).get(k) for k in (
            "attention_impl_resolved", "lora_impl_resolved")},
        "mesh_shape": start.get("mesh_shape"),
        "trace_dir": prof_dir, "run_dir": d["run"],
    }


def _trace_written(prof_dir) -> bool:
    from .xplane import find_xplane
    p = find_xplane(prof_dir) if prof_dir else None
    return bool(p) and os.path.getsize(p) > 0 \
        and time.time() - os.path.getmtime(p) > 1.0


def end_to_end(obs: dict) -> dict:
    """Tokens per flush over the MEDIAN whole flush interval of the
    window, not all tokens over all time: every flush is a host sync
    that drains the device, so whatever holds the host up just then (a
    neighbour on a shared host, a slow write of the log line) is paid in
    full, and one such stall in some runs spread the mean by 2 % between
    runs of one code (the driver's first check of PR 22) while the steps
    themselves repeat to 0.03 %. The median leaves out stalls that hit
    fewer than half of the intervals; `train_stall_share` reports what it
    left out."""
    out = {"setup_s": obs["setup_s"]}
    if obs["interval_s"]:
        out["train_tokens_per_s_per_chip"] = (
            obs["tokens_per_flush"] / stats.median(obs["interval_s"])
            / obs["chips"])
    return out


def in_window_correct(obs: dict) -> dict:
    fl = obs["flushes"]
    losses = [f.get("loss") for f in fl]
    finite = all(isinstance(x, (int, float)) and math.isfinite(x)
                 for x in losses)
    skipped = sum(int(f.get("skipped") or 0) for f in fl)
    nonfinite = sum(1 for f in fl if (f.get("nonfinite_count") or 0) > 0)
    steps = len(fl) * obs["log_interval"]
    falling = len(fl) >= 2 and finite and losses[-1] < losses[0]
    return {"attempted": steps, "failed": skipped + nonfinite,
            "ok": bool(fl) and finite and falling
            and obs["window_compiles"] == 0 and skipped == 0,
            "losses": [losses[0], losses[-1]] if fl else [],
            "window_compiles": obs["window_compiles"]}
