"""Runner `harness.train_cli_afmoe` (a workload file's `kind` names it by
module path): harness/train_cli.py's runner for a train cell of the
AFMoE family. The timed child is the user's CLI by argv all the same —
`cli/train_lora_afmoe` over `run_training` — and the window, the rate
(`end_to_end`), the in-window checks and every helper are train_cli's
own, imported. Only `observe` is restated, because train_cli's names
GPT-2's fixture/correct child and its fixture directory inline; it
differs from the original in three places: the child is
harness/train_child_afmoe.py, the one weight fixture of the
configuration lies under `<config>-w0/afmoe` whatever the seed (8.6 GB:
two would put 17 GB beside gpt2-xl's), and the corpus is written by
`write_word_corpus` (the generator's draw, its words renamed so that
each is one token). `obs["kind"]` stays "train_cli":
the readers of harness/readers.py and span_readers.py ask for that.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import time

from . import common, stats, traffic as traffic_mod
from .common import BenchError, say
from .traffic import argv_value
from .train_cli import (STARTUP_LIMIT_S, WARMUP_FLUSHES, POLL_S, Tail,  # noqa: F401
                        _trace_written, end_to_end, in_window_correct,
                        prune_corpora, run_child, stop)

CHILD = "train_child_afmoe.py"
WORD_LEN = 4


def word_name(i: int) -> str:
    """Word i of the corpus as WORD_LEN lower-case letters (base 26): one
    piece for the byte-level pre-tokenizer, which cuts the generator's
    own `w0123` between the letter and the digits."""
    return "".join(chr(97 + i // 26 ** p % 26)
                   for p in range(WORD_LEN - 1, -1, -1))


def write_word_corpus(d: str, spec: dict, seed: int) -> str:
    """harness/traffic.write_corpus with the generator's words renamed
    (`word_name`), so that with the fixture's tokenizer (one token a
    word, train_child_afmoe.write_tokenizer) a sequence draws on
    thousands of token ids and not on a dozen byte tokens — a router fed
    thirteen distinct embeddings sends every token to the same few
    experts. Same draw, same splits, same marker file; returns the
    sha256 of the text."""
    import hashlib
    import re
    texts = {k: re.sub(r"w(\d+)", lambda m: word_name(int(m.group(1))), v)
             for k, v in traffic_mod.corpus_text(spec, seed).items()}
    h = hashlib.sha256()
    for split in sorted(texts):
        h.update(texts[split].encode())
    digest = h.hexdigest()
    mark = os.path.join(d, "corpus.sha256")
    if os.path.exists(mark) and open(mark).read().strip() == digest \
            and all(os.path.exists(os.path.join(d, f"wiki.{s}.tokens"))
                    for s in texts):
        return digest
    os.makedirs(d, exist_ok=True)
    for split, text in texts.items():
        with open(os.path.join(d, f"wiki.{split}.tokens"), "w") as f:
            f.write(text)
    with open(mark, "w") as f:
        f.write(digest + "\n")
    return digest


def fixture_dirs(cell: dict, seed: int) -> dict:
    root = os.path.join(common.WORK, "fixtures")
    return {"root": root,
            "model": os.path.join(root, f"{cell['config']}-w0", "afmoe"),
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
