#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the system starts on the chip.

Drives the main path once on ONE TPU chip through the entry points a user
would call, at the full published width of GPT-2 small (12 layers, H 768,
12 heads, D 64, vocab 50,257; weights random, from --seed):

  device    JAX must find a TPU — there is no CPU fallback
  fixtures  full-size HF-layout checkpoint dir + WikiText-shaped corpus
  train     cli.gpt2_lora_finetune in bf16 at (B,S) = (64,128) and (4,1024),
            then --resume_from a mid-run checkpoint of the first
  eval      cli.eval_ppl with the trained adapter
  numerics  one bf16 forward on the chip against float32 on the host CPU
  serve     a ServeEngine built like tools/serve_bench.py builds it answers
            a few dozen mixed requests; the paged Pallas kernel must be IN
            the decode program and agree with the gather oracle

Every phase prints one JSON line and asserts from the telemetry stream and
the process what `auto` resolved to; timings on those lines are smoke
timings, not metrics. The LAST line of stdout is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}`, or
`{"ok": false, ...}` with a non-zero exit if any phase failed.

  --chips 4   runs ONLY what exists across chips, and what it is compared
              with: FSDP LoRA training (--mesh_fsdp 4 vs a one-device mesh,
              at S = 128 and at S = 1024, where attention is the flash
              kernels) and tensor-parallel decode (mesh_tp=4 vs the (1,1)
              engine).
              Fails with fewer than four devices; the last line's count is 4.
  --rehearse  CPU rehearsal of the control flow at tiny sizes. Every line it
              prints says so, and it can never print the line above.

One process per chip: this parent never imports JAX; its children run one
after another, each the only process on the chip while it runs.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from mobilefinetuner_tpu.core.compile_cache import cache_dir  # noqa: E402

BUDGET_S = 1100.0          # the driver allows 1200 s, compilation included
LOSS_TOL_FSDP = 2e-2       # |loss_fsdp4 - loss_1dev| / loss, per step, bf16
LOSS_TOL_RESUME = 2e-2     # resumed steps vs the uninterrupted run's
LOGIT_TOL = 4e-2           # max|bf16 chip - f32 cpu| / max|f32 cpu|
KERNEL_TOL = 2e-2          # paged kernel vs gather oracle, bf16 (atol=rtol)


# --------------------------- plumbing ---------------------------------------

def print_line(rehearse: bool, phase: str, ok: bool, **fields) -> bool:
    """One JSON object per phase; a rehearsal says so in every line."""
    rec = {"phase": phase, "ok": bool(ok), **fields}
    if rehearse:
        rec["rehearsal"] = True
    print(json.dumps(rec), flush=True)
    return bool(ok)


class Smoke:
    """Run state of the parent: where things go, how much time is left,
    and the line printer (one JSON object per phase)."""

    def __init__(self, args):
        self.args = args
        self.rehearse = bool(args.rehearse)
        self.t0 = time.time()
        self.out = os.path.abspath(args.out)
        self.work = os.path.abspath(args.work_dir)
        os.makedirs(self.out, exist_ok=True)
        os.makedirs(self.work, exist_ok=True)
        self.device = None
        self.failed = None
        if self.rehearse and args.chips > 1:
            # the rehearsal's "chips" are virtual CPU devices, for every
            # child alike (an environment variable: no JAX import here)
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                f"platform_device_count={args.chips}").strip()

    def left(self) -> float:
        return BUDGET_S - (time.time() - self.t0)

    def line(self, phase: str, ok: bool, **fields) -> bool:
        if not ok and self.failed is None:
            self.failed = phase
        return print_line(self.rehearse, phase, ok, **fields)

    def report(self, phase: str, ck: "Check", rc: int, secs: float,
               log: list, **fields) -> bool:
        """The line of a phase that ran one child: passed iff no
        assertion failed; a failure carries the child's last lines."""
        return self.line(phase, not ck.bad, rc=rc, smoke_seconds=secs,
                         failures=ck.bad, **fields,
                         **({"log_tail": log[-12:]} if ck.bad else {}))

    def child(self, name: str, cmd: list, cap_s: float = 600.0):
        """Run one child to its end (or kill it at its time limit);
        returns (rc, stdout_lines, seconds). Its output goes to
        <out>/<name>.log so a failure can be read after the run."""
        log = os.path.join(self.out, f"{name}.log")
        t = time.time()
        timeout = max(min(cap_s, self.left()), 1.0)
        with open(log, "w") as f:
            try:
                rc = subprocess.run(cmd, stdout=f, stderr=subprocess.STDOUT,
                                    cwd=HERE, timeout=timeout).returncode
            except subprocess.TimeoutExpired:
                rc = 124       # subprocess.run killed and reaped it
        with open(log, errors="replace") as f:
            lines = f.read().splitlines()
        return rc, lines, round(time.time() - t, 1)

    def self_child(self, phase: str, cap_s: float = 600.0):
        """A JAX-touching phase of THIS script in a child process; its
        JSON lines are re-printed and returned (None if it failed)."""
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
               "--seed", str(self.args.seed), "--out", self.out,
               "--work_dir", self.work, "--chips", str(self.args.chips)]
        if self.rehearse:
            cmd.append("--rehearse")
        rc, lines, secs = self.child(phase, cmd, cap_s)
        recs = []
        for ln in lines:
            if ln.startswith("{") and '"phase"' in ln:
                try:
                    recs.append(json.loads(ln))
                except ValueError:
                    pass
        for r in recs:
            self.line(r["phase"], r["ok"], **{
                k: v for k, v in r.items()
                if k not in ("phase", "ok", "rehearsal")})
        if rc != 0 or not recs:
            self.line(phase, False, rc=rc, smoke_seconds=secs,
                      log_tail=lines[-12:])
            return None
        return recs


def read_events(ck: "Check", path: str) -> list:
    """A telemetry stream through the report tools' own loader, which
    holds every record to EVENT_SCHEMA (jax-free, like this parent)."""
    sys.path.insert(0, os.path.join(HERE, "tools"))
    from report_sections import load_events
    events, invalid = load_events(path)
    ck(invalid == 0, f"{invalid} schema-invalid record(s) in {path}")
    return events


def by_event(events: list, name: str) -> list:
    return [e for e in events if e["event"] == name]


class Check:
    """Collects failed assertions of one phase instead of stopping at
    the first, so one chip run reports everything that is wrong."""

    def __init__(self):
        self.bad = []

    def __call__(self, cond, what: str) -> bool:
        if not cond:
            self.bad.append(what)
        return bool(cond)


def sizes(rehearse: bool) -> dict:
    """The smoke's shapes: published GPT-2-small widths on the chip;
    a tiny model for the CPU rehearsal (control flow only)."""
    if rehearse:
        return {"vocab": 300, "train": [(8, 32, 6), (2, 64, 4)],
                "fsdp": [(8, 32, 4), (4, 64, 3)], "eval": (2, 32, 3),
                "serve_model": "tiny-gpt2", "slots": 4, "block_T": 8,
                "blocks": 64, "max_prompt": 16, "max_new": 8,
                "requests": 10, "numerics_S": 64, "tp": 2}
    return {"vocab": 50257, "train": [(64, 128, 12), (4, 1024, 6)],
            "fsdp": [(64, 128, 8), (8, 1024, 4)], "eval": (8, 128, 4),
            "serve_model": "gpt2s", "slots": 8, "block_T": 16,
            "blocks": 256, "max_prompt": 64, "max_new": 32,
            "requests": 36, "numerics_S": 1024, "tp": 4}


# --------------------------- train / eval (the CLIs) ------------------------

TARGETS = "attn_qkv,attn_proj,mlp_fc_in,mlp_fc_out"


def train_cmd(smoke: Smoke, name: str, B: int, S: int, steps: int,
              extra=()) -> tuple:
    tel = os.path.join(smoke.out, f"{name}.telemetry.jsonl")
    if os.path.exists(tel):
        os.remove(tel)          # a stream is appended to, never reset
    adapter = os.path.join(smoke.work, f"{name}.safetensors")
    cmd = [sys.executable, "-m",
           "mobilefinetuner_tpu.cli.gpt2_lora_finetune",
           "--pretrained_dir", os.path.join(smoke.work, "gpt2"),
           "--data_dir", os.path.join(smoke.work, "corpus"),
           "--dtype", "bfloat16", "--batch_size", str(B),
           "--seq_len", str(S), "--steps", str(steps), "--lr", "1e-3",
           "--lora_targets", TARGETS, "--seed", str(smoke.args.seed),
           "--log_interval", "1", "--lora_out", adapter,
           "--telemetry_out", tel, *extra]
    return cmd, tel, adapter


def check_train(smoke: Smoke, ck: Check, events: list, *, steps: range,
                want_kernel: bool, device_count: int) -> dict:
    """The assertions every training run of the smoke must meet, from
    its own telemetry stream. Returns what the phase line reports."""
    sz = sizes(smoke.rehearse)
    on_chip = not smoke.rehearse
    start = by_event(events, "run_start")
    ck(len(start) == 1, "exactly one run_start")
    man = start[0] if start else {}
    dev = smoke.device
    ck(man.get("platform") == dev["platform"],
       f"run_start.platform {man.get('platform')!r} != {dev['platform']!r}")
    ck(man.get("device_kind") == dev["kind"],
       f"run_start.device_kind {man.get('device_kind')!r}")
    ck(man.get("device_count") == device_count,
       f"run_start.device_count {man.get('device_count')} != "
       f"{device_count}")
    stats = by_event(events, "step_stats")
    losses = [e["loss"] for e in stats]
    ck([e["step"] for e in stats] == list(steps),
       f"step_stats steps {[e['step'] for e in stats]} != {list(steps)}")
    ck(all(isinstance(x, (int, float)) and math.isfinite(x)
           for x in losses), f"non-finite loss in {losses}")
    compiles = by_event(events, "compile")
    ck(len(compiles) == 1, f"{len(compiles)} compile events, want 1")
    first_stat = min((e["seq"] for e in stats), default=0)
    ck(all(c["seq"] < first_stat for c in compiles),
       "a compile event after the first step")
    comp = compiles[0] if compiles else {}
    ck(isinstance(comp.get("flops"), (int, float)) and comp["flops"] > 0,
       f"compile.flops is {comp.get('flops')!r}, not a number")
    kernels = comp.get("tpu_custom_calls")
    if on_chip and want_kernel:
        ck(kernels and kernels > 0,
           "no tpu_custom_call in the compiled step: the flash kernels "
           "are not in the program")
    mem = by_event(events, "mem_check")
    ck(len(mem) >= 1, "no mem_check event")
    mc = mem[-1] if mem else {}
    ck(mc.get("verdict") != "over", f"mem_check verdict {mc.get('verdict')}")
    if on_chip:
        ck(mc.get("cap_source") == "memory_stats",
           f"mem_check.cap_source {mc.get('cap_source')!r}: the HBM "
           f"preflight did not read memory_stats()")
        ck(mc.get("live_mb") is not None, "mem_check.live_mb is null")
    end = by_event(events, "run_end")
    ck(len(end) == 1 and end[0].get("exit") == "ok",
       f"run_end {[e.get('exit') for e in end]}")
    return {"losses": [round(x, 4) for x in losses],
            "ln_vocab": round(math.log(sz["vocab"]), 4),
            "attention_impl": man.get("config", {}).get(
                "attention_impl_resolved"),
            "lora_impl": man.get("config", {}).get("lora_impl_resolved"),
            "tpu_custom_calls": kernels,
            "compile_cache_hit": comp.get("cache_hit"),
            "compile_flops": comp.get("flops"),
            "smoke_compile_s": comp.get("wall_s"),
            "mem_check": {k: mc.get(k) for k in (
                "verdict", "cap_source", "cap_mb", "est_mb", "live_mb",
                "live_mb_by_device")}}


def phase_train(smoke: Smoke) -> bool:
    sz = sizes(smoke.rehearse)
    (B1, S1, n1), (B2, S2, n2) = sz["train"]
    ok = True
    # ---- (64,128): the shape of every on-chip record r01-r05 ----------
    half = n1 // 2
    cmd, tel, adapter = train_cmd(smoke, "train_a", B1, S1, n1,
                                  ["--save_every", str(half)])
    rc, lines, secs = smoke.child("train_a", cmd)
    ck = Check()
    rep = {}
    if ck(rc == 0, f"CLI exit code {rc}"):
        rep = check_train(smoke, ck, read_events(ck, tel),
                          steps=range(1, n1 + 1),
                          want_kernel=False, device_count=1)
        L = rep["losses"]
        if L:
            ck(abs(L[0] - rep["ln_vocab"]) < 0.5,
               f"first loss {L[0]} not near ln(vocab) {rep['ln_vocab']}")
            ck(L[-1] < L[0], f"loss did not fall: {L[0]} -> {L[-1]}")
        ck(os.path.exists(adapter), "adapter file not written")
    ok &= smoke.report(f"train_B{B1}_S{S1}", ck, rc, secs, lines, **rep)
    losses_a = rep.get("losses", [])
    # ---- (4,1024): the flash forward + merged backward kernels --------
    cmd, tel, _ = train_cmd(smoke, "train_b", B2, S2, n2)
    rc, lines, secs = smoke.child("train_b", cmd)
    ck, rep = Check(), {}
    if ck(rc == 0, f"CLI exit code {rc}"):
        rep = check_train(smoke, ck, read_events(ck, tel),
                          steps=range(1, n2 + 1), want_kernel=True,
                          device_count=1)
        if not smoke.rehearse:
            ck(rep["attention_impl"] == "flash",
               f"attention auto -> {rep['attention_impl']!r} at S={S2}")
    ok &= smoke.report(f"train_B{B2}_S{S2}", ck, rc, secs, lines, **rep)
    # ---- --resume_from the mid-run checkpoint of the first run: the
    # SAME program (same shapes, same total steps) in a new process, so
    # the persistent compile cache must serve it, and the resumed steps
    # must retrace the uninterrupted run's losses ------------------------
    root, ext = os.path.splitext(adapter)
    mid = f"{root}_step{half}{ext}"
    cmd, tel, _ = train_cmd(smoke, "train_resume", B1, S1, n1,
                            ["--resume_from", mid])
    rc, lines, secs = smoke.child("train_resume", cmd)
    ck, rep = Check(), {}
    if ck(os.path.exists(mid), f"mid-run checkpoint {mid} missing") and \
            ck(rc == 0, f"CLI exit code {rc}"):
        rep = check_train(smoke, ck, read_events(ck, tel),
                          steps=range(half + 1, n1 + 1),
                          want_kernel=False, device_count=1)
        want = losses_a[half:]
        got = rep["losses"]
        ck(len(got) == len(want) and all(
            abs(g - w) <= LOSS_TOL_RESUME * abs(w)
            for g, w in zip(got, want)),
           f"resumed losses {got} != uninterrupted {want} "
           f"(tol {LOSS_TOL_RESUME})")
        if not smoke.rehearse:
            ck(rep["compile_cache_hit"] is True,
               "the resumed run recompiled: no persistent-cache hit in "
               f"{cache_dir()}")
    ok &= smoke.report("train_resume", ck, rc, secs, lines,
                       resumed_from_step=half, **rep)
    return ok


def phase_eval(smoke: Smoke) -> bool:
    sz = sizes(smoke.rehearse)
    B, S, nb = sz["eval"]
    tel = os.path.join(smoke.out, "eval.telemetry.jsonl")
    if os.path.exists(tel):
        os.remove(tel)
    cmd = [sys.executable, "-m", "mobilefinetuner_tpu.cli.eval_ppl",
           "--pretrained_dir", os.path.join(smoke.work, "gpt2"),
           "--data_root", os.path.join(smoke.work, "corpus"),
           "--lora_path", os.path.join(smoke.work, "train_a.safetensors"),
           "--dtype", "bfloat16", "--batch_size", str(B),
           "--seq_len", str(S), "--max_batches", str(nb),
           "--log_every", "0", "--telemetry_out", tel]
    rc, lines, secs = smoke.child("eval", cmd)
    ck, rep = Check(), {}
    if ck(rc == 0, f"CLI exit code {rc}"):
        final = [json.loads(ln) for ln in lines
                 if ln.startswith("{") and '"type": "final"' in ln]
        ck(len(final) == 1, "no final record on stdout")
        rec = final[0] if final else {}
        ppl = rec.get("ppl")
        ck(isinstance(ppl, (int, float)) and math.isfinite(ppl)
           and 1.0 < ppl < sz["vocab"],
           f"perplexity {ppl!r} not finite / not below the vocab size")
        ck(rec.get("tokens", 0) > 0, "no tokens evaluated")
        man = (by_event(read_events(ck, tel), "run_start") or [{}])[0]
        ck(man.get("platform") == smoke.device["platform"],
           f"eval run_start.platform {man.get('platform')!r}")
        rep = {"ppl": ppl, "nll": rec.get("nll"),
               "tokens": rec.get("tokens"),
               "platform": man.get("platform")}
    return smoke.report("eval", ck, rc, secs, lines, **rep)


# --------------------------- four chips -------------------------------------

def phase_fsdp(smoke: Smoke) -> bool:
    """FSDP LoRA training over four chips against the same seed and
    global batch on a one-device mesh of the same machine, at each of
    two sizes: the short one every record has, and S = 1024, where
    `auto` is the flash kernels — under shard_map over the mesh's batch
    axes on four chips (ops/attention.attention), plain on one."""
    ok = True
    for B, S, n in sizes(smoke.rehearse)["fsdp"]:
        ok &= fsdp_against_one_device(smoke, B, S, n)
    return ok


def fsdp_against_one_device(smoke: Smoke, B: int, S: int, n: int) -> bool:
    flash = not smoke.rehearse and S >= 512   # resolve_impl at D = 64
    runs = {}
    ok = True
    for name, fsdp in ((f"fsdp4_S{S}", 4), (f"fsdp1_S{S}", 1)):
        cmd, tel, _ = train_cmd(smoke, name, B, S, n,
                                ["--mesh_fsdp", str(fsdp)])
        rc, lines, secs = smoke.child(name, cmd)
        ck, rep = Check(), {}
        if ck(rc == 0, f"CLI exit code {rc}"):
            ev = read_events(ck, tel)
            rep = check_train(smoke, ck, ev, steps=range(1, n + 1),
                              want_kernel=flash,
                              device_count=smoke.device["count"])
            if flash:
                ck(rep["attention_impl"] == "flash",
                   f"attention auto -> {rep['attention_impl']!r} at S={S}")
            comp = (by_event(ev, "compile") or [{}])[0]
            rep["collectives"] = comp.get("collectives")
            rep["mesh_shape"] = by_event(ev, "run_start")[0].get(
                "mesh_shape")
        runs[fsdp] = rep
        ok &= smoke.report(f"train_{name}", ck, rc, secs, lines, **rep)
    if not ok:
        return False
    ck = Check()
    a, b = runs[4], runs[1]
    rel = [abs(x - y) / abs(y) for x, y in zip(a["losses"], b["losses"])]
    ck(len(rel) == n and max(rel) <= LOSS_TOL_FSDP,
       f"per-step losses differ by up to {max(rel or [1]):.2e} "
       f"(tol {LOSS_TOL_FSDP})")
    col = a["collectives"] or {}
    ck(col.get("all-gather", 0) > 0,
       f"no all-gather in the FSDP step: {col}")
    ck(col.get("reduce-scatter", 0) + col.get("all-reduce", 0) > 0,
       f"no gradient reduction in the FSDP step: {col}")
    ck(not (b["collectives"] or {}),
       f"collectives in the one-device step: {b['collectives']}")
    # placement by bytes, not by spec: before the first step each of the
    # four devices holds about a quarter of what the one device held
    per4 = a["mem_check"]["live_mb_by_device"]
    one = b["mem_check"]["live_mb"]
    if smoke.rehearse:      # the CPU backend reports no memory_stats
        ck(per4 is None and one is None, "memory_stats on CPU?")
        share = None
    else:
        share = [round(mb / one, 3) for mb in per4[:4]] if per4 and one \
            else None
        ck(share is not None and len(per4) >= 4
           and all(0.15 <= s <= 0.40 for s in share),
           f"per-device share of the one-device bytes {share}, "
           f"want about 0.25 each")
    both = lambda key: {"fsdp4": a[key], "fsdp1": b[key]}
    return smoke.line(f"fsdp4_vs_1_S{S}", not ck.bad, failures=ck.bad,
                      max_rel_loss_diff=max(rel) if rel else None,
                      loss_tol=LOSS_TOL_FSDP,
                      attention_impl_resolved=both("attention_impl"),
                      tpu_custom_calls=both("tpu_custom_calls"),
                      collectives_fsdp4=col, mesh_fsdp4=a["mesh_shape"],
                      live_mb_by_device_fsdp4=per4, live_mb_one_device=one,
                      share_of_one_device=share)


# --------------------------- JAX-touching children --------------------------

def _allow_host_cpu() -> None:
    """Keep the accelerator the default backend but let the process also
    see the host CPU (the float32 reference runs there). Must run before
    jax is imported; with JAX_PLATFORMS unset both are visible anyway."""
    p = os.environ.get("JAX_PLATFORMS", "")
    if p and "cpu" not in p.split(","):
        os.environ["JAX_PLATFORMS"] = p + ",cpu"


def child_device(args) -> int:
    """Find the chip (or fail), say what it is, then write the fixtures
    in the same process (one start-up, and the weights are drawn on the
    device)."""
    import jax
    from mobilefinetuner_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    want = "cpu" if args.rehearse else "tpu"
    stats = d.memory_stats() or {}
    from mobilefinetuner_tpu.native.build import engines_in_use
    ok = print_line(args.rehearse, "device", d.platform == want
               and len(devs) >= args.chips, device=info,
               need={"platform": want, "count": args.chips},
               jax=jax.__version__,
               memory_stats_keys=sorted(stats)[:12],
               bytes_limit=stats.get("bytes_limit"),
               compile_cache_dir=cache_dir(),
               compile_cache_from_env=bool(
                   os.environ.get("JAX_COMPILATION_CACHE_DIR")),
               native_engines=engines_in_use())
    if not ok:
        return 2
    t = time.time()
    sys.path.insert(0, os.path.join(HERE, "tools"))
    from e2e_ppl_pipeline import (write_synthetic_corpus,
                                  write_synthetic_gpt2)
    cfg = None
    if args.rehearse:
        from mobilefinetuner_tpu.core.config import GPT2Config
        # wide enough that the stacked leaves pass the FSDP size floor
        cfg = GPT2Config(vocab_size=sizes(True)["vocab"],
                         n_positions=64, n_embd=128, n_layer=2, n_head=4)
    cfg = write_synthetic_gpt2(os.path.join(args.work_dir, "gpt2"),
                               seed=args.seed, cfg=cfg)
    write_synthetic_corpus(os.path.join(args.work_dir, "corpus"),
                           n_train_words=20_000 if args.rehearse
                           else 120_000, seed=args.seed)
    size = os.path.getsize(os.path.join(args.work_dir, "gpt2",
                                        "model.safetensors"))
    print_line(args.rehearse, "fixtures", True, seed=args.seed,
          model={"n_layer": cfg.n_layer, "n_embd": cfg.n_embd,
                 "n_head": cfg.n_head, "vocab_size": cfg.vocab_size},
          checkpoint_mb=round(size / 2 ** 20, 1),
          smoke_seconds=round(time.time() - t, 1))
    return 0


def _kernel_vs_oracle(seed, S, KV, G, D, bT, M, NB, L, dtype):
    """(max |paged Pallas kernel - gather oracle|, within tolerance?) on
    seeded pools with ragged lengths, at the engine's shapes, on the
    default device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mobilefinetuner_tpu.ops.decode_attention import (
        paged_attention, paged_decode_attention)
    from mobilefinetuner_tpu.serve.paged_kv import pool_shape
    rng = np.random.default_rng(seed)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (S, KV, G, D), dtype)
    shape = pool_shape(NB, L, KV, bT, D)
    pk = jax.random.normal(kk, shape, dtype)
    pv = jax.random.normal(kv, shape, dtype)
    tbl = jnp.asarray(rng.integers(1, NB, (S, M)), jnp.int32)
    lens = rng.integers(1, M * bT + 1, S)
    ok = jnp.asarray(np.arange(M * bT)[None, :] < lens[:, None])
    worst, close = 0.0, True
    for layer in (0, L - 1):
        fast = jax.jit(lambda *a: paged_decode_attention(
            *a, layer, ok, D ** -0.5))(q, pk, pv, tbl)
        slow = jax.jit(lambda *a: paged_attention(
            *a, layer, ok, D ** -0.5))(q, pk, pv, tbl)
        worst = max(worst, float(jnp.max(jnp.abs(fast - slow))))
        close &= bool(jnp.allclose(fast, slow, atol=KERNEL_TOL,
                                   rtol=KERNEL_TOL))
    return worst, close


def _serve_requests(seed, n, vocab, lo, hi, max_new, names):
    import numpy as np
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(lo, hi + 1))
        reqs.append({
            "prompt": [int(t) for t in rng.integers(1, vocab, plen)],
            "max_new_tokens": int(rng.integers(2, max_new + 1)),
            "adapter": (names[int(rng.integers(0, len(names) + 1)) - 1]
                        if names and rng.random() < 0.75 else None)})
    return reqs


def _run_requests(eng, reqs):
    """submit + drain; returns the terminal Request objects in order."""
    out = [eng.submit(r["prompt"], max_new_tokens=r["max_new_tokens"],
                      adapter=r["adapter"]) for r in reqs]
    eng.drain()
    return out


def child_serve(args) -> int:
    """numerics, then serve — one process, so one start-up on the chip."""
    _allow_host_cpu()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mobilefinetuner_tpu.core.compile_cache import (cache_counts,
                                                        enable_compile_cache)
    enable_compile_cache()
    sys.path.insert(0, os.path.join(HERE, "tools"))
    import serve_bench
    from mobilefinetuner_tpu.core.xla_stats import tpu_custom_calls
    from mobilefinetuner_tpu.models import gpt2
    sz = sizes(args.rehearse)
    on_chip = not args.rehearse
    rc = 0

    # ---- numerics: bf16 on the chip vs float32 on the host CPU --------
    import dataclasses
    from mobilefinetuner_tpu.core.config import GPT2Config
    t = time.time()
    cfg = (GPT2Config.gpt2_small() if on_chip else GPT2Config.tiny(
        vocab_size=sz["vocab"]))
    S = sz["numerics_S"]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(args.seed))
    ids = jax.random.randint(jax.random.PRNGKey(args.seed + 1), (1, S), 1,
                             cfg.vocab_size)
    fast = jax.jit(lambda p, i: gpt2.forward(
        cfg, p, i, compute_dtype=jnp.bfloat16))
    got = np.asarray(fast(params, ids), np.float32)
    cpu = jax.devices("cpu")[0]
    # the reference never takes a kernel: float32, the XLA attention
    ref_cfg = dataclasses.replace(cfg, attention_impl="xla")
    ref = jax.jit(lambda p, i: gpt2.forward(ref_cfg, p, i))
    want = np.asarray(ref(jax.device_put(params, cpu),
                          jax.device_put(ids, cpu)), np.float32)
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    kernels = tpu_custom_calls(
        fast.lower(params, ids).compile().as_text())
    good = (got.shape == (1, S, cfg.vocab_size) and np.isfinite(got).all()
            and rel <= LOGIT_TOL and (kernels > 0 or not on_chip))
    if not print_line(args.rehearse, "numerics", good, shape=list(got.shape),
                 max_abs_diff_over_max_abs_ref=rel, tol=LOGIT_TOL,
                 compared="logits, bf16 on the chip vs float32 on the "
                          "host CPU, same process",
                 tpu_custom_calls_in_forward=kernels,
                 smoke_seconds=round(time.time() - t, 1)):
        rc = 1
    del params, got, want

    # ---- serve: built the way tools/serve_bench.py builds it ----------
    t = time.time()
    eng, names = serve_bench.build_engine(
        sz["serve_model"], sz["slots"], sz["block_T"], sz["blocks"],
        sz["max_prompt"], sz["max_new"], adapters=2, dtype="bfloat16",
        telemetry_out=os.path.join(args.out, "serve.telemetry.jsonl"),
        seed=args.seed, on_step_error="raise")
    ck = Check()
    ck(eng.cfg.attn_impl == "auto" and eng.cfg.lora_impl == "auto",
       "the engine was not built with the auto rules")
    # warm-up: one request compiles prefill + decode step
    warm = eng.submit([1] * 8, max_new_tokens=2, adapter=names[0])
    eng.drain()
    ck(warm.state == "finished", f"warm-up ended {warm.state}")
    traces0 = dict(eng.trace_counts)
    ck(traces0.get("prefill") == 1 and traces0.get("decode_step") == 1,
       f"traces after warm-up {traces0}")
    reqs = _serve_requests(args.seed, sz["requests"], eng.config.vocab_size,
                           4, sz["max_prompt"], sz["max_new"], names)
    t_load = time.time()
    done = _run_requests(eng, reqs)
    load_s = time.time() - t_load
    states = {}
    for r in done:
        states[r.state] = states.get(r.state, 0) + 1
    ck(states == {"finished": len(reqs)}, f"terminal states {states}")
    asked = sum(r["max_new_tokens"] for r in reqs)
    returned = sum(len(r.tokens) for r in done)
    ck(all(len(d.tokens) == r["max_new_tokens"]
           for d, r in zip(done, reqs)),
       f"tokens returned {returned} != asked {asked}")
    ck(dict(eng.trace_counts) == traces0,
       f"retraced under load: {dict(eng.trace_counts)} vs {traces0}")
    compiled = eng.lower_decode_step().compile()
    kernels = tpu_custom_calls(compiled.as_text())
    if on_chip:
        ck(eng.attn_impl_resolved == "pallas",
           f"paged decode auto -> {eng.attn_impl_resolved!r}")
        ck(kernels > 0, "no tpu_custom_call in the decode-step program")
    L, KV, D = eng._pool_dims
    kernel_err = None
    if on_chip:
        kernel_err, close = _kernel_vs_oracle(
            args.seed, sz["slots"], KV, 1, D, sz["block_T"], eng.M,
            sz["blocks"], L, jnp.bfloat16)
        ck(close, f"paged kernel vs gather oracle: max abs diff "
                  f"{kernel_err} outside atol=rtol={KERNEL_TOL}")
    mem = compiled.memory_analysis()
    stats = jax.devices()[0].memory_stats() or {}
    eng.close()
    if not print_line(
            args.rehearse, "serve", not ck.bad, failures=ck.bad,
            requests=len(reqs), states=states, tokens_asked=asked,
            tokens_returned=returned, trace_counts=dict(eng.trace_counts),
            decode_steps=eng.decode_steps,
            auto_resolved={"paged_decode": eng.attn_impl_resolved,
                           "lora": eng.lora_impl_resolved,
                           "prefill_attention": "xla (S=%d < 512)"
                           % sz["max_prompt"]},
            pool_layout=eng.pool_layout,
            tpu_custom_calls_in_decode_step=kernels,
            kernel_vs_oracle_max_abs_diff=kernel_err,
            kernel_tol=KERNEL_TOL,
            decode_step_temp_mb=round(
                mem.temp_size_in_bytes / 2 ** 20, 1),
            peak_bytes_in_use=stats.get("peak_bytes_in_use"),
            mem_check=eng.mem_check.event(),
            compile_cache=cache_counts(),
            smoke_seconds=round(time.time() - t, 1),
            smoke_load_seconds=round(load_s, 2)):
        rc = 1
    return rc


def child_tp(args) -> int:
    """Tensor-parallel decode (mesh_tp over the chips) against the (1,1)
    engine of the same process, on the same requests."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mobilefinetuner_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.path.insert(0, os.path.join(HERE, "tools"))
    import serve_bench
    from mobilefinetuner_tpu.core.xla_stats import tpu_custom_calls
    from mobilefinetuner_tpu.models.generate import gpt2_decode_step_paged
    sz = sizes(args.rehearse)
    on_chip = not args.rehearse
    tp = sz["tp"]
    t = time.time()
    ck = Check()
    ck(jax.device_count() >= args.chips,
       f"{jax.device_count()} devices, need {args.chips}")
    if ck.bad:
        print_line(args.rehearse, "tp_decode", False, failures=ck.bad)
        return 2

    def build(mesh_tp):
        return serve_bench.build_engine(
            sz["serve_model"], sz["slots"], sz["block_T"], sz["blocks"],
            sz["max_prompt"], sz["max_new"], adapters=2, dtype="bfloat16",
            seed=args.seed, on_step_error="raise", mesh_tp=mesh_tp)

    one, names = build(1)
    par, _ = build(tp)
    reqs = _serve_requests(args.seed, sz["requests"] // 2,
                           one.config.vocab_size, 4, sz["max_prompt"],
                           sz["max_new"], names)
    agree = []
    for eng in (one, par):
        done = _run_requests(eng, reqs)
        ck(all(d.state == "finished"
               and len(d.tokens) == r["max_new_tokens"]
               for d, r in zip(done, reqs)),
           f"mesh_tp={eng.cfg.mesh_tp}: states "
           f"{sorted({d.state for d in done})}")
        agree.append([d.tokens for d in done])
    same = sum(a == b for a, b in zip(*agree))
    if on_chip:
        ck(par.attn_impl_resolved == "pallas",
           f"paged decode auto under tp -> {par.attn_impl_resolved!r}")

    # logits, not argmax tokens: ONE decode step of the model function
    # the engine jits, through each engine's own placement, on the same
    # seeded pool contents and slot state
    S, M, bT, NB = sz["slots"], one.M, sz["block_T"], sz["blocks"]
    rng = np.random.default_rng(args.seed)
    tbl = rng.integers(1, NB, (S, M)).astype(np.int32)
    pos = rng.integers(1, M * bT - 1, S).astype(np.int32)
    tok = rng.integers(1, one.config.vocab_size, S).astype(np.int32)
    aid = rng.integers(0, 3, S).astype(np.int32)
    kk, kv = jax.random.split(jax.random.PRNGKey(args.seed))
    pk = jax.random.normal(kk, one.pool_k.shape, jnp.bfloat16)
    pv = jax.random.normal(kv, one.pool_k.shape, jnp.bfloat16)

    def logits_of(eng):
        shd = eng.sharding
        put = ((lambda x: jax.device_put(x, shd.pool_sharding()))
               if shd is not None else jnp.asarray)

        def step(params, bank, pool_k, pool_v, tok, pos, tbl, aid):
            return gpt2_decode_step_paged(
                eng.config, params, pool_k, pool_v, tok, pos, tbl,
                lora=eng._route(bank, aid), compute_dtype=eng.dtype,
                attn_impl="auto", lora_impl="auto", shardings=shd)[0]

        fn = jax.jit(step)
        a = (eng.params, eng.bank.tree, put(pk), put(pv), eng._dev(tok),
             eng._dev(pos), eng._dev(tbl), eng._dev(aid))
        text = fn.lower(*a).compile().as_text()
        return np.asarray(fn(*a), np.float32), text

    l1, _ = logits_of(one)
    l4, text4 = logits_of(par)
    rel = float(np.max(np.abs(l4 - l1)) / np.max(np.abs(l1)))
    ck(np.isfinite(l4).all() and rel <= LOGIT_TOL,
       f"tp logits differ from (1,1): {rel} (tol {LOGIT_TOL})")
    kernels = tpu_custom_calls(text4)
    if on_chip:
        ck(kernels > 0, "no tpu_custom_call in the tp decode step")
    ck("all-reduce" in text4, "no all-reduce in the tp decode step")

    # placement by shards and by bytes
    def shard_devs(x):
        return sorted({s.device.id for s in x.addressable_shards
                       if s.data.size < x.size})
    blocks = par.params["blocks"]
    placed = {"pool_k": shard_devs(par.pool_k),
              "qkv_w": shard_devs(blocks["attn"]["qkv_w"]),
              "fc_w": shard_devs(blocks["mlp"]["fc_w"]),
              "attn_proj_w": shard_devs(blocks["attn"]["proj_w"]),
              "mlp_proj_w": shard_devs(blocks["mlp"]["proj_w"])}
    for name, devs in placed.items():
        ck(len(devs) == tp, f"{name} sharded over devices {devs}, "
                            f"want {tp} distinct")
    frac = (par.pool_k.addressable_shards[0].data.size
            / par.pool_k.size)
    ck(abs(frac - 1.0 / tp) < 1e-6, f"pool shard fraction {frac}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()[:args.chips]]
    one.close()
    par.close()
    ok = print_line(args.rehearse, "tp_decode", not ck.bad, failures=ck.bad,
               mesh=[1, tp], requests=len(reqs),
               requests_token_identical=same,
               max_abs_logit_diff_over_max_abs_ref=rel, tol=LOGIT_TOL,
               auto_resolved={"paged_decode": par.attn_impl_resolved,
                              "lora": par.lora_impl_resolved},
               tpu_custom_calls_in_tp_step=kernels,
               sharded_over_devices=placed,
               pool_shard_fraction=frac, bytes_in_use_by_device=in_use,
               smoke_seconds=round(time.time() - t, 1))
    return 0 if ok else 1


# --------------------------- the parent -------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, corpus, requests are all drawn from it")
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 = ONLY the cross-chip phases (FSDP training, "
                         "tensor-parallel decode) and what they are "
                         "compared with; needs four devices")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes: says so in every "
                         "line and never prints the chip line")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"),
        help="small outputs: per-phase logs and telemetry streams")
    ap.add_argument("--work_dir", default=os.path.join(
        HERE, ".chip_smoke_work"),
        help="large outputs: the checkpoint, corpus and adapters")
    ap.add_argument("--phase", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return {"device": child_device, "serve": child_serve,
                "tp": child_tp}[args.phase](args)

    smoke = Smoke(args)
    print(json.dumps({
        "phase": "start", "chips": args.chips, "seed": args.seed,
        "compile_cache_dir": cache_dir(), "out": smoke.out,
        **({"rehearsal": True} if smoke.rehearse else {})}), flush=True)
    dev = smoke.self_child("device", cap_s=300.0)
    if dev is not None:
        smoke.device = dev[0]["device"]
        phases = ([phase_train, phase_eval,
                   lambda s: s.self_child("serve") is not None]
                  if args.chips == 1 else
                  [phase_fsdp, lambda s: s.self_child("tp") is not None])
        for run in phases:
            if smoke.left() < 30:
                smoke.line("budget", False,
                           reason=f"{BUDGET_S:.0f} s budget spent")
                break
            run(smoke)
    passed = smoke.failed is None
    total = round(time.time() - smoke.t0, 1)
    if smoke.rehearse:
        print(json.dumps({"rehearsal": True, "rehearsal_passed": passed,
                          "failed": smoke.failed, "device": smoke.device,
                          "smoke_seconds": total}), flush=True)
        return 0 if passed else 1
    if not passed:
        print(json.dumps({"ok": False, "failed": smoke.failed,
                          "device": smoke.device,
                          "smoke_seconds": total}), flush=True)
        return 1
    print(json.dumps({"phase": "done", "ok": True,
                      "smoke_seconds": total}), flush=True)
    print(json.dumps({"ok": True, "device": smoke.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
