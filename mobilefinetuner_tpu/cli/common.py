"""Shared CLI machinery: flag groups mirroring the reference binaries'
surfaces, governor/offload/mesh wiring, and the generic training loop driver.

Reference flag surfaces: gpt2_lora_finetune/main.cpp:80-171 (CmdArgs
defaults), train_lora_gemma.cpp parse block, eval_ppl.cpp, eval_mmlu.cpp.
TPU-native additions beyond the reference: --dtype (bf16 compute), --remat
(gradient checkpointing), --mesh_data/--mesh_fsdp (multi-chip mesh), and
optimizer-state save/resume (the reference leaves Adam state unwired,
SURVEY.md §5 Checkpoint/resume).
"""

from __future__ import annotations

import argparse
import contextlib
import itertools
import math
import os
import statistics
import time
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

# enable_compile_cache is re-exported: every train CLI's main() calls
# common.enable_compile_cache() before anything can compile
from mobilefinetuner_tpu.core.compile_cache import (cache_counts,
                                                    enable_compile_cache)
from mobilefinetuner_tpu.core.logging import (JSONLWriter, MetricsLogger,
                                              get_logger)
from mobilefinetuner_tpu.core.preempt import EXIT_PREEMPTED, PreemptionGuard
from mobilefinetuner_tpu.core.telemetry import (GoodputMeter, HangWatchdog,
                                                SpikeConfig, SpikeDetector,
                                                Telemetry, device_peak_flops,
                                                mfu_from, run_manifest)
from mobilefinetuner_tpu.core.xla_stats import (compiled_flops,
                                                compiled_peak_mb,
                                                live_hbm_mb,
                                                tpu_custom_calls)
from mobilefinetuner_tpu.data.prefetch import Prefetcher
from mobilefinetuner_tpu.data.wikitext2 import WikiText2Dataset
from mobilefinetuner_tpu.ops.loss import perplexity_from_loss
from mobilefinetuner_tpu.parallel.mesh import (make_batch_placer, make_mesh,
                                               params_shardings,
                                               replicated_sharding)
from mobilefinetuner_tpu.parallel.offload import (OffloadConfig,
                                                  apply_placement, fetch,
                                                  placement_stats,
                                                  plan_placement)
from mobilefinetuner_tpu.system.governor import GovernorConfig, StepGovernor
from mobilefinetuner_tpu.train.trainer import (StepClock, TrainConfig,
                                               init_optimizer,
                                               make_eval_step,
                                               make_train_step)

log = get_logger()


# --------------------------- flag groups ------------------------------------

def add_train_flags(p: argparse.ArgumentParser, lr: float = 1e-4,
                    seq_len: int = 128, batch_size: int = 1):
    """Training hparams (gpt2_lora_finetune/main.cpp CmdArgs defaults)."""
    g = p.add_argument_group("training")
    g.add_argument("--epochs", type=int, default=0,
                   help="epochs (overrides steps when > 0)")
    g.add_argument("--steps", type=int, default=0, help="training steps")
    g.add_argument("--batch_size", type=int, default=batch_size,
                   help="micro-batch size per accumulation step")
    g.add_argument("--grad_accum_steps", "--grad_accum", type=int, default=1)
    g.add_argument("--seq_len", type=int, default=seq_len)
    g.add_argument("--lr", type=float, default=lr)
    g.add_argument("--weight_decay", type=float, default=0.0)
    g.add_argument("--warmup_steps", type=int, default=0)
    g.add_argument("--warmup_ratio", type=float, default=None,
                   help="overrides warmup_steps when set")
    g.add_argument("--clip_grad_norm", "--max_grad_norm", type=float,
                   default=1.0)
    g.add_argument("--lr_schedule", choices=["cosine", "linear", "constant"],
                   default="cosine")
    g.add_argument("--data_fraction", type=float, default=1.0)
    g.add_argument("--log_interval", type=int, default=1)
    g.add_argument("--eval_interval", type=int, default=0)
    g.add_argument("--eval_batches", type=int, default=50)
    g.add_argument("--eval_batch_size", type=int, default=2)
    g.add_argument("--save_every", type=int, default=0)
    g.add_argument("--async_save", type=int, default=1,
                   help="1 = snapshot-then-write checkpointing "
                        "(io/async_ckpt.py): at a save step the loop "
                        "blocks only for a batched device->host "
                        "snapshot; key-mapping, bf16 encode, and the "
                        "safetensors write run on a background thread "
                        "(depth-1 queue — a save landing while one is "
                        "in flight coalesces to the newest snapshot "
                        "with a ckpt_dropped telemetry event; final "
                        "saves drain). 0 = fully synchronous oracle. "
                        "Files are byte-identical either way and every "
                        "writer publishes atomically (tmp+fsync+rename "
                        "— a kill mid-write cannot corrupt the "
                        "checkpoint --resume_from loads); telemetry's "
                        "checkpoint event splits snapshot_ms (blocking) "
                        "from write_ms/bytes/mb_s (background)")
    g.add_argument("--ema_beta", type=float, default=0.9)
    g.add_argument("--seed", type=int, default=42)
    g.add_argument("--coupled_weight_decay", action="store_true",
                   help="L2-into-gradient decay for reference parity "
                        "(adam.cpp:65-67); default is decoupled AdamW")
    g.add_argument("--metrics_csv", default="",
                   help="CSV metrics sink (logger.h:131-190 analog)")
    # TPU-native knobs
    g.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="float32", help="compute dtype")
    g.add_argument("--remat", action="store_true",
                   help="gradient checkpointing over the layer scan")
    g.add_argument("--attention_impl", choices=["auto", "xla", "flash"],
                   default="auto",
                   help="'auto' picks per shape (flash from S >= 512 at "
                        "D <= 128, S >= 2048 at D = 256; measured e2e on "
                        "v5e, ops/attention.resolve_impl); 'flash' = "
                        "Pallas block-sparse kernel; 'xla' = plain fused "
                        "attention")
    g.add_argument("--lora_impl", choices=["auto", "naive", "fused"],
                   default="auto",
                   help="LoRA hot-path implementation "
                        "(models/lora_apply.py, DESIGN.md §17): 'naive' "
                        "= the parity oracle, fixed (x@A)@B order; "
                        "'fused' = shape-aware contraction order + the "
                        "Pallas epilogue kernels at eligible sites (the "
                        "[N, d_out] adapter delta never round-trips "
                        "HBM); 'auto' resolves per call site — fused "
                        "where the kernel is eligible and the delta is "
                        "memory-bound, else naive. All impls accumulate "
                        "the rank-r bottleneck in f32; value+grad "
                        "parity is pinned by tests/test_lora.py. The "
                        "per-target resolution is logged in the "
                        "telemetry run_start manifest")
    g.add_argument("--no_model_dropout", action="store_true",
                   help="zero the checkpoint's embd/resid/attn pdrop "
                        "(HF GPT-2 configs carry 0.1; dropout changes "
                        "loss curves — both attention impls support "
                        "train-mode attn dropout, the flash kernel via "
                        "its in-kernel hash mask)")
    g.add_argument("--profile_dir", default="",
                   help="emit a jax.profiler trace of a few steady-state "
                        "steps to this directory (the reference's "
                        "performance_monitor.h analog; view with "
                        "tensorboard/xprof)")
    g.add_argument("--profile_start", type=int, default=10,
                   help="first profiled step (past compile+warmup)")
    g.add_argument("--profile_steps", type=int, default=5)
    g.add_argument("--prefetch", type=int, default=2,
                   help="async input pipeline (data/prefetch.py): a "
                        "background thread produces host batches into a "
                        "bounded queue of this depth, and batch k+1's "
                        "device placement is issued while step k "
                        "computes. 0 = fully synchronous kill-switch. "
                        "The batch sequence is byte-identical either "
                        "way (incl. resume and multi-host sharding); "
                        "the metrics' host_wait_ms column shows what "
                        "the overlap buys")
    g.add_argument("--telemetry_out", default="",
                   help="append-only JSONL run-telemetry stream "
                        "(core/telemetry.py): run_start manifest, "
                        "compile, step_stats (loss/mfu/tok_s/health), "
                        "throttle/eval/checkpoint/anomaly, run_end. "
                        "Under multi-host every process writes: the "
                        "coordinator to this path, host k to "
                        "PATH.host<k> (merge with tools/"
                        "fleet_report.py); appending to an existing "
                        "file continues its sequence numbers "
                        "(crash/resume). Render with "
                        "tools/telemetry_report.py")
    g.add_argument("--run_registry", default="",
                   help="append-only run registry stream "
                        "(core/run_registry.py, DESIGN.md §28): one "
                        "crash-safe `run` record per invocation — id, "
                        "git rev, config fingerprint, mesh, platform, "
                        "artifacts, terminal status — finalized on any "
                        "exit path; a SIGKILLed run is settled to "
                        "'interrupted' on the next registry open. "
                        "Default: $MFT_RUN_REGISTRY; empty = off. "
                        "Query with tools/observatory.py; resolve runs "
                        "by id/rev in bench_compare/telemetry_report/"
                        "fleet_report via --run")
    g.add_argument("--spike_z", type=float, default=8.0,
                   help="loss-spike detector: emit an `anomaly` "
                        "telemetry event when a step's loss exceeds "
                        "this many EMA standard deviations (host-side, "
                        "on the flushed metrics; <= 0 disables)")
    g.add_argument("--spike_beta", type=float, default=0.98,
                   help="EMA decay of the spike detector's running "
                        "mean/variance")
    g.add_argument("--spike_warmup", type=int, default=20,
                   help="steps observed before the spike detector arms "
                        "(early-training loss is legitimately wild)")
    # fleet observability (DESIGN.md §14)
    g.add_argument("--watchdog", type=int, default=1,
                   choices=[0, 1, 2],
                   help="hang watchdog: a daemon thread dumps every "
                        "Python thread's stack (faulthandler) and emits "
                        "a `hang` telemetry event when no step completes "
                        "within watchdog_mult x the rolling-median step "
                        "time. 0 = off (kill-switch), 1 = report and "
                        "keep waiting (deadline backs off 2x), 2 = "
                        "report then abort the process (exit 113 — for "
                        "pods where a wedged collective should fail "
                        "fast instead of burning the reservation)")
    g.add_argument("--watchdog_mult", type=float, default=10.0,
                   help="hang deadline = this many rolling-median step "
                        "times (floored at --watchdog_min_s)")
    g.add_argument("--watchdog_min_s", type=float, default=60.0,
                   help="hang deadline floor in seconds; also the "
                        "pre-first-step grace (compile/eval/checkpoint "
                        "pauses suspend the clock, so they need no "
                        "extra padding)")
    g.add_argument("--straggler_cadence", type=int, default=0,
                   help="every K steps gather each host's median step "
                        "time across the fleet (collective; "
                        "deterministic cadence), stamp the per-host "
                        "map into step_stats.host_step_ms, and emit a "
                        "`straggler` event for any host slower than "
                        "straggler_mult x the fleet median. 0 = off "
                        "(default: single-host runs have nothing to "
                        "compare)")
    g.add_argument("--straggler_mult", type=float, default=1.5,
                   help="straggler threshold: host median step time vs "
                        "fleet median")
    # live observability plane (DESIGN.md §22)
    g.add_argument("--trace_spans", type=int, default=0, choices=[0, 1],
                   help="1 = emit `span` events (core/trace.py) into "
                        "the telemetry stream: the goodput phases "
                        "(init/compile/step/input_wait/eval/checkpoint/"
                        "...) on a 'phase' track, each async checkpoint "
                        "write on 'ckpt', each prefetch-producer batch "
                        "on 'prefetch' — one tools/trace_export.py run "
                        "turns the stream into a Perfetto-loadable "
                        "timeline whose per-phase span sums reconcile "
                        "with run_end's goodput buckets. Opt-in: a "
                        "traced loop emits a handful of events per "
                        "step. Requires --telemetry_out")
    g.add_argument("--auto_profile", type=int, default=0, choices=[0, 1],
                   help="1 = flight recorder: arm a ONE-SHOT "
                        "jax.profiler capture when a sensor fires — a "
                        "flush interval slower than "
                        "auto_profile_slow_mult x the rolling median, "
                        "a loss_spike/divergence anomaly, a straggler "
                        "attribution, or the hang watchdog pre-exit — "
                        "saving the device trace of the BAD step next "
                        "to the stack dumps (a pre-scheduled "
                        "--profile_dir window cannot catch these). "
                        "Each capture emits a `profile_capture` event; "
                        "cooldown + budget bound the disk cost")
    g.add_argument("--auto_profile_dir", default="",
                   help="capture root (default: <telemetry_out>"
                        ".profiles); each capture lands in its own "
                        "cap<k>_<trigger>_step<n> subdirectory")
    g.add_argument("--auto_profile_steps", type=int, default=2,
                   help="steps per triggered capture")
    g.add_argument("--auto_profile_cooldown", type=float, default=300.0,
                   help="seconds between captures (a persistently sick "
                        "run produces a few traces, not a disk full)")
    g.add_argument("--auto_profile_budget", type=int, default=2,
                   help="max captures per run")
    g.add_argument("--auto_profile_slow_mult", type=float, default=3.0,
                   help="slow-step trigger: capture when a flush "
                        "interval's per-step time exceeds this multiple "
                        "of the rolling median (<= 0 disables the "
                        "slow-step sensor; anomaly/straggler/hang "
                        "triggers stay armed)")
    g.add_argument("--metrics_port", type=int, default=0,
                   help="serve a live OpenMetrics /metrics endpoint + "
                        "/healthz on this port (core/metrics_http.py): "
                        "step-time/TTFT histograms, tok/s, MFU, live "
                        "HBM, queue depth, goodput fractions, skip/"
                        "rollback/degrade counters — fed from the same "
                        "emit path the telemetry sink uses (no second "
                        "instrumentation layer, zero added device "
                        "syncs). Coordinator-only under multi-host. "
                        "0 = off")
    g.add_argument("--metrics_addr", default="127.0.0.1",
                   help="bind address for --metrics_port (default "
                        "loopback: the endpoint exposes operational "
                        "detail; exporting it beyond the host is an "
                        "explicit decision)")
    # elastic fleet (DESIGN.md §18)
    g.add_argument("--on_preempt", choices=["drain", "off"],
                   default="drain",
                   help="SIGTERM/SIGINT handling (core/preempt.py): "
                        "'drain' (default) finishes the step in flight, "
                        "takes ONE final atomic checkpoint through the "
                        "async checkpointer, ends the telemetry stream "
                        "with run_end{reason=preempted}, and exits with "
                        f"the resumable code {EXIT_PREEMPTED} — a "
                        "preemption notice costs one step plus one "
                        "drain instead of the steps since the last "
                        "periodic save (a second signal aborts the "
                        "drain). 'off' keeps default signal behavior")
    g.add_argument("--data_retries", type=int, default=3,
                   help="bounded retry budget for transient I/O errors "
                        "on the streaming data refetch path (shared-"
                        "filesystem hiccups under a fleet): each retry "
                        "backs off exponentially with jitter and emits "
                        "an anomaly{kind=data_retry} telemetry event; "
                        "after the budget the original error raises. "
                        "0 = fail fast")
    g.add_argument("--data_backoff_s", type=float, default=0.5,
                   help="base backoff for --data_retries (doubles per "
                        "attempt, +25%% jitter to desynchronize a fleet "
                        "retrying the same filesystem)")
    # numerical-fault recovery (DESIGN.md §20)
    g.add_argument("--skip_nonfinite", type=int, default=0,
                   help="1 = guarded update: when a step's gradients "
                        "carry any non-finite element (or the global "
                        "grad norm is non-finite) the Adam update "
                        "degenerates to identity INSIDE the compiled "
                        "step (params/opt state pass through a "
                        "jnp.where tree-select; donation, shardings, "
                        "and the LR schedule untouched) and a "
                        "`skipped` count rides step_stats with zero "
                        "added syncs. A clean run is byte-identical "
                        "with the guard on or off. 0 = off (a NaN "
                        "grad poisons the params, as before)")
    g.add_argument("--rollback_budget", type=int, default=0,
                   help="> 0 arms in-process rollback: on sustained "
                        "divergence (anomaly{kind=divergence}), a "
                        "streak of --rollback_skip_streak skipped/"
                        "nonfinite steps, or a nonfinite loss with the "
                        "skip guard off, the loop reloads the newest "
                        "VERIFIED lineage checkpoint + .opt sidecar "
                        "without restarting the process or recompiling "
                        "the step, fast-forwards the data stream, and "
                        "keeps training — at most this many times per "
                        "run (each decision emits a `rollback` event). "
                        "Requires --save_every checkpoints. 0 = off")
    g.add_argument("--rollback_skip_streak", type=int, default=3,
                   help="consecutive skipped-update/nonfinite-loss "
                        "steps that trigger a rollback (a single "
                        "skipped step is the guard doing its job, not "
                        "a reason to lose progress)")
    g.add_argument("--rollback_data_offset", type=int, default=1,
                   help="extra data-stream steps skipped per rollback "
                        "so the replayed window sees a DIVERGED batch "
                        "sequence (a deterministically poisonous batch "
                        "must not be replayed verbatim); 0 replays the "
                        "byte-pinned original sequence")
    g.add_argument("--keep_ckpts", type=int, default=0,
                   help="retain only the K newest step-tagged "
                        "checkpoints in the lineage (<final>.lineage."
                        "json), GC'ing older files AFTER the pruned "
                        "lineage publishes atomically (a kill mid-GC "
                        "leaves orphans, never a lineage naming "
                        "deleted files); the final artifact is never "
                        "pruned. 0 = keep all")
    g.add_argument("--verify_ckpt", type=int, default=1,
                   help="1 = verify the per-tensor checksum manifest "
                        "on every checkpoint load (--resume_from and "
                        "rollback): a corrupt/truncated/stale file is "
                        "rejected with a ckpt_verify{ok=false} event "
                        "and the load falls back down the lineage "
                        "chain instead of crashing or silently "
                        "loading garbage. 0 = trust the newest file")
    g.add_argument("--inject", default="",
                   help="fault-injection harness (the multihost_smoke/"
                        "serve_bench --inject pattern, CPU-testable): "
                        "grad_nan:<step>[:<n>] poisons n (default 1) "
                        "consecutive step batches with NaN so the "
                        "gradients go non-finite; loss_spike:<step>"
                        "[:<n>] scrambles n batches' labels (loss "
                        "level-shift); ckpt_corrupt flips a byte in "
                        "the newest lineage checkpoint after its "
                        "first periodic save; hbm_pressure:<mb> "
                        "allocates <mb> MB of device ballast before "
                        "compile (on TPU a real RESOURCE_EXHAUSTED "
                        "follows; backends that cannot genuinely OOM "
                        "raise a simulated one at the first dispatch) "
                        "— drives the --on_oom_risk degradation "
                        "ladder end to end. Each fires ONCE per "
                        "process (latched), so a post-rollback replay "
                        "of the same steps runs clean")
    add_mem_flags(p)


def add_mem_flags(p: argparse.ArgumentParser):
    """Memory-admission knobs (core/memory_guard.py, DESIGN.md §21) —
    shared by the train CLIs (full preflight + degradation ladder) and
    the eval CLIs (preflight only: eval has no ladder, so 'degrade'
    behaves like 'warn' there)."""
    g = p.add_argument_group("memory admission (DESIGN.md §21)")
    g.add_argument("--hbm_cap_mb", type=int, default=0,
                   help="per-device memory capacity override in MB for "
                        "the admission preflight; 0 = auto (the "
                        "backend's memory_stats bytes_limit, else a "
                        "device-kind table of public HBM sizes). The "
                        "override is what lets CPU tests drive the "
                        "verdict deterministically")
    g.add_argument("--hbm_headroom", type=float, default=0.1,
                   help="admission margin: a config is OVER when its "
                        "estimate exceeds capacity x (1 - headroom) — "
                        "runtime allocations the compile-time analysis "
                        "cannot see (collectives scratch, fragmentation) "
                        "need somewhere to live")
    g.add_argument("--on_oom_risk", choices=["fail", "degrade", "warn"],
                   default="degrade",
                   help="what a failed admission does: 'fail' raises a "
                        "named MemoryAdmissionError immediately after "
                        "compile — before data loading, not 40 steps in "
                        "(the r13 controller reads it as an inadmissible "
                        "CONFIG, not a restartable crash); 'degrade' "
                        "(default) walks the bounded ladder — enable "
                        "--remat, double grad-accum at constant global "
                        "batch, enable weight offload/streaming — "
                        "recompiling and re-preflighting at each rung "
                        "(each decision is a `degrade` telemetry event; "
                        "loss trajectory stays parity-pinned <=1e-5), "
                        "raising the named error with the attempted "
                        "ladder when the last rung still does not fit; "
                        "'warn' logs and proceeds (the pre-round-16 "
                        "behavior). A RESOURCE_EXHAUSTED caught at "
                        "compile or first dispatch takes the same "
                        "ladder. Verdict 'unknown' (no capacity source) "
                        "always proceeds — admission never refuses on a "
                        "guess")
    g.add_argument("--prefetch_rss_mb", type=int, default=0,
                   help="host-RSS shed guard for the async input "
                        "pipeline: while the process RSS exceeds this "
                        "many MB the producer stops assembling "
                        "lookahead batches until the queue drains "
                        "(degrade toward depth-1 instead of the OS "
                        "OOM-killer picking a victim). 0 = off")


def add_align_flags(p: argparse.ArgumentParser):
    """Alignment-harness flags (train_lora_gemma.cpp:620-920 analog)."""
    g = p.add_argument_group("alignment harness")
    g.add_argument("--align_dump_dir", default="",
                   help="align mode: dump one batch's activations/grads/"
                        "post-step adapter as npy and exit; compare with "
                        "tools/align_torch_mirror.py")
    g.add_argument("--align_steps", type=int, default=5,
                   help="steps of the align-mode loss curve")


def add_pm_flags(p: argparse.ArgumentParser):
    """Energy-governor flags (CmdArgs pm_* block; pm_interval=0 disables)."""
    g = p.add_argument_group("step governor (pm_*)")
    g.add_argument("--pm_interval", type=int, default=0,
                   help="telemetry check every K steps; 0 disables")
    g.add_argument("--pm_batt_thresh", type=float, default=20.0)
    g.add_argument("--pm_temp_thresh", type=float, default=42.0)
    g.add_argument("--pm_fb_high", type=float, default=2.0)
    g.add_argument("--pm_fb_low", type=float, default=0.5)
    g.add_argument("--pm_ft_high", type=float, default=2.0)
    g.add_argument("--pm_ft_low", type=float, default=0.5)
    g.add_argument("--pm_manual_batt", type=float, default=100.0)
    g.add_argument("--pm_manual_temp", type=float, default=30.0)
    g.add_argument("--pm_disable_batt", action="store_true")
    g.add_argument("--pm_disable_temp", action="store_true")
    g.add_argument("--pm_schedule", default="",
                   help='deterministic override, e.g. "0-99:300,100-:50"')


def add_shard_flags(p: argparse.ArgumentParser):
    """Offload flags (CmdArgs shard_* block). --shard_dir is accepted for
    reference-CLI compatibility but unused: the offload tier is pinned host
    RAM, not disk (parallel/offload.py)."""
    g = p.add_argument_group("parameter offload (shard_*)")
    g.add_argument("--shard_enable", action="store_true")
    g.add_argument("--shard_dir", default="",
                   help="ignored (offload targets host RAM, not disk)")
    g.add_argument("--shard_budget_mb", type=int, default=512,
                   help="HBM budget for resident frozen params")
    g.add_argument("--shard_fp16_disk", type=int, default=1,
                   help="1 = store offloaded params as bf16 (TPU-idiomatic "
                        "16-bit; analog of fp16-on-disk quantization)")
    g.add_argument("--shard_stream", type=int, default=1,
                   help="1 = stream offloaded block weights host->HBM one "
                        "layer at a time inside the layer scan (bounds peak "
                        "HBM like the reference's per-layer require(), "
                        "parameter_sharder.cpp:242-271); 0 = whole-tree "
                        "fetch per step (budget governs idle placement "
                        "only)")


def add_mesh_flags(p: argparse.ArgumentParser):
    g = p.add_argument_group("device mesh")
    g.add_argument("--mesh_data", type=int, default=1,
                   help="data-parallel mesh axis size")
    g.add_argument("--mesh_fsdp", type=int, default=1,
                   help="fsdp mesh axis size; 0 = all remaining devices "
                        "(default 1 = single chip, like the reference; "
                        "multi-chip is opt-in)")
    g.add_argument("--sequence_parallel", action="store_true",
                   help="long-context mode: shard the SEQUENCE axis over "
                        "the fsdp mesh axis and run ring attention "
                        "(parallel/ring_attention.py); seq_len must "
                        "divide by mesh_fsdp")
    g.add_argument("--multihost", action="store_true",
                   help="multi-process run: bring up jax.distributed "
                        "(auto-detected on TPU pods) and lay the mesh out "
                        "DCN-aware (fsdp on ICI within a host, data "
                        "across hosts; parallel/distributed.py)")
    g.add_argument("--dist_coordinator", default="",
                   help="coordinator host:port (or JAX_COORDINATOR_ADDRESS; "
                        "omit on TPU pods — auto-detected)")
    g.add_argument("--dist_num_processes", type=int, default=0,
                   help="process count (or JAX_NUM_PROCESSES; 0 = auto)")
    g.add_argument("--dist_process_id", type=int, default=-1,
                   help="this process's id (or JAX_PROCESS_ID; -1 = auto)")


def governor_from_args(args, event_sink=None) -> StepGovernor:
    cfg = GovernorConfig(
        enable=args.pm_interval > 0 or bool(args.pm_schedule),
        # 0 = telemetry disabled: a schedule-only run stays full speed on
        # uncovered steps; pm_interval > 0 makes uncovered steps fall
        # through to the telemetry policy (reference PowerMonitor).
        check_interval_steps=args.pm_interval,
        battery_threshold=args.pm_batt_thresh,
        temp_threshold=args.pm_temp_thresh,
        freq_batt_high=args.pm_fb_high,
        freq_batt_low=args.pm_fb_low,
        freq_temp_high=args.pm_ft_high,
        freq_temp_low=args.pm_ft_low,
        schedule=args.pm_schedule,
        manual_battery=None if args.pm_disable_batt else args.pm_manual_batt,
        manual_temp=None if args.pm_disable_temp else args.pm_manual_temp,
    )
    return StepGovernor(cfg, event_sink=event_sink)


def offload_config_from_args(args) -> OffloadConfig:
    return OffloadConfig(
        enable=bool(args.shard_enable),
        max_resident_bytes=args.shard_budget_mb * 1024 * 1024,
        offload_dtype="bfloat16" if args.shard_fp16_disk else "float32")


def build_mesh(args):
    """Returns (mesh, cp_mesh): cp_mesh is the mesh again when
    --sequence_parallel is set (pass it to the model forwards so ring
    attention engages), else None — deriving it HERE keeps every CLI's
    wiring consistent. The mesh itself goes to the model forwards too
    (mesh=): over a batch-parallel mesh the attention dispatcher runs
    the flash kernels under shard_map, so --attention_impl means over a
    mesh what it means on one chip; --lora_impl's `auto` still becomes
    the naive path here (its kernel is not wrapped). --multihost (or
    JAX_* env) first brings up the distributed runtime and switches to
    the DCN-aware hybrid layout."""
    from mobilefinetuner_tpu.parallel.distributed import (initialize,
                                                          make_hybrid_mesh)
    # initialize() no-ops without --multihost / --dist_coordinator /
    # JAX_COORDINATOR_ADDRESS-style env, so the env-var-only launch mode
    # works without any flag
    multi = initialize(
        coordinator=getattr(args, "dist_coordinator", ""),
        num_processes=getattr(args, "dist_num_processes", 0) or None,
        process_id=(getattr(args, "dist_process_id", -1)
                    if getattr(args, "dist_process_id", -1) >= 0 else None),
        force=getattr(args, "multihost", False))
    n = len(jax.devices())
    multi = multi or jax.process_count() > 1
    if multi:
        # the mesh must span every process's devices, so (data, fsdp) is
        # interpreted globally. mesh_fsdp=0 keeps its "all remaining"
        # meaning, resolved hierarchy-aware: fsdp = one host's ICI domain
        # (or the global remainder when an explicit data size is given).
        fsdp = args.mesh_fsdp
        if fsdp == 0:
            fsdp = (n // args.mesh_data if args.mesh_data > 1
                    else len(jax.local_devices()))
        data = args.mesh_data if args.mesh_data > 1 else n // fsdp
        mesh = make_hybrid_mesh(data=data, fsdp=fsdp)
    else:
        data = args.mesh_data
        fsdp = args.mesh_fsdp or (n // max(data, 1))
        mesh = make_mesh(data=data, fsdp=fsdp,
                         devices=jax.devices()[:data * fsdp])
    size = data * fsdp
    sp = getattr(args, "sequence_parallel", False)
    if size < n:
        # the mesh flags default to one device: say so on a multi-chip
        # host instead of quietly leaving chips idle
        log.info(f"mesh: using {size} of {n} devices "
                 f"(--mesh_data/--mesh_fsdp select more)")
    if size > 1:
        log.info(f"mesh: data={data} fsdp={fsdp}"
                 + (f" over {jax.process_count()} processes" if multi
                    else "")
                 + (" (sequence-parallel)" if sp else ""))
        # one validation block for both layouts: batch shards over the
        # whole mesh (or just "data" under sequence parallelism, where
        # "fsdp" carries the sequence axis instead)
        b_div = max(data, 1) if sp else size
        b_axis = f"mesh_data={data}" if sp else f"the mesh size {size}"
        if args.batch_size % b_div != 0:
            raise SystemExit(
                f"batch_size={args.batch_size} (the "
                f"{'GLOBAL ' if multi else ''}micro-batch) must be "
                f"divisible by {b_axis}")
        if sp and args.seq_len % fsdp != 0:
            raise SystemExit(
                f"seq_len={args.seq_len} must divide by mesh_fsdp={fsdp} "
                f"in sequence-parallel mode")
        if (multi and getattr(args, "eval_interval", 0)
                and getattr(args, "eval_batch_size", 1) % b_div != 0):
            raise SystemExit(
                f"eval_batch_size={args.eval_batch_size} must be "
                f"divisible by {b_axis} under multi-host (eval batches "
                f"shard like train batches)")
        # the partitioned step cannot hold a Mosaic kernel outside
        # shard_map (ops/pallas_util.partitioned_impl): the LoRA
        # epilogue is not wrapped, so `auto` becomes the naive path HERE,
        # where the mesh is known. --attention_impl is left as given:
        # the attention dispatcher wraps the flash kernels over the
        # batch axes itself (ops/attention.attention, which the model
        # forwards hand this mesh), and under sequence parallelism
        # attention is the ring.
        if hasattr(args, "lora_impl"):
            from mobilefinetuner_tpu.ops.pallas_util import \
                partitioned_impl
            try:
                args.lora_impl = partitioned_impl(
                    "--lora_impl", args.lora_impl, "fused", "naive", size)
            except ValueError as e:
                raise SystemExit(str(e))
    return mesh, (mesh if sp else None)


# --------------------------- loop helpers -----------------------------------

def load_full_resume(path: str):
    """Raw HF-keyed tensor dict from a full-model resume source: an HF
    checkpoint dir (single-file or sharded) or a single safetensors file.
    Shared by the full-FT CLIs (gpt2_full_finetune, gemma_full_finetune)
    so the load idiom cannot drift between them."""
    from mobilefinetuner_tpu.io.checkpoints import load_hf_state_dict
    if os.path.isdir(path):
        return load_hf_state_dict(path)
    from mobilefinetuner_tpu.io.safetensors_io import SafeTensorsReader
    return SafeTensorsReader(path).load_all(promote_to_f32=True)


def resolve_total_steps(args, steps_per_epoch: int) -> int:
    """epochs overrides steps (reference CmdArgs semantics)."""
    if args.epochs > 0:
        return max(args.epochs * steps_per_epoch, 1)
    if args.steps > 0:
        return args.steps
    return max(steps_per_epoch, 1)  # default: one epoch


def train_config_from_args(args, total_steps: int) -> TrainConfig:
    if args.warmup_ratio is not None:
        warmup_ratio = args.warmup_ratio
    else:
        warmup_ratio = args.warmup_steps / max(total_steps, 1)
    return TrainConfig(
        total_steps=total_steps, lr=args.lr, warmup_ratio=warmup_ratio,
        schedule=args.lr_schedule, clip_grad_norm=args.clip_grad_norm,
        grad_accum_steps=args.grad_accum_steps,
        weight_decay=args.weight_decay,
        coupled_weight_decay=args.coupled_weight_decay,
        skip_nonfinite=bool(getattr(args, "skip_nonfinite", 0)))


def micro_batches(dataset: WikiText2Dataset, accum: int,
                  skip_steps: int = 0) -> Iterator[tuple]:
    """Yield (epoch, [accum*micro_b, ...] step batch) forever, cycling
    epochs (the reference's per-step micro-batch pulls, main.cpp:569-583).

    The step batch is assembled ONCE: chunk rows are written straight
    into preallocated [accum*b, S] arrays (dataset.fill_rows) instead of
    per-micro-batch np.stack followed by an np.concatenate over the
    accumulation — that was two full copies of every step batch on the
    host critical path. The arrays are freshly allocated per step, never
    reused: the async prefetch queue (data/prefetch.py) may hold several
    step batches at once, and a recycled buffer would corrupt them.

    skip_steps fast-forwards the stream past batches an interrupted run
    already consumed, WITHOUT building them — a resumed run continues the
    exact data order of an uninterrupted one (same seed => same per-epoch
    shuffles) instead of replaying epoch 0 from the top."""
    nb = dataset.num_batches()
    if nb == 0:
        raise ValueError(
            "dataset yields zero batches (num_chunks < batch_size with "
            "drop_last=True — seq_len/batch_size too large or "
            "--data_fraction too small for this split)")
    b = dataset.config.batch_size
    S = dataset.config.seq_len
    # the stream is continuous across epochs (a partial accumulation at an
    # epoch boundary carries into the next epoch), so step s consumes
    # micro-batches [s*accum, (s+1)*accum) of the concatenated stream
    epoch, bi = divmod(skip_steps * accum, nb)
    order = dataset.chunk_order(epoch)
    while True:
        # collect the step's chunk-index slices first — they can cross an
        # epoch boundary (reshuffling the order) and, without drop_last,
        # the final slice of an epoch may be short — then fill one buffer
        slices = []
        for _ in range(accum):
            if bi >= nb:
                bi = 0
                epoch += 1
                order = dataset.chunk_order(epoch)
            slices.append(order[bi * b:(bi + 1) * b])
            bi += 1
        rows = sum(len(s) for s in slices)
        ids = np.empty((rows, S), np.int32)
        mask = np.empty((rows, S), np.float32)
        labels = np.empty((rows, S), np.int32)
        r0 = 0
        for sl in slices:
            dataset.fill_rows(sl, ids, mask, labels, row0=r0)
            r0 += len(sl)
        yield epoch, {"input_ids": ids, "attention_mask": mask,
                      "labels": labels}


def evaluate(eval_step, trainable, frozen, dataset: WikiText2Dataset,
             max_batches: int, mesh=None,
             sequence_parallel: bool = False, prefetch: int = 2) -> dict:
    """Token-weighted mean NLL over the split -> {loss, ppl, tokens}
    (eval_ppl.cpp:157-200 semantics), under the no-grad eval step.
    `mesh`: place eval batches like train batches (required under
    multi-host, where raw host numpy cannot feed a global-mesh jit).

    The sum-NLL/token-count accumulators stay ON DEVICE (one tiny add per
    batch rides the async dispatch queue) and transfer once after the
    loop — per-batch float()/int() forced a full device sync per eval
    step. Batches come through the same background producer + placement
    lookahead as training (prefetch=0: synchronous)."""
    place = make_batch_placer(mesh, sequence_parallel)
    source = dataset.epoch(0)
    if max_batches:
        source = itertools.islice(source, max_batches)
    total, count, n = None, None, 0
    with Prefetcher(source, depth=prefetch, place_fn=place) as batches:
        for b in batches:
            s, c = eval_step(trainable, frozen, b)
            total = s if total is None else total + s
            count = c if count is None else count + c
            n += 1
    if n == 0:
        tokens, mean = 0, 0.0
    else:
        # graftlint: disable=sync-hazard(one transfer after the eval loop, the r07 on-device accumulation contract)
        total, count = jax.device_get((total, count))
        tokens = int(count)
        mean = float(total) / max(tokens, 1)
    return {"loss": mean, "ppl": perplexity_from_loss(mean),
            "tokens": tokens, "batches": n}


def compute_dtype_from_args(args):
    return jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32


def with_attention_impl(args, config):
    """`config` with --attention_impl applied, for a train CLI to call
    after seq_len is clamped. Also resolves `auto` for the run's
    (seq_len, head_dim) through the model blocks' own rule
    (ops/attention.resolve_impl — the same on one chip and over a
    batch-parallel mesh, where the dispatcher wraps the kernels in
    shard_map) and stamps it into args, so the run_start manifest says
    which attention path trained — flash means the Pallas kernels, and
    the `compile` event's tpu_custom_calls then says they are really in
    the program (0 with `flash` stamped: the dispatcher fell back, e.g.
    a batch the mesh does not divide)."""
    import dataclasses
    from mobilefinetuner_tpu.ops.attention import resolve_impl
    impl = args.attention_impl
    if getattr(args, "sequence_parallel", False):
        impl = "ring"
    elif impl == "auto":
        impl = resolve_impl(args.seq_len, config.head_dim)
    args.attention_impl_resolved = impl
    log.info(f"attention_impl={args.attention_impl} -> {impl}")
    return dataclasses.replace(config,
                               attention_impl=args.attention_impl)


def log_lora_impl_resolution(args, target_dims, rank: int,
                             compute_dtype) -> None:
    """Resolve `--lora_impl auto` per target for the run's dominant
    shapes (models/lora_apply.impl_summary) and stamp the result into
    args so the telemetry run_start manifest records which path served
    the run. Shared by the LoRA CLIs — the convention must not drift
    between them."""
    from mobilefinetuner_tpu.models.lora_apply import impl_summary
    args.lora_impl_resolved = impl_summary(
        target_dims, args.batch_size * args.seq_len, rank,
        args.lora_impl, jnp.dtype(compute_dtype).itemsize)
    log.info(f"lora_impl={args.lora_impl} -> {args.lora_impl_resolved}")


def maybe_resume_opt_state(args, trainable, tc: TrainConfig, mask=None):
    """(opt_state, start_step) from the .opt sidecar next to
    --resume_from, or (None, 0). The sidecar carries Adam m/v AND the step
    counter — restoring both is an improvement over the reference, which
    never wires Adam::save/load into any CLI (SURVEY.md §5).

    The restored tree is HOST numpy and the template is abstract
    (jax.eval_shape — shapes/dtypes only, no device zeros allocated just
    to be overwritten): nothing here commits the state to any device, so
    the SAME sidecar loads at any mesh shape — the caller places it (the
    full-FT CLIs via `place_opt_state` at their mesh, the LoRA path via
    run_training's replication), which is what makes `--resume_from`
    mesh-shape-agnostic (elastic resume, DESIGN.md §18)."""
    from mobilefinetuner_tpu.optim import adam as adam_mod
    from mobilefinetuner_tpu.train.trainer import init_optimizer
    path = getattr(args, "resume_from", "")
    if not path or not os.path.exists(path + ".opt"):
        return None, 0
    # trainable rides as the abstracted ARGUMENT (not a closure constant:
    # eval_shape only abstracts arguments — a closed-over concrete tree
    # would make zeros_like allocate real device zeros during tracing)
    template = jax.eval_shape(lambda t: init_optimizer(t, tc, mask),
                              trainable)
    opt_state, _ = adam_mod.load_state(path + ".opt", template,
                                       to_host=True)
    # the LOOP step, not Adam's: under --skip_nonfinite the Adam step
    # counter lags the loop step by the skipped updates, so resuming at
    # opt_state["step"] would replay already-consumed batches. The
    # sidecar's loop_step metadata (round 15) is authoritative; the
    # lineage json is the fallback for sidecars that predate it.
    from mobilefinetuner_tpu.io.checkpoints import lineage_step_for
    from mobilefinetuner_tpu.io.safetensors_io import SafeTensorsReader
    md = SafeTensorsReader(path + ".opt").metadata
    if "loop_step" in md:
        start_step = int(md["loop_step"])
    else:
        start_step = lineage_step_for(path)
        if start_step is None:
            start_step = int(opt_state["step"])
    log.info(f"restored optimizer state @ step {start_step} "
             f"(adam step {int(opt_state['step'])})")
    return opt_state, start_step


def place_opt_state(opt_state, mesh):
    """Place a host-side resumed Adam tree onto THIS run's mesh with the
    same FSDP rule as the params (`mesh.shard_params`): m/v leaves share
    the param shapes, so they land on the param specs by construction —
    ZeRO's optimizer-state partitioning survives a mesh reshape — while
    the step scalar and masked zero-size placeholders replicate. With
    the sidecar holding full tensors (writers gather before saving),
    this is the whole elastic-resume placement story: save at mesh
    (1,N), load + re-shard at (1,M), byte-identical values
    (tests/test_elastic.py pins the round trip)."""
    from mobilefinetuner_tpu.parallel.mesh import shard_params
    return shard_params(opt_state, mesh)


def data_retry_kwargs(args) -> dict:
    """WT2Config kwargs for the bounded-retry streaming refetch
    (--data_retries/--data_backoff_s) — one place, so the four train
    CLIs cannot drift. Applied to the TRAIN and EVAL datasets alike (a
    mid-run eval refetch over the same flaky filesystem deserves the
    same budget)."""
    return {"retries": max(getattr(args, "data_retries", 0), 0),
            "retry_backoff_s": getattr(args, "data_backoff_s", 0.5)}


def make_data_retry_sink(tel, cur_step: dict):
    """The WikiText2Dataset.event_sink adapter: render a survived-retry
    report (`_io_retry`'s kind/attempt/error/what/backoff_s kwargs) as
    an `anomaly`{kind=data_retry} telemetry event plus a log line.
    Module-level (not an inline closure) so the wiring is unit-testable
    against the real payload shape — the dataset swallows sink
    exceptions by design, which would otherwise hide an argument
    mismatch here forever. `cur_step` is the loop's mutable
    latest-step cell; the stamp is approximate by design (the retry
    happens BETWEEN steps on the producer thread)."""
    def sink(**fields):
        kind = fields.pop("kind", "data_retry")
        tel.emit("anomaly", step=cur_step["step"] + 1, kind=kind,
                 loss=None, ema=None, zscore=None, **fields)
        log.warning(
            f"data retry #{fields.get('attempt')}: "
            f"{fields.get('error')} (backing off "
            f"{fields.get('backoff_s')}s)")
    return sink


def resolve_resume_from(args) -> None:
    """Verify `--resume_from` against its integrity lineage BEFORE any
    load touches it (DESIGN.md §20 verify-on-load contract): the
    checksum manifest of the named checkpoint (+ .opt sidecar) is
    recomputed; a corrupt/truncated/stale file makes the resolution
    FALL BACK down `<path>.lineage.json` to the newest verified entry
    instead of crashing — or worse, silently loading garbage into a
    run. args.resume_from is REWRITTEN to the resolved path (all
    downstream loads — adapter/model file and the opt sidecar — then
    agree on the same artifact), and the per-candidate ckpt_verify
    verdicts are stashed on args for run_training to emit right after
    run_start (the stream's first event must stay run_start). Shared
    by all four train CLIs so the fallback rule cannot drift."""
    path = getattr(args, "resume_from", "")
    if not path:
        return
    if os.path.isdir(path):
        # an HF checkpoint DIRECTORY (full-FT resume source): external
        # HF artifacts carry no per-file manifests and there is no
        # lineage to fall back down — load as before
        return
    from mobilefinetuner_tpu.io.checkpoints import resolve_checkpoint
    resolved, _step, events = resolve_checkpoint(
        path, verify=bool(getattr(args, "verify_ckpt", 1)))
    args._ckpt_verify_events = events
    if resolved != path:
        log.warning(f"--resume_from {path} failed integrity "
                    f"verification; falling back down the lineage to "
                    f"{resolved}")
        args.resume_from = resolved
    elif events and not events[0]["ok"]:
        log.warning(f"--resume_from {path}: {events[-1]['reason']} "
                    f"(loading unverified — no verified lineage "
                    f"alternative)")


def offload_rung_state(args, params, mesh):
    """The degradation ladder's offload-rung POLICY, shared by the two
    LoRA CLIs so it cannot fork: force host offload at the streams-only
    budget (whole-fetch leaves stay resident, [L,...] stacks stream per
    layer) plus remat (streaming requires a remat'd scan body), then
    re-place the frozen base through the CLI's own setup path. Returns
    the new (params, fetch_fn, offload_arg) — or None when offload is
    already on (nothing left to give back). The caller rebinds its
    closure cells and hands (new_params, loss_fn) to run_training."""
    if args.shard_enable:
        return None
    from mobilefinetuner_tpu.parallel.offload import streams_only_budget
    args.shard_enable = True
    args.remat = True
    args.shard_budget_mb = max(
        int(streams_only_budget(params)) // 2 ** 20, 1)
    return setup_frozen_params(args, params, mesh)


def preflight_eval_compile(make_compiled, args, tel, what="eval step",
                           compiled_of=lambda out: out):
    """Run an eval CLI's AOT compile UNDER the admission contract
    (DESIGN.md §21): a RESOURCE_EXHAUSTED from the compiler itself is
    an admission verdict, not an unnamed crash — it lands as
    mem_check{verdict=over, phase=compile} plus a schema-valid run_end
    before the named MemoryAdmissionError raises (fleet tooling must
    read an inadmissible eval config, not a crashed host). On success
    the result is preflighted as usual. `make_compiled` is the compile
    thunk; `compiled_of` extracts the compiled executable from its
    return value (identity by default — eval_mmlu's factory returns a
    (logits_fn, compiled) pair)."""
    from mobilefinetuner_tpu.core import memory_guard as mg
    try:
        out = make_compiled()
    except Exception as e:
        if not mg.is_resource_exhausted(e):
            raise
        cap, src = mg.device_capacity_mb(getattr(args, "hbm_cap_mb", 0))
        check = mg.MemCheck(
            est_mb=None, cap_mb=cap, verdict="over", phase="compile",
            headroom=getattr(args, "hbm_headroom", 0.1), cap_source=src)
        tel.emit("mem_check", **check.event())
        tel.emit("run_end", steps=0, wall_s=0.0,
                 exit="MemoryAdmissionError", goodput=None)
        tel.close()
        raise mg.MemoryAdmissionError(
            f"{what} failed memory admission at compile: {e}",
            check=check) from e
    preflight_compiled_eval(compiled_of(out), args, tel, what=what)
    return out


def preflight_compiled_eval(compiled, args, tel, what="eval step"):
    """Admission preflight for an eval CLI's compiled forward
    (DESIGN.md §21): the same mem_check the train path emits, minus
    the degradation ladder (eval has no remat/accum/offload levers, so
    --on_oom_risk degrade behaves like warn here). Under 'fail' an
    over verdict terminates the stream with a schema-valid run_end and
    raises the named MemoryAdmissionError — before the eval data loop
    starts."""
    from mobilefinetuner_tpu.core import memory_guard as mg
    check = mg.preflight(compiled, cap_mb=getattr(args, "hbm_cap_mb", 0),
                         headroom=getattr(args, "hbm_headroom", 0.1))
    tel.emit("mem_check", **check.event())
    if check.verdict != "over":
        return check
    if getattr(args, "on_oom_risk", "warn") == "fail":
        tel.emit("run_end", steps=0, wall_s=0.0,
                 exit="MemoryAdmissionError", goodput=None)
        tel.close()
        raise mg.MemoryAdmissionError(
            f"{what} failed memory admission ({check.describe()})",
            check=check)
    log.warning(f"memory admission ({what}): {check.describe()} "
                f"(proceeding)")
    return check


def record_ckpt_files(args, final_path: str, step: int, files) -> None:
    """Write-hook tail shared by the train CLIs: record a completed
    save into `<final_path>.lineage.json` and GC past --keep_ckpts
    (io/checkpoints.record_checkpoint — lineage publishes atomically
    BEFORE any unlink, so a kill mid-GC never strands the retained
    set). Runs on the async writer thread; failures are logged, not
    raised (a lineage bookkeeping error must not fail the save whose
    files are already durable)."""
    try:
        from mobilefinetuner_tpu.io.checkpoints import record_checkpoint
        record_checkpoint(final_path, step, list(files),
                          keep=max(getattr(args, "keep_ckpts", 0), 0))
    except Exception as e:
        log.warning(f"checkpoint lineage update failed: {e}")


def make_rollback_loader(tc: TrainConfig, mask, load_trainable):
    """Build run_training's `load_hook(path) -> (trainable_host,
    opt_state_host)` from a CLI's trainable loader. `load_trainable`
    maps a checkpoint path to the host trainable tree (the adapter for
    the LoRA CLIs, the full param tree for full FT); the Adam sidecar
    at `<path>.opt` is restored to HOST numpy against an abstract
    template (no device allocation — the caller places both trees at
    THIS run's mesh, reusing the elastic-resume machinery)."""
    from mobilefinetuner_tpu.optim import adam as adam_mod

    def load_hook(path):
        tr_h = load_trainable(path)
        template = jax.eval_shape(
            lambda t: init_optimizer(t, tc, mask), tr_h)
        opt_h, _ = adam_mod.load_state(path + ".opt", template,
                                       to_host=True)
        return tr_h, opt_h
    return load_hook


def parse_train_inject(spec: str):
    """--inject grammar -> (kind, step, n) | ('ckpt_corrupt', None, 1)
    | ('hbm_pressure', None, <mb>) | None. Shared validation so a typo
    dies at startup, not at the injection step. slow_step's third slot
    is the sleep in ms (the FaultInjector re-reads it); its optional
    FOURTH slot is the repeat count."""
    if not spec:
        return None
    parts = spec.split(":")
    kind = parts[0]
    if kind == "ckpt_corrupt":
        return ("ckpt_corrupt", None, 1)
    if kind == "hbm_pressure":
        if len(parts) < 2:
            raise SystemExit(f"--inject hbm_pressure needs a ballast "
                             f"size in MB: {spec!r}")
        return ("hbm_pressure", None, max(int(parts[1]), 1))
    if kind == "slow_step":
        # host-side straggler step(s): sleep <ms> before dispatching
        # step(s) >= <step> — the sensor food for --auto_profile's
        # slow-step trigger and the straggler/latency-tail harness
        # (the serve-side twin is serve_bench --inject slow_step)
        if len(parts) < 3:
            raise SystemExit(f"--inject slow_step needs a step and ms: "
                             f"slow_step:<step>:<ms>[:<n>], got {spec!r}")
        ms = float(parts[2])  # validated here, stored by the injector
        if not (ms >= 0) or math.isinf(ms):  # `not >=` catches NaN too
            raise SystemExit(f"--inject slow_step ms must be a finite "
                             f"non-negative number, got {parts[2]!r}")
        n = int(parts[3]) if len(parts) > 3 else 1
        return ("slow_step", int(parts[1]), max(n, 1))
    if kind not in ("grad_nan", "loss_spike"):
        raise SystemExit(
            f"--inject must be grad_nan:<step>[:<n>] | "
            f"loss_spike:<step>[:<n>] | slow_step:<step>:<ms>[:<n>] | "
            f"ckpt_corrupt | hbm_pressure:<mb>, got {spec!r}")
    if len(parts) < 2:
        raise SystemExit(f"--inject {kind} needs a step: {spec!r}")
    step = int(parts[1])
    n = int(parts[2]) if len(parts) > 2 else 1
    return (kind, step, max(n, 1))


class FaultInjector:
    """Host-side numerical-fault injection for the train path (the
    r13/r14 --inject pattern): poisons step BATCHES on the input side —
    a NaN `grad_scale` row multiplies the accumulated gradients INSIDE
    the compiled step (genuinely non-finite grads through the real
    backward), scrambled labels drive a real loss level-shift — so the
    skip/rollback machinery is exercised end to end, not simulated.
    Each fault fires ONCE per process (latched by a fired counter):
    after a rollback replays the poisoned window, the same steps run
    clean — the recovery, not the fault, repeats."""

    def __init__(self, spec: str):
        parsed = parse_train_inject(spec)
        self.kind, self.at, self.n = parsed if parsed else (None, None, 0)
        self.fired = 0
        self.ballast = None  # hbm_pressure: the held device allocation
        self.slow_ms = (float(spec.split(":")[2])
                        if self.kind == "slow_step" else 0.0)

    def maybe_slow(self, step: int) -> None:
        """slow_step:<step>:<ms>[:<n>]: a host-side sleep before the
        dispatch of n consecutive steps from <step> — a real straggler
        step the flush-interval timing (and therefore the slow-step
        sensor, the straggler window, and the watchdog median) sees,
        without doctoring any metric."""
        if self.kind != "slow_step" or self.fired >= self.n \
                or step < self.at:
            return
        self.fired += 1
        log.warning(f"--inject slow_step: sleeping {self.slow_ms:.0f} ms "
                    f"before step {step} ({self.fired}/{self.n})")
        time.sleep(self.slow_ms / 1000.0)

    @property
    def active(self) -> bool:
        return self.kind is not None

    def arm_ballast(self) -> None:
        """hbm_pressure:<mb>: allocate and HOLD <mb> MB on the default
        device BEFORE the step compiles. On TPU that shrinks real free
        HBM, so compile/first-dispatch hits a genuine
        RESOURCE_EXHAUSTED when the config was near the ceiling; the
        allocation also lands in memory_stats bytes_in_use, so the
        preflight's live-bytes term sees it on any backend that
        reports stats."""
        if self.kind != "hbm_pressure" or self.ballast is not None:
            return
        mb = self.n
        self.ballast = jax.device_put(
            np.zeros(mb * 2 ** 20 // 4, np.float32))
        # graftlint: disable=sync-hazard(fault injection: the ballast must be resident before the next compile)
        self.ballast.block_until_ready()
        log.warning(f"--inject hbm_pressure: holding {mb} MB of device "
                    f"ballast")

    def maybe_oom_dispatch(self, step: int) -> None:
        """The dispatch half of hbm_pressure on backends that cannot
        genuinely exhaust device memory (CPU grows the host heap
        instead): raise ONE simulated RESOURCE_EXHAUSTED at the first
        dispatch, so the ladder's caught-at-dispatch recovery path is
        exercised end to end. On real accelerators (TPU/GPU) this is a
        no-op — the held ballast produces the real thing."""
        if self.kind != "hbm_pressure" or self.fired \
                or jax.default_backend() in ("tpu", "gpu", "cuda",
                                             "rocm"):
            return  # real accelerators: the held ballast OOMs for real
        self.fired = 1
        raise RuntimeError(
            f"RESOURCE_EXHAUSTED: simulated OOM at dispatch of step "
            f"{step} (--inject hbm_pressure:{self.n} on a backend "
            f"that cannot genuinely exhaust device memory)")

    def maybe_poison(self, step: int, batch: dict) -> dict:
        if self.kind == "grad_nan":
            # EVERY batch carries the [B] grad_scale row while armed
            # (batch structure must be constant for the AOT-compiled
            # step); only the poison window carries NaN
            batch = dict(batch)
            poison = self.fired < self.n and step >= self.at
            if poison:
                self.fired += 1
            batch["grad_scale"] = np.full(
                batch["input_ids"].shape[0],
                np.nan if poison else 1.0, np.float32)
            if poison:
                log.warning(f"--inject grad_nan: NaN grads for step "
                            f"{step} ({self.fired}/{self.n})")
            return batch
        if self.kind == "loss_spike" and self.fired < self.n \
                and step >= self.at:
            self.fired += 1
            # misaligned labels = a REAL loss level-shift through the
            # actual forward, not a doctored metric
            batch = dict(batch)
            batch["labels"] = np.roll(batch["labels"], 7, axis=-1)
            log.warning(f"--inject loss_spike: scrambled labels for "
                        f"step {step} ({self.fired}/{self.n})")
        return batch

    def maybe_corrupt_ckpt(self, ckpt_path: str) -> bool:
        """ckpt_corrupt: flip one payload byte in the newest lineage
        checkpoint (once). Returns True when it fired."""
        if self.kind != "ckpt_corrupt" or self.fired:
            return False
        from mobilefinetuner_tpu.io.checkpoints import lineage_entries
        entries = lineage_entries(ckpt_path)
        if not entries:
            return False
        victim = entries[0]["files"][0]
        try:
            with open(victim, "r+b") as f:
                f.seek(-1, os.SEEK_END)
                b = f.read(1)
                f.seek(-1, os.SEEK_END)
                f.write(bytes([b[0] ^ 0xFF]))
        except OSError as e:
            log.warning(f"--inject ckpt_corrupt failed: {e}")
            return False
        self.fired = 1
        log.warning(f"--inject ckpt_corrupt: flipped a byte in {victim}")
        return True


class EMA:
    """EMA-smoothed loss (CmdArgs ema_beta, default 0.9)."""

    def __init__(self, beta: float):
        self.beta = beta
        self.value: Optional[float] = None

    def update(self, x: float) -> float:
        self.value = x if self.value is None else \
            self.beta * self.value + (1 - self.beta) * x
        return self.value


def drained_record(flush_stamps, t_wait, t_input, t_dispatch,
                   t_dispatched) -> tuple:
    """(ms, parts): the host time the device had nothing queued after a
    flush and its split (step_stats.drained_ms / drained_parts_ms).

    flush_stamps = the flush's perf_counter readings (device_get
    returned, sensors done, telemetry line written, log line written);
    then the readings around the next `next(stream)` and around the
    next dispatch. The boundaries are those of the train.flush.* /
    train.input_wait / train.dispatch spans, and consecutive, so the
    parts sum to the whole; `loop_tail` is everything of the loop that
    is neither input wait nor dispatch."""
    t_sync, t_sensors, t_emit, t_log = flush_stamps
    ms = lambda seconds: round(seconds * 1000.0, 3)
    return ms(t_dispatched - t_sync), {
        "sensors": ms(t_sensors - t_sync), "emit": ms(t_emit - t_sensors),
        "log": ms(t_log - t_emit),
        "loop_tail": ms((t_wait - t_log) + (t_dispatch - t_input)),
        "input_wait": ms(t_input - t_wait),
        "dispatch": ms(t_dispatched - t_dispatch)}


def run_training(args, *, trainable, frozen, loss_fn, nll_fn,
                 train_ds: WikiText2Dataset,
                 valid_ds: Optional[WikiText2Dataset],
                 total_steps: int, tc: TrainConfig,
                 mask=None, start_step: int = 0, opt_state=None,
                 save_hook: Optional[Callable] = None,
                 mesh=None, replicate_trainable: bool = True,
                 dropout_rng=None, step_builder=None,
                 flops_per_step: Optional[float] = None,
                 load_hook: Optional[Callable] = None,
                 ckpt_path: str = "",
                 degrade_builders: Optional[dict] = None):
    """The shared optimizer-step loop: compiled step + eval cadence + EMA +
    metrics CSV + JSONL eval records + governor throttle + periodic saves
    + the run-telemetry event stream (--telemetry_out, core/telemetry.py).

    save_hook(step, trainable, opt_state, final, ckpt=None) persists
    checkpoints: the hook snapshots its trees to host (blocking, batched
    — io/async_ckpt.timed_snapshot) and routes the disk write through
    `ckpt` (async_ckpt.submit), which under --async_save runs it on a
    background thread so the step loop resumes after the snapshot.
    dropout_rng: base PRNG key; when set, a fresh per-sample key array
    folded with the step index rides in batch["dropout_rng"], so dropout
    masks differ across steps AND micro-batches (a fixed closure key would
    silently reuse one mask for the whole run).
    flops_per_step: the CLI's analytic transformer_flops estimate for ONE
    optimizer step — drives the in-loop MFU in the log line, the CSV, and
    step_stats (None: MFU omitted).
    load_hook(path) -> (trainable_host, opt_state_host) is the INVERSE
    of save_hook (make_rollback_loader builds it): with it, `ckpt_path`
    (the run's final artifact, whose .lineage.json tracks the
    step-tagged last-known-good set) and --rollback_budget > 0, the
    loop closes the SpikeDetector loop in-process — on sustained
    divergence / a skipped-step streak / nonfinite loss it reloads the
    newest VERIFIED lineage checkpoint at this run's mesh, rebuilds the
    data stream (byte-pinned skip_steps + --rollback_data_offset), and
    keeps training with the SAME compiled step (DESIGN.md §20).
    degrade_builders: the CLI's hooks for the memory-admission
    degradation ladder (DESIGN.md §21). The step is AOT-compiled BEFORE
    the data stream exists (a zero probe batch with the stream's exact
    shapes/placement) and preflighted against device capacity
    (core/memory_guard.py); under --on_oom_risk=degrade a failed
    admission — or a RESOURCE_EXHAUSTED caught at compile/first
    dispatch — walks remat -> accum_x2 -> offload, recompiling and
    re-preflighting at each rung. The remat rung flips args.remat
    (every CLI's loss closure reads it at trace time); accum_x2 doubles
    tc.grad_accum_steps for the STEP only (the stream keeps assembling
    the original global batch, so batch shapes/shardings never change);
    the "offload" key of degrade_builders, when provided, is
    `() -> (new_frozen, loss_fn) | None` — it re-places the frozen base
    with host offload enabled (None: not applicable / already on).
    Returns (trainable, opt_state, last_metrics).
    """
    from mobilefinetuner_tpu.parallel.distributed import (allgather_scalars,
                                                          device_put_global,
                                                          gather_to_host,
                                                          is_coordinator)
    # multi-host: every process runs the identical compiled step over global
    # arrays; the CSV/JSONL/checkpoint sinks write once, on process 0.
    # TELEMETRY writes on every process — the coordinator to the given
    # path, host k to PATH.host<k>, each record host-stamped — so a
    # stalled worker leaves evidence instead of silently dropping events
    # (merge with tools/fleet_report.py). Saving first gathers
    # cross-process-sharded trees to host on EVERY process
    # (gather_to_host is collective), then only process 0 writes.
    coord = is_coordinator()
    multiproc = jax.process_count() > 1
    tel = Telemetry.for_process(getattr(args, "telemetry_out", ""))
    tel.emit("run_start", **run_manifest(vars(args), mesh))
    # run registry (core/run_registry.py, DESIGN.md §28): one durable
    # record per run, coordinator-only (one run, one record — the
    # per-host shards already carry the host story). The start record
    # flushes immediately, so a SIGKILL mid-run is settled to
    # "interrupted" on the next registry open; finalize rides end_run,
    # the same single-exit path run_end uses.
    import sys as _sys
    from mobilefinetuner_tpu.core.run_registry import RunRegistry
    _registry = RunRegistry.from_args(args) if coord else None
    run_rec = _registry.begin(
        "train", os.path.basename(_sys.argv[0] or "train").replace(
            ".py", ""),
        config=vars(args), mesh=dict(mesh.shape) if mesh is not None
        else None,
        platform=jax.devices()[0].platform,
        artifacts=[p for p in (tel.path,
                               getattr(args, "out", "")) if p],
        telemetry=tel) if _registry else None
    # --resume_from integrity verdicts (resolve_resume_from ran in the
    # CLI, BEFORE this stream existed): emitted here so the acceptance
    # contract — a corrupted newest checkpoint resolves down the
    # lineage WITH ckpt_verify evidence in the run's own stream — holds
    # while run_start stays the stream's first event of the run.
    for _ev in getattr(args, "_ckpt_verify_events", None) or []:
        tel.emit("ckpt_verify", **_ev)
    t_start = time.time()
    # ONE tracer (core/trace.py) threaded to every producer — the step
    # loop's own spans, the goodput meter's phase track, the async
    # checkpoint writer, the prefetch producer. Its spans are in any
    # profiler trace taken of this run, always; --trace_spans
    # additionally writes them as `span` events into the stream
    # tools/trace_export.py converts
    from mobilefinetuner_tpu.core.trace import AutoProfiler, Tracer
    tracer = Tracer(tel.emit, enabled=bool(
        getattr(args, "trace_spans", 0)) and tel.enabled)
    # wall-clock bucket accounting over run_training's whole span; the
    # buckets sum to run_end.wall_s by construction (DESIGN.md §14);
    # under --trace_spans every phase segment also lands as a span, so
    # the exported timeline reconciles with the buckets structurally
    meter = GoodputMeter(tracer=tracer)
    done_steps = 0
    governor = None  # assigned in setup; end_run late-binds the local
    wd = None        # assigned in setup; the outer finally stops it
    ckpt = None      # async checkpointer; end_run drains it
    guard = None     # preemption guard; the outer finally uninstalls it
    metrics_srv = None  # live /metrics endpoint; outer finally closes it
    auto_prof = None    # anomaly-triggered profiler; ditto

    def end_run(exit_name: str, steps: int, **extra_fields):
        """Terminate the stream exactly once on any exit path: run_end
        carries the goodput buckets (plus the governor's own run-total
        sleep counter — an independently-clocked cross-check of the
        meter's governor_sleep bucket); emit/close no-op on a closed
        stream, so nested handlers compose without double emission.
        The async checkpoint writer is drained FIRST: a snapshot already
        taken is a recovery point worth finishing even when the loop
        died, and its checkpoint event must land before run_end closes
        the stream (write errors are swallowed here — they must not
        mask the exception that brought us down)."""
        if ckpt is not None:
            ckpt.close(raise_errors=False)
        # a capture still open at exit must land its profile_capture
        # event BEFORE run_end closes the stream (emit on a closed
        # stream is a hard no-op — the on-disk trace would lose its
        # pointer); the outer finally's close() is then idempotent
        if auto_prof is not None:
            auto_prof.close()
        extra = dict(extra_fields)
        if governor is not None:
            extra["governor_slept_ms"] = round(governor.total_slept_ms, 1)
        # finalize the registry record BEFORE run_end: the mirrored
        # `run` end event must land inside the run's own stream, and
        # run_end must stay the stream's LAST event (the r13 controller
        # keys restart decisions off it); finalize is idempotent, so
        # nested handlers compose exactly like emit/close do
        if run_rec is not None:
            run_rec.finalize(exit_name)
        tel.emit("run_end", steps=steps,
                 wall_s=round(time.time() - t_start, 3),
                 exit=exit_name, goodput=meter.summary(), **extra)
        tel.close()
    # EVERYTHING after run_start runs under one handler: a setup
    # failure (device placement OOM, stream construction) must still
    # terminate the stream with run_end{exit: <type>} — emit/close
    # are no-ops once the stream is closed, so the inner handlers
    # (loop, post-loop tail) and this outer one compose without
    # double emission.
    try:
        governor = governor_from_args(
            args, event_sink=lambda p: tel.emit("throttle", **p))
        # live OpenMetrics endpoint (--metrics_port, DESIGN.md §22):
        # the registry attaches as a telemetry OBSERVER — every number
        # a scraper reads came through the same emit call the JSONL
        # sink wrote, and the registry never touches a device (it has
        # no jax import to touch one with). Coordinator-only: one
        # endpoint per run, like the CSV/JSONL sinks. A bind failure
        # raises HERE, before data loading, under the run_end contract.
        if getattr(args, "metrics_port", 0) > 0 and coord:
            from mobilefinetuner_tpu.core.metrics_http import \
                start_metrics
            metrics_srv = start_metrics(
                tel, args.metrics_port,
                addr=getattr(args, "metrics_addr", "127.0.0.1"))
            log.info(f"metrics endpoint: http://{metrics_srv.addr}:"
                     f"{metrics_srv.port}/metrics (+ /healthz)")
        # anomaly-triggered profiler capture (--auto_profile, DESIGN.md
        # §22): a one-shot jax.profiler capture armed by the sensors —
        # slow step, loss spike/divergence, straggler, hang pre-exit —
        # under a budget and cooldown; each capture is a
        # `profile_capture` event pointing at the trace on disk
        if getattr(args, "auto_profile", 0):
            prof_root = getattr(args, "auto_profile_dir", "") or \
                ((tel.path + ".profiles") if tel.path
                 else "auto_profile_traces")
            auto_prof = AutoProfiler(
                prof_root, sink=tel.emit,
                steps=getattr(args, "auto_profile_steps", 2),
                cooldown_s=getattr(args, "auto_profile_cooldown", 300.0),
                budget=getattr(args, "auto_profile_budget", 2))
        slow_mult = getattr(args, "auto_profile_slow_mult", 3.0)
        # preemption drain (core/preempt.py, DESIGN.md §18): SIGTERM/
        # SIGINT flips a flag the loop checks at every step boundary —
        # finish the step, one final atomic save, run_end{reason=
        # preempted}, exit EXIT_PREEMPTED. Main-thread only (signal
        # semantics); embedded runs degrade to default behavior.
        if getattr(args, "on_preempt", "drain") == "drain":
            guard = PreemptionGuard().install()
            if not guard.installed:
                guard = None
        # streaming-data retry telemetry: the datasets' bounded-retry
        # refetch (data/wikitext2.py _io_retry) reports each survived
        # I/O error as an anomaly{kind=data_retry} through this sink.
        # cur_step is the loop's latest dispatched step — the producer
        # thread runs a batch or two ahead, so the stamp is approximate
        # by design (the retry has no exact step; it happens BETWEEN
        # steps on the producer side).
        cur_step = {"step": start_step}
        _data_retry_sink = make_data_retry_sink(tel, cur_step)
        for _ds in (train_ds, valid_ds):
            if _ds is not None and getattr(_ds, "event_sink", None) is None:
                _ds.event_sink = _data_retry_sink
        # snapshot-then-write checkpointing (io/async_ckpt.py): the save
        # hooks snapshot on the loop thread (blocking, batched D2H) and
        # hand the disk write to this checkpointer's background thread;
        # --async_save 0 is the synchronous oracle (same writer, inline).
        # The checkpointer emits the `checkpoint`/`ckpt_dropped` events
        # itself — including from its writer thread; Telemetry.emit is
        # lock-serialized — so the blocking/background split is recorded
        # where it is measured.
        from mobilefinetuner_tpu.io.async_ckpt import AsyncCheckpointer
        ckpt = AsyncCheckpointer(
            enabled=bool(getattr(args, "async_save", 1)),
            event_sink=tel.emit, tracer=tracer)
        spikes = SpikeDetector(SpikeConfig(
            zscore=getattr(args, "spike_z", 8.0),
            beta=getattr(args, "spike_beta", 0.98),
            warmup=getattr(args, "spike_warmup", 20)))
        if tel.resumed and tel.trailing_step_stats and start_step > 0:
            # crash/resume: re-seed the detector from the prior run's
            # flushed losses so it does not re-enter warmup and miss a
            # spike in the first post-resume steps (the exact window
            # where resume bugs bite). Gated on an ACTUAL checkpoint
            # resume (start_step > 0): a fresh run that merely reuses a
            # telemetry path must keep its warmup, or its legitimately
            # wild early losses fire against the old run's statistics
            fed = spikes.seed(
                [r.get("loss") for r in tel.trailing_step_stats],
                count_hint=max(r.get("step", 0)
                               for r in tel.trailing_step_stats))
            log.info(f"spike detector re-seeded from {fed} resumed "
                     f"step_stats (armed={spikes.count >= spikes.config.warmup})")
        # hang watchdog (--watchdog 0 disables): fires when no step
        # completes within watchdog_mult x rolling-median step time,
        # dumps all thread stacks + emits a `hang` event, then keeps
        # waiting (1) or aborts the process (2)
        wd_mode = getattr(args, "watchdog", 1)
        if wd_mode:
            wd = HangWatchdog(
                mult=getattr(args, "watchdog_mult", 10.0),
                min_deadline_s=getattr(args, "watchdog_min_s", 60.0),
                # the grace honors the flag exactly (its documented
                # meaning): compile no longer needs a padded grace —
                # the compile block suspends the clock
                grace_s=getattr(args, "watchdog_min_s", 60.0),
                stacks_file=(tel.path + ".stacks") if tel.path else "",
                abort=wd_mode == 2,
                # before an abort's os._exit(113): flush + newline-
                # terminate the stream so the shard a post-mortem reads
                # ends with the complete hang record, not a truncated
                # line (the flush serializes against any emit mid-write
                # on the step loop's thread)
                flush_fn=tel.flush_tail,
                # graftlint: disable=sync-hazard(the watchdog's device probe IS a deliberate sync, off the step loop's thread)
                probe_fn=lambda: jax.device_put(
                    jnp.zeros(())).block_until_ready(),
                on_hang=lambda p: (
                    # pre-exit flight recorder: grab the device trace
                    # of the wedged state BEFORE a --watchdog 2 abort
                    # can os._exit (bounded hold; never raises)
                    (auto_prof.capture_now("hang", p["step"])
                     if auto_prof is not None else None),
                    tel.emit("hang", last_seq=tel.last_seq, **p),
                    log.error(
                        f"HANG: no step for {p['stall_s']:.1f}s "
                        f"(deadline {p['deadline_s']:.1f}s) after step "
                        f"{p['step']}; stacks -> {p['stacks_file']}, "
                        f"device probe: {p['device_probe']}, "
                        f"action: {p['action']}")))
        # wd.paused() as a with-block at every known long pause
        # (compile, eval, checkpoint): the deadline clock stops — such
        # a pause may exceed any step-derived deadline — and the resume
        # cannot be forgotten. No-op context when the watchdog is off.
        pause = wd.paused if wd is not None else contextlib.nullcontext
        # straggler attribution: every straggler_cadence steps each host
        # gathers its median step time (collective, deterministic
        # cadence); the per-host map lands in step_stats.host_step_ms
        # and outliers raise `straggler` events (coordinator-side)
        strag_k = max(getattr(args, "straggler_cadence", 0), 0)
        strag_mult = getattr(args, "straggler_mult", 1.5)
        step_clock = StepClock()
        host_step_ms = {"latest": None}
        # flops_per_step covers the GLOBAL batch, so the MFU denominator is
        # the GLOBAL peak: per-chip peak × every device in the run (a
        # single-chip run is unchanged; an 8-chip run divided by one chip's
        # peak would report 8× the true utilization)
        peak_flops = device_peak_flops() * len(jax.devices())
        metrics_csv = MetricsLogger(args.metrics_csv) \
            if args.metrics_csv and coord else None
        eval_jsonl = JSONLWriter(args.eval_out) \
            if getattr(args, "eval_out", "") and coord else None
        if save_hook is not None and multiproc:
            orig_save = save_hook

            # gather-then-coordinator-write, unchanged under async save:
            # the gather is COLLECTIVE (every process participates, and
            # its cost is part of the blocking snapshot the loop pays);
            # only the coordinator snapshots/queues the write, so the
            # background writer thread exists on one process
            def save_hook(step, tr, opt, final=False, ckpt=None):
                tr_h, opt_h = gather_to_host(tr), gather_to_host(opt)
                if coord:
                    orig_save(step, tr_h, opt_h, final=final, ckpt=ckpt)
        # the eval path must feed global arrays under multi-host (raw host
        # numpy cannot address a global mesh); single-process keeps the
        # uncommitted-numpy fast path
        eval_mesh = mesh if (mesh is not None and multiproc) else None
        eval_sp = getattr(args, "sequence_parallel", False)

        eval_step = make_eval_step(nll_fn)
        if opt_state is None:
            opt_state = init_optimizer(trainable, tc, mask)

        if mesh is not None and replicate_trainable:
            # LoRA-style tiny trainables: replicate A/B + Adam state; FSDP'd
            # trainables (full FT) arrive pre-placed and are left alone.
            repl = replicated_sharding(mesh)
            trainable = jax.tree.map(
                lambda x: device_put_global(x, repl), trainable)
            opt_state = jax.tree.map(
                lambda x: device_put_global(x, repl), opt_state)

        # step_builder: alternate step factory with make_train_step's contract
        # (the optimizer-offload path, optim/opt_offload.py, plugs in here).
        # On a mesh, the compiled step's trainable/opt OUTPUTS are pinned to
        # their INPUT shardings (metrics replicate): the loop runs ONE
        # AOT-compiled executable with donated buffers, and a compiler-chosen
        # output sharding that drifts from the input sharding would make the
        # very next call reject its own donated outputs (seen on the
        # (1,N)-mesh full-FT path: replicated bias inputs came back
        # fsdp-sharded). Pinning makes the step a sharding fixed point by
        # construction. The offload step_builder manages its own placements.
        out_shardings = None
        if mesh is not None and step_builder is None:
            from jax.sharding import NamedSharding
            from mobilefinetuner_tpu.parallel.mesh import params_shardings
            tr_on_mesh = all(
                isinstance(getattr(x, "sharding", None), NamedSharding)
                and x.sharding.mesh == mesh
                for x in jax.tree.leaves(trainable))
            if tr_on_mesh:
                # trainable: keep exactly its input shardings. opt m/v:
                # the same FSDP RULE as the params (a fresh
                # init_optimizer's eager zeros sit uncommitted on one
                # device — their .sharding is not the intent; a resumed
                # tree arrives via place_opt_state, which IS this rule).
                repl = replicated_sharding(mesh)
                out_shardings = (
                    jax.tree.map(lambda x: x.sharding, trainable),
                    repl if replicate_trainable
                    else params_shardings(opt_state, mesh),
                    repl)  # prefix: every metrics leaf replicates
        # the degradation ladder (DESIGN.md §21) may REBUILD the step:
        # loss_fn is re-traced (the CLIs' loss closures read args.remat
        # and their offload cell at trace time) and tc_step carries the
        # accum_x2 rung's doubled micro-batch count. The STREAM keeps
        # the original tc.grad_accum_steps — the step batch is the
        # constant global batch either way, so batch shapes and
        # shardings never change across rungs and neither do the
        # donation/output-sharding pins above.
        tc_step = tc

        def build_step():
            if step_builder is not None:
                return step_builder(loss_fn, tc_step, mask=mask,
                                    donate=True)
            return make_train_step(loss_fn, tc_step, mask=mask,
                                   donate=True,
                                   out_shardings=out_shardings)

        step_fn = build_step()

        def place_state(tr_h, opt_h):
            """Host trees -> this run's mesh placement (the r13
            elastic-resume rule: replicate LoRA-style trainables, FSDP
            re-shard otherwise) — ONE helper shared by the rollback
            reload and the dispatch-OOM retry so the placement rule
            cannot fork."""
            if mesh is not None and replicate_trainable:
                repl = replicated_sharding(mesh)
                put = lambda x: device_put_global(jnp.asarray(x), repl)
                return jax.tree.map(put, tr_h), jax.tree.map(put, opt_h)
            if mesh is not None:
                from mobilefinetuner_tpu.parallel.mesh import shard_params
                return shard_params(tr_h, mesh), shard_params(opt_h, mesh)
            return (jax.tree.map(jnp.asarray, tr_h),
                    jax.tree.map(jnp.asarray, opt_h))

        ema = EMA(args.ema_beta)
        # async input pipeline: micro-batch assembly (tokenization, streaming
        # refetch, accum fill) runs in a background producer thread; dropout
        # keys + device placement are issued one batch AHEAD on the consumer
        # side, so batch k+1's host->HBM transfer overlaps step k's compute.
        # --prefetch 0 collapses to the synchronous path (same interface,
        # byte-identical batch sequence).
        prefetch_depth = max(getattr(args, "prefetch", 2), 0)
        sp = getattr(args, "sequence_parallel", False)
        place_batch = make_batch_placer(mesh, sp)

        # fault-injection harness (--inject, DESIGN.md §20): batches are
        # poisoned on the HOST side inside place_step — before dropout
        # keys and device placement — so the injected fault flows
        # through the real compiled forward/backward
        injector = FaultInjector(getattr(args, "inject", ""))

        def place_step(item):
            step, epoch, batch = item
            if injector.active:
                batch = injector.maybe_poison(step, batch)
            if dropout_rng is not None:
                nb = batch["input_ids"].shape[0]
                batch["dropout_rng"] = jax.random.split(
                    jax.random.fold_in(dropout_rng, step), nb)
            return step, epoch, place_batch(batch)

        def make_stream(from_step: int, data_skip: int) -> Prefetcher:
            """The numbered, placed step-batch stream from `from_step`.
            `data_skip` is the byte-pinned fast-forward in STEPS —
            normally == from_step (resume continues the exact data
            order); a rollback passes from_step + k*rollback_data_offset
            to diverge the replayed window's batch sequence. max(..., 0):
            a resume at/after total_steps runs zero steps (the loop
            below is empty) and must not build a stream at all."""
            def numbered():
                gen = micro_batches(train_ds, tc.grad_accum_steps,
                                    skip_steps=data_skip)
                for step in itertools.count(from_step):
                    epoch, batch = next(gen)
                    yield step, epoch, batch
            return Prefetcher(
                itertools.islice(numbered(),
                                 max(total_steps - from_step, 0)),
                depth=prefetch_depth, place_fn=place_step, lookahead=1,
                rss_limit_mb=getattr(args, "prefetch_rss_mb", 0),
                tracer=tracer)

        # ---- memory admission + degradation ladder (DESIGN.md §21) ------
        # The step is AOT-compiled HERE, from a zero probe batch with the
        # stream's exact shapes and placement — before the data stream
        # (and its producer thread) exists — so an inadmissible config
        # dies with a named error in seconds, and the `compile` event's
        # peak-HBM estimate is the SAME number the preflight judges.
        from mobilefinetuner_tpu.core import memory_guard as mg
        oom_mode = getattr(args, "on_oom_risk", "warn")
        adm_cap_mb = getattr(args, "hbm_cap_mb", 0)
        adm_headroom = getattr(args, "hbm_headroom", 0.1)
        compiled_step = None
        peak_hbm = {"mb": 0.0}     # from the compiled step's memory analysis
        rungs_applied: list = []
        oom_snap = None            # host insurance for the dispatch retry
        compile_err = {"e": None}  # original compile-time OOM (warn mode)

        def probe_batch():
            """The AOT compile's stand-in: zero arrays with exactly the
            step-batch rows the ORIGINAL accum assembles, run through
            the same place_step as real batches (injector grad_scale
            key, dropout keys, mesh placement) — the compiled
            executable serves the stream's batches unchanged."""
            b = train_ds.config.batch_size
            S = train_ds.config.seq_len
            rows = b * tc.grad_accum_steps
            zero = {"input_ids": np.zeros((rows, S), np.int32),
                    "attention_mask": np.zeros((rows, S), np.float32),
                    "labels": np.zeros((rows, S), np.int32)}
            # the probe must not CONSUME an injector fire (an --inject
            # grad_nan at the start step would otherwise spend one of
            # its n poisons on a batch that never trains): run the real
            # place_step for structural fidelity, then restore the latch
            fired = injector.fired
            placed = place_step((start_step, 0, zero))[2]
            injector.fired = fired
            return placed

        def over_check(phase: str) -> "mg.MemCheck":
            """A forced-over verdict for a REAL RESOURCE_EXHAUSTED (the
            estimate side is moot: the device already said no)."""
            cap, src = mg.device_capacity_mb(adm_cap_mb)
            return mg.MemCheck(est_mb=None, cap_mb=cap, verdict="over",
                               phase=phase, headroom=adm_headroom,
                               cap_source=src)

        def compile_and_check(at_step: int = start_step) -> "mg.MemCheck":
            """AOT-compile the current step and preflight it: one
            `compile` + one `mem_check` event per attempt (the ladder
            re-enters here after every rung). A RESOURCE_EXHAUSTED from
            the compiler itself IS an admission verdict, not a crash —
            it leaves compiled_step as None with the original error in
            compile_err (the warn-mode driver re-raises it: warn means
            'proceed anyway', and with no executable there is nothing
            to proceed WITH). The probe batch is built fresh per
            attempt and dropped with the frame: a full step batch of
            zeros must not sit in device memory for the whole run
            inside the very feature that budgets memory."""
            nonlocal compiled_step
            probe = probe_batch()
            meter.enter("compile")
            t_comp = time.perf_counter()
            hits0 = cache_counts()["hits"]
            try:
                with pause():
                    compiled_step = step_fn.lower(
                        trainable, frozen, opt_state, probe,
                        jnp.int32(start_step)).compile()
            except Exception as e:
                meter.enter("init")
                if not mg.is_resource_exhausted(e):
                    raise
                log.warning(f"RESOURCE_EXHAUSTED at compile: {e}")
                compiled_step = None
                compile_err["e"] = e
                c = over_check("compile")
                tel.emit("mem_check", **c.event())
                return c
            meter.enter("init")
            compile_s = round(time.perf_counter() - t_comp, 3)
            peak_hbm["mb"] = compiled_peak_mb(compiled_step)
            xla_flops = compiled_flops(compiled_step)
            # what is IN the executable: the Pallas kernels (0 = the XLA
            # paths or interpret mode served every `auto` rule) and the
            # collectives the partitioner put in; cache_hit: the
            # persistent compile cache served this program
            from mobilefinetuner_tpu.core.static_checks import \
                hlo_collective_census
            hlo = compiled_step.as_text()
            # at_step: a mid-run ladder recompile (dispatch OOM) logs
            # at the step that forced it, aligned with its degrade/
            # mem_check neighbors — not back at start_step
            tel.emit("compile", step=at_step, wall_s=compile_s,
                     flops=xla_flops or None,
                     peak_hbm_mb=peak_hbm["mb"] or None,
                     tpu_custom_calls=tpu_custom_calls(hlo),
                     collectives={k: n for k, n in
                                  hlo_collective_census(hlo).items() if n},
                     cache_hit=cache_counts()["hits"] > hits0)
            c = mg.preflight(compiled_step, cap_mb=adm_cap_mb,
                             headroom=adm_headroom)
            tel.emit("mem_check", **c.event())
            if peak_hbm["mb"]:
                log.info(f"compiled step peak HBM: {peak_hbm['mb']:.0f} "
                         f"MB ({c.describe()})")
            return c

        def apply_rung(est_mb, at_step=None) -> bool:
            """Walk ONE rung of the bounded ladder (memory_guard.LADDER
            order: remat -> accum_x2 -> offload): mutate the config,
            emit a `degrade` event, and let the caller recompile.
            Returns False when no applicable rung remains."""
            nonlocal loss_fn, tc_step, frozen
            for rung in mg.LADDER:
                if rung in rungs_applied:
                    continue
                if rung == "remat":
                    if getattr(args, "remat", True):
                        continue  # already on: nothing left to give
                    # every CLI's loss closure reads args.remat at
                    # trace time — the flip lands at the recompile
                    args.remat = True
                    frm, to = "remat=off", "remat=on"
                elif rung == "accum_x2":
                    rows = (train_ds.config.batch_size
                            * tc.grad_accum_steps)
                    new_accum = tc_step.grad_accum_steps * 2
                    if new_accum > rows or rows % new_accum:
                        continue  # micro-batch cannot split further
                    import dataclasses as _dc
                    tc_step = _dc.replace(tc_step,
                                          grad_accum_steps=new_accum)
                    frm = f"accum={new_accum // 2}"
                    to = f"accum={new_accum}"
                else:  # offload
                    builder = (degrade_builders or {}).get("offload")
                    out = builder() if builder is not None else None
                    if out is None:
                        continue  # no offload path / already enabled
                    frozen, loss_fn = out
                    frm, to = "offload=off", "offload=on"
                rungs_applied.append(rung)
                tel.emit("degrade", step=at_step,
                         **{"rung": rung, "from": frm, "to": to,
                            "est_mb": (round(est_mb, 2) if est_mb
                                       else None)})
                log.warning(
                    f"DEGRADE rung {len(rungs_applied)} ({rung}: {frm} "
                    f"-> {to})"
                    + (f" — estimate {est_mb:.0f} MB over capacity"
                       if est_mb else "")
                    + "; recompiling")
                return True
            return False

        def recover_dispatch_oom(e: BaseException, step: int) -> None:
            """A RESOURCE_EXHAUSTED escaped the compiled step's
            dispatch: under --on_oom_risk=degrade walk the remaining
            ladder (recompile + re-preflight per rung), restore the
            donated trees from the host insurance snapshot, and let the
            loop retry the SAME batch — no process restart, no
            checkpoint touched, no rollback triggered. Re-raises when
            recovery is impossible (mode, no rungs left, or donated
            state unrecoverable on a real accelerator)."""
            nonlocal step_fn, trainable, opt_state, t_interval
            can_retry = (oom_snap is not None
                         or jax.default_backend() == "cpu")
            if oom_mode != "degrade" or not can_retry:
                raise e
            tel.emit("mem_check", **over_check("dispatch").event())
            log.warning(f"RESOURCE_EXHAUSTED at dispatch of step "
                        f"{step}: walking the degradation ladder")
            # settle the buffered steps first, then keep the recovery
            # wall OUT of the next flush's per-step average (the rule
            # the first-step compile block enforced and eval/save/
            # rollback all follow): an inflated sample here would feed
            # the watchdog deadline and the straggler window
            flush_metrics(emit_log=False)
            while True:
                if not apply_rung(peak_hbm["mb"] or None, at_step=step):
                    raise mg.MemoryAdmissionError(
                        f"RESOURCE_EXHAUSTED at dispatch of step {step} "
                        f"and the degradation ladder "
                        f"{tuple(rungs_applied)} is exhausted",
                        ladder=rungs_applied) from e
                step_fn = build_step()
                c = compile_and_check(at_step=step)
                if c.verdict != "over":
                    break
            if oom_snap is not None:
                trainable, opt_state = place_state(*oom_snap)
            t_interval = time.perf_counter()  # recompile ≠ step time

        if start_step < total_steps:
            injector.arm_ballast()
            check = compile_and_check()
            while check.verdict == "over" and oom_mode != "warn":
                if oom_mode == "fail":
                    raise mg.MemoryAdmissionError(
                        f"memory admission failed ({check.describe()}); "
                        f"rerun with a smaller config, --on_oom_risk "
                        f"degrade, or a larger device", check=check)
                if not apply_rung(check.est_mb):
                    raise mg.MemoryAdmissionError(
                        f"memory admission failed after exhausting the "
                        f"degradation ladder {tuple(rungs_applied)} "
                        f"({check.describe()})", check=check,
                        ladder=rungs_applied)
                step_fn = build_step()
                check = compile_and_check()
            if compiled_step is None:
                # warn mode with a compile-time RESOURCE_EXHAUSTED:
                # 'proceed anyway' has nothing to proceed with — the
                # honest outcome is the ORIGINAL compiler error, as
                # before round 16 (not a NoneType crash 30 lines later)
                raise compile_err["e"]
            if check.verdict == "over":
                log.warning(f"memory admission: {check.describe()} "
                            f"(--on_oom_risk warn: proceeding)")
            elif rungs_applied:
                log.warning(f"admitted after degradation ladder "
                            f"{tuple(rungs_applied)}: {check.describe()}")
            # dispatch-retry insurance: ONLY under armed pressure
            # injection keep a HOST copy of the donated trees until
            # the first step retires — a failed dispatch consumes
            # donated buffers on real accelerators, and the
            # retry-at-next-rung contract needs intact state to
            # re-place. In degrade mode this point is only reached
            # with verdict ok/unknown (an over verdict walked a rung
            # or raised), and neither justifies a whole-model
            # device_get per run: 'unknown' is EVERY run on platforms
            # without memory analysis. CPU ignores donation (retries
            # in place, no copy); multi-host skips it (device_get
            # cannot fetch cross-process shards — a pod-scale OOM is
            # the controller's problem, not an in-process retry).
            if (oom_mode == "degrade" and not multiproc
                    and jax.default_backend() != "cpu"
                    and injector.kind == "hbm_pressure"):
                # graftlint: disable=sync-hazard(OOM-retry insurance snapshot, armed only under a live admission-risk signal)
                oom_snap = jax.device_get((trainable, opt_state))

        stream = make_stream(start_step, start_step)
        # in-process rollback state (armed only when the CLI wired the
        # inverse load hook AND checkpoints exist to roll back to)
        rb = None
        if (load_hook is not None and ckpt_path
                and getattr(args, "rollback_budget", 0) > 0):
            rb = {"budget": int(args.rollback_budget), "count": 0,
                  "streak": 0, "due": None, "suppressed": False,
                  "skip_streak": max(
                      getattr(args, "rollback_skip_streak", 3), 1),
                  "offset": max(
                      getattr(args, "rollback_data_offset", 1), 0)}
        metrics = {}
        epoch = 0
        profile_dir = getattr(args, "profile_dir", "")
        prof_start = start_step + getattr(args, "profile_start", 10)
        prof_end = prof_start + getattr(args, "profile_steps", 5)
        prof_active = False

        def maybe_profile(step, closing=False):
            nonlocal prof_active
            if not profile_dir:
                return
            try:
                if step == prof_start and not prof_active:
                    jax.profiler.start_trace(profile_dir)
                    prof_active = True
                elif (step >= prof_end or closing) and prof_active:
                    if metrics:
                        # graftlint: disable=sync-hazard(profiler stop drains queued work so the trace window holds it)
                        jax.device_get(metrics["loss"])
                    jax.profiler.stop_trace()
                    prof_active = False
                    log.info(f"profiler trace -> {profile_dir}")
                    # the stream names every trace taken of its run,
                    # the manual window like the AutoProfiler's
                    tel.emit("profile_capture", step=step,
                             trigger="manual", path=profile_dir,
                             steps=step - prof_start, budget_left=None)
            except Exception as e:  # profiling must never kill training
                log.warning(f"profiler: {e}")
                prof_active = False

        # Per-step metrics stay on device; they are buffered and pulled to host
        # in ONE device_get per log boundary. An unconditional per-step
        # float(loss) would sync the dispatch queue every step and serialize
        # the pipeline (the reference has no such concern: it is synchronous
        # CPU code; on TPU async dispatch is the throughput lever).
        buffered = []  # [(step, epoch, tokens, device_metrics), ...]
        t_interval = time.perf_counter()
        slept_ms = 0.0  # governor sleep inside the interval, excluded from dt
        waited_ms = 0.0  # host-wait: step loop blocked on the input pipeline
        # flush cadence: the log interval; if step logging is off but a CSV was
        # requested, flush every 50 steps so rows survive a crash; 1000-step
        # hard cap bounds the device-metrics buffer in all cases.
        flush_every = (min(args.log_interval, 1000) if args.log_interval
                       else (50 if metrics_csv else 1000))
        # the flush counter (step_stats.drained_ms / drained_parts_ms): a
        # flush fetches the NEWEST step, so from its device_get's return
        # until the next dispatch returns the device has nothing queued.
        # `drain` holds the open period's stamps from the flush until the
        # loop closes it after that dispatch; `drained` is the closed
        # (ms, parts), which the NEXT flush's line carries.
        drain, drained = None, (None, None)

        def flush_buffered(emit_log):
            """One host sync for everything buffered since the last flush —
            the telemetry zero-sync invariant: the on-device health scalars
            (param_norm/update_ratio/nonfinite_count) ride the SAME
            device_get as loss/grad_norm/lr, so observability adds no syncs.
            Rows in a flush share the interval-averaged step_time_ms (per-step
            wall time under async dispatch measures only dispatch latency, so
            the average over a synced interval is the honest number) and
            host_wait_ms — the interval-averaged time the step loop spent
            BLOCKED pulling the next batch from the input pipeline (queue
            wait + lookahead placement; with the producer keeping up this is
            ~0, which is the observable proof the prefetch overlap works —
            the host/device breakdown, not an assumption). One step_stats
            telemetry event per flush; the host-side spike detector sees
            every per-step loss and emits `anomaly` events instead of
            silently training through divergence."""
            nonlocal t_interval, slept_ms, waited_ms, drain, drained
            with tracer.span("train.flush.sync", "loop"):
                # graftlint: disable=sync-hazard(the zero-sync contract: ONE device_get per metrics flush, DESIGN.md section 13)
                fetched = jax.device_get([m for _, _, _, m in buffered])
            t_sync = time.perf_counter()
            with tracer.span("train.flush.sensors", "loop"):
                dt_ms = ((t_sync - t_interval) * 1000 - slept_ms) \
                    / len(buffered)
                wait_ms = waited_ms / len(buffered)
                # the device_get above SYNCED the interval, so dt_ms is the
                # honest per-step time (a per-iteration clock under async
                # dispatch measures only enqueue latency): feed the fleet
                # timing consumers — the straggler window and the watchdog's
                # deadline median — from here, the same number step_stats
                # publishes
                prior_n, prior_med = step_clock.n, step_clock.median_ms()
                step_clock.record(dt_ms / 1000.0)
                if wd is not None:
                    wd.pet(buffered[-1][0], dt_ms / 1000.0)
                # slow-step flight-recorder trigger (--auto_profile): this
                # flush interval ran a multiple of the rolling median —
                # arm a capture over the NEXT steps while whatever made it
                # slow is plausibly still happening. The median is the
                # PRIOR window's (the slow sample must not judge itself),
                # and the manual --profile_dir window keeps priority.
                if (auto_prof is not None and slow_mult > 0 and prior_n >= 3
                        and prior_med > 0 and dt_ms > slow_mult * prior_med
                        and not prof_active):
                    if auto_prof.trigger("slow_step", buffered[-1][0] + 1):
                        log.warning(
                            f"auto_profile: step time {dt_ms:.1f} ms > "
                            f"{slow_mult:g}x rolling median "
                            f"{prior_med:.1f} ms — capturing "
                            f"{auto_prof.steps} step(s)")
                # live bytes when the backend reports them, else the
                # compiled-peak estimate, else NULL — a backend with no
                # memory accounting must not masquerade as 0 MB (round 16;
                # live_hbm_mb logs the backend once)
                hbm = live_hbm_mb()
                if hbm is None:
                    hbm = peak_hbm["mb"] or None
                mfu = mfu_from(flops_per_step, dt_ms / 1000, peak_flops)
                for (s, ep, toks, _), m in zip(buffered, fetched):
                    loss = float(m["loss"])
                    avg = ema.update(loss)
                    anom = spikes.update(loss)
                    if anom is not None:
                        tel.emit("anomaly", step=s + 1, loss=loss, ema=avg,
                                 **anom)
                        log.warning(
                            f"anomaly @ step {s + 1}: {anom['kind']} "
                            f"loss={loss:.4f}"
                            + (f" z={anom['zscore']}" if anom["zscore"]
                               else ""))
                        if auto_prof is not None and not prof_active:
                            auto_prof.trigger(anom["kind"], s + 1)
                    if rb is not None:
                        # rollback triggers, evaluated per flushed step:
                        # sustained divergence (the detector's escalated
                        # kind), a streak of skipped/nonfinite steps, or a
                        # nonfinite loss with the skip guard OFF (params
                        # already poisoned — waiting is pointless). A
                        # single skipped step or one-off loss_spike never
                        # triggers: that is the guard/winsorizer working.
                        # `suppressed` (set by a FAILED rollback) holds
                        # triggers until a clean step ends the episode —
                        # without it a checkpoint-less NaN run would emit
                        # one ok=false rollback + a full lineage CRC walk
                        # per step forever (stream-sizing rule).
                        bad = (int(m.get("skipped") or 0) > 0
                               or not math.isfinite(loss))
                        rb["streak"] = rb["streak"] + 1 if bad else 0
                        if not bad:
                            rb["suppressed"] = False
                        if rb["due"] is not None or rb["suppressed"]:
                            pass
                        elif anom is not None \
                                and anom["kind"] == "divergence":
                            rb["due"] = ("divergence", s + 1)
                        elif rb["streak"] >= rb["skip_streak"]:
                            rb["due"] = ("skip_streak", s + 1)
                        elif (not math.isfinite(loss)
                              and not tc.skip_nonfinite):
                            rb["due"] = ("nonfinite_loss", s + 1)
                    if metrics_csv:
                        metrics_csv.log(epoch=ep, step=s + 1, loss=loss,
                                        avg_loss=avg, lr=float(m["lr"]),
                                        grad_norm=float(m["grad_norm"]),
                                        step_time_ms=dt_ms,
                                        host_wait_ms=wait_ms,
                                        tok_s=toks / (dt_ms / 1000), mfu=mfu,
                                        hbm_mb=hbm if hbm is not None else 0.0)
                s, ep, toks, _ = buffered[-1]
                m = fetched[-1]
                opt_f = lambda k: (float(m[k]) if k in m else None)
            t_sensors = time.perf_counter()
            with tracer.span("train.flush.emit", "loop"):
                tel.emit(
                    "step_stats", step=s + 1, loss=float(m["loss"]),
                    # graftlint: disable=sync-hazard(ema is the host-side spike detector's Python scalar, not a device array)
                    ema=float(ema.value), lr=float(m["lr"]),
                    grad_norm=float(m["grad_norm"]), step_time_ms=dt_ms,
                    host_wait_ms=wait_ms, slept_ms=slept_ms,
                    tok_s=toks / (dt_ms / 1000), mfu=mfu,
                    param_norm=opt_f("param_norm"),
                    update_ratio=opt_f("update_ratio"),
                    nonfinite_count=(int(m["nonfinite_count"])
                                     if "nonfinite_count" in m else None),
                    # COUNT over the flush interval (unlike the last-step
                    # health scalars): the report's skipped-step total is a
                    # sum of these, so no skip can fall between flushes
                    skipped=(sum(int(fm["skipped"]) for fm in fetched)
                             if "skipped" in m else None),
                    hbm_mb=hbm, queue_depth=stream.queue_depth(),
                    host_step_ms=host_step_ms["latest"],
                    # the PREVIOUS flush's drained period (a line cannot
                    # hold the time of its own write)
                    drained_ms=drained[0], drained_parts_ms=drained[1],
                    # the model's own device counters (make_train_step):
                    # mean / max over the flush's steps, under their names
                    **{k: sum(float(fm["counters_sum"][k])
                              for fm in fetched) / len(fetched)
                       for k in m.get("counters_sum", ())},
                    **{k: max(float(fm["counters_max"][k])
                              for fm in fetched)
                       for k in m.get("counters_max", ())})
            t_emit = time.perf_counter()
            with tracer.span("train.flush.log", "loop"):
                if emit_log and args.log_interval:
                    log.info(
                        f"step {s + 1}/{total_steps} "
                        f"loss={float(m['loss']):.4f} "
                        f"ema={ema.value:.4f} "
                        f"ppl={perplexity_from_loss(float(m['loss'])):.2f} "
                        f"grad_norm={float(m['grad_norm']):.3f} "
                        f"lr={float(m['lr']):.2e} "
                        f"{toks / (dt_ms / 1000):.0f} tok/s "
                        + (f"mfu={mfu:.3f} " if mfu is not None else "")
                        + f"host_wait={wait_ms:.1f}ms")
            buffered.clear()
            slept_ms = 0.0
            waited_ms = 0.0
            t_interval = time.perf_counter()
            # the device has been drained since t_sync; the loop closes
            # this period after the next dispatch (drained_record)
            drain, drained = (t_sync, t_sensors, t_emit, t_interval), \
                (None, None)

        def flush_metrics(emit_log=True):
            """Flush what is buffered (flush_buffered), as one
            `train.flush` span; nothing buffered, nothing done."""
            if buffered:
                with tracer.span("train.flush", "loop"):
                    flush_buffered(emit_log)

        def attempt_rollback(reason: str, at_step: int):
            """Close the sensors→recovery loop IN PROCESS (DESIGN.md
            §20): resolve the newest VERIFIED lineage checkpoint at or
            below the trigger step, reload trainable + Adam sidecar as
            host numpy, place both at THIS run's mesh (the r13
            elastic-resume placement — replicate for LoRA-style
            trainables, FSDP re-shard otherwise), rebuild the data
            stream past the poison region, and hand the loop its resume
            step. The compiled step is REUSED — shapes, shardings and
            donation are unchanged, so recovery costs a load + place,
            not a recompile. Returns the resume step, or None when no
            rollback happened (every verdict lands in the stream)."""
            nonlocal trainable, opt_state, stream, ema, spikes, \
                t_interval
            with pause():
                # the WHOLE recovery is a legitimate long pause — the
                # drain of an in-flight multi-GB write and the CRC walk
                # over the lineage candidates can each exceed any
                # step-derived watchdog deadline, same as the load
                try:  # lineage must be settled: finish in-flight writes
                    ckpt.drain()
                except Exception as e:
                    log.warning(f"rollback: checkpoint drain failed "
                                f"({e}); resolving against what is on "
                                f"disk")
                from mobilefinetuner_tpu.io.checkpoints import \
                    resolve_checkpoint
                # max_step = at_step - 1: a checkpoint written at the
                # very trigger boundary may already hold the poisoned
                # update (skip guard off) — never "recover" into it
                resolved, to_step, events = resolve_checkpoint(
                    None, verify=bool(getattr(args, "verify_ckpt", 1)),
                    lineage_base=ckpt_path, max_step=at_step - 1)
                for ev in events:
                    tel.emit("ckpt_verify", **ev)
                if resolved is None or to_step is None:
                    tel.emit("rollback", step=at_step, reason=reason,
                             ok=False, to_step=None, steps_lost=None,
                             ckpt=None, data_offset=None,
                             budget_left=rb["budget"])
                    log.warning(f"rollback wanted ({reason} @ step "
                                f"{at_step}) but no verified "
                                f"checkpoint exists — continuing "
                                f"without")
                    # suppress further triggers until a CLEAN step ends
                    # this episode: a checkpoint-less diverged run must
                    # not emit one ok=false rollback + a lineage CRC
                    # walk per step forever
                    rb["streak"] = 0
                    rb["suppressed"] = True
                    return None
                tr_h, opt_h = load_hook(resolved)
                trainable, opt_state = place_state(tr_h, opt_h)
            rb["count"] += 1
            rb["budget"] -= 1
            rb["streak"] = 0
            data_offset = rb["count"] * rb["offset"]
            stream.close()
            stream = make_stream(to_step, to_step + data_offset)
            # fresh host-side statistics: the old EMA/variance describe
            # the diverged trajectory, not the restored one (count_hint
            # keeps the detector armed — post-rollback losses are
            # healthy, not early-training wild)
            ema = EMA(args.ema_beta)
            spikes = SpikeDetector(SpikeConfig(
                zscore=getattr(args, "spike_z", 8.0),
                beta=getattr(args, "spike_beta", 0.98),
                warmup=getattr(args, "spike_warmup", 20)))
            spikes.seed([], count_hint=to_step)
            cur_step["step"] = to_step
            # recovery wall time is not step time: restart the flush
            # interval or the first post-rollback flush would fold the
            # whole drain+verify+load into its per-step average (and
            # feed that corrupted sample to the watchdog deadline and
            # the straggler window)
            t_interval = time.perf_counter()
            tel.emit("rollback", step=at_step, reason=reason, ok=True,
                     to_step=to_step, steps_lost=at_step - to_step,
                     ckpt=resolved, data_offset=data_offset,
                     budget_left=rb["budget"])
            log.warning(
                f"ROLLBACK ({reason}): step {at_step} -> {to_step} "
                f"from {resolved} ({at_step - to_step} step(s) lost, "
                f"data offset +{data_offset}, budget left "
                f"{rb['budget']})")
            return to_step

        if wd is not None:
            wd.start()
        try:
            step = start_step
            while step < total_steps:
                # the prefetched stream yields batches already placed (and
                # dropout-keyed); this next() is the step loop's only input
                # dependency, and the time it blocks is the host/device
                # breakdown's host_wait_ms
                meter.enter("input_wait")
                with tracer.span("train.input_wait", "loop"):
                    t_wait = time.perf_counter()
                    step_i, epoch, batch = next(stream)
                    t_input = time.perf_counter()
                waited_ms += (t_input - t_wait) * 1000
                meter.enter("step")
                assert step_i == step  # strict order preservation
                maybe_profile(step)
                if injector.kind == "slow_step":
                    injector.maybe_slow(step)
                # the step was AOT-compiled (and admission-checked)
                # BEFORE the stream existed; a RESOURCE_EXHAUSTED that
                # still escapes the dispatch walks the remaining
                # degradation ladder and retries the SAME batch (the
                # batch is not donated — only trainable/opt are, and
                # recover_dispatch_oom restores those)
                if drain is not None:
                    t_dispatch = time.perf_counter()
                with tracer.span("train.dispatch", "loop"):
                    while True:
                        try:
                            if injector.kind == "hbm_pressure":
                                injector.maybe_oom_dispatch(step)
                            trainable, opt_state, metrics = compiled_step(
                                trainable, frozen, opt_state, batch,
                                jnp.int32(step))
                            break
                        except (KeyboardInterrupt, SystemExit):
                            raise
                        except Exception as e:
                            if not mg.is_resource_exhausted(e):
                                raise
                            recover_dispatch_oom(e, step)
                if drain is not None:
                    # the first dispatch after a flush: the device has
                    # work again, the drained period is closed. Whatever
                    # restarted the flush interval since (an eval, a
                    # save, a rollback: "that time is not step time")
                    # owns the time in between: no record then
                    if drain[-1] == t_interval:
                        drained = drained_record(
                            drain, t_wait, t_input, t_dispatch,
                            time.perf_counter())
                    drain = None
                oom_snap = None  # a retired step ends the retry window
                toks = batch["input_ids"].shape[0] * batch["input_ids"].shape[1]
                buffered.append((step, epoch, toks, metrics))
                if auto_prof is not None and auto_prof.active:
                    # countdown an armed capture; the stop syncs the
                    # device first so the async-dispatched step work is
                    # actually inside the captured window
                    auto_prof.tick(step, sync=lambda m=metrics:
                                   # graftlint: disable=sync-hazard(the flight recorder's stop syncs so the dispatched step lands inside the capture)
                                   jax.device_get(m["loss"]))
                log_boundary = bool(args.log_interval) \
                    and (step + 1) % args.log_interval == 0
                if log_boundary or (step + 1) % flush_every == 0:
                    # capped flushes (flush_every < log_interval) only write
                    # CSV rows; the log line fires exactly on the requested
                    # cadence
                    flush_metrics(emit_log=log_boundary)
                # idle-reset only (no duration sample — the honest
                # per-step time comes from the flush's synced interval
                # average, fed to the watchdog/straggler window inside
                # flush_metrics)
                if wd is not None:
                    wd.pet(step)
                if strag_k and (step + 1) % strag_k == 0 \
                        and step_clock.n:
                    # collective on a deterministic cadence: every
                    # process reaches this gather at the same step
                    fleet = allgather_scalars(step_clock.median_ms())
                    host_step_ms["latest"] = {
                        str(i): round(v, 3) for i, v in enumerate(fleet)}
                    med = statistics.median(fleet)
                    if coord and med > 0:
                        for h, v in enumerate(fleet):
                            if v > strag_mult * med:
                                tel.emit("straggler", step=step + 1,
                                         slow_host=h, host_ms=round(v, 3),
                                         fleet_ms=round(med, 3),
                                         ratio=round(v / med, 3))
                                log.warning(
                                    f"straggler: host {h} at {v:.1f} "
                                    f"ms/step vs fleet median "
                                    f"{med:.1f} ms ({v / med:.2f}x)")
                                if auto_prof is not None \
                                        and not prof_active:
                                    auto_prof.trigger("straggler",
                                                      step + 1)
                    step_clock.reset()

                if (args.eval_interval and valid_ds is not None
                        and (step + 1) % args.eval_interval == 0):
                    flush_metrics(emit_log=False)  # off-cadence boundary flush
                    meter.enter("eval")
                    # pause(): an eval may exceed any step deadline
                    with tracer.span("train.eval", "loop"), pause():
                        ev = evaluate(eval_step, trainable, frozen,
                                      valid_ds, args.eval_batches,
                                      mesh=eval_mesh,
                                      sequence_parallel=eval_sp,
                                      prefetch=prefetch_depth)
                    meter.enter("step")
                    log.info(f"eval @ step {step + 1}: loss={ev['loss']:.4f} "
                             f"ppl={ev['ppl']:.2f} ({ev['tokens']} tokens)")
                    if eval_jsonl:
                        eval_jsonl.write({"type": "eval", "step": step + 1,
                                          "loss": ev["loss"], "ppl": ev["ppl"],
                                          "tokens": ev["tokens"],
                                          "time": time.time() - t_start})
                    tel.emit("eval", step=step + 1, loss=ev["loss"],
                             ppl=ev["ppl"], tokens=ev["tokens"])
                    t_interval = time.perf_counter()  # eval time ≠ step time

                if args.save_every and save_hook and (step + 1) % \
                        args.save_every == 0 and (step + 1) < total_steps:
                    flush_metrics(emit_log=False)  # off-cadence boundary flush
                    # the meter's checkpoint bucket spans only this
                    # blocking call: under --async_save that is the
                    # batched snapshot (+ enqueue), and the background
                    # write's wall time stays charged to `step` — the
                    # overlap IS the feature. The checkpoint telemetry
                    # event (with the snapshot/write split) is emitted
                    # by the checkpointer when the write completes.
                    meter.enter("checkpoint")
                    # pause(): a slow save is not a hang
                    with tracer.span("train.checkpoint", "loop"), pause():
                        save_hook(step + 1, trainable, opt_state,
                                  final=False, ckpt=ckpt)
                    meter.enter("step")
                    t_interval = time.perf_counter()  # save time ≠ step time
                    if injector.kind == "ckpt_corrupt" and ckpt_path:
                        # fault harness: bit-flip the newest lineage
                        # entry AFTER its write lands, so a later
                        # rollback/resume must fall back down the chain
                        try:
                            ckpt.drain()
                        except Exception:
                            pass
                        injector.maybe_corrupt_ckpt(ckpt_path)

                meter.enter("governor_sleep")
                with tracer.span("train.governor_sleep", "loop"):
                    slept_ms += governor.throttle(step)
                meter.enter("step")
                done_steps = step + 1 - start_step
                cur_step["step"] = step + 1

                if guard is not None and guard.triggered:
                    # preemption drain: the step in flight is done —
                    # flush the metrics buffer, take ONE final atomic
                    # checkpoint (final=True drains the async writer:
                    # the process must not exit before the recovery
                    # point is durable), end the stream with a
                    # schema-valid run_end{reason=preempted}, and exit
                    # with the RESUMABLE code. `--resume_from` the final
                    # artifact continues at step+1 with the data stream
                    # fast-forwarded (skip_steps) — the preemption cost
                    # is this one drain, not the steps since the last
                    # periodic save.
                    log.warning(
                        f"{guard.signal_name} received: draining at step "
                        f"{step + 1} (final save, then exit "
                        f"{EXIT_PREEMPTED})")
                    flush_metrics(emit_log=False)
                    tel.emit("preempt", step=step + 1,
                             signal=guard.signal_name or "SIGTERM")
                    if save_hook is not None:
                        meter.enter("checkpoint")
                        # pause(): a slow drain save is not a hang
                        with tracer.span("train.checkpoint", "loop"), \
                                pause():
                            save_hook(step + 1, trainable, opt_state,
                                      final=True, ckpt=ckpt)
                    meter.enter("shutdown")
                    if metrics_csv:
                        metrics_csv.close()
                    end_run("preempted", done_steps, reason="preempted")
                    raise SystemExit(EXIT_PREEMPTED)

                if rb is not None and rb["due"] is not None:
                    # a flush inside THIS iteration raised a trigger:
                    # act at the step boundary (the metrics buffer is
                    # empty — triggers only arise from a flush)
                    reason, at_step = rb["due"]
                    rb["due"] = None
                    if rb["budget"] <= 0:
                        tel.emit("rollback", step=at_step, reason=reason,
                                 ok=False, to_step=None, steps_lost=None,
                                 ckpt=None, data_offset=None,
                                 budget_left=0)
                        log.warning(
                            f"rollback budget exhausted; training on "
                            f"through {reason} @ step {at_step}")
                        rb = None  # stop evaluating triggers
                    else:
                        resumed = attempt_rollback(reason, at_step)
                        if resumed is not None:
                            step = resumed
                            continue
                step += 1
        except BaseException as e:
            # the stream records HOW the run ended before the exception
            # propagates — a crashed run's tail is run_start..last flush +
            # run_end{exit: <type>}, which is what post-mortems need
            end_run(type(e).__name__, done_steps)
            raise
        finally:
            # stop the producer thread even when the consumer dies mid-epoch
            # (compiled-step failure, KeyboardInterrupt): no leaked threads,
            # and the original exception propagates untouched. The
            # watchdog is NOT stopped here — the post-loop tail (final
            # eval + final save) stays monitored; wd_ref's outer finally
            # owns the stop.
            stream.close()
            # profiler-leak fix: a run whose total_steps end (or whose
            # exception) lands inside the profiling window used to leave the
            # trace open — stop_trace() was only reachable from inside the
            # step loop. Closing here makes the trace land on EVERY exit
            # path (regression: tests/test_cli.py short-run profile test).
            if prof_active:
                maybe_profile(step, closing=True)

        # the post-loop tail (final flush/eval/save) carries the same
        # run_end-on-exception contract as the loop: a disk-full save or a
        # lost-worker collective here must still leave run_end{exit: <type>}
        meter.enter("shutdown")
        try:
            flush_metrics()
            if valid_ds is not None and args.eval_interval:
                meter.enter("eval")
                # pause(): unbounded legitimate pause
                with tracer.span("train.eval", "loop"), pause():
                    ev = evaluate(eval_step, trainable, frozen, valid_ds,
                                  args.eval_batches, mesh=eval_mesh,
                                  sequence_parallel=eval_sp,
                                  prefetch=prefetch_depth)
                meter.enter("shutdown")
                log.info(f"final eval: loss={ev['loss']:.4f} "
                         f"ppl={ev['ppl']:.2f}")
                if eval_jsonl:
                    eval_jsonl.write({"type": "final_eval",
                                      "step": total_steps,
                                      "loss": ev["loss"], "ppl": ev["ppl"],
                                      "tokens": ev["tokens"]})
                tel.emit("eval", step=total_steps, loss=ev["loss"],
                         ppl=ev["ppl"], tokens=ev["tokens"])
            if save_hook:
                # final=True drains the writer inside the hook's submit:
                # the run must not end before its last checkpoint is on
                # disk, so this blocking span (snapshot + any queued
                # writes) honestly lands in the checkpoint bucket
                meter.enter("checkpoint")
                with tracer.span("train.checkpoint", "loop"), pause():
                    save_hook(total_steps, trainable, opt_state,
                              final=True, ckpt=ckpt)
                meter.enter("shutdown")
        except BaseException as e:
            end_run(type(e).__name__, done_steps)
            raise
        live = live_hbm_mb()
        log.info(f"peak HBM: {peak_hbm['mb']:.0f} MB (compiled estimate)"
                 + (f", {live:.0f} MB live" if live else ""))
        if metrics_csv:
            metrics_csv.close()
        end_run("ok", total_steps - start_step)
        return trainable, opt_state, metrics
    except BaseException as e:
        end_run(type(e).__name__, done_steps)
        raise
    finally:
        # the watchdog outlives the step loop on purpose (the post-loop
        # tail stays monitored); this is the single stop for every exit
        # path — return, loop exception, tail exception, setup failure
        if wd is not None:
            wd.stop()
        # a capture left open by an exiting loop is stopped (the trace
        # of the steps that DID run is worth keeping), and the metrics
        # endpoint goes down with the run it described
        if auto_prof is not None:
            auto_prof.close()
        if metrics_srv is not None:
            metrics_srv.close()
        # belt-and-braces: end_run already drained the writer on every
        # path (close is idempotent) — this guards exits that never
        # reached an end_run, e.g. a failure inside end_run itself
        if ckpt is not None:
            ckpt.close(raise_errors=False)
        # restore the process's previous signal handlers: repeated
        # in-process runs (tests, notebooks) must not stack handlers
        if guard is not None:
            guard.uninstall()


def setup_frozen_params(args, params, mesh):
    """Place frozen base params: FSDP shardings + optional host offload.

    Returns (placed_params, fetch_fn, offload_arg):
      - fetch_fn pulls ALL offloaded leaves to device at once (the
        --shard_stream 0 path: fast, but the whole fetched tree is
        HBM-resident for the step);
      - offload_arg is the (plan, shardings) pair the model forwards accept
        to stream block weights per layer instead (default; the budget then
        bounds peak HBM, not just idle placement). None when offload is
        disabled or streaming is turned off.
    """
    shardings = params_shardings(params, mesh)
    ocfg = offload_config_from_args(args)
    plan = plan_placement(params, ocfg)
    placed = apply_placement(params, plan, shardings, ocfg)
    if ocfg.enable:
        stats = placement_stats(params, plan, ocfg)
        log.info(
            f"offload: {stats['n_offloaded']} params "
            f"({stats['offloaded_bytes'] / 2**20:.0f} MB) -> host RAM, "
            f"{stats['resident_bytes'] / 2**20:.0f} MB resident "
            f"(budget {args.shard_budget_mb} MB, "
            f"stream={'on' if getattr(args, 'shard_stream', 1) else 'off'})")

    def fetch_fn(p):
        return fetch(p, plan, shardings, compute_dtype=None)

    offload_arg = ((plan, shardings)
                   if ocfg.enable and getattr(args, "shard_stream", 1)
                   else None)
    return placed, fetch_fn, offload_arg
