"""Benchmark suite: the driver's BASELINE configs on one chip.

Covers the three driver configs (BASELINE.md): GPT-2-small LoRA (r=8 α=16
S=128), GPT-2-small full fine-tune, and Gemma-3-270M LoRA (r=8 α=32 S=256,
full targets, chunked 262k-vocab CE) — each with bf16/f32, grad-accum, and
host-offload-streaming variants, plus a long-context config where the
Pallas flash kernel (auto-dispatched) is measured against the forced XLA
path. Per config: tokens/sec/chip, an MFU estimate, and the compiled peak
device memory (XLA memory analysis: device args + temps + outputs − donated
aliases).

The reference's analog is scripts/benchmark/ (wall-time + peak RSS over
baseline-vs-sharded configs, measure_rss.sh:22-42) — peak compiled HBM is
the TPU-native RSS, and the offload variants are the sharded runs.

stdout: ONE JSON line (the headline GPT-2s LoRA config; driver contract).
The full suite is written to BENCH_SUITE.json and summarized on stderr.
Baseline: the reference's 4-6 h/epoch (batch=4, S=128, mobile SoC,
README.md:419) ≈ 2.39M-token epoch / 18000 s ≈ 133 tokens/sec.

Sync note: every timed region ends by reading a scalar back to host, which
waits for the device.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from mobilefinetuner_tpu.core.config import GPT2Config, Gemma3TextConfig
from mobilefinetuner_tpu.lora.lora import (LoRASpec, init_lora_gemma3,
                                           init_lora_gpt2, trainable_mask)
from mobilefinetuner_tpu.models import gemma3, gpt2
from mobilefinetuner_tpu.ops.loss import (chunked_lm_cross_entropy_sum,
                                          lm_cross_entropy_sum)
from mobilefinetuner_tpu.parallel.mesh import (make_mesh,
                                               replicated_sharding)
from mobilefinetuner_tpu.parallel.offload import (OffloadConfig,
                                                  apply_placement,
                                                  plan_placement,
                                                  resolve_offload)
from mobilefinetuner_tpu.train.trainer import (TrainConfig, init_optimizer,
                                               make_train_step)

BASELINE_TOKENS_PER_SEC = 2_391_884 / 18_000.0  # ≈ 132.9 (reference CPU)
# Per-chip bf16 peak resolved from the device kind via the SAME table the
# training loop's in-loop MFU uses (core/telemetry.device_peak_flops), so
# the two MFU columns share numerator AND denominator; 197e12 (v5e) is
# the fallback for unknown kinds (CPU smoke runs — their MFU is not
# meaningful anyway). The same number applies to "float32" configs:
# XLA's default matmul precision on TPU runs f32 matmuls as bf16 passes
# on the MXU, so the available peak is the bf16 one (measured f32 MFU vs
# a hypothetical smaller f32 peak came out >1, confirming the
# default-precision lowering). Resolved LAZILY: device_peak_flops()
# touches jax.devices(), and importing bench must not initialize the
# backend as a side effect (it would pin a single-process backend under
# an importer that calls jax.distributed.initialize afterwards).
from mobilefinetuner_tpu.core.telemetry import device_peak_flops

_PEAK_CACHE = {}


def peak_flops(dtype: str) -> float:
    if "chip" not in _PEAK_CACHE:
        _PEAK_CACHE["chip"] = device_peak_flops() or 197e12
    return _PEAK_CACHE["chip"]


# The analytic per-step FLOP estimator lives in core/telemetry.py so the
# in-loop step_stats.mfu and this suite's MFU column agree by
# construction (tests/test_bench_contract.py pins the identity).
from mobilefinetuner_tpu.core.telemetry import transformer_flops  # noqa: E402


def executed_flops(n_block_mm, n_head_mm, n_active, B, S, n_layer, n_head,
                   head_dim, full_ft, remat_blocks, remat_head,
                   attn_factor=1.0):
    """FLOPs the compiled step actually EXECUTES — the MFU denominator the
    6ND-style formula above gets wrong in two ways (DESIGN.md §5): it
    counts neither the rematerialization recompute (the checkpointed
    chunked-CE head and, with --remat, the whole block stack run forward
    a second time in the backward) nor the fact that the embedding table
    GATHERS rather than multiplies (only matmul parameters do FLOPs).
    n_block_mm: matmul params in the layer stack (ndim>=3 leaves);
    n_head_mm: lm-head matmul params (V*H for the tied-embed head);
    n_active: EXTRA trainable matmul params beyond the base stacks (the
    LoRA A/B factors; pass 0 for full FT — the full_ft branch already
    counts dW over n_block_mm + n_head_mm). attn_factor: fraction of the dense S^2
    attention actually executed (_attn_factor; 1.0 for the XLA path)."""
    T = B * S
    attn = int(4 * B * n_layer * n_head * S * S * head_dim * attn_factor)
    mm = n_block_mm + n_head_mm + n_active
    fwd = 2 * T * mm + attn
    recompute = ((2 * T * (n_block_mm + n_active) + attn)
                 if remat_blocks else 0) \
        + (2 * T * n_head_mm if remat_head else 0)
    bwd_dx = 2 * T * mm + 2 * attn
    bwd_dw = 2 * T * (n_active if not full_ft
                      else n_block_mm + n_head_mm + n_active)
    return fwd + recompute + bwd_dx + bwd_dw


def _attn_factor(S, head_dim, impl="auto"):
    """Fraction of the dense S^2 attention the step actually executes.
    The flash kernel visits only causally-reachable 512-row blocks: with
    nb = S/512 blocks it runs (nb+1)/(2*nb) of the dense work (1.0 at
    S=512 — a single block skips nothing; 0.75 at S=1024; -> 0.5 as nb
    grows). XLA's masked dense attention always executes everything."""
    from mobilefinetuner_tpu.ops.attention import resolve_impl
    use_flash = impl == "flash" or (impl == "auto"
                                    and resolve_impl(S, head_dim)
                                    == "flash")
    if not use_flash:
        return 1.0
    nb = max(S // 512, 1)
    return (nb + 1) / (2 * nb)


def matmul_param_counts(params, head_key):
    """(block matmul params, head matmul params): ndim>=3 leaves under
    "blocks" are the [L, in, out] weight stacks; the tied head is the
    [V, H] table, a real matmul in the logits projection."""
    n_block = sum(x.size for x in jax.tree.leaves(params["blocks"])
                  if x.ndim >= 3)
    n_head = params[head_key].size
    return n_block, n_head


# Loss columns are comparable ACROSS rows of the same model: every row
# trains on the SAME seeded token stream (prefix-stable across batch
# shapes) for the same number of TOKENS (not steps), then the loss is
# probed on a shared held-out eval stream. Rows that differ only in
# batching/offload/remat land within optimizer-dynamics noise of each
# other, so the column is a training-quality regression signal (round-3
# verdict: per-row step counts made losses pure config skew).
# 24576 = lcm of every row's tokens/step (1024..24576, all powers of two
# times 1 or 3), so the mark is EXACT for every current row; a future
# non-dividing shape rounds up and reports its actual loss_tokens_seen.
LOSS_MARK_TOKENS = 24_576
WARMUP_STEPS = 3


def _loss_mark(tokens_per_step: int) -> int:
    """Steps to reach the loss mark (shared by measure/row_batches so the
    stream length and the training schedule cannot drift apart)."""
    return -(-LOSS_MARK_TOKENS // tokens_per_step)


def measure(step_fn, trainable, frozen, opt, batches, eval_batch,
            steps) -> dict:
    from mobilefinetuner_tpu.core.xla_stats import compiled_peak_bytes
    # AOT-compile once and call the executable directly (jit dispatch
    # would recompile: AOT results don't populate the jit cache), reusing
    # the same compiled object for the memory analysis.
    compiled = step_fn.lower(trainable, frozen, opt, batches[0],
                             jnp.int32(0)).compile()
    peak = compiled_peak_bytes(compiled)
    tokens_per_step = int(batches[0]["input_ids"].size)
    mark = _loss_mark(tokens_per_step)
    tr, op = trainable, opt
    for s in range(mark):
        tr, op, m = compiled(tr, frozen, op, batches[s], jnp.int32(s))
    # comparable-loss probe: the step's loss metric is evaluated at the
    # CURRENT weights before its update, so feeding the shared eval batch
    # reads held-out loss after exactly `mark * tokens_per_step`
    # (== LOSS_MARK_TOKENS for every current row) training tokens. The
    # probe's outputs MUST become the live state (tr/op are donated, so
    # the inputs are dead after the call); that lands one eval-batch
    # update in the weights used for the timed window — accepted: the
    # loss column is read pre-update and throughput is schedule-identical.
    tr, op, m = compiled(tr, frozen, op, eval_batch, jnp.int32(mark))
    loss = float(m["loss"])
    # rows whose mark is short still get WARMUP_STEPS executions before
    # the timed window opens
    warm = max(0, WARMUP_STEPS - mark)
    for s in range(warm):
        tr, op, m = compiled(tr, frozen, op, batches[mark + s],
                             jnp.int32(mark + 1 + s))
    if warm:
        float(m["loss"])
    t0 = time.perf_counter()
    base = mark + warm
    for s in range(steps):
        tr, op, m = compiled(tr, frozen, op, batches[base + s],
                             jnp.int32(base + 1 + s))
    float(m["loss"])  # host sync closes the timed window
    dt = time.perf_counter() - t0
    return {"dt": dt, "loss": loss, "peak_bytes": peak,
            "loss_tokens_seen": mark * tokens_per_step}


EVAL_SEED = 12_345


def synth_batch(vocab, B, S, seed=0):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, vocab, (B, S)), jnp.int32)
    return {"input_ids": ids, "attention_mask": jnp.ones_like(ids),
            "labels": ids}


def synth_stream(vocab, B, S, n_batches, seed=0):
    """n_batches distinct step batches sliced from ONE seeded token
    stream. numpy's per-element generation makes the stream prefix-stable
    across total sizes, so every row of a model trains on the same
    underlying tokens regardless of its batch shape — only the
    partitioning differs (as it would across real-data configs)."""
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, (n_batches, B, S))
    out = []
    for t in toks:
        ids = jnp.asarray(t, jnp.int32)
        out.append({"input_ids": ids, "attention_mask": jnp.ones_like(ids),
                    "labels": ids})
    return out


def row_batches(vocab, step_b, S, steps):
    """(train stream, eval batch) for one bench row: enough distinct
    batches to cover the loss mark + warmup + the timed window, plus the
    shared held-out eval batch (EVAL_SEED streams are prefix-stable too,
    so different-B rows eval on nested token sets)."""
    mark = _loss_mark(step_b * S)
    n = mark + max(0, WARMUP_STEPS - mark) + steps
    return (synth_stream(vocab, step_b, S, n),
            synth_batch(vocab, step_b, S, seed=EVAL_SEED))


def offload_setup(params, budget_bytes=0):
    """budget_bytes: int, or "streams_only" — the intermediate-budget point
    that spills exactly the streamable layer stacks (whose per-layer
    streaming overlaps compute) and keeps whole-fetch leaves (embedding
    table, norms, biases) HBM-resident, avoiding the serial embed transfer
    on the step's critical path (offload.streams_only_budget)."""
    if budget_bytes == "streams_only":
        from mobilefinetuner_tpu.parallel.offload import streams_only_budget
        budget_bytes = streams_only_budget(params)
    ocfg = OffloadConfig(enable=True, max_resident_bytes=budget_bytes,
                         offload_dtype="bfloat16")
    plan = plan_placement(params, ocfg)
    sh = replicated_sharding(make_mesh(1, 1, devices=jax.devices()[:1]))
    shardings = jax.tree.map(lambda _: sh, params)
    placed = apply_placement(params, plan, shardings, ocfg)
    return placed, (plan, shardings)


def bench_gpt2_lora(B, S, dtype, accum=1, offload=False, impl="auto",
                    steps=40, size="small", remat=False,
                    lora_impl="auto"):
    base = {"small": GPT2Config.gpt2_small, "medium": GPT2Config.gpt2_medium,
            "large": GPT2Config.gpt2_large, "xl": GPT2Config.gpt2_xl,
            "tiny": GPT2Config.tiny}[size]()
    # long-context rows past GPT-2's native 1024 positions: the bench
    # trains randomly-initialized weights, so extending the learned
    # position table is shape plumbing, not a semantics change
    if S > base.n_positions:
        base = dataclasses.replace(base, n_positions=S)
    config = dataclasses.replace(base, attention_impl=impl)
    params = gpt2.init_params(config, jax.random.PRNGKey(0))
    spec = LoRASpec(rank=8, alpha=16.0)
    lora = init_lora_gpt2(config, spec, jax.random.PRNGKey(1))
    mask = trainable_mask(lora)
    tc = TrainConfig(total_steps=1000, lr=2e-4, schedule="constant",
                     warmup_ratio=0.0, grad_accum_steps=accum)
    off = None
    if offload:
        params, off = offload_setup(params)

    def loss_fn(lora_t, p, mb):
        logits = gpt2.forward(config, p, mb["input_ids"],
                              attention_mask=mb["attention_mask"],
                              lora=lora_t, compute_dtype=dtype,
                              offload=off, remat=remat,
                              lora_impl=lora_impl)
        return lm_cross_entropy_sum(logits, mb["labels"])

    step_fn = make_train_step(loss_fn, tc, mask=mask, donate=True)
    opt = init_optimizer(lora, tc, mask)
    batches, eval_batch = row_batches(config.vocab_size, B * accum, S,
                                      steps)
    r = measure(step_fn, lora, params, opt, batches, eval_batch, steps)
    r["lora_impl"] = lora_impl
    n_frozen = gpt2.param_count(params)
    n_active = sum(x.size for x in jax.tree.leaves(lora))
    r["flops"] = transformer_flops(n_active, n_frozen, B * accum, S,
                                   config.n_layer, config.n_head,
                                   config.head_dim, full_ft=False)
    n_block, n_head = matmul_param_counts(params, "wte")
    r["flops_exec"] = executed_flops(
        n_block, n_head, n_active, B * accum, S, config.n_layer,
        config.n_head, config.head_dim, full_ft=False,
        remat_blocks=remat or offload, remat_head=False,
        attn_factor=_attn_factor(S, config.head_dim, impl))
    r["tokens"] = B * accum * S
    return r


def bench_gpt2_full(B, S, dtype, steps=40):
    config = GPT2Config.gpt2_small()
    params = gpt2.init_params(config, jax.random.PRNGKey(0))
    tc = TrainConfig(total_steps=1000, lr=2e-5, schedule="constant",
                     warmup_ratio=0.0, grad_accum_steps=1)

    def loss_fn(p, _unused, mb):
        logits = gpt2.forward(config, p, mb["input_ids"],
                              attention_mask=mb["attention_mask"],
                              compute_dtype=dtype)
        return lm_cross_entropy_sum(logits, mb["labels"])

    step_fn = make_train_step(loss_fn, tc, mask=None, donate=True)
    opt = init_optimizer(params, tc, None)
    batches, eval_batch = row_batches(config.vocab_size, B, S, steps)
    r = measure(step_fn, params, {}, opt, batches, eval_batch, steps)
    n = gpt2.param_count(params)
    r["flops"] = transformer_flops(n, 0, B, S, config.n_layer,
                                   config.n_head, config.head_dim,
                                   full_ft=True)
    n_block, n_head = matmul_param_counts(params, "wte")
    r["flops_exec"] = executed_flops(
        n_block, n_head, 0, B, S, config.n_layer, config.n_head,
        config.head_dim, full_ft=True, remat_blocks=False,
        remat_head=False)
    r["tokens"] = B * S
    return r


def bench_gemma_lora(B, S, dtype, accum=1, offload=False, steps=20,
                     loss_chunks=4, size="270m", offload_budget=0,
                     remat=False, impl="auto", lora_impl="auto"):
    config = (Gemma3TextConfig.gemma3_1b() if size == "1b"
              else Gemma3TextConfig.gemma3_270m())
    config = dataclasses.replace(config, attention_impl=impl)
    params = gemma3.init_params(config, jax.random.PRNGKey(0))
    spec = LoRASpec(rank=8, alpha=32.0, targets="full")
    lora = init_lora_gemma3(config, spec, jax.random.PRNGKey(1))
    mask = trainable_mask(lora)
    tc = TrainConfig(total_steps=1000, lr=2e-4, schedule="constant",
                     warmup_ratio=0.0, grad_accum_steps=accum)
    off = None
    if offload:
        params, off = offload_setup(params, offload_budget)

    def loss_fn(lora_t, p, mb):
        p2, stream = resolve_offload(p, off)
        hidden = gemma3.hidden_states(
            config, p2, mb["input_ids"],
            attention_mask=mb["attention_mask"], lora=lora_t,
            compute_dtype=dtype, block_stream=stream, remat=remat,
            lora_impl=lora_impl)
        return chunked_lm_cross_entropy_sum(hidden, p2["embed"],
                                            mb["labels"],
                                            num_chunks=loss_chunks,
                                            lora_impl=lora_impl)

    step_fn = make_train_step(loss_fn, tc, mask=mask, donate=True)
    opt = init_optimizer(lora, tc, mask)
    batches, eval_batch = row_batches(config.vocab_size, B * accum, S,
                                      steps)
    r = measure(step_fn, lora, params, opt, batches, eval_batch, steps)
    r["lora_impl"] = lora_impl
    n_frozen = sum(x.size for x in jax.tree.leaves(params))
    n_active = sum(x.size for x in jax.tree.leaves(lora))
    r["flops"] = transformer_flops(
        n_active, n_frozen, B * accum, S, config.num_hidden_layers,
        config.num_attention_heads, config.head_dim, full_ft=False)
    n_block, n_head = matmul_param_counts(params, "embed")
    r["flops_exec"] = executed_flops(
        n_block, n_head, n_active, B * accum, S,
        config.num_hidden_layers, config.num_attention_heads,
        config.head_dim, full_ft=False,
        remat_blocks=remat or offload,   # streaming forces body remat
        remat_head=True,                 # chunked CE is checkpointed
        attn_factor=_attn_factor(S, config.head_dim, impl))
    r["tokens"] = B * accum * S
    return r


def bench_multitenant(dtype, steps, k=8, model="gpt2", B_per=2, S=128,
                      size="small", ref_step_ms=None):
    """Multi-tenant LoRA rows (round 18, DESIGN.md §23): k independent
    adapter jobs through ONE fused train step — stacked [k, r, d] bank,
    ids-routed `_multi_lora` forward, per-slot Adam/LR/clip
    (train/trainer.make_multi_train_step). Each tenant contributes B_per
    rows per step, so the k sweep holds PER-TENANT work constant and
    step_time-vs-k is the fusion claim (LoRAFusion: the memory-bound
    LoRA step has compute headroom for k jobs — near-flat step time).
    Aggregate tokens/s/chip counts every tenant's rows. ref_step_ms:
    the family's k=1 step time, for the step_time_vs_k1 column."""
    from mobilefinetuner_tpu.lora.lora import stack_adapters
    from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_rows
    from mobilefinetuner_tpu.optim.adam import init_multi_state
    from mobilefinetuner_tpu.train.trainer import make_multi_train_step
    if model == "gemma":
        config = Gemma3TextConfig.gemma3_270m() if size != "tiny" else \
            Gemma3TextConfig.tiny(vocab_size=211)
        params = gemma3.init_params(config, jax.random.PRNGKey(0))
        fwd = gemma3.forward
        spec = LoRASpec(rank=8, alpha=32.0, targets="full", init="peft")
        init_fn, n_layer, n_head, head_dim = (
            init_lora_gemma3, config.num_hidden_layers,
            config.num_attention_heads, config.head_dim)
    else:
        base = {"small": GPT2Config.gpt2_small,
                "tiny": GPT2Config.tiny}[size]()
        config = base
        params = gpt2.init_params(config, jax.random.PRNGKey(0))
        fwd = gpt2.forward
        spec = LoRASpec(rank=8, alpha=16.0)
        init_fn, n_layer, n_head, head_dim = (
            init_lora_gpt2, config.n_layer, config.n_head,
            config.head_dim)
    bank = stack_adapters([init_fn(config, spec, jax.random.PRNGKey(i))
                           for i in range(k)])
    mask = trainable_mask(bank)
    tc = TrainConfig(total_steps=1000, lr=2e-4, schedule="constant",
                     warmup_ratio=0.0)

    def loss_rows(tr, p, mb):
        from mobilefinetuner_tpu.lora.lora import assign_adapters
        routed = assign_adapters(tr, mb["adapter_ids"])
        logits = fwd(config, p, mb["input_ids"],
                     attention_mask=mb["attention_mask"], lora=routed,
                     compute_dtype=dtype)
        return lm_cross_entropy_rows(logits, mb["labels"])

    step_fn = make_multi_train_step(loss_rows, tc, k, mask=mask)
    opt = init_multi_state(bank, tc.adam(), k, mask)
    sched = {"step": jnp.zeros(k, jnp.int32),
             "total": jnp.full(k, 1000.0, jnp.float32),
             "lr": jnp.full(k, 2e-4, jnp.float32),
             "warmup_ratio": jnp.zeros(k, jnp.float32),
             "active": jnp.ones(k, bool)}
    ids = jnp.asarray(np.repeat(np.arange(k, dtype=np.int32), B_per))
    # the shared loss-mark/eval-probe protocol (measure()): train to
    # LOSS_MARK_TOKENS on the seeded stream, read held-out loss on the
    # shared EVAL_SEED batch — the loss column stays comparable across
    # rows (and across `steps` settings), like every other row
    tokens_per_step = k * B_per * S
    mark = _loss_mark(tokens_per_step)
    warm = max(0, WARMUP_STEPS - mark)
    batches = synth_stream(config.vocab_size, k * B_per, S,
                           mark + warm + steps)
    eval_batch = synth_batch(config.vocab_size, k * B_per, S,
                             seed=EVAL_SEED)
    for b in batches + [eval_batch]:
        b["adapter_ids"] = ids
    from mobilefinetuner_tpu.core.xla_stats import compiled_peak_bytes
    compiled = step_fn.lower(bank, params, opt, batches[0],
                             sched).compile()
    peak = compiled_peak_bytes(compiled)
    tr, op = bank, opt

    def advance(tr, op, batch, sched):
        tr, op, m = compiled(tr, params, op, batch, sched)
        return tr, op, m, dict(sched, step=sched["step"] + 1)

    for s in range(mark):
        tr, op, m, sched = advance(tr, op, batches[s], sched)
    # held-out probe: the step's loss metric reads the CURRENT weights
    # pre-update (its outputs must become the live state — donation);
    # aggregate = token-weighted mean over the k slots
    tr, op, m, sched = advance(tr, op, eval_batch, sched)
    l_k = np.asarray(m["loss"], np.float64)
    w_k = np.asarray(m["tokens"], np.float64)
    loss = float((l_k * w_k).sum() / max(w_k.sum(), 1.0))
    for s in range(warm):
        tr, op, m, sched = advance(tr, op, batches[mark + s], sched)
    if warm:
        float(np.asarray(m["loss"])[0])
    t0 = time.perf_counter()
    for s in range(steps):
        tr, op, m, sched = advance(tr, op, batches[mark + warm + s],
                                   sched)
    np.asarray(m["loss"])  # host sync closes the timed window
    dt = time.perf_counter() - t0
    n_frozen = sum(x.size for x in jax.tree.leaves(params))
    # MFU numerator: each token routes through exactly ONE adapter, so
    # the active-param term is one adapter's factors, not the k-slot
    # bank (charging the whole bank would inflate MFU with k)
    n_active = sum(int(x.size) for x in jax.tree.leaves(bank)) // k
    return {"dt": dt, "loss": loss, "peak_bytes": peak,
            "k": k, "tokens": tokens_per_step,
            "flops": transformer_flops(n_active, n_frozen, k * B_per, S,
                                       n_layer, n_head, head_dim,
                                       full_ft=False),
            "ref_step_ms": ref_step_ms,
            "loss_tokens_seen": mark * tokens_per_step}


def mt_finish(name, r, dtype, steps) -> dict:
    """Row schema for the multitenant sweep: the base finish() columns
    plus k, step_time_ms, and step_time_vs_k1 — the LoRAFusion target
    is step_time_vs_k1 staying near 1.0 as k grows (near-flat step time
    while aggregate tokens/s scales with k)."""
    row = finish(name, r, dtype, steps)
    step_ms = r["dt"] / steps * 1000.0
    row["k"] = r["k"]
    row["step_time_ms"] = round(step_ms, 2)
    row["step_time_vs_k1"] = (round(step_ms / r["ref_step_ms"], 3)
                              if r.get("ref_step_ms")
                              else (1.0 if r["k"] == 1 else None))
    return row


def _pipeline_corpus(path: str, n_lines: int = 8000, seed: int = 0):
    """Synthetic WikiText-shaped corpus for the input-pipeline rows."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(n_lines):
            n = int(rng.integers(8, 40))
            f.write(" ".join(f"w{rng.integers(0, 5000)}"
                             for _ in range(n)) + "\n")


def bench_input_pipeline(dtype, steps, model="gpt2", prefetch=2, B=8,
                         S=128, accum=2, size=None, warmup=2,
                         window_tokens=20_000):
    """Input-pipeline rows: the REAL host data path — streaming-mode
    WikiText2Dataset (bounded window, per-epoch shuffle, on-demand
    re-tokenization), grad-accum step-batch assembly, and device
    placement — feeding the standard LoRA train step, with the async
    prefetcher on (depth `prefetch`, lookahead 1) or off (prefetch=0,
    the synchronous reference path). Reports tokens/s plus the step
    loop's measured host-wait, so the BENCH artifact carries the
    host/device breakdown the overlap claims rest on. The other rows
    feed pre-built device arrays and so never see host cost; these two
    columns are where input-pipeline regressions become visible."""
    import itertools
    import tempfile
    import zlib

    from mobilefinetuner_tpu.cli.common import micro_batches
    from mobilefinetuner_tpu.core.xla_stats import compiled_peak_bytes
    from mobilefinetuner_tpu.data.prefetch import Prefetcher
    from mobilefinetuner_tpu.data.wikitext2 import (WT2Config,
                                                    WikiText2Dataset)
    from mobilefinetuner_tpu.parallel.mesh import make_batch_placer

    if model == "gemma":
        config = (Gemma3TextConfig.tiny() if size == "tiny"
                  else Gemma3TextConfig.gemma3_270m())
        params = gemma3.init_params(config, jax.random.PRNGKey(0))
        spec = LoRASpec(rank=8, alpha=32.0, targets="full")
        lora = init_lora_gemma3(config, spec, jax.random.PRNGKey(1))

        def loss_fn(lora_t, p, mb):
            hidden = gemma3.hidden_states(
                config, p, mb["input_ids"],
                attention_mask=mb["attention_mask"], lora=lora_t,
                compute_dtype=dtype)
            return chunked_lm_cross_entropy_sum(hidden, p["embed"],
                                                mb["labels"], num_chunks=4)
    else:
        config = (GPT2Config.tiny() if size == "tiny"
                  else GPT2Config.gpt2_small())
        params = gpt2.init_params(config, jax.random.PRNGKey(0))
        spec = LoRASpec(rank=8, alpha=16.0)
        lora = init_lora_gpt2(config, spec, jax.random.PRNGKey(1))

        def loss_fn(lora_t, p, mb):
            logits = gpt2.forward(config, p, mb["input_ids"],
                                  attention_mask=mb["attention_mask"],
                                  lora=lora_t, compute_dtype=dtype)
            return lm_cross_entropy_sum(logits, mb["labels"])

    mask = trainable_mask(lora)
    tc = TrainConfig(total_steps=1000, lr=2e-4, schedule="constant",
                     warmup_ratio=0.0, grad_accum_steps=accum)
    step_fn = make_train_step(loss_fn, tc, mask=mask, donate=True)
    opt = init_optimizer(lora, tc, mask)

    # deterministic word->id hash (crc32, NOT python hash(): unsalted, so
    # prefetch-on and prefetch-off rows train on the identical stream and
    # their loss columns stay comparable across runs too)
    V = config.vocab_size
    encode = lambda s: [zlib.crc32(w.encode()) % (V - 1)
                        for w in s.split()]
    with tempfile.TemporaryDirectory() as d:
        corpus = f"{d}/wiki.train.tokens"
        _pipeline_corpus(corpus)
        cfg = WT2Config(seq_len=S, batch_size=B, seed=0, streaming=True,
                        window_tokens=window_tokens)
        ds = WikiText2Dataset(corpus, "train", cfg, encode, eos_id=V - 1)
        place = make_batch_placer(
            make_mesh(1, 1, devices=jax.devices()[:1]))
        gen = (b for _, b in micro_batches(ds, accum))
        # budget: the compile batch + max(warmup-1, 0) + the timed steps
        stream = Prefetcher(
            itertools.islice(gen, max(warmup, 1) + steps),
            depth=prefetch, place_fn=place, lookahead=1)
        try:
            first = next(stream)
            compiled = step_fn.lower(lora, params, opt, first,
                                     jnp.int32(0)).compile()
            peak = compiled_peak_bytes(compiled)
            tr, op, m = compiled(lora, params, opt, first, jnp.int32(0))
            for s in range(1, warmup):
                tr, op, m = compiled(tr, params, op, next(stream),
                                     jnp.int32(s))
            float(m["loss"])  # drain: the timed window starts clean
            wait_ms = 0.0
            t0 = time.perf_counter()
            for s in range(steps):
                tw = time.perf_counter()
                batch = next(stream)
                wait_ms += (time.perf_counter() - tw) * 1000
                tr, op, m = compiled(tr, params, op, batch,
                                     jnp.int32(warmup + s))
            loss = float(m["loss"])  # host sync closes the window
            dt = time.perf_counter() - t0
        finally:
            stream.close()
    return {"dt": dt, "loss": loss, "peak_bytes": peak,
            "tokens": B * accum * S, "host_wait_ms": wait_ms,
            "flops": 0}


def cap_frac_of(peak_mb):
    """peak_hbm_mb / per-device HBM capacity — how close to the ceiling
    this row runs, the number the round-16 admission layer budgets
    against (core/memory_guard.device_capacity_mb: memory_stats
    bytes_limit, else the device-kind table). None when either side is
    unknown (e.g. CPU smoke runs)."""
    from mobilefinetuner_tpu.core.memory_guard import device_capacity_mb
    cap, _ = device_capacity_mb()
    if not cap or not peak_mb:
        return None
    return round(peak_mb / cap, 4)


def pipe_finish(name, r, dtype, steps) -> dict:
    """Input-pipeline row shape: throughput + host/device breakdown."""
    toks_per_sec = r["tokens"] * steps / r["dt"]
    peak_mb = round(r["peak_bytes"] / 2 ** 20, 1)
    return {
        "config": name,
        "tokens_per_sec_per_chip": round(toks_per_sec, 1),
        "vs_baseline": round(toks_per_sec / BASELINE_TOKENS_PER_SEC, 2),
        # fraction of the timed window the step loop spent blocked on the
        # input pipeline (queue wait + lookahead placement); the sync-vs-
        # prefetch row pair is the overlap measurement
        "host_wait_frac": round(r["host_wait_ms"] / (r["dt"] * 1000), 4),
        "host_wait_ms_per_step": round(r["host_wait_ms"] / steps, 2),
        "mfu": None,
        "peak_hbm_mb": peak_mb,
        "cap_frac": cap_frac_of(peak_mb),
        "loss": round(r["loss"], 4),
    }


_GEMMA1B_NP = None


def bench_gemma_full_offload(B, S, dtype, steps=10, loss_chunks=8,
                             tier16: bool = False):
    """Gemma-1B FULL fine-tune on one chip: f32 master weights + Adam m/v
    live in pinned host RAM and stream through the scanned update
    (optim/opt_offload.py); the device holds only the bf16 compute copy.
    Resident full FT would need ~16 GB of optimizer state alone — the
    reference cannot do this at any scale.

    tier16 stores the streamed master (stochastic-rounded) and m/v
    (sqrt-encoded v) in bf16 on the host — 12 GB/step of DMA instead of
    24 (OptOffloadSpec; the analog of the reference's fp16 slow tier,
    parameter_sharder.cpp:215-232)."""
    from mobilefinetuner_tpu.optim.opt_offload import (
        OptOffloadSpec, init_opt_offload, make_offload_train_step,
        plan_opt_offload)
    spec = OptOffloadSpec(state_dtype="bfloat16", master_dtype="bfloat16") \
        if tier16 else OptOffloadSpec()
    config = Gemma3TextConfig.gemma3_1b()
    # host-numpy param cache shared by the f32 and tier16 rows: the 1B
    # init + device->host staging costs minutes on this platform and is
    # identical for both specs (init_opt_offload stages from host numpy
    # either way)
    global _GEMMA1B_NP
    if _GEMMA1B_NP is None:
        _GEMMA1B_NP = jax.tree.map(
            lambda x: np.asarray(jax.device_get(x)),
            gemma3.init_params(config, jax.random.PRNGKey(0)))
    params = _GEMMA1B_NP
    n = sum(x.size for x in jax.tree.leaves(params))
    plan = plan_opt_offload(params, spec)
    compute, opt = init_opt_offload(params, plan, compute_dtype=dtype,
                                    spec=spec)
    del params  # the module-level np cache keeps the host copy
    tc = TrainConfig(total_steps=1000, lr=2e-5, schedule="constant",
                     warmup_ratio=0.0)

    def loss_fn(p, _unused, mb):
        hidden = gemma3.hidden_states(
            config, p, mb["input_ids"],
            attention_mask=mb["attention_mask"], compute_dtype=dtype,
            remat=True)
        return chunked_lm_cross_entropy_sum(hidden, p["embed"],
                                            mb["labels"],
                                            num_chunks=loss_chunks)

    step_fn = make_offload_train_step(loss_fn, tc, plan,
                                      compute_dtype=dtype, donate=True,
                                      spec=spec)
    batches, eval_batch = row_batches(config.vocab_size, B, S, steps)
    r = measure(step_fn, compute, None, opt, batches, eval_batch, steps)
    r["flops"] = transformer_flops(
        n, 0, B, S, config.num_hidden_layers,
        config.num_attention_heads, config.head_dim, full_ft=True)
    n_block, n_head = matmul_param_counts(compute, "embed")
    r["flops_exec"] = executed_flops(
        n_block, n_head, 0, B, S, config.num_hidden_layers,
        config.num_attention_heads, config.head_dim, full_ft=True,
        remat_blocks=True, remat_head=True,
        attn_factor=_attn_factor(S, config.head_dim))
    r["tokens"] = B * S
    return r


def bench_generate(B=8, P=128, N=64, dtype=jnp.bfloat16, pipeline=8,
                   model="gpt2", adapters=0):
    """Generate throughput (models/generate.py): B prompts of length P, N
    greedy tokens each; tokens/sec counts only the B*N GENERATED tokens.

    Two numbers: `latency_ms` is one synchronous call (prefill + N decode
    steps + the host round trip — what an interactive user sees), and
    the primary tokens/sec is
    SUSTAINED serving throughput: `pipeline` calls dispatched
    back-to-back with one sync at the end, so the dispatch latency
    overlaps device work the way a serving loop overlaps requests.

    The B=8 marginal decode cost is byte-floor-bound (weights+cache reads
    per token-step, DESIGN.md §10a), so batch is the serving-throughput
    lever — hence the B=32 rows alongside the historical B=8 row.

    adapters=k serves k distinct stacked LoRA adapters routed round-robin
    over the batch rows through the dynamic per-layer LoRA path
    (lora.stack_adapters + assign_adapters; correctness oracle:
    tests/test_multi_adapter.py row-exact equality)."""
    from mobilefinetuner_tpu.models.generate import (SampleConfig,
                                                     gemma3_generate,
                                                     gpt2_generate)
    if model == "gemma":
        config = Gemma3TextConfig.gemma3_270m()
        params = gemma3.init_params(config, jax.random.PRNGKey(0))
        gen = gemma3_generate
    else:
        config = GPT2Config.gpt2_small()
        params = gpt2.init_params(config, jax.random.PRNGKey(0))
        gen = gpt2_generate
    lora = None
    if adapters:
        from mobilefinetuner_tpu.lora.lora import (LoRASpec,
                                                   assign_adapters,
                                                   init_lora_gemma3,
                                                   init_lora_gpt2,
                                                   stack_adapters)
        init_fn = init_lora_gemma3 if model == "gemma" else init_lora_gpt2
        spec = LoRASpec(rank=8, alpha=16.0)
        adv = [init_fn(config, spec, jax.random.PRNGKey(i))
               for i in range(adapters)]
        # randomize B so the adapter deltas are real work, not zeros
        adv = [jax.tree.map(
            lambda l, k=i: l if l.ndim == 0 else
            0.02 * jax.random.normal(jax.random.PRNGKey(k + 77), l.shape),
            a) for i, a in enumerate(adv)]
        lora = assign_adapters(stack_adapters(adv),
                               [b % adapters for b in range(B)])
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, config.vocab_size, (B, P)), jnp.int32)
    mask = jnp.ones_like(ids)
    cfg = SampleConfig(max_new_tokens=N, greedy=True, eos_id=None)
    # params AND lora as jit ARGUMENTS (a closure would bake the weights
    # and adapter stacks into the HLO as constants — an oversized
    # program, and a serving loop swaps adapters without recompiling)
    fn = jax.jit(lambda p, lo, i, m: gen(config, p, i, m, cfg,
                                         compute_dtype=dtype, lora=lo))
    out = fn(params, lora, ids, mask)
    np.asarray(out)  # compile + run
    t0 = time.perf_counter()
    out = fn(params, lora, ids, mask)
    np.asarray(out)  # host sync
    latency = time.perf_counter() - t0
    t0 = time.perf_counter()
    outs = [fn(params, lora, ids, mask) for _ in range(pipeline)]
    np.asarray(outs[-1])
    dt = time.perf_counter() - t0
    return {"dt": dt, "tokens": pipeline * B * N, "loss": 0.0,
            "peak_bytes": 0, "flops": 0,
            "latency_ms": round(latency * 1000, 1)}


def finish(name, r, dtype, steps) -> dict:
    toks_per_sec = r["tokens"] * steps / r["dt"]
    peak_mb = round(r["peak_bytes"] / 2 ** 20, 1)
    return {
        "config": name,
        "tokens_per_sec_per_chip": round(toks_per_sec, 1),
        "vs_baseline": round(toks_per_sec / BASELINE_TOKENS_PER_SEC, 2),
        "mfu": round(r["flops"] * steps / r["dt"] / peak_flops(dtype), 4),
        # mfu from XLA's executed-FLOP count (remat recompute included,
        # embedding gathers excluded); mfu above is the standard 6ND-style
        # formula — both published so neither misleads alone
        "mfu_executed": (round(r["flops_exec"] * steps / r["dt"]
                               / peak_flops(dtype), 4)
                         if r.get("flops_exec") else None),
        "peak_hbm_mb": peak_mb,
        # how close to the per-device HBM ceiling the row ran (the
        # round-16 admission layer's cap source); None off-accelerator
        "cap_frac": cap_frac_of(peak_mb),
        # held-out loss after >= LOSS_MARK_TOKENS training tokens on the
        # shared stream — comparable across rows of the same model
        "loss": round(r["loss"], 4),
        "loss_tokens_seen": r.get("loss_tokens_seen"),
        # present on the LoRA rows: which models/lora_apply.py path the
        # row ran (the lorafused-vs-loranaive pairs are the r12 delta)
        **({"lora_impl": r["lora_impl"]} if "lora_impl" in r else {}),
    }


def main():
    from mobilefinetuner_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    on_tpu = jax.devices()[0].platform != "cpu"
    # run registry (core/run_registry.py, DESIGN.md §28): bench.py takes
    # no flags, so registration rides $MFT_RUN_REGISTRY alone. A kill
    # mid-suite leaves the start record; the next registry open settles
    # it to "interrupted" (completed rows survive via the per-row flush).
    from mobilefinetuner_tpu.core.run_registry import registry_from
    _reg = registry_from("")
    run_rec = _reg.begin(
        "bench", "bench", config={"on_tpu": on_tpu},
        platform=jax.devices()[0].platform,
        artifacts=["BENCH_SUITE.json"]) if _reg else None
    steps = 40 if on_tpu else 2
    gsteps = 20 if on_tpu else 2
    bf16, f32 = "bfloat16", "float32"
    # batch sizes from the v5e sweep (B=64 beats 32 by 12% for GPT-2s
    # LoRA at 10.9 GB peak; B=128 OOMs on the [B,S,V] CE temps; Gemma
    # B=16/chunks=4 beats 8/8 by 30% at 8.4 GB)
    B = 64 if on_tpu else 2
    FB = 32 if on_tpu else 2  # full-FT: Adam m/v + grads double the tree
    S = 128 if on_tpu else 64
    GB, GS = (16, 256) if on_tpu else (2, 64)

    suite = []

    def flush_suite():
        # incremental: a killed/timed-out run still leaves every
        # completed row on disk (the 1B full-offload rows alone take
        # ~30 min; losing 19 finished rows to a timeout is worse than
        # a partial artifact). temp+rename so a kill MID-flush can't
        # leave truncated JSON.
        import os
        with open("BENCH_SUITE.json.tmp", "w") as f:
            json.dump({"suite": suite,
                       "peak_flops_assumed": {"bfloat16": peak_flops("bfloat16"),
                                              "float32": peak_flops("float32")},
                       "baseline_tokens_per_sec": BASELINE_TOKENS_PER_SEC},
                      f, indent=1)
        os.replace("BENCH_SUITE.json.tmp", "BENCH_SUITE.json")

    def run(name, fn, dtype, n, finisher=finish, **kw):
        try:
            r = fn(dtype=jnp.bfloat16 if dtype == bf16 else jnp.float32,
                   steps=n, **kw)
            row = finisher(name, r, dtype, n)
        except Exception as e:  # record, don't kill the suite
            row = {"config": name, "error": f"{type(e).__name__}: {e}"}
        suite.append(row)
        print(json.dumps(row), file=sys.stderr)
        flush_suite()
        return row

    headline = run(f"gpt2s_lora_bf16_B{B}_S128", bench_gpt2_lora, bf16,
                   steps, B=B, S=S)
    # driver contract: exactly one JSON line on stdout (headline config).
    # Printed IMMEDIATELY after the headline row — the full suite now
    # runs >1 h on the chip (two 1B full-FT offload configs alone cost
    # ~30 min of init+compile), and a driver-side timeout killing the
    # tail must not lose the headline metric (completed rows survive in
    # BENCH_SUITE.json via the per-row flush either way). A failed
    # headline reports value 0 and exits 1 at the END — the remaining
    # rows still run and land in the artifact.
    if "error" in headline:
        print(json.dumps({
            "metric": "gpt2s_lora_train_tokens_per_sec_per_chip",
            "value": 0.0, "unit": "tokens/sec/chip", "vs_baseline": 0.0,
            "error": headline["error"]}), flush=True)
    else:
        print(json.dumps({
            "metric": "gpt2s_lora_train_tokens_per_sec_per_chip",
            "value": headline["tokens_per_sec_per_chip"],
            "unit": "tokens/sec/chip",
            "vs_baseline": headline["vs_baseline"],
            "mfu": headline["mfu"],
            "peak_hbm_mb": headline["peak_hbm_mb"],
            "cap_frac": headline.get("cap_frac"),
        }), flush=True)
    if on_tpu:  # the full suite is a TPU artifact; off-TPU is a smoke
        run(f"gpt2s_lora_f32_B{B}_S128", bench_gpt2_lora, f32, steps,
            B=B, S=S)
        run("gpt2s_lora_bf16_accum4", bench_gpt2_lora, bf16, steps,
            B=max(B // 4, 1), S=S, accum=4)
        run("gpt2s_lora_bf16_offload_stream", bench_gpt2_lora, bf16,
            steps, B=B, S=S, offload=True)
        run(f"gpt2s_full_bf16_B{FB}_S128", bench_gpt2_full, bf16, steps,
            B=FB, S=S)
        run(f"gpt2s_full_f32_B{FB}_S128", bench_gpt2_full, f32, steps,
            B=FB, S=S)
        run(f"gemma270m_lora_bf16_B{GB}_S256", bench_gemma_lora, bf16,
            gsteps, B=GB, S=GS)
        run("gemma270m_lora_bf16_offload_stream", bench_gemma_lora, bf16,
            gsteps, B=GB, S=GS, offload=True)
        # intermediate-budget point on the overhead/residency curve: spill
        # only the streamable layer stacks, keep the 262k-vocab embedding
        # HBM-resident (its whole-tensor fetch is a serial transfer on the
        # critical path; the per-layer streams overlap compute). B=32 so
        # each fetched byte feeds 2x the tokens — with the B=32 resident
        # row next to it as the apples-to-apples comparison.
        run("gemma270m_lora_bf16_offload_embed_resident_B32",
            bench_gemma_lora, bf16, gsteps, B=32, S=GS, offload=True,
            offload_budget="streams_only")
        run("gemma270m_lora_bf16_resident_B32", bench_gemma_lora, bf16,
            gsteps, B=32, S=GS)
        # the reference's benchmark table spans GPT-2 S/M and Gemma
        # 270M/1B (README.md:406-411); cover the larger two as well
        run("gpt2m_lora_bf16_B32_S128", bench_gpt2_lora, bf16, steps,
            B=32, S=S, size="medium")
        # the README claims GPT-2 small/medium/large/xl: measure all four
        run("gpt2l_lora_bf16_B16_S128", bench_gpt2_lora, bf16,
            max(steps // 2, 2), B=16, S=S, size="large")
        run("gpt2xl_lora_bf16_B8_S128", bench_gpt2_lora, bf16,
            max(steps // 4, 2), B=8, S=S, size="xl", remat=True)
        run("gemma1b_lora_bf16_B8_S256", bench_gemma_lora, bf16,
            max(gsteps // 2, 2), B=8, S=GS, loss_chunks=8, size="1b")
        run("gemma1b_lora_bf16_offload_stream", bench_gemma_lora, bf16,
            max(gsteps // 2, 2), B=8, S=GS, offload=True, loss_chunks=8,
            size="1b")  # same B as the resident row: comparable
        # what the freed HBM is FOR: the resident model caps out at B=8
        # (14.5 GB peak); streaming the blocks frees enough HBM for B=32,
        # amortizing the (DMA-bound) layer fetches over 4x the tokens
        run("gemma1b_lora_bf16_offload_B32", bench_gemma_lora, bf16,
            max(gsteps // 2, 2), B=32, S=GS, offload=True, loss_chunks=8,
            size="1b", offload_budget="streams_only")
        # the offload FRONTIER between the 1.2 GB floor and the 3.9 GB
        # streams-only point (r3 verdict #5): at minimum memory the step
        # is bound by the serial 604 MB embed fetch (~270 ms at the
        # ~2 GiB/s single-stream host link), so batch is the lever —
        # B=16 at budget 0 clears 10k tok/s in 1.7 GB
        run("gemma1b_lora_bf16_offload_B16", bench_gemma_lora, bf16,
            max(gsteps // 2, 2), B=16, S=GS, offload=True, loss_chunks=8,
            size="1b")
        run("gemma1b_lora_bf16_offload_embed_resident_B16",
            bench_gemma_lora, bf16, max(gsteps // 2, 2), B=16, S=GS,
            offload=True, loss_chunks=8, size="1b",
            offload_budget="streams_only")
        # rematerialization as a THROUGHPUT lever at the 1B scale: the
        # recompute costs less than the batch-size constraint it lifts
        # (B=8 no-remat is activation-bound at 14.5 GB; remat B=24 runs
        # 12% faster at half the memory — v5e sweep: B=16 17.2k,
        # B=24 18.0k, B=32 18.0k, so 24 is the knee)
        run("gemma1b_lora_bf16_remat_B24", bench_gemma_lora, bf16,
            max(gsteps // 2, 2), B=24, S=GS, loss_chunks=12, size="1b",
            remat=True)
        # FULL fine-tuning of the 1B model on one 16 GB chip: master +
        # Adam state stream from pinned host (~24 GB/step of DMA that XLA
        # overlaps with compute — measured B sweep: 8->1.1k, 24->2.8k,
        # 48->4.7k, 96->6.8k, 128->7.5k tok/s at 13.4 GB peak; the
        # optimizer stream is a fixed cost, so batch amortizes it)
        run("gemma1b_full_bf16_opt_offload_B96", bench_gemma_full_offload,
            bf16, max(gsteps // 2, 2), B=96, S=GS)
        # the 16-bit host tier halves the dominant optimizer DMA
        # (24 -> 12 GB/step): bf16 master (stochastic-rounded write-back)
        # + bf16 m + sqrt-encoded bf16 v, dequantized on-chip
        run("gemma1b_full_bf16_opt_offload16_B96",
            bench_gemma_full_offload, bf16, max(gsteps // 2, 2), B=96,
            S=GS, tier16=True)
        # the 1B host-numpy cache (~4 GB) has no further consumers —
        # release it before the flash/generate rows
        global _GEMMA1B_NP
        _GEMMA1B_NP = None
        # flash vs xla at the long-context shape ('auto' resolves flash)
        run("gpt2s_lora_bf16_S1024_flash", bench_gpt2_lora, bf16, steps,
            B=4, S=1024, impl="flash")
        run("gpt2s_lora_bf16_S1024_xla", bench_gpt2_lora, bf16, steps,
            B=4, S=1024, impl="xla")
        # the r4 crossover retune: flash wins from S=512 at D=64 (e2e
        # +20%; the dispatch-floor-limited microbench said parity —
        # resolve_impl docstring has the measurement story)
        run("gpt2s_lora_bf16_S512_flash", bench_gpt2_lora, bf16, steps,
            B=16, S=512, impl="flash")
        run("gpt2s_lora_bf16_S512_xla", bench_gpt2_lora, bf16, steps,
            B=16, S=512, impl="xla")
        # S=2048 long-context e2e (r6): the regime the memory-efficient
        # attention exists for. Pins DESIGN §6a's 2.7-2.8x claim (which
        # only had a microbench artifact behind it) with driver-captured
        # e2e rows, and exercises the merged one-pass backward kernel at
        # depth 4 k-blocks per row block. GPT-2s runs with the position
        # table extended to 2048 (randomly-init weights — shape plumbing
        # only); the Gemma pair is the FIRST e2e measurement of the
        # D=256 S>=2048 crossover resolve_impl asserts.
        run("gpt2s_lora_bf16_S2048_flash", bench_gpt2_lora, bf16, steps,
            B=2, S=2048, impl="flash")
        run("gpt2s_lora_bf16_S2048_xla", bench_gpt2_lora, bf16, steps,
            B=2, S=2048, impl="xla")
        run("gemma270m_lora_bf16_S2048_flash", bench_gemma_lora, bf16,
            gsteps, B=2, S=2048, impl="flash")
        run("gemma270m_lora_bf16_S2048_xla", bench_gemma_lora, bf16,
            gsteps, B=2, S=2048, impl="xla")
        # LoRA hot-path rows (r12, DESIGN.md §17): fused (shape-aware
        # contraction order + Pallas epilogue at eligible sites) vs the
        # naive oracle, both families, S=512/1024/2048 — the tokens/s
        # delta of never round-tripping the [N, d_out] adapter delta
        # through HBM. Parity is pinned by tests/test_lora.py; these
        # rows price it.
        for s_len, b_sz in ((512, 16), (1024, 4), (2048, 2)):
            for li in ("naive", "fused"):
                run(f"gpt2s_lora_bf16_S{s_len}_lora{li}",
                    bench_gpt2_lora, bf16, steps, B=b_sz, S=s_len,
                    lora_impl=li)
                run(f"gemma270m_lora_bf16_S{s_len}_lora{li}",
                    bench_gemma_lora, bf16, gsteps,
                    B=max(b_sz // 2, 2), S=s_len, lora_impl=li)
        # multi-tenant LoRA rows (r18, DESIGN.md §23): k adapter jobs
        # through ONE fused train step, per-tenant work held constant —
        # step_time_vs_k1 near 1.0 while aggregate tokens/s scales with
        # k is the LoRAFusion claim (the memory-bound LoRA step has
        # compute headroom for k jobs). k=32 GPT-2s at B_per=2 keeps
        # the peak under the fused-CE temps ceiling.
        mt_ref = {}
        for fam, mt_kw in (("gpt2s", dict(model="gpt2", B_per=2, S=S)),
                           ("gemma270m", dict(model="gemma", B_per=2,
                                              S=GS))):
            for kk in (1, 8, 32):
                row = run(f"{fam}_multitenant_k{kk}_bf16",
                          bench_multitenant, bf16, gsteps, k=kk,
                          finisher=mt_finish,
                          ref_step_ms=mt_ref.get(fam), **mt_kw)
                if kk == 1 and "step_time_ms" in row:
                    mt_ref[fam] = row["step_time_ms"]
        # input-pipeline rows (r7): every other row feeds pre-built
        # device arrays, so host-side batch production (streaming-window
        # tokenization + accum assembly + placement) never shows up in
        # them. These four run the REAL data path and measure the step
        # loop's host-wait with the async prefetcher off vs on — the
        # sync/prefetch pair per model is the overlap measurement, and
        # host_wait_frac is the bubble the prefetcher exists to close.
        run(f"gpt2s_input_pipeline_sync_B{B}_S128", bench_input_pipeline,
            bf16, steps, B=B, S=S, prefetch=0, finisher=pipe_finish)
        run(f"gpt2s_input_pipeline_prefetch2_B{B}_S128",
            bench_input_pipeline, bf16, steps, B=B, S=S, prefetch=2,
            finisher=pipe_finish)
        run(f"gemma270m_input_pipeline_sync_B{GB}_S256",
            bench_input_pipeline, bf16, gsteps, model="gemma", B=GB,
            S=GS, prefetch=0, finisher=pipe_finish)
        run(f"gemma270m_input_pipeline_prefetch2_B{GB}_S256",
            bench_input_pipeline, bf16, gsteps, model="gemma", B=GB,
            S=GS, prefetch=2, finisher=pipe_finish)
        # end-to-end generate throughput (prefill + sequential decode;
        # tokens/sec counts generated tokens only).
        # finish() is training-shaped, so pass run() a custom finisher.
        gen_finish = lambda name, r, dtype, n: {
            "config": name,
            "tokens_per_sec_per_chip": round(r["tokens"] / r["dt"], 1),
            "single_call_latency_ms": r["latency_ms"],
            "vs_baseline": None, "mfu": None, "peak_hbm_mb": None,
            "cap_frac": None, "loss": None}
        run("gpt2s_generate_e2e_B8_P128_N64",
            lambda dtype, steps: bench_generate(dtype=dtype), bf16, 1,
            finisher=gen_finish)
        # serving regime: the B=8 marginal decode cost is pinned at the
        # weights+cache byte floor (DESIGN.md §10a), so batch is the
        # throughput lever — B=32 amortizes the dominant weight stream
        # over 4x the rows
        run("gpt2s_generate_e2e_B32_P128_N64",
            lambda dtype, steps: bench_generate(B=32, dtype=dtype), bf16,
            1, finisher=gen_finish)
        run("gemma270m_generate_e2e_B8_P128_N64",
            lambda dtype, steps: bench_generate(model="gemma",
                                                dtype=dtype), bf16, 1,
            finisher=gen_finish)
        run("gemma270m_generate_e2e_B32_P128_N64",
            lambda dtype, steps: bench_generate(B=32, model="gemma",
                                                dtype=dtype), bf16, 1,
            finisher=gen_finish)
        # multi-adapter batched serving (4 adapters round-robin over the
        # rows, dynamic per-layer LoRA path): priced against the B=32
        # merged-weights row above (r4 verdict #6)
        run("gpt2s_generate_multi_adapter4_B32_P128_N64",
            lambda dtype, steps: bench_generate(B=32, adapters=4,
                                                dtype=dtype), bf16, 1,
            finisher=gen_finish)

    # (run() flushed after every row; the headline stdout line was
    # printed right after the headline row above)
    if run_rec is not None:
        # per-row errors are recorded IN the artifact; the suite itself
        # completed, so the registry record is "ok" either way
        run_rec.finalize("ok")
    return 1 if "error" in headline else 0


if __name__ == "__main__":
    sys.exit(main())
