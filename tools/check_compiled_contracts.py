"""Compiled-artifact contract checker (DESIGN.md §24): lower the
representative train / decode / multitenant programs on CPU and pin
machine-readable contracts about WHAT THE COMPILER PRODUCED —

  - retrace count: N same-shape calls must share ONE executable (the
    zero-retrace-after-warmup invariant, measured the same way the
    serve/multitenant engines' trace_counts observables measure it);
  - collective census: named all-gather/all-reduce/reduce-scatter/
    collective-permute/all-to-all counts per program — a GSPMD
    regression that materializes a V-sharded embed all-gather (the r06
    incident) moves a pinned number here instead of a pod bill;
  - donation: the number of input->output alias entries in the compiled
    module header (a donating step whose aliasing silently vanished
    doubles its peak HBM);
  - named-scope spans: the embed/attention/mlp/loss/optimizer phase
    scopes must survive into compiled HLO metadata (the telemetry
    layer's semantic trace contract).

Contracts live in tools/compiled_contracts.json. `--update` regenerates
the file from the current build (run it when an intentional change
moves a number, and review the diff like any other pin).

Usage:
  python tools/check_compiled_contracts.py                 # check all
  python tools/check_compiled_contracts.py --programs train_gpt2_lora
  python tools/check_compiled_contracts.py --update        # re-pin
  python tools/check_compiled_contracts.py --format json

Exit codes (bench_compare convention): 0 = contracts hold, 2 = contract
violated, 1 = usage/build error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_CONTRACTS = os.path.join(REPO, "tools", "compiled_contracts.json")

# the phase scopes the telemetry layer pins (DESIGN.md §13)
TRAIN_SCOPES = ("embed", "attention", "mlp", "loss", "optimizer")
# the paged decode step's and prefill chunk's scopes (models/generate.py):
# how a device trace tells the KV scatter from the paged read from the
# block's matmuls. `decode_weight_cast` is not pinned here: it holds an
# op only where a program is handed a tree of another dtype than it
# computes in; these programs take float32 and compute in float32, and
# the engine's own steps take the tree it cast at build (DESIGN.md §16)
DECODE_SCOPES = ("decode_kv_write", "decode_paged_attn",
                 "decode_proj_mlp", "decode_head")


# an AFMoE step's scopes (models/afmoe.py, ops/moe.py): the families'
# outer ones, and inside them what tells the router from the sort from
# the grouped product, and windowed attention from full
MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_shared",
              "moe_combine")
AFMOE_SCOPES = TRAIN_SCOPES + MOE_SCOPES + ("attn_window", "attn_full")
# the Ling hybrid's (models/ling_hybrid.py): inside `attention` what
# tells the delta rule's projections and conv from its gate, its chunked
# scan (forward and backward) and its output, and latent attention's
# projections from the attention call
LING_SCOPES = TRAIN_SCOPES + MOE_SCOPES + (
    "kda_proj_conv", "kda_gate", "kda_scan", "kda_out", "mla_proj",
    "attn_latent", "mla_out")
# Xing4.0's (models/xing4.py, ops/hyper_connections.py): the stream's
# coefficients and its two mixes around every sublayer, latent
# attention's three, and `mtp` around the multi-token-prediction module
# (its block's scopes nest under it, its loss chunks too)
XING4_SCOPES = TRAIN_SCOPES + MOE_SCOPES + (
    "mhc_coeff", "mhc_pre", "mhc_post", "mla_proj", "attn_latent",
    "mla_out", "mtp")
# Phi-4-mini-flash's (models/phi4flash.py): the selective scan's
# projections, conv and scan, the gated memory units, differential
# attention's projections and its two maps (no `attention` scope: the
# family's mixers are named for what they are)
PHI4FLASH_SCOPES = ("embed", "mlp", "loss", "optimizer", "ssm_proj",
                    "ssm_conv", "ssm_scan", "gmu", "diff_attn_proj",
                    "diff_attn")


def _ensure_cpu_devices() -> None:
    """Force the 8-virtual-device CPU platform BEFORE jax initializes
    (same recipe as tests/conftest.py) so the fsdp program lowers at a
    real (2, 4) mesh and its collective census is nonzero."""
    from mobilefinetuner_tpu.parallel.host_devices import force_host_devices
    force_host_devices(8)


# ---------------------------------------------------------------------------
# program builders: each returns (hlo_text, retraces, required_scopes)
# retraces = executables traced across 3 same-shape calls (None when the
# program pins lowering-only contracts)
# ---------------------------------------------------------------------------

def _tiny_batch(cfg, rows, S, seed=0):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, S)), jnp.int32)
    return {"input_ids": ids, "attention_mask": jnp.ones_like(ids),
            "labels": ids}


def prog_train_gpt2_lora():
    """Single-device GPT-2 LoRA optimizer step, donate=True — the solo
    train path's executable."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.lora.lora import (LoRASpec, init_lora_gpt2,
                                               trainable_mask)
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
    from mobilefinetuner_tpu.train.trainer import (TrainConfig,
                                                   init_optimizer,
                                                   make_train_step)
    cfg = GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    lora = init_lora_gpt2(cfg, LoRASpec(rank=2, alpha=4.0),
                          jax.random.PRNGKey(1))
    mask = trainable_mask(lora)
    tc = TrainConfig(total_steps=8, lr=1e-3, warmup_ratio=0.0,
                     schedule="constant")
    traces = {"n": 0}

    def loss_fn(lo, p, mb):
        traces["n"] += 1  # runs exactly when jax (re)traces
        logits = gpt2.forward(cfg, p, mb["input_ids"],
                              attention_mask=mb["attention_mask"], lora=lo)
        return lm_cross_entropy_sum(logits, mb["labels"])

    step = make_train_step(loss_fn, tc, mask=mask, donate=True)
    opt = init_optimizer(lora, tc, mask)
    batch = _tiny_batch(cfg, 2, 16)
    tr = lora
    for i in range(3):
        tr, opt, _ = step(tr, params, opt, batch, jnp.int32(i))
    retraces = traces["n"]
    text = step.lower(tr, params, opt, batch,
                      jnp.int32(3)).compile().as_text()
    return text, retraces, TRAIN_SCOPES


def _prog_expert_lora(model, cfg, init_lora, scopes, head="lm_head"):
    """Single-device LoRA optimizer step of a family on
    cli/expert_lora.py's entry point (one expert-parallel rank where it
    has experts), donate=True, the model's counters riding the metrics;
    `head` names the frozen leaf the loss scores against."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.lora.lora import LoRASpec, trainable_mask
    from mobilefinetuner_tpu.ops.loss import chunked_lm_cross_entropy_sum
    from mobilefinetuner_tpu.train.trainer import (TrainConfig,
                                                   init_optimizer,
                                                   make_train_step)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    lora = init_lora(cfg, LoRASpec(rank=2, alpha=4.0, init="peft"),
                     jax.random.PRNGKey(1))
    mask = trainable_mask(lora)
    tc = TrainConfig(total_steps=8, lr=1e-3, warmup_ratio=0.0,
                     schedule="constant")
    traces = {"n": 0}

    def loss_fn(lo, p, mb):
        traces["n"] += 1  # runs exactly when jax (re)traces
        hidden, aux = model.hidden_states(
            cfg, p, mb["input_ids"], attention_mask=mb["attention_mask"],
            lora=lo, remat=True, with_aux=True)
        s, n = chunked_lm_cross_entropy_sum(hidden, p[head],
                                            mb["labels"], num_chunks=2)
        counters = aux["counters"]
        if "second" in aux:     # a family's second loss, as the CLI adds it
            from mobilefinetuner_tpu.cli.expert_lora import add_second_loss
            s, counters = add_second_loss(s, n, counters, aux["second"],
                                          p[head], mb["labels"])
        return s, n, counters

    step = make_train_step(loss_fn, tc, mask=mask, donate=True)
    opt = init_optimizer(lora, tc, mask)
    batch = _tiny_batch(cfg, 2, 32)
    tr = lora
    for i in range(3):
        tr, opt, _ = step(tr, params, opt, batch, jnp.int32(i))
    retraces = traces["n"]
    text = step.lower(tr, params, opt, batch,
                      jnp.int32(3)).compile().as_text()
    return text, retraces, scopes


def prog_train_afmoe_lora():
    """AFMoE, rank 1 of two."""
    from mobilefinetuner_tpu.core.config import AfmoeConfig
    from mobilefinetuner_tpu.lora.lora import init_lora_afmoe
    from mobilefinetuner_tpu.models import afmoe
    return _prog_expert_lora(
        afmoe, AfmoeConfig.tiny().with_expert_parallel(2, 1),
        init_lora_afmoe, AFMOE_SCOPES)


def prog_train_ling_lora():
    """The Ling hybrid, rank 1 of four (one routing group)."""
    from mobilefinetuner_tpu.core.config import LingHybridConfig
    from mobilefinetuner_tpu.lora.lora import init_lora_ling
    from mobilefinetuner_tpu.models import ling_hybrid
    return _prog_expert_lora(
        ling_hybrid, LingHybridConfig.tiny().with_expert_parallel(4, 1),
        init_lora_ling, LING_SCOPES)


def prog_train_xing4_lora():
    """Xing4.0, every expert held, the multi-token-prediction module's
    second loss in the step."""
    from mobilefinetuner_tpu.core.config import Xing4Config
    from mobilefinetuner_tpu.lora.lora import init_lora_xing4
    from mobilefinetuner_tpu.models import xing4
    return _prog_expert_lora(xing4, Xing4Config.tiny(), init_lora_xing4,
                             XING4_SCOPES)


def prog_train_gpt2_fsdp():
    """GPT-2 full-FT step lowered at a (data=2, fsdp=4) mesh: the
    collective-census program (the r06 V-sharded-embed regression class
    fails HERE instead of on a pod)."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
    from mobilefinetuner_tpu.parallel.mesh import (make_mesh,
                                                   params_shardings,
                                                   replicated_sharding,
                                                   shard_batch)
    from mobilefinetuner_tpu.train.trainer import (TrainConfig,
                                                   init_optimizer,
                                                   make_train_step)
    cfg = GPT2Config.tiny()
    mesh = make_mesh(data=2, fsdp=4, devices=jax.devices()[:8])
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    fsdp_sh = params_shardings(params, mesh, min_size=2 ** 12)
    params = jax.device_put(params, fsdp_sh)

    def loss_fn(p, _unused, mb):
        logits = gpt2.forward(cfg, p, mb["input_ids"],
                              attention_mask=mb["attention_mask"])
        return lm_cross_entropy_sum(logits, mb["labels"])

    tc = TrainConfig(total_steps=8, lr=1e-3, warmup_ratio=0.0,
                     schedule="constant")
    step = make_train_step(loss_fn, tc, donate=False)
    opt = init_optimizer(params, tc)
    repl = replicated_sharding(mesh)
    opt = jax.device_put(opt, jax.tree.map(lambda _: repl, opt))
    batch = _tiny_batch(cfg, 8, 32)
    with mesh:
        text = step.lower(params, None, opt, shard_batch(batch, mesh),
                          jnp.int32(0)).compile().as_text()
    return text, None, TRAIN_SCOPES


def prog_decode_gpt2_paged():
    """The serve loop's paged decode-step executable (block-table KV
    reads, pools donated) — zero collectives, one executable across
    steps with moving pos/tok/tbl DATA."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.models.generate import gpt2_decode_step_paged
    from mobilefinetuner_tpu.serve.paged_kv import init_pools
    cfg = GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    L, H = cfg.n_layer, cfg.n_head
    D = cfg.n_embd // cfg.n_head
    bT, NB = 8, 8
    # the pool's layout is init_pools' to say (serve/paged_kv.py)
    pool_k, pool_v = init_pools(NB, L, H, bT, D)
    traces = {"n": 0}

    def step_py(p, pk, pv, tok, pos, tbl):
        traces["n"] += 1
        logits, pk2, pv2 = gpt2_decode_step_paged(
            cfg, p, pk, pv, tok, pos, tbl, compute_dtype=jnp.float32,
            attn_impl="xla")
        return jnp.argmax(logits, -1).astype(jnp.int32), pk2, pv2

    step = jax.jit(step_py, donate_argnums=(1, 2))
    tbl = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
    for i in range(3):
        tok = jnp.asarray([11 + i, 23 + i], jnp.int32)
        pos = jnp.asarray([i + 1, i + 2], jnp.int32)
        _, pool_k, pool_v = step(params, pool_k, pool_v, tok, pos, tbl)
    retraces = traces["n"]
    tok = jnp.asarray([1, 2], jnp.int32)
    pos = jnp.asarray([4, 5], jnp.int32)
    text = step.lower(params, pool_k, pool_v, tok, pos,
                      tbl).compile().as_text()
    return text, retraces, DECODE_SCOPES


def prog_decode_gpt2_paged_tp():
    """The SHARDED serve step (r20, serve/sharding.py): GPT-2 paged
    decode with a 2-tenant block-diagonal bank lowered at a (1, 4)
    ("dp", "tp") mesh — the GSPMD safety net the tensor-parallel serve
    plane stands on. The pinned census IS the perf contract: the
    partitioner may only pay activation-sized all-reduces (row-parallel
    matmul sums + head re-gathers); a regression that starts moving
    weight- or pool-sized tensors shows up as new collective entries
    here, not as a pod bill. Donation and zero-retrace are pinned
    exactly like the single-chip program's."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.lora.lora import (LoRASpec, assign_adapters,
                                               init_lora_gpt2,
                                               stack_adapters)
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.models.generate import gpt2_decode_step_paged
    from mobilefinetuner_tpu.serve.paged_kv import init_pools
    from mobilefinetuner_tpu.serve.sharding import ServeSharding
    # tiny() has 2 heads; tp=4 needs a head-aligned split
    cfg = dataclasses.replace(GPT2Config.tiny(), n_head=4)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    L, H = cfg.n_layer, cfg.n_head
    D = cfg.n_embd // cfg.n_head
    bT, NB = 8, 8
    sh = ServeSharding.build("gpt2", cfg, 1, 4)
    params = jax.device_put(params, sh.param_shardings(params))
    bank = stack_adapters([
        init_lora_gpt2(cfg, LoRASpec(rank=2, alpha=4.0),
                       jax.random.PRNGKey(i + 1)) for i in range(2)])
    bank = jax.device_put(bank, sh.bank_shardings(bank))
    pool_k, pool_v = init_pools(NB, L, H, bT, D)
    psh = sh.pool_sharding()
    pool_k = jax.device_put(pool_k, psh)
    pool_v = jax.device_put(pool_v, psh)
    traces = {"n": 0}

    def step_py(p, bk, pk, pv, tok, pos, tbl, aid):
        traces["n"] += 1
        lora = assign_adapters(bk, aid)
        logits, pk2, pv2 = gpt2_decode_step_paged(
            cfg, p, pk, pv, tok, pos, tbl, lora=lora,
            compute_dtype=jnp.float32, attn_impl="xla", shardings=sh)
        return jnp.argmax(logits, -1).astype(jnp.int32), pk2, pv2

    step = jax.jit(step_py, donate_argnums=(2, 3),
                   out_shardings=(sh.repl, psh, psh))
    dev = lambda a: jax.device_put(np.asarray(a), sh.repl)
    tbl = dev(np.array([[1, 2], [3, 4]], np.int32))
    aid = dev(np.array([0, 1], np.int32))
    for i in range(3):
        tok = dev(np.array([11 + i, 23 + i], np.int32))
        pos = dev(np.array([i + 1, i + 2], np.int32))
        _, pool_k, pool_v = step(params, bank, pool_k, pool_v, tok, pos,
                                 tbl, aid)
    retraces = traces["n"]
    tok = dev(np.array([1, 2], np.int32))
    pos = dev(np.array([4, 5], np.int32))
    text = step.lower(params, bank, pool_k, pool_v, tok, pos, tbl,
                      aid).compile().as_text()
    return text, retraces, DECODE_SCOPES


def prog_prefill_chunk_gpt2_tp():
    """The round-21 chunked-prefill executable (models/generate.py
    gpt2_prefill_chunk) at ONE static bucket width, lowered at a
    (1, 2) ("dp", "tp") mesh: W prompt rows scatter into the paged
    pools at data-driven start/n_tok offsets, so the whole bucket set
    costs one trace per width — never one per prompt length or chunk
    offset. Donation (pools aliased through) and the collective census
    are pinned exactly like the decode step's: chunk admission must
    pay only activation-sized all-reduces under tp, and a regression
    that re-traces per offset or drops the pool alias shows up here,
    not as a serving stall."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.models.generate import gpt2_prefill_chunk
    from mobilefinetuner_tpu.serve.paged_kv import init_pools
    from mobilefinetuner_tpu.serve.sharding import ServeSharding
    cfg = GPT2Config.tiny()            # 2 heads: tp=2 is head-aligned
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    L, H = cfg.n_layer, cfg.n_head
    D = cfg.n_embd // cfg.n_head
    bT, NB, W, M = 8, 8, 8, 4          # one bucket width W = block_T
    sh = ServeSharding.build("gpt2", cfg, 1, 2)
    params = jax.device_put(params, sh.param_shardings(params))
    pool_k, pool_v = init_pools(NB, L, H, bT, D)
    psh = sh.pool_sharding()
    pool_k = jax.device_put(pool_k, psh)
    pool_v = jax.device_put(pool_v, psh)
    traces = {"n": 0}

    def chunk_py(p, pk, pv, ids, start, n_tok, tbl):
        traces["n"] += 1
        logits, pk2, pv2 = gpt2_prefill_chunk(
            cfg, p, pk, pv, ids, start, n_tok, tbl,
            compute_dtype=jnp.float32, shardings=sh)
        return jnp.argmax(logits, -1).astype(jnp.int32), pk2, pv2

    chunk = jax.jit(chunk_py, donate_argnums=(1, 2),
                    out_shardings=(sh.repl, psh, psh))
    dev = lambda a: jax.device_put(np.asarray(a), sh.repl)
    tbl = dev(np.array([[1, 2, 3, 0]], np.int32))
    # three chunks of one walking admission: moving start, a full
    # chunk, then a partial tail — all DATA, one executable
    for i, (st, nt) in enumerate(((0, 8), (8, 8), (16, 3))):
        ids = dev((np.arange(W, dtype=np.int32) + 7 * i + 1)[None])
        _, pool_k, pool_v = chunk(params, pool_k, pool_v, ids,
                                  dev(np.int32(st)), dev(np.int32(nt)),
                                  tbl)
    retraces = traces["n"]
    ids = dev(np.full((1, W), 5, np.int32))
    text = chunk.lower(params, pool_k, pool_v, ids, dev(np.int32(0)),
                       dev(np.int32(W)), tbl).compile().as_text()
    return text, retraces, DECODE_SCOPES


def prog_multitenant_gpt2():
    """The k-tenant fused optimizer step (ids-routed bank, per-slot
    Adam) — the r18 engine's executable, donated, zero retraces across
    sched-data changes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.lora.lora import (LoRASpec, assign_adapters,
                                               init_lora_gpt2,
                                               stack_adapters,
                                               trainable_mask)
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_rows
    from mobilefinetuner_tpu.optim.adam import init_multi_state
    from mobilefinetuner_tpu.train.trainer import (TrainConfig,
                                                   make_multi_train_step)
    cfg = GPT2Config.tiny()
    k = 2
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    adapters = [init_lora_gpt2(cfg, LoRASpec(rank=2, alpha=4.0),
                               jax.random.PRNGKey(i + 1))
                for i in range(k)]
    bank = stack_adapters(adapters)
    mask = trainable_mask(bank)
    tc = TrainConfig(total_steps=1, lr=0.0, warmup_ratio=0.0,
                     schedule="constant")
    traces = {"n": 0}

    def loss_rows(tr, frozen, mb):
        traces["n"] += 1
        routed = assign_adapters(tr, mb["adapter_ids"])
        logits = gpt2.forward(cfg, frozen, mb["input_ids"],
                              attention_mask=mb["attention_mask"],
                              lora=routed)
        return lm_cross_entropy_rows(logits, mb["labels"])

    step = make_multi_train_step(loss_rows, tc, k, mask=mask)
    opt = init_multi_state(bank, tc.adam(), k, mask)
    batch = _tiny_batch(cfg, 4, 16)
    batch["adapter_ids"] = jnp.asarray([0, 1, 0, 1], jnp.int32)

    def sched(i):
        return {"step": jnp.asarray(np.full(k, i, np.int32)),
                "total": jnp.asarray(np.full(k, 8.0, np.float32)),
                "lr": jnp.asarray(np.full(k, 1e-3, np.float32)),
                "warmup_ratio": jnp.asarray(np.zeros(k, np.float32)),
                "active": jnp.asarray(np.ones(k, bool))}

    tr = bank
    for i in range(3):
        tr, opt, _ = step(tr, params, opt, batch, sched(i))
    retraces = traces["n"]
    text = step.lower(tr, params, opt, batch,
                      sched(3)).compile().as_text()
    return text, retraces, TRAIN_SCOPES


def prog_train_phi4flash_lora():
    """Phi-4-mini-flash (SambaY), the tied head."""
    from mobilefinetuner_tpu.core.config import Phi4FlashConfig
    from mobilefinetuner_tpu.lora.lora import init_lora_phi4flash
    from mobilefinetuner_tpu.models import phi4flash
    return _prog_expert_lora(phi4flash, Phi4FlashConfig.tiny(),
                             init_lora_phi4flash, PHI4FLASH_SCOPES,
                             head="embed")


PROGRAMS = {
    "train_gpt2_lora": prog_train_gpt2_lora,
    "train_afmoe_lora": prog_train_afmoe_lora,
    "train_ling_lora": prog_train_ling_lora,
    "train_xing4_lora": prog_train_xing4_lora,
    "train_phi4flash_lora": prog_train_phi4flash_lora,
    "train_gpt2_fsdp": prog_train_gpt2_fsdp,
    "decode_gpt2_paged": prog_decode_gpt2_paged,
    "decode_gpt2_paged_tp": prog_decode_gpt2_paged_tp,
    "prefill_chunk_gpt2_tp": prog_prefill_chunk_gpt2_tp,
    "multitenant_gpt2": prog_multitenant_gpt2,
}


def build_contract(name: str) -> dict:
    from mobilefinetuner_tpu.core.static_checks import (
        hlo_collective_census, hlo_donated_inputs, missing_hlo_scopes)
    text, retraces, scopes = PROGRAMS[name]()
    missing = set(missing_hlo_scopes(text, scopes))
    present = [s for s in scopes if s not in missing]
    return {
        "retraces": retraces,
        "donated": hlo_donated_inputs(text),
        "collectives": hlo_collective_census(text),
        "scopes": present,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compiled-artifact contract checker (graftlint's "
                    "runtime half)")
    ap.add_argument("--contracts", default=DEFAULT_CONTRACTS,
                    help="pinned contract JSON (default: "
                         "tools/compiled_contracts.json)")
    ap.add_argument("--programs", default=None,
                    help="comma-separated subset (default: all)")
    ap.add_argument("--update", action="store_true",
                    help="regenerate the pinned contracts from the "
                         "current build")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    names = list(PROGRAMS)
    if args.programs:
        names = [n.strip() for n in args.programs.split(",") if n.strip()]
        unknown = [n for n in names if n not in PROGRAMS]
        if unknown:
            print(f"error: unknown program(s): {', '.join(unknown)} "
                  f"(have: {', '.join(PROGRAMS)})", file=sys.stderr)
            return 1

    _ensure_cpu_devices()
    try:
        built = {n: build_contract(n) for n in names}
    except Exception as e:  # noqa: BLE001 — build errors are exit 1
        print(f"error: building contracts failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1

    if args.update:
        pinned = {}
        if os.path.exists(args.contracts):
            with open(args.contracts) as f:
                pinned = json.load(f).get("programs", {})
        pinned.update(built)
        doc = {"_comment": "pinned by tools/check_compiled_contracts.py "
                           "--update; review diffs like any other pin",
               "programs": {n: pinned[n] for n in sorted(pinned)}}
        with open(args.contracts, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"pinned {len(built)} program contract(s) -> "
              f"{args.contracts}")
        return 0

    if not os.path.exists(args.contracts):
        print(f"error: no pinned contracts at {args.contracts} "
              f"(run --update once)", file=sys.stderr)
        return 1
    with open(args.contracts) as f:
        pinned = json.load(f).get("programs", {})

    violations = []
    for n in names:
        want = pinned.get(n)
        if want is None:
            violations.append((n, "no pinned contract (run --update)"))
            continue
        got = built[n]
        for key in ("retraces", "donated", "collectives", "scopes"):
            if got[key] != want.get(key):
                violations.append(
                    (n, f"{key}: pinned {want.get(key)!r} != built "
                        f"{got[key]!r}"))

    if args.format == "json":
        print(json.dumps({
            "programs": built,
            "violations": [{"program": n, "detail": d}
                           for n, d in violations],
        }, indent=1, sort_keys=True))
    else:
        for n in names:
            c = built[n]
            col = ", ".join(f"{k}={v}" for k, v in
                            sorted(c["collectives"].items()) if v)
            print(f"{n}: retraces={c['retraces']} "
                  f"donated={c['donated']} "
                  f"collectives=[{col or 'none'}] "
                  f"scopes={','.join(c['scopes']) or '-'}")
        for n, d in violations:
            print(f"VIOLATION {n}: {d}")
        print(f"check_compiled_contracts: {len(names)} program(s), "
              f"{len(violations)} violation(s)")
    return 2 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
