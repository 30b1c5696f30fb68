"""GPT-2 LoRA fine-tuning CLI.

TPU-native rebuild of the reference `gpt2_lora_finetune` binary
(reference: gpt2_lora_finetune/main.cpp — flag surface :80-171, training
loop :561-684): same flags and reporting, but the step is one compiled XLA
program (forward+backward+clip+LR+Adam with lax.scan grad-accum) running on
a ("data","fsdp") device mesh, with optional host-RAM offload of the frozen
base params replacing disk sharding.

Improvements over the reference, on purpose:
  - attention gradients flow on every path (the reference's default
    mem-efficient attention is forward-only, SURVEY.md §2.12.1);
  - --resume_from restores optimizer state + step counter from the .opt
    sidecar when present (the reference never wires Adam::save/load,
    SURVEY.md §5);
  - seeded LoRA init (the reference uses std::random_device,
    SURVEY.md §2.12.6).

Usage (tiny smoke):
  python -m mobilefinetuner_tpu.cli.gpt2_lora_finetune \
      --pretrained_dir /path/gpt2 --data_dir /path/wikitext-2 \
      --steps 10 --batch_size 4 --lora_out out/adapter.safetensors
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

import jax

from mobilefinetuner_tpu.cli import common
from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.data.tokenizer_bpe import GPT2BPETokenizer
from mobilefinetuner_tpu.data.wikitext2 import WT2Config, WikiText2Dataset
from mobilefinetuner_tpu.io import async_ckpt
from mobilefinetuner_tpu.io.checkpoints import load_gpt2
from mobilefinetuner_tpu.lora import peft_io
from mobilefinetuner_tpu.lora.lora import (LoRASpec, init_lora_gpt2,
                                           num_trainable, trainable_mask)
from mobilefinetuner_tpu.models import gpt2
from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
from mobilefinetuner_tpu.optim import adam as adam_mod

log = get_logger()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="gpt2_lora_finetune",
        description="GPT-2 LoRA fine-tuning on WikiText-2 (TPU)")
    p.add_argument("--data_dir", required=True,
                   help="WikiText-2 directory (wiki.{train,valid}.tokens)")
    p.add_argument("--pretrained_dir", required=True,
                   help="HF GPT-2 checkpoint dir (config.json, "
                        "model.safetensors, vocab.json, merges.txt)")
    p.add_argument("--lora_out", default="gpt2_lora.safetensors")
    p.add_argument("--resume_from", default="",
                   help="adapter safetensors to resume from")
    p.add_argument("--eval_out", default="", help="eval JSONL output path")
    p.add_argument("--rank", type=int, default=8)
    p.add_argument("--alpha", type=float, default=16.0)
    p.add_argument("--lora_dropout", type=float, default=0.0)
    p.add_argument("--lora_targets", default="attn_qkv,attn_proj",
                   help="comma list of attn_qkv,attn_proj,mlp_fc_in,"
                        "mlp_fc_out,attn_q,attn_k,attn_v,lm_head "
                        "(PEFT-aligned default: fused c_attn + c_proj, "
                        "main.cpp:381-390; lm_head is a single unstacked "
                        "site on the tied head — native format only, "
                        "cannot be merged)")
    p.add_argument("--split_qkv", action="store_true",
                   help="replace the fused attn_qkv target with separate "
                        "q/k/v column-range adapters "
                        "(lora_injector.h:169-191)")
    p.add_argument("--peft_export_dir", default="",
                   help="also export an HF-PEFT adapter directory")
    common.add_align_flags(p)
    common.add_train_flags(p, lr=1e-4, seq_len=128, batch_size=1)
    common.add_pm_flags(p)
    common.add_shard_flags(p)
    common.add_mesh_flags(p)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    common.enable_compile_cache()

    if args.split_qkv and args.peft_export_dir:
        raise SystemExit("--split_qkv adapters have no PEFT "
                         "representation; drop --peft_export_dir "
                         "(the native adapter format supports them)")
    config, params = load_gpt2(args.pretrained_dir)
    if args.no_model_dropout:
        config = dataclasses.replace(config, embd_pdrop=0.0,
                                     resid_pdrop=0.0, attn_pdrop=0.0)
    if args.seq_len > config.n_positions:
        log.warning(f"seq_len({args.seq_len}) > n_positions"
                    f"({config.n_positions}), clamped")
        args.seq_len = config.n_positions
    log.info(f"GPT-2: layers={config.n_layer} hidden={config.n_embd} "
             f"heads={config.n_head}")

    # LoRA: fresh init or resume (main.cpp:340-400). The resume source
    # is checksum-verified first and falls back down its lineage on
    # corruption (common.resolve_resume_from rewrites args.resume_from;
    # the ckpt_verify verdicts land in the telemetry stream).
    if args.resume_from:
        common.resolve_resume_from(args)
        lora, spec = peft_io.load_adapter(args.resume_from)
        log.info(f"resumed adapter: r={spec.rank} alpha={spec.alpha} "
                 f"targets={spec.targets}")
    else:
        targets = [t for t in args.lora_targets.split(",") if t]
        if args.split_qkv:
            targets = [t for t in targets if t != "attn_qkv"]
            targets = ["attn_q", "attn_k", "attn_v"] + targets
        spec = LoRASpec(rank=args.rank, alpha=args.alpha,
                        dropout=args.lora_dropout,
                        targets=targets, init="gpt2")
        lora = init_lora_gpt2(config, spec, jax.random.PRNGKey(args.seed))
    mask = trainable_mask(lora)
    log.info(f"trainable params: {num_trainable(lora):,}")

    tok = GPT2BPETokenizer.from_pretrained(args.pretrained_dir)
    wt2 = WT2Config(seq_len=args.seq_len, batch_size=args.batch_size,
                    data_fraction=args.data_fraction, seed=args.seed,
                    **common.data_retry_kwargs(args))
    train_ds = WikiText2Dataset(args.data_dir, "train", wt2, tok.encode,
                                tok.eos_id)
    valid_ds = None
    if args.eval_interval:
        wt2_eval = WT2Config(seq_len=args.seq_len,
                             batch_size=args.eval_batch_size, shuffle=False,
                             **common.data_retry_kwargs(args))
        valid_ds = WikiText2Dataset(args.data_dir, "valid", wt2_eval,
                                    tok.encode, tok.eos_id)

    steps_per_epoch = max(train_ds.num_batches() // args.grad_accum_steps, 1)
    total_steps = common.resolve_total_steps(args, steps_per_epoch)
    tc = common.train_config_from_args(args, total_steps)
    log.info(f"{train_ds.num_chunks} chunks, {steps_per_epoch} steps/epoch, "
             f"{total_steps} total steps")

    opt_state, start_step = common.maybe_resume_opt_state(
        args, lora, tc, mask)

    mesh, cp_mesh = common.build_mesh(args)
    config = common.with_attention_impl(args, config)
    if cp_mesh is not None and config.attn_pdrop > 0:
        log.warning(f"attn_pdrop={config.attn_pdrop} is unsupported by "
                    f"ring attention; attention-probs dropout is OFF in "
                    f"sequence-parallel mode (embd/resid dropout still "
                    f"applies; --no_model_dropout silences this)")
    params, fetch_fn, offload_arg = common.setup_frozen_params(
        args, params, mesh)
    compute_dtype = common.compute_dtype_from_args(args)
    model_pdrop = max(config.embd_pdrop, config.resid_pdrop,
                      config.attn_pdrop)
    base_rng = (jax.random.PRNGKey(args.seed + 1)
                if args.lora_dropout > 0 or model_pdrop > 0 else None)

    from mobilefinetuner_tpu.lora.lora import GPT2_TARGETS
    common.log_lora_impl_resolution(
        args, {t: GPT2_TARGETS[t](config) for t in spec.targets or []},
        spec.rank, compute_dtype)

    # loss/nll read args.remat and the offload cells AT TRACE TIME: the
    # memory-admission degradation ladder (common.run_training,
    # DESIGN.md §21) re-traces them after flipping remat or enabling
    # offload, so the rungs need no separate loss builders
    def loss_fn(lora_t, frozen, mb):
        # per-(step, micro-batch) dropout key, threaded via the batch
        rng = mb["dropout_rng"][0] if "dropout_rng" in mb else None
        p = frozen if offload_arg is not None else fetch_fn(frozen)
        logits = gpt2.forward(config, p, mb["input_ids"],
                              attention_mask=mb["attention_mask"],
                              lora=lora_t, compute_dtype=compute_dtype,
                              remat=args.remat, offload=offload_arg,
                              lora_dropout=args.lora_dropout,
                              dropout_rng=rng, cp_mesh=cp_mesh,
                              lora_impl=args.lora_impl, mesh=mesh)
        return lm_cross_entropy_sum(logits, mb["labels"])

    def nll_fn(lora_t, frozen, mb):
        p = frozen if offload_arg is not None else fetch_fn(frozen)
        logits = gpt2.forward(config, p, mb["input_ids"],
                              attention_mask=mb["attention_mask"],
                              lora=lora_t, compute_dtype=compute_dtype,
                              offload=offload_arg, cp_mesh=cp_mesh,
                              lora_impl=args.lora_impl, mesh=mesh)
        return lm_cross_entropy_sum(logits, mb["labels"])

    def offload_rung():
        """The ladder's last rung (policy shared with the Gemma LoRA
        CLI via common.offload_rung_state): re-place the frozen base
        with host offload at the streams-only budget. The loss/nll
        closures read the rebound cells at the ladder's recompile."""
        nonlocal params, fetch_fn, offload_arg
        out = common.offload_rung_state(args, params, mesh)
        if out is None:
            return None
        params, fetch_fn, offload_arg = out
        return params, loss_fn

    if args.align_dump_dir:
        from mobilefinetuner_tpu.align.dump import run_align_dump

        def trace_fn(lora_t, frozen, mb):
            p = fetch_fn(frozen)
            x, acts = gpt2.hidden_states(
                config, p, mb["input_ids"],
                attention_mask=mb["attention_mask"], lora=lora_t,
                compute_dtype=compute_dtype, collect_layers=True)
            logits = x @ p["wte"].astype(compute_dtype).T
            return logits, acts

        _, batch = next(common.micro_batches(train_ds, 1))
        run_align_dump(
            args.align_dump_dir, trace_fn=trace_fn, loss_fn=loss_fn,
            trainable=lora, frozen=params, batch=batch, tc=tc, mask=mask,
            spec=spec, family="gpt2", model_dir=args.pretrained_dir,
            steps=args.align_steps)
        return 0

    def save_hook(step, lora_t, opt_st, final, ckpt=None):
        path = args.lora_out
        if not final:  # _stepN suffix (main.cpp:180-187)
            root, ext = os.path.splitext(path)
            path = f"{root}_step{step}{ext}"
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        # blocking part: one batched D2H snapshot of adapter + opt state;
        # the write (key-map, encode, atomic safetensors publish) runs on
        # the checkpointer's background thread under --async_save
        (lora_h, opt_h), snap_ms = async_ckpt.timed_snapshot(
            (lora_t, opt_st))

        def write():
            peft_io.save_adapter(path, lora_h, spec)
            # loop_step: the resume point (Adam's own counter lags it
            # under --skip_nonfinite); lineage + GC ride the write hook
            adam_mod.save_state(path + ".opt", opt_h, tc.adam(),
                                extra_metadata={"loop_step": str(step)})
            common.record_ckpt_files(args, args.lora_out, step,
                                     [path, path + ".opt"])
            log.info(f"saved adapter -> {path}")
            if final and args.peft_export_dir:
                peft_io.export_peft(args.peft_export_dir, lora_h, spec,
                                    "gpt2",
                                    base_model_name=args.pretrained_dir)
                log.info(f"PEFT export -> {args.peft_export_dir}")
            return [path, path + ".opt"]

        async_ckpt.submit(ckpt, step, write, final=final,
                          snapshot_ms=snap_ms)

    # in-loop MFU: the SAME analytic estimator as bench.py's MFU column
    # (core/telemetry.transformer_flops), per GLOBAL optimizer step
    from mobilefinetuner_tpu.core.telemetry import transformer_flops
    flops = transformer_flops(
        sum(int(x.size) for x in jax.tree.leaves(lora)),
        gpt2.param_count(params), args.batch_size * tc.grad_accum_steps,
        args.seq_len, config.n_layer, config.n_head, config.head_dim,
        full_ft=False)

    common.run_training(
        args, trainable=lora, frozen=params, loss_fn=loss_fn, nll_fn=nll_fn,
        train_ds=train_ds, valid_ds=valid_ds, total_steps=total_steps,
        tc=tc, mask=mask, start_step=start_step, opt_state=opt_state,
        save_hook=save_hook, mesh=mesh, dropout_rng=base_rng,
        flops_per_step=flops,
        # the inverse of save_hook: arms in-process rollback
        # (--rollback_budget) against the lineage at --lora_out
        load_hook=common.make_rollback_loader(
            tc, mask, lambda p: peft_io.load_adapter(p)[0]),
        ckpt_path=args.lora_out,
        # memory-admission degradation ladder (DESIGN.md §21): remat
        # and accum_x2 need no hooks (run_training flips args.remat /
        # tc.grad_accum_steps and re-traces); offload re-places the
        # frozen base through this CLI's own setup path
        degrade_builders={"offload": offload_rung})
    return 0


if __name__ == "__main__":
    sys.exit(main())
