"""Gemma-3 LoRA fine-tuning CLI.

TPU-native rebuild of the reference `train_lora_gemma` binary
(reference: operators/finetune_ops/optim/train_lora_gemma.cpp — config/
weights/tokenizer load :352-496, target presets + --lora_targets override
:498-540, pretokenized-data mode :477-496, sharding registration :431-475,
training via GemmaLoRATrainer). The 262k-vocab lm_head+CE runs through the
chunked loss (ops/loss.py chunked_lm_cross_entropy) so [B,S,262144] fp32
logits are never materialized (SURVEY.md §7 hard part (d)).

Alignment-dump mode (--align_dump_dir) mirrors the reference's
single-batch npy dumps (:620-920) via align/dump.py; compare with the
torch/PEFT mirror tools/align_torch_mirror.py.

Usage (tiny smoke):
  python -m mobilefinetuner_tpu.cli.train_lora_gemma \
      --model_dir /path/gemma-3-270m --data_dir /path/wikitext-2 \
      --max_steps 10 --batch 2 --output_dir out/
"""

from __future__ import annotations

import argparse
import os
import sys

import jax

from mobilefinetuner_tpu.cli import common
from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.data.tokenizer_gemma import GemmaTokenizer
from mobilefinetuner_tpu.data.wikitext2 import WT2Config, WikiText2Dataset
from mobilefinetuner_tpu.io import async_ckpt
from mobilefinetuner_tpu.io.checkpoints import load_gemma3
from mobilefinetuner_tpu.lora import peft_io
from mobilefinetuner_tpu.lora.lora import (GEMMA_PRESETS, LoRASpec,
                                           init_lora_gemma3, num_trainable,
                                           trainable_mask)
from mobilefinetuner_tpu.models import gemma3
from mobilefinetuner_tpu.ops.loss import chunked_lm_cross_entropy_sum
from mobilefinetuner_tpu.optim import adam as adam_mod

log = get_logger()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="train_lora_gemma",
        description="Gemma-3 LoRA fine-tuning on WikiText-2 (TPU)")
    p.add_argument("--model_dir", required=True,
                   help="HF Gemma-3 checkpoint dir")
    p.add_argument("--data_dir", default="",
                   help="WikiText-2 directory (or use --pretokenized_path)")
    p.add_argument("--output_dir", default="gemma_lora_out")
    p.add_argument("--resume_from", default="")
    p.add_argument("--eval_out", default="")
    p.add_argument("--rank", type=int, default=8)
    p.add_argument("--alpha", type=float, default=32.0)
    p.add_argument("--lora_dropout", type=float, default=0.0)
    p.add_argument("--targets", default="full",
                   choices=list(GEMMA_PRESETS),
                   help="preset (gemma_lora_injector.h:9-34)")
    p.add_argument("--lora_targets", default="",
                   help="comma list overriding --targets "
                        "(q_proj,k_proj,v_proj,o_proj,gate_proj,up_proj,"
                        "down_proj,lm_head — lm_head is a single "
                        "unstacked site on the tied head; its delta "
                        "rides the chunked-CE epilogue, native adapter "
                        "format only)")
    p.add_argument("--pretokenized_path", default="",
                   help="pretokenized .bin (train split)")
    p.add_argument("--pretokenized_meta", default="",
                   help="(accepted for reference-CLI compat; the .bin's "
                        "sidecar meta.json is found automatically)")
    p.add_argument("--loss_chunks", type=int, default=8,
                   help="sequence chunks for the 262k-vocab chunked CE")
    p.add_argument("--peft_export_dir", default="")
    common.add_align_flags(p)
    p.add_argument("--max_steps", type=int, default=0,
                   help="alias of --steps (reference flag name)")
    common.add_train_flags(p, lr=1e-4, seq_len=256, batch_size=1)
    common.add_pm_flags(p)
    common.add_shard_flags(p)
    common.add_mesh_flags(p)
    # reference flag aliases
    p.add_argument("--batch", type=int, default=None,
                   help="alias of --batch_size")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    common.enable_compile_cache()
    if args.batch is not None:
        args.batch_size = args.batch
    if args.max_steps and not args.steps:
        args.steps = args.max_steps

    config, params = load_gemma3(args.model_dir)
    log.info(f"Gemma-3: layers={config.num_hidden_layers} "
             f"hidden={config.hidden_size} vocab={config.vocab_size} "
             f"q/kv heads={config.num_attention_heads}/"
             f"{config.num_key_value_heads}")

    if args.resume_from:
        # verify-on-load with lineage fallback (DESIGN.md §20)
        common.resolve_resume_from(args)
        lora, spec = peft_io.load_adapter(args.resume_from)
        log.info(f"resumed adapter: r={spec.rank} targets={spec.targets}")
    else:
        targets = ([t for t in args.lora_targets.split(",") if t]
                   or GEMMA_PRESETS[args.targets])
        spec = LoRASpec(rank=args.rank, alpha=args.alpha,
                        dropout=args.lora_dropout, targets=targets,
                        init="peft")  # PEFT-default init (SURVEY §2.5)
        lora = init_lora_gemma3(config, spec, jax.random.PRNGKey(args.seed))
    mask = trainable_mask(lora)
    log.info(f"trainable params: {num_trainable(lora):,}")

    tok = GemmaTokenizer.from_pretrained(args.model_dir)
    encode = lambda s: tok.encode(s, add_bos=False)
    wt2 = WT2Config(seq_len=args.seq_len, batch_size=args.batch_size,
                    data_fraction=args.data_fraction, seed=args.seed,
                    **common.data_retry_kwargs(args))
    train_ds = WikiText2Dataset(
        args.data_dir, "train", wt2, encode, tok.eos_id,
        pad_id=tok.pad_id,
        pretokenized_bin=args.pretokenized_path or None)
    valid_ds = None
    if args.eval_interval and args.data_dir:
        wt2_eval = WT2Config(seq_len=args.seq_len,
                             batch_size=args.eval_batch_size, shuffle=False,
                             **common.data_retry_kwargs(args))
        valid_ds = WikiText2Dataset(args.data_dir, "valid", wt2_eval,
                                    encode, tok.eos_id, pad_id=tok.pad_id)

    steps_per_epoch = max(train_ds.num_batches() // args.grad_accum_steps, 1)
    total_steps = common.resolve_total_steps(args, steps_per_epoch)
    tc = common.train_config_from_args(args, total_steps)
    log.info(f"{train_ds.num_chunks} chunks, {total_steps} total steps")

    opt_state, start_step = common.maybe_resume_opt_state(
        args, lora, tc, mask)

    mesh, cp_mesh = common.build_mesh(args)
    config = common.with_attention_impl(args, config)
    params, fetch_fn, offload_arg = common.setup_frozen_params(
        args, params, mesh)
    compute_dtype = common.compute_dtype_from_args(args)
    base_rng = (jax.random.PRNGKey(args.seed + 1)
                if args.lora_dropout > 0 else None)

    def resolve(frozen):
        """Fetch offloaded top-level leaves (incl. the embed table, reused
        by the tied-lm-head chunked CE) once; block weights stream per
        layer via the returned stream fn. Reads the offload cells at
        TRACE time, so the degradation ladder's offload rung takes
        effect at its recompile (DESIGN.md §21)."""
        from mobilefinetuner_tpu.parallel.offload import resolve_offload
        if offload_arg is None:
            return fetch_fn(frozen), None
        return resolve_offload(frozen, offload_arg)

    def offload_rung():
        """Memory-admission ladder, last rung (policy shared with the
        GPT-2 LoRA CLI via common.offload_rung_state): re-place the
        frozen base with host offload at the streams-only budget — the
        262k embed stays resident, block stacks stream per layer
        inside the remat'd scan. None when offload is already on."""
        nonlocal params, fetch_fn, offload_arg
        out = common.offload_rung_state(args, params, mesh)
        if out is None:
            return None
        params, fetch_fn, offload_arg = out
        return params, loss_fn

    # vocab-parallel CE on multi-device meshes: the fsdp-sharded 262k
    # embed must not be all-gathered per step (ops/loss.py). In
    # sequence-parallel mode the fsdp axis carries the sequence, so the
    # CE runs the seq-sharded composition (chunk-wise hidden gather +
    # vocab-parallel softmax — ops/loss.py seq_shard).
    ce_mesh = mesh if mesh.size > 1 else None
    ce_sp = cp_mesh is not None

    from mobilefinetuner_tpu.lora.lora import GEMMA_TARGETS
    common.log_lora_impl_resolution(
        args, {t: GEMMA_TARGETS[t](config) for t in spec.targets or []},
        spec.rank, compute_dtype)

    def loss_fn(lora_t, frozen, mb):
        p, stream = resolve(frozen)
        # per-(step, micro-batch) dropout key, threaded via the batch
        rng = mb["dropout_rng"][0] if "dropout_rng" in mb else None
        hidden = gemma3.hidden_states(
            config, p, mb["input_ids"],
            attention_mask=mb["attention_mask"], lora=lora_t,
            compute_dtype=compute_dtype, remat=args.remat,
            lora_dropout=args.lora_dropout, dropout_rng=rng,
            block_stream=stream, cp_mesh=cp_mesh,
            lora_impl=args.lora_impl, mesh=mesh)
        # lm_head tied to embeddings; chunked CE avoids [B,S,262k]
        # logits — an opt-in "lm_head" adapter rides it as lora_head
        # (its delta stays chunk-local / in-kernel, DESIGN.md §17),
        # with --lora_dropout applied to its branch input like every
        # per-layer site
        return chunked_lm_cross_entropy_sum(
            hidden, p["embed"], mb["labels"], num_chunks=args.loss_chunks,
            mesh=ce_mesh, sequence_parallel=ce_sp,
            lora_head=lora_t["blocks"].get("lm_head"),
            lora_impl=args.lora_impl,
            lora_dropout=args.lora_dropout, dropout_rng=rng)

    def nll_fn(lora_t, frozen, mb):
        p, stream = resolve(frozen)
        hidden = gemma3.hidden_states(
            config, p, mb["input_ids"],
            attention_mask=mb["attention_mask"], lora=lora_t,
            compute_dtype=compute_dtype, block_stream=stream,
            cp_mesh=cp_mesh, lora_impl=args.lora_impl, mesh=mesh)
        return chunked_lm_cross_entropy_sum(
            hidden, p["embed"], mb["labels"], num_chunks=args.loss_chunks,
            mesh=ce_mesh, sequence_parallel=ce_sp,
            lora_head=lora_t["blocks"].get("lm_head"),
            lora_impl=args.lora_impl)

    if args.align_dump_dir:
        from mobilefinetuner_tpu.align.dump import run_align_dump

        def trace_fn(lora_t, frozen, mb):
            p = fetch_fn(frozen)
            x, acts = gemma3.hidden_states(
                config, p, mb["input_ids"],
                attention_mask=mb["attention_mask"], lora=lora_t,
                compute_dtype=compute_dtype, collect_layers=True)
            logits = x @ p["embed"].astype(compute_dtype).T
            return logits, acts

        _, batch = next(common.micro_batches(train_ds, 1))
        run_align_dump(
            args.align_dump_dir, trace_fn=trace_fn, loss_fn=loss_fn,
            trainable=lora, frozen=params, batch=batch, tc=tc, mask=mask,
            spec=spec, family="gemma", model_dir=args.model_dir,
            steps=args.align_steps)
        return 0

    def save_hook(step, lora_t, opt_st, final, ckpt=None):
        os.makedirs(args.output_dir, exist_ok=True)
        name = "gemma_lora.safetensors" if final \
            else f"gemma_lora_step{step}.safetensors"
        path = os.path.join(args.output_dir, name)
        # blocking snapshot on the loop thread; write off-loop (atomic)
        (lora_h, opt_h), snap_ms = async_ckpt.timed_snapshot(
            (lora_t, opt_st))

        def write():
            peft_io.save_adapter(path, lora_h, spec)
            adam_mod.save_state(path + ".opt", opt_h, tc.adam(),
                                extra_metadata={"loop_step": str(step)})
            common.record_ckpt_files(
                args, os.path.join(args.output_dir,
                                   "gemma_lora.safetensors"),
                step, [path, path + ".opt"])
            log.info(f"saved adapter -> {path}")
            if final and args.peft_export_dir:
                peft_io.export_peft(args.peft_export_dir, lora_h, spec,
                                    "gemma",
                                    base_model_name=args.model_dir)
            return [path, path + ".opt"]

        async_ckpt.submit(ckpt, step, write, final=final,
                          snapshot_ms=snap_ms)

    # in-loop MFU from the shared estimator (core/telemetry.py)
    from mobilefinetuner_tpu.core.telemetry import transformer_flops
    flops = transformer_flops(
        sum(int(x.size) for x in jax.tree.leaves(lora)),
        sum(int(x.size) for x in jax.tree.leaves(params)),
        args.batch_size * tc.grad_accum_steps, args.seq_len,
        config.num_hidden_layers, config.num_attention_heads,
        config.head_dim, full_ft=False)

    common.run_training(
        args, trainable=lora, frozen=params, loss_fn=loss_fn, nll_fn=nll_fn,
        train_ds=train_ds, valid_ds=valid_ds, total_steps=total_steps,
        tc=tc, mask=mask, start_step=start_step, opt_state=opt_state,
        save_hook=save_hook, mesh=mesh, dropout_rng=base_rng,
        flops_per_step=flops,
        load_hook=common.make_rollback_loader(
            tc, mask, lambda p: peft_io.load_adapter(p)[0]),
        ckpt_path=os.path.join(args.output_dir, "gemma_lora.safetensors"),
        # memory-admission degradation ladder (DESIGN.md §21)
        degrade_builders={"offload": offload_rung})
    return 0


if __name__ == "__main__":
    sys.exit(main())
