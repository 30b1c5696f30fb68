"""Gemma-3 full fine-tuning CLI (every parameter trainable).

Beyond-reference capability: the reference has full fine-tuning for GPT-2
only (gpt2_full_finetune/main.cpp) and LoRA-only for Gemma
(train_lora_gemma.cpp) — this CLI completes the model×mode matrix with
the same TPU-native skeleton as cli/gpt2_full_finetune.py: params are the
trainable tree, FSDP-sharded over the mesh with Adam m/v inheriting the
shardings (ZeRO optimizer-state partitioning), and the 262k-vocab
lm_head+CE runs through the chunked loss so [B, S, 262144] fp32 logits are
never materialized. The tied embedding is trainable, so its gradient sums
the embedding-gather and lm-head paths — which the chunked CE's
scan-accumulated dW provides (ops/loss.py).

Usage (tiny smoke):
  python -m mobilefinetuner_tpu.cli.gemma_full_finetune \
      --model_dir /path/gemma-3-270m --data_dir /path/wikitext-2 \
      --steps 10 --output_path out/gemma_full_ft.safetensors
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax

from mobilefinetuner_tpu.cli import common
from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.data.tokenizer_gemma import GemmaTokenizer
from mobilefinetuner_tpu.data.wikitext2 import WT2Config, WikiText2Dataset
from mobilefinetuner_tpu.io import async_ckpt
from mobilefinetuner_tpu.io.checkpoints import (gemma3_params_from_hf,
                                                load_gemma3, save_gemma3)
from mobilefinetuner_tpu.models import gemma3
from mobilefinetuner_tpu.ops.loss import chunked_lm_cross_entropy_sum
from mobilefinetuner_tpu.optim import adam as adam_mod
from mobilefinetuner_tpu.parallel.mesh import shard_params

log = get_logger()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="gemma_full_finetune",
        description="Gemma-3 full fine-tuning on WikiText-2 (TPU)")
    p.add_argument("--model_dir", required=True,
                   help="HF Gemma-3 checkpoint dir")
    p.add_argument("--data_dir", required=True)
    p.add_argument("--output_path", default="gemma_full_ft.safetensors")
    p.add_argument("--resume_from", default="",
                   help="full-model safetensors (or HF dir) to resume from")
    p.add_argument("--eval_out", default="")
    p.add_argument("--loss_chunks", type=int, default=8,
                   help="sequence chunks for the 262k-vocab chunked CE")
    p.add_argument("--opt_offload", action="store_true",
                   help="stream f32 master weights + Adam m/v from pinned "
                        "host RAM through a per-leaf scanned update; the "
                        "device holds only the compute-dtype copy. "
                        "Enables 1B-class full FT on one 16 GB chip "
                        "(optim/opt_offload.py); single-chip only")
    p.add_argument("--opt_offload_state_dtype", default="float32",
                   choices=["float32", "bfloat16", "float16"],
                   help="storage dtype for the streamed Adam m/v host "
                        "tier (16-bit halves their stream; v is "
                        "sqrt-encoded — OptOffloadSpec). The sidecar "
                        "must be resumed with the same dtype.")
    p.add_argument("--opt_offload_master_dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="storage dtype for the streamed master weights "
                        "(bfloat16 quantizes the update write-back with "
                        "stochastic rounding — OptOffloadSpec)")
    common.add_train_flags(p, lr=2e-5, seq_len=256, batch_size=1)
    common.add_pm_flags(p)
    common.add_mesh_flags(p)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    common.enable_compile_cache()

    config, params = load_gemma3(args.model_dir)
    log.info(f"Gemma-3 full FT: layers={config.num_hidden_layers} "
             f"hidden={config.hidden_size} vocab={config.vocab_size}")
    if args.no_model_dropout:
        # the shared flag surface carries this for GPT-2 configs; Gemma-3
        # checkpoints have no embd/resid/attn pdrop fields to zero
        log.warning("--no_model_dropout is a no-op for Gemma-3 "
                    "(the config has no dropout fields)")
    if args.resume_from:
        # verify-on-load with lineage fallback (DESIGN.md §20)
        common.resolve_resume_from(args)
        params = gemma3_params_from_hf(
            common.load_full_resume(args.resume_from), config)
        log.info(f"resumed full model from {args.resume_from}")
    if args.seq_len > config.max_position_embeddings:
        args.seq_len = config.max_position_embeddings

    tok = GemmaTokenizer.from_pretrained(args.model_dir)
    encode = lambda s: tok.encode(s, add_bos=False)
    wt2 = WT2Config(seq_len=args.seq_len, batch_size=args.batch_size,
                    data_fraction=args.data_fraction, seed=args.seed,
                    **common.data_retry_kwargs(args))
    train_ds = WikiText2Dataset(args.data_dir, "train", wt2, encode,
                                tok.eos_id, pad_id=tok.pad_id)
    valid_ds = None
    if args.eval_interval:
        wt2_eval = WT2Config(seq_len=args.seq_len,
                             batch_size=args.eval_batch_size, shuffle=False,
                             **common.data_retry_kwargs(args))
        valid_ds = WikiText2Dataset(args.data_dir, "valid", wt2_eval,
                                    encode, tok.eos_id, pad_id=tok.pad_id)

    steps_per_epoch = max(train_ds.num_batches() // args.grad_accum_steps, 1)
    total_steps = common.resolve_total_steps(args, steps_per_epoch)
    tc = common.train_config_from_args(args, total_steps)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log.info(f"full FT: {n_params:,} trainable params, "
             f"{total_steps} steps")

    mesh, cp_mesh = common.build_mesh(args)
    config = common.with_attention_impl(args, config)
    compute_dtype = common.compute_dtype_from_args(args)
    step_builder = None
    plan = None
    if args.opt_offload:
        # master + Adam state stream from pinned host; device holds only
        # the compute copy (optim/opt_offload.py)
        from mobilefinetuner_tpu.optim import opt_offload as oo
        if mesh.size > 1:
            raise SystemExit("--opt_offload is single-chip (it streams "
                             "state through one chip's host link); drop "
                             "--mesh_data/--mesh_fsdp")
        if getattr(args, "skip_nonfinite", 0) \
                or getattr(args, "rollback_budget", 0) > 0:
            # refuse loudly rather than silently void the safety
            # promise: the offloaded step builder has no skip guard
            # (a NaN grad would poison the host-tier master/m/v) and
            # the generic rollback cannot reproduce its placements
            raise SystemExit(
                "--skip_nonfinite/--rollback_budget are not supported "
                "with --opt_offload (the offloaded update has no "
                "guarded-identity path; recovery there is "
                "process-level --resume_from) — drop the recovery "
                "flags or --opt_offload")
        oo_spec = oo.OptOffloadSpec(
            state_dtype=args.opt_offload_state_dtype,
            master_dtype=args.opt_offload_master_dtype)
        plan = oo.plan_opt_offload(params, oo_spec)
        trainable, opt_state = oo.init_opt_offload(
            params, plan, compute_dtype=compute_dtype, spec=oo_spec)
        start_step = 0
        if args.resume_from and os.path.exists(args.resume_from + ".opt"):
            opt_state = oo.resume_opt_sidecar(args.resume_from + ".opt",
                                              opt_state)
            start_step = int(opt_state["step"])
            log.info(f"restored offloaded opt state @ step {start_step}")
        n_streamed = sum(1 for c in jax.tree.leaves(plan) if c)
        import jax.numpy as jnp
        per_param = (jnp.dtype(oo_spec.master_dtype).itemsize
                     + 2 * jnp.dtype(oo_spec.state_dtype).itemsize)
        host_mb = sum(x.size * per_param / 2 ** 20
                      for x, c in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(plan)) if c)
        log.info(f"opt offload: {n_streamed} leaves "
                 f"({host_mb:.0f} MB master+m+v, "
                 f"master={oo_spec.master_dtype} "
                 f"state={oo_spec.state_dtype}) -> pinned host")

        def step_builder(loss_fn, tc, mask=None, donate=True):
            return oo.make_offload_train_step(
                loss_fn, tc, plan, compute_dtype=compute_dtype,
                donate=donate, mask=mask, spec=oo_spec)
        params = trainable
    else:
        opt_state, start_step = common.maybe_resume_opt_state(
            args, params, tc, None)
        # Full FT: params themselves are the trainable tree — FSDP-shard
        # them (and thus Adam m/v) over the mesh; no host offload of
        # trainables. Checkpoint + sidecar hold FULL host tensors, so
        # this placement is where ANY mesh shape re-shards a resume
        # (elastic resume, DESIGN.md §18; shard_params is multi-host
        # safe, unlike a raw device_put).
        params = shard_params(params, mesh)
        if opt_state is not None:
            opt_state = common.place_opt_state(opt_state, mesh)

    # vocab-parallel CE on multi-device meshes (ops/loss.py): with the
    # tied embed TRAINABLE, this also keeps its gradient V-sharded
    # (reduce-scatter) instead of all-gathering table + grad per step.
    ce_mesh = mesh if (mesh.size > 1 and cp_mesh is None) else None

    def loss_fn(params_t, _unused, mb):
        hidden = gemma3.hidden_states(
            config, params_t, mb["input_ids"],
            attention_mask=mb["attention_mask"],
            compute_dtype=compute_dtype, remat=args.remat,
            cp_mesh=cp_mesh, mesh=mesh)
        return chunked_lm_cross_entropy_sum(
            hidden, params_t["embed"], mb["labels"],
            num_chunks=args.loss_chunks, mesh=ce_mesh)

    def nll_fn(params_t, _unused, mb):
        hidden = gemma3.hidden_states(
            config, params_t, mb["input_ids"],
            attention_mask=mb["attention_mask"],
            compute_dtype=compute_dtype, cp_mesh=cp_mesh, mesh=mesh)
        return chunked_lm_cross_entropy_sum(
            hidden, params_t["embed"], mb["labels"],
            num_chunks=args.loss_chunks, mesh=ce_mesh)

    def save_hook(step, params_t, opt_st, final, ckpt=None):
        path = args.output_path
        if not final:
            root, ext = os.path.splitext(path)
            path = f"{root}_step{step}{ext}"
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        if args.opt_offload:
            t0 = time.perf_counter()
            # the f32 MASTER is the real model (params_t is the bf16
            # compute copy); the sidecar carries step + m/v only. The
            # master/m/v tiers already live in host RAM — "snapshot"
            # here is the batched pull of the few device-resident
            # leaves plus reshaping, still the only blocking work
            from mobilefinetuner_tpu.optim import opt_offload as oo
            model_h = oo.master_to_params(opt_st, plan, params_t)
            side_h = async_ckpt.snapshot(
                {"step": opt_st["step"], "m": opt_st["m"],
                 "v": opt_st["v"]})
            snap_ms = (time.perf_counter() - t0) * 1000.0

            def write():
                save_gemma3(path, model_h)
                adam_mod.save_state(path + ".opt", side_h, tc.adam(),
                                    extra_metadata={
                                        "loop_step": str(step)})
                common.record_ckpt_files(args, args.output_path, step,
                                         [path, path + ".opt"])
                log.info(f"saved full model -> {path}")
                return [path, path + ".opt"]
        else:
            (params_h, opt_h), snap_ms = async_ckpt.timed_snapshot(
                (params_t, opt_st))

            def write():
                save_gemma3(path, params_h)
                adam_mod.save_state(path + ".opt", opt_h, tc.adam(),
                                    extra_metadata={
                                        "loop_step": str(step)})
                common.record_ckpt_files(args, args.output_path, step,
                                         [path, path + ".opt"])
                log.info(f"saved full model -> {path}")
                return [path, path + ".opt"]

        async_ckpt.submit(ckpt, step, write, final=final,
                          snapshot_ms=snap_ms)

    # in-loop MFU from the shared estimator (core/telemetry.py)
    from mobilefinetuner_tpu.core.telemetry import transformer_flops
    flops = transformer_flops(
        sum(int(x.size) for x in jax.tree.leaves(params)), 0,
        args.batch_size * tc.grad_accum_steps, args.seq_len,
        config.num_hidden_layers, config.num_attention_heads,
        config.head_dim, full_ft=True)

    common.run_training(
        args, trainable=params, frozen=None, loss_fn=loss_fn,
        nll_fn=nll_fn, train_ds=train_ds, valid_ds=valid_ds,
        total_steps=total_steps, tc=tc, mask=None, start_step=start_step,
        opt_state=opt_state, save_hook=save_hook, mesh=mesh,
        replicate_trainable=False, step_builder=step_builder,
        flops_per_step=flops,
        # rollback rides the plain-Adam path only: the opt-offload
        # builder owns its own host-tier placements, which the generic
        # rollback re-placement cannot reproduce — its recovery story
        # stays process-level restart (--resume_from)
        load_hook=(None if args.opt_offload
                   else common.make_rollback_loader(
                       tc, None,
                       lambda p: _load_full_gemma(p, config))),
        ckpt_path="" if args.opt_offload else args.output_path,
        # memory-admission ladder (DESIGN.md §21): remat + accum_x2
        # rungs only (loss_fn reads args.remat at trace time; the
        # accum rung re-invokes step_builder with the doubled count —
        # the opt-offload builder takes the same (loss_fn, tc) surface
        # as make_train_step). No frozen base, so no offload rung.
        degrade_builders=None)
    return 0


def _load_full_gemma(path, config):
    """Rollback inverse of the plain-Adam save_hook: HF-keyed Gemma-3
    file -> stacked host param tree."""
    from mobilefinetuner_tpu.io.safetensors_io import SafeTensorsReader
    return gemma3_params_from_hf(
        SafeTensorsReader(path).load_all(promote_to_f32=True), config)


if __name__ == "__main__":
    sys.exit(main())
