"""GPT-2 full fine-tuning CLI (all 124M+ params trainable).

TPU-native rebuild of the reference `gpt2_full_finetune` binary
(reference: gpt2_full_finetune/main.cpp — same skeleton as the LoRA CLI but
every parameter is trainable :318-322, the full model is saved as
safetensors :156-237, and resume reloads that file). Adam state is
FSDP-sharded with the params (the m/v trees inherit the param shardings),
which is exactly ZeRO's optimizer-state partitioning — the reference's
single-device sharder has no analog for this.

Usage (tiny smoke):
  python -m mobilefinetuner_tpu.cli.gpt2_full_finetune \
      --pretrained_dir /path/gpt2 --data_dir /path/wikitext-2 \
      --steps 10 --output_path out/model.safetensors
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
from mobilefinetuner_tpu.io.checkpoints import (gpt2_params_from_hf,
                                                load_gpt2, save_gpt2)
from mobilefinetuner_tpu.models import gpt2
from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
from mobilefinetuner_tpu.optim import adam as adam_mod
from mobilefinetuner_tpu.parallel.mesh import shard_params

log = get_logger()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="gpt2_full_finetune",
        description="GPT-2 full fine-tuning on WikiText-2 (TPU)")
    p.add_argument("--data_dir", required=True)
    p.add_argument("--pretrained_dir", required=True)
    p.add_argument("--output_path", default="gpt2_full_ft.safetensors")
    p.add_argument("--resume_from", default="",
                   help="full-model safetensors to resume from")
    p.add_argument("--eval_out", default="")
    common.add_train_flags(p, lr=5e-5, seq_len=128, batch_size=1)
    common.add_pm_flags(p)
    common.add_mesh_flags(p)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    common.enable_compile_cache()

    config, params = load_gpt2(args.pretrained_dir)
    if args.no_model_dropout:
        config = dataclasses.replace(config, embd_pdrop=0.0,
                                     resid_pdrop=0.0, attn_pdrop=0.0)
    if args.resume_from:
        # verify-on-load with lineage fallback (DESIGN.md §20)
        common.resolve_resume_from(args)
        params = gpt2_params_from_hf(
            common.load_full_resume(args.resume_from), config)
        log.info(f"resumed full model from {args.resume_from}")
    if args.seq_len > config.n_positions:
        args.seq_len = config.n_positions

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
    log.info(f"full FT: {gpt2.param_count(params):,} trainable params, "
             f"{total_steps} steps")

    opt_state, start_step = common.maybe_resume_opt_state(
        args, params, tc, None)

    # Full FT: params themselves are the trainable tree — FSDP-shard them
    # (and thus Adam m/v) over the mesh; no host offload of trainables.
    mesh, cp_mesh = common.build_mesh(args)
    config = common.with_attention_impl(args, config)
    if cp_mesh is not None and config.attn_pdrop > 0:
        log.warning(f"attn_pdrop={config.attn_pdrop} is unsupported by "
                    f"ring attention; attention-probs dropout is OFF in "
                    f"sequence-parallel mode (--no_model_dropout "
                    f"silences this)")
    # mesh-shape-agnostic placement (elastic resume, DESIGN.md §18): the
    # checkpoint + sidecar hold FULL host tensors, so whatever mesh THIS
    # run built re-shards them here — a save at (1,N) resumes at (1,M)
    # with the Adam m/v landing on the same FSDP specs as the params
    # (shard_params is multi-host safe, unlike a raw device_put).
    params = shard_params(params, mesh)
    if opt_state is not None:
        opt_state = common.place_opt_state(opt_state, mesh)
    compute_dtype = common.compute_dtype_from_args(args)
    model_pdrop = max(config.embd_pdrop, config.resid_pdrop,
                      config.attn_pdrop)
    base_rng = (jax.random.PRNGKey(args.seed + 1)
                if model_pdrop > 0 else None)

    def loss_fn(params_t, _unused, mb):
        rng = mb["dropout_rng"][0] if "dropout_rng" in mb else None
        logits = gpt2.forward(config, params_t, mb["input_ids"],
                              attention_mask=mb["attention_mask"],
                              compute_dtype=compute_dtype, remat=args.remat,
                              dropout_rng=rng, cp_mesh=cp_mesh,
                              mesh=mesh)
        return lm_cross_entropy_sum(logits, mb["labels"])

    def nll_fn(params_t, _unused, mb):
        logits = gpt2.forward(config, params_t, mb["input_ids"],
                              attention_mask=mb["attention_mask"],
                              compute_dtype=compute_dtype,
                              cp_mesh=cp_mesh, mesh=mesh)
        return lm_cross_entropy_sum(logits, mb["labels"])

    def save_hook(step, params_t, opt_st, final, ckpt=None):
        path = args.output_path
        if not final:
            root, ext = os.path.splitext(path)
            path = f"{root}_step{step}{ext}"
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        # full-FT trees are the expensive case: the batched snapshot is
        # the loop's only stall; the HF key-mapping + write of params
        # AND the 2x-params .opt sidecar happen off-loop
        (params_h, opt_h), snap_ms = async_ckpt.timed_snapshot(
            (params_t, opt_st))

        def write():
            save_gpt2(path, params_h)
            adam_mod.save_state(path + ".opt", opt_h, tc.adam(),
                                extra_metadata={"loop_step": str(step)})
            common.record_ckpt_files(args, args.output_path, step,
                                     [path, path + ".opt"])
            log.info(f"saved full model -> {path}")
            return [path, path + ".opt"]

        async_ckpt.submit(ckpt, step, write, final=final,
                          snapshot_ms=snap_ms)

    def load_trainable(path):
        """Rollback inverse of save_hook: HF-keyed full model file ->
        the stacked host param tree (mesh placement happens in
        run_training's rollback, reusing the elastic-resume rule)."""
        from mobilefinetuner_tpu.io.safetensors_io import SafeTensorsReader
        return gpt2_params_from_hf(
            SafeTensorsReader(path).load_all(promote_to_f32=True), config)

    # in-loop MFU from the shared estimator (core/telemetry.py)
    from mobilefinetuner_tpu.core.telemetry import transformer_flops
    flops = transformer_flops(
        gpt2.param_count(params), 0,
        args.batch_size * tc.grad_accum_steps, args.seq_len,
        config.n_layer, config.n_head, config.head_dim, full_ft=True)

    common.run_training(
        args, trainable=params, frozen=None, loss_fn=loss_fn,
        nll_fn=nll_fn, train_ds=train_ds, valid_ds=valid_ds,
        total_steps=total_steps, tc=tc, mask=None, start_step=start_step,
        opt_state=opt_state, save_hook=save_hook, mesh=mesh,
        replicate_trainable=False, dropout_rng=base_rng,
        flops_per_step=flops,
        load_hook=common.make_rollback_loader(tc, None, load_trainable),
        ckpt_path=args.output_path,
        # memory-admission ladder (DESIGN.md §21): full FT gets the
        # remat and accum_x2 rungs (loss_fn reads args.remat at trace
        # time; accum doubles inside run_training at constant global
        # batch). No offload rung — the TRAINABLE tree is the HBM cost
        # here and offload targets frozen bases only.
        degrade_builders=None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
