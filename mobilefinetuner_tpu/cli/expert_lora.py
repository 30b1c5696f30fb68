"""What the LoRA CLIs of the sparse-expert and hybrid families share: one
trainer entry point over `cli/common.run_training`, parametrised by a
record that says what a family is (cli/train_lora_afmoe.py,
cli/train_lora_ling.py, cli/train_lora_xing4.py and
cli/train_lora_phi4flash.py each hold one and nothing else; a family
without experts selects nothing by `--expert_parallel 1 --expert_rank
0`).

Common to them: the flags; `--expert_parallel N --expert_rank r`
describing the deployment this process is one rank of (it holds experts
[r·E/N, (r+1)·E/N); they select no code path: 1/0 holds every expert); a
checkpoint published in bf16 that stays bf16 from disk to the MXU; the
chunked cross-entropy over the rows of the head held here, in
`loss_chunks` sequence chunks; `step_stats`
carrying the model's own device counters (`moe_assign_here`,
`moe_load_max_over_mean`, `moe_dropped`, and whatever else the family's
`hidden_states(..., with_aux=True)` counts); adapters saved in the
native format and, on request, as an HF-PEFT directory.

What a family's `hidden_states(..., with_aux=True)` hands back: the
final-norm hidden states [B, S, H] that the shared head scores against
the labels, and `aux` with "counters" ({"sum": {...}, "max": {...}},
device scalars). Beyond that ONE hidden state a family may hand back a
second, `aux["second"] = {"hidden": [B, S, H], "shift": k, "weight": w,
"name": str}` (a multi-token-prediction module's): the same head and
chunked cross-entropy score it against the labels moved k further
(position i then predicts label i + 1 + k), under the device scope
`name`, and the step's loss is L_main + w · L_second, each a mean over
its own valid positions; `step_stats` then carries `loss_main` and
`loss_<name>` beside `loss`. Evaluation (`nll_fn`) reports the main
loss only.

The tokenizer is read from `vocab.json` / `merges.txt` (byte-level BPE);
the published families' own tokenizer file formats are not implemented.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.cli import common
from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.data.tokenizer_bpe import GPT2BPETokenizer
from mobilefinetuner_tpu.data.wikitext2 import WT2Config, WikiText2Dataset
from mobilefinetuner_tpu.io import async_ckpt
from mobilefinetuner_tpu.lora import peft_io
from mobilefinetuner_tpu.lora.lora import (LoRASpec, num_trainable,
                                           trainable_mask)
from mobilefinetuner_tpu.ops.loss import (IGNORE_INDEX,
                                          chunked_lm_cross_entropy_sum)
from mobilefinetuner_tpu.optim import adam as adam_mod

log = get_logger()
# sequence chunks of the cross-entropy: at least LOSS_CHUNKS, and as many
# more (doubling) as keep one chunk's float32 logits [B, S/c, V] within
# LOSS_CHUNK_BYTES (loss_chunks)
LOSS_CHUNKS = 8
LOSS_CHUNK_BYTES = 1 << 29


def loss_chunks(B: int, S: int, V: int) -> int:
    """The number of sequence chunks the head and the cross-entropy run
    in for a [B, S] batch over V rows: the least c >= LOSS_CHUNKS, doubled
    from it, whose [B, ceil(S / c), V] float32 logits fit LOSS_CHUNK_BYTES
    (and at most S). One chunk's logits and their gradient are the loss's
    peak, so a 200k-row head at 16k tokens takes 32 where the families
    with smaller heads keep 8."""
    c = LOSS_CHUNKS
    while B * -(-S // c) * V * 4 > LOSS_CHUNK_BYTES and 2 * c <= S:
        c *= 2
    return c


@dataclasses.dataclass(frozen=True)
class ExpertFamily:
    """A sparse-expert family on the LoRA trainer."""
    name: str                       # PEFT family name, log prefix
    prog: str
    title: str
    config_cls: Any                 # .from_pretrained, .with_expert_parallel
    load: Callable                  # (dir, config=) -> (config, params)
    # .hidden_states (one hidden state, or a second in aux["second"]:
    # the module docstring), .matmul_params_per_token
    model: Any
    targets: Dict[str, Callable]    # LoRA target -> dims(config)
    init_lora: Callable             # (config, spec, key) -> tree
    default_out: str
    describe: Callable              # config -> one log line
    # args, config -> config: apply --attention_impl and stamp what every
    # `auto` resolved to into args (the run_start manifest)
    resolve: Callable = common.with_attention_impl
    # config -> {target: layer index of each stack position}, where a
    # target's stack does not run over all layers
    peft_layer_ids: Optional[Callable] = None
    # parser -> None: the family's own flags (`resolve` reads them)
    add_flags: Optional[Callable] = None
    # frozen params -> the head's [V, H] rows (a tied head reads the
    # embedding)
    head: Callable = lambda p: p["lm_head"]


def build_parser(fam: ExpertFamily) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog=fam.prog, description=f"{fam.title} LoRA fine-tuning (TPU)")
    p.add_argument("--data_dir", required=True,
                   help="WikiText-2 directory (wiki.{train,valid}.tokens)")
    p.add_argument("--pretrained_dir", required=True,
                   help=f"HF {fam.name} checkpoint dir (config.json, "
                        "safetensors, vocab.json, merges.txt)")
    p.add_argument("--lora_out", default=fam.default_out)
    p.add_argument("--resume_from", default="",
                   help="adapter safetensors to resume from")
    p.add_argument("--eval_out", default="", help="eval JSONL output path")
    p.add_argument("--rank", type=int, default=8)
    p.add_argument("--alpha", type=float, default=16.0)
    p.add_argument("--lora_dropout", type=float, default=0.0)
    p.add_argument("--lora_targets", default=",".join(fam.targets),
                   help="comma list of " + ",".join(fam.targets))
    p.add_argument("--peft_export_dir", default="",
                   help="also export an HF-PEFT adapter directory")
    p.add_argument("--expert_parallel", type=int, default=1,
                   help="ranks that share each expert layer (the "
                        "deployment this process is one rank of)")
    p.add_argument("--expert_rank", type=int, default=0,
                   help="this process's rank among them: it holds "
                        "experts [r*E/N, (r+1)*E/N)")
    if fam.add_flags:
        fam.add_flags(p)
    common.add_train_flags(p, lr=1e-4, seq_len=256, batch_size=1)
    common.add_pm_flags(p)
    common.add_shard_flags(p)
    common.add_mesh_flags(p)
    return p


def add_second_loss(s, n, counters, second, head, labels, accum: int = 1):
    """The step's loss sum and counters with a family's second hidden
    state scored (module docstring): returns (s', counters') with
    s' / n = L_main + weight · L_second, each loss a mean over its own
    valid positions, and `loss_main` / `loss_<name>` among the summed
    counters (÷ accum: the micro-batches' values add up to their mean)."""
    k = second["shift"]
    with jax.named_scope(second["name"]):
        moved = jnp.concatenate(
            [labels[:, k:], jnp.full_like(labels[:, :k], IGNORE_INDEX)], 1)
        s2, n2 = chunked_lm_cross_entropy_sum(
            second["hidden"], head, moved,
            num_chunks=loss_chunks(*labels.shape, head.shape[0]))
    nf = jnp.maximum(n.astype(jnp.float32), 1.0)
    n2f = jnp.maximum(n2.astype(jnp.float32), 1.0)
    counters = {**counters, "sum": {
        **counters.get("sum", {}), "loss_main": s / nf / accum,
        f"loss_{second['name']}": s2 / n2f / accum}}
    return s + second["weight"] * s2 * (nf / n2f), counters


def make_nll(fam: ExpertFamily, args, config, fetch_fn, compute_dtype,
             accum: int):
    """The step's loss, nll(lora, frozen, micro-batch, train) -> (sum,
    count, counters): the family's hidden states as `args` set them up,
    the head's chunked cross-entropy and, in training, a second hidden
    state's loss (module docstring). `main` trains on it; a check of the
    trainer builds the same step from it."""
    def nll(lora_t, frozen, mb, train: bool):
        rng = mb["dropout_rng"][0] if train and "dropout_rng" in mb \
            else None
        p = fetch_fn(frozen)
        hidden, aux = fam.model.hidden_states(
            config, p, mb["input_ids"],
            attention_mask=mb["attention_mask"], lora=lora_t,
            compute_dtype=compute_dtype, remat=train and args.remat,
            lora_dropout=args.lora_dropout if train else 0.0,
            dropout_rng=rng, lora_impl=args.lora_impl, with_aux=True)
        head = fam.head(p)
        s, n = chunked_lm_cross_entropy_sum(
            hidden, head, mb["labels"],
            num_chunks=loss_chunks(*mb["labels"].shape, head.shape[0]))
        counters = aux["counters"]
        second = aux.get("second") if train else None
        if second is not None:
            s, counters = add_second_loss(
                s, n, counters, second, head, mb["labels"], accum)
        return s, n, counters

    return nll


def main(fam: ExpertFamily, argv=None) -> int:
    args = build_parser(fam).parse_args(argv)
    common.enable_compile_cache()

    config = fam.config_cls.from_pretrained(args.pretrained_dir) \
        .with_expert_parallel(args.expert_parallel, args.expert_rank)
    config, params = fam.load(args.pretrained_dir, config=config)
    log.info(fam.describe(config))

    if args.resume_from:
        common.resolve_resume_from(args)
        lora, spec = peft_io.load_adapter(args.resume_from)
        log.info(f"resumed adapter: r={spec.rank} alpha={spec.alpha} "
                 f"targets={spec.targets}")
    else:
        spec = LoRASpec(rank=args.rank, alpha=args.alpha,
                        dropout=args.lora_dropout,
                        targets=[t for t in args.lora_targets.split(",")
                                 if t], init="peft")
        lora = fam.init_lora(config, spec, jax.random.PRNGKey(args.seed))
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

    mesh, _ = common.build_mesh(args)
    if mesh.size > 1:
        raise SystemExit(
            f"{fam.prog} runs one expert-parallel rank on one device: "
            "the exchange over an expert axis is not implemented "
            "(ROADMAP R1); describe the deployment with --expert_parallel")
    config = fam.resolve(args, config)
    params, fetch_fn, _ = common.setup_frozen_params(args, params, mesh)
    compute_dtype = common.compute_dtype_from_args(args)
    base_rng = (jax.random.PRNGKey(args.seed + 1)
                if args.lora_dropout > 0 else None)
    common.log_lora_impl_resolution(
        args, {t: fam.targets[t](config) for t in spec.targets or []},
        spec.rank, compute_dtype)

    nll = make_nll(fam, args, config, fetch_fn, compute_dtype,
                   tc.grad_accum_steps)

    def loss_fn(lora_t, frozen, mb):
        return nll(lora_t, frozen, mb, True)

    def nll_fn(lora_t, frozen, mb):
        """Evaluation: the MAIN loss only (a second hidden state is a
        training objective, not what perplexity is reported on)."""
        return nll(lora_t, frozen, mb, False)[:2]

    def save_hook(step, lora_t, opt_st, final, ckpt=None):
        path = args.lora_out
        if not final:
            root, ext = os.path.splitext(path)
            path = f"{root}_step{step}{ext}"
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        (lora_h, opt_h), snap_ms = async_ckpt.timed_snapshot(
            (lora_t, opt_st))

        def write():
            peft_io.save_adapter(path, lora_h, spec)
            adam_mod.save_state(path + ".opt", opt_h, tc.adam(),
                                extra_metadata={"loop_step": str(step)})
            common.record_ckpt_files(args, args.lora_out, step,
                                     [path, path + ".opt"])
            log.info(f"saved adapter -> {path}")
            if final and args.peft_export_dir:
                peft_io.export_peft(
                    args.peft_export_dir, lora_h, spec, fam.name,
                    base_model_name=args.pretrained_dir,
                    layer_ids=fam.peft_layer_ids and
                    fam.peft_layer_ids(config))
                log.info(f"PEFT export -> {args.peft_export_dir}")
            return [path, path + ".opt"]

        async_ckpt.submit(ckpt, step, write, final=final,
                          snapshot_ms=snap_ms)

    # in-loop MFU: the shared estimator over the matmul parameters a token
    # is EXPECTED to meet here (uniform routing over the held experts)
    from mobilefinetuner_tpu.core.telemetry import transformer_flops
    flops = transformer_flops(
        sum(int(x.size) for x in jax.tree.leaves(lora)),
        fam.model.matmul_params_per_token(config),
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
        ckpt_path=args.lora_out)
    return 0
