"""WikiText-2 perplexity evaluation CLI (GPT-2 AND Gemma-3).

TPU-native rebuild of the reference `eval_ppl` binary
(reference: gpt2_lora_finetune/eval_ppl.cpp): load the model (+ optional
LoRA adapter, merged into the base weights or applied dynamically,
eval_ppl.cpp:110-127), run the split with token-weighted mean NLL
(mean_nll = Σ(loss·tokens)/Σtokens; ppl = exp(mean_nll),
eval_ppl.cpp:157-200), JSONL progress + final record, unmerge after
(eval_ppl.cpp:222 — moot here: merge is functional, the base tree is never
mutated). Goes beyond the reference by also covering Gemma-3 adapters
(merge via merge_gemma3 or dynamic), with the 262k-vocab head evaluated
through the chunked CE so [B,S,262144] fp32 logits are never materialized
— the reference has no Gemma eval binary at all.

Usage:
  python -m mobilefinetuner_tpu.cli.eval_ppl \
      --pretrained_dir /path/gpt2-or-gemma --data_root /path/wikitext-2 \
      --split valid [--lora_path adapter.safetensors --lora_merge]
The model family is auto-detected from config.json (model_type /
text_config); force with --family.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.core.compile_cache import enable_compile_cache
from mobilefinetuner_tpu.core.logging import JSONLWriter, get_logger
from mobilefinetuner_tpu.data.prefetch import Prefetcher
from mobilefinetuner_tpu.data.wikitext2 import WT2Config, WikiText2Dataset
from mobilefinetuner_tpu.models.family import apply_adapter, load_family
from mobilefinetuner_tpu.ops.loss import (chunked_lm_cross_entropy_sum,
                                          lm_cross_entropy_sum,
                                          perplexity_from_loss)

log = get_logger()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="eval_ppl", description="WikiText-2 perplexity (TPU)")
    p.add_argument("--pretrained_dir", required=True)
    p.add_argument("--data_root", required=True)
    p.add_argument("--split", default="valid", choices=["valid", "test"])
    p.add_argument("--lora_path", default="")
    p.add_argument("--lora_merge", action="store_true",
                   help="fold the adapter into base weights instead of "
                        "applying it dynamically")
    p.add_argument("--batch_size", type=int, default=2)
    p.add_argument("--seq_len", type=int, default=1024)
    p.add_argument("--stride", type=int, default=0,
                   help="chunk stride; 0 = seq_len (no overlap, the "
                        "reference default stride=-1)")
    p.add_argument("--max_batches", type=int, default=0)
    p.add_argument("--log_every", type=int, default=20)
    p.add_argument("--out", default="", help="JSONL output path")
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="float32")
    p.add_argument("--family", choices=["auto", "gpt2", "gemma"],
                   default="auto")
    p.add_argument("--loss_chunks", type=int, default=8,
                   help="sequence chunks for Gemma's 262k-vocab chunked "
                        "CE")
    p.add_argument("--prefetch", type=int, default=2,
                   help="async input pipeline depth (background batch "
                        "producer + device-placement lookahead, "
                        "data/prefetch.py); 0 = synchronous")
    p.add_argument("--telemetry_out", default="",
                   help="JSONL run-telemetry stream (core/telemetry.py): "
                        "run_start manifest + eval progress + run_end")
    p.add_argument("--run_registry", default="",
                   help="append-only run registry stream (core/"
                        "run_registry.py): one crash-safe record per "
                        "eval run; default $MFT_RUN_REGISTRY, empty = "
                        "off")
    from mobilefinetuner_tpu.cli.common import add_mem_flags
    add_mem_flags(p)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    compute_dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    b = load_family(args.pretrained_dir, args.family)
    fam = b.family
    lora = apply_adapter(b, args.lora_path, args.lora_merge)
    config, params, tok = b.config, b.params, b.tok
    # the eval stream carries no sequence-start token; GPT-2's pad id is
    # its eos id, which is also WikiText2Dataset's default
    encode, eos_id, pad_id = fam.encode_plain(tok), tok.eos_id, tok.pad_id

    @jax.jit
    def step(params, lora, batch):
        kw = dict(attention_mask=batch["attention_mask"], lora=lora,
                  compute_dtype=compute_dtype)
        if fam.full_logits_eval:
            logits = fam.model.forward(config, params, batch["input_ids"],
                                       **kw)
            return lm_cross_entropy_sum(logits, batch["labels"])
        hidden = fam.model.hidden_states(config, params,
                                         batch["input_ids"], **kw)
        # an lm_head adapter entry rides the chunked CE as lora_head
        # (hidden_states only applies per-layer sites; dropping it here
        # would score a different model than the one trained —
        # DESIGN.md §17)
        head_entry = (None if lora is None
                      else lora["blocks"].get("lm_head"))
        return chunked_lm_cross_entropy_sum(
            hidden, params[fam.head_key], batch["labels"],
            num_chunks=args.loss_chunks, lora_head=head_entry)

    max_pos = b.max_len

    # Commit the weights to the device ONCE: checkpoint loading yields
    # host numpy arrays, and leaving them as jit arguments re-transfers
    # the full model host->HBM every batch.
    params = jax.device_put(params)
    if lora is not None:
        lora = jax.device_put(lora)

    args.seq_len = min(args.seq_len, max_pos)
    wt2 = WT2Config(seq_len=args.seq_len, batch_size=args.batch_size,
                    stride=args.stride or None, shuffle=False,
                    drop_last=False)
    ds = WikiText2Dataset(args.data_root, args.split, wt2, encode,
                          eos_id, pad_id=pad_id)

    jsonl = JSONLWriter(args.out) if args.out else None
    from mobilefinetuner_tpu.core.telemetry import Telemetry, run_manifest
    # fleet-aware: each process writes its own host-stamped shard
    # (coordinator at the given path; merge with tools/fleet_report.py)
    tel = Telemetry.for_process(args.telemetry_out)
    tel.emit("run_start", **run_manifest(vars(args)))
    # run registry (core/run_registry.py): a crash between here and
    # finalize settles to "interrupted" on the next registry open
    from mobilefinetuner_tpu.core.run_registry import RunRegistry
    _reg = RunRegistry.from_args(args)
    run_rec = _reg.begin(
        "eval", "eval_ppl", config=vars(args),
        platform=jax.devices()[0].platform,
        artifacts=[p for p in (tel.path, args.out) if p],
        telemetry=tel) if _reg else None
    # memory-admission preflight (DESIGN.md §21): AOT-compile the
    # dominant full-shape batch and check it against device capacity
    # BEFORE the data loop — the same mem_check the train path emits,
    # minus the degradation ladder (--on_oom_risk fail raises the
    # named MemoryAdmissionError here; degrade/warn proceed with a
    # warning). The compiled executable then serves every full-shape
    # batch below, so the preflight compile IS the run's compile — the
    # short epoch tail (drop_last=False) falls back to the jit cache.
    from mobilefinetuner_tpu.cli.common import preflight_eval_compile
    full_shape = (args.batch_size, args.seq_len)
    spec = {"input_ids": jax.ShapeDtypeStruct(full_shape, jnp.int32),
            "attention_mask": jax.ShapeDtypeStruct(full_shape,
                                                   jnp.float32),
            "labels": jax.ShapeDtypeStruct(full_shape, jnp.int32)}
    compiled_step = preflight_eval_compile(
        lambda: step.lower(params, lora, spec).compile(), args, tel,
        what="eval_ppl compiled step")

    def run_step(batch):
        if batch["input_ids"].shape == full_shape:
            return compiled_step(params, lora, batch)
        return step(params, lora, batch)
    # device-side accumulation: per-batch float(s)/int(c) forced a full
    # device sync per eval step — the sums stay on device (tiny adds on
    # the async dispatch queue) and come to host only at progress-log
    # boundaries and once after the loop. Batches arrive via the async
    # producer + placement lookahead (tokenization and the host->device
    # transfer overlap the previous batch's compute; --prefetch 0 is the
    # synchronous reference path).
    total, count, n_done = None, None, 0
    t0 = time.time()
    source = ds.epoch(0)
    if args.max_batches:
        source = itertools.islice(source, args.max_batches)
    with Prefetcher(source, depth=args.prefetch,
                    place_fn=jax.device_put,
                    rss_limit_mb=args.prefetch_rss_mb) as batches:
        for n, batch in enumerate(batches):
            s, c = run_step(batch)
            total = s if total is None else total + s
            count = c if count is None else count + c
            n_done = n + 1
            if args.log_every and (n + 1) % args.log_every == 0:
                t, k = jax.device_get((total, count))
                mean = float(t) / max(int(k), 1)
                log.info(f"batch {n + 1}/{ds.num_batches()} "
                         f"nll={mean:.4f} "
                         f"ppl={perplexity_from_loss(mean):.2f}")
                if jsonl:
                    jsonl.write({"type": "progress", "batch": n + 1,
                                 "nll": mean,
                                 "ppl": perplexity_from_loss(mean)})
                tel.emit("eval", step=n + 1, loss=mean,
                         ppl=perplexity_from_loss(mean), tokens=int(k))
    if n_done:
        total, count = jax.device_get((total, count))
    total, count = (float(total), int(count)) if n_done else (0.0, 0)
    mean = total / max(count, 1)
    ppl = perplexity_from_loss(mean)
    record = {"type": "final", "family": fam.name, "split": args.split,
              "nll": mean, "ppl": ppl, "tokens": count,
              "seq_len": args.seq_len, "lora": bool(args.lora_path),
              "merged": args.lora_merge,
              "seconds": round(time.time() - t0, 1)}
    log.info(f"{args.split} ppl={ppl:.3f} nll={mean:.4f} ({count} tokens)")
    if jsonl:
        jsonl.write(record)
    tel.emit("eval", step=n_done, loss=mean, ppl=ppl, tokens=count)
    # finalize before run_end so the mirrored `run` end event lands in
    # the stream while run_end stays the stream's LAST event
    if run_rec is not None:
        run_rec.finalize("ok")
    # goodput is None: the eval CLIs have no metered phase loop
    tel.emit("run_end", steps=n_done,
             wall_s=round(time.time() - t0, 3), exit="ok", goodput=None)
    tel.close()
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
