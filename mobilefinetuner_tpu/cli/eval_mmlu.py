"""MMLU evaluation CLI, GPT-2 and Gemma-3.

TPU-native rebuild of the reference `eval_mmlu` binary
(reference: gpt2_lora_finetune/eval_mmlu.cpp + mmlu/mmlu_runner.{h,cpp}):
load a model (+ optional adapter, merged or dynamic), evaluate 4-choice
accuracy with k-shot prompts, report per-subject + macro/micro. The
reference binary is GPT-2-only; like eval_ppl, this CLI auto-detects the
family from config.json so the Gemma track has the same eval story.

Variable-length prompts vs XLA's static shapes: prompts are right-padded to
power-of-two length buckets, so the whole eval compiles a handful of
programs instead of one per length. The last REAL token's logits are
selected by index (padding never shifts the prediction), and only that one
position is projected through the lm_head — materializing [1, S, V] logits
would cost ~1 MB/token on Gemma's 262k vocab for values that are discarded.

Usage:
  python -m mobilefinetuner_tpu.cli.eval_mmlu \
      --pretrained_dir /path/gpt2-or-gemma --mmlu_root /path/mmlu \
      --split test [--fewshot 5] [--lora_path adapter.safetensors \
      --lora_merge]
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from mobilefinetuner_tpu.core.compile_cache import enable_compile_cache
from mobilefinetuner_tpu.core.logging import JSONLWriter, get_logger
from mobilefinetuner_tpu.eval import mmlu
from mobilefinetuner_tpu.models.family import apply_adapter, load_family

log = get_logger()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="eval_mmlu", description="MMLU 4-choice accuracy (TPU)")
    p.add_argument("--pretrained_dir", required=True)
    p.add_argument("--family", choices=["auto", "gpt2", "gemma"],
                   default="auto")
    p.add_argument("--mmlu_root", required=True,
                   help="dir containing <split>/ with per-subject CSVs")
    p.add_argument("--split", default="test")
    p.add_argument("--fewshot", type=int, default=0)
    p.add_argument("--lora_path", default="")
    p.add_argument("--lora_merge", action="store_true")
    p.add_argument("--max_items", type=int, default=0,
                   help="cap items per subject (debug)")
    p.add_argument("--out", default="", help="JSON report path")
    p.add_argument("--synthetic", action="store_true",
                   help="stamp the report synthetic=true: the model "
                        "weights and/or MMLU data are synthetic (harness "
                        "proof, not a real evaluation) — keeps artifacts "
                        "self-describing")
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="float32")
    p.add_argument("--telemetry_out", default="",
                   help="JSONL run-telemetry stream (core/telemetry.py)")
    p.add_argument("--run_registry", default="",
                   help="append-only run registry stream (core/"
                        "run_registry.py): one crash-safe record per "
                        "eval run; default $MFT_RUN_REGISTRY, empty = "
                        "off")
    p.add_argument("--eval_batch", type=int, default=16,
                   help="items per forward (bucketed batching; the "
                        "reference runs per-item — on the MXU that "
                        "leaves the batch dimension idle)")
    from mobilefinetuner_tpu.cli.common import add_mem_flags
    add_mem_flags(p)
    return p


def setup_family(args):
    """(hidden_fn, head_key, compute_dtype, tok, letter_encode, max_len,
    params, lora): family dispatch via models/family.py. hidden_fn(params,
    lora, ids) -> [1, S, E] final-norm hidden states; params[head_key] is
    the (tied) lm_head weight [V, E]; letter_encode is the BOS-free
    encoder for the A-D letter-id lookup."""
    compute_dtype = jnp.bfloat16 if args.dtype == "bfloat16" \
        else jnp.float32
    b = load_family(args.pretrained_dir, args.family)
    lora = apply_adapter(b, args.lora_path, args.lora_merge)
    config, model = b.config, b.family.model

    def hidden_fn(params, lora, ids):
        return model.hidden_states(config, params, ids, lora=lora,
                                   compute_dtype=compute_dtype)

    # letter-id lookup must not see an auto-BOS (eval/mmlu.py)
    letter_encode = b.family.encode_plain(b.tok)
    # prompts are bucketed; cap at 4096 (far above MMLU prompt sizes,
    # far below Gemma's 32k max — a 32k zero-pad bucket would be waste)
    max_len = min(b.max_len, 4096)

    # Commit weights to device once; numpy-backed jit args would be
    # re-transferred per item (see eval_ppl.py).
    params = jax.device_put(b.params)
    lora = jax.device_put(lora) if lora is not None else None
    return (hidden_fn, b.family.head_key, compute_dtype, b.tok,
            letter_encode, max_len, params, lora)


def make_batched_logits_fn(hidden_fn, head_key, compute_dtype, params,
                           lora, worst_shape=None):
    """Batched bucketed last-REAL-token logits: (ids [B,S], last [B]) ->
    [B, V]. Only the selected positions go through the lm_head (a full
    [B, S, V] would cost ~1 MB/token on Gemma's 262k vocab).

    `worst_shape` (B, S): additionally AOT-compile that bucket and
    return the compiled executable (the round-16 admission preflight's
    subject). Calls matching it dispatch through the SAME executable —
    an AOT compile does not seed the jit cache, and without the routing
    the eval's most expensive bucket would compile twice."""

    @jax.jit
    def fwd(params, lora, ids, last_idx):
        h = hidden_fn(params, lora, ids)            # [B, S, E]
        head = params[head_key].astype(compute_dtype)
        rows = h[jnp.arange(h.shape[0]), last_idx]  # [B, E]
        logits = rows @ head.T                      # [B, V]
        # hidden_fn applies only the per-layer sites; an lm_head
        # adapter entry must land at this head projection too, or the
        # scored model differs from the trained one (DESIGN.md §17)
        if lora is not None and "lm_head" in lora.get("blocks", {}):
            from mobilefinetuner_tpu.models.lora_apply import maybe_lora
            logits = maybe_lora(logits, rows, lora["blocks"]["lm_head"])
        return logits

    compiled_worst = None
    if worst_shape is not None:
        compiled_worst = fwd.lower(
            params, lora,
            jax.ShapeDtypeStruct(worst_shape, jnp.int32),
            jax.ShapeDtypeStruct((worst_shape[0],), jnp.int32)).compile()

    def logits_fn(ids: np.ndarray, last: np.ndarray) -> np.ndarray:
        if compiled_worst is not None and ids.shape == worst_shape:
            return np.asarray(compiled_worst(
                params, lora, jnp.asarray(ids), jnp.asarray(last)))
        return np.asarray(fwd(params, lora, jnp.asarray(ids),
                              jnp.asarray(last)))

    return logits_fn, compiled_worst


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    import time as _time
    from mobilefinetuner_tpu.core.telemetry import Telemetry, run_manifest
    # fleet-aware: each process writes its own host-stamped shard
    # (coordinator at the given path; merge with tools/fleet_report.py)
    tel = Telemetry.for_process(getattr(args, "telemetry_out", ""))
    tel.emit("run_start", **run_manifest(vars(args)))
    # run registry (core/run_registry.py): a crash between here and
    # finalize settles to "interrupted" on the next registry open
    from mobilefinetuner_tpu.core.run_registry import RunRegistry
    _reg = RunRegistry.from_args(args)
    run_rec = _reg.begin(
        "eval", "eval_mmlu", config=vars(args),
        platform=jax.devices()[0].platform,
        artifacts=[p for p in (tel.path, args.out) if p],
        telemetry=tel) if _reg else None
    t0 = _time.time()
    (hidden_fn, head_key, compute_dtype, tok, letter_encode, max_len,
     params, lora) = setup_family(args)

    by_subject = mmlu.load_split(args.mmlu_root, args.split)
    n_items = sum(len(v) for v in by_subject.values())
    log.info(f"MMLU {args.split}: {len(by_subject)} subjects, "
             f"{n_items} items, fewshot={args.fewshot}")

    # memory-admission preflight (DESIGN.md §21) on the REAL worst-case
    # bucket: the work list is materialized (prompts encoded once, the
    # same list the runner consumes) and the largest bucket it actually
    # lands in — not the theoretical max_len cap — is what gets
    # compiled and checked. The runner always pads batches to
    # eval_batch rows, so the preflight's compiled executable SERVES
    # every batch of that bucket (logits_fn routes matching shapes
    # through it): the check costs no extra compile. Same flags and
    # mem_check event as the train path; no ladder (eval has no
    # levers), so --on_oom_risk fail raises before any item is scored
    # and degrade/warn proceed with a warning.
    from mobilefinetuner_tpu.cli.common import preflight_eval_compile
    work, _totals = mmlu.materialize_work(
        by_subject, tok.encode, fewshot_k=args.fewshot,
        max_items_per_subject=args.max_items, max_len=max_len)
    worst_S = max((mmlu.bucket_for(len(w[4]), max_len=max_len)
                   for w in work), default=max_len)
    B = max(args.eval_batch, 1)
    logits_fn, compiled_worst = preflight_eval_compile(
        lambda: make_batched_logits_fn(
            hidden_fn, head_key, compute_dtype, params, lora,
            worst_shape=(B, worst_S)),
        args, tel, what=f"eval_mmlu worst-case bucket [{B}, {worst_S}]",
        compiled_of=lambda out: out[1])
    done = [0]

    def progress(subject, i, n):
        done[0] += 1
        if done[0] % 50 == 0:
            log.info(f"{done[0]} items... ({subject} {i}/{n})")

    result = mmlu.evaluate_batched(
        by_subject, logits_fn, tok.encode, fewshot_k=args.fewshot,
        progress_fn=progress, max_items_per_subject=args.max_items,
        letter_encode_fn=letter_encode,
        batch_size=B, max_len=max_len, work=work)

    from mobilefinetuner_tpu.eval.mmlu_categories import category_rollup
    categories = category_rollup(result)
    report = {
        "split": args.split, "fewshot": args.fewshot,
        # provenance: a reader must be able to tell a harness proof on
        # synthetic weights/data from a real evaluation (round-3 verdict:
        # the r03 report lacked this and could be mistaken for real)
        "synthetic": bool(args.synthetic),
        "model_dir": args.pretrained_dir,
        "mmlu_root": args.mmlu_root,
        "macro_accuracy": round(result.macro, 4),
        "micro_accuracy": round(result.micro, 4),
        "total_items": result.total,
        "categories": categories,
        "per_subject": {r.subject: {"accuracy": round(r.accuracy, 4),
                                    "correct": r.correct, "total": r.total}
                        for r in result.per_subject},
    }
    for r in result.per_subject:
        log.info(f"  {r.subject}: {r.accuracy:.3f} "
                 f"({r.correct}/{r.total})")
    for cat, c in categories.items():
        log.info(f"  [{cat}] macro={c['macro_accuracy']:.3f} "
                 f"micro={c['micro_accuracy']:.3f} "
                 f"({c['correct']}/{c['total']}, {c['subjects']} subjects)")
    log.info(f"macro={result.macro:.4f} micro={result.micro:.4f}")
    if args.out:
        JSONLWriter(args.out).write(report)
    # an accuracy eval is not NLL-shaped: loss/ppl are null (the schema
    # allows it) and the real result rides as accuracy fields, which
    # telemetry_report renders
    tel.emit("eval", step=result.total, loss=None, ppl=None,
             tokens=result.total, macro_accuracy=report["macro_accuracy"],
             micro_accuracy=report["micro_accuracy"])
    # finalize before run_end so the mirrored `run` end event lands in
    # the stream while run_end stays the stream's LAST event
    if run_rec is not None:
        run_rec.finalize("ok")
    tel.emit("run_end", steps=result.total,
             wall_s=round(_time.time() - t0, 3), exit="ok", goodput=None)
    tel.close()
    print(json.dumps({"macro_accuracy": report["macro_accuracy"],
                      "micro_accuracy": report["micro_accuracy"],
                      "total_items": result.total,
                      "categories": {c: v["macro_accuracy"]
                                     for c, v in categories.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
