"""Text-generation CLI: KV-cached autoregressive sampling for GPT-2 and
Gemma-3, with optional LoRA adapters (merged or dynamic).

A capability the reference framework ships only as excluded legacy code
(reference: legacy/transformer/kv_cache.cpp + autoregressive_ops,
SURVEY.md §2.10 — "the active framework is training/eval only, no sampling
loop"). Here it is a first-class surface over models/generate.py: one
compiled program per (batch, prompt-length-bucket, max_new_tokens).

Usage:
  python -m mobilefinetuner_tpu.cli.generate \
      --pretrained_dir /path/gpt2 --prompt "The meaning of life is" \
      [--prompt ...] [--lora_path adapter.safetensors] \
      [--max_new_tokens 64] [--greedy | --temperature 0.8 --top_k 50 \
       --top_p 0.95] [--seed 0] [--dtype bfloat16] [--json]

Model family is auto-detected from config.json (model_type / presence of
Gemma fields); --model forces it.
"""

from __future__ import annotations

import argparse
import functools
import json as json_mod
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from mobilefinetuner_tpu.core.compile_cache import enable_compile_cache
from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.models.family import apply_adapter, load_family
from mobilefinetuner_tpu.models.generate import (GENERATE, SampleConfig,
                                                 left_pad)

log = get_logger()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "generate", description="KV-cached sampling (GPT-2 / Gemma-3)")
    p.add_argument("--pretrained_dir", required=True)
    p.add_argument("--model", choices=["auto", "gpt2", "gemma"],
                   default="auto")
    p.add_argument("--prompt", action="append", default=[],
                   help="repeatable; one generation per prompt")
    p.add_argument("--prompt_file", default="",
                   help="one prompt per line (adds to --prompt)")
    p.add_argument("--lora_path", default="",
                   help="adapter safetensors; merged into the base weights "
                        "by default. Repeat-free multi-adapter form: a "
                        "comma list serves SEVERAL adapters in one batch "
                        "(implies --lora_dynamic; route prompts with "
                        "--adapter_ids)")
    p.add_argument("--adapter_ids", default="",
                   help="comma list, one 0-based adapter index per "
                        "prompt (default: prompt i -> adapter "
                        "i %% n_adapters)")
    p.add_argument("--lora_dynamic", action="store_true",
                   help="apply the adapter dynamically at every site "
                        "instead of merging — no merged weight copy, so "
                        "many adapters can be served off one base")
    p.add_argument("--lora_impl", choices=["auto", "naive", "fused"],
                   default="auto",
                   help="dynamic-LoRA hot-path implementation "
                        "(models/lora_apply.py; parity-pinned — 'naive' "
                        "is the oracle, 'fused' the shape-aware + "
                        "Pallas-epilogue path, 'auto' resolves per "
                        "call site)")
    p.add_argument("--max_new_tokens", type=int, default=64)
    p.add_argument("--prefill_chunk", type=int, default=0,
                   help="Gemma long-prompt mode: prefill in W-token "
                        "windows against the growing KV cache (prefill "
                        "score memory O(W*P) instead of O(P^2) blocks); "
                        "0 = whole-prompt forward. GPT-2's 1024 learned "
                        "positions cap prompts before memory does, so "
                        "the flag is Gemma-only")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top_k", type=int, default=0)
    p.add_argument("--top_p", type=float, default=1.0)
    p.add_argument("--greedy", action="store_true")
    p.add_argument("--no_eos_stop", action="store_true",
                   help="keep sampling past the eos token")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="float32")
    p.add_argument("--json", action="store_true", dest="json_out",
                   help="emit one JSON object per prompt on stdout")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    prompts = list(args.prompt)
    if args.prompt_file:
        with open(args.prompt_file, encoding="utf-8") as f:
            prompts += [ln.rstrip("\n") for ln in f if ln.strip()]
    if not prompts:
        raise SystemExit("no prompts (--prompt / --prompt_file)")
    compute_dtype = jnp.bfloat16 if args.dtype == "bfloat16" \
        else jnp.float32
    b = load_family(args.pretrained_dir, args.model)
    gen = GENERATE[b.family.name]
    if args.prefill_chunk:
        if b.family.dims(b.config).cap is not None:
            raise SystemExit(
                "--prefill_chunk is Gemma-only (GPT-2's learned "
                "positions cap prompts at n_positions)")
        gen = functools.partial(gen, prefill_chunk=args.prefill_chunk)
    tok, encode = b.tok, b.tok.encode  # Gemma: add_bos default (HF parity)
    lora_paths = [p for p in args.lora_path.split(",") if p]
    if len(lora_paths) > 1:
        # multi-adapter batch serving: stack the adapters and route each
        # prompt to its adapter (lora/lora.py stack_adapters semantics)
        from mobilefinetuner_tpu.lora import peft_io
        from mobilefinetuner_tpu.lora.lora import (assign_adapters,
                                                   stack_adapters)
        adapters = [peft_io.load_adapter(p)[0] for p in lora_paths]
        if args.adapter_ids:
            try:
                ids = [int(x) for x in args.adapter_ids.split(",") if x]
            except ValueError:
                raise SystemExit(
                    f"--adapter_ids must be a comma list of integers, "
                    f"got {args.adapter_ids!r}")
            if len(ids) != len(prompts):
                raise SystemExit(
                    f"--adapter_ids has {len(ids)} entries for "
                    f"{len(prompts)} prompts")
            bad = [i for i in ids if not 0 <= i < len(adapters)]
            if bad:
                raise SystemExit(f"adapter ids out of range: {bad}")
        else:
            ids = [i % len(adapters) for i in range(len(prompts))]
        lora = assign_adapters(stack_adapters(adapters), ids)
        log.info(f"multi-adapter serving: {len(adapters)} adapters, "
                 f"prompt routing {ids}")
    else:
        if args.adapter_ids:
            raise SystemExit(
                "--adapter_ids requires at least two --lora_path entries "
                "(comma list) to route between")
        lora = apply_adapter(b, args.lora_path,
                             lora_merge=not args.lora_dynamic)
    config, params = b.config, b.params

    encoded = [encode(p) for p in prompts]
    empty = [p for p, e in zip(prompts, encoded) if not e]
    if empty:
        raise SystemExit(f"prompt(s) encode to zero tokens: {empty!r}")
    ids, mask = left_pad(encoded, tok.pad_id)
    cfg = SampleConfig(
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        greedy=args.greedy,
        eos_id=None if args.no_eos_stop else tok.eos_id,
        pad_id=tok.pad_id)
    rng = jax.random.PRNGKey(args.seed)

    t0 = time.time()
    # jit with params/rng as ARGUMENTS: closing over full-size weights
    # would embed them in the HLO as constants (oversized programs)
    gen_jit = jax.jit(lambda p, l, i, m, r: gen(
        config, p, i, m, cfg, r, compute_dtype=compute_dtype, lora=l,
        lora_impl=args.lora_impl))
    out = np.asarray(gen_jit(params, lora, jnp.asarray(ids),
                             jnp.asarray(mask), rng))
    dt = time.time() - t0
    n_tok = int(out.size)
    log.info(f"{n_tok} tokens in {dt:.2f}s "
             f"({n_tok / max(dt, 1e-9):.1f} tok/s incl. compile)")

    for i, prompt in enumerate(prompts):
        row = out[i].tolist()
        if cfg.eos_id is not None and cfg.eos_id in row:
            row = row[:row.index(cfg.eos_id) + 1]
        text = tok.decode(row)
        if args.json_out:
            print(json_mod.dumps({"prompt": prompt, "ids": row,
                                  "text": text}))
        else:
            print(f"=== {prompt!r}\n{text}\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
