"""Phi-4-mini-flash-reasoning (SambaY: Mamba selective scans beside
differential window attention, a full-attention layer whose keys and
values a cross-decoder of gated memory units and cross-attention reads;
models/phi4flash.py) LoRA fine-tuning CLI: the sixth family on the shared
training loop, through cli/expert_lora.py's entry point like
cli/train_lora_ling.py. The family has no experts: `--expert_parallel 1
--expert_rank 0` (the defaults) is the only deployment it takes.

What it adds to `run_start.config`: `attention_impl_resolved` (flash |
xla), `attn_bwd_resolved` (merged | split, where flash: the kernels at
d 64 against a value of 128), `layers_<kind>` (how many layers of each
kind: mamba, swa, full, gmu, cross), `ssm_chunk`, `ssm_state`,
`ssm_inner`, `mixer_chunk` (the tokens a Mamba mixer and an MLP take at
a time); to `step_stats`: `ssm_state_absmax` (the largest |h| at a
chunk boundary of any scan over the step: a scan that diverges or decays
to nothing shows there).

Usage (tiny smoke):
  python -m mobilefinetuner_tpu.cli.train_lora_phi4flash \\
      --pretrained_dir /path/phi4flash --data_dir /path/wikitext-2 \\
      --steps 10 --batch_size 2 --lora_out out/adapter.safetensors
"""

from __future__ import annotations

import dataclasses
import sys
from collections import Counter

from mobilefinetuner_tpu.cli import expert_lora
from mobilefinetuner_tpu.core.config import Phi4FlashConfig
from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.io.checkpoints import load_phi4flash
from mobilefinetuner_tpu.lora.lora import (PHI4FLASH_TARGETS,
                                           init_lora_phi4flash)
from mobilefinetuner_tpu.models import phi4flash

log = get_logger()


def resolve(args, config: Phi4FlashConfig) -> Phi4FlashConfig:
    """--attention_impl applied, and every `auto` of the family resolved
    for the run's length and stamped into args (the run_start
    manifest)."""
    from mobilefinetuner_tpu.ops.attention import resolve_impl
    from mobilefinetuner_tpu.ops.flash_attention import resolve_bwd_impl
    c, S = config, args.seq_len
    impl = args.attention_impl
    if impl == "auto":
        impl = resolve_impl(S, c.head_dim)
    args.attention_impl_resolved = impl
    args.attn_bwd_resolved = None
    if impl == "flash":
        args.attn_bwd_resolved = resolve_bwd_impl(
            S, c.head_dim, 512, 2 if args.dtype == "bfloat16" else 4,
            2 * c.head_dim)
    counts = Counter(c.layer_kinds)
    for kind in ("mamba", "swa", "full", "gmu", "cross"):
        setattr(args, f"layers_{kind}", counts[kind])
    args.ssm_chunk, args.ssm_state = c.ssm_chunk, c.mamba_d_state
    args.mixer_chunk = c.mixer_chunk
    args.ssm_inner = c.ssm_inner
    log.info(f"attention_impl={args.attention_impl} -> {impl} (backward "
             f"{args.attn_bwd_resolved}); selective scan chunk "
             f"{c.ssm_chunk}, state {c.mamba_d_state} x {c.ssm_inner}; "
             f"layers {dict(counts)}")
    return dataclasses.replace(c, attention_impl=args.attention_impl)


FAMILY = expert_lora.ExpertFamily(
    name="phi4flash", prog="train_lora_phi4flash",
    title="Phi-4-mini-flash (SambaY: Mamba + differential attention + "
          "gated memory units)",
    config_cls=Phi4FlashConfig, load=load_phi4flash, model=phi4flash,
    targets=PHI4FLASH_TARGETS, init_lora=init_lora_phi4flash,
    default_out="phi4flash_lora.safetensors", resolve=resolve,
    peft_layer_ids=phi4flash.stack_layer_ids,
    head=lambda p: p["embed"],
    describe=lambda c: (
        f"Phi-4-mini-flash: layers={c.num_hidden_layers} "
        f"({dict(Counter(c.layer_kinds))}) hidden={c.hidden_size} heads="
        f"{c.num_attention_heads}/{c.num_key_value_heads} of {c.head_dim} "
        f"window={c.sliding_window} ssm {c.ssm_inner} x {c.mamba_d_state} "
        f"vocab={c.vocab_size} (tied)"))


def build_parser():
    return expert_lora.build_parser(FAMILY)


def main(argv=None) -> int:
    return expert_lora.main(FAMILY, argv)


if __name__ == "__main__":
    sys.exit(main())
