"""Ling-3.0-flash hybrid (delta-rule linear attention + latent attention
+ group-routed experts, models/ling_hybrid.py) LoRA fine-tuning CLI: the
fourth family on the shared training loop, through cli/expert_lora.py's
entry point like cli/train_lora_afmoe.py.

What the family adds to `run_start.config`: `layer_kinds` (k = delta
rule, m = latent attention, in layer order), `kda_chunk`,
`kda_impl_resolved` (kernel | chunked | recurrent: read from the run's
shapes, models/ling_hybrid.resolve_kda_impl), `attention_impl_resolved`
(the latent layers' softmax attention: flash | xla) and
`attn_latent_bwd_resolved` (merged | split, where flash); to
`step_stats`: `moe_groups_here` beside the `moe_*` counters.

Usage (tiny smoke):
  python -m mobilefinetuner_tpu.cli.train_lora_ling \
      --pretrained_dir /path/ling --data_dir /path/wikitext-2 \
      --steps 10 --batch_size 2 --lora_out out/adapter.safetensors
"""

from __future__ import annotations

import dataclasses
import sys

from mobilefinetuner_tpu.cli import expert_lora
from mobilefinetuner_tpu.cli.expert_lora import LOSS_CHUNKS  # noqa: F401
from mobilefinetuner_tpu.core.config import LingHybridConfig
from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.io.checkpoints import load_ling_hybrid
from mobilefinetuner_tpu.lora.lora import LING_TARGETS, init_lora_ling
from mobilefinetuner_tpu.models import ling_hybrid

log = get_logger()


def resolve(args, config: LingHybridConfig) -> LingHybridConfig:
    """--attention_impl applied, and every `auto` of the family resolved
    for the run's length and stamped into args (the run_start
    manifest)."""
    from mobilefinetuner_tpu.ops.attention import resolve_impl
    from mobilefinetuner_tpu.ops.flash_attention import (HEAD_SIZES,
                                                         resolve_bwd_impl)
    c, S = config, args.seq_len
    impl = args.attention_impl
    if impl == "auto":
        impl = resolve_impl(S, c.qk_head_dim)
    args.attention_impl_resolved = impl
    args.attn_latent_bwd_resolved = None
    if impl == "flash":
        padded = min(d for d in HEAD_SIZES if d >= c.qk_head_dim)
        args.attn_latent_bwd_resolved = resolve_bwd_impl(
            S, padded, 512, 2 if args.dtype == "bfloat16" else 4,
            c.v_head_dim)
    args.layer_kinds = ",".join(k[0] for k in c.layer_kinds)
    args.kda_chunk = c.kda_chunk
    args.kda_impl_resolved = ling_hybrid.resolve_kda_impl(
        S, c.kda_chunk, c.head_dim)
    log.info(f"attention_impl={args.attention_impl} -> {impl} (latent "
             f"layers; backward {args.attn_latent_bwd_resolved}); delta "
             f"rule -> {args.kda_impl_resolved} at chunk {c.kda_chunk}; "
             f"layers {args.layer_kinds}")
    return dataclasses.replace(c, attention_impl=args.attention_impl)


def peft_layer_ids(c: LingHybridConfig) -> dict:
    kinds = c.layer_kinds
    return {t: [i for i, k in enumerate(kinds) if k == t.split("_")[0]]
            for t in LING_TARGETS}


FAMILY = expert_lora.ExpertFamily(
    name="ling_hybrid", prog="train_lora_ling",
    title="Ling-3.0-flash hybrid (KDA + MLA + group-routed experts)",
    config_cls=LingHybridConfig, load=load_ling_hybrid, model=ling_hybrid,
    targets=LING_TARGETS, init_lora=init_lora_ling,
    default_out="ling_lora.safetensors", resolve=resolve,
    peft_layer_ids=peft_layer_ids,
    describe=lambda c: (
        f"Ling hybrid: layers={c.num_hidden_layers} "
        f"({''.join(k[0] for k in c.layer_kinds)}, dense "
        f"{c.first_k_dense_replace}) hidden={c.hidden_size} "
        f"heads={c.num_attention_heads} experts held {c.experts_held} of "
        f"{c.num_experts} top-{c.num_experts_per_tok} in {c.topk_group}/"
        f"{c.n_group} groups vocab={c.vocab_size}"))


def build_parser():
    return expert_lora.build_parser(FAMILY)


def main(argv=None) -> int:
    return expert_lora.main(FAMILY, argv)


if __name__ == "__main__":
    sys.exit(main())
