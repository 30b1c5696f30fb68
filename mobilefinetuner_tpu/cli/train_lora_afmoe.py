"""AFMoE (Arcee Trinity) LoRA fine-tuning CLI.

The third family on the shared training loop (cli/common.run_training):
same flags, flush, telemetry, memory admission, prefetch, resume and
`--lora_out` as `gpt2_lora_finetune`. The entry point itself —
`--expert_parallel N --expert_rank r`, the bf16-resident frozen base,
the chunked loss, the `moe_*` counters in `step_stats` — is
cli/expert_lora.py's, shared with cli/train_lora_ling.py; this file says
what the family is.

Usage (tiny smoke):
  python -m mobilefinetuner_tpu.cli.train_lora_afmoe \
      --pretrained_dir /path/afmoe --data_dir /path/wikitext-2 \
      --steps 10 --batch_size 2 --lora_out out/adapter.safetensors
"""

from __future__ import annotations

import sys

from mobilefinetuner_tpu.cli import expert_lora
from mobilefinetuner_tpu.cli.expert_lora import LOSS_CHUNKS  # noqa: F401
from mobilefinetuner_tpu.core.config import AfmoeConfig
from mobilefinetuner_tpu.io.checkpoints import load_afmoe
from mobilefinetuner_tpu.lora.lora import AFMOE_TARGETS, init_lora_afmoe
from mobilefinetuner_tpu.models import afmoe

FAMILY = expert_lora.ExpertFamily(
    name="afmoe", prog="train_lora_afmoe", title="AFMoE (Arcee Trinity)",
    config_cls=AfmoeConfig, load=load_afmoe, model=afmoe,
    targets=AFMOE_TARGETS, init_lora=init_lora_afmoe,
    default_out="afmoe_lora.safetensors",
    describe=lambda c: (
        f"AFMoE: layers={c.num_hidden_layers} "
        f"(dense {c.num_dense_layers}) hidden={c.hidden_size} "
        f"q/kv heads={c.num_attention_heads}/{c.num_key_value_heads} "
        f"experts held {c.experts_held} of {c.num_experts} "
        f"top-{c.num_experts_per_tok} vocab={c.vocab_size}"))


def build_parser():
    return expert_lora.build_parser(FAMILY)


def main(argv=None) -> int:
    return expert_lora.main(FAMILY, argv)


if __name__ == "__main__":
    sys.exit(main())
