"""Xing4.0 (latent attention with a query latent, a four-row residual
stream under manifold-constrained hyper-connections, sigmoid-routed
experts, a multi-token-prediction module; models/xing4.py) LoRA
fine-tuning CLI: the fifth family on the shared training loop, through
cli/expert_lora.py's entry point like cli/train_lora_afmoe.py and
cli/train_lora_ling.py.

The family's one flag: `--mtp_loss_weight` (default 0.3; 0 drops the
module from the step). What it adds to `run_start.config`:
`attention_impl_resolved` (flash | xla), `attn_latent_bwd_resolved`
(merged | split, where flash), `hc_mult`, `mtp_loss_weight`; to
`step_stats`: `hc_res_row_err` (the largest |row sum − 1| of a
hyper-connection's H_res over the step) beside the `moe_*` counters,
and `loss_main` / `loss_mtp` where the module runs.

Usage (tiny smoke):
  python -m mobilefinetuner_tpu.cli.train_lora_xing4 \
      --pretrained_dir /path/xing4 --data_dir /path/wikitext-2 \
      --steps 10 --batch_size 2 --lora_out out/adapter.safetensors
"""

from __future__ import annotations

import dataclasses
import sys

from mobilefinetuner_tpu.cli import expert_lora
from mobilefinetuner_tpu.cli.expert_lora import LOSS_CHUNKS  # noqa: F401
from mobilefinetuner_tpu.core.config import Xing4Config
from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.io.checkpoints import load_xing4
from mobilefinetuner_tpu.lora.lora import XING4_TARGETS, init_lora_xing4
from mobilefinetuner_tpu.models import xing4

log = get_logger()


def add_flags(p) -> None:
    p.add_argument("--mtp_loss_weight", type=float, default=0.3,
                   help="weight of the multi-token-prediction module's "
                        "loss (0 drops the module from the step)")


def resolve(args, config: Xing4Config) -> Xing4Config:
    """--attention_impl and --mtp_loss_weight applied, and every `auto`
    resolved for the run's length and stamped into args (the run_start
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
    if args.mtp_loss_weight < 0:
        raise SystemExit("--mtp_loss_weight must be >= 0")
    args.hc_mult = c.hc_mult
    log.info(f"attention_impl={args.attention_impl} -> {impl} (backward "
             f"{args.attn_latent_bwd_resolved}); mtp_loss_weight="
             f"{args.mtp_loss_weight}")
    return dataclasses.replace(c, attention_impl=args.attention_impl,
                               mtp_loss_weight=args.mtp_loss_weight)


FAMILY = expert_lora.ExpertFamily(
    name="xing4_0", prog="train_lora_xing4",
    title="Xing4.0 (MLA with a query latent + hyper-connections + "
          "sigmoid-routed experts + MTP)",
    config_cls=Xing4Config, load=load_xing4, model=xing4,
    targets=XING4_TARGETS, init_lora=init_lora_xing4,
    default_out="xing4_lora.safetensors", resolve=resolve,
    add_flags=add_flags,
    describe=lambda c: (
        f"Xing4.0: layers={c.num_hidden_layers} (dense "
        f"{c.first_k_dense_replace}) + {c.num_nextn_predict_layers} MTP "
        f"hidden={c.hidden_size} x {c.hc_mult} rows "
        f"heads={c.num_attention_heads} latents q {c.q_lora_rank} / kv "
        f"{c.kv_lora_rank} experts held {c.experts_held} of "
        f"{c.n_routed_experts} top-{c.num_experts_per_tok} "
        f"vocab={c.vocab_size}"))


def build_parser():
    return expert_lora.build_parser(FAMILY)


def main(argv=None) -> int:
    return expert_lora.main(FAMILY, argv)


if __name__ == "__main__":
    sys.exit(main())
