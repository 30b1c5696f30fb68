#!/usr/bin/env python3
"""How `phi4flash_lora_long`'s batch size was found: compile the LoRA
optimizer step of Phi-4-mini-flash-reasoning for a DESCRIBED TPU v5e (no
chip attached, nothing runs) and print each compiled peak against 0.85 x
bytes_limit (16,909,336,064 B as the chip reports it).

  JAX_PLATFORMS=cpu python benchmark/tools/size_phi4flash.py 1 2 [--seq_len S]

The rule (the train cells' ladder): B = the largest of 1, 2 at
S = 16,384 whose compiled peak is <= 0.85 of bytes_limit. The step is
built the way cli/train_lora_phi4flash builds it (cli/expert_lora.main):
the configuration file's widths, the whole model, the frozen base in
bf16, bf16 compute, remat, LoRA r 8 on the traffic file's targets, the
chunked loss over the tied 200,064-row head in `loss_chunks` chunks. A
compile that passes is not a chip run. `lower_step` is also what
tests/test_aot_tpu.py compiles.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

BYTES_LIMIT = 16_909_336_064


def lower_step(B: int, device, cell: str = "phi4flash_lora_long", S=None):
    """The lowered optimizer step of `cell` at batch size B (and length
    S, default the cell's), every argument placed on `device` (a
    described one)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from harness import common
    from harness.traffic import argv_value
    from harness.train_child_phi4flash import model_config
    from mobilefinetuner_tpu.cli.expert_lora import loss_chunks
    from mobilefinetuner_tpu.lora.lora import (LoRASpec, init_lora_phi4flash,
                                               trainable_mask)
    from mobilefinetuner_tpu.models import phi4flash
    from mobilefinetuner_tpu.ops.loss import chunked_lm_cross_entropy_sum
    from mobilefinetuner_tpu.train.trainer import (TrainConfig,
                                                   init_optimizer,
                                                   make_train_step)
    c = common.load_cell(cell)
    tr = c["traffic_file"]
    cfg = model_config(c["config_file"])
    S = int(S or argv_value(tr["argv"], "--seq_len"))
    params = jax.eval_shape(lambda: phi4flash.init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    spec = LoRASpec(rank=tr["lora"]["rank"], alpha=tr["lora"]["alpha"],
                    init="peft", targets=list(tr["lora"]["targets"]))
    lora = jax.eval_shape(
        lambda: init_lora_phi4flash(cfg, spec, jax.random.PRNGKey(0)))
    mask = trainable_mask(lora)
    tc = TrainConfig(total_steps=1000, schedule="constant")
    opt = jax.eval_shape(lambda l: init_optimizer(l, tc, mask), lora)

    def loss_fn(lora_t, frozen, mb):
        hidden, aux = phi4flash.hidden_states(
            cfg, frozen, mb["input_ids"],
            attention_mask=mb["attention_mask"], lora=lora_t,
            compute_dtype=jnp.bfloat16, remat="--remat" in tr["argv"],
            lora_impl="auto", with_aux=True)
        s, n = chunked_lm_cross_entropy_sum(
            hidden, frozen["embed"], mb["labels"],
            num_chunks=loss_chunks(B, S, cfg.vocab_size))
        return s, n, aux["counters"]

    step = make_train_step(loss_fn, tc, mask=mask, donate=True)
    one = SingleDeviceSharding(device)
    on0 = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        np.shape(x), x.dtype, sharding=one), t)
    batch = {"input_ids": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "attention_mask": jax.ShapeDtypeStruct((B, S), jnp.float32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    return step.lower(on0(lora), on0(params), on0(opt), on0(batch),
                      jax.ShapeDtypeStruct((), jnp.int32, sharding=one))


def main() -> int:
    import argparse
    import time
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from mobilefinetuner_tpu.core.xla_stats import (compiled_peak_bytes,
                                                    tpu_custom_calls)
    ap = argparse.ArgumentParser()
    ap.add_argument("batch", nargs="*", type=int, default=[1, 2])
    ap.add_argument("--seq_len", type=int, default=None)
    args = ap.parse_args()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.default_backend = lambda: "tpu"     # every `auto` rule as on a chip
    for B in args.batch:
        t = time.perf_counter()
        try:
            comp = lower_step(B, topo.devices[0], S=args.seq_len).compile()
        except Exception as e:   # the compiler's own refusal is the verdict
            print(json.dumps({"B": B, "refused": str(e)[:600]}), flush=True)
            continue
        peak = compiled_peak_bytes(comp)
        ma = comp.memory_analysis()
        print(json.dumps({
            "B": B, "S": args.seq_len, "compiled_peak_bytes": peak,
            "share_of_bytes_limit": round(peak / BYTES_LIMIT, 4),
            "fits_0.85": peak <= 0.85 * BYTES_LIMIT,
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "tpu_custom_calls": tpu_custom_calls(comp.as_text()),
            "compile_s": round(time.perf_counter() - t, 1),
            "compiled_for": "described v5e:2x2, no chip"}), flush=True)
        if os.environ.get("SIZE_PHI4FLASH_HLO"):
            with open(f"{os.environ['SIZE_PHI4FLASH_HLO']}.B{B}.txt",
                      "w") as f:
                f.write(comp.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main())
