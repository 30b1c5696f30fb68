#!/usr/bin/env python3
"""How the train cells' batch sizes were found: compile the optimizer
step for a DESCRIBED TPU v5e (no chip attached, nothing runs) at a
ladder of batch sizes and print each compiled peak against
0.85 x bytes_limit (16,909,336,064 B as the chip reported it, PERF.md).

  JAX_PLATFORMS=cpu python benchmark/tools/size_cells.py lora 2 4 6 8
  JAX_PLATFORMS=cpu python benchmark/tools/size_cells.py fullft_fsdp4 4 8 12 16

The step is built the way the CLIs build it (cli/gpt2_lora_finetune,
cli/gpt2_full_finetune + cli/common.run_training): bf16 compute, remat,
S = 1024, LoRA r=8 on the four GPT-2 targets / every parameter
trainable and FSDP-sharded over a (1, 4) mesh with Adam's state on the
params' shardings. A compile that passes is not a chip run; the number
frozen in benchmark/traffic/*.json is the largest B under the limit.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

BYTES_LIMIT = 16_909_336_064
S = 1024


def main() -> int:
    kind, ladder = sys.argv[1], [int(b) for b in sys.argv[2:]]
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.core.xla_stats import (compiled_peak_bytes,
                                                    tpu_custom_calls)
    from mobilefinetuner_tpu.lora.lora import (LoRASpec, init_lora_gpt2,
                                               trainable_mask)
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
    from mobilefinetuner_tpu.train.trainer import (TrainConfig,
                                                   init_optimizer,
                                                   make_train_step)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.default_backend = lambda: "tpu"     # every `auto` rule as on a chip
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "gpt2-xl.json")) as f:
        c = json.load(f)
    cfg = GPT2Config(vocab_size=c["vocab_size"], n_positions=c["n_positions"],
                     n_embd=c["n_embd"], n_layer=c["n_layer"],
                     n_head=c["n_head"], embd_pdrop=0.0, resid_pdrop=0.0,
                     attn_pdrop=0.0)
    params = jax.eval_shape(
        lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    tc = TrainConfig(total_steps=1000, schedule="constant")

    def batch_of(B):
        return {"input_ids": jax.ShapeDtypeStruct((B, S), jnp.int32),
                "attention_mask": jax.ShapeDtypeStruct((B, S), jnp.float32),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}

    def place(tree, sh):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype, sharding=s), tree, sh)

    for B in ladder:
        if kind == "lora":
            spec = LoRASpec(rank=8, alpha=16.0, init="gpt2", targets=[
                "attn_qkv", "attn_proj", "mlp_fc_in", "mlp_fc_out"])
            lora = jax.eval_shape(
                lambda: init_lora_gpt2(cfg, spec, jax.random.PRNGKey(0)))
            lora_c = init_lora_gpt2(GPT2Config.tiny(), spec,
                                    jax.random.PRNGKey(0))
            mask = trainable_mask(lora_c)
            opt = jax.eval_shape(lambda l: init_optimizer(l, tc, mask), lora)

            def loss_fn(lora_t, frozen, mb):
                logits = gpt2.forward(
                    cfg, frozen, mb["input_ids"],
                    attention_mask=mb["attention_mask"], lora=lora_t,
                    compute_dtype=jnp.bfloat16, remat=True,
                    lora_impl="auto")
                return lm_cross_entropy_sum(logits, mb["labels"])

            step = make_train_step(loss_fn, tc, mask=mask, donate=True)
            one = SingleDeviceSharding(topo.devices[0])
            on0 = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                np.shape(x), x.dtype, sharding=one), t)
            low = step.lower(on0(lora), on0(params), on0(opt),
                             on0(batch_of(B)),
                             jax.ShapeDtypeStruct((), jnp.int32,
                                                  sharding=one))
        else:
            from mobilefinetuner_tpu.parallel.mesh import (
                batch_sharding, make_mesh, params_shardings,
                replicated_sharding)
            mesh = make_mesh(data=1, fsdp=4, devices=topo.devices)
            repl = replicated_sharding(mesh)
            xcfg = dataclasses.replace(cfg, attention_impl="xla")
            opt = jax.eval_shape(lambda p: init_optimizer(p, tc, None),
                                 params)

            def loss_fn(params_t, _unused, mb):
                logits = gpt2.forward(
                    xcfg, params_t, mb["input_ids"],
                    attention_mask=mb["attention_mask"],
                    compute_dtype=jnp.bfloat16, remat=True)
                return lm_cross_entropy_sum(logits, mb["labels"])

            psh = params_shardings(params, mesh)
            osh = params_shardings(opt, mesh)
            step = make_train_step(loss_fn, tc, mask=None, donate=True,
                                   out_shardings=(psh, osh, repl))
            low = step.lower(
                place(params, psh), None, place(opt, osh),
                place(batch_of(B), jax.tree.map(
                    lambda _: batch_sharding(mesh), batch_of(B))),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=repl))
        try:
            comp = low.compile()
        except Exception as e:   # the compiler's own refusal is the verdict
            print(json.dumps({"kind": kind, "B": B, "S": S,
                              "refused": str(e)[:300]}), flush=True)
            continue
        peak = compiled_peak_bytes(comp)
        ma = comp.memory_analysis()
        print(json.dumps({
            "kind": kind, "B": B, "S": S, "compiled_peak_bytes": peak,
            "share_of_bytes_limit": round(peak / BYTES_LIMIT, 4),
            "fits_0.85": peak <= 0.85 * BYTES_LIMIT,
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "tpu_custom_calls": tpu_custom_calls(comp.as_text()),
            "compiled_for": "described v5e:2x2, no chip"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
