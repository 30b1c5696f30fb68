#!/usr/bin/env python3
"""How the serve cells' pool was sized: compile the engine's pool-
touching programs (the prompt-page write and the paged decode step, both
with the pools donated, as `ServeEngine` jits them) for a DESCRIBED TPU
v5e at a ladder of `num_blocks`, from shapes alone, and print each
compiled peak against the chip's 15.75 GiB.

  JAX_PLATFORMS=cpu python benchmark/tools/size_serve.py md_batchgen_long 6001 4097

Why it exists (PR 22): the engine's own admission (`mem_check`: params +
bank + pools <= 0.9 x capacity) admitted 6,001 blocks (9.4 GB of pools),
and its first prefill then died on the chip — the compiled prompt-page
write holds a second copy of the pools.
"""
from __future__ import annotations

import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def main() -> int:
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from harness import common
    from harness.serve_engine import model_config
    from mobilefinetuner_tpu.core.xla_stats import (compiled_peak_bytes,
                                                    tpu_custom_calls)
    from mobilefinetuner_tpu.models import generate as g
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.serve.paged_kv import (blocks_for,
                                                    write_prompt_blocks)
    cell = common.load_cell(sys.argv[1])
    e = cell["traffic_file"]["engine"]
    cfg = model_config(cell["config_file"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.default_backend = lambda: "tpu"
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    L, H, D = cfg.n_layer, cfg.n_head, cfg.head_dim
    S, bT, P = e["num_slots"], e["block_T"], e["max_prompt"]
    M = blocks_for(P + e["max_new_tokens"] - 1, bT)
    params = jax.tree.map(
        lambda x: sds(x.shape, jnp.float32),
        jax.eval_shape(lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0))))
    for NB in [int(n) for n in sys.argv[2:]]:
        pool = sds((NB, L, H, bT, D), jnp.bfloat16)
        kv = sds((L, H, P, D), jnp.bfloat16)
        progs = {
            "write": (write_prompt_blocks,
                      (pool, pool, kv, kv, sds((P // bT,), jnp.int32)),
                      (0, 1)),
            "decode_step": (functools.partial(
                g.gpt2_decode_step_paged, cfg, compute_dtype=jnp.bfloat16),
                (params, pool, pool, sds((S,), jnp.int32),
                 sds((S,), jnp.int32), sds((S, M), jnp.int32)), (1, 2))}
        for name, (fn, ops, donate) in progs.items():
            try:
                comp = jax.jit(fn, donate_argnums=donate).lower(
                    *ops).compile()
            except Exception as ex:
                print(json.dumps({"num_blocks": NB, "program": name,
                                  "refused": str(ex)[:240]}), flush=True)
                continue
            ma = comp.memory_analysis()
            print(json.dumps({
                "num_blocks": NB, "program": name,
                "pools_gb": round(2 * NB * L * H * bT * D * 2 / 1e9, 3),
                "compiled_peak_gb": round(compiled_peak_bytes(comp) / 1e9,
                                          3),
                "argument_gb": round(ma.argument_size_in_bytes / 1e9, 3),
                "temp_gb": round(ma.temp_size_in_bytes / 1e9, 3),
                "alias_gb": round(ma.alias_size_in_bytes / 1e9, 3),
                "tpu_custom_calls": tpu_custom_calls(comp.as_text()),
                "compiled_for": "described v5e, no chip"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
