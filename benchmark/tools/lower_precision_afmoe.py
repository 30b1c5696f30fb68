#!/usr/bin/env python3
"""The second reading behind harness/correct_moe.LOGIT_TOL_RMS: the plain
reference computed in the nearest precision BELOW the one the
configuration states (bfloat16) — every matrix rounded to an 8-bit float
(e4m3, 3 mantissa bits) — against the reference itself, at the published
widths, under the routing and on the sample a run of the cell kept.

  python3 benchmark/tools/lower_precision_afmoe.py --seed <n>

Run it on the chip after `run.py --workload trinity_lora_s8192 --seed <n>`
in the same checkout: it reads that run's fixture and its kept reference
(`.bench_work/fixtures/trinity-large-preview-w0/`). Prints one JSON line:
the reading has to come out over the limit (PERF.md §6, PR 27).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--workload", default="trinity_lora_s8192")
    ap.add_argument("--search", action="append", default=[])
    args = ap.parse_args()
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np
    from harness import common, correct_moe, train_child_afmoe as child
    from harness.traffic import argv_value
    from harness.train_cli_afmoe import fixture_dirs
    from mobilefinetuner_tpu.io.checkpoints import load_afmoe
    from reference import afmoe as ref
    cell = common.load_cell(args.workload, args.search)
    config = child.model_config(cell["config_file"])
    model_dir = fixture_dirs(cell, args.seed)["model"]
    z = np.load(os.path.join(os.path.dirname(model_dir),
                             f"reference-{cell['name']}-seed{args.seed}.npz"))
    _, params = load_afmoe(model_dir, config=config)

    def low(t):
        return np.asarray(t, np.float32).astype(ml_dtypes.float8_e4m3fn) \
            .astype(ml_dtypes.bfloat16) if np.ndim(t) >= 2 \
            and t.dtype.name == "bfloat16" else t

    import jax
    params = jax.tree.map(low, params)
    tr = cell["traffic_file"]
    S = int(argv_value(tr["argv"], "--seq_len"))
    n_seq = int(tr["correct"].get("n_seq", 2))
    rng = np.random.default_rng([int(args.seed), 0xC1])
    ids = rng.integers(1, config.vocab_size - 1, (n_seq, S)).astype(np.int32)
    pos_idx = np.unique(np.linspace(0, S - 1, 64).astype(int))
    lora = child.seeded_lora(config, tr["lora"], args.seed)
    cfg = {**child.hf_config(cell["config_file"]),
           "experts_held": config.experts_held}
    hs, routings = ref.run_layers(
        cfg, params, [ref.embed(cfg, params, jnp.asarray(x)) for x in ids],
        range(cfg["num_hidden_layers"]), lora,
        [z["idx"][i] for i in range(n_seq)])
    got = np.stack([np.asarray(ref.head(cfg, params, h)[pos_idx])
                    for h in hs])
    routes = [correct_moe.routing_disagreement(
        z["idx"][i], np.stack([np.asarray(r["biased"]) for r in routings[i]]))
        for i in range(n_seq)]
    print(json.dumps({
        "seed": args.seed, "precision": "float8_e4m3fn matrices",
        "route_share": max(r["share"] for r in routes),
        "route_gap": max(r["max_gap"] for r in routes),
        "logits_rms": correct_moe.rms_err(got, z["logits"]),
        "limit": correct_moe.LOGIT_TOL_RMS,
        "logits_rel_max": correct_moe.rel_max(got, z["logits"])}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
