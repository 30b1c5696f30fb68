#!/usr/bin/env python3
"""The second readings behind harness/correct_ling.py's limits: the plain
reference computed in the nearest precision BELOW the one the
configuration states, one thing lowered at a time, against the reference
itself — at the published widths, under the routing and on the sample a
run of the cell kept:

  state    the delta rule's state carried in bfloat16 (rounded each token)
  gate     the log-decay kept in bfloat16
  router   the router's product and scores in bfloat16
  e4m3     every matrix rounded to an 8-bit float (as lower_precision_afmoe)

  python3 benchmark/tools/lower_precision_ling.py --seed <n> [--only state]

Run it on the chip after `run.py --workload ling_lora_long --seed <n>` in
the same checkout: it reads that run's fixture and its kept reference
(`.bench_work/fixtures/ling-3.0-flash-vl-w0/`). Every reading the cell's
child takes is taken here too — logits, loss, the routing's two, and the
tail gradient's rel-max and cosine of each leaf the traffic file names —
and goes through the child's own `checks_of` and the harness's `verdict`.
Prints one JSON line a variant, each reading beside its limit and
`refused_by` (PERF.md §6, PR 31, has them per limit beside the sound
runs' readings).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

VARIANTS = ("state", "gate", "router", "e4m3")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--workload", default="ling_lora_long")
    ap.add_argument("--only", action="append", choices=VARIANTS)
    ap.add_argument("--search", action="append", default=[])
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np
    from harness import common, correct_ling, train_child_ling as child
    from harness.traffic import argv_value
    from harness.train_cli_ling import fixture_dirs
    from mobilefinetuner_tpu.io.checkpoints import load_ling_hybrid
    from reference import ling_hybrid as ref
    cell = common.load_cell(args.workload, args.search)
    config = child.model_config(cell["config_file"])
    model_dir = fixture_dirs(cell, args.seed)["model"]
    z = np.load(os.path.join(os.path.dirname(model_dir),
                             f"reference-{cell['name']}-seed{args.seed}.npz"))
    _, params = load_ling_hybrid(model_dir, config=config)
    tr = cell["traffic_file"]
    S = int(argv_value(tr["argv"], "--seq_len"))
    n_seq = int(tr["correct"].get("n_seq", 2))
    ids, ids_g, pos_idx = child.sample_ids(
        args.seed, config.vocab_size, n_seq, S,
        int(tr["correct"]["grad_seq_len"]))
    names = list(tr["correct"]["grad_leaves"])
    K = int(tr["correct"].get("grad_tail_layers", 2))
    lora = child.seeded_lora(config, tr["lora"], args.seed)
    cfg = {**child.hf_config(cell["config_file"]),
           "experts_held": config.experts_held}

    def e4m3(t):
        return np.asarray(t, np.float32).astype(ml_dtypes.float8_e4m3fn) \
            .astype(ml_dtypes.bfloat16) if np.ndim(t) >= 2 \
            and t.dtype.name == "bfloat16" else t

    for variant in args.only or VARIANTS:
        ref.LOWER.update(state=jnp.float32, gate=jnp.float32,
                         router=jnp.float32)
        p = params
        if variant == "e4m3":
            p = jax.tree.map(e4m3, params)
        else:
            ref.LOWER[variant] = jnp.bfloat16
        jax.clear_caches()          # LOWER is read when a block is traced
        L = cfg["num_hidden_layers"]
        hs, routings = ref.run_layers(
            cfg, p, [ref.embed(cfg, p, jnp.asarray(x)) for x in ids],
            range(L), lora, [z["idx"][i] for i in range(n_seq)])
        got = np.stack([np.asarray(ref.head(cfg, p, h)[pos_idx])
                        for h in hs])
        loss = float(np.mean([float(ref.loss(ref.head(cfg, p, h),
                                             jnp.asarray(x)))
                              for h, x in zip(hs, ids)]))
        ds = [child.disagreement(cfg, z["idx"][i], routings[i])
              for i in range(n_seq)]
        del hs, routings
        mid, _ = ref.run_layers(
            cfg, p, [ref.embed(cfg, p, jnp.asarray(ids_g[0]))],
            range(L - K), lora, [z["idx_g"]])
        g = ref.tail_grad(cfg, p, mid[0], jnp.asarray(ids_g[0]), lora,
                          z["idx_g"], K)
        v = correct_ling.verdict(child.checks_of(
            got, loss, {n: np.asarray(child.get_leaf(g, n)) for n in names},
            z, max(d["share"] for d in ds), max(d["max_gap"] for d in ds)))
        print(json.dumps({
            "seed": args.seed, "lowered": variant, "ok": v["ok"],
            "checks": v["checks"],
            "refused_by": [k for k, c in v["checks"].items()
                           if not c["ok"]]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
