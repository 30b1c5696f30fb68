#!/usr/bin/env python3
"""The second readings behind harness/correct_xing4.py's limits: the plain
reference lowered or broken, one thing at a time, against the reference
itself — at the published widths, under the routing and on the sample a
run of the cell kept:

  sinkhorn   the Sinkhorn rounds (exp, every row and column pass) in
             bfloat16
  router     the router's product and scores in bfloat16
  rounds19   19 Sinkhorn rounds for hc_sinkhorn_iters = 20
  no_mscale  the softmax scale without mscale^2
  drop_mtp   the loss (and so the tail gradient) without the module's
             term
  e4m3       every matrix rounded to an 8-bit float (as
             lower_precision_afmoe)

  python3 benchmark/tools/lower_precision_xing4.py --seed <n> [--only router]

Run it on the chip after `run.py --workload xing_lora_long --seed <n>` in
the same checkout: it reads that run's fixture and its kept reference
(`.bench_work/fixtures/xing4.0-29b-a4b-w19/`). Every reading the cell's
child takes is taken here too — both heads' logits, both losses, the
routing's two, Sinkhorn on the probe, the tail gradient's rel-max and
cosine of each leaf the traffic file names — and goes through the
child's own `checks_of` and the harness's `verdict`. Prints one JSON line
a variant, each reading beside its limit and `refused_by` (PERF.md
section 6, PR 33, has them per limit beside the sound runs' readings).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

VARIANTS = ("sinkhorn", "router", "rounds19", "no_mscale", "drop_mtp",
            "e4m3")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--workload", default="xing_lora_long")
    ap.add_argument("--only", action="append", choices=VARIANTS)
    ap.add_argument("--search", action="append", default=[])
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np
    from harness import common, correct_xing4, train_child_xing4 as child
    from harness.traffic import argv_value
    from harness.train_cli_xing4 import fixture_dirs
    from mobilefinetuner_tpu.io.checkpoints import load_xing4
    from reference import xing4 as ref
    cell = common.load_cell(args.workload, args.search)
    config = child.cell_config(cell)
    model_dir = fixture_dirs(cell, args.seed)["model"]
    z = np.load(os.path.join(os.path.dirname(model_dir),
                             f"reference-{cell['name']}-seed{args.seed}.npz"))
    _, params = load_xing4(model_dir, config=config)
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
    probe = correct_xing4.probe_logits(args.seed, config.hc_mult, S)
    # the program's side of the comparison is the kept routing; the
    # lowered reference is scored as the program is
    prog = {k: z[k] for k in ("idx", "idx_mtp", "idx_g", "idx_mtp_g")}

    def e4m3(t):
        return np.asarray(t, np.float32).astype(ml_dtypes.float8_e4m3fn) \
            .astype(ml_dtypes.bfloat16) if np.ndim(t) >= 2 \
            and t.dtype.name == "bfloat16" else t

    for variant in args.only or VARIANTS:
        ref.LOWER.update(sinkhorn=jnp.float32, router=jnp.float32)
        ref.FAULT.update(sinkhorn_iters=0, no_mscale=False, drop_mtp=False)
        p = params
        if variant == "e4m3":
            p = jax.tree.map(e4m3, params)
        elif variant in ref.LOWER:
            ref.LOWER[variant] = jnp.bfloat16
        elif variant == "rounds19":
            ref.FAULT["sinkhorn_iters"] = -1
        else:
            ref.FAULT[variant] = True
        jax.clear_caches()      # LOWER and FAULT are read when traced
        got = child.reference_side(cfg, p, lora, ids, ids_g, pos_idx, names,
                                   K, prog, config.mtp_loss_weight, probe)
        got["grads"] = {n: got[f"grad:{n}"] for n in names}
        v = correct_xing4.verdict(child.checks_of(
            got, z, float(got["route_share"]), float(got["route_gap"]),
            0.0))
        print(json.dumps({
            "seed": args.seed, "lowered": variant, "ok": v["ok"],
            "checks": v["checks"],
            "refused_by": [k for k, c in v["checks"].items()
                           if not c["ok"]]},
            default=lambda o: o.item()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
