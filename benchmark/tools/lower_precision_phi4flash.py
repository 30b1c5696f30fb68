#!/usr/bin/env python3
"""The second readings behind harness/correct_phi4flash.py's limits, at
the published widths, on the sample a run of the cell kept — a control
in the nearest precision BELOW the one the configuration states, two
planted faults, and the program in float32:

  state        the plain reference with the selective scan's state AND
               its decay exp(Delta A) rounded to bfloat16 every token (the
               configuration states float32), against the reference
  detached     the reference's gradients with the cross-decoder reading
               the memory and layer 17's keys and values detached from
               the gradient (reference.phi4flash.DETACH): what the
               leaves below those mechanisms are there to see
  no_update    the program's own readings of the run (the child keeps
               them) with the train step's change of every leaf dropped:
               a step that leaves the adapters where they were
  program_f32  the CLI's train step at --dtype float32 under matmul
               precision "highest" (the gradient and update readings
               only): what of the bf16 program's gradient gap is
               rounding

  python3 benchmark/tools/lower_precision_phi4flash.py --seed <n>

Run it on the chip after `run.py --workload phi4flash_lora_long --seed
<n>` in the same checkout: it reads that run's fixture, its kept
reference and the program's readings (`.bench_work/fixtures/
phi-4-mini-flash-reasoning-w0/`). Every reading goes through the child's
`checks_of` and the harness's `verdict`. Prints one JSON line a variant,
each reading beside its limit and `refused_by` (PERF.md §6, the
phi4flash cell's entry, has them beside the sound runs' readings).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

VARIANTS = ("state", "detached", "no_update", "program_f32")


def variant_readings(variant, cell, config, params, lora, sample, z,
                     model_dir, seed: int, lr: float) -> dict:
    """What `checks_of` compares with the kept reference `z`, for one
    variant."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import train_child_phi4flash as child
    from reference import phi4flash as ref
    ids, ids_g, pos_idx, names, first = sample
    cfg = child.hf_config(cell["config_file"])

    def as_program(got):
        grads = {n: got[f"grad:{n}"] for n in names}
        return {**{k: got[k] for k in ("logits", "loss") if k in got},
                "grads": grads,
                "updates": {n: ref.adam_first_update(g, lr)
                            for n, g in grads.items()}}

    if variant == "no_update":
        got = child.load_program(os.path.join(
            os.path.dirname(model_dir), child.program_key(cell, seed)))
        got["updates"] = {n: np.zeros_like(u)
                          for n, u in got["updates"].items()}
        return got
    if variant == "program_f32":
        with jax.default_matmul_precision("highest"):
            step = child.program_step(cell, config, params, lora, ids_g,
                                      names, model_dir,
                                      ["--dtype", "float32"])
        return {"grads": step["grads"], "updates": step["updates"]}
    try:
        if variant == "state":
            ref.LOWER["state"] = jnp.bfloat16
            jax.clear_caches()      # read when a block is traced
            return as_program(child.reference_side(
                cfg, params, lora, ids, ids_g, pos_idx, names, first))
        ref.DETACH["shared"] = True
        jax.clear_caches()
        return as_program({"logits": z["logits"], "loss": float(z["loss"]),
                           **child.reference_grads(cfg, params, lora,
                                                   ids_g, names, first)})
    finally:
        ref.LOWER["state"] = jnp.float32
        ref.DETACH["shared"] = False
        jax.clear_caches()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--workload", default="phi4flash_lora_long")
    ap.add_argument("--only", action="append", choices=VARIANTS)
    ap.add_argument("--search", action="append", default=[])
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import common, correct_phi4flash
    from harness import train_child_phi4flash as child
    from harness.traffic import argv_value
    from harness.train_cli_phi4flash import fixture_dirs
    from mobilefinetuner_tpu.io.checkpoints import load_phi4flash
    cell = common.load_cell(args.workload, args.search)
    config = child.model_config(cell["config_file"])
    model_dir = fixture_dirs(cell, args.seed)["model"]
    z = dict(np.load(os.path.join(os.path.dirname(model_dir),
                                  child.reference_key(cell, args.seed))))
    _, host = load_phi4flash(model_dir, config=config)
    params = jax.tree.map(jnp.asarray, host)
    del host
    tr = cell["traffic_file"]
    ck = tr["correct"]
    ids, ids_g, pos_idx = child.sample_ids(
        args.seed, config.vocab_size, int(ck.get("n_seq", 2)),
        int(argv_value(tr["argv"], "--seq_len")), int(ck["grad_seq_len"]))
    sample = (ids, ids_g, pos_idx, list(ck["grad_leaves"]),
              int(ck["grad_first_layer"]))
    lora = child.seeded_lora(config, tr["lora"], args.seed)
    lr = child.cli_args(cell, model_dir).lr
    rc = 0
    for variant in args.only or VARIANTS:
        try:
            got = variant_readings(variant, cell, config, params, lora,
                                   sample, z, model_dir, args.seed, lr)
        except Exception as e:          # one variant's failure is printed
            rc = 1
            print(json.dumps({"seed": args.seed, "variant": variant,
                              "error": f"{type(e).__name__}: {e}"[:2000],
                              "trace": traceback.format_exc()[-2000:]}),
                  flush=True)
            continue
        v = correct_phi4flash.verdict(child.checks_of(got, z, lr))
        print(json.dumps({
            "seed": args.seed, "variant": variant, "ok": v["ok"],
            "checks": v["checks"],
            "readings": child.readings_of(got, z),
            "refused_by": [k for k, c in v["checks"].items()
                           if not c["ok"]]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
