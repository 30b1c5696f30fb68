#!/usr/bin/env python3
"""The JAX-touching child of the Ling hybrid's train cell, run BEFORE the
timed child (harness/train_cli_ling.py starts it), as
harness/train_child_afmoe.py is for its family — whose generic helpers
(`out`, `write_tokenizer`, `get_leaf`) are imported; what names a family
is restated:

  1. imports the family's modules (a checkout without them fails here,
     in seconds: exit code 1 and no verdict) and finds the chip;
  2. writes the checkpoint directory the CLI loads, if the fixture is
     not there yet: seeded bf16 weights, the held experts under their own
     numbers; ONE weight fixture per configuration whatever --seed
     (5.6 GB);
  3. compares the program with reference/ling_hybrid.py at the published
     widths and the timed length, harness/correct_ling.py holding the
     limits: the program first (hidden states of `n_seq` 16,384-token
     sequences through models/ling_hybrid.py with the cell's settings —
     bf16, remat, the chunked delta rule, flash at two head sizes —
     logits at 64 positions, the loss through the CLI's chunked
     cross-entropy, every expert layer's routing, the gradient of one
     delta-rule-layer and one latent-layer adapter leaf on one sequence);
     then the reference UNDER THE PROGRAM'S ROUTING, which in the same
     pass scores every expert layer for itself; its gradient is taken
     over the last `grad_tail_layers` layers (one of each kind), the
     forward below them runs without autodiff, and the program's full
     backward is sliced to those layers' positions among their kind.

Prints one JSON line per phase; the last line is the verdict.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import numpy as np  # noqa: E402

from harness import common, correct_ling  # noqa: E402
from harness.traffic import argv_value  # noqa: E402
from harness.train_child_afmoe import (get_leaf, out,  # noqa: E402
                                       write_tokenizer)

WEIGHT_SEED = 0
NOT_CONFIG = ("source", "reduced", "published", "deployment", "assumed",
              "fixture", "name")


def hf_config(cfgfile: dict) -> dict:
    """The config.json the CLI reads: the file's published keys, with the
    router at its published width (`num_experts` in the file counts the
    experts HELD; the CLI is told the deployment by its two flags)."""
    return {**{k: v for k, v in cfgfile.items() if k not in NOT_CONFIG},
            "num_experts": cfgfile["deployment"]["router_width"]}


def model_config(cfgfile: dict):
    """The program's configuration of this chip's share."""
    from mobilefinetuner_tpu.core.config import LingHybridConfig
    raw = hf_config(cfgfile)
    dep = cfgfile["deployment"]
    c = LingHybridConfig(**{f.name: raw[f.name]
                            for f in dataclasses.fields(LingHybridConfig)
                            if f.name in raw})
    c = c.with_expert_parallel(dep["expert_parallel"], dep["expert_rank"])
    assert c.experts_held[1] == cfgfile["num_experts"], c.experts_held
    return c


def fixture_mark(config, cfgfile: dict) -> dict:
    return {"weight_seed": WEIGHT_SEED, "fixture": cfgfile["fixture"],
            "hidden_size": config.hidden_size,
            "layer_kinds": config.layer_kinds,
            "experts_held": list(config.experts_held),
            "vocab_size": config.vocab_size}


def fixture_ok(d: str, config, cfgfile: dict) -> bool:
    try:
        m = common.load_json(os.path.join(d, "fixture.json"))
    except (OSError, ValueError):
        return False
    return m == fixture_mark(config, cfgfile) \
        and os.path.exists(os.path.join(d, "model.safetensors"))


def write_model_dir(d: str, cfgfile: dict, config) -> float:
    """The checkpoint directory with seeded bf16 weights and the stand-in
    tokenizer of vocab_size entries; seconds taken."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.io.checkpoints import ling_hybrid_params_to_hf
    from mobilefinetuner_tpu.io.safetensors_io import save_safetensors
    from mobilefinetuner_tpu.models import ling_hybrid
    t = time.perf_counter()
    os.makedirs(d, exist_ok=True)
    params = jax.jit(lambda k: ling_hybrid.init_params(
        config, k, jnp.bfloat16,
        expert_bias_std=cfgfile["fixture"]["expert_bias_std"],
        embed_std=cfgfile["fixture"].get("embed_std", 0.02)))(
        jax.random.PRNGKey(WEIGHT_SEED))
    host = jax.device_get(params)
    del params
    save_safetensors(os.path.join(d, "model.safetensors"),
                     ling_hybrid_params_to_hf(host, config.experts_held[0]),
                     metadata={"format": "pt"}, manifest=False)
    del host
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(hf_config(cfgfile), f)
    write_tokenizer(d, config.vocab_size, cfgfile["fixture"]["words"])
    with open(os.path.join(d, "fixture.json"), "w") as f:
        json.dump(fixture_mark(config, cfgfile), f)
    return time.perf_counter() - t


def seeded_lora(config, spec_d: dict, seed: int):
    """An adapter of the shape the traffic file states with BOTH factors
    N(0, 0.02): a fresh adapter's B is zero, which would make the
    comparison blind to A."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.lora.lora import LoRASpec, init_lora_ling

    def make(key):
        spec = LoRASpec(rank=spec_d["rank"], alpha=float(spec_d["alpha"]),
                        targets=list(spec_d["targets"]), init="peft")
        lora = init_lora_ling(config, spec, key)
        leaves, td = jax.tree.flatten(lora)
        keys = jax.random.split(jax.random.fold_in(key, 1000), len(leaves))
        return jax.tree.unflatten(td, [
            l if l.ndim == 0
            else 0.02 * jax.random.normal(k, l.shape, jnp.float32)
            for l, k in zip(leaves, keys)])

    return jax.jit(make)(jax.random.PRNGKey(seed))


def tail_positions(config, name: str, K: int) -> list:
    """Positions, in the stack of target `name`, of the layers of its
    kind among the model's last K."""
    from mobilefinetuner_tpu.models.ling_hybrid import kind_index
    L = config.num_hidden_layers
    at = kind_index(config)
    return [at[l] for l in range(L - K, L)
            if config.layer_kind(l) == name.split("_")[0]]


def program_side(cell, config, params, lora, ids, ids_g, pos_idx, names,
                 K: int) -> dict:
    """What the timed path's own code gives for the sample (numpy)."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.cli.train_lora_ling import LOSS_CHUNKS
    from mobilefinetuner_tpu.models import ling_hybrid
    from mobilefinetuner_tpu.ops.loss import chunked_lm_cross_entropy_sum
    tr = cell["traffic_file"]
    dt = jnp.bfloat16 if argv_value(tr["argv"], "--dtype") == "bfloat16" \
        else jnp.float32
    remat = "--remat" in tr["argv"]
    lora_impl = argv_value(tr["argv"], "--lora_impl", "auto")

    def loss_of(p, l, x):
        h, aux = ling_hybrid.hidden_states(
            config, p, x, attention_mask=jnp.ones_like(x, jnp.float32),
            lora=l, compute_dtype=dt, remat=remat, lora_impl=lora_impl,
            with_aux=True)
        s, n = chunked_lm_cross_entropy_sum(h, p["lm_head"], x,
                                            num_chunks=LOSS_CHUNKS)
        return s / n, (h, aux)

    @jax.jit
    def sample(p, l, x):
        loss, (h, aux) = loss_of(p, l, x)
        logits = h[:, pos_idx] @ p["lm_head"].astype(dt).T
        return logits, loss, aux

    @jax.jit
    def grad(p, l, x):
        (_, (_, aux)), g = jax.value_and_grad(
            lambda l_: loss_of(p, l_, x), has_aux=True)(l)
        return g, aux["idx"]

    n_seq, S = ids.shape
    # one sequence a call: the batch the timed step runs
    lgs, lss, idxs, counters = [], [], [], None
    for i in range(n_seq):
        lg, ls, aux = sample(params, lora, jnp.asarray(ids[i:i + 1]))
        lgs.append(np.asarray(lg, np.float32)[0])
        lss.append(float(ls))
        idxs.append(np.asarray(aux["idx"]))       # [expert layers, S, k]
        counters = jax.tree.map(float, aux["counters"])
    g, idx_g = grad(params, lora, jnp.asarray(ids_g))
    grads = {}
    for n in names:
        pos = tail_positions(config, n.split(".")[0], K)
        grads[n] = np.asarray(get_leaf(g["blocks"], n), np.float32)[pos]
    return {"logits": np.stack(lgs), "loss": float(np.mean(lss)),
            "idx": np.stack(idxs), "idx_g": np.asarray(idx_g),
            "grads": grads, "counters": counters}


def disagreement(cfg: dict, prog_idx, routings) -> dict:
    return correct_ling.routing_disagreement_grouped(
        prog_idx, np.stack([np.asarray(r["biased"]) for r in routings]),
        np.stack([np.asarray(r["groups"]) for r in routings]),
        cfg["topk_group"])


def reference_side(cfg: dict, host_params, lora, ids, ids_g, pos_idx,
                   names, K: int, prog: dict) -> dict:
    """The reference under the program's routing; arrays to keep."""
    import jax.numpy as jnp
    from reference import ling_hybrid as ref
    L = cfg["num_hidden_layers"]
    n_seq = ids.shape[0]
    hs, routings = ref.run_layers(
        cfg, host_params, [ref.embed(cfg, host_params, jnp.asarray(x))
                           for x in ids],
        range(L), lora, [prog["idx"][i] for i in range(n_seq)])
    want, losses, ds = [], [], []
    for i in range(n_seq):
        lg = ref.head(cfg, host_params, hs[i])
        losses.append(float(ref.loss(lg, jnp.asarray(ids[i]))))
        want.append(np.asarray(lg[pos_idx]))
        ds.append(disagreement(cfg, prog["idx"][i], routings[i]))
    del hs, routings, lg
    keep = {"logits": np.stack(want), "loss": np.float64(np.mean(losses)),
            "route_share": np.float64(max(d["share"] for d in ds)),
            "route_gap": np.float64(max(d["max_gap"] for d in ds)),
            "route_group_share": np.float64(max(d["group_share"]
                                                for d in ds)),
            "idx": prog["idx"], "idx_g": prog["idx_g"]}
    mid, _ = ref.run_layers(
        cfg, host_params, [ref.embed(cfg, host_params,
                                     jnp.asarray(ids_g[0]))],
        range(L - K), lora, [prog["idx_g"]])
    g = ref.tail_grad(cfg, host_params, mid[0], jnp.asarray(ids_g[0]), lora,
                      prog["idx_g"], K)
    for nme in names:
        keep[f"grad:{nme}"] = np.asarray(get_leaf(g, nme))
    return keep


def checks_of(logits, loss: float, grads: dict, want, route_share: float,
              route_gap: float) -> dict:
    """Every reading of the comparison beside its limit, for
    correct_ling.verdict: `logits`, `loss` and `grads` {leaf: array}
    against the kept reference `want` ("logits", "loss", "grad:<leaf>"),
    and the routing's two. tools/lower_precision_ling.py puts the
    reference in a lower precision through the same function."""
    checks = {
        "logits_rms": (correct_ling.rms_err(logits, want["logits"]),
                       correct_ling.LOGIT_TOL_RMS, "max"),
        "loss": (abs(loss - float(want["loss"])), correct_ling.LOSS_TOL,
                 "max"),
        "route_share": (route_share, correct_ling.ROUTE_SHARE_TOL, "max"),
        "route_gap": (route_gap, correct_ling.ROUTE_GAP_TOL, "max")}
    for n, g in grads.items():
        checks[f"grad_rel:{n}"] = (
            correct_ling.rel_max(g, want[f"grad:{n}"]),
            correct_ling.GRAD_TOL, "max")
        checks[f"grad_cos:{n}"] = (
            correct_ling.cosine(g, want[f"grad:{n}"]),
            correct_ling.GRAD_COS, "min")
    return checks


def sample_ids(seed: int, vocab: int, n_seq: int, S: int, Sg: int):
    rng = np.random.default_rng([int(seed), 0xC1])
    return (rng.integers(1, vocab - 1, (n_seq, S)).astype(np.int32),
            rng.integers(1, vocab - 1, (1, Sg)).astype(np.int32),
            np.unique(np.linspace(0, S - 1, 64).astype(int)))


def compare(args, cell, config, model_dir) -> dict:
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.io.checkpoints import load_ling_hybrid
    tr, cfgfile = cell["traffic_file"], cell["config_file"]
    ck = tr["correct"]
    S = int(argv_value(tr["argv"], "--seq_len"))
    Sg, K = int(ck["grad_seq_len"]), int(ck.get("grad_tail_layers", 2))
    n_seq = int(ck.get("n_seq", 2))
    names = list(ck["grad_leaves"])
    ids, ids_g, pos_idx = sample_ids(args.seed, config.vocab_size, n_seq, S,
                                     Sg)
    t = time.perf_counter()
    _, host_params = load_ling_hybrid(model_dir, config=config)
    t_load = time.perf_counter() - t
    lora = seeded_lora(config, tr["lora"], args.seed)
    # ---- program side, every run ---------------------------------------
    t = time.perf_counter()
    params = jax.tree.map(jnp.asarray, host_params)
    prog = program_side(cell, config, params, lora, ids, ids_g, pos_idx,
                        names, K)
    jax.tree.map(lambda a: a.delete(), params)
    del params
    t_prog = time.perf_counter() - t
    # ---- reference side (kept with the fixture per cell and seed) ------
    ref_file = os.path.join(os.path.dirname(model_dir),
                            f"reference-{cell['name']}-seed{args.seed}.npz")
    t = time.perf_counter()
    want = None
    if os.path.exists(ref_file):
        z = np.load(ref_file)
        if np.array_equal(z["idx"], prog["idx"]) \
                and np.array_equal(z["idx_g"], prog["idx_g"]) \
                and all(f"grad:{n}" in z.files for n in names):
            want = {k: z[k] for k in z.files}
    ref_cached = want is not None
    if want is None:
        cfg = {**hf_config(cfgfile), "experts_held": config.experts_held}
        want = reference_side(cfg, host_params, lora, ids, ids_g, pos_idx,
                              names, K, prog)
        os.makedirs(os.path.dirname(ref_file), exist_ok=True)
        np.savez(ref_file, **want)
    t_ref = time.perf_counter() - t
    checks = checks_of(prog["logits"], prog["loss"], prog["grads"], want,
                       float(want["route_share"]), float(want["route_gap"]))
    v = correct_ling.verdict(checks)
    v.update(reference_cached=ref_cached, reference_s=round(t_ref, 2),
             program_s=round(t_prog, 2), load_s=round(t_load, 2),
             loss_program=prog["loss"], loss_reference=float(want["loss"]),
             logits_rel_max=correct_ling.rel_max(prog["logits"],
                                                 want["logits"]),
             route_group_share=float(want["route_group_share"]),
             counters=prog["counters"],
             attention_impl=config.attention_impl, S=S, grad_S=Sg,
             grad_tail_layers=K)
    return v


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--model_dir", required=True)
    ap.add_argument("--search", default="")
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args()
    # before anything is drawn or written: a checkout without the family
    # ends here
    from mobilefinetuner_tpu.models import ling_hybrid  # noqa: F401
    from mobilefinetuner_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = common.load_cell(args.workload,
                            [s for s in args.search.split(os.pathsep) if s])
    try:
        device = common.require_chip(cell["chips"], bool(args.rehearse))
    except common.BenchError as e:
        out(phase="device", ok=False, error=str(e))
        return 2
    out(phase="device", ok=True, device=device)
    config = model_config(cell["config_file"])
    if not fixture_ok(args.model_dir, config, cell["config_file"]):
        secs = write_model_dir(args.model_dir, cell["config_file"], config)
        out(phase="fixture", ok=True, written=True, seconds=round(secs, 2),
            bytes=os.path.getsize(os.path.join(args.model_dir,
                                               "model.safetensors")))
    else:
        out(phase="fixture", ok=True, written=False)
    v = compare(args, cell, config, args.model_dir)
    out(phase="correct", **v)
    return 0 if v["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
