#!/usr/bin/env python3
"""The JAX-touching child of Xing4.0's train cell, run BEFORE the timed
child (harness/train_cli_xing4.py starts it), as
harness/train_child_ling.py is for its family — the generic helpers of
train_child_afmoe (`out`, `write_tokenizer`, `get_leaf`) are imported;
what names a family is restated:

  1. imports the family's modules (a checkout without them fails here,
     in seconds: exit code 1 and no verdict) and finds the chip;
  2. writes the checkpoint directory the CLI loads, if the fixture is
     not there yet: seeded bf16 weights; ONE weight fixture per
     configuration whatever --seed (8.0 GB; its seed is the
     configuration file's `fixture.weight_seed`);
  3. compares the program with reference/xing4.py at the published
     widths and the timed length, harness/correct_xing4.py holding the
     limits: the program first (hidden states of `n_seq` 8,192-token
     sequences through models/xing4.py with the cell's settings — bf16,
     remat, flash at two head sizes, the module at the cell's loss weight
     — both heads' logits at 64 positions, both losses through the CLI's
     own `add_second_loss` and chunked cross-entropy, every expert
     block's routing, the gradient of three adapter leaves on one
     sequence, ops/hyper_connections.sinkhorn on the probe); then the
     reference UNDER THE PROGRAM'S ROUTING, which in the same pass scores
     every expert block for itself; its gradient is taken over the last
     `grad_tail_layers` layers and the module, the forward below them
     runs without autodiff, and the program's full backward is sliced to
     those positions of the adapter stacks.

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

from harness import common, correct_xing4  # noqa: E402
from harness.traffic import argv_value  # noqa: E402
from harness.train_child_afmoe import (get_leaf, out,  # noqa: E402
                                       write_tokenizer)

WEIGHT_SEED = 0     # where the configuration's `fixture` names none
NOT_CONFIG = ("source", "reduced", "published", "deployment", "assumed",
              "fixture", "name")


def hf_config(cfgfile: dict) -> dict:
    """The config.json the CLI reads: the file's published keys (every
    expert is held: `n_routed_experts` is the router's width and the
    count held alike)."""
    return {k: v for k, v in cfgfile.items() if k not in NOT_CONFIG}


def model_config(cfgfile: dict, mtp_loss_weight=None):
    """The program's configuration of this chip's share, with the loss
    weight the cell's argv gives."""
    from mobilefinetuner_tpu.core.config import Xing4Config
    raw = hf_config(cfgfile)
    dep = cfgfile["deployment"]
    c = Xing4Config(**{f.name: raw[f.name]
                       for f in dataclasses.fields(Xing4Config)
                       if f.name in raw})
    c = c.with_expert_parallel(dep["expert_parallel"], dep["expert_rank"])
    assert c.experts_held == (0, cfgfile["n_routed_experts"]), c.experts_held
    if mtp_loss_weight is not None:
        c = dataclasses.replace(c, mtp_loss_weight=float(mtp_loss_weight))
    return c


def weight_seed(cfgfile: dict) -> int:
    """The ONE weight fixture's seed: the configuration file's
    (`fixture.weight_seed`: PERF.md section 6, PR 33, says how it was chosen)."""
    return int(cfgfile["fixture"].get("weight_seed", WEIGHT_SEED))


def fixture_mark(config, cfgfile: dict) -> dict:
    return {"weight_seed": weight_seed(cfgfile),
            "fixture": cfgfile["fixture"],
            "hidden_size": config.hidden_size,
            "num_hidden_layers": config.num_hidden_layers,
            "hc_mult": config.hc_mult,
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
    from mobilefinetuner_tpu.io.checkpoints import xing4_params_to_hf
    from mobilefinetuner_tpu.io.safetensors_io import save_safetensors
    from mobilefinetuner_tpu.models import xing4
    t = time.perf_counter()
    os.makedirs(d, exist_ok=True)
    params = jax.jit(lambda k: xing4.init_params(
        config, k, jnp.bfloat16,
        expert_bias_std=cfgfile["fixture"]["expert_bias_std"],
        embed_std=cfgfile["fixture"].get("embed_std", 0.02),
        hc_res_logit=cfgfile["fixture"].get("hc_res_logit", 8.0)))(
        jax.random.PRNGKey(weight_seed(cfgfile)))
    host = jax.device_get(params)
    del params
    save_safetensors(os.path.join(d, "model.safetensors"),
                     xing4_params_to_hf(host, config.experts_held[0]),
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
    from mobilefinetuner_tpu.lora.lora import LoRASpec, init_lora_xing4

    def make(key):
        spec = LoRASpec(rank=spec_d["rank"], alpha=float(spec_d["alpha"]),
                        targets=list(spec_d["targets"]), init="peft")
        lora = init_lora_xing4(config, spec, key)
        leaves, td = jax.tree.flatten(lora)
        keys = jax.random.split(jax.random.fold_in(key, 1000), len(leaves))
        return jax.tree.unflatten(td, [
            l if l.ndim == 0
            else 0.02 * jax.random.normal(k, l.shape, jnp.float32)
            for l, k in zip(leaves, keys)])

    return jax.jit(make)(jax.random.PRNGKey(seed))


def leaf_of(name: str):
    """"q_b_proj.B@1" -> ("q_b_proj.B", 1): an adapter leaf and which of
    the tail's blocks (0 = the first of the last K layers, K = the
    module's)."""
    leaf, at = name.split("@")
    return leaf, int(at)


def program_side(cell, config, params, lora, ids, ids_g, pos_idx, names,
                 K: int, probe) -> dict:
    """What the timed path's own code gives for the sample (numpy)."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.cli.expert_lora import (LOSS_CHUNKS,
                                                     add_second_loss)
    from mobilefinetuner_tpu.models import xing4
    from mobilefinetuner_tpu.ops import hyper_connections as hc
    from mobilefinetuner_tpu.ops.loss import chunked_lm_cross_entropy_sum
    tr = cell["traffic_file"]
    dt = jnp.bfloat16 if argv_value(tr["argv"], "--dtype") == "bfloat16" \
        else jnp.float32
    remat = "--remat" in tr["argv"]
    lora_impl = argv_value(tr["argv"], "--lora_impl", "auto")

    def loss_of(p, l, x):
        h, aux = xing4.hidden_states(
            config, p, x, attention_mask=jnp.ones_like(x, jnp.float32),
            lora=l, compute_dtype=dt, remat=remat, lora_impl=lora_impl,
            with_aux=True)
        s, n = chunked_lm_cross_entropy_sum(h, p["lm_head"], x,
                                            num_chunks=LOSS_CHUNKS)
        s, counters = add_second_loss(s, n, aux["counters"], aux["second"],
                                      p["lm_head"], x)
        return s / n, (h, aux, counters)

    @jax.jit
    def sample(p, l, x):
        _, (h, aux, counters) = loss_of(p, l, x)
        head = p["lm_head"].astype(dt).T
        return (h[:, pos_idx] @ head,
                aux["second"]["hidden"][:, pos_idx] @ head,
                aux["idx"], aux["idx_mtp"], counters)

    @jax.jit
    def grad(p, l, x):
        (_, (_, aux, _)), g = jax.value_and_grad(
            lambda l_: loss_of(p, l_, x), has_aux=True)(l)
        return g, aux["idx"], aux["idx_mtp"]

    n_seq, S = ids.shape
    L = config.num_hidden_layers
    # one sequence a call: the batch the timed step runs
    lgs, lms, idxs, idxm, main, second, row_err = [], [], [], [], [], [], 0.0
    for i in range(n_seq):
        lg, lm, ix, im, counters = sample(params, lora,
                                          jnp.asarray(ids[i:i + 1]))
        lgs.append(np.asarray(lg, np.float32)[0])
        lms.append(np.asarray(lm, np.float32)[0])
        idxs.append(np.asarray(ix))               # [expert layers, S, k]
        idxm.append(np.asarray(im))               # [S, k]
        main.append(float(counters["sum"]["loss_main"]))
        second.append(float(counters["sum"]["loss_mtp"]))
        row_err = max(row_err, float(counters["max"]["hc_res_row_err"]))
        counters = jax.tree.map(float, counters)
    g, idx_g, idxm_g = grad(params, lora, jnp.asarray(ids_g))
    grads = {}
    for name in names:
        leaf, at = leaf_of(name)
        grads[name] = np.asarray(get_leaf(g["blocks"], leaf),
                                 np.float32)[L - K + at]
    n = config.hc_mult
    res = hc.sinkhorn(jnp.asarray(probe).transpose(1, 2, 0),
                      config.hc_sinkhorn_iters, config.hc_eps,
                      config.mhc_h_res_clamp_min, config.mhc_h_res_clamp_max)
    return {"logits": np.stack(lgs), "logits_mtp": np.stack(lms),
            "loss_main": float(np.mean(main)),
            "loss_mtp": float(np.mean(second)),
            "idx": np.stack(idxs), "idx_mtp": np.stack(idxm),
            "idx_g": np.asarray(idx_g), "idx_mtp_g": np.asarray(idxm_g),
            "grads": grads, "counters": counters, "row_err": row_err,
            "sinkhorn": np.asarray(res).transpose(2, 0, 1)}


def reference_side(cfg: dict, host_params, lora, ids, ids_g, pos_idx,
                   names, K: int, prog: dict, weight: float, probe) -> dict:
    """The reference under the program's routing; arrays to keep."""
    import jax.numpy as jnp
    from reference import xing4 as ref
    n_seq = ids.shape[0]
    want, want_m, main, second, ds = [], [], [], [], []
    for i in range(n_seq):
        x = jnp.asarray(ids[i])
        lg, lm, infos = ref.forward(
            cfg, host_params, x, lora,
            ref.forced_of(cfg, prog["idx"][i], prog["idx_mtp"][i]))
        _, a, b = ref.total_loss(cfg, lg, lm, x, weight)
        main.append(float(a))
        second.append(float(b))
        want.append(np.asarray(lg[pos_idx]))
        want_m.append(np.asarray(lm[pos_idx]))
        ds.append(correct_xing4.routing_disagreement(
            np.concatenate([prog["idx"][i], prog["idx_mtp"][i][None]]),
            np.stack([np.asarray(f["biased"]) for f in infos
                      if "biased" in f])))
        del lg, lm, infos
    keep = {"logits": np.stack(want), "logits_mtp": np.stack(want_m),
            "loss_main": np.float64(np.mean(main)),
            "loss_mtp": np.float64(np.mean(second)),
            "route_share": np.float64(max(d["share"] for d in ds)),
            "route_gap": np.float64(max(d["max_gap"] for d in ds)),
            "idx": prog["idx"], "idx_mtp": prog["idx_mtp"],
            "idx_g": prog["idx_g"], "idx_mtp_g": prog["idx_mtp_g"],
            "sinkhorn": np.asarray(ref.sinkhorn(cfg, jnp.asarray(probe)))}
    L = cfg["num_hidden_layers"]
    forced = ref.forced_of(cfg, prog["idx_g"], prog["idx_mtp_g"])
    x = jnp.asarray(ids_g[0])
    mid, _ = ref.run_blocks(
        cfg, host_params, [ref.copy_in(cfg, ref.embed(cfg, host_params, x))],
        range(L - K), lora, [forced])
    g = ref.tail_grad(cfg, host_params, mid[0], x, lora, forced, K, weight)
    for name in names:
        leaf, at = leaf_of(name)
        keep[f"grad:{name}"] = np.asarray(get_leaf(g, leaf))[at]
    return keep


def checks_of(got: dict, want, route_share: float, route_gap: float,
              row_err: float) -> dict:
    """Every reading of the comparison beside its limit, for
    correct_xing4.verdict: `got` {"logits", "logits_mtp", "loss_main",
    "loss_mtp", "sinkhorn", "grads": {leaf: array}} against the kept
    reference `want` (the same keys, "grad:<leaf>"), the routing's two
    and the program's row error. tools/lower_precision_xing4.py puts the
    reference, lowered or broken, through the same function."""
    cx = correct_xing4
    checks = {
        "logits_rms": (cx.rms_err(got["logits"], want["logits"]),
                       cx.LOGIT_TOL_RMS, "max"),
        "logits_mtp_rms": (cx.rms_err(got["logits_mtp"],
                                      want["logits_mtp"]),
                           cx.LOGIT_TOL_RMS, "max"),
        "loss_main": (abs(got["loss_main"] - float(want["loss_main"])),
                      cx.LOSS_TOL, "max"),
        "loss_mtp": (abs(got["loss_mtp"] - float(want["loss_mtp"])),
                     cx.LOSS_TOL, "max"),
        "route_share": (route_share, cx.ROUTE_SHARE_TOL, "max"),
        "route_gap": (route_gap, cx.ROUTE_GAP_TOL, "max"),
        "sinkhorn_probe": (float(np.max(np.abs(
            np.asarray(got["sinkhorn"], np.float64) - want["sinkhorn"]))),
            cx.SINKHORN_TOL, "max"),
        "hc_res_row_err": (row_err, cx.ROW_ERR_TOL, "max")}
    for n, g in got["grads"].items():
        checks[f"grad_rel:{n}"] = (cx.rel_max(g, want[f"grad:{n}"]),
                                   cx.GRAD_TOL, "max")
        checks[f"grad_cos:{n}"] = (cx.cosine(g, want[f"grad:{n}"]),
                                   cx.GRAD_COS, "min")
    return checks


def sample_ids(seed: int, vocab: int, n_seq: int, S: int, Sg: int):
    rng = np.random.default_rng([int(seed), 0xC1])
    return (rng.integers(1, vocab - 1, (n_seq, S)).astype(np.int32),
            rng.integers(1, vocab - 1, (1, Sg)).astype(np.int32),
            np.unique(np.linspace(0, S - 3, 64).astype(int)))


def cell_config(cell):
    """The program's configuration at the cell's loss weight."""
    return model_config(cell["config_file"], argv_value(
        cell["traffic_file"]["argv"], "--mtp_loss_weight", 0.3))


def compare(args, cell, config, model_dir) -> dict:
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.io.checkpoints import load_xing4
    tr, cfgfile = cell["traffic_file"], cell["config_file"]
    ck = tr["correct"]
    S = int(argv_value(tr["argv"], "--seq_len"))
    Sg, K = int(ck["grad_seq_len"]), int(ck.get("grad_tail_layers", 2))
    n_seq = int(ck.get("n_seq", 2))
    names = list(ck["grad_leaves"])
    ids, ids_g, pos_idx = sample_ids(args.seed, config.vocab_size, n_seq, S,
                                     Sg)
    probe = correct_xing4.probe_logits(args.seed, config.hc_mult, S)
    t = time.perf_counter()
    _, host_params = load_xing4(model_dir, config=config)
    t_load = time.perf_counter() - t
    lora = seeded_lora(config, tr["lora"], args.seed)
    # ---- program side, every run ---------------------------------------
    t = time.perf_counter()
    params = jax.tree.map(jnp.asarray, host_params)
    prog = program_side(cell, config, params, lora, ids, ids_g, pos_idx,
                        names, K, probe)
    jax.tree.map(lambda a: a.delete(), params)
    del params
    t_prog = time.perf_counter() - t
    # ---- reference side (kept with the fixture per cell and seed) ------
    ref_file = os.path.join(os.path.dirname(model_dir),
                            f"reference-{cell['name']}-seed{args.seed}.npz")
    t = time.perf_counter()
    want = None
    routing = ("idx", "idx_mtp", "idx_g", "idx_mtp_g")
    if os.path.exists(ref_file):
        z = np.load(ref_file)
        if all(k in z.files and np.array_equal(z[k], prog[k])
               for k in routing) \
                and all(f"grad:{n}" in z.files for n in names):
            want = {k: z[k] for k in z.files}
    ref_cached = want is not None
    if want is None:
        cfg = {**hf_config(cfgfile), "experts_held": config.experts_held}
        want = reference_side(cfg, host_params, lora, ids, ids_g, pos_idx,
                              names, K, prog, config.mtp_loss_weight, probe)
        os.makedirs(os.path.dirname(ref_file), exist_ok=True)
        np.savez(ref_file, **want)
    t_ref = time.perf_counter() - t
    checks = checks_of(prog, want, float(want["route_share"]),
                       float(want["route_gap"]), prog["row_err"])
    v = correct_xing4.verdict(checks)
    v.update(reference_cached=ref_cached, reference_s=round(t_ref, 2),
             program_s=round(t_prog, 2), load_s=round(t_load, 2),
             loss_main_program=prog["loss_main"],
             loss_main_reference=float(want["loss_main"]),
             loss_mtp_program=prog["loss_mtp"],
             loss_mtp_reference=float(want["loss_mtp"]),
             logits_rel_max=correct_xing4.rel_max(prog["logits"],
                                                  want["logits"]),
             counters=prog["counters"],
             attention_impl=config.attention_impl, S=S, grad_S=Sg,
             grad_tail_layers=K, mtp_loss_weight=config.mtp_loss_weight)
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
    from mobilefinetuner_tpu.models import xing4  # noqa: F401
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
    config = cell_config(cell)
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
