#!/usr/bin/env python3
"""The JAX-touching child of Phi-4-mini-flash-reasoning's train cell, run
BEFORE the timed child (harness/train_cli_phi4flash.py starts it), as
harness/train_child_ling.py is for its family — whose generic helpers
(`out`, `write_tokenizer`, `get_leaf`, `sample_ids`) are imported; what
names a family is restated:

  1. imports the family's modules (a checkout without them fails here,
     in seconds: exit code 1 and no verdict) and finds the chip;
  2. writes the checkpoint directory the CLI loads, if the fixture is
     not there yet: seeded bf16 weights of the whole model, Mamba's own
     initialisation for the scan's parameters; ONE weight fixture per
     configuration whatever --seed (7.8 GB);
  3. compares the program with reference/phi4flash.py at the published
     widths, harness/correct_phi4flash.py holding the limits: the
     program first — logits at 64 positions and the loss over all
     200,064 rows of `n_seq` sequences at the timed length, through
     models/phi4flash.py with the cell's settings (bf16, remat, the
     chunked scan, the flash kernels at 64 against 128) and the CLI's
     chunked cross-entropy; then ONE step of the CLI's own train step
     (cli/expert_lora.make_nll, train/trainer.make_train_step: clip and
     Adam at the cell's lr) on one sequence of `grad_seq_len` tokens,
     read as the named adapter leaves' change and as the gradient the
     step computed — then the reference on the same device-resident bf16
     weights, upcast a layer at a time: the forward token by token, the
     gradient over the layers from `grad_first_layer` on (the layers
     below it run without autodiff), its update Adam's first step on
     that float32 gradient. The leaves lie BELOW the family's mechanisms
     (the traffic file's `correct.note` says how each is reached). The
     program's readings are kept beside the reference
     (`program_key`).

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

from harness import common, correct_phi4flash  # noqa: E402
from harness.traffic import argv_value  # noqa: E402
from harness.train_child_afmoe import (get_leaf, out,  # noqa: E402
                                       write_tokenizer)
from harness.train_child_ling import sample_ids  # noqa: E402

NOT_CONFIG = ("source", "reduced", "deployment", "assumed", "fixture",
              "name")


def hf_config(cfgfile: dict) -> dict:
    """The config.json the CLI reads: the file's published keys."""
    return {k: v for k, v in cfgfile.items() if k not in NOT_CONFIG}


def model_config(cfgfile: dict):
    """The program's configuration (the whole model: nothing is cut)."""
    from mobilefinetuner_tpu.core.config import Phi4FlashConfig
    raw = hf_config(cfgfile)
    return Phi4FlashConfig(**{f.name: raw[f.name]
                              for f in dataclasses.fields(Phi4FlashConfig)
                              if f.name in raw})


def fixture_mark(config, cfgfile: dict) -> dict:
    return {"fixture": cfgfile["fixture"],
            "layer_kinds": config.layer_kinds,
            "hidden_size": config.hidden_size,
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
    from mobilefinetuner_tpu.io.checkpoints import phi4flash_params_to_hf
    from mobilefinetuner_tpu.io.safetensors_io import save_safetensors
    from mobilefinetuner_tpu.models import phi4flash
    fx = cfgfile["fixture"]
    t = time.perf_counter()
    os.makedirs(d, exist_ok=True)
    params = jax.jit(lambda k: phi4flash.init_params(
        config, k, jnp.bfloat16, lambda_std=fx["lambda_std"]))(
        jax.random.PRNGKey(fx.get("weight_seed", 0)))
    host = jax.device_get(params)
    del params
    save_safetensors(os.path.join(d, "model.safetensors"),
                     phi4flash_params_to_hf(host, config),
                     metadata={"format": "pt"}, manifest=False)
    del host
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(hf_config(cfgfile), f)
    write_tokenizer(d, config.vocab_size, fx["words"])
    with open(os.path.join(d, "fixture.json"), "w") as f:
        json.dump(fixture_mark(config, cfgfile), f)
    return time.perf_counter() - t


def seeded_lora(config, spec_d: dict, seed: int):
    """An adapter of the shape the traffic file states with BOTH factors
    N(0, 0.02): a fresh adapter's B is zero, which would make the
    comparison blind to A."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.lora.lora import LoRASpec, init_lora_phi4flash

    def make(key):
        spec = LoRASpec(rank=spec_d["rank"], alpha=float(spec_d["alpha"]),
                        targets=list(spec_d["targets"]), init="peft")
        lora = init_lora_phi4flash(config, spec, key)
        leaves, td = jax.tree.flatten(lora)
        keys = jax.random.split(jax.random.fold_in(key, 1000), len(leaves))
        return jax.tree.unflatten(td, [
            l if l.ndim == 0
            else 0.02 * jax.random.normal(k, l.shape, jnp.float32)
            for l, k in zip(leaves, keys)])

    return jax.jit(make)(jax.random.PRNGKey(seed))


def leaf_at(name: str):
    """'in_proj.B@8' -> ('in_proj.B', 8): a leaf and its stack position."""
    leaf, at = name.split("@")
    return leaf, int(at)


def program_forward(cell, config, params, lora, ids, pos_idx) -> dict:
    """What the timed path's own model code gives for the sample at the
    timed length (numpy): logits at `pos_idx`, the loss over all rows
    through the CLI's chunked cross-entropy, the scan's state counter."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.cli.expert_lora import loss_chunks
    from mobilefinetuner_tpu.models import phi4flash
    from mobilefinetuner_tpu.ops.loss import chunked_lm_cross_entropy_sum
    tr = cell["traffic_file"]
    dt = jnp.bfloat16 if argv_value(tr["argv"], "--dtype") == "bfloat16" \
        else jnp.float32

    @jax.jit
    def sample(p, l, x):
        h, aux = phi4flash.hidden_states(
            config, p, x, attention_mask=jnp.ones_like(x, jnp.float32),
            lora=l, compute_dtype=dt, remat="--remat" in tr["argv"],
            lora_impl=argv_value(tr["argv"], "--lora_impl", "auto"),
            with_aux=True)
        s, n = chunked_lm_cross_entropy_sum(
            h, p["embed"], x,
            num_chunks=loss_chunks(*x.shape, config.vocab_size))
        logits = h[:, pos_idx] @ p["embed"].astype(dt).T
        return logits, s / n, aux

    lgs, lss, absmax = [], [], []
    for i in range(ids.shape[0]):   # one sequence a call: the timed batch
        lg, ls, aux = sample(params, lora, jnp.asarray(ids[i:i + 1]))
        lgs.append(np.asarray(lg, np.float32)[0])
        lss.append(float(ls))
        absmax.append(float(aux["counters"]["max"]["ssm_state_absmax"]))
    return {"logits": np.stack(lgs), "loss": float(np.mean(lss)),
            "ssm_state_absmax": max(absmax)}


def cli_args(cell, model_dir: str, extra=()):
    """The CLI's flags as the traffic file's argv sets them (`extra`
    overrides: argparse keeps a flag's last value)."""
    from mobilefinetuner_tpu.cli.train_lora_phi4flash import build_parser
    return build_parser().parse_args(
        list(cell["traffic_file"]["argv"]) + list(extra)
        + ["--pretrained_dir", model_dir, "--data_dir", model_dir])


def program_step(cell, config, params, lora, ids_g, names, model_dir,
                 extra=()) -> dict:
    """ONE optimizer step of the CLI's own train step on one sequence
    (numpy): the step `cli/common.run_training` times (the family's loss
    from `cli/expert_lora.make_nll`, `train/trainer.make_train_step`'s
    clip and Adam at the cell's lr, from a fresh optimizer state), at
    the cell's settings but the length of `ids_g`. Returns the named
    leaves' change (`updates`), their gradient as the step computed it
    (`grads`: Adam's first moment after one step is (1 - beta1) times
    the clipped gradient; the clip is undone by the step's own pre-clip
    norm) and the step's metrics."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.cli import common as cli
    from mobilefinetuner_tpu.cli import expert_lora
    from mobilefinetuner_tpu.cli.train_lora_phi4flash import FAMILY
    from mobilefinetuner_tpu.lora.lora import trainable_mask
    from mobilefinetuner_tpu.train.trainer import (init_optimizer,
                                                   make_train_step)
    args = cli_args(cell, model_dir,
                    ["--seq_len", str(ids_g.shape[1]), *extra])
    cfg = FAMILY.resolve(args, config)
    # the first step of a cosine schedule without warmup is the lr itself
    tc = cli.train_config_from_args(args, total_steps=1)
    nll = expert_lora.make_nll(FAMILY, args, cfg, lambda p: p,
                               cli.compute_dtype_from_args(args),
                               tc.grad_accum_steps)
    mask = trainable_mask(lora)
    step = make_train_step(lambda l, f, mb: nll(l, f, mb, True), tc,
                           mask=mask, donate=False)
    x = jnp.asarray(ids_g)
    batch = {"input_ids": x, "labels": x,
             "attention_mask": jnp.ones(x.shape, jnp.float32)}
    new, opt, metrics = step(lora, params, init_optimizer(lora, tc, mask),
                             batch, jnp.int32(0))
    metrics = {k: float(v) for k, v in metrics.items()
               if not isinstance(v, dict)}     # the counters ride as dicts
    adam = tc.adam()
    unclip = max(1.0, metrics["grad_norm"] / tc.clip_grad_norm) \
        if tc.clip_grad_norm else 1.0
    out = {"updates": {}, "grads": {}, "metrics": metrics}
    for n in names:
        leaf, at = leaf_at(n)
        before = np.asarray(get_leaf(lora["blocks"], leaf)[at], np.float32)
        after = np.asarray(get_leaf(new["blocks"], leaf)[at], np.float32)
        out["updates"][n] = after - before
        out["grads"][n] = np.asarray(get_leaf(opt["m"]["blocks"], leaf)[at],
                                     np.float32) / (1 - adam.beta1) * unclip
    return out


def reference_forward(cfg: dict, params, lora, ids, pos_idx) -> dict:
    """The reference's logits at `pos_idx` and mean loss, on the
    device-resident bf16 weights upcast a layer at a time."""
    import jax.numpy as jnp
    from reference import phi4flash as ref
    L = cfg["num_hidden_layers"]
    want, losses = [], []
    for x in ids:
        h, _ = ref.run_layers(cfg, params, ref.embed(cfg, params,
                                                     jnp.asarray(x)),
                              range(L), lora)
        want.append(np.asarray(ref.head(cfg, params, h[pos_idx])))
        losses.append(float(ref.head_loss(cfg, params, h, jnp.asarray(x))))
        del h
    return {"logits": np.stack(want), "loss": np.float64(np.mean(losses))}


def reference_grads(cfg: dict, params, lora, ids_g, names,
                    first: int) -> dict:
    """The reference's gradient of each named leaf on `ids_g`'s one
    sequence, autodiff over layers first ... L - 1 ("grad:<leaf>")."""
    import jax.numpy as jnp
    from reference import phi4flash as ref
    L = cfg["num_hidden_layers"]
    x = jnp.asarray(ids_g[0])
    h, state = ref.run_layers(cfg, params, ref.embed(cfg, params, x),
                              range(first), lora)
    g = ref.tail_grad(cfg, params, h, state, x, lora, first)
    keep = {}
    for n in names:
        leaf, at = leaf_at(n)
        target = leaf.split(".")[0]
        lo = min(p for p in (ref.stack_position(cfg, target, l)
                             for l in range(first, L)) if p is not None)
        keep[f"grad:{n}"] = np.asarray(get_leaf(g, leaf)[at - lo])
    return keep


def reference_side(cfg: dict, params, lora, ids, ids_g, pos_idx, names,
                   first: int) -> dict:
    """The reference on the device-resident bf16 weights; arrays to
    keep."""
    return {**reference_forward(cfg, params, lora, ids, pos_idx),
            **reference_grads(cfg, params, lora, ids_g, names, first)}


def checks_of(got: dict, want, lr: float) -> dict:
    """Every reading of the comparison beside its limit, for
    correct_phi4flash.verdict: `got` ("logits", "loss" where taken,
    "grads" and "updates" {leaf: array}) against the kept reference
    `want` ("logits", "loss", "grad:<leaf>"), the reference's update
    being Adam's first step on its float32 gradient at `lr`.
    tools/lower_precision_phi4flash.py hands the same function a
    reference in a lower precision and planted faults."""
    from reference.phi4flash import adam_first_update
    cp = correct_phi4flash
    checks = {}
    if "logits" in got:
        checks["logits_rms"] = (cp.rms_err(got["logits"], want["logits"]),
                                cp.LOGIT_TOL_RMS, "max")
        checks["loss"] = (abs(float(got["loss"]) - float(want["loss"])),
                          cp.LOSS_TOL, "max")
    for n, g in got["grads"].items():
        w = want[f"grad:{n}"]
        checks[f"grad_cos:{n}"] = (cp.cosine(g, w), cp.GRAD_COS, "min")
        du, dw = got["updates"][n], adam_first_update(w, lr)
        checks[f"update_cos:{n}"] = (cp.cosine(du, dw), cp.UPDATE_COS,
                                     "min")
        checks[f"update_norm:{n}"] = (cp.norm_gap(du, dw), cp.UPDATE_NORM,
                                      "max")
    return checks


def readings_of(got: dict, want) -> dict:
    """What is printed beside the verdict and compared with no limit:
    each leaf's gradient rel-max (PERF.md section 6 gives why)."""
    return {f"grad_rel:{n}": correct_phi4flash.rel_max(g, want[f"grad:{n}"])
            for n, g in got["grads"].items()}


def reference_key(cell: dict, seed: int) -> str:
    return f"reference-{cell['name']}-seed{seed}.npz"


def program_key(cell: dict, seed: int) -> str:
    """The program's readings of the last run at a seed, beside the kept
    reference: tools/lower_precision_phi4flash.py plants its faults in
    them."""
    return f"program-{cell['name']}-seed{seed}.npz"


def save_program(path: str, prog: dict) -> None:
    np.savez(path, logits=prog["logits"], loss=np.float64(prog["loss"]),
             **{f"grad:{n}": g for n, g in prog["grads"].items()},
             **{f"update:{n}": u for n, u in prog["updates"].items()})


def load_program(path: str) -> dict:
    z = np.load(path)
    return {"logits": z["logits"], "loss": float(z["loss"]),
            **{k: {f.split(":", 1)[1]: z[f] for f in z.files
                   if f.startswith(p + ":")}
               for k, p in (("grads", "grad"), ("updates", "update"))}}


def compare(args, cell, config, model_dir) -> dict:
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.io.checkpoints import load_phi4flash
    tr, cfgfile = cell["traffic_file"], cell["config_file"]
    ck = tr["correct"]
    S = int(argv_value(tr["argv"], "--seq_len"))
    Sg, first = int(ck["grad_seq_len"]), int(ck["grad_first_layer"])
    names = list(ck["grad_leaves"])
    ids, ids_g, pos_idx = sample_ids(args.seed, config.vocab_size,
                                     int(ck.get("n_seq", 2)), S, Sg)
    t = time.perf_counter()
    _, host_params = load_phi4flash(model_dir, config=config)
    params = jax.tree.map(jnp.asarray, host_params)
    del host_params
    t_load = time.perf_counter() - t
    lora = seeded_lora(config, tr["lora"], args.seed)
    # ---- program side, every run ---------------------------------------
    t = time.perf_counter()
    prog = program_forward(cell, config, params, lora, ids, pos_idx)
    step = program_step(cell, config, params, lora, ids_g, names,
                        model_dir)
    prog.update(grads=step["grads"], updates=step["updates"])
    t_prog = time.perf_counter() - t
    fixtures = os.path.dirname(model_dir)
    save_program(os.path.join(fixtures, program_key(cell, args.seed)), prog)
    # ---- reference side (kept with the fixture per cell and seed) ------
    ref_file = os.path.join(fixtures, reference_key(cell, args.seed))
    t = time.perf_counter()
    want = None
    if os.path.exists(ref_file):
        z = np.load(ref_file)
        if all(f"grad:{n}" in z.files for n in names):
            want = {k: z[k] for k in z.files}
    ref_cached = want is not None
    if want is None:
        want = reference_side(hf_config(cfgfile), params, lora, ids, ids_g,
                              pos_idx, names, first)
        os.makedirs(fixtures, exist_ok=True)
        np.savez(ref_file, **want)
    t_ref = time.perf_counter() - t
    jax.tree.map(lambda a: a.delete(), params)
    del params
    lr = cli_args(cell, model_dir).lr
    v = correct_phi4flash.verdict(checks_of(prog, want, lr))
    v.update(readings=readings_of(prog, want),
             reference_cached=ref_cached, reference_s=round(t_ref, 2),
             program_s=round(t_prog, 2), load_s=round(t_load, 2),
             loss_program=prog["loss"], loss_reference=float(want["loss"]),
             logits_rel_max=correct_phi4flash.rel_max(prog["logits"],
                                                      want["logits"]),
             ssm_state_absmax=prog["ssm_state_absmax"],
             step_metrics=step["metrics"],
             attention_impl=config.attention_impl, S=S, grad_S=Sg,
             grad_first_layer=first)
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
    from mobilefinetuner_tpu.models import phi4flash  # noqa: F401
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
