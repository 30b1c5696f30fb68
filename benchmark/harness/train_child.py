#!/usr/bin/env python3
"""The JAX-touching child of a train cell, run BEFORE the timed child:

  1. finds the chip (or exits 2 with no verdict);
  2. writes the HF-layout checkpoint directory the CLI loads, if the
     fixture of (configuration, weight seed) is not there yet — the
     weights drawn on the device in one jitted call;
  3. compares the program with the plain reference at the published
     widths on a seeded sample (harness/correct.py holds the tolerances):
     logits and loss of `n_seq` full-length sequences, and the gradient
     of two named leaves on one sequence of `grad_seq_len` tokens.

The program side is `gpt2.forward` + the CLI's loss with the cell's own
settings (bf16, remat, the LoRA targets, the attention path the cell's
`auto` rule takes). The reference side's gradient is taken over the last
`grad_tail_layers` blocks only — the forward below them runs without
autodiff — so that it fits beside 6.2 GB of float32 weights; it is the
exact gradient of those layers' slices, and the program's full backward
is sliced to match. The reference's outputs depend only on the seed and
the benchmark's files, so they are kept with the fixture.

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

from harness import common, correct  # noqa: E402
from harness.traffic import argv_value  # noqa: E402


def out(**rec):
    print(json.dumps(rec), flush=True)


def write_hf_dir(d: str, cfgfile: dict, config, wseed: int) -> float:
    """HF GPT2LMHeadModel directory with random weights (layout copied
    from tools/e2e_ppl_pipeline.write_synthetic_gpt2; config.json holds
    the published keys). Returns seconds taken."""
    import jax
    from mobilefinetuner_tpu.data.tokenizer_bpe import bytes_to_unicode
    from mobilefinetuner_tpu.io.checkpoints import gpt2_params_to_hf
    from mobilefinetuner_tpu.io.safetensors_io import save_safetensors
    from mobilefinetuner_tpu.models import gpt2
    t = time.perf_counter()
    os.makedirs(d, exist_ok=True)
    params = jax.jit(lambda k: gpt2.init_params(config, k))(
        jax.random.PRNGKey(wseed))
    sd = gpt2_params_to_hf(jax.device_get(params))
    del params
    save_safetensors(os.path.join(d, "model.safetensors"),
                     {k: np.asarray(v) for k, v in sd.items()})
    del sd
    keys = ("model_type", "vocab_size", "n_positions", "n_ctx", "n_embd",
            "n_layer", "n_head", "activation_function",
            "layer_norm_epsilon", "resid_pdrop", "embd_pdrop", "attn_pdrop")
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump({k: cfgfile[k] for k in keys if k in cfgfile}, f)
    byte_tokens = list(bytes_to_unicode().values())
    vocab = {t_: i for i, t_ in enumerate(byte_tokens)}
    for i in range(len(byte_tokens), config.vocab_size - 1):
        vocab[f"[unused{i}]"] = i
    vocab["<|endoftext|>"] = config.vocab_size - 1
    with open(os.path.join(d, "vocab.json"), "w") as f:
        json.dump(vocab, f)
    with open(os.path.join(d, "merges.txt"), "w") as f:
        f.write("#version: 0.2\n")
    with open(os.path.join(d, "fixture.json"), "w") as f:
        json.dump({"weight_seed": wseed, "n_embd": config.n_embd,
                   "n_layer": config.n_layer,
                   "vocab_size": config.vocab_size}, f)
    return time.perf_counter() - t


def fixture_ok(d: str, config, wseed: int) -> bool:
    try:
        m = common.load_json(os.path.join(d, "fixture.json"))
    except (OSError, ValueError):
        return False
    return (m == {"weight_seed": wseed, "n_embd": config.n_embd,
                  "n_layer": config.n_layer,
                  "vocab_size": config.vocab_size}
            and os.path.exists(os.path.join(d, "model.safetensors")))


def get_leaf(tree, dotted: str):
    for k in dotted.split("."):
        tree = tree[k]
    return tree


def seeded_lora(config, spec_d: dict, seed: int):
    import jax
    from harness.serve_engine import seeded_adapter
    return jax.jit(lambda k: seeded_adapter(config, spec_d, k))(
        jax.random.PRNGKey(seed))


def compare(args, cell, config, model_dir) -> dict:
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.io.checkpoints import load_gpt2
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
    from reference import gpt2 as ref
    tr, cfgfile = cell["traffic_file"], cell["config_file"]
    ck = tr["correct"]
    S = int(argv_value(tr["argv"], "--seq_len"))
    Sg, K = int(ck["grad_seq_len"]), int(ck.get("grad_tail_layers", 2))
    n_seq = int(ck.get("n_seq", 2))
    L = config.n_layer
    rng = np.random.default_rng([int(args.seed), 0xC1])
    ids = rng.integers(1, config.vocab_size - 1, (n_seq, S)).astype(np.int32)
    ids_g = rng.integers(1, config.vocab_size - 1, (1, Sg)).astype(np.int32)
    _, params = load_gpt2(model_dir)
    params = jax.tree.map(jnp.asarray, params)
    lora = seeded_lora(config, tr["lora"], args.seed) if tr.get("lora") \
        else None
    # the attention path the cell takes: its own `auto` rule on one
    # chip; over a mesh the CLI resolves `auto` to the XLA path
    pconf = config if cell["chips"] == 1 else dataclasses.replace(
        config, attention_impl="xla")
    dt = jnp.bfloat16 if argv_value(tr["argv"], "--dtype") == "bfloat16" \
        else jnp.float32
    remat = "--remat" in tr["argv"]

    def prog_logits(p, l, x):
        return gpt2.forward(pconf, p, x, attention_mask=jnp.ones_like(
            x, jnp.float32), lora=l, compute_dtype=dt, remat=remat,
            lora_impl=argv_value(tr["argv"], "--lora_impl", "auto"))

    def prog_loss(p, l, x):
        s, n = lm_cross_entropy_sum(prog_logits(p, l, x), x)
        return s / n

    # logits are compared at 64 positions spread over the whole length
    # (the loss covers every position); it keeps the kept file small
    pos_idx = np.unique(np.linspace(0, S - 1, 64).astype(int))
    # ---- reference side (kept with the fixture per cell and seed) -----
    names = list(ck["grad_leaves"])
    ref_file = os.path.join(os.path.dirname(model_dir),
                            f"reference-{cell['name']}-seed{args.seed}.npz")
    t = time.perf_counter()
    if os.path.exists(ref_file):
        z = np.load(ref_file)
        want = {k: z[k] for k in z.files}
        ref_cached = True
    else:
        ref_cached = False
        lg = np.stack([np.asarray(ref.forward(cfgfile, params, ids[i:i + 1],
                                              lora)[0])
                       for i in range(n_seq)])
        want = {"logits": lg[:, pos_idx], "loss": np.asarray(ref.loss(jnp.asarray(lg),
                                            jnp.asarray(ids)))}
        del lg
        lb = None if lora is None else lora["blocks"]
        x_mid = ref.run_blocks(cfgfile, params["blocks"],
                               ref.embed(params, ids_g), lb, range(L - K))
        tail = lambda t_: jax.tree.map(lambda a: a[L - K:] if a.ndim else a,
                                       t_)

        def tail_loss(blocks_t, lora_t):
            x = ref.run_blocks(cfgfile, blocks_t, x_mid, lora_t, range(K))
            return ref.loss(ref.head(cfgfile, params, x), jnp.asarray(ids_g))

        g_blocks, g_lora = jax.grad(tail_loss, argnums=(0, 1))(
            tail(params["blocks"]), None if lb is None else tail(lb))
        for nme in names:
            src = g_lora if lora is not None else g_blocks
            want[f"grad:{nme}"] = np.asarray(get_leaf(src, nme))
        os.makedirs(os.path.dirname(ref_file), exist_ok=True)
        np.savez(ref_file, **want)
    t_ref = time.perf_counter() - t
    # ---- program side, every run ---------------------------------------
    t = time.perf_counter()
    def prog_both(p, l, x):
        logits = prog_logits(p, l, x)
        s, n = lm_cross_entropy_sum(logits, x)
        return logits, s / n

    lg, got_loss = jax.jit(prog_both)(params, lora, jnp.asarray(ids))
    lg, got_loss = np.asarray(lg, np.float32), float(got_loss)
    checks = {
        "logits": (correct.rel_max(lg[:, pos_idx], want["logits"]),
                   correct.logit_tol(L), "max"),
        "loss": (abs(got_loss - float(want["loss"])), correct.LOSS_TOL,
                 "max")}
    del lg
    if lora is not None:
        g = jax.jit(jax.grad(prog_loss, argnums=1))(params, lora,
                                                    jnp.asarray(ids_g))
        got_g = {n: np.asarray(get_leaf(g["blocks"], n), np.float32)[L - K:]
                 for n in names}
    else:
        def loss_of_leaves(leaves, p, x):
            blocks = p["blocks"]
            for n, v in zip(names, leaves):
                a, b = n.split(".")
                blocks = {**blocks, a: {**blocks[a], b: v}}
            return prog_loss({**p, "blocks": blocks}, None, x)
        g = jax.jit(jax.grad(loss_of_leaves))(
            [get_leaf(params["blocks"], n) for n in names], params,
            jnp.asarray(ids_g))
        got_g = {n: np.asarray(v, np.float32)[L - K:]
                 for n, v in zip(names, g)}
    for n in names:
        checks[f"grad_rel:{n}"] = (
            correct.rel_max(got_g[n], want[f"grad:{n}"]),
            correct.GRAD_TOL, "max")
        checks[f"grad_cos:{n}"] = (
            correct.cosine(got_g[n], want[f"grad:{n}"]),
            correct.GRAD_COS, "min")
    v = correct.verdict(checks)
    v.update(reference_cached=ref_cached, reference_s=round(t_ref, 2),
             program_s=round(time.perf_counter() - t, 2),
             loss_program=got_loss, loss_reference=float(want["loss"]),
             attention_impl=pconf.attention_impl, S=S, grad_S=Sg,
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
    import jax
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
    from harness.serve_engine import model_config
    config = model_config(cell["config_file"])
    wseed = common.fixture_seed(args.seed)
    if not fixture_ok(args.model_dir, config, wseed):
        secs = write_hf_dir(args.model_dir, cell["config_file"], config,
                            wseed)
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
