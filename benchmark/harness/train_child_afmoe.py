#!/usr/bin/env python3
"""The JAX-touching child of an AFMoE train cell, run BEFORE the timed
child (harness/train_cli_afmoe.py starts it; harness/train_child.py is
GPT-2's):

  1. imports the family's modules (a checkout without them fails here,
     in seconds, before anything is drawn or written) and finds the chip
     (or exits 2 with no verdict);
  2. writes the HF-layout checkpoint directory the CLI loads, if the
     fixture is not there yet: the weights drawn on the device in one
     jitted call, stored bf16 with the held experts under their own
     numbers. ONE weight fixture per configuration (weight seed 0)
     whatever --seed — 8.6 GB; inputs, adapters and samples are --seed's;
  3. compares the program with the plain reference (reference/afmoe.py)
     at the published widths on a seeded sample, harness/correct_moe.py
     holding the limits:
       - the program runs first: hidden states of `n_seq` full-length
         sequences through models/afmoe.py with the cell's own settings
         (bf16, remat, LoRA targets, the attention path `auto` takes),
         logits at 64 positions spread over the length, the loss through
         the CLI's chunked cross-entropy, the router's choices of every
         expert layer, and the gradient of the named adapter leaves on
         one sequence of `grad_seq_len` tokens;
       - the program's weights leave the device, and the reference runs
         UNDER THE PROGRAM'S ROUTING (forced), a layer's float32 weights
         made once for all sequences; in the same pass it scores every
         expert layer for itself, and its own top-k is held against the
         program's choices (near-ties only, and few);
       - the reference's gradient is taken over the last
         `grad_tail_layers` layers only — the forward below them runs
         without autodiff — and the program's full backward is sliced
         to match.
     The reference's outputs depend on the seed, the benchmark's files
     and the routing it was forced to; they are kept beside the fixture
     with that routing and reused while the program still routes so.

Prints one JSON line per phase; the last line is the verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import numpy as np  # noqa: E402

from harness import common, correct_moe  # noqa: E402
from harness.traffic import argv_value  # noqa: E402

WEIGHT_SEED = 0
CONFIG_KEYS = (
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "num_dense_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "num_experts_per_tok", "num_shared_experts", "score_func", "route_norm",
    "route_scale", "sliding_window", "global_attn_every_n_layers",
    "layer_types", "rope_theta", "rms_norm_eps", "mup_enabled",
    "tie_word_embeddings", "max_position_embeddings", "hidden_act")


def out(**rec):
    print(json.dumps(rec), flush=True)


def hf_config(cfgfile: dict) -> dict:
    """The config.json the CLI reads: the file's keys, with the router at
    its published width (`num_experts` in the file counts the experts
    HELD; the CLI is told the deployment by its two flags)."""
    return {**{k: cfgfile[k] for k in CONFIG_KEYS if k in cfgfile},
            "num_experts": cfgfile["deployment"]["router_width"]}


def model_config(cfgfile: dict):
    """The program's configuration of this chip's share."""
    from mobilefinetuner_tpu.core.config import AfmoeConfig
    import dataclasses
    raw = hf_config(cfgfile)
    dep = cfgfile["deployment"]
    c = AfmoeConfig(**{f.name: raw[f.name]
                       for f in dataclasses.fields(AfmoeConfig)
                       if f.name in raw})
    c = c.with_expert_parallel(dep["expert_parallel"], dep["expert_rank"])
    assert c.experts_held[1] == cfgfile["num_experts"], c.experts_held
    return c


def fixture_mark(config, cfgfile: dict) -> dict:
    return {"weight_seed": WEIGHT_SEED, "fixture": cfgfile["fixture"],
            "hidden_size": config.hidden_size,
            "num_hidden_layers": config.num_hidden_layers,
            "experts_held": list(config.experts_held),
            "vocab_size": config.vocab_size}


def fixture_ok(d: str, config, cfgfile: dict) -> bool:
    try:
        m = common.load_json(os.path.join(d, "fixture.json"))
    except (OSError, ValueError):
        return False
    return m == fixture_mark(config, cfgfile) \
        and os.path.exists(os.path.join(d, "model.safetensors"))


def write_hf_dir(d: str, cfgfile: dict, config) -> float:
    """HF-layout afmoe directory with seeded bf16 weights, the byte-level
    stand-in tokenizer of vocab_size entries; seconds taken."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.io.checkpoints import afmoe_params_to_hf
    from mobilefinetuner_tpu.io.safetensors_io import save_safetensors
    from mobilefinetuner_tpu.models import afmoe
    t = time.perf_counter()
    os.makedirs(d, exist_ok=True)
    params = jax.jit(lambda k: afmoe.init_params(
        config, k, jnp.bfloat16,
        post_norm_scale=cfgfile["fixture"]["post_norm_scale"],
        expert_bias_std=cfgfile["fixture"]["expert_bias_std"]))(
        jax.random.PRNGKey(WEIGHT_SEED))
    host = jax.device_get(params)
    del params
    save_safetensors(os.path.join(d, "model.safetensors"),
                     afmoe_params_to_hf(host, config.experts_held[0]),
                     metadata={"format": "pt"}, manifest=False)
    del host
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(hf_config(cfgfile), f)
    write_tokenizer(d, config.vocab_size, cfgfile["fixture"]["words"])
    with open(os.path.join(d, "fixture.json"), "w") as f:
        json.dump(fixture_mark(config, cfgfile), f)
    return time.perf_counter() - t


def write_tokenizer(d: str, vocab_size: int, words: int) -> None:
    """The stand-in tokenizer: byte-level BPE whose merges make each of
    the corpus's `words` words (train_cli_afmoe.word_name, with its
    leading space) ONE token — a merge for every prefix, left to right —
    then filler up to vocab_size - 1 and <|endoftext|> last."""
    from harness.train_cli_afmoe import word_name
    from mobilefinetuner_tpu.data.tokenizer_bpe import bytes_to_unicode
    vocab = {t: i for i, t in enumerate(bytes_to_unicode().values())}
    merges = []
    for i in range(words):
        w = "\u0120" + word_name(i)         # the byte-level space
        for j in range(2, len(w) + 1):
            if w[:j] not in vocab:
                vocab[w[:j]] = len(vocab)
                merges.append(f"{w[:j - 1]} {w[j - 1]}")
    assert len(vocab) < vocab_size, (len(vocab), vocab_size)
    for i in range(len(vocab), vocab_size - 1):
        vocab[f"[unused{i}]"] = i
    vocab["<|endoftext|>"] = vocab_size - 1
    with open(os.path.join(d, "vocab.json"), "w") as f:
        json.dump(vocab, f)
    with open(os.path.join(d, "merges.txt"), "w") as f:
        f.write("#version: 0.2\n" + "\n".join(merges) + "\n")


def seeded_lora(config, spec_d: dict, seed: int):
    """An adapter of the shape the traffic file states with BOTH factors
    N(0, 0.02): a fresh adapter's B is zero, which would make the
    comparison blind to A."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.lora.lora import LoRASpec, init_lora_afmoe

    def make(key):
        spec = LoRASpec(rank=spec_d["rank"], alpha=float(spec_d["alpha"]),
                        targets=list(spec_d["targets"]), init="peft")
        lora = init_lora_afmoe(config, spec, key)
        leaves, td = jax.tree.flatten(lora)
        keys = jax.random.split(jax.random.fold_in(key, 1000), len(leaves))
        return jax.tree.unflatten(td, [
            l if l.ndim == 0
            else 0.02 * jax.random.normal(k, l.shape, jnp.float32)
            for l, k in zip(leaves, keys)])

    return jax.jit(make)(jax.random.PRNGKey(seed))


def get_leaf(tree, dotted: str):
    for k in dotted.split("."):
        tree = tree[k]
    return tree


def program_side(cell, config, params, lora, ids, ids_g, pos_idx, names,
                 K: int) -> dict:
    """What the timed path's own code gives for the sample (numpy)."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.models import afmoe
    from mobilefinetuner_tpu.ops.loss import chunked_lm_cross_entropy_sum
    tr = cell["traffic_file"]
    dt = jnp.bfloat16 if argv_value(tr["argv"], "--dtype") == "bfloat16" \
        else jnp.float32
    remat = "--remat" in tr["argv"]
    lora_impl = argv_value(tr["argv"], "--lora_impl", "auto")
    from mobilefinetuner_tpu.cli.train_lora_afmoe import LOSS_CHUNKS

    def loss_of(p, l, x):
        h, aux = afmoe.hidden_states(
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
    lg, ls, aux = sample(params, lora, jnp.asarray(ids))
    # idx [expert layers, n_seq * S, k] -> per sequence
    idx = np.asarray(aux["idx"]).reshape(-1, n_seq, S, aux["idx"].shape[-1])
    g, idx_g = grad(params, lora, jnp.asarray(ids_g))
    L = config.num_hidden_layers
    return {"logits": np.asarray(lg, np.float32), "loss": float(ls),
            "idx": idx.transpose(1, 0, 2, 3), "idx_g": np.asarray(idx_g),
            "grads": {n: np.asarray(get_leaf(g["blocks"], n),
                                    np.float32)[L - K:] for n in names},
            "counters": jax.tree.map(float, aux["counters"])}


def reference_side(cfg: dict, host_params, lora, ids, ids_g, pos_idx,
                   names, K: int, prog: dict) -> dict:
    """The reference under the program's routing; arrays to keep."""
    import jax
    import jax.numpy as jnp
    from reference import afmoe as ref
    L = cfg["num_hidden_layers"]
    n_seq = ids.shape[0]
    hs, routings = ref.run_layers(
        cfg, host_params, [ref.embed(cfg, host_params, jnp.asarray(x))
                           for x in ids],
        range(L), lora, [prog["idx"][i] for i in range(n_seq)])
    want, losses, share, gap = [], [], [], []
    for i in range(n_seq):
        lg = ref.head(cfg, host_params, hs[i])
        losses.append(float(ref.loss(lg, jnp.asarray(ids[i]))))
        want.append(np.asarray(lg[pos_idx]))
        d = correct_moe.routing_disagreement(
            prog["idx"][i], np.stack([np.asarray(r["biased"])
                                      for r in routings[i]]))
        share.append(d["share"])
        gap.append(d["max_gap"])
    del hs, routings, lg
    keep = {"logits": np.stack(want), "loss": np.float64(np.mean(losses)),
            "route_share": np.float64(max(share)),
            "route_gap": np.float64(max(gap)),
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


def compare(args, cell, config, model_dir) -> dict:
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.io.checkpoints import load_afmoe
    tr, cfgfile = cell["traffic_file"], cell["config_file"]
    ck = tr["correct"]
    S = int(argv_value(tr["argv"], "--seq_len"))
    Sg, K = int(ck["grad_seq_len"]), int(ck.get("grad_tail_layers", 2))
    n_seq = int(ck.get("n_seq", 2))
    names = list(ck["grad_leaves"])
    rng = np.random.default_rng([int(args.seed), 0xC1])
    ids = rng.integers(1, config.vocab_size - 1, (n_seq, S)).astype(np.int32)
    ids_g = rng.integers(1, config.vocab_size - 1, (1, Sg)).astype(np.int32)
    pos_idx = np.unique(np.linspace(0, S - 1, 64).astype(int))
    t = time.perf_counter()
    _, host_params = load_afmoe(model_dir, config=config)
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
                and np.array_equal(z["idx_g"], prog["idx_g"]):
            want = {k: z[k] for k in z.files}
    ref_cached = want is not None
    if want is None:
        cfg = {**hf_config(cfgfile), "experts_held": config.experts_held}
        want = reference_side(cfg, host_params, lora, ids, ids_g, pos_idx,
                              names, K, prog)
        os.makedirs(os.path.dirname(ref_file), exist_ok=True)
        np.savez(ref_file, **want)
    t_ref = time.perf_counter() - t
    checks = {
        "logits_rms": (correct_moe.rms_err(prog["logits"], want["logits"]),
                       correct_moe.LOGIT_TOL_RMS, "max"),
        "loss": (abs(prog["loss"] - float(want["loss"])),
                 correct_moe.LOSS_TOL, "max"),
        "route_share": (float(want["route_share"]),
                        correct_moe.ROUTE_SHARE_TOL, "max"),
        "route_gap": (float(want["route_gap"]), correct_moe.ROUTE_GAP_TOL,
                      "max")}
    for n in names:
        checks[f"grad_rel:{n}"] = (
            correct_moe.rel_max(prog["grads"][n], want[f"grad:{n}"]),
            correct_moe.GRAD_TOL, "max")
        checks[f"grad_cos:{n}"] = (
            correct_moe.cosine(prog["grads"][n], want[f"grad:{n}"]),
            correct_moe.GRAD_COS, "min")
    v = correct_moe.verdict(checks)
    v.update(reference_cached=ref_cached, reference_s=round(t_ref, 2),
             program_s=round(t_prog, 2), load_s=round(t_load, 2),
             loss_program=prog["loss"], loss_reference=float(want["loss"]),
             logits_rel_max=correct_moe.rel_max(prog["logits"],
                                                want["logits"]),
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
    from mobilefinetuner_tpu.models import afmoe  # noqa: F401
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
        secs = write_hf_dir(args.model_dir, cell["config_file"], config)
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
