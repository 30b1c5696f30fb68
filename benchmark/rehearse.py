#!/usr/bin/env python3
"""CPU rehearsal of both runners' control flow at a tiny preset.

  JAX_PLATFORMS=cpu python benchmark/rehearse.py [--only train|serve]

It writes a throw-away cell for each runner — three NEW files each
(configuration, traffic, workload) under .bench_work/rehearsal/ — and
runs them through the same `observe` / `end_to_end` / reader code as
run.py, with the device gate open. Every line it prints is labelled a
rehearsal; it never calls `common.result_line`, so it can never print a
metrics line, and a number from it is a number about XLA's CPU backend.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

from harness import common  # noqa: E402

TINY = {"source": "rehearsal preset, not a model", "model_type": "gpt2",
        "vocab_size": 300, "n_positions": 64, "n_ctx": 64, "n_embd": 32,
        "n_layer": 2, "n_head": 2, "activation_function": "gelu_new",
        "layer_norm_epsilon": 1e-05, "resid_pdrop": 0.1, "embd_pdrop": 0.1,
        "attn_pdrop": 0.1, "reduced": [], "assumed": {}}
T = ["attn_qkv", "attn_proj", "mlp_fc_in", "mlp_fc_out"]
AD = {"resident": 2, "rank": 4, "alpha": 8, "targets": T, "mix": "zipf",
      "zipf_s": 1.0, "include_base": True}
WIDE = dict(TINY, n_embd=128, n_head=4)   # leaves pass the FSDP size floor
CELLS = {
    "tiny_serve": ("serve_engine", {
        "kind": "serve",
        "arrivals": {"process": "poisson", "rate_per_s": 20.0},
        "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 2, "max": 16},
        "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                       "min": 2, "max": 8},
        "adapters": AD, "sampling": "greedy",
        "engine": {"num_slots": 4, "block_T": 8, "num_blocks": 64,
                   "max_prompt": 16, "max_new_tokens": 8,
                   "dtype": "float32"},
        "warmup": {"seconds": 1.0}}),
    "tiny_train": ("train_cli", {
        "kind": "train",
        "module": "mobilefinetuner_tpu.cli.gpt2_lora_finetune",
        "argv": ["--dtype", "float32", "--seq_len", "32", "--batch_size",
                 "2", "--remat", "--rank", "4", "--alpha", "8",
                 "--lora_targets", ",".join(T), "--lr", "1e-3",
                 "--no_model_dropout"],
        "out_flag": "--lora_out", "log_interval": 2, "profile_steps": 2,
        "lora": {"rank": 4, "alpha": 8, "targets": T},
        "corpus": {"train_words": 4000, "vocab_words": 50,
                   "p_follow": 0.55},
        "correct": {"n_seq": 2, "grad_seq_len": 32, "grad_tail_layers": 1,
                    "grad_leaves": ["attn_qkv.B", "mlp_fc_out.A"]}}),
    # four virtual CPU devices stand in for the four chips
    "tiny_fullft_fsdp4": ("train_cli", {
        "kind": "train",
        "module": "mobilefinetuner_tpu.cli.gpt2_full_finetune",
        "argv": ["--mesh_fsdp", "4", "--dtype", "float32", "--seq_len",
                 "32", "--batch_size", "8", "--remat", "--lr", "1e-3",
                 "--no_model_dropout"],
        "out_flag": "--output_path", "log_interval": 2, "profile_steps": 2,
        "corpus": {"train_words": 4000, "vocab_words": 50,
                   "p_follow": 0.55},
        "correct": {"n_seq": 2, "grad_seq_len": 32, "grad_tail_layers": 1,
                    "grad_leaves": ["attn.proj_w", "mlp.fc_w"]}}),
}


def line(**rec):
    print(json.dumps({"rehearsal": True, **rec}, default=str), flush=True)


def write_cell(root: str, name: str, kind: str, traffic: dict) -> None:
    chips = 4 if name.endswith("fsdp4") else 1
    cfg = ("tiny_wide", WIDE) if chips == 4 else ("tiny", TINY)
    for sub, fname, obj in (
            ("configs", *cfg), ("traffic", name, traffic),
            ("workloads", name, {"config": cfg[0], "traffic": name,
                                 "kind": kind, "chips": chips,
                                 "why": "rehearsal"})):
        os.makedirs(os.path.join(root, sub), exist_ok=True)
        with open(os.path.join(root, sub, f"{fname}.json"), "w") as f:
            json.dump(obj, f, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=("train", "serve", "fsdp4"))
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, default=1)
    args = ap.parse_args()
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        line(ok=False, error="set JAX_PLATFORMS=cpu: this is the CPU "
                             "rehearsal; the chip runs benchmark/run.py")
        return 2
    import importlib
    from harness import xplane
    root = os.path.join(common.WORK, "rehearsal")
    ok = True
    for name, (kind, traffic) in CELLS.items():
        if args.only and args.only not in name:
            continue
        write_cell(root, name, kind, traffic)
        cell = common.load_cell(name, [root])
        if cell["chips"] > 1:      # for the children only (no JAX here yet)
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                f"platform_device_count={cell['chips']}").strip()
        mod = importlib.import_module(f"harness.{kind}")
        try:
            obs = mod.observe(cell, 0, args.seconds, bool(args.trace),
                              rehearse=True)
        except common.BenchError as e:
            line(cell=name, ok=False, error=str(e))
            ok = False
            continue
        e2e = mod.end_to_end(obs)
        inwin = mod.in_window_correct(obs)
        trace = None
        if obs.get("trace_dir") and xplane.find_xplane(obs["trace_dir"]):
            trace = xplane.load(xplane.find_xplane(obs["trace_dir"]))
        ctx = {"peaks": {"flops_bf16": 1.0, "hbm_bytes_per_s": 1.0},
               "config": cell["config_file"],
               "traffic": cell["traffic_file"], "e2e": e2e}
        layer = {}
        for f in sorted(os.listdir(os.path.join(BENCH, "layer_metrics"))):
            spec = common.load_json(os.path.join(BENCH, "layer_metrics", f))
            v = common.resolve(spec["reader"])(obs, trace, ctx,
                                               **spec.get("args", {}))
            if v is not None:
                layer[f[:-5]] = v
        line(cell=name, kind=kind, ok=bool(obs["verdict"]["ok"]),
             verdict=obs["verdict"], in_window=inwin,
             cpu_values_not_metrics={"end_to_end": e2e, "per_layer": layer},
             trace_planes=[p.name for p in trace.planes] if trace else None,
             setup_phases=obs["setup_phases"])
        ok &= bool(obs["verdict"]["ok"])
    line(done=True, ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
