"""Phi-4-mini-flash-reasoning's cell's benchmark code at a tiny size on the
CPU (run by hand: `JAX_PLATFORMS=cpu python -m pytest
benchmark/tests/test_phi4flash_cell.py`): the child's own comparison end
to end, the knob of the reference that tools/lower_precision_phi4flash.py
turns, the chip's readings through harness/correct_phi4flash.py's
limits, the FLOP and byte counts of harness/flops_phi4flash.py against a
hand count at the published widths, the readers of
harness/phi4flash_readers.py on a hand-made trace, and the cell's files
against the configuration's published keys and the cell's parameters."""
import argparse
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

from harness import (common, correct_phi4flash as cp,  # noqa: E402
                     flops_phi4flash as fl, phi4flash_readers as rd,
                     train_child_phi4flash as child)
from harness.xplane import Event, Line, Plane, Trace  # noqa: E402

TARGETS = ["q_proj", "k_proj", "v_proj", "o_proj", "in_proj", "out_proj",
           "gmu_in_proj", "gmu_out_proj"]
# the tiny preset's leaves below the mechanisms (P = 2 pairs): layer 4's
# in_proj (the memory), layer 5's k_proj / v_proj (the shared keys and
# values), layer 2's in_proj (the self-decoder's last Mamba layer)
LEAVES = ["in_proj.B@2", "k_proj.B@2", "v_proj.B@2", "in_proj.B@1"]


def tiny_cell(tmp_path) -> dict:
    """A throw-away cell of three new files: the family's tiny preset,
    float32."""
    from mobilefinetuner_tpu.core.config import Phi4FlashConfig
    raw = dataclasses.asdict(Phi4FlashConfig.tiny())
    # as the published file: the Mamba sizes are not keys of it (they are
    # `assumed`, and both sides take the convention)
    for k in ("attention_impl", "mamba_d_state", "mamba_d_conv",
              "mamba_expand", "mamba_dt_rank"):
        del raw[k]
    files = {
        "configs/tiny_phi4flash": {
            "source": "test preset, not a model",
            "model_type": "phi4flash", **raw, "vocab_size": 512,
            "reduced": [], "fixture": {"words": 30, "lambda_std": 0.1},
            "deployment": {"chips": 1}, "assumed": {}},
        "traffic/tiny_phi4flash_train": {
            "kind": "train",
            "module": "mobilefinetuner_tpu.cli.train_lora_phi4flash",
            "argv": ["--dtype", "float32", "--seq_len", "40",
                     "--batch_size", "1", "--remat", "--rank", "4",
                     "--alpha", "8"],
            "lora": {"rank": 4, "alpha": 8, "targets": TARGETS},
            "correct": {"n_seq": 2, "grad_seq_len": 40,
                        "grad_first_layer": 2, "grad_leaves": LEAVES}},
        "workloads/tiny_phi4flash_train": {
            "config": "tiny_phi4flash", "traffic": "tiny_phi4flash_train",
            "kind": "harness.train_cli_phi4flash", "chips": 1,
            "why": "test"}}
    for name, obj in files.items():
        p = tmp_path / f"{name}.json"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(obj))
    return common.load_cell("tiny_phi4flash_train", [str(tmp_path)])


def published() -> dict:
    return common.named("configs", "phi-4-mini-flash-reasoning")


def test_the_child_compares_program_and_reference_and_keeps_the_reference(
        tmp_path):
    cell = tiny_cell(tmp_path)
    config = child.model_config(cell["config_file"])
    assert config.layer_kinds == ["mamba", "swa", "mamba", "swa", "mamba",
                                  "full", "gmu", "cross"]
    model_dir = str(tmp_path / "fixtures" / "tiny-w0" / "phi4flash")
    child.write_model_dir(model_dir, cell["config_file"], config)
    assert child.fixture_ok(model_dir, config, cell["config_file"])
    args = argparse.Namespace(seed=2 ** 31 + 11)
    v = child.compare(args, cell, config, model_dir)
    assert v["ok"], v
    assert not v["reference_cached"]
    assert v["checks"]["logits_rms"]["value"] < 1e-4        # float32
    for leaf in LEAVES:
        assert v["readings"][f"grad_rel:{leaf}"] < 1e-3, leaf
        assert v["checks"][f"update_cos:{leaf}"]["value"] > 0.999, leaf
        assert v["checks"][f"update_norm:{leaf}"]["value"] < 1e-3, leaf
    assert v["ssm_state_absmax"] > 0
    assert v["step_metrics"]["lr"] == pytest.approx(1e-4)
    again = child.compare(args, cell, config, model_dir)
    assert again["ok"] and again["reference_cached"]
    # the program's readings are kept: a step that moves nothing, planted
    # in them, is refused through the same checks and verdict
    fixtures = os.path.dirname(model_dir)
    got = child.load_program(os.path.join(
        fixtures, child.program_key(cell, args.seed)))
    want = dict(np.load(os.path.join(
        fixtures, child.reference_key(cell, args.seed))))
    assert cp.verdict(child.checks_of(got, want, 1e-4))["ok"]
    got["updates"] = {n: 0 * u for n, u in got["updates"].items()}
    v = cp.verdict(child.checks_of(got, want, 1e-4))
    assert sorted(k for k, c in v["checks"].items() if not c["ok"]) \
        == sorted(f"update_{m}:{n}" for m in ("cos", "norm")
                  for n in LEAVES)


def test_the_state_knob_moves_the_reference(tmp_path):
    """What tools/lower_precision_phi4flash.py lowers — the scan's state
    and decay in bfloat16 — moves the reference's logits and gradients,
    and is off by default (at this size by little: 40 tokens, where the
    scan's readout is a small part of each Mamba layer's output; the
    chip's readings at 16,384 are what the limits answer to)."""
    import jax
    import jax.numpy as jnp
    from reference import phi4flash as ref
    cell = tiny_cell(tmp_path)
    config = child.model_config(cell["config_file"])
    from mobilefinetuner_tpu.models import phi4flash
    params = phi4flash.init_params(config, jax.random.PRNGKey(0))
    lora = child.seeded_lora(config, cell["traffic_file"]["lora"], 7)
    ids, ids_g, pos = child.sample_ids(5, 512, 2, 40, 40)
    cfg = child.hf_config(cell["config_file"])
    assert set(ref.LOWER.values()) == {jnp.float32}
    base = child.reference_side(cfg, params, lora, ids, ids_g, pos, LEAVES,
                                2)
    try:
        ref.LOWER["state"] = jnp.bfloat16
        jax.clear_caches()
        low = child.reference_side(cfg, params, lora, ids, ids_g, pos,
                                   LEAVES, 2)
    finally:
        ref.LOWER["state"] = jnp.float32
        jax.clear_caches()
    assert cp.rms_err(low["logits"], base["logits"]) > 1e-6
    assert cp.rel_max(low["grad:in_proj.B@1"], base["grad:in_proj.B@1"]) \
        > 1e-6


@pytest.mark.parametrize("variant", ["detached", "no_update",
                                     "program_f32"])
def test_the_tool_s_variants_through_the_child_s_checks(tmp_path, variant):
    """tools/lower_precision_phi4flash.py's faults and its float32
    program, at the tiny size on a kept run: the cross-decoder's reads
    detached turn the gradients of the leaves below them (the memory's
    in_proj, the shared keys' and values' k_proj / v_proj) past the
    cosine's limit; a step that moves nothing is refused by both update
    readings; the program in float32 passes (here it IS float32)."""
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import lower_precision_phi4flash as tool
    cell = tiny_cell(tmp_path)
    config = child.model_config(cell["config_file"])
    model_dir = str(tmp_path / "fixtures" / "tiny-w0" / "phi4flash")
    child.write_model_dir(model_dir, cell["config_file"], config)
    seed = 2 ** 31 + 13
    assert child.compare(argparse.Namespace(seed=seed), cell, config,
                         model_dir)["ok"]
    from mobilefinetuner_tpu.io.checkpoints import load_phi4flash
    _, params = load_phi4flash(model_dir, config=config)
    ck = cell["traffic_file"]["correct"]
    ids, ids_g, pos = child.sample_ids(seed, config.vocab_size, 2, 40, 40)
    z = dict(np.load(os.path.join(os.path.dirname(model_dir),
                                  child.reference_key(cell, seed))))
    lora = child.seeded_lora(config, cell["traffic_file"]["lora"], seed)
    got = tool.variant_readings(
        variant, cell, config, params, lora,
        (ids, ids_g, pos, LEAVES, int(ck["grad_first_layer"])), z,
        model_dir, seed, 1e-4)
    v = cp.verdict(child.checks_of(got, z, 1e-4))
    refused = sorted(k for k, c in v["checks"].items() if not c["ok"])
    if variant == "detached":
        assert {f"grad_cos:{n}" for n in LEAVES[:3]} <= set(refused), v
        assert "logits_rms" not in refused
    elif variant == "no_update":
        assert refused == sorted(f"update_{m}:{n}" for m in ("cos", "norm")
                                 for n in LEAVES)
    else:
        assert refused == [] and "logits_rms" not in v["checks"]


def test_counts_match_a_hand_count_at_the_published_widths():
    """The cell's arithmetic, re-reckoned from the configuration file and
    from the program's own parameter tree."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.models import phi4flash
    cfg = published()
    p, M = fl.matmul_params(cfg), 1e6
    assert round(p["mamba"] / M, 1) == 41.1     # in 26.2, out 13.1, x 1.0, dt 0.8
    assert round(p["swa"] / M, 1) == 19.7 and round(p["cross"] / M, 1) \
        == 13.1
    assert round(p["gmu"] / M, 1) == 26.2 and round(p["mlp"] / M, 1) \
        == 78.6
    assert round(p["head"] / M, 1) == 512.2
    assert fl.layer_counts(cfg) == {"mamba": 9, "swa": 8, "full": 1,
                                    "gmu": 7, "cross": 7}
    # the reference reads the file as the child hands it (no Mamba sizes:
    # they are `assumed`), and reaches the program's sizes
    from reference import phi4flash as ref
    rcfg = dict(ref.freeze(child.hf_config(cfg)))
    assert ref.dims(rcfg) == {"H": 2560, "di": 5120, "N": 16, "r": 160,
                              "d": 64, "nq": 40, "nkv": 20}
    c = child.model_config(cfg)
    tree = jax.eval_shape(lambda: phi4flash.init_params(
        c, jax.random.PRNGKey(0), jnp.bfloat16))
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
    assert total == fl.param_count(cfg)
    assert round(total / 1e9, 2) == 3.85            # 7.7 GB in bfloat16
    assert phi4flash.matmul_params_per_token(c) == fl.params_per_token(cfg)
    n_lora = fl.lora_params(cfg, 8, TARGETS)
    assert n_lora == 8 * (16 * (2560 + 2560) * 2 + 9 * (2560 + 1280) * 2
                          + 9 * ((2560 + 10240) + (5120 + 2560))
                          + 7 * ((2560 + 5120) + (5120 + 2560)))
    # the forward's matmuls 7.7 GF a token; attention 1.07 GF; a step's
    # required work 18.65 GF a token
    assert round(2 * fl.params_per_token(cfg) / 1e9, 1) == 7.7
    assert round(fl.attention_flops(cfg, 1, 16384) / 16384 / 1e9, 2) \
        == 1.07
    need = fl.required_train_flops(cfg, 1, 16384, n_lora=n_lora)
    assert round(need / 16384 / 1e9, 2) == 18.65
    # the scan's bytes: forward u (2 B), Delta, y (4 B) a channel and B,
    # C (16 x 4 B each) a token; backward dy, dDelta (4 B), du (2 B),
    # dB, dC; nine layers, plus dA_log and dD once a layer
    w = fl.ssm_scan_work(cfg, 1, 16384)
    tok = 2 * (5120 * 10 + 128)
    assert w["bytes"] == 9 * (16384 * tok + (5120 * 16 + 5120) * 4)
    assert w["flops"] == 3 * 7 * 5120 * 16 * 16384 * 9


def test_every_roofline_reads_under_100_at_the_least_time():
    """A share is least time over measured time: at a measured time equal
    to the least time each reads 100 %, never more, whichever bound."""
    cfg = published()
    peaks = common.peaks_for("TPU v5 lite")
    for work in (fl.ssm_scan_work(cfg, 1, 16384),
                 fl.flash_diff_work(cfg, 1, 16384, remat=True)):
        least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                           peaks)
        assert least > 0 and bound in ("compute", "memory")
    assert fl.roofline_seconds(*fl.ssm_scan_work(cfg, 1, 16384).values(),
                               peaks)[1] == "memory"


def hand_trace(cut: bool = False):
    """One optimizer step, an execution of the step's program: the flash
    forward twice (remat) and the merged backward once for each of the
    32 maps, and the family's scopes. `cut`: that step twice more, the
    profiler's window opening inside the first and closing inside the
    last (their ops partly held, their executions cut short)."""
    ops, mods, t = [], [], [0]

    def ev(name, us, text):
        ops.append(Event(name, t[0] * 1000, us * 1000, text))
        t[0] += us

    def step(keep=lambda name: True):
        t0 = t[0]
        for name, us, text, n in (
                ("flash_fwd", 100, "jit(step)/diff_attn/flash_fwd", 64),
                ("flash_bwd_merged", 250,
                 "jit(step)/diff_attn/flash_bwd_merged", 32),
                ("fusion.1", 3000, "jit(step)/ssm_scan/while/body/fusion",
                 1),
                ("fusion.2", 1000, "jit(step)/ssm_proj/dot_general", 1),
                ("fusion.3", 500, "jit(step)/ssm_conv/add", 1),
                ("fusion.4", 400, "jit(step)/gmu/dot_general", 1),
                ("fusion.5", 2000, "jit(step)/mlp/dot_general", 1)):
            for _ in range(n):
                if keep(name):
                    ev(name, us, text)
                else:
                    t[0] += us
        mods.append(Event("jit_step_fn(7)", t0 * 1000,
                          (t[0] - t0) * 1000, ""))

    if cut:      # the window opens after the forward of a first step
        step(lambda name: name != "flash_fwd")
        mods[-1] = mods[-1]._replace(
            start=mods[-1].start + 6_400_000,
            dur=mods[-1].dur - 6_400_000)
    step()
    t[0] += 500                                           # idle
    ev("copy.9", 1, "")
    if cut:      # and closes after the forward of a last one
        step(lambda name: name == "flash_fwd")
        mods[-1] = mods[-1]._replace(dur=6_400_000)
    return Trace([Plane("/device:TPU:0", [Line("XLA Ops", ops),
                                          Line("XLA Modules", mods)])])


def test_the_readers_read_a_hand_made_trace_and_the_counters():
    cell = common.load_cell("phi4flash_lora_long")
    flushes = [{"ssm_state_absmax": 2.0 + i} for i in range(3)]
    obs = {"kind": "train_cli", "B": 1, "S": 16384, "flushes": flushes}
    peaks = common.peaks_for("TPU v5 lite")
    ctx = {"peaks": peaks, "config": cell["config_file"],
           "traffic": cell["traffic_file"],
           "e2e": {"train_tokens_per_s_per_chip": 3000.0}}
    trace = hand_trace()
    cfg = ctx["config"]
    need = fl.required_train_flops(cfg, 1, 16384,
                                   n_lora=fl.lora_params(cfg, 8, TARGETS))
    assert rd.mfu_required_phi4flash(obs, None, ctx) \
        == pytest.approx(100 * 3000.0 * need / 16384 / 197e12)
    assert obs["notes"]["ssm_state_absmax"] == 3.0
    busy_us = 64 * 100 + 32 * 250 + 3000 + 1000 + 500 + 400 + 2000
    w = fl.ssm_scan_work(cfg, 1, 16384)
    least_ssm = max(w["flops"] / 197e12, w["bytes"] / 819e9)
    f = fl.flash_diff_work(cfg, 1, 16384, remat=True)
    least_flash = max(f["flops"] / 197e12, f["bytes"] / 819e9)
    # a trace cut inside its first and last step reads as the one whole
    # step it holds: the cut steps' ops (a backward without its forward,
    # a forward without its backward) count nowhere
    for cut in (False, True):
        trace = hand_trace(cut)
        assert rd.ssm_share_of_step(obs, trace, ctx) \
            == pytest.approx(100 * 4500 / busy_us)
        per = obs["notes"]["phi4flash_scope_s_per_step"]
        assert per["whole_steps_in_trace"] == 1
        assert per["ssm_scan"] == pytest.approx(3000e-6)
        assert per["diff_attn"] == pytest.approx(14400e-6)
        assert per["busy"] == pytest.approx(busy_us * 1e-6)
        assert rd.ssm_scan_roofline(obs, trace, ctx) \
            == pytest.approx(100 * least_ssm / 3000e-6)
        assert rd.flash_diff_roofline(obs, trace, ctx, fwd="flash_fwd",
                                      bwd="flash_bwd_") \
            == pytest.approx(100 * least_flash / 14400e-6)
    # nothing to read: None, not an error — no trace, no counters, or
    # another family's cell (the parent's checkout has no such scope)
    assert rd.ssm_share_of_step(obs, None, ctx) is None
    empty = Trace([Plane("/device:TPU:0", [Line("XLA Ops", [
        Event("fusion.1", 0, 1000, "jit(step)/mlp/dot_general")])])])
    assert rd.ssm_share_of_step(obs, empty, ctx) is None
    assert rd.ssm_scan_roofline(obs, empty, ctx) is None
    assert rd.flash_diff_roofline(obs, empty, ctx, fwd="flash_fwd",
                                  bwd="flash_bwd_") is None
    other = dict(ctx, config=common.named("configs",
                                          "trinity-large-preview"),
                 traffic=common.named("traffic", "lora_s8192_ep8"))
    for read in (rd.mfu_required_phi4flash, rd.ssm_share_of_step,
                 rd.ssm_scan_roofline):
        assert read(obs, trace, other) is None


def test_the_cell_s_files_agree_with_benchmark_json_and_the_catalog():
    b = common.benchmark_json()
    cell = common.load_cell("phi4flash_lora_long")
    w = next(x for x in b["workloads"] if x["name"] == cell["name"])
    assert w == {k: cell[k] for k in ("name", "config", "traffic", "chips",
                                      "why")}
    assert w["chips"] == 1 and len(w["why"]) <= 200
    c = next(x for x in b["configs"] if x["name"] == cell["config"])
    cf, tr = cell["config_file"], cell["traffic_file"]
    assert c["source"] == cf["source"] and c["reduced"] == cf["reduced"] \
        == []
    assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    assert cell["kind"] == "harness.train_cli_phi4flash"
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
               "intermediate_size": 10240, "layer_norm_eps": 1e-05,
               "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40,
               "num_hidden_layers": 32, "num_key_value_heads": 20,
               "resid_pdrop": 0, "sliding_window": 512,
               "tie_word_embeddings": True, "mlp_bias": False,
               "lm_head_bias": False, "vocab_size": 200064}
    assert {k: cf[k] for k in catalog} == catalog
    assert tr["argv"] == ["--dtype", "bfloat16", "--seq_len", "16384",
                          "--batch_size", "1", "--remat", "--rank", "8",
                          "--alpha", "16", "--lr", "1e-4"]
    assert tr["log_interval"] == 1
    assert tr["corpus"]["train_words"] == 2000000
    assert tr["module"] == "mobilefinetuner_tpu.cli.train_lora_phi4flash"
    assert sorted(tr["lora"]["targets"]) == sorted(TARGETS)
    ck = tr["correct"]
    assert ck["grad_seq_len"] >= 4096
    # the leaves lie BELOW the mechanisms: the memory's Mamba layer (16,
    # position 8 of in_proj), the full layer's keys and values (17,
    # position 8 of k_proj / v_proj), a self-decoder Mamba layer (14,
    # position 7), all above grad_first_layer
    assert ck["grad_leaves"] == ["in_proj.B@8", "k_proj.B@8", "v_proj.B@8",
                                 "in_proj.B@7"]
    assert ck["grad_first_layer"] == 14
    listed = {m["name"] for m in common.metrics_for(cell["name"],
                                                    "per_layer")}
    assert {"mfu_required_phi4flash", "ssm_share_of_step",
            "ssm_scan_roofline", "flash_diff_roofline", "train_step_ms",
            "train_host_wait_share", "train_stall_share",
            "peak_hbm_gb_train", "window_compiles_train",
            "device_idle_share_train"} == listed
    for name in ("mfu_required_phi4flash", "ssm_share_of_step",
                 "ssm_scan_roofline", "flash_diff_roofline"):
        m = next(x for x in b["per_layer"] if x["name"] == name)
        f = common.named("layer_metrics", name)
        assert m["workloads"] == f["workloads"] == ["phi4flash_lora_long"]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (f["unit"], f["better"], f["source"],
                                f["layer"], f["moves"])


# The chip's readings at the published widths (PERF.md section 6, the
# phi4flash cell's entry; the logs are not committed): the bf16 program
# against the float32 reference, each seed's worst over the four leaves
# (the update readings from ...41 on, when the check began to read the
# CLI's train step); the reference with the scan's state and decay in
# bfloat16, the reference's gradients with the cross-decoder's shared
# reads detached, and the program's step with its update dropped, all
# through tools/lower_precision_phi4flash.py. A fault's grad_cos and
# update_cos are those of the leaves below the shared reads.
CHIP_READINGS = {
    "program_4000000021": {"logits_rms": 0.29724, "loss": 6.68e-4,
                           "grad_cos": 0.99544},
    "program_4000000022": {"logits_rms": 0.31264, "loss": 6.04e-4,
                           "grad_cos": 0.99543},
    "program_4000000023": {"logits_rms": 0.29246, "loss": 1.23e-4,
                           "grad_cos": 0.99528},
    "program_4000000024": {"logits_rms": 0.28560, "loss": 7.7e-5,
                           "grad_cos": 0.99530},
    "program_4000000031": {"logits_rms": 0.34566, "loss": 2.82e-4,
                           "grad_cos": 0.99533},
    "program_4000000041": {"logits_rms": 0.28491, "loss": 4.1e-5,
                           "grad_cos": 0.99539, "update_cos": 0.93939,
                           "update_norm": 0.00115},
    "program_4000000042": {"logits_rms": 0.31280, "loss": 4.53e-4,
                           "grad_cos": 0.99163, "update_cos": 0.93706,
                           "update_norm": 0.00100},
    "state_4000000021": {"logits_rms": 3.19598, "loss": 2.57e-3,
                         "grad_cos": 0.99126},
    "state_4000000041": {"logits_rms": 3.14663, "loss": 5.83e-4,
                         "grad_cos": 0.99134, "update_cos": 0.91689,
                         "update_norm": 6.8e-5},
    "detached_4000000041": {"grad_cos": 0.62777, "update_cos": 0.62827,
                            "update_norm": 0.00081},
    "no_update_4000000041": {"update_cos": 0.0, "update_norm": 1.0}}
LIMITS = {"logits_rms": (cp.LOGIT_TOL_RMS, "max"), "loss": (cp.LOSS_TOL,
                                                            "max"),
          "grad_cos": (cp.GRAD_COS, "min"),
          "update_cos": (cp.UPDATE_COS, "min"),
          "update_norm": (cp.UPDATE_NORM, "max")}
REFUSED_BY = {"program": set(), "state": {"logits_rms"},
              "detached": {"grad_cos", "update_cos"},
              "no_update": {"update_cos", "update_norm"}}


@pytest.mark.parametrize("what", sorted(CHIP_READINGS))
def test_the_committed_limits_on_the_chip_s_readings(what):
    """The limits as committed, through the harness's own `verdict`:
    every seed of the program passes; the bfloat16 state is refused by
    the logits (and on ...21 by the loss), the detached reads by the
    gradient's and the update's cosine, the step that moves nothing by
    both update readings; and each limit keeps room on both sides: at
    least 1.5 times the program's worst (in 1 - cos for a cosine), and
    the control or a fault at least three times the program's worst."""
    r = CHIP_READINGS[what]
    v = cp.verdict({k: (x, *LIMITS[k]) for k, x in r.items()})
    refused = {k for k, c in v["checks"].items() if not c["ok"]}
    kind = what.rsplit("_", 1)[0]
    assert REFUSED_BY[kind] <= refused, v
    assert refused <= REFUSED_BY[kind] | ({"loss"} if kind == "state"
                                          else set()), v
    off = lambda k, x: 1 - x if k.endswith("cos") else x
    program = [x for n, x in CHIP_READINGS.items()
               if n.startswith("program")]
    for k, (limit, _) in LIMITS.items():
        worst = max(off(k, x[k]) for x in program if k in x)
        beyond = max(off(k, x[k]) for n, x in CHIP_READINGS.items()
                     if not n.startswith("program") and k in x)
        assert 1.5 * worst < off(k, limit) < beyond, k
        assert beyond >= 3 * worst, k
