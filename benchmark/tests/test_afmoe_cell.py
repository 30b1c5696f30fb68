"""The AFMoE cell's benchmark code at a tiny size on the CPU (run by hand:
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_afmoe_cell.py`):
the child's own comparison end to end, the limits of
harness/correct_moe.py against a lower precision and a wrong router,
the FLOP and byte counts of harness/flops_afmoe.py against a hand count
at the published widths, and the readers of harness/afmoe_readers.py on
a hand-made trace."""
import argparse
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

from harness import (afmoe_readers, common, correct_moe,  # noqa: E402
                     flops_afmoe as fl, train_child_afmoe as child)
from harness.xplane import Event, Line, Plane, Trace  # noqa: E402

TARGETS = ["q_proj", "k_proj", "v_proj", "o_proj"]


def tiny_cell(tmp_path) -> dict:
    """A throw-away cell of three new files: the family's tiny preset as
    rank 1 of 2, float32."""
    from mobilefinetuner_tpu.core.config import AfmoeConfig
    raw = dataclasses.asdict(AfmoeConfig.tiny())
    del raw["experts_held"], raw["attention_impl"]
    files = {
        "configs/tiny_afmoe": {
            "source": "test preset, not a model", "model_type": "afmoe",
            **raw, "hidden_act": "silu", "num_experts": 4, "reduced": [],
            "fixture": {"post_norm_scale": 0.3, "expert_bias_std": 0.1,
                        "words": 30},
            "deployment": {"expert_parallel": 2, "expert_rank": 1,
                           "router_width": 8}, "assumed": {}},
        "traffic/tiny_afmoe_train": {
            "kind": "train",
            "module": "mobilefinetuner_tpu.cli.train_lora_afmoe",
            "argv": ["--dtype", "float32", "--seq_len", "32",
                     "--batch_size", "2", "--remat", "--rank", "4",
                     "--alpha", "8", "--lora_targets", ",".join(TARGETS),
                     "--expert_parallel", "2", "--expert_rank", "1"],
            "lora": {"rank": 4, "alpha": 8, "targets": TARGETS},
            "correct": {"n_seq": 2, "grad_seq_len": 32,
                        "grad_tail_layers": 2,
                        "grad_leaves": ["q_proj.B", "o_proj.A"]}},
        "workloads/tiny_afmoe_train": {
            "config": "tiny_afmoe", "traffic": "tiny_afmoe_train",
            "kind": "harness.train_cli_afmoe", "chips": 1, "why": "test"}}
    for name, obj in files.items():
        p = tmp_path / f"{name}.json"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(obj))
    return common.load_cell("tiny_afmoe_train", [str(tmp_path)])


def test_the_child_compares_program_and_reference_and_keeps_the_reference(
        tmp_path):
    cell = tiny_cell(tmp_path)
    config = child.model_config(cell["config_file"])
    assert config.experts_held == (4, 4) and config.num_experts == 8
    model_dir = str(tmp_path / "fixtures" / "tiny-w0" / "afmoe")
    child.write_hf_dir(model_dir, cell["config_file"], config)
    assert child.fixture_ok(model_dir, config, cell["config_file"])
    from mobilefinetuner_tpu.data.tokenizer_bpe import GPT2BPETokenizer
    from harness.train_cli_afmoe import word_name
    tok = GPT2BPETokenizer.from_pretrained(model_dir)
    assert tok.vocab_size == 300 and tok.eos_id == 299
    ids = tok.encode(f" {word_name(0)} {word_name(29)} {word_name(7)}")
    assert len(ids) == 3 and len(set(ids)) == 3      # one token a word
    raw = json.load(open(os.path.join(model_dir, "config.json")))
    assert raw["num_experts"] == 8          # the router's width
    args = argparse.Namespace(seed=2 ** 31 + 11)
    v = child.compare(args, cell, config, model_dir)
    assert v["ok"], v
    assert not v["reference_cached"]
    assert v["checks"]["logits_rms"]["value"] < 1e-4        # float32
    assert v["checks"]["grad_rel:o_proj.A"]["value"] < 1e-4
    assert v["counters"]["sum"]["moe_dropped"] == 0.0
    again = child.compare(args, cell, config, model_dir)
    assert again["ok"] and again["reference_cached"]


def test_the_limits_refuse_a_lower_precision_by_one_of_them(tmp_path):
    """bf16 passes every limit; the same weights rounded to an 8-bit
    float (e4m3, 3 mantissa bits) — the nearest precision below the one
    the configuration states — fail, by the logits' limit and not by
    each (the loss is a mean and hardly moves)."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.models import afmoe
    from reference import afmoe as ref
    cell = tiny_cell(tmp_path)
    config = child.model_config(cell["config_file"])
    cfg = {**child.hf_config(cell["config_file"]),
           "experts_held": config.experts_held}
    params = afmoe.init_params(
        config, jax.random.PRNGKey(0), post_norm_scale=cell[
            "config_file"]["fixture"]["post_norm_scale"])
    lora = child.seeded_lora(config, cell["traffic_file"]["lora"], 3)
    ids = np.random.default_rng(5).integers(1, 299, (2, 32)).astype(np.int32)

    def reading(p, dt):
        """The program on weights p in dt, against the reference on the
        fixture's own (bf16-stored) weights under the program's routing."""
        logits, aux = afmoe.forward(config, p, jnp.asarray(ids), lora=lora,
                                    compute_dtype=dt, with_aux=True)
        idx = np.asarray(aux["idx"]).reshape(-1, 2, 32, 2)
        want = np.stack([np.asarray(ref.forward(
            cfg, bf16, jnp.asarray(ids[b]), lora, idx[:, b])[0])
            for b in range(2)])
        return (correct_moe.rms_err(np.asarray(logits, np.float32), want),
                abs(float(ref.loss(jnp.asarray(logits, jnp.float32),
                                   jnp.asarray(ids)))
                    - float(ref.loss(jnp.asarray(want), jnp.asarray(ids)))))

    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
                        if a.ndim >= 2 else a, params)
    r_bf16 = reading(bf16, jnp.bfloat16)
    fp8 = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(
        jnp.float32) if a.ndim >= 2 else a, params)
    r_fp8 = reading(fp8, jnp.float32)
    print("readings (logits rms, loss): bf16", r_bf16, "fp8", r_fp8)
    assert r_bf16[0] < correct_moe.LOGIT_TOL_RMS
    assert r_bf16[1] < correct_moe.LOSS_TOL
    assert r_fp8[0] > 2 * correct_moe.LOGIT_TOL_RMS
    assert r_fp8[1] < correct_moe.LOSS_TOL      # one limit, not each


def test_routing_disagreement_counts_assignments_under_the_cut():
    sc = np.array([[0.9, 0.8, 0.7, 0.1], [0.5, 0.49, 0.2, 0.1]])
    same = correct_moe.routing_disagreement(np.array([[1, 0], [0, 1]]), sc)
    assert same == {"share": 0.0, "max_gap": 0.0}
    d = correct_moe.routing_disagreement(np.array([[0, 2], [0, 2]]), sc)
    assert d["share"] == 0.5
    assert d["max_gap"] == pytest.approx(0.29)
    assert correct_moe.rms_err([1.0, 3.0], [1.0, 1.0]) == pytest.approx(2.0)


# --------------------------- counts -----------------------------------------

def published() -> dict:
    return common.named("configs", "trinity-large-preview")


def test_counts_match_a_hand_count_at_the_published_widths():
    cfg = published()
    p = fl.matmul_params(cfg)
    # q, gate 3072x6144; o 6144x3072; k, v 3072x1024
    assert p["attn"] == 3 * 3072 * 6144 + 2 * 3072 * 1024 == 62_914_560
    assert p["expert"] == p["shared"] == 3 * 3072 * 3072 == 28_311_552
    assert p["dense_mlp"] == 3 * 3072 * 12288 == 113_246_208
    assert p["router"] == 3072 * 256 and p["head"] == 25024 * 3072
    fixed = fl.fixed_params_per_token(cfg)
    assert fixed == 5 * 62_914_560 + 113_246_208 \
        + 4 * (28_311_552 + 786_432) + 76_873_728
    assert fl.lora_params(cfg, 8, TARGETS) == 5 * 8 * (
        2 * (3072 + 6144) + 2 * (3072 + 1024))
    # pairs: the full triangle, and the window's band
    assert fl.admitted_pairs(4, None) == 10
    assert fl.admitted_pairs(8192, 4096) == sum(
        min(i + 1, 4096) for i in range(8192))
    assert fl.pairs_per_sequence(cfg, 8192) == 4 * fl.admitted_pairs(
        8192, 4096) + fl.admitted_pairs(8192, None)
    B, S, assign = 1, 8192, 4096.0
    n_lora = fl.lora_params(cfg, 8, TARGETS)
    need = fl.required_train_flops(cfg, B, S, n_lora=n_lora,
                                   assign_here=assign)
    attn = 4 * 48 * 128 * fl.pairs_per_sequence(cfg, S)
    assert need == pytest.approx(
        4 * ((fixed + n_lora) * S + assign * 28_311_552)
        + 2 * n_lora * S + 3 * attn)
    g = fl.gmm_work(cfg, assign, remat=True)
    assert g["flops"] == 3 * 2 * assign * 28_311_552
    assert g["bytes"] == 3 * 2 * (4 * 32 * 28_311_552
                                  + assign * 3 * (3072 + 3072))
    f = fl.flash_mixed_work(cfg, B, S, remat=True)
    assert f["flops"] == (2 * 4 + 10) * 48 * 128 * fl.pairs_per_sequence(
        cfg, S)
    assert f["bytes"] == (2 * (2 * 48 + 2 * 8) + 4 * 48 + 4 * 8) \
        * 5 * S * 128 * 2


# --------------------------- readers ----------------------------------------

def hand_trace() -> Trace:
    """One device, one traced step of the cut (5 layers, remat): ten
    flash forwards, five merged backwards, and ops under each inner
    scope. Times in us -> ns."""
    us = 1000
    t, ops = [0], []

    def ev(name, dur, text):
        ops.append(Event(name, t[0] * us, dur * us, text))
        t[0] += dur

    for i in range(10):
        ev(f"flash_fwd.{i}", 100,
           "jit(step)/attention/attn_window/attention/flash_fwd/pallas_call"
           if i % 5 else
           "jit(step)/attention/attn_full/attention/flash_fwd/pallas_call")
    for i in range(5):
        ev(f"flash_bwd_merged.{i}", 300,
           "jit(step)/attention/attn_window/attention/flash_bwd_merged/x")
    ev("fusion.1", 40, "jit(step)/mlp/moe_router/moe_router/dot_general")
    ev("fusion.2", 60, "jit(step)/mlp/moe_dispatch/gather")
    ev("gmm.1", 500, "jit(step)/mlp/moe_experts/jit(gmm)/pallas_call")
    ev("fusion.3", 100, "jit(step)/mlp/moe_shared/dot_general")
    ev("fusion.4", 50, "jit(step)/mlp/moe_combine/add")
    ev("fusion.5", 250, "jit(step)/mlp/dot_general")     # the dense layer
    t[0] += 500                                           # idle
    ev("copy.9", 1, "")
    return Trace([Plane("/device:TPU:0", [Line("XLA Ops", ops)])])


def test_the_readers_read_a_hand_made_trace_and_the_counters():
    cell = common.load_cell("trinity_lora_s8192")
    flushes = [{"moe_assign_here": 4000.0 + 10 * i,
                "moe_load_max_over_mean": 1.2 + 0.1 * i} for i in range(3)]
    obs = {"kind": "train_cli", "B": 1, "S": 8192, "flushes": flushes}
    peaks = common.peaks_for("TPU v5 lite")
    ctx = {"peaks": peaks, "config": cell["config_file"],
           "traffic": cell["traffic_file"],
           "e2e": {"train_tokens_per_s_per_chip": 10000.0}}
    trace = hand_trace()
    assert afmoe_readers.moe_load_max_over_mean(obs, None, ctx) \
        == pytest.approx(1.3)
    n_lora = fl.lora_params(ctx["config"], 8, TARGETS)
    need = fl.required_train_flops(ctx["config"], 1, 8192, n_lora=n_lora,
                                   assign_here=4010.0)
    assert afmoe_readers.mfu_required_afmoe(obs, None, ctx) \
        == pytest.approx(100 * 10000.0 * need / 8192 / 197e12)
    assert 3.5 < obs["notes"]["mfu_required_afmoe"][
        "required_gflop_per_token"] < 4.5
    busy_us = 10 * 100 + 5 * 300 + 750 + 250 + 1
    assert afmoe_readers.moe_share_of_step(obs, trace, ctx) \
        == pytest.approx(100 * 750 / busy_us)
    per = obs["notes"]["afmoe_scope_s_per_step"]
    assert per["steps_in_trace"] == 1.0
    assert per["moe_experts"] == pytest.approx(500e-6)
    assert per["attn_full"] == pytest.approx(200e-6)
    assert per["attn_window"] == pytest.approx((800 + 1500) * 1e-6)
    g = fl.gmm_work(ctx["config"], 4010.0, remat=True)
    least = max(g["flops"] / 197e12, g["bytes"] / 819e9)
    assert afmoe_readers.expert_gmm_roofline(obs, trace, ctx) \
        == pytest.approx(100 * least / 500e-6)
    assert obs["notes"]["expert_gmm_roofline_bound"] == "memory"
    f = fl.flash_mixed_work(ctx["config"], 1, 8192, remat=True)
    assert afmoe_readers.flash_mixed_roofline(
        obs, trace, ctx, fwd="flash_fwd", bwd="flash_bwd_merged") \
        == pytest.approx(100 * (f["flops"] / 197e12) / (2500e-6))
    # nothing to read: None, not an error — no trace, no counters, or
    # another family's cell
    assert afmoe_readers.moe_share_of_step(obs, None, ctx) is None
    bare = dict(obs, flushes=[{"loss": 1.0}])
    assert afmoe_readers.mfu_required_afmoe(bare, None, ctx) is None
    assert afmoe_readers.expert_gmm_roofline(bare, trace, ctx) is None
    gpt2 = dict(ctx, config=common.named("configs", "gpt2-xl"),
                traffic=common.named("traffic", "lora_s1024"))
    for read in (afmoe_readers.mfu_required_afmoe,
                 afmoe_readers.moe_share_of_step,
                 afmoe_readers.expert_gmm_roofline,
                 afmoe_readers.moe_load_max_over_mean):
        assert read(obs, trace, gpt2) is None
    assert afmoe_readers.flash_mixed_roofline(
        obs, trace, gpt2, fwd="flash_fwd", bwd="flash_bwd_merged") is None


def test_the_cell_s_files_say_what_benchmark_json_says():
    b = common.benchmark_json()
    cell = common.load_cell("trinity_lora_s8192")
    w = next(x for x in b["workloads"] if x["name"] == cell["name"])
    assert w == {k: cell[k] for k in ("name", "config", "traffic", "chips",
                                      "why")}
    c = next(x for x in b["configs"] if x["name"] == cell["config"])
    cf = cell["config_file"]
    assert c["source"] == cf["source"] and c["reduced"] == cf["reduced"] \
        == ["num_hidden_layers", "num_dense_layers", "layer_types",
            "num_experts", "vocab_size"]
    assert len(w["why"]) <= 200 and len(c["why"]) <= 200
    assert cell["kind"] == "harness.train_cli_afmoe"
    listed = {m["name"] for m in common.metrics_for(cell["name"],
                                                    "per_layer")}
    assert {"mfu_required_afmoe", "moe_share_of_step",
            "expert_gmm_roofline", "flash_mixed_roofline",
            "moe_load_max_over_mean", "train_step_ms",
            "device_idle_share_train"} <= listed
    assert "mfu_required" not in listed and "flash_roofline" not in listed
