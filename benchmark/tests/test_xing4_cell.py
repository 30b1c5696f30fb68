"""Xing4.0's cell's benchmark code at a tiny size on the CPU (run by hand:
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_xing4_cell.py`):
the child's own comparison end to end, the knobs and faults of the
reference that tools/lower_precision_xing4.py turns, the chip's readings
through harness/correct_xing4.py's limits, the FLOP and byte counts of
harness/flops_xing4.py against a hand count at the published widths, the
readers of harness/xing4_readers.py on a hand-made trace, and the cell's
files against ISSUE 33's parameters."""
import argparse
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

from harness import (common, correct_xing4 as cx,  # noqa: E402
                     flops_xing4 as fl, train_child_xing4 as child,
                     xing4_readers as xr)
from harness.xplane import Event, Line, Plane, Trace  # noqa: E402

TARGETS = ["q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj"]
LEAVES = ["q_b_proj.B@1", "kv_b_proj.B@1", "o_proj.B@0"]


def tiny_cell(tmp_path) -> dict:
    """A throw-away cell of three new files: the family's tiny preset,
    every expert held, float32."""
    from mobilefinetuner_tpu.core.config import Xing4Config
    raw = dataclasses.asdict(Xing4Config.tiny())
    for k in ("experts_held", "attention_impl", "mtp_loss_weight"):
        del raw[k]
    files = {
        "configs/tiny_xing4": {
            "source": "test preset, not a model", "model_type": "xing4_0",
            **raw, "vocab_size": 512, "reduced": [],
            "fixture": {"expert_bias_std": 0.02, "words": 30,
                        "embed_std": 1.0},
            "deployment": {"expert_parallel": 1, "expert_rank": 0,
                           "router_width": 8}, "assumed": {}},
        "traffic/tiny_xing4_train": {
            "kind": "train",
            "module": "mobilefinetuner_tpu.cli.train_lora_xing4",
            "argv": ["--dtype", "float32", "--seq_len", "32",
                     "--batch_size", "1", "--remat", "--rank", "4",
                     "--alpha", "8", "--expert_parallel", "1",
                     "--expert_rank", "0", "--mtp_loss_weight", "0.3"],
            "lora": {"rank": 4, "alpha": 8, "targets": TARGETS},
            "correct": {"n_seq": 2, "grad_seq_len": 32,
                        "grad_tail_layers": 2, "grad_leaves": LEAVES}},
        "workloads/tiny_xing4_train": {
            "config": "tiny_xing4", "traffic": "tiny_xing4_train",
            "kind": "harness.train_cli_xing4", "chips": 1, "why": "test"}}
    for name, obj in files.items():
        p = tmp_path / f"{name}.json"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(obj))
    return common.load_cell("tiny_xing4_train", [str(tmp_path)])


def test_the_child_compares_program_and_reference_and_keeps_the_reference(
        tmp_path):
    cell = tiny_cell(tmp_path)
    config = child.cell_config(cell)
    assert config.experts_held == (0, 8) and config.mtp_loss_weight == 0.3
    model_dir = str(tmp_path / "fixtures" / "tiny-w0" / "xing4_0")
    child.write_model_dir(model_dir, cell["config_file"], config)
    assert child.fixture_ok(model_dir, config, cell["config_file"])
    raw = json.load(open(os.path.join(model_dir, "config.json")))
    assert raw["n_routed_experts"] == 8 and raw["hc_mult"] == 4
    assert child.leaf_of("q_b_proj.B@1") == ("q_b_proj.B", 1)
    args = argparse.Namespace(seed=2 ** 31 + 11)
    v = child.compare(args, cell, config, model_dir)
    assert v["ok"], v
    assert not v["reference_cached"]
    assert v["checks"]["logits_rms"]["value"] < 1e-4        # float32
    assert v["checks"]["logits_mtp_rms"]["value"] < 1e-4
    assert v["checks"]["sinkhorn_probe"]["value"] < 1e-6
    for leaf in LEAVES:
        assert v["checks"][f"grad_rel:{leaf}"]["value"] < 1e-3
    assert v["counters"]["sum"]["moe_dropped"] == 0.0
    assert abs(v["loss_mtp_program"] - v["loss_mtp_reference"]) < 1e-5
    again = child.compare(args, cell, config, model_dir)
    assert again["ok"] and again["reference_cached"]


def test_the_reference_s_knobs_and_faults_move_what_they_name(tmp_path):
    """What tools/lower_precision_xing4.py turns: each changes the
    reference's output where it says, and none is on by default."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.models import xing4
    from reference import xing4 as ref
    cell = tiny_cell(tmp_path)
    config = child.cell_config(cell)
    cfg = {**child.hf_config(cell["config_file"]),
           "experts_held": config.experts_held}
    params = xing4.init_params(config, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(5).integers(1, 299, 32),
                      jnp.int32)
    assert set(ref.LOWER.values()) == {jnp.float32}
    assert ref.FAULT == {"sinkhorn_iters": 0, "no_mscale": False,
                         "drop_mtp": False}
    probe = jnp.asarray(cx.probe_logits(1, 4, 256))
    base, base_m, infos = ref.forward(cfg, params, ids)
    forced = {at: np.asarray(i["idx"]) for at, i in enumerate(infos)
              if "idx" in i}
    h0 = ref.sinkhorn(cfg, probe)
    total0 = float(ref.total_loss(cfg, base, base_m, ids, 0.3)[0])
    readings = {}
    try:
        ref.LOWER["sinkhorn"] = jnp.bfloat16
        readings["sinkhorn"] = float(jnp.abs(ref.sinkhorn(cfg, probe)
                                             - h0).max())
        ref.LOWER["sinkhorn"] = jnp.float32
        ref.FAULT["sinkhorn_iters"] = -1
        readings["rounds19"] = float(jnp.abs(ref.sinkhorn(cfg, probe)
                                             - h0).max())
        ref.FAULT["sinkhorn_iters"] = 0
        ref.LOWER["router"] = jnp.bfloat16
        jax.clear_caches()
        _, _, lowered = ref.forward(cfg, params, ids, forced_idx=forced)
        readings["router"] = max(
            float(np.abs(np.asarray(a["biased"]) - np.asarray(b["biased"]))
                  .max()) for a, b in zip(lowered, infos) if "biased" in a)
        ref.LOWER["router"] = jnp.float32
        ref.FAULT["no_mscale"] = True
        jax.clear_caches()
        got, _, _ = ref.forward(cfg, params, ids, forced_idx=forced)
        readings["no_mscale"] = cx.rms_err(got, base)
        ref.FAULT["no_mscale"] = False
        ref.FAULT["drop_mtp"] = True
        readings["drop_mtp"] = total0 - float(
            ref.total_loss(cfg, base, base_m, ids, 0.3)[0])
    finally:
        ref.LOWER.update(sinkhorn=jnp.float32, router=jnp.float32)
        ref.FAULT.update(sinkhorn_iters=0, no_mscale=False, drop_mtp=False)
        jax.clear_caches()
    print("readings:", readings)
    assert readings["sinkhorn"] > 10 * cx.SINKHORN_TOL
    assert readings["rounds19"] > 10 * cx.SINKHORN_TOL
    assert readings["router"] > 1e-4            # the scores move
    assert readings["no_mscale"] > 1e-3
    assert readings["drop_mtp"] > 1.0           # 0.3 x ln(vocabulary)


# The chip's readings at the published widths and 8,192 tokens (PERF.md
# section 6, PR 33; the logs are chiprun_out/xing*/, not committed): the
# bf16 program against the float32 reference — the largest (cosine:
# smallest) over the seeds run — and the reference with one thing lowered
# or broken against itself, through tools/lower_precision_xing4.py.
# grad_rel / grad_cos: of the three leaves the program's WORST, a control's MILDEST.
# Seeds 3300000021 to ...24 (program), 3300000021 (controls), the fixture
# as committed; `first_fixture` is the program on the first fixture
# (embedding std 1, weight seed 0, 6 on H~res's diagonal; seeds ...11 to
# ...14), which passes the same limits.
CHIP_READINGS = {
    "program_worst": {"logits_rms": 0.032364, "logits_mtp_rms": 0.033596,
                      "loss_main": 1.08e-4, "loss_mtp": 1.62e-4,
                      "route_share": 0.005255, "route_gap": 0.004584,
                      "sinkhorn_probe": 0.0, "grad_rel": 0.023336,
                      "grad_cos": 0.999868},
    "first_fixture": {"logits_rms": 0.042695, "logits_mtp_rms": 0.039708,
                      "loss_main": 9.3e-5, "loss_mtp": 8.9e-5,
                      "route_share": 0.006940, "route_gap": 0.006009,
                      "sinkhorn_probe": 0.0, "grad_rel": 0.030333,
                      "grad_cos": 0.999731},
    "sinkhorn_bf16": {"logits_rms": 0.012948, "logits_mtp_rms": 0.011543,
                      "loss_main": 4e-6, "loss_mtp": 2e-6,
                      "route_share": 0.005225, "route_gap": 0.003894,
                      "sinkhorn_probe": 0.012038, "grad_rel": 0.009696,
                      "grad_cos": 0.999991},
    "router_bf16": {"logits_rms": 0.001747, "logits_mtp_rms": 0.002891,
                    "loss_main": 1e-6, "loss_mtp": 3e-6,
                    "route_share": 0.0104, "route_gap": 0.006306,
                    "sinkhorn_probe": 0.0, "grad_rel": 0.000538,
                    "grad_cos": 1.0},
    "rounds19": {"logits_rms": 3e-6, "logits_mtp_rms": 3e-6,
                 "loss_main": 0.0, "loss_mtp": 0.0, "route_share": 0.005096,
                 "route_gap": 0.003952, "sinkhorn_probe": 0.00478,
                 "grad_rel": 1e-6, "grad_cos": 1.0},
    "no_mscale": {"logits_rms": 0.080691, "logits_mtp_rms": 0.111724,
                  "loss_main": 2.5e-5, "loss_mtp": 5.8e-5,
                  "route_share": 0.007794, "route_gap": 0.023263,
                  "sinkhorn_probe": 0.0, "grad_rel": 0.306232,
                  "grad_cos": 0.937559},
    "drop_mtp": {"logits_rms": 0.0, "logits_mtp_rms": 0.0, "loss_main": 0.0,
                 "loss_mtp": 10.421514, "route_share": 0.005096,
                 "route_gap": 0.003952, "sinkhorn_probe": 0.0,
                 "grad_rel": 0.21928, "grad_cos": 0.973862},
    "e4m3": {"logits_rms": 0.307886, "logits_mtp_rms": 0.35548,
             "loss_main": 4.09e-4, "loss_mtp": 2.68e-4,
             "route_share": 0.042273, "route_gap": 0.042291,
             "sinkhorn_probe": 0.0, "grad_rel": 0.07446,
             "grad_cos": 0.995349}}
REFUSED_BY = {
    "program_worst": [], "first_fixture": [],
    "sinkhorn_bf16": ["sinkhorn_probe"], "router_bf16": ["route_share"],
    "rounds19": ["sinkhorn_probe"],
    "no_mscale": ["logits_rms", "logits_mtp_rms", "route_share",
                  "route_gap", "grad_rel", "grad_cos"],
    "drop_mtp": ["loss_mtp", "grad_rel", "grad_cos"],
    "e4m3": ["logits_rms", "logits_mtp_rms", "route_share", "route_gap",
             "grad_rel"]}


@pytest.mark.parametrize("what", sorted(CHIP_READINGS) or ["none"])
def test_the_committed_limits_on_the_chip_s_readings(what):
    """The limits as committed, through the harness's own `verdict`: the
    program's worst readings pass, every control is refused by the
    limits named."""
    if what == "none":
        pytest.skip("no chip readings recorded")
    r = CHIP_READINGS[what]
    limits = {"logits_rms": (cx.LOGIT_TOL_RMS, "max"),
              "logits_mtp_rms": (cx.LOGIT_TOL_RMS, "max"),
              "loss_main": (cx.LOSS_TOL, "max"),
              "loss_mtp": (cx.LOSS_TOL, "max"),
              "route_share": (cx.ROUTE_SHARE_TOL, "max"),
              "route_gap": (cx.ROUTE_GAP_TOL, "max"),
              "sinkhorn_probe": (cx.SINKHORN_TOL, "max"),
              "grad_rel": (cx.GRAD_TOL, "max"),
              "grad_cos": (cx.GRAD_COS, "min")}
    v = cx.verdict({k: (r[k], *limits[k]) for k in limits if k in r})
    assert sorted(k for k, c in v["checks"].items() if not c["ok"]) \
        == sorted(REFUSED_BY[what]), v
    # the share's limit has room on both sides of its two readings
    assert 1.3 * CHIP_READINGS["program_worst"]["route_share"] \
        < cx.ROUTE_SHARE_TOL \
        < CHIP_READINGS["router_bf16"]["route_share"] / 1.3


# --------------------------- counts -----------------------------------------

def published() -> dict:
    return common.load_cell("xing_lora_long")["config_file"]


def test_counts_match_a_hand_count_at_the_published_widths():
    """ISSUE 33's arithmetic, re-reckoned from the configuration file and
    from the program's own parameter tree."""
    cfg = published()
    p, held = fl.matmul_params(cfg), fl.held_params(cfg)
    M = 1e6
    assert p["mla"] == 3584 * 768 + 768 * 32 * 192 + 3584 * 576 \
        + 512 * 32 * 256 + 32 * 128 * 3584
    assert round(p["mla"] / M, 2) == 28.41
    assert p["expert"] == 3 * 3584 * 1024
    assert p["hyper"] == 2 * 14336 * 24
    assert round(held["experts_held_a_layer"] / M, 1) == 704.6
    assert round(held["sparse_layer"] / M, 1) == 745.0
    assert round(held["dense_layer"] / M, 1) == 128.2
    assert round(held["mtp_module"] / M, 1) == 770.7
    assert round(held["embedding_and_head"] / M, 1) == 117.4
    # 3.996 B held (+- 0.5 %), 47 % of bytes_limit in bf16
    assert abs(held["total"] / 3.996e9 - 1) < 0.005
    assert round(2 * held["total"] / 16_909_336_064, 2) == 0.47
    assert fl.block_counts(cfg) == {"dense": 1, "moe": 5, "mtp": 1,
                                    "blocks": 6}
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.models import xing4
    c = child.model_config(cfg)
    assert c.experts_held == (0, 64) and c.num_hidden_layers == 5
    tree = jax.eval_shape(lambda: xing4.init_params(
        c, jax.random.PRNGKey(0), jnp.bfloat16))
    matrices = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree)
                   if l.ndim >= 2)
    assert matrices == held["total"]
    assert xing4.mixer_params(c) == p["mla"]
    n_lora = fl.lora_params(cfg, 8, TARGETS)
    assert n_lora == 6 * 8 * (3584 + 768 + 768 + 6144 + 3584 + 576 + 512
                              + 8192 + 4096 + 3584)
    # a step's required work at the cell's shape, every assignment held
    need = fl.required_train_flops(cfg, 1, 8192, n_lora=n_lora,
                                   assign_here=8192 * 4 * 5)
    assert round(need / 8192 / 1e9, 2) == 4.29
    # the stream's mixing: 12 sublayers x (6 X + 5 Y), X = 4 Y,
    # Y = 8192 x 3584 x 2 B
    Y = 8192 * 3584 * 2
    assert fl.mhc_mix_work(cfg, 1, 8192)["bytes"] == 12 * 29 * Y


def test_every_roofline_reads_under_100_on_the_recorded_shape():
    """At the traced run's times (PERF.md section 5, PR 33) the shares the
    cell reports stay under 100 %; and each count alone can never pass it
    at the chip's peaks on a time at least its own least time."""
    cfg = published()
    peaks = common.peaks_for("TPU v5 lite")
    n_lora = fl.lora_params(cfg, 8, TARGETS)
    for work in (fl.mhc_mix_work(cfg, 1, 8192),
                 fl.flash_latent_work(cfg, 1, 8192, remat=True),
                 fl.gmm_work(cfg, 8192 * 4 * 5, remat=True)):
        least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                           peaks)
        assert least > 0 and bound in ("compute", "memory")
    mix = fl.mhc_mix_work(cfg, 1, 8192)
    assert fl.roofline_seconds(mix["flops"], mix["bytes"], peaks)[1] \
        == "memory"
    # the recorded seconds a step under each group (the traced run)
    for name, (work, seconds) in RECORDED.items():
        least, _ = fl.roofline_seconds(work(cfg)["flops"],
                                       work(cfg)["bytes"], peaks)
        assert 0 < 100 * least / seconds < 100, name
    need = fl.required_train_flops(cfg, 1, 8192, n_lora=n_lora,
                                   assign_here=8192 * 4 * 5)
    assert 0 < 100 * RECORDED_TOKENS_PER_S * need / 8192 / 197e12 < 100


# seconds a step under `mhc_*`, of the flash kernels, under `moe_experts`
# and the untraced rate (my chip run, PR 33: PERF.md section 5)
RECORDED = {
    "mhc_mix": (lambda c: fl.mhc_mix_work(c, 1, 8192), 0.192868),
    "flash_latent": (lambda c: fl.flash_latent_work(c, 1, 8192, remat=True),
                     0.175033),
    "expert_gmm": (lambda c: fl.gmm_work(c, 8192 * 4 * 5, remat=True),
                   0.165950)}
RECORDED_TOKENS_PER_S = 9159.78


# --------------------------- readers ----------------------------------------

def hand_trace() -> Trace:
    """One device, one traced step of a cut with ONE block and the
    module (two blocks: four flash forwards under remat, the split
    backward's two kernels twice), and ops under each inner scope. Times
    in us -> ns."""
    us = 1000
    t, ops = [0], []

    def ev(name, dur, text):
        ops.append(Event(name, t[0] * us, dur * us, text))
        t[0] += dur

    for i in range(2):
        ev(f"flash_fwd.{i}", 1000,
           "jit(step)/attention/attn_latent/attention/flash_fwd/pallas_call")
    for i in range(2, 4):
        ev(f"flash_fwd.{i}", 1000,
           "jit(step)/mtp/attention/attn_latent/attention/flash_fwd/x")
    ev("flash_bwd_dq.0", 1500,
       "jit(step)/transpose(jvp(attention))/attn_latent/flash_bwd_dq/x")
    ev("flash_bwd_dkv.0", 2500,
       "jit(step)/transpose(jvp(attention))/attn_latent/flash_bwd_dkv/x")
    ev("flash_bwd_dq.1", 1500,
       "jit(step)/transpose(jvp(mtp))/attention/attn_latent/flash_bwd_dq/x")
    ev("flash_bwd_dkv.1", 2500,
       "jit(step)/transpose(jvp(mtp))/attention/attn_latent/flash_bwd_dkv/x")
    ev("fusion.a", 300, "jit(step)/attention/mhc_coeff/dot_general")
    ev("fusion.b", 100, "jit(step)/attention/mhc_pre/reduce_sum")
    ev("fusion.c", 400, "jit(step)/mlp/mhc_post/add")
    ev("fusion.d", 200, "jit(step)/mtp/mlp/mhc_post/add")
    ev("fusion.e", 150, "jit(step)/attention/mla_proj/dot_general")
    ev("gmm.1", 500, "jit(step)/mlp/moe_experts/jit(gmm)/pallas_call")
    ev("fusion.f", 250, "jit(step)/mtp/loss/dot_general")
    ev("fusion.g", 350, "jit(step)/loss/dot_general")
    ev("fusion.h", 50, "jit(step)/smtpx/dot_general")    # not the module
    t[0] += 500                                           # idle
    ev("copy.9", 1, "")
    return Trace([Plane("/device:TPU:0", [Line("XLA Ops", ops)])])


def one_block_cfg() -> dict:
    """The published widths at one sparse layer and the module: two
    blocks, which is what `hand_trace` holds."""
    return dict(published(), num_hidden_layers=1, first_k_dense_replace=0)


def test_the_readers_read_a_hand_made_trace_and_the_counters():
    cell = common.load_cell("xing_lora_long")
    flushes = [{"moe_assign_here": 65000.0 + 10 * i,
                "moe_load_max_over_mean": 1.2 + 0.1 * i,
                "moe_dropped": 0.0, "hc_res_row_err": 7e-5,
                "loss_main": 9.0, "loss_mtp": 9.5} for i in range(3)]
    obs = {"kind": "train_cli", "B": 1, "S": 8192, "flushes": flushes}
    peaks = common.peaks_for("TPU v5 lite")
    cfg = one_block_cfg()
    ctx = {"peaks": peaks, "config": cfg, "traffic": cell["traffic_file"],
           "e2e": {"train_tokens_per_s_per_chip": 9000.0}}
    trace = hand_trace()
    n_lora = fl.lora_params(cfg, 8, TARGETS)
    need = fl.required_train_flops(cfg, 1, 8192, n_lora=n_lora,
                                   assign_here=65010.0)
    assert xr.mfu_required_xing4(obs, None, ctx) \
        == pytest.approx(100 * 9000.0 * need / 8192 / 197e12)
    assert obs["notes"]["xing4_counters"]["loss_mtp"] == 9.5
    assert xr.moe_load_max_over_mean_xing4(obs, None, ctx) \
        == pytest.approx(1.3)
    busy_us = 4 * 1000 + 2 * (1500 + 2500) + 300 + 100 + 400 + 200 + 150 \
        + 500 + 250 + 350 + 50 + 1
    assert xr.mhc_share_of_step(obs, trace, ctx) \
        == pytest.approx(100 * 1000 / busy_us)
    per = obs["notes"]["xing4_scope_s_per_step"]
    assert per["steps_in_trace"] == 1.0
    assert per["mhc_post"] == pytest.approx(600e-6)
    assert per["attn_latent"] == pytest.approx(12000e-6)
    assert per["moe_experts"] == pytest.approx(500e-6)
    # the module: two forwards, its backward pair, its post-mix, its loss
    mtp_us = 2 * 1000 + 1500 + 2500 + 200 + 250
    assert per["mtp"] == pytest.approx(mtp_us * 1e-6)
    assert per["loss"] == pytest.approx(600e-6)     # both heads' chunks
    assert xr.mtp_share_of_step(obs, trace, ctx) \
        == pytest.approx(100 * mtp_us / busy_us)
    m = fl.mhc_mix_work(cfg, 1, 8192)
    least = max(m["flops"] / 197e12, m["bytes"] / 819e9)
    assert xr.mhc_mix_roofline(obs, trace, ctx) \
        == pytest.approx(100 * least / 1000e-6)
    f = fl.flash_latent_work(cfg, 1, 8192, remat=True)
    least = max(f["flops"] / 197e12, f["bytes"] / 819e9)
    assert xr.flash_latent_roofline_xing4(
        obs, trace, ctx, fwd="flash_fwd", bwd="flash_bwd_") \
        == pytest.approx(100 * least / 12000e-6)
    g = fl.gmm_work(cfg, 65010.0, remat=True)
    least = max(g["flops"] / 197e12, g["bytes"] / 819e9)
    assert xr.expert_gmm_roofline_xing4(obs, trace, ctx) \
        == pytest.approx(100 * least / 500e-6)
    # nothing to read: None, not an error — no trace, no counters, a
    # program without the family's scopes (the parent's checkout), or
    # another family's cell
    assert xr.mhc_share_of_step(obs, None, ctx) is None
    bare = dict(obs, flushes=[{"loss": 1.0}])
    assert xr.mfu_required_xing4(bare, None, ctx) is None
    assert xr.moe_load_max_over_mean_xing4(bare, None, ctx) is None
    empty = Trace([Plane("/device:TPU:0", [Line("XLA Ops", [
        Event("fusion.1", 0, 1000, "jit(step)/mlp/dot_general")])])])
    for read in (xr.mhc_share_of_step, xr.mhc_mix_roofline,
                 xr.mtp_share_of_step, xr.expert_gmm_roofline_xing4):
        assert read(obs, empty, ctx) is None
    assert xr.flash_latent_roofline_xing4(
        obs, empty, ctx, fwd="flash_fwd", bwd="flash_bwd_") is None
    other = dict(ctx, config=common.named("configs", "ling-3.0-flash-vl"),
                 traffic=common.named("traffic", "lora_s16384_ep8"))
    for read in (xr.mfu_required_xing4, xr.mhc_share_of_step,
                 xr.mhc_mix_roofline, xr.mtp_share_of_step,
                 xr.expert_gmm_roofline_xing4,
                 xr.moe_load_max_over_mean_xing4):
        assert read(obs, trace, other) is None


def test_the_cell_s_files_say_what_benchmark_json_and_the_issue_say():
    b = common.benchmark_json()
    cell = common.load_cell("xing_lora_long")
    w = next(x for x in b["workloads"] if x["name"] == cell["name"])
    assert w == {k: cell[k] for k in ("name", "config", "traffic", "chips",
                                      "why")}
    assert w["chips"] == 1 and b["workloads"][-1] == w
    c = next(x for x in b["configs"] if x["name"] == cell["config"])
    cf, tr = cell["config_file"], cell["traffic_file"]
    assert c["source"] == cf["source"] and c["reduced"] == cf["reduced"] \
        == ["num_hidden_layers", "first_k_dense_replace", "vocab_size"]
    assert len(w["why"]) <= 200 and len(c["why"]) <= 200 \
        and len(c["source"]) <= 200
    assert cell["kind"] == "harness.train_cli_xing4"
    assert (cf["num_hidden_layers"], cf["first_k_dense_replace"],
            cf["vocab_size"], cf["n_routed_experts"]) == (5, 1, 16384, 64)
    assert cf["published"]["num_hidden_layers"] == 40 \
        and cf["deployment"]["pipeline_stages"] == 8 \
        and cf["deployment"]["expert_parallel"] == 1
    # every number of the catalog row's config under the same key
    row = next(json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Xing4.0-29B-A4B"' in l) \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else None
    if row is not None:
        assert cf["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert k in cf and (cf[k] == v or k in cf["reduced"]), k
    argv = tr["argv"]
    assert argv == ["--dtype", "bfloat16", "--seq_len", "8192",
                    "--batch_size", "1", "--remat", "--rank", "8",
                    "--alpha", "16", "--lr", "1e-4", "--expert_parallel",
                    "1", "--expert_rank", "0", "--mtp_loss_weight", "0.3"]
    assert tr["log_interval"] == 4
    assert tr["corpus"] == {"train_words": 2000000, "vocab_words": 15000,
                            "p_follow": 0.9}
    assert cf["fixture"]["words"] == 15000
    assert tr["module"] == "mobilefinetuner_tpu.cli.train_lora_xing4"
    # leaves BELOW the attention call (dQ; dK and dV) and below a mix
    assert tr["correct"]["grad_leaves"] == LEAVES
    listed = {m["name"] for m in common.metrics_for(cell["name"],
                                                    "per_layer")}
    new = {"mfu_required_xing4", "mhc_share_of_step", "mhc_mix_roofline",
           "mtp_share_of_step", "flash_latent_roofline_xing4",
           "expert_gmm_roofline_xing4", "moe_load_max_over_mean_xing4"}
    assert new | {"train_step_ms", "train_host_wait_share",
                  "train_stall_share", "peak_hbm_gb_train",
                  "window_compiles_train", "device_idle_share_train"} \
        == listed
    assert {m["name"] for m in b["per_layer"][-7:]} == new
    for name in new:
        m = next(x for x in b["per_layer"] if x["name"] == name)
        f = common.named("layer_metrics", name)
        assert m["workloads"] == f["workloads"] == ["xing_lora_long"]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (f["unit"], f["better"], f["source"],
                                f["layer"], f["moves"])
        assert f["reader"].startswith("harness.xing4_readers:")
