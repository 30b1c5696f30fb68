"""Rehearsal tests of the benchmark's own arithmetic (run by hand:
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`; not tier-1)."""
from __future__ import annotations

import json
import os
import re
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

from harness import common, flops, stats, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def mix(name: str) -> dict:
    """A shipped mix by name, or the shipped backlog mix turned into an
    open loop (arrivals are a field of the file, not code)."""
    if name == "batchgen_long":
        return common.named("traffic", name)
    return dict(common.named("traffic", "batchgen_long"),
                arrivals={"process": "poisson", "rate_per_s": 5.0})


SERVE_MIXES = ["batchgen_long", "batchgen_long_as_poisson"]


# --------------------------- generator --------------------------------------

@pytest.mark.parametrize("name", SERVE_MIXES)
def test_same_seed_same_schedule(name):
    tr = mix(name)
    a = traffic.serve_schedule(tr, 50257, 7, 200)
    b = traffic.serve_schedule(tr, 50257, 7, 200)
    c = traffic.serve_schedule(tr, 50257, 8, 200)
    assert traffic.schedule_digest(a) == traffic.schedule_digest(b)
    assert traffic.schedule_digest(a) != traffic.schedule_digest(c)


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_schedule_respects_the_file(name):
    tr = mix(name)
    s = traffic.serve_schedule(tr, 50257, 1, 2000)
    pl = np.array([len(p) for p in s["prompts"]])
    ol = np.array(s["max_new"])
    e = tr["engine"]
    assert pl.min() >= tr["prompt_len"]["min"]
    assert pl.max() <= tr["prompt_len"]["max"] <= e["max_prompt"]
    assert ol.min() >= tr["output_len"]["min"]
    assert ol.max() <= tr["output_len"]["max"] <= e["max_new_tokens"]
    assert e["max_prompt"] + e["max_new_tokens"] <= 1024
    # medians land near the stated ones (clipping moves them little)
    assert abs(np.median(pl) / tr["prompt_len"]["median"] - 1) < 0.15
    assert abs(np.median(ol) / tr["output_len"]["median"] - 1) < 0.15
    # Zipf(1) over base + 4: the base route takes 1/H5 = 0.438
    share_base = np.mean(np.array(s["adapter"]) == -1)
    assert abs(share_base - 1 / sum(1 / k for k in range(1, 6))) < 0.04
    assert all(0 < t < 50257 for p in s["prompts"][:50] for t in p)
    if tr["arrivals"]["process"] == "backlog":
        assert not np.any(s["due_s"])
    else:
        rate = len(s["due_s"]) / s["due_s"][-1]
        assert abs(rate / tr["arrivals"]["rate_per_s"] - 1) < 0.1
        assert np.all(np.diff(s["due_s"]) >= 0)


def test_bursty_and_prefix_sharing_are_data_not_code():
    """Mixes PERF.md keeps for later run through the same generator."""
    tr = mix("batchgen_long_as_poisson")
    tr["arrivals"] = {"process": "bursty", "rate_per_s": 10.0, "high": 3.0,
                      "low": 0.2, "on_s": 2.0, "off_s": 5.0}
    tr["prefix_sharing"] = {"pool": 4, "share": 0.7, "prefix_len": 64}
    s = traffic.serve_schedule(tr, 50257, 3, 1000)
    heads = {tuple(p[:64]) for p in s["prompts"] if len(p) > 64}
    assert 4 <= len(heads) < 0.5 * len(s["prompts"])
    gaps = np.diff(s["due_s"])
    assert gaps.std() / gaps.mean() > 1.2         # burstier than Poisson


def test_corpus_is_a_function_of_the_seed(tmp_path):
    spec = {"train_words": 3000, "vocab_words": 50, "p_follow": 0.55}
    a = traffic.write_corpus(str(tmp_path / "a"), spec, 5)
    b = traffic.write_corpus(str(tmp_path / "b"), spec, 5)
    c = traffic.write_corpus(str(tmp_path / "c"), spec, 6)
    assert a == b != c
    # a kept directory is re-checked by its bytes' hash
    (tmp_path / "a" / "wiki.train.tokens").write_text("tampered")
    (tmp_path / "a" / "corpus.sha256").write_text("0" * 64)
    assert traffic.write_corpus(str(tmp_path / "a"), spec, 5) == a
    assert (tmp_path / "a" / "wiki.train.tokens").read_text() \
        == (tmp_path / "b" / "wiki.train.tokens").read_text()


# --------------------------- percentiles and windows ------------------------

def test_percentile_rule():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50.5
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    # the highest percentile with at least ten samples beyond it
    assert stats.highest_supported_percentile(150) == 90.0
    assert stats.highest_supported_percentile(200) == 95.0
    assert stats.highest_supported_percentile(1000) == 99.0
    assert stats.highest_supported_percentile(30) == 50.0
    # a percentile the sample does not support is not given at all
    assert stats.tail(list(range(200)), 95.0) == pytest.approx(189.05)
    assert stats.tail(list(range(199)), 95.0) is None
    assert stats.tail(list(range(100)), 90.0) == pytest.approx(89.1)
    assert stats.tail(list(range(20)), 50.0) == 9.5
    assert stats.tail(list(range(3)), 50.0) is None      # a median too


def test_window_accounting_keeps_whole_intervals_only():
    # flushes seen at these times; warm-up ended (window opened) at 10.0
    seen = [4.0, 10.0, 13.0, 16.0, 19.5, 21.0]
    assert stats.whole_intervals(seen, 10.0, 10.0) == [2, 3, 4]
    assert stats.whole_intervals(seen, 10.0, 11.0) == [2, 3, 4, 5]
    assert stats.whole_intervals(seen, 10.0, 2.0) == []


# the `t_mono` of the step_stats lines 10..85 of PR 22's traced chip run of
# xl_lora_s1024 (five steps a flush, 634.7 ms a step); the profiler started
# in the interval that ends at step 20 and stopped (2.3 s) in the next
CHIP_STAMPS = [487.260441568, 490.43538928, 493.690879774, 499.167767844,
               502.341781295, 505.516168637, 508.690426425, 511.865694164,
               515.039747519, 518.214101227, 521.388208798, 524.562754522,
               527.737741248, 530.911325421, 534.086002207, 537.259900367]


def train_obs(stamps, first_step=10, every=5, prof=None):
    idx = stats.whole_intervals(stamps, stamps[0], 1e9)
    return {"kind": "train_cli", "setup_s": 1.0, "chips": 1,
            "log_interval": every, "tokens_per_flush": every * 6 * 1024,
            "interval_s": [stamps[k] - stamps[k - 1] for k in idx],
            "flushes": [{"step": first_step + every * k} for k in idx],
            "profile_steps": prof}


def test_train_rate_is_tokens_over_the_median_interval():
    from harness import readers, train_cli
    obs = train_obs(CHIP_STAMPS, prof=(15, 20))
    assert len(obs["interval_s"]) == 15
    tps = train_cli.end_to_end(obs)["train_tokens_per_s_per_chip"]
    # the stalled intervals do not move it: 6,144 tokens / 634.87 ms
    assert tps == pytest.approx(30720 / 3.17435, rel=1e-4)
    mean = 15 * 30720 / sum(obs["interval_s"])
    assert mean == pytest.approx(9216.0, rel=1e-3)      # what the mean said
    # the two intervals the profiler fell into are not the program's stalls
    assert abs(readers.train_stall_share(obs, None, {})) < 0.01
    # ... and counted, they are the whole gap between mean and median
    untraced = dict(obs, profile_steps=None)
    assert readers.train_stall_share(untraced, None, {}) == pytest.approx(
        100.0 * (1.0 - mean / tps), rel=1e-6)
    # one stall of a second in a steady run: the rate stays, the share shows
    steady = [3.1735 * k for k in range(16)]
    late = steady[:8] + [t + 1.0 for t in steady[8:]]
    a, b = (train_cli.end_to_end(train_obs(x)) for x in (steady, late))
    assert a == pytest.approx(b, rel=1e-9)
    assert readers.train_stall_share(train_obs(late), None, {}) \
        == pytest.approx(100.0 / (15 * 3.1735 + 1.0), rel=1e-6)
    assert train_cli.end_to_end(train_obs(steady[:1])) == {"setup_s": 1.0}
    assert readers.train_stall_share(train_obs(steady[:1]), None, {}) is None


# --------------------------- flops against hand counts ----------------------

@pytest.mark.parametrize("name,params,blocks", [
    ("gpt2-xl", 1_557_611_200, 48 * 12 * 1600 * 1600),
    ("gpt2-medium", 354_823_168, 24 * 12 * 1024 * 1024)])
def test_param_counts(name, params, blocks):
    cfg = common.named("configs", name)
    assert flops.gpt2_param_count(cfg) == params
    mm = flops.gpt2_matmul_params(cfg)
    assert mm["blocks"] == blocks
    assert mm["head"] == 50257 * cfg["n_embd"]


def test_required_flops_by_hand_xl_lora():
    cfg = common.named("configs", "gpt2-xl")
    T4 = ["attn_qkv", "attn_proj", "mlp_fc_in", "mlp_fc_out"]
    n_lora = flops.lora_params(cfg, 8, T4)
    # per layer: 8*(1600+4800) + 8*(1600+1600) + 8*(1600+6400)*2
    assert n_lora == 48 * 8 * (6400 + 3200 + 8000 + 8000)
    B, S = 6, 1024
    N = 48 * 12 * 1600 ** 2 + 50257 * 1600 + n_lora
    attn = 4 * B * 48 * 25 * S * S * 64
    want = 4 * N * B * S + 2 * n_lora * B * S + 3 * attn
    got = flops.required_train_flops(cfg, B, S, full_ft=False,
                                     n_lora=n_lora)
    assert got == want
    # by hand, per token: 4 x 1.5648e9 + 2 x 9.83e6 + 3 x 314.6e6
    # = 7.22 GFLOP (ISSUE 22 said 6.7: it left the attention out)
    assert got / (B * S) == pytest.approx(7.2226e9, rel=1e-3)
    ex = flops.executed_train_flops(cfg, B, S, full_ft=False, n_lora=n_lora,
                                    remat=True,
                                    attn_factor=flops.flash_attn_factor(S))
    assert ex > got and ex / (B * S) == pytest.approx(10.19e9, rel=0.01)


def test_required_flops_by_hand_full_ft_and_medium():
    cfg = common.named("configs", "gpt2-xl")
    B, S = 32, 1024
    N = 48 * 12 * 1600 ** 2 + 50257 * 1600
    attn = 4 * B * 48 * 25 * S * S * 64
    assert flops.required_train_flops(cfg, B, S, full_ft=True) \
        == 6 * N * B * S + 3 * attn
    md = common.named("configs", "gpt2-medium")
    # decode at 40,000 cached tokens: K and V, 24 layers, 1024 wide, bf16
    assert flops.paged_attention_bytes(md, 40_000) \
        == 2 * 24 * 1024 * 40_000 * 2
    t, bound = flops.roofline_seconds(1e12, 1e9, {"flops_bf16": 197e12,
                                                  "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)
    w = flops.flash_kernel_work(cfg, 6, 1024, remat=True)
    assert flops.roofline_seconds(w["flops"], w["bytes"], {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})[1] == "compute"


# --------------------------- BENCHMARK.json ---------------------------------

def test_benchmark_json_meets_the_contract():
    b = common.benchmark_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(b)) < 64 * 1024
    assert 1 <= b["run_seconds"] <= 51
    # a full check with all 24 cells must fit 43,200 s
    assert 1200 + 24 * (14 * (b["run_seconds"] + 60) + 2 * 90) \
        + 2 * (b["run_seconds"] + 60) <= 43_200
    assert b["paths"] == ["benchmark"]
    assert all(not w.startswith("/") and ".." not in w
               for w in b["command"])
    cfgs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    assert len(cfgs) == len(b["configs"]) and len(cells) == len(
        b["workloads"])
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        f = common.load_json(os.path.join(ROOT, c["file"]))
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        assert not [k for k in c["reduced"]
                    if re.search(r"_dim$|_rank$|embd|hidden|head", k)]
    used = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        used.add(w["config"])
        # every cell's three files are found by name, and agree
        cell = common.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert cell["why"] == w["why"]
        assert cell["kind"] in ("train_cli", "serve_engine")
    assert used == set(cfgs)
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        spec = common.named("layer_metrics", m["name"])
        for k in ("layer", "unit", "better", "source", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert callable(common.resolve(spec["reader"]))
        # reported only where the metric it moves is
        for w in m.get("workloads", cells):
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", cells), (m["name"], w)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for w in cells:                     # every cell reports enough
        mine = [m["name"] for m in common.metrics_for(w, "end_to_end")]
        assert "setup_s" in mine and len(mine) >= 2
        assert common.metrics_for(w, "per_layer")
    for root, _, files in os.walk(BENCH):    # file names under `paths`
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_unknown_device_kind_is_an_error():
    assert common.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(common.BenchError):
        common.peaks_for("TPU v9 imaginary")
    with pytest.raises(common.BenchError):
        common.peaks_for("_source")
