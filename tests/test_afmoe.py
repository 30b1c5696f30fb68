"""AFMoE (Arcee Trinity) on the LoRA trainer, at a tiny size on the CPU:
models/afmoe.py + ops/moe.py against the plain reference the benchmark
keeps (benchmark/reference/afmoe.py, which imports nothing from the
program), the expert layer's contract (told which experts it holds,
routes over all, computes its part — the shares add up), the router's
properties, the HF-layout loader that keeps bf16 and reads only the held
experts, and the CLI through cli/common.run_training."""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import correct_moe  # noqa: E402
from reference import afmoe as ref  # noqa: E402

from mobilefinetuner_tpu.core.config import AfmoeConfig  # noqa: E402
from mobilefinetuner_tpu.lora.lora import (LoRASpec,  # noqa: E402
                                           init_lora_afmoe)
from mobilefinetuner_tpu.models import afmoe  # noqa: E402
from mobilefinetuner_tpu.ops import moe  # noqa: E402
from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum  # noqa: E402

B, S = 2, 32        # window 8 < S: the sliding mask is live


def make(seed=0, dtype=jnp.float32, **over):
    c = dataclasses.replace(AfmoeConfig.tiny(), **over)
    params = afmoe.init_params(c, jax.random.PRNGKey(seed), dtype)
    spec = LoRASpec(rank=4, alpha=8.0, init="peft",
                    targets=["q_proj", "k_proj", "v_proj", "o_proj"])
    lora = init_lora_afmoe(c, spec, jax.random.PRNGKey(seed + 1))
    # B = 0 at init: give every adapter a delta so the comparison sees it
    kb = jax.random.split(jax.random.PRNGKey(seed + 2), 4)
    lora["blocks"] = {
        t: {**e, "B": jax.random.normal(k, e["B"].shape) * 0.05}
        for k, (t, e) in zip(kb, sorted(lora["blocks"].items()))}
    ids = jax.random.randint(jax.random.PRNGKey(seed + 3), (B, S), 0,
                             c.vocab_size)
    return c, params, lora, ids


def cfg_of(c) -> dict:
    return dataclasses.asdict(c)


def prog_loss(c, params, lora, ids, dt=jnp.float32, **kw):
    logits = afmoe.forward(c, params, ids, lora=lora, compute_dtype=dt,
                           **kw)
    s, n = lm_cross_entropy_sum(logits, ids)
    return s / n


def ref_logits(c, params, lora, ids, forced=None):
    f = None if forced is None else np.asarray(forced).reshape(
        c.num_moe_layers, B, S, -1)
    return jnp.stack([ref.forward(cfg_of(c), params, ids[b], lora,
                                  None if f is None else f[:, b])[0]
                      for b in range(B)])


# ----------------------------- (a) parity -----------------------------------

@pytest.fixture(scope="module")
def tiny():
    c, params, lora, ids = make()
    want = ref_logits(c, params, lora, ids)
    return c, params, lora, ids, want


def test_logits_and_loss_match_the_reference_in_float32(tiny):
    c, params, lora, ids, want = tiny
    got = afmoe.forward(c, params, ids, lora=lora)
    assert got.shape == (B, S, c.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(prog_loss(c, params, lora, ids),
                               ref.loss(want, ids), atol=1e-5)


def test_remat_and_padding_mask_leave_the_logits_alone(tiny):
    c, params, lora, ids, want = tiny
    got = afmoe.forward(c, params, ids, lora=lora, remat=True,
                        attention_mask=jnp.ones((B, S), jnp.float32))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_every_adapter_gradient_matches_the_reference_in_float32(tiny):
    c, params, lora, ids, _ = tiny
    got = jax.grad(lambda l: prog_loss(c, params, l, ids, remat=True))(lora)

    def ref_loss(l):
        return ref.loss(ref_logits(c, params, l, ids), ids)

    want = jax.grad(ref_loss)(lora)
    for t in sorted(lora["blocks"]):
        for leaf in ("A", "B"):
            g, w = got["blocks"][t][leaf], want["blocks"][t][leaf]
            assert float(jnp.abs(w).max()) > 1e-6, (t, leaf)
            np.testing.assert_allclose(g, w, atol=1e-5, err_msg=f"{t}.{leaf}")


def test_bfloat16_stays_inside_the_cell_s_tolerances():
    """The comparison the benchmark's child makes, at the tiny size: the
    reference under the program's own routing (logits, loss, an adapter
    gradient), and the two routings against each other."""
    c, params, lora, ids = make(seed=3)
    pb = jax.tree.map(lambda t: t.astype(jnp.bfloat16)
                      if t.ndim >= 3 or t.shape[0] == c.vocab_size else t,
                      params)
    logits, aux = afmoe.forward(c, pb, ids, lora=lora,
                                compute_dtype=jnp.bfloat16, with_aux=True)
    p32 = jax.tree.map(lambda t: t.astype(jnp.float32), pb)
    want = ref_logits(c, p32, lora, ids, forced=aux["idx"])
    assert correct_moe.rms_err(logits.astype(jnp.float32), want) \
        < correct_moe.LOGIT_TOL_RMS
    s, n = lm_cross_entropy_sum(logits, ids)
    assert abs(float(s / n) - float(ref.loss(want, ids))) \
        < correct_moe.LOSS_TOL
    g = jax.grad(lambda l: prog_loss(c, pb, l, ids, jnp.bfloat16))(lora)
    f = np.asarray(aux["idx"]).reshape(c.num_moe_layers, B, S, -1)
    gw = jax.grad(lambda l: ref.loss(jnp.stack(
        [ref.forward(cfg_of(c), p32, ids[b], l, f[:, b])[0]
         for b in range(B)]), ids))(lora)
    for t in ("q_proj", "o_proj"):
        assert correct_moe.rel_max(g["blocks"][t]["B"],
                                   gw["blocks"][t]["B"]) \
            < correct_moe.GRAD_TOL
        assert correct_moe.cosine(g["blocks"][t]["B"],
                                  gw["blocks"][t]["B"]) \
            > correct_moe.GRAD_COS
    # free routing: fed by the same history, the reference scores every
    # layer for itself; what the two disagree on are near-ties, and few
    for b in range(B):
        _, routings = ref.forward(cfg_of(c), p32, ids[b], lora, f[:, b])
        d = correct_moe.routing_disagreement(
            f[:, b], np.stack([r["biased"] for r in routings]))
        assert d["share"] <= correct_moe.ROUTE_SHARE_TOL, d
        assert d["max_gap"] <= correct_moe.ROUTE_GAP_TOL, d


# ----------------------------- (b) the shares add up ------------------------

def test_the_ranks_parts_and_one_shared_expert_add_up_to_the_uncut_layer():
    """--expert_parallel 4: every rank routes over all 8 experts and
    computes its two; the four parts plus ONE shared-expert term are the
    uncut reference layer."""
    c, params, _, _ = make(seed=5)
    m = jax.random.normal(jax.random.PRNGKey(9), (B * S, c.hidden_size))
    lp = jax.tree.map(lambda t: t[1], params["moe"])
    idx, w, _ = moe.route(m, lp["router_w"], lp["expert_bias"],
                          c.num_experts_per_tok, c.route_norm, c.route_scale)
    total = afmoe._swiglu(m, lp["shared"], jnp.float32)
    here = 0.0
    for rank in range(4):
        held = c.with_expert_parallel(4, rank).experts_held
        mine = jax.tree.map(lambda t: t[held[0]:held[0] + held[1]],
                            lp["experts"])
        part, counters = moe.expert_layer(m, idx, w, mine, held)
        assert float(counters["dropped"]) == 0.0
        here += float(counters["assign_here"])
        total = total + part
    assert here == B * S * c.num_experts_per_tok
    cfg = cfg_of(c)
    ridx, rw, _, _ = ref.router(cfg, m, lp)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(ridx, -1))
    want = ref.swiglu(m, lp["shared"]) + ref.routed_sum(
        cfg, m, ridx, rw, lp["experts"])
    np.testing.assert_allclose(total, want, atol=1e-5)


def test_a_rank_s_model_output_is_the_reference_given_the_same_share():
    c, params, lora, ids = make(seed=6)
    c4 = c.with_expert_parallel(4, 2)
    first, n = c4.experts_held
    mine = dict(params, moe={**params["moe"], "experts": jax.tree.map(
        lambda t: t[:, first:first + n], params["moe"]["experts"])})
    got = afmoe.forward(c4, mine, ids, lora=lora)
    want = ref_logits(c4, mine, lora, ids)
    np.testing.assert_allclose(got, want, atol=1e-5)
    whole = afmoe.forward(c, params, ids, lora=lora)
    assert float(jnp.abs(got - whole).max()) > 1e-3    # a part, not the sum


# ----------------------------- (c) the router -------------------------------

def router_inputs(seed=0, T=64, H=64, E=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (T, H)),
            jax.random.normal(k[1], (H, E)) * 0.1,
            jax.random.normal(k[2], (E,)) * 0.3)


def test_the_bias_moves_the_selection_and_not_the_weights():
    m, w_r, bias = router_inputs()
    i0, w0, _ = moe.route(m, w_r, jnp.zeros_like(bias), 2, False, 1.0)
    i1, w1, _ = moe.route(m, w_r, bias, 2, False, 1.0)
    assert (np.sort(i0, -1) != np.sort(i1, -1)).any()
    s = jax.nn.sigmoid(m @ w_r)
    np.testing.assert_allclose(w1, jnp.take_along_axis(s, i1, -1),
                               atol=1e-6)
    # a bias on one expert so large that every token selects it
    big = jnp.zeros_like(bias).at[5].set(10.0)
    i2, w2, _ = moe.route(m, w_r, big, 2, False, 1.0)
    assert (i2 == 5).any(-1).all() and float(w2.max()) <= 1.0


def test_the_weights_sum_to_route_scale():
    m, w_r, bias = router_inputs(1)
    _, w, _ = moe.route(m, w_r, bias, 2, True, 2.448)
    np.testing.assert_allclose(w.sum(-1), 2.448, rtol=1e-5)
    _, raw, _ = moe.route(m, w_r, bias, 2, False, 1.0)
    assert float(jnp.abs(raw.sum(-1) - 1.0).max()) > 1e-2


def experts_for(n, H=64, I=32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"gate_w": jax.random.normal(k[0], (n, H, I)) * 0.1,
            "up_w": jax.random.normal(k[1], (n, H, I)) * 0.1,
            "down_w": jax.random.normal(k[2], (n, I, H)) * 0.1}


def test_an_assignment_to_an_absent_expert_adds_exactly_nothing():
    m = router_inputs(2)[0]
    ex = experts_for(2)
    idx = jnp.stack([jnp.full((64,), 0), jnp.full((64,), 7)], -1)  # absent
    f, c = moe.expert_layer(m, idx.astype(jnp.int32),
                            jnp.ones((64, 2)), ex, (2, 2))
    assert float(jnp.abs(f).max()) == 0.0
    assert float(c["assign_here"]) == 0.0 and float(c["dropped"]) == 0.0
    g = jax.grad(lambda m: moe.expert_layer(
        m, idx.astype(jnp.int32), jnp.ones((64, 2)), ex, (2, 2))[0].sum())(m)
    assert float(jnp.abs(g).max()) == 0.0


def test_no_assignment_is_dropped_when_every_token_goes_to_one_expert():
    m = router_inputs(3)[0]
    ex = experts_for(2, seed=1)
    idx = jnp.stack([jnp.full((64,), 3), jnp.full((64,), 2)], -1) \
        .astype(jnp.int32)                  # both held: the worst case
    w = jax.random.uniform(jax.random.PRNGKey(4), (64, 2))
    f, c = moe.expert_layer(m, idx, w, ex, (2, 2))
    assert float(c["assign_here"]) == 128.0 and float(c["dropped"]) == 0.0
    assert float(c["load_max_over_mean"]) == 1.0

    def one(e):
        p = jax.tree.map(lambda t: t[e], ex)
        return afmoe._swiglu(m, p, jnp.float32)

    want = w[:, :1] * one(1) + w[:, 1:] * one(0)
    np.testing.assert_allclose(f, want, atol=1e-5)
    # all to ONE held expert, the other choice absent
    idx1 = jnp.stack([jnp.full((64,), 3), jnp.full((64,), 6)], -1) \
        .astype(jnp.int32)
    f1, c1 = moe.expert_layer(m, idx1, w, ex, (2, 2))
    assert float(c1["assign_here"]) == 64.0 and float(c1["dropped"]) == 0.0
    assert float(c1["load_max_over_mean"]) == 2.0
    np.testing.assert_allclose(f1, w[:, :1] * one(1), atol=1e-5)


@pytest.mark.parametrize("fault", ["bias_in_the_weights", "no_normalisation",
                                   "softmax_for_sigmoid",
                                   "top_k_over_the_held_only"])
def test_a_wrong_router_fails_the_comparison(monkeypatch, fault):
    """Each classic router fault moves the logits of a rank's model away
    from the reference by far more than the float32 tolerance."""
    c, params, lora, ids = make(seed=7)
    c = c.with_expert_parallel(2, 1)
    first, n = c.experts_held
    params = dict(params, moe={**params["moe"], "experts": jax.tree.map(
        lambda t: t[:, first:first + n], params["moe"]["experts"])})
    want = ref_logits(c, params, lora, ids)
    np.testing.assert_allclose(afmoe.forward(c, params, ids, lora=lora),
                               want, atol=1e-5)

    def wrong(m, w_r, bias, k, norm, scale):
        logits = m.astype(jnp.float32) @ w_r
        s = jax.nn.sigmoid(logits)
        if fault == "softmax_for_sigmoid":
            s = jax.nn.softmax(logits, -1)
        sel = s + bias
        if fault == "top_k_over_the_held_only":
            e = jnp.arange(s.shape[-1])
            sel = jnp.where((e >= first) & (e < first + n), sel, -jnp.inf)
        _, idx = jax.lax.top_k(sel, k)
        w = jnp.take_along_axis(
            sel if fault == "bias_in_the_weights" else s, idx, -1)
        if norm and fault != "no_normalisation":
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * scale, None

    monkeypatch.setattr(moe, "route", wrong)
    got = afmoe.forward(c, params, ids, lora=lora)
    assert float(jnp.abs(got - want).max()) > 1e-3


# ----------------------------- (d) positions and terms ----------------------

def test_full_layers_ignore_positions_and_sliding_layers_do_not():
    c, params, lora, ids = make(seed=8)
    shifted = jnp.arange(S) + 5
    full = dataclasses.replace(
        c, layer_types=["full_attention"] * c.num_hidden_layers)
    a = afmoe.forward(full, params, ids, lora=lora)
    b = afmoe.forward(full, params, ids, lora=lora, position_ids=shifted)
    np.testing.assert_allclose(a, b, atol=1e-6)
    # rotary is relative: a common shift leaves a sliding layer alone,
    # a stretch does not
    a = afmoe.forward(c, params, ids, lora=lora)
    b = afmoe.forward(c, params, ids, lora=lora, position_ids=shifted)
    np.testing.assert_allclose(a, b, atol=2e-5)
    d = afmoe.forward(c, params, ids, lora=lora,
                      position_ids=2 * jnp.arange(S))
    assert float(jnp.abs(a - d).max()) > 1e-3
    for b_ in range(B):
        want = ref.forward(cfg_of(c), params, ids[b_], lora,
                           positions=2 * jnp.arange(S))[0]
        np.testing.assert_allclose(d[b_], want, atol=1e-5)


NORMS = ("input_ln", "q_norm", "k_norm", "post_attn_ln", "pre_mlp_ln",
         "post_mlp_ln")


@pytest.mark.parametrize("term", ("output_gate", "sqrt_h_scale") + NORMS)
def test_parity_fails_when_a_term_is_removed(monkeypatch, tiny, term):
    """The comparison sees each term of the block: the program with the
    output gate, one of the six norms or the sqrt(H) multiplier removed
    no longer matches the reference."""
    c, params, lora, ids, want = tiny
    if term == "sqrt_h_scale":
        c = dataclasses.replace(c, mup_enabled=False)
    elif term == "output_gate":
        monkeypatch.setattr(afmoe, "_output_gate", lambda ctx, gate: ctx)
    else:
        # _block calls rms_norm six times, in NORMS' order, per trace
        real, calls = afmoe.rms_norm, {"n": 0}

        def skipping(x, w, eps):
            i = calls["n"]
            calls["n"] += 1
            blocks = 6 * (c.num_dense_layers + 1)   # dense eager + scan
            if i < blocks and i % 6 == NORMS.index(term):
                return x
            return real(x, w, eps)

        monkeypatch.setattr(afmoe, "rms_norm", skipping)
    got = afmoe.forward(c, params, ids, lora=lora)
    assert float(jnp.abs(got - want).max()) > 1e-3, term


# ----------------------------- (e) the loader -------------------------------

def test_the_loader_keeps_bf16_and_reads_only_the_held_experts(
        tmp_path, monkeypatch):
    from fixtures import write_tiny_afmoe_dir
    from mobilefinetuner_tpu.io import checkpoints, safetensors_io
    d = str(tmp_path / "afmoe")
    c, written = write_tiny_afmoe_dir(d, dtype=jnp.bfloat16)
    seen = []
    real = safetensors_io.SafeTensorsReader.load

    def spy(self, name, *a, **kw):
        seen.append(name)
        return real(self, name, *a, **kw)

    monkeypatch.setattr(safetensors_io.SafeTensorsReader, "load", spy)
    c2, params = checkpoints.load_afmoe(d, experts_held=(2, 4))
    assert c2.experts_held == (2, 4) and c2.num_experts == c.num_experts
    held = {int(m.group(1)) for m in (
        re.search(r"experts\.(\d+)\.", k) for k in seen) if m}
    assert held == {2, 3, 4, 5}
    for name in ("gate_w", "up_w", "down_w"):
        got = params["moe"]["experts"][name]
        assert got.dtype.name == "bfloat16"
        assert got.shape[:2] == (c.num_moe_layers, 4)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(written["moe"]["experts"][name][:, 2:6], np.float32))
    assert params["embed"].dtype.name == "bfloat16"
    assert params["moe"]["router_w"].dtype == np.float32
    assert params["final_norm"].dtype == np.float32
    # the whole tree round-trips, and a placed tree stays bf16
    _, full = checkpoints.load_afmoe(d)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), full,
        jax.tree.map(np.asarray, written))
    placed = jax.tree.map(jnp.asarray, params)
    assert placed["dense"]["mlp"]["up_w"].dtype == jnp.bfloat16
    back = checkpoints.afmoe_params_to_hf(params, first_expert=2)
    assert "model.layers.1.mlp.experts.2.up_proj.weight" in back
    assert "model.layers.1.mlp.experts.1.up_proj.weight" not in back


# ----------------------------- (f) the CLI ----------------------------------

def test_the_cli_trains_through_run_training_with_counters_and_an_adapter(
        tmp_path):
    from fixtures import write_tiny_afmoe_dir, write_wikitext_dir
    from mobilefinetuner_tpu.cli import train_lora_afmoe
    from mobilefinetuner_tpu.lora import peft_io
    model = str(tmp_path / "afmoe")
    c, _ = write_tiny_afmoe_dir(model)
    data = write_wikitext_dir(str(tmp_path / "wt2"))
    out = str(tmp_path / "out" / "adapter.safetensors")
    tel = str(tmp_path / "tel.jsonl")
    rc = train_lora_afmoe.main([
        "--pretrained_dir", model, "--data_dir", data, "--dtype", "float32",
        "--seq_len", "32", "--batch_size", "2", "--steps", "4", "--remat",
        "--rank", "4", "--alpha", "8", "--lr", "2e-2",
        "--lora_targets", "q_proj,k_proj,v_proj,o_proj",
        "--expert_parallel", "2", "--expert_rank", "1",
        "--lr_schedule", "constant", "--log_interval", "1",
        "--telemetry_out", tel, "--lora_out", out,
        "--peft_export_dir", str(tmp_path / "peft")])
    assert rc == 0
    events = [json.loads(ln) for ln in open(tel)]
    stats = [e for e in events if e["event"] == "step_stats"]
    assert len(stats) == 4
    assert stats[-1]["loss"] < stats[0]["loss"]
    T_k = 2 * 32 * c.num_experts_per_tok * c.num_moe_layers
    for st in stats:
        assert 0 < st["moe_assign_here"] < T_k
        assert st["moe_dropped"] == 0.0
        assert st["moe_load_max_over_mean"] >= 1.0
    start = next(e for e in events if e["event"] == "run_start")
    assert start["config"]["expert_parallel"] == 2
    # the native adapter resumes, the PEFT export reads back equal
    lora, spec = peft_io.load_adapter(out)
    assert spec.targets == ["k_proj", "o_proj", "q_proj", "v_proj"]
    assert lora["blocks"]["q_proj"]["A"].shape[0] == c.num_hidden_layers
    back, spec2 = peft_io.import_peft(str(tmp_path / "peft"), "afmoe")
    assert spec2.rank == 4
    for t in spec.targets:
        for leaf in ("A", "B"):
            np.testing.assert_allclose(back["blocks"][t][leaf],
                                       lora["blocks"][t][leaf], atol=1e-7)
    assert float(jnp.abs(lora["blocks"]["q_proj"]["B"]).max()) > 0


# ----------------------------- scopes ---------------------------------------

def test_every_op_of_an_expert_layer_s_mlp_lies_under_one_moe_scope():
    """The device times under moe_router / moe_dispatch / moe_experts /
    moe_shared / moe_combine add up to the expert layers' `mlp` time: in
    the lowered train step, forward and backward, every op whose name
    path runs through an `mlp` scope runs through exactly one of them;
    the two kinds of attention call lie under attn_window and attn_full,
    one branch of the layer's cond each."""
    # no dense layer here, so that every `mlp` op is an expert layer's
    c, params, lora, ids = make(seed=9, num_dense_layers=0,
                                num_hidden_layers=4)
    text = jax.jit(jax.grad(
        lambda l: prog_loss(c, params, l, ids, remat=True))).lower(
        lora).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    in_mlp = [n for n in names if re.search(r"\bmlp\b", n)]
    assert len(in_mlp) > 50
    kinds = set()
    for n in in_mlp:
        inside = set(re.findall(r"moe_(?:router|dispatch|experts|shared"
                                r"|combine)", n))
        assert len(inside) == 1, n
        kinds |= inside
    assert kinds == {"moe_router", "moe_dispatch", "moe_experts",
                     "moe_shared", "moe_combine"}
    assert any("attention/cond/branch_0_fun/attn_window" in n
               for n in names)
    assert any("attention/cond/branch_1_fun/attn_full" in n for n in names)
