"""The serve engine's RESIDENT base weights (DESIGN.md §16, §21).

`ServeEngine` holds its base tree in `cfg.dtype`: cast once at build,
leaf by leaf, so the decode step and the prefill chunk never re-cast
the tree they are handed. The model programs keep their contract — any
float tree in, compute in `compute_dtype` — so the same programs on the
caller's float32 tree and on the engine's pre-cast tree must agree:
exactly where every weight is read AFTER its cast (decode step, chunk),
within one bf16 rounding of the embedding sum in GPT-2's one-shot
prefill (`gpt2.hidden_states` adds the two rows in the tree's dtype)."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark"))

from mobilefinetuner_tpu.core import memory_guard as mg
from mobilefinetuner_tpu.core.config import GPT2Config, Gemma3TextConfig
from mobilefinetuner_tpu.core.telemetry import Telemetry, validate_event
from mobilefinetuner_tpu.models import gemma3, gpt2
from mobilefinetuner_tpu.models.family import get_family
from mobilefinetuner_tpu.models.generate import (decode_step_paged, prefill,
                                                 prefill_chunk)
from mobilefinetuner_tpu.serve import ServeConfig, ServeEngine
from mobilefinetuner_tpu.serve.paged_kv import init_pools

from test_telemetry import read_events

GPT2_CFG = dataclasses.replace(
    GPT2Config.tiny(vocab_size=211), n_embd=64, n_head=4, n_positions=64,
    n_layer=3, embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)
GEMMA_CFG = dataclasses.replace(
    Gemma3TextConfig.tiny(vocab_size=199), hidden_size=48, head_dim=12,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    num_hidden_layers=4, sliding_window=6, sliding_window_pattern=3)
FAMS = {"gpt2": (GPT2_CFG, gpt2.init_params),
        "gemma": (GEMMA_CFG, gemma3.init_params)}
ENGINE = dict(num_slots=2, block_T=8, num_blocks=16, max_prompt=16,
              max_new_tokens=8)


@pytest.fixture(scope="module")
def f32_params():
    return {f: init(c, jax.random.PRNGKey(3)) for f, (c, init)
            in FAMS.items()}


def bf16_engine(family, params, **kw):
    return ServeEngine(family, FAMS[family][0], params,
                       ServeConfig(dtype="bfloat16", **{**ENGINE, **kw}))


# --------------------------- (a) what the engine holds ----------------------

@pytest.mark.parametrize("family", sorted(FAMS))
@pytest.mark.parametrize("born", ["device", "host"])
def test_bf16_engine_holds_only_bf16_leaves(family, born, f32_params):
    given = f32_params[family]
    if born == "host":
        given = jax.tree.map(np.asarray, given)
    eng = bf16_engine(family, given)
    held, src = jax.tree.leaves(eng.params), jax.tree.leaves(given)
    assert len(held) == len(src)
    assert all(isinstance(x, jax.Array) and x.dtype == jnp.bfloat16
               for x in held)
    assert not any(h is s for h in held for s in src)
    # the same values, rounded once: what the in-step cast produced
    for h, s in zip(held, src):
        np.testing.assert_array_equal(
            np.asarray(h), np.asarray(jnp.asarray(s).astype(jnp.bfloat16)))
    # and the engine serves from them
    r = eng.submit([5, 6, 7], 4)
    eng.drain()
    assert r.state == "finished" and len(r.tokens) == 4


@pytest.mark.parametrize("family", sorted(FAMS))
def test_f32_engine_holds_the_arrays_it_was_given(family, f32_params):
    given = f32_params[family]
    eng = ServeEngine(family, FAMS[family][0], given,
                      ServeConfig(dtype="float32", **ENGINE))
    held, src = jax.tree.leaves(eng.params), jax.tree.leaves(given)
    assert len(held) == len(src)
    assert all(h is s for h, s in zip(held, src))


def test_a_bf16_tree_is_kept_as_it_is(f32_params):
    given = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                         f32_params["gpt2"])
    eng = bf16_engine("gpt2", given)
    assert all(h is s for h, s in zip(jax.tree.leaves(eng.params),
                                      jax.tree.leaves(given)))


# --------------------------- (b) the same logits ----------------------------

def _decode_inputs(config, fam):
    d = fam.dims(config)
    pk, pv = init_pools(8, d.L, d.KV, 8, d.D, jnp.bfloat16)
    # pages that hold something, so attention reads more than zeros
    k0, k1 = jax.random.split(jax.random.PRNGKey(11))
    pk = (0.5 * jax.random.normal(k0, pk.shape)).astype(pk.dtype)
    pv = (0.5 * jax.random.normal(k1, pv.shape)).astype(pv.dtype)
    tok = jnp.asarray([7, 9], jnp.int32)
    pos = jnp.asarray([5, 13], jnp.int32)
    tbl = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
    return pk, pv, tok, pos, tbl


@pytest.mark.parametrize("family", sorted(FAMS))
def test_decode_step_and_chunk_logits_are_bit_identical(family,
                                                        f32_params):
    """Every weight of the decode step and of the chunk is read after
    its cast to compute_dtype, so rounding the tree once beforehand
    changes no bit: logits AND the written pools compare equal."""
    config, fam = FAMS[family][0], get_family(family)
    raw = f32_params[family]
    held = bf16_engine(family, raw).params
    pk, pv, tok, pos, tbl = _decode_inputs(config, fam)

    step = jax.jit(lambda p: decode_step_paged(
        fam, config, p, pk, pv, tok, pos, tbl,
        compute_dtype=jnp.bfloat16, attn_impl="xla"))
    for a, b in zip(step(raw), step(held)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    ids = jnp.asarray([[4, 8, 15, 16, 23, 42, 0, 0]], jnp.int32)
    chunk = jax.jit(lambda p: prefill_chunk(
        fam, config, p, pk, pv, ids, jnp.int32(8), jnp.int32(6),
        tbl[1:], compute_dtype=jnp.bfloat16))
    for a, b in zip(chunk(raw), chunk(held)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_one_shot_prefill_differs_by_the_embedding_sums_rounding_only(
        f32_params):
    """The one numerical difference of a resident bf16 tree. GPT-2's
    one-shot prefill goes through `gpt2.hidden_states`, which adds
    wte[ids] + wpe[pos] in the TREE's dtype and casts the sum: on a
    bf16 tree both rows are rounded before the add (what the decode
    step's and the chunk's `fam.embed` always did), on a float32 tree
    only the sum is. PROOF that nothing else differs: the float32 tree
    with just those two tables rounded to bf16 values gives the bf16
    tree's logits and K/V bit for bit. Gemma's prefill casts the row
    before it scales it, so there the raw trees agree exactly.

    The tolerance this sets for an engine-level bf16 prefill: at the
    input the difference is at most one bf16 unit of an embedding
    entry, but a changed input re-draws every rounding through the
    blocks, so at the logits the two bf16 runs lie as far from each
    other as either lies from float32 (4e-3 to 1.1e-2 of the largest
    logit over six seeds at this size). The bound is therefore the
    benchmark's own for bf16 compute at this depth, `logit_tol` =
    6e-3 x sqrt(3) = 1.04e-2, no looser: each tree's logits against
    the float32 forward, and the two against each other."""
    from harness.correct import logit_tol, rel_max
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 200, (1, 16)),
                      jnp.int32)
    mask = (jnp.arange(16) < 13).astype(jnp.int32)[None]

    def run(family, p, dt=jnp.bfloat16):
        out = jax.jit(lambda p: prefill(
            get_family(family), FAMS[family][0], p, ids, mask,
            compute_dtype=dt))(p)
        return [np.asarray(x) for x in jax.tree.leaves(out)]

    raw = f32_params["gemma"]
    for a, b in zip(run("gemma", raw),
                    run("gemma", bf16_engine("gemma", raw).params)):
        np.testing.assert_array_equal(a, b)

    raw = f32_params["gpt2"]
    held = bf16_engine("gpt2", raw).params
    rows_first = {**raw, "wte": held["wte"].astype(jnp.float32),
                  "wpe": held["wpe"].astype(jnp.float32)}
    of_raw, of_held = run("gpt2", raw), run("gpt2", held)
    for a, b in zip(run("gpt2", rows_first), of_held):
        np.testing.assert_array_equal(a, b)
    tol = logit_tol(GPT2_CFG.n_layer)
    f32 = run("gpt2", raw, jnp.float32)[0]
    assert 0.0 < rel_max(of_held[0], of_raw[0]) <= tol
    assert rel_max(of_held[0], f32) <= tol
    assert rel_max(of_raw[0], f32) <= tol


# --------------------------- (c) the lowered step ---------------------------

@pytest.mark.parametrize("family", sorted(FAMS))
def test_lowered_bf16_step_takes_no_f32_weight_and_casts_none(
        family, f32_params):
    eng = bf16_engine(family, f32_params[family])
    text = eng.lower_decode_step().as_text(debug_info=True)
    main = text[text.index("func.func public @main"):]
    args = {(tuple(int(d) for d in dims.split("x") if d), dt)
            for dims, dt in re.findall(r"tensor<((?:\d+x)*)(\w+)>",
                                       main[:main.index("{\n")])}
    for shape in {tuple(np.shape(x)) for x in jax.tree.leaves(eng.params)}:
        assert (shape, "bf16") in args and (shape, "f32") not in args, shape
    # no op under the weight-cast scope: on a tree that already is
    # compute_dtype `astype` emits nothing
    assert "decode_weight_cast" not in text
    # the float32 engine's step never had one either; a bf16 engine
    # handed the programs' contract tree (float32) still casts in-step
    fam, conf = get_family(family), FAMS[family][0]
    raw = f32_params[family]
    legacy = jax.jit(lambda p, pk, pv, tok, pos, tbl: decode_step_paged(
        fam, conf, p, pk, pv, tok, pos, tbl, compute_dtype=jnp.bfloat16,
        attn_impl="xla")).lower(
            raw, eng.pool_k, eng.pool_v, jnp.zeros(2, jnp.int32),
            jnp.zeros(2, jnp.int32), jnp.zeros((2, eng.M), jnp.int32)
    ).as_text(debug_info=True)
    assert "decode_weight_cast" in legacy


# --------------------------- (d) admission ----------------------------------

def test_mem_check_counts_the_weights_at_the_held_dtype(f32_params):
    raw = f32_params["gpt2"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(raw))
    e16 = bf16_engine("gpt2", raw, hbm_cap_mb=1000)
    e32 = ServeEngine("gpt2", GPT2_CFG, raw, ServeConfig(
        dtype="float32", hbm_cap_mb=1000, **ENGINE))
    assert e16.mem_check.est_mb - e16.pool_mb \
        == pytest.approx(2 * n / 2 ** 20)
    assert e32.mem_check.est_mb - e32.pool_mb \
        == pytest.approx(4 * n / 2 ** 20)
    # the refusal's max_blocks follows: budget = cap x (1 - headroom)
    # less the weights AS HELD, in pages of the pool's dtype
    cap, head = 2, 0.1
    with pytest.raises(mg.MemoryAdmissionError) as ei:
        bf16_engine("gpt2", raw, num_blocks=4096, hbm_cap_mb=cap,
                    hbm_headroom=head)
    d = get_family("gpt2").dims(GPT2_CFG)
    page_mb = 2 * d.L * d.KV * ENGINE["block_T"] * d.D * 2 / 2 ** 20
    want = int((cap * (1 - head) - 2 * n / 2 ** 20) // page_mb)
    msg = str(ei.value)
    assert int(msg.split("num_blocks=")[1].split()[0]) == want
    assert f"params {2 * n / 2 ** 20:.0f} MB" in msg
    # counted as it arrives (4 B) the same tree left fewer pages
    assert int((cap * (1 - head) - 4 * n / 2 ** 20) // page_mb) < want


# --------------------------- (e) placement under a mesh ---------------------

@pytest.mark.parametrize("family", sorted(FAMS))
def test_cast_tree_carries_param_shardings_placement(family, f32_params):
    raw = f32_params[family]
    eng = bf16_engine(family, raw, num_slots=4, num_blocks=32, mesh_dp=2,
                      mesh_tp=2)
    want = eng.sharding.param_shardings(raw)
    sharded = 0
    for x, s in zip(jax.tree.leaves(eng.params), jax.tree.leaves(want)):
        assert x.dtype == jnp.bfloat16
        assert x.sharding.is_equivalent_to(s, x.ndim), (x.shape, s)
        sharded += not s.is_fully_replicated
    assert sharded            # tp really shards some leaf
    one = bf16_engine(family, raw, num_slots=4, num_blocks=32)
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8, 9, 7]]
    toks = []
    for e in (eng, one):
        rs = [e.submit(p, 6) for p in prompts]
        e.drain()
        toks.append([r.tokens for r in rs])
    assert toks[0] == toks[1]


# --------------------------- (f) the stream ---------------------------------

def test_run_start_says_what_the_weights_are_held_in(f32_params,
                                                     tmp_path):
    raw = f32_params["gpt2"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(raw))
    for dtype, size in (("bfloat16", 2), ("float32", 4)):
        stream = str(tmp_path / f"{dtype}.jsonl")
        eng = ServeEngine("gpt2", GPT2_CFG, raw, ServeConfig(
            dtype=dtype, trace_spans=True, **ENGINE),
            telemetry=Telemetry(stream))
        eng.close()
        recs = read_events(stream)
        assert all(validate_event(r) is None for r in recs)
        assert recs[0]["event"] == "run_start"
        conf = recs[0]["config"]
        assert conf["weights_dtype"] == dtype
        assert conf["weights_mb"] == pytest.approx(size * n / 2 ** 20,
                                                   abs=1e-3)
        assert conf["weight_cast_ms"] >= 0.0
        spans = [r for r in recs if r["event"] == "span"
                 and r["name"] == "serve.weight_cast"]
        assert len(spans) == 1
        assert spans[0]["dur_ms"] == pytest.approx(
            conf["weight_cast_ms"], abs=1e-3)
