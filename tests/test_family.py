"""A model family is one record (models/family.py).

(a) no module of the package other than models/family.py compares a
family name with a literal; (b) each record's facts agree with its
config and with init_params' tree; (c) a THIRD record, made here, is
served by an unmodified ServeEngine; (d) the family-prefixed program
names are bindings of the single programs."""

import ast
import dataclasses
import functools
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mobilefinetuner_tpu.core.config import GPT2Config, Gemma3TextConfig
from mobilefinetuner_tpu.models import family as family_mod
from mobilefinetuner_tpu.models import generate as g
from mobilefinetuner_tpu.models.family import (FAMILIES, GEMMA, GPT2,
                                               family_of, get_family)
from mobilefinetuner_tpu.serve import ServeConfig, ServeEngine
from mobilefinetuner_tpu.serve.paged_kv import init_pools

PKG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                   "mobilefinetuner_tpu")

GPT2_CFG = dataclasses.replace(
    GPT2Config.tiny(vocab_size=211), n_embd=64, n_head=4, n_positions=64,
    n_layer=3, embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)
# sliding_window (6) < prompt+gen so local layers actually truncate
GEMMA_CFG = dataclasses.replace(
    Gemma3TextConfig.tiny(vocab_size=199), hidden_size=48, head_dim=12,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    num_hidden_layers=4, sliding_window=6, sliding_window_pattern=3)
CASES = [pytest.param(GPT2, GPT2_CFG, id="gpt2"),
         pytest.param(GEMMA, GEMMA_CFG, id="gemma")]


# --------------------------- (a) the branches are gone ----------------------

def _literal_strings(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return True
    return isinstance(node, (ast.Tuple, ast.List, ast.Set)) and any(
        _literal_strings(e) for e in node.elts)


def _names_family(node):
    return (isinstance(node, ast.Name) and node.id == "family") or (
        isinstance(node, ast.Attribute) and node.attr == "family")


def test_no_module_but_family_compares_a_family_name_with_a_literal():
    found = []
    for root, _dirs, files in os.walk(PKG):
        for name in files:
            path = os.path.join(root, name)
            rel = os.path.relpath(path, PKG)
            if not name.endswith(".py") or rel == os.path.join(
                    "models", "family.py"):
                continue
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Compare):
                    continue
                sides = [node.left] + node.comparators
                if any(_names_family(s) for s in sides) and any(
                        _literal_strings(s) for s in sides):
                    found.append(f"{rel}:{node.lineno}")
    assert not found, (
        f"family compared with a string literal at {found}: ask the "
        f"record (models/family.py) instead")


# --------------------------- (b) the records' facts -------------------------

@pytest.mark.parametrize("fam,config", CASES)
def test_record_facts_agree_with_config_and_param_tree(fam, config):
    assert get_family(fam.name) is fam and family_of(config) is fam
    d = fam.dims(config)
    params = jax.eval_shape(
        lambda: fam.model.init_params(config, jax.random.PRNGKey(0)))
    blocks = params["blocks"]
    assert all(x.shape[0] == d.L for x in jax.tree.leaves(blocks))
    V, E = params[fam.head_key].shape
    assert V == config.vocab_size
    # q/k/v of one row through the record's own qkv
    layer0 = jax.tree.map(
        lambda x: jnp.zeros(x.shape[1:], x.dtype), blocks)
    xs = fam.layer_xs(config)
    pos = jnp.zeros((1,), jnp.int32)
    q, k, v = fam.qkv(config, jnp.zeros((1, E)), layer0, 0,
                      None if xs is None else xs[0],
                      fam.pos_ctx(config, pos), g.AdapterSites(None, "xla"),
                      None)
    assert q.shape == (1, d.KV, d.nq // d.KV, d.D)
    assert k.shape == v.shape == (1, d.KV, d.D)
    assert d.scale > 0
    if fam is GPT2:
        assert d.cap == config.n_positions and d.window is None
        assert fam.max_len(config) == config.n_positions
    else:
        assert d.cap is None and d.window == config.sliding_window
        assert fam.max_len(config) == config.max_position_embeddings
    # placement: every matmul weight of a block ([L, in, out]) is in
    # exactly one leaf set, and the sets name nothing the tree lacks;
    # what they hold besides is the column-parallel matmuls' biases
    sets = (fam.col_leaves, fam.kv_col_leaves, fam.row_leaves)
    assert not (sets[0] & sets[1] or sets[0] & sets[2] or sets[1] & sets[2])
    named = set().union(*sets)
    leaves = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(blocks):
        leaves.setdefault(path[-1].key, set()).add(leaf.ndim)
    matmuls = {n for n, nds in leaves.items() if max(nds) >= 3}
    assert matmuls <= named <= set(leaves)
    assert all(n.endswith("_b") and n[:-2] + "_w" in fam.col_leaves
               for n in named - matmuls)


def naive_forward(fam, config, params, ids, dtype=jnp.float32):
    """[T] ids -> (final-normed x [T, E], k, v [L, KV, T, D]): every
    position recomputed with dense attention, from the record's own
    five functions and nothing else of the program."""
    d = fam.dims(config)
    T = ids.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    sites = g.AdapterSites(None, "xla")
    x = fam.embed(config, params, ids, pos, dtype)
    ctx = fam.pos_ctx(config, pos)
    causal = pos[:, None] >= pos[None, :]
    win = None if d.window is None else \
        (pos[:, None] - pos[None, :]) < d.window
    xs = fam.layer_xs(config)
    ks, vs = [], []
    for i in range(d.L):
        bp = jax.tree.map(lambda a: jnp.asarray(a)[i], params["blocks"])
        layer_x = None if xs is None else xs[i]
        q, k, v = fam.qkv(config, x, bp, i, layer_x, ctx, sites, None)
        s = jnp.einsum("rkgd,ckd->rkgc", q, k) * d.scale
        ok = fam.layer_ok(layer_x, causal, win)
        p = jax.nn.softmax(jnp.where(ok[:, None, None, :], s, -1e30), -1)
        attn = jnp.einsum("rkgc,ckd->rkgd", p, v)
        x = fam.post(config, x, attn.reshape(T, d.nq * d.D), bp, i, sites,
                     None)
        ks.append(k.transpose(1, 0, 2))
        vs.append(v.transpose(1, 0, 2))
    return (fam.final_norm(config, params, x, dtype), jnp.stack(ks),
            jnp.stack(vs))


@pytest.mark.parametrize("fam,config", CASES)
def test_record_block_is_the_models_block(fam, config):
    """The five functions, composed naively, give the training module's
    hidden states and K/V: the record describes the SAME model."""
    params = fam.model.init_params(config, jax.random.PRNGKey(2))
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 190, 13),
                      jnp.int32)
    want, (wk, wv) = fam.model.hidden_states(
        config, params, ids[None], jnp.ones((1, 13), jnp.int32),
        collect_kv=True)
    got, k, v = naive_forward(fam, config, params, ids)
    np.testing.assert_allclose(got, want[0], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(k, wk[:, 0], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(v, wv[:, 0], atol=2e-5, rtol=2e-5)


# --------------------------- (c) a third family is one record ---------------

def _loud_qkv(c, x, bp, i, layer_x, ctx, sites, shd):
    q, k, v = GPT2.qkv(c, x, bp, i, layer_x, ctx, sites, shd)
    return -2.0 * q, k, v


def _hidden_states(fam, config, params, input_ids, attention_mask,
                   lora=None, compute_dtype=jnp.float32, collect_kv=False,
                   lora_impl="auto"):
    assert lora is None and collect_kv
    x, k, v = jax.vmap(functools.partial(
        naive_forward, fam, config, params, dtype=compute_dtype))(input_ids)
    return x, (k.transpose(1, 0, 2, 3, 4), v.transpose(1, 0, 2, 3, 4))


def rollout(fam, config, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        x, _, _ = naive_forward(fam, config, params,
                                jnp.asarray(toks, jnp.int32))
        logits = x[-1] @ jnp.asarray(params[fam.head_key]).T
        toks.append(int(jnp.argmax(logits)))
    return toks[len(prompt):]


def test_a_third_record_is_served_by_the_unmodified_engine(monkeypatch):
    """GPT-2's record with q negated and doubled in `qkv` — a model no
    module of the package knows — registered under a new name: the
    engine's prefill, prefill chunks and paged decode steps serve it
    token-identically to a full-recompute rollout of the record."""
    loud = dataclasses.replace(GPT2, name="gpt2-loud", qkv=_loud_qkv)
    loud = dataclasses.replace(loud, model=types.SimpleNamespace(
        hidden_states=functools.partial(_hidden_states, loud)))
    monkeypatch.setitem(FAMILIES, loud.name, loud)
    params = GPT2.model.init_params(GPT2_CFG, jax.random.PRNGKey(0))
    # a fresh init attends almost uniformly: make attention matter
    params["blocks"]["attn"] = jax.tree.map(lambda w: 25.0 * w,
                                            params["blocks"]["attn"])
    rng = np.random.default_rng(5)
    # 13 > max_prompt: admitted through two prefill chunks
    prompts = [list(rng.integers(1, 200, n)) for n in (3, 8, 5, 13)]
    eng = ServeEngine(loud.name, GPT2_CFG, params, ServeConfig(
        num_slots=2, block_T=4, num_blocks=24, max_prompt=8,
        max_new_tokens=6, dtype="float32", max_prompt_chunked=16))
    try:
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        done = {r.id: r.tokens for r in eng.drain()}
        assert eng.trace_counts["prefill_chunk"] >= 1
    finally:
        eng.close()
    want = [rollout(loud, GPT2_CFG, params, p, 6) for p in prompts]
    assert [done[r.id] for r in reqs] == want
    # and the difference is visible: it is not GPT-2 that was served
    assert want != [rollout(GPT2, GPT2_CFG, params, p, 6) for p in prompts]
    with pytest.raises(ValueError, match="unknown family 'bert'"):
        ServeEngine("bert", GPT2_CFG, params)


# --------------------------- (d) one program, six names ---------------------

def _step_jaxpr(step, fam, config):
    d = fam.dims(config)
    params = jax.eval_shape(
        lambda: fam.model.init_params(config, jax.random.PRNGKey(0)))
    pk, pv = jax.eval_shape(
        lambda: init_pools(5, d.L, d.KV, 4, d.D, jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    return str(jax.make_jaxpr(functools.partial(
        step, config, attn_impl="xla"))(params, pk, pv, i32(2), i32(2),
                                        i32(2, 3)))


def test_family_prefixed_names_are_the_single_programs():
    for one, names in ((g.prefill, ("gpt2_prefill", "gemma3_prefill")),
                       (g.prefill_chunk, ("gpt2_prefill_chunk",
                                          "gemma3_prefill_chunk")),
                       (g.decode_step_paged, ("gpt2_decode_step_paged",
                                              "gemma3_decode_step_paged"))):
        for name, fam in zip(names, (GPT2, GEMMA)):
            bound = getattr(g, name)
            assert bound.func is one and bound.args == (fam,), name
            assert not bound.keywords
    # one function, two programs
    a = _step_jaxpr(g.gpt2_decode_step_paged, GPT2, GPT2_CFG)
    b = _step_jaxpr(g.gemma3_decode_step_paged, GEMMA, GEMMA_CFG)
    assert a != b and "cos" in b and "cos" not in a
    assert family_mod.detect_family.__module__ == family_mod.__name__
