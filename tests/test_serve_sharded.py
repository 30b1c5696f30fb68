"""Sharded serving tests (serve/sharding.py, DESIGN.md §25).

The correctness anchor is TOKEN PARITY: a ServeEngine running its
decode step over a (dp, tp) device mesh — TP-partitioned attention
heads + MLP hidden, per-shard KV pool slices, block-diagonally placed
adapter banks — must be token-IDENTICAL to the single-chip engine for
the same request set, mixed base+adapter, through hot-swaps. And the
COMPILE-STABILITY invariant survives sharding: zero post-warmup
retraces at every mesh shape (the bank swap stays one traced
`at[slot].set` on NamedSharding-stable buffers).

Runs on the 8-virtual-device CPU platform conftest.py forces."""

import dataclasses
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mobilefinetuner_tpu.core.config import GPT2Config, Gemma3TextConfig
from mobilefinetuner_tpu.lora.lora import (LoRASpec, init_lora_gemma3,
                                           init_lora_gpt2)
from mobilefinetuner_tpu.models import gemma3, gpt2
from mobilefinetuner_tpu.ops.decode_attention import (paged_eligible,
                                                      pick_kvb,
                                                      shard_heads)
from mobilefinetuner_tpu.serve import (AdapterBank, ServeConfig,
                                       ServeEngine, ServeSharding,
                                       make_serve_mesh)

GPT2_CFG = dataclasses.replace(
    GPT2Config.tiny(vocab_size=211), n_embd=64, n_head=4, n_positions=64,
    n_layer=3, embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)
# sliding_window (6) < prompt+gen so local layers actually truncate
GEMMA_CFG = dataclasses.replace(
    Gemma3TextConfig.tiny(vocab_size=199), hidden_size=48, head_dim=12,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    num_hidden_layers=4, sliding_window=6, sliding_window_pattern=3)

FAMS = {
    "gpt2": (GPT2_CFG, gpt2.init_params,
             lambda seed: init_lora_gpt2(GPT2_CFG, LoRASpec(rank=3,
                                                            alpha=6.0),
                                         jax.random.PRNGKey(seed))),
    "gemma": (GEMMA_CFG, gemma3.init_params,
              lambda seed: init_lora_gemma3(GEMMA_CFG,
                                            LoRASpec(rank=3, alpha=6.0),
                                            jax.random.PRNGKey(seed))),
}


def rand_lora(family, seed, scale=0.05):
    lora = FAMS[family][2](seed)
    leaves, td = jax.tree.flatten(lora)
    keys = jax.random.split(jax.random.PRNGKey(seed + 50), len(leaves))
    return jax.tree.unflatten(td, [
        l if l.ndim == 0 else scale * jax.random.normal(k, l.shape)
        for l, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def params():
    return {f: FAMS[f][1](FAMS[f][0], jax.random.PRNGKey(0))
            for f in FAMS}


def run_engine(family, params, mesh, attn_impl="auto"):
    """One full serve session: two adapters resident, a mixed
    base+adapter wave, then a hot-swap + second wave that must add
    ZERO traces. Returns (tokens by submit order, post-warmup traces,
    health snapshot)."""
    dp, tp = mesh
    cfg = ServeConfig(num_slots=4, block_T=8, num_blocks=32,
                      max_prompt=16, max_new_tokens=8,
                      attn_impl=attn_impl, mesh_dp=dp, mesh_tp=tp)
    bank = AdapterBank(rand_lora(family, 5), capacity=2)
    eng = ServeEngine(family, FAMS[family][0], params[family], cfg,
                      bank=bank)
    try:
        eng.load_adapter("a", rand_lora(family, 7))
        eng.load_adapter("b", rand_lora(family, 8))
        rng = np.random.default_rng(0)
        vocab = 211 if family == "gpt2" else 199
        reqs = []
        for i, ad in enumerate([None, "a", "b", None, "a"]):
            p = rng.integers(1, vocab, size=4 + 2 * i).tolist()
            reqs.append(eng.submit(p, adapter=ad))
        eng.drain()
        warm = eng.total_traces()
        # hot-swap "a" in place + a second wave: the swap is one traced
        # at[slot].set on sharding-stable buffers — no new executables
        eng.load_adapter("a", rand_lora(family, 9))
        for i, ad in enumerate([None, "b", "a"]):
            p = rng.integers(1, vocab, size=5 + i).tolist()
            reqs.append(eng.submit(p, adapter=ad))
        eng.drain()
        retraces = eng.total_traces() - warm
        health = eng.health()
    finally:
        eng.close()
    return [list(r.tokens) for r in reqs], retraces, health


@pytest.fixture(scope="module")
def baseline(params):
    """Single-chip (1, 1) engine outputs — what every mesh must match."""
    return {f: run_engine(f, params, (1, 1))[0] for f in FAMS}


# ------------------------- the parity acceptance -------------------------

@pytest.mark.parametrize("mesh", [
    (1, 2), (1, 4),
    # the dp > 1 cells ride the full acceptance matrix, not the
    # budgeted tier-1 run
    pytest.param((2, 2), marks=pytest.mark.slow)])
@pytest.mark.parametrize("family", ["gpt2", "gemma"])
def test_sharded_engine_token_parity(family, mesh, params, baseline):
    """Sharded decode == single-chip decode, token for token: mixed
    base+adapter routing, gemma's sliding-window layers engaged
    (window 6 < prompt+gen), a mid-session hot-swap — and ZERO
    post-warmup retraces at every mesh shape. The bank's block-diagonal
    layout is pure PLACEMENT (dense math), so parity is exact, not
    approximate."""
    got, retraces, health = run_engine(family, params, mesh)
    assert retraces == 0, f"{family} {mesh}: {retraces} post-warmup traces"
    assert health["mesh"] == list(mesh)
    for i, (g, want) in enumerate(zip(got, baseline[family])):
        assert g == want, f"{family} {mesh} req {i}: {g} != {want}"


def test_sharded_pallas_path_token_parity(params, baseline):
    """attn_impl=pallas under the mesh: sharded_paged_attend wraps the
    unchanged kernel in shard_map over per-shard pool slices (interpret
    mode on CPU) — still token-identical to the single-chip xla path."""
    got, retraces, _ = run_engine("gpt2", params, (1, 2),
                                  attn_impl="pallas")
    assert retraces == 0
    assert got == baseline["gpt2"]


def test_sharded_run_ahead_is_one_executable(params):
    """Under the mesh the decode step's carried operands (its own
    outputs, pinned replicated) and the ones the host places
    (`device_put(..., repl)`) must be the same kind of array: after the
    first stale and the first carried turn nothing traces or compiles,
    whichever kind a turn is."""
    from fixtures import backend_compiles
    eng = ServeEngine(
        "gpt2", GPT2_CFG, params["gpt2"],
        ServeConfig(num_slots=2, block_T=4, num_blocks=32, max_prompt=16,
                    max_new_tokens=8, mesh_tp=2))
    try:
        rng = np.random.default_rng(3)
        prompt = lambda n: rng.integers(1, 211, size=n).tolist()
        for n in (5, 9, 3):
            eng.submit(prompt(n), max_new_tokens=5)
        eng.drain()
        assert eng.steps_carried > 0 and eng.steps_uploaded > 0
        carried0, uploaded0 = eng.steps_carried, eng.steps_uploaded
        traces0 = eng.total_traces()
        with backend_compiles() as compiles:
            for n, m in ((4, 8), (11, 6), (6, 8), (2, 7)):
                eng.submit(prompt(n), max_new_tokens=m)
            eng.drain()
            assert compiles == []
        assert eng.total_traces() == traces0
        assert eng.trace_counts["decode_step"] == 1
        assert eng.steps_carried - carried0 >= 6
        assert eng.steps_uploaded - uploaded0 >= 3
        assert eng._inflight is None
    finally:
        eng.close()


# ------------------------- placement unit tests --------------------------

def test_shard_heads_axis_choice():
    """KV divisible -> pool shards; else GQA groups shard; else heads
    replicate. This is the ONE head-axis decision (ops + sharding +
    eligibility all consult it)."""
    assert shard_heads(8, 1, 1) == (8, 1)          # tp=1: identity
    assert shard_heads(8, 1, 4) == (2, 1)          # KV shards
    assert shard_heads(2, 2, 2) == (1, 2)          # KV wins when both fit
    assert shard_heads(2, 4, 4) == (2, 1)          # groups shard
    assert shard_heads(2, 2, 4) == (2, 2)          # neither: replicate
    assert shard_heads(8, 1, 0) == (8, 1)          # tp=None/0 tolerated


def test_vmem_gates_charge_per_shard_head_counts():
    """A shape whose GLOBAL K/V pages overflow the VMEM budget must
    still pass the gate at tp=4 when each shard streams only KV/tp
    heads — otherwise the Pallas path would falsely gate off exactly
    as tp grows (the regression this pins)."""
    KV, G, bT, D = 8, 1, 8, 16384
    assert not paged_eligible(KV, G, bT, D, itemsize=4)
    assert paged_eligible(KV, G, bT, D, itemsize=4, tp=4)
    # pick_kvb's divisor search runs over the LOCAL head count
    assert pick_kvb(8, T=128, D=512, itemsize=4) == 8
    assert pick_kvb(8, T=128, D=512, itemsize=4, tp=4) == 2
    # indivisible heads replicate: per-shard bill == global bill
    assert paged_eligible(3, 1, bT, 64, itemsize=4, tp=2) == \
        paged_eligible(3, 1, bT, 64, itemsize=4)


def test_serve_sharding_build_and_validation():
    sh = ServeSharding.build("gemma", GEMMA_CFG, 1, 2)
    assert (sh.kv_shards, sh.g_shards) == (2, 1)    # KV=2 shards at tp=2
    sh4 = ServeSharding.build("gemma", GEMMA_CFG, 1, 4)
    assert (sh4.kv_shards, sh4.g_shards) == (1, 1)  # nq=4, kv=2: replicate
    shg = ServeSharding.build("gpt2", GPT2_CFG, 1, 4)
    assert (shg.kv_shards, shg.g_shards) == (4, 1)  # MHA: KV == nq shards
    with pytest.raises(ValueError, match="query-head"):
        ServeSharding.build("gpt2", GPT2_CFG, 1, 3)  # 4 % 3 != 0
    with pytest.raises(ValueError, match="family"):
        ServeSharding.build("bert", GPT2_CFG, 1, 2)
    with pytest.raises(ValueError, match=">= 1"):
        make_serve_mesh(0, 2)


@pytest.mark.parametrize("family,tp,frac", [
    ("gpt2", 2, 0.5), ("gpt2", 4, 0.25),   # MHA: the KV heads shard
    ("gemma", 2, 0.5),                     # KV=2 shards at tp=2
    ("gemma", 4, 1.0),                     # nq=4, kv=2: pools replicate
])
def test_pool_shard_is_whole_heads_of_every_row(family, tp, frac, params):
    """Each tp shard holds 1/tp of the pool (chip_smoke.py's fraction
    check), and what it holds is KV/tp WHOLE heads of every page row —
    a contiguous lane range (serve/paged_kv.py's layout), so the
    per-shard kernel reads its own heads and the token write stays
    collective-free."""
    cfg = ServeConfig(num_slots=4, block_T=8, num_blocks=16,
                      max_prompt=16, max_new_tokens=8, mesh_tp=tp)
    eng = ServeEngine(family, FAMS[family][0], params[family], cfg)
    try:
        L, KV, D = eng._pool_dims
        for pool in (eng.pool_k, eng.pool_v):
            shard = pool.addressable_shards[0].data
            assert shard.size / pool.size == frac
            assert shard.shape == (*pool.shape[:3],
                                   round(KV * frac) * D)
    finally:
        eng.close()


def test_serve_config_mesh_validation(params):
    with pytest.raises(ValueError, match="mesh"):
        ServeConfig(mesh_dp=0).validate()
    with pytest.raises(ValueError, match="num_slots"):
        ServeConfig(num_slots=3, block_T=8, num_blocks=32, max_prompt=16,
                    max_new_tokens=8, mesh_dp=2, mesh_tp=1).validate()
    with pytest.raises(ValueError, match="query-head"):
        ServeEngine("gpt2", GPT2_CFG, params["gpt2"],
                    ServeConfig(num_slots=3, block_T=8, num_blocks=32,
                                max_prompt=16, max_new_tokens=8,
                                mesh_dp=1, mesh_tp=3))


def test_bank_block_diagonal_placement():
    """The stacked [k, ...] bank shards each factor on its MODEL axis
    only — B on d_out where the layer is column-parallel, A on d_in
    where it is row-parallel — and never on the adapter axis, so the
    hot-swap at[slot].set is shard-local (no resharding collective)."""
    sh = ServeSharding.build("gpt2", GPT2_CFG, 1, 2)
    bank = AdapterBank(rand_lora("gpt2", 3), capacity=2)
    shardings = sh.bank_shardings(bank.tree)
    flat = jax.tree_util.tree_flatten_with_path(shardings)[0]
    saw_sharded = 0
    for path, ns in flat:
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        axes = [a for a in ns.spec if a is not None]
        assert len(axes) <= 1
        if axes:
            saw_sharded += 1
            # leading (adapter-slot) axis always replicated
            assert ns.spec[0] is None
    assert saw_sharded > 0
    # placing + swapping keeps the committed shardings stable
    bank.place(shardings, sh.put_repl)
    before = jax.tree.map(lambda a: a.sharding, bank.tree)
    bank.load("t", rand_lora("gpt2", 4))
    after = jax.tree.map(lambda a: a.sharding, bank.tree)
    assert jax.tree.all(jax.tree.map(lambda x, y: x == y, before, after))
