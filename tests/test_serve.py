"""Serving subsystem tests (serve/, DESIGN.md §16).

The correctness anchor is the ACCEPTANCE ORACLE: for any request set,
the continuous-batching loop's per-request greedy outputs — paged KV
pool, static slots, per-slot adapter routing — must be token-identical
to batch-at-a-time generate() with the same adapter per row (contiguous
cache). And the COMPILE-STABILITY invariant: after warmup the engine
holds exactly one prefill + one decode-step executable, reused across
every admission, eviction, and adapter hot-swap (<= 2 new traces
allowed, 0 expected)."""

import dataclasses
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mobilefinetuner_tpu.core.config import GPT2Config, Gemma3TextConfig
from mobilefinetuner_tpu.core.telemetry import Telemetry, validate_event
from mobilefinetuner_tpu.lora.lora import LoRASpec, init_lora_gemma3
from mobilefinetuner_tpu.models import gemma3, gpt2
from mobilefinetuner_tpu.models.generate import (SampleConfig,
                                                 gemma3_generate,
                                                 gpt2_generate)
from mobilefinetuner_tpu.serve import (AdapterBank, BlockAllocator,
                                       OutOfBlocks, ServeConfig,
                                       ServeEngine, TRASH_BLOCK,
                                       blocks_for)

GPT2_CFG = dataclasses.replace(
    GPT2Config.tiny(vocab_size=211), n_embd=64, n_head=4, n_positions=64,
    n_layer=3, embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)
# sliding_window (6) < prompt+gen so local layers actually truncate
GEMMA_CFG = dataclasses.replace(
    Gemma3TextConfig.tiny(vocab_size=199), hidden_size=48, head_dim=12,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    num_hidden_layers=4, sliding_window=6, sliding_window_pattern=3)


@pytest.fixture(scope="module")
def gpt2_params():
    return gpt2.init_params(GPT2_CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def gemma_params():
    return gemma3.init_params(GEMMA_CFG, jax.random.PRNGKey(1))


def oracle(family, params, req, lora=None, eos_id=None):
    """Batch-at-a-time generate() with a CONTIGUOUS cache, truncated the
    way the serve loop reports (eos inclusive)."""
    gen = gpt2_generate if family == "gpt2" else gemma3_generate
    config = GPT2_CFG if family == "gpt2" else GEMMA_CFG
    ids = jnp.asarray([req.prompt], jnp.int32)
    cfg = SampleConfig(max_new_tokens=req.max_new_tokens, greedy=True,
                       eos_id=eos_id, pad_id=0)
    row = np.asarray(gen(config, params, ids, jnp.ones_like(ids), cfg,
                         lora=lora))[0].tolist()
    if eos_id is not None and eos_id in row:
        row = row[:row.index(eos_id) + 1]
    return row


def rand_lora(seed, scale=0.05):
    lora = init_lora_gemma3(GEMMA_CFG, LoRASpec(rank=3, alpha=6.0),
                            jax.random.PRNGKey(seed))
    leaves, td = jax.tree.flatten(lora)
    keys = jax.random.split(jax.random.PRNGKey(seed + 50), len(leaves))
    return jax.tree.unflatten(td, [
        l if l.ndim == 0 else scale * jax.random.normal(k, l.shape)
        for l, k in zip(leaves, keys)])


# --------------------------- allocator + config ------------------------------

def test_block_allocator_lifecycle():
    a = BlockAllocator(8)                 # 7 allocatable, 0 reserved
    assert a.free_blocks == 7
    got = a.alloc(3)
    assert len(set(got)) == 3 and TRASH_BLOCK not in got
    b = a.append()
    assert b not in got and a.free_blocks == 3
    with pytest.raises(OutOfBlocks):
        a.alloc(4)
    a.free(got)
    assert a.free_blocks == 6
    with pytest.raises(ValueError):
        a.free(got[:1])                   # double free
    with pytest.raises(ValueError):
        a.free([TRASH_BLOCK])
    with pytest.raises(ValueError):
        BlockAllocator(1)
    assert blocks_for(0, 8) == 0 and blocks_for(1, 8) == 1
    assert blocks_for(8, 8) == 1 and blocks_for(9, 8) == 2


@pytest.mark.parametrize("KV,D,dtype", [
    (5, 64, jnp.bfloat16),     # odd head count at D=64: a row of 320
    (2, 128, jnp.bfloat16),    # D=128 GQA pool
    (4, 16, jnp.float32),      # the tiny engines' widths
])
def test_pool_round_trip_is_bit_exact(KV, D, dtype):
    """serve/paged_kv.py's writers against its reader, with no model in
    between: a prompt's pages (write_prompt_blocks), a prefill chunk's
    rows (write_chunk_rows) and a decode step's fed-token rows
    (write_token_rows) come back from gather_pages bit for bit in the
    pool's dtype, at the column the block table says — and the trash
    page takes the padding."""
    from mobilefinetuner_tpu.serve.paged_kv import (gather_pages,
                                                    init_pools,
                                                    write_chunk_rows,
                                                    write_prompt_blocks,
                                                    write_token_rows)
    L, bT, NB, P = 3, 8, 9, 20            # 2.5 pages of prompt
    Ppad = blocks_for(P, bT) * bT
    kk, kv, kt = jax.random.split(jax.random.PRNGKey(KV * D), 3)
    k = jax.random.normal(kk, (L, KV, Ppad, D), dtype)
    v = jax.random.normal(kv, (L, KV, Ppad, D), dtype)
    pages = np.asarray([7, 2, 5], np.int32)            # not in order
    pk, pv = init_pools(NB, L, KV, bT, D, dtype)
    pk, pv = write_prompt_blocks(pk, pv, k, v, pages)

    def columns(pool):
        """[L, KV, 3 * bT, D]: the request's columns as the table
        orders them."""
        got = jnp.stack([gather_pages(pool, pages, i, KV)
                         for i in range(L)])            # [L, M, bT, KV, D]
        return np.asarray(got.transpose(0, 3, 1, 2, 4).reshape(
            L, KV, -1, D).astype(jnp.float32))

    np.testing.assert_array_equal(columns(pk), np.asarray(k, np.float32))
    np.testing.assert_array_equal(columns(pv), np.asarray(v, np.float32))
    # the same prompt written as ONE chunk (rows past P go to trash)
    rows = jnp.arange(Ppad)
    blk = jnp.where(rows < P, jnp.asarray(pages)[rows // bT], TRASH_BLOCK)
    ck, _ = init_pools(NB, L, KV, bT, D, dtype)
    ck = write_chunk_rows(ck, blk, rows % bT, k.transpose(0, 2, 1, 3))
    np.testing.assert_array_equal(columns(ck)[:, :, :P],
                                  np.asarray(k, np.float32)[:, :, :P])
    assert not np.asarray(ck)[[1, 3, 4, 6, 8]].any()   # untouched pages
    # a decode step's write at position P of two slots (one idle: trash)
    tok = jax.random.normal(kt, (L, 2, KV, D), dtype)
    for i in range(L):
        pk = write_token_rows(pk, jnp.asarray([pages[P // bT], TRASH_BLOCK]),
                              i, jnp.asarray([P % bT, 0]), tok[i])
    want = np.array(k, np.float32)
    want[:, :, P] = np.asarray(tok[:, 0], np.float32)
    np.testing.assert_array_equal(columns(pk), want)
    np.testing.assert_array_equal(
        np.asarray(gather_pages(pk, jnp.asarray([TRASH_BLOCK]), L - 1,
                                KV)[0, 0], np.float32),
        np.asarray(tok[L - 1, 1], np.float32))


def test_run_start_names_the_pool_layout(gpt2_params, tmp_path):
    """The stream says which pool layout served, next to the resolved
    attention path: the shape the pools really have, and how many heads
    fold into a page row."""
    path = str(tmp_path / "serve.jsonl")
    eng = ServeEngine("gpt2", GPT2_CFG, gpt2_params,
                      ServeConfig(num_slots=2, block_T=8, num_blocks=8,
                                  max_prompt=16, max_new_tokens=8),
                      telemetry=Telemetry(path))
    try:
        L, KV, D = eng._pool_dims
        shape = list(eng.pool_k.shape)
        assert shape == list(eng.pool_v.shape)
    finally:
        eng.close()
    start = next(e for e in map(json.loads, open(path))
                 if e["event"] == "run_start")
    assert validate_event(start) is None
    conf = start["config"]
    assert conf["pool_layout"] == "block,layer,token,kv_head*head_dim"
    assert conf["pool_shape"] == "x".join(map(str, shape))
    assert (conf["pool_heads_per_row"], conf["pool_head_dim"]) == (KV, D)
    assert shape[-1] == KV * D and conf["pool_lane_fill"] == 0.5  # 64 lanes
    assert conf["attn_impl_resolved"] == eng.attn_impl_resolved


# rows of 128 lanes (2 heads of 64), so the paged kernel brings pages by
# its own copies, several a grid step; Gemma's window of 6 keeps the
# local layers' live range in the table's second chunk once a context
# passes 69 tokens
WIDE = {
    "gpt2": (dataclasses.replace(GPT2_CFG, n_embd=128, n_head=2,
                                 n_positions=128), gpt2),
    "gemma": (dataclasses.replace(GEMMA_CFG, head_dim=64), gemma3),
}


@pytest.mark.parametrize("family", sorted(WIDE))
def test_paged_kernel_serves_and_counts(family, tmp_path):
    """attn_impl=pallas end to end (interpret mode here): the decode
    step makes the kernel's plan once a step and every layer picks its
    mask's part of it — token for token what the gather oracle serves,
    Gemma's windowed layers included — and the stream and /metrics say
    how many pages a grid step folds and what share of the grid's
    steps hold a live page."""
    from mobilefinetuner_tpu.core.metrics_http import MetricsRegistry
    from mobilefinetuner_tpu.ops.decode_attention import pick_pages
    config, model = WIDE[family]
    params = model.init_params(config, jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 190, n).tolist() for n in (60, 9, 33)]

    def serve(attn_impl, path):
        tel = Telemetry(path)
        reg = MetricsRegistry()
        tel.add_observer(reg.observe)
        eng = ServeEngine(family, config, params, ServeConfig(
            num_slots=2, block_T=8, num_blocks=40, max_prompt=64,
            max_new_tokens=32, attn_impl=attn_impl, stats_every=1),
            telemetry=tel)
        try:
            reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
            eng.drain()
        finally:
            eng.close()
        return eng, [r.tokens for r in reqs], reg.render(), \
            [json.loads(l) for l in open(path)]

    eng, want, text, evs = serve("xla", str(tmp_path / "xla.jsonl"))
    assert eng.paged_pages_per_step is None
    assert all(e["paged_live_chunk_share"] is None
               for e in evs if e["event"] == "serve_stats")
    eng, got, text, evs = serve("pallas", str(tmp_path / "pallas.jsonl"))
    assert got == want
    d = eng._pool_dims
    P = pick_pages(d[1], config.num_attention_heads // d[1]
                   if family == "gemma" else 1, 8, d[2], 4, eng.M)
    assert P == 8 and eng.M == 12          # two chunks a slot
    start = next(e for e in evs if e["event"] == "run_start")
    assert start["config"]["paged_pages_per_step"] == P
    stats = [e for e in evs if e["event"] == "serve_stats"]
    assert stats and all(validate_event(e) is None for e in stats)
    shares = [e["paged_live_chunk_share"] for e in stats]
    # 2 slots x 2 chunks: a slot holds one live chunk or two
    assert all(0.5 <= x <= 1.0 for x in shares) and max(shares) > 0.5
    if family == "gpt2":
        assert set(shares) <= {0.5, 0.75, 1.0}
    else:
        # a windowed layer's range is one chunk where a global layer's
        # is two: the share is the layers' mean, no multiple of 1/4
        assert any(x % 0.25 for x in shares)
    assert f"mft_serve_paged_pages_per_step {P}\n" in text
    assert "mft_serve_paged_live_chunk_share " in text


def test_serve_config_validation(gpt2_params):
    with pytest.raises(ValueError, match="multiple"):
        ServeConfig(max_prompt=12, block_T=8).validate()
    # a pool too small for even one worst-case request must fail fast
    # (regression: admission could never fire and drain() spun forever)
    with pytest.raises(ValueError, match="worst-case"):
        ServeConfig(num_blocks=4, block_T=16, max_prompt=64,
                    max_new_tokens=64).validate()
    with pytest.raises(ValueError, match="n_positions"):
        ServeEngine("gpt2", GPT2_CFG, gpt2_params,
                    ServeConfig(block_T=8, max_prompt=56,
                                max_new_tokens=32))
    with pytest.raises(ValueError, match="family"):
        ServeEngine("bert", GPT2_CFG, gpt2_params)


# --------------------------- the acceptance oracle ---------------------------

@pytest.fixture(scope="module")
def gpt2_engine(gpt2_params):
    eng = ServeEngine(
        "gpt2", GPT2_CFG, gpt2_params,
        ServeConfig(num_slots=3, block_T=8, num_blocks=32, max_prompt=16,
                    max_new_tokens=12))
    yield eng
    eng.close()


def test_gpt2_paged_serving_matches_contiguous_generate(gpt2_engine,
                                                        gpt2_params):
    """More requests than slots, ragged prompt lengths: every request's
    greedy tokens equal its own batch-at-a-time generate() run — the
    paged-pool cache is observationally identical to the contiguous
    cache, through continuous-batching admissions and evictions."""
    eng = gpt2_engine
    free0 = eng.alloc.free_blocks
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, 200, n)) for n in (5, 9, 2, 13, 7, 3)]
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    done = eng.drain()
    assert sorted(r.id for r in done) == [r.id for r in reqs]
    for r in done:
        assert r.tokens == oracle("gpt2", gpt2_params, r), f"req {r.id}"
        assert r.ttft_ms is not None and r.tpot_ms is not None
    # eviction returned every page; slots all idle
    assert eng.alloc.free_blocks == free0
    assert eng.idle and not eng.active
    # warmup state for the trace-stability test below
    assert eng.trace_counts["decode_step"] == 1
    assert eng.trace_counts["prefill"] == 1


def test_gpt2_eos_stops_request_early(gpt2_engine, gpt2_params):
    """Declare one of a request's own later greedy tokens to be eos —
    the first that differs from every token before it (index j >= 1),
    so that it is the FIRST eos the request emits: the serve loop must
    stop that request there (emitting the eos), freeing its slot, while
    others run to their cap. The tiny fixture often repeats one token
    (94, 94, ...), so probes are drawn until one has such a token."""
    eng = gpt2_engine
    rng = np.random.default_rng(3)
    for _ in range(16):
        probe = list(rng.integers(1, 200, 6))
        r0 = eng.submit(probe, max_new_tokens=6)
        eng.drain()
        js = [j for j in range(1, 5) if r0.tokens[j] not in r0.tokens[:j]]
        if js:
            break
    else:
        pytest.fail("no probe whose greedy tokens change before the cap")
    j = js[0]
    eos = r0.tokens[j]
    eng.eos_id = eos
    try:
        reqs = [eng.submit(probe, max_new_tokens=6),
                eng.submit(list(rng.integers(1, 200, 4)),
                           max_new_tokens=6)]
        done = eng.drain()
        by_id = {r.id: r for r in done}
        want0 = oracle("gpt2", gpt2_params, reqs[0], eos_id=eos)
        assert by_id[reqs[0].id].tokens == want0
        assert by_id[reqs[0].id].tokens[-1] == eos
        assert len(by_id[reqs[0].id].tokens) == j + 1  # stopped early
        assert len(by_id[reqs[1].id].tokens) <= 6
    finally:
        eng.eos_id = None


def test_trace_stability_across_admissions_evictions_cancel(gpt2_engine):
    """THE compile-stability acceptance: after warmup, admissions with
    new prompt lengths, evictions, mid-flight cancels, and pool
    turnover add <= 2 traces (expected: 0). Shapes are static by
    construction; this pins that no code path smuggles in a dynamic
    one."""
    eng = gpt2_engine
    warm = eng.total_traces()
    rng = np.random.default_rng(7)
    reqs = [eng.submit(list(rng.integers(1, 200, int(n))),
                       max_new_tokens=int(m))
            for n, m in zip((1, 16, 4, 11, 8, 2, 6),
                            (12, 3, 7, 1, 5, 9, 2))]
    eng.step()
    eng.cancel(reqs[2])                   # queued cancel
    eng.step()
    active = eng.active
    if active:
        eng.cancel(active[0])             # mid-flight eviction
    eng.drain()
    assert eng.total_traces() - warm <= 2
    assert eng.total_traces() - warm == 0  # the design target
    assert eng.idle


def test_cancel_frees_pages_and_slot(gpt2_engine):
    eng = gpt2_engine
    free0 = eng.alloc.free_blocks
    r = eng.submit([1, 2, 3, 4, 5], max_new_tokens=10)
    eng.step()
    assert r.state == "active" and eng.alloc.free_blocks < free0
    eng.cancel(r)
    assert r.state == "cancelled"
    assert eng.alloc.free_blocks == free0 and eng.idle
    eng.cancel(r)                          # idempotent


def test_submit_validation(gpt2_engine):
    eng = gpt2_engine
    # round 21: an over-cap prompt is a POLICY reject the caller reads
    # off .state (reason=prompt_too_long), not a ValueError — prompts
    # in (max_prompt, true_cap] are valid chunked admissions when
    # max_prompt_chunked is set, and the closed-set reason taxonomy is
    # how a proxy tells "too long" from "queue full". On this engine
    # (chunking off) the true cap IS max_prompt: 16 queues, 17 rejects.
    at_cap = eng.submit(list(range(1, 17)))         # 16 == cap: queued
    assert at_cap.state == "queued"
    over = eng.submit(list(range(1, 18)))           # 17 > 16: rejected
    assert over.state == "rejected" and over.reason == "prompt_too_long"
    assert not over.blocks and over.tokens == []
    eng.cancel(at_cap)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], max_new_tokens=99)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])
    with pytest.raises(RuntimeError, match="bank"):
        eng.submit([1, 2], adapter="nope")  # bankless engine
    # sampling knobs on a greedy engine are a CALLER error (the engine
    # compiled no sampling lanes), as is a nonsense distribution
    with pytest.raises(ValueError, match="sampling"):
        eng.submit([1, 2], temperature=0.7)


def test_prompt_too_long_boundary_with_chunking(gpt2_params):
    """Satellite 5 regression, both sides of the TRUE cap: with
    max_prompt_chunked set, prompts in (max_prompt, true_cap] route to
    chunked admission (NOT rejected — the pre-r21 hard ValueError is
    the bug this pins against), and reason=prompt_too_long fires only
    beyond the true cap."""
    eng = ServeEngine(
        "gpt2", GPT2_CFG, gpt2_params,
        ServeConfig(num_slots=2, block_T=8, num_blocks=32, max_prompt=16,
                    max_new_tokens=8, max_prompt_chunked=40))
    rng = np.random.default_rng(17)
    inside = eng.submit(list(rng.integers(1, 200, 40)),   # == true cap
                        max_new_tokens=4)
    assert inside.state == "queued"
    beyond = eng.submit(list(rng.integers(1, 200, 41)),   # cap + 1
                        max_new_tokens=4)
    assert beyond.state == "rejected"
    assert beyond.reason == "prompt_too_long"
    eng.drain()
    assert inside.state == "finished"
    assert inside.tokens == oracle("gpt2", gpt2_params, inside)
    eng.close()


def test_admission_backpressure_tiny_pool(gpt2_params):
    """A pool that fits ~one worst-case request at a time still serves
    everything correctly: admission waits for pages, requests queue,
    outputs stay oracle-equal."""
    # worst case = blocks_for(8 + 8 - 1, 8) = 2 pages; pool has 3
    eng = ServeEngine(
        "gpt2", GPT2_CFG, gpt2_params,
        ServeConfig(num_slots=2, block_T=8, num_blocks=4, max_prompt=8,
                    max_new_tokens=8))
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, 200, n)) for n in (6, 8, 3)]
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    seen_queued_while_active = False
    while not eng.idle:
        eng.step()
        if eng.queue and eng.active:
            seen_queued_while_active = True
    assert seen_queued_while_active       # backpressure actually engaged
    for r in reqs:
        assert r.state == "finished"
        assert r.tokens == oracle("gpt2", gpt2_params, r), f"req {r.id}"
    eng.close()


# --------------------------- multi-adapter + hot-swap ------------------------

@pytest.fixture(scope="module")
def gemma_engine(gemma_params):
    bank = AdapterBank(rand_lora(5), capacity=2)
    eng = ServeEngine(
        "gemma", GEMMA_CFG, gemma_params,
        ServeConfig(num_slots=2, block_T=8, num_blocks=32, max_prompt=24,
                    max_new_tokens=10),
        bank=bank)
    yield eng
    eng.close()


def test_gemma_multi_adapter_serving_matches_per_adapter_generate(
        gemma_engine, gemma_params):
    """Slots carrying different adapter ids in the SAME decode step must
    each produce their own adapter's tokens (and base-only requests the
    base model's) — sliding-window layers engaged (window 6 < len)."""
    eng = gemma_engine
    a1, a2 = rand_lora(5), rand_lora(9)
    eng.load_adapter("t1", a1)
    eng.load_adapter("t2", a2)
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(3, 190, n)) for n in (7, 18, 11, 4)]
    route = ["t1", "t2", None, "t1"]
    trees = {"t1": a1, "t2": a2, None: None}
    reqs = [eng.submit(p, max_new_tokens=9, adapter=a)
            for p, a in zip(prompts, route)]
    done = {r.id: r for r in eng.drain()}
    for req, aname in zip(reqs, route):
        want = oracle("gemma", gemma_params, req, lora=trees[aname])
        assert done[req.id].tokens == want, f"req {req.id} ({aname})"


def test_hot_swap_without_recompile(gemma_engine, gemma_params):
    """Evict a tenant, load a new adapter into the freed slot: requests
    routed to the new name get the NEW weights, base/base-slot rows are
    untouched, and the decode step is NOT retraced."""
    eng = gemma_engine
    warm = eng.total_traces()
    a3 = rand_lora(13)
    eng.evict_adapter("t2")
    slot = eng.load_adapter("t3", a3)
    assert slot == eng.bank.resident["t3"]
    rng = np.random.default_rng(2)
    req = eng.submit(list(rng.integers(3, 190, 12)), max_new_tokens=9,
                     adapter="t3")
    base = eng.submit(list(rng.integers(3, 190, 5)), max_new_tokens=9)
    done = {r.id: r for r in eng.drain()}
    assert done[req.id].tokens == oracle("gemma", gemma_params, req,
                                         lora=a3)
    assert done[base.id].tokens == oracle("gemma", gemma_params, base)
    assert eng.total_traces() - warm == 0


def test_tenancy_protocol_guards(gemma_engine):
    """The hot-swap protocol: in-use residents cannot be replaced or
    evicted; unknown residents cannot be routed to; a full bank refuses
    loads until an eviction frees a slot."""
    eng = gemma_engine
    for name, seed in (("t1", 5), ("t3", 13)):  # self-provision: the
        # module's earlier tests leave these resident, but the guards
        # must also hold when this test runs alone
        if name not in eng.bank.resident:
            eng.load_adapter(name, rand_lora(seed))
    r = eng.submit([3, 4, 5], max_new_tokens=9, adapter="t1")
    with pytest.raises(RuntimeError, match="in-flight"):
        eng.load_adapter("t1", rand_lora(21))
    with pytest.raises(RuntimeError, match="in-flight"):
        eng.evict_adapter("t1")
    with pytest.raises(KeyError, match="not resident"):
        eng.submit([3, 4], adapter="t2")   # evicted in the prior test
    with pytest.raises(OverflowError, match="full"):
        eng.load_adapter("t9", rand_lora(22))   # t1 + t3 fill capacity 2
    eng.cancel(r)
    eng.drain()
    # structure mismatches are refused before touching the bank
    bad = init_lora_gemma3(GEMMA_CFG, LoRASpec(rank=5, alpha=10.0),
                           jax.random.PRNGKey(0))
    eng.evict_adapter("t3")
    with pytest.raises(ValueError, match="rank|shape"):
        eng.load_adapter("t9", bad)


def test_queued_request_pins_its_adapter(gemma_params):
    """Satellite regression (round 14): submit() resolves the bank slot
    at enqueue, so the in-use guard must cover QUEUED requests too —
    otherwise evict/load while a request waits silently serves another
    tenant's weights at admission. Pin both directions: replacement AND
    eviction of a queued-referenced resident are refused, and a
    non-referenced resident still swaps freely while the queue is
    non-empty."""
    bank = AdapterBank(rand_lora(5), capacity=2)
    eng = ServeEngine("gemma", GEMMA_CFG, gemma_params,
                      ServeConfig(num_slots=1, block_T=8, num_blocks=32,
                                  max_prompt=24, max_new_tokens=10),
                      bank=bank)
    a1, a2 = rand_lora(6), rand_lora(7)
    eng.load_adapter("t1", a1)
    eng.load_adapter("t2", a2)
    # one active request occupies the single slot, one QUEUED request
    # references t2 — nothing active routes to t2
    active = eng.submit([3, 4, 5], max_new_tokens=9, adapter="t1")
    eng.step()
    assert active.state == "active"
    queued = eng.submit([6, 7, 8], max_new_tokens=9, adapter="t2")
    assert queued.state == "queued"
    with pytest.raises(RuntimeError, match="in-flight"):
        eng.load_adapter("t2", rand_lora(8))   # replacement refused
    with pytest.raises(RuntimeError, match="in-flight"):
        eng.evict_adapter("t2")                # eviction refused
    done = {r.id: r for r in eng.drain()}
    # the queued tenant got ITS OWN weights, not a swapped-in stranger's
    assert done[queued.id].tokens == oracle("gemma", gemma_params,
                                            queued, lora=a2)
    # with the queue empty the same swap is legal again
    eng.evict_adapter("t2")
    eng.load_adapter("t3", rand_lora(9))
    eng.close()


# --------------------------- telemetry + e2e smoke ---------------------------

def test_enqueue_event_reports_tenant_slot(gemma_params, tmp_path):
    """enqueue/cancel events must attribute a request to its resident
    bank slot — aid resolves at submit, not admission (regression:
    every queued tenant reported adapter slot 0)."""
    stream = str(tmp_path / "t.jsonl")
    bank = AdapterBank(rand_lora(5), capacity=2)
    eng = ServeEngine("gemma", GEMMA_CFG, gemma_params,
                      ServeConfig(num_slots=1, block_T=8, num_blocks=32,
                                  max_prompt=24, max_new_tokens=10),
                      bank=bank, telemetry=Telemetry(stream))
    eng.load_adapter("a", rand_lora(6))
    eng.load_adapter("b", rand_lora(7))            # bank slot 1
    rb = eng.submit([3, 4, 5], max_new_tokens=2, adapter="b")
    r0 = eng.submit([6, 7], max_new_tokens=2)      # base-only
    assert rb.aid == eng.bank.slot("b") == 1
    eng.cancel(rb)
    eng.cancel(r0)
    eng.close()
    with open(stream) as f:
        recs = [json.loads(l) for l in f.read().splitlines()
                if l.strip()]
    ev = {(r["id"], r["phase"]): r for r in recs
          if r["event"] == "request"}
    assert ev[(rb.id, "enqueue")]["adapter"] == 1
    assert ev[(rb.id, "cancel")]["adapter"] == 1
    assert ev[(r0.id, "enqueue")]["adapter"] is None


# --------------------------- run-ahead (PR 34) -------------------------------
# The loop dispatches decode step k+1 before it reads step k's tokens;
# slot state is carried on the device between host touches. What that
# may never change: a request's tokens, its length, the one executable.

def sampled_reference(params, prompt, n, temperature, top_k, top_p, seed):
    """A seeded-sampling request replayed WITHOUT the engine: the full
    forward over prompt + tokens so far, then the engine's convention —
    the request's raw key folded with the emitted token's ABSOLUTE
    position — through `sample_per_row`."""
    from mobilefinetuner_tpu.models.generate import sample_per_row
    key = jnp.asarray([[(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF]],
                      jnp.uint32)
    ids, out = list(prompt), []
    for _ in range(n):
        logits = gpt2.forward(GPT2_CFG, params,
                              jnp.asarray([ids], jnp.int32))[:, -1]
        folded = jax.vmap(jax.random.fold_in)(
            key, jnp.asarray([len(ids)], jnp.int32))
        tok = int(sample_per_row(
            logits, jnp.asarray([temperature], jnp.float32),
            jnp.asarray([top_k], jnp.int32),
            jnp.asarray([top_p], jnp.float32), folded)[0])
        out.append(tok)
        ids.append(tok)
    return out


def assert_run_ahead_accounting(eng, min_uploaded):
    """After a drain: nothing in flight, every dispatch counted once as
    carried or uploaded, both kinds seen, nothing leaked."""
    h = eng.health()
    assert eng.idle and eng._inflight is None
    assert h["steps_carried"] + h["steps_uploaded"] == h["decode_steps"]
    assert h["steps_carried"] > 0 and h["steps_uploaded"] >= min_uploaded
    assert eng.alloc.in_use == 0
    assert eng.trace_counts["decode_step"] == 1


def test_run_ahead_matches_generate_gpt2_with_a_sampled_row(gpt2_params):
    """Seven requests over three slots (>= 3 turnovers), pages of four
    tokens (every row crosses pages while its state is carried), ragged
    lengths, one seeded-sampling request in the mix: greedy rows equal
    generate(), the sampled row equals its replay, to the token."""
    eng = ServeEngine(
        "gpt2", GPT2_CFG, gpt2_params,
        ServeConfig(num_slots=3, block_T=4, num_blocks=64, max_prompt=16,
                    max_new_tokens=12, sampling=True))
    rng = np.random.default_rng(11)
    lens = ((5, 12), (9, 7), (2, 10), (13, 12), (7, 3), (3, 9), (11, 6))
    samp = dict(temperature=0.9, top_k=40, top_p=0.95, seed=(7 << 32) | 1234)
    reqs = [eng.submit(list(rng.integers(1, 200, n)), max_new_tokens=m,
                       **(samp if i == 3 else {}))
            for i, (n, m) in enumerate(lens)]
    eng.drain()
    for i, r in enumerate(reqs):
        assert r.state == "finished" and len(r.tokens) == r.max_new_tokens
        if i == 3:
            want = sampled_reference(
                gpt2_params, r.prompt, r.max_new_tokens, samp["temperature"],
                samp["top_k"], samp["top_p"], samp["seed"])
            assert want != oracle("gpt2", gpt2_params, r)   # it sampled
        else:
            want = oracle("gpt2", gpt2_params, r)
        assert r.tokens == want, f"req {r.id}"
    assert_run_ahead_accounting(eng, min_uploaded=4)
    assert eng.health()["eos_discarded_tokens"] == 0
    eng.close()


def test_run_ahead_matches_per_adapter_generate_gemma(gemma_params):
    """The same for the sliding-window family with adapters routed per
    slot: six requests over two slots, two tenants and the base."""
    a1, a2 = rand_lora(5), rand_lora(9)
    eng = ServeEngine(
        "gemma", GEMMA_CFG, gemma_params,
        ServeConfig(num_slots=2, block_T=4, num_blocks=64, max_prompt=24,
                    max_new_tokens=10),
        bank=AdapterBank(rand_lora(5), capacity=2))
    eng.load_adapter("t1", a1)
    eng.load_adapter("t2", a2)
    rng = np.random.default_rng(2)
    route = ["t1", "t2", None, "t1", "t2", None]
    trees = {"t1": a1, "t2": a2, None: None}
    reqs = [eng.submit(list(rng.integers(3, 190, n)), max_new_tokens=m,
                       adapter=a)
            for (n, m), a in zip(((7, 9), (18, 5), (11, 10), (4, 7),
                                  (21, 8), (9, 4)), route)]
    eng.drain()
    for r, a in zip(reqs, route):
        assert r.tokens == oracle("gemma", gemma_params, r,
                                  lora=trees[a]), f"req {r.id} ({a})"
    assert_run_ahead_accounting(eng, min_uploaded=4)
    eng.close()


def test_late_eos_discards_one_token_and_frees_the_row(gpt2_params):
    """A request whose EOS comes while the next step is already queued:
    it finishes with the EOS as its last token, the one row-step past
    it is discarded (counted, never appended), its pages and slot are
    free, and the neighbour's tokens are what they are without it."""
    cfg = ServeConfig(num_slots=2, block_T=4, num_blocks=32, max_prompt=16,
                      max_new_tokens=12)
    rng = np.random.default_rng(3)
    for _ in range(32):
        probe = list(rng.integers(1, 200, 6))
        other = list(rng.integers(1, 200, 4))
        row = oracle("gpt2", gpt2_params, types.SimpleNamespace(
            prompt=probe, max_new_tokens=8))
        js = [j for j in range(1, 6) if row[j] not in row[:j]]
        if not js:
            continue
        eos = row[js[0]]
        far = oracle("gpt2", gpt2_params, types.SimpleNamespace(
            prompt=other, max_new_tokens=12))
        if eos not in far:
            break
    else:
        pytest.fail("no probe with a fresh token before the cap and a "
                    "neighbour that never emits it")
    j = js[0]
    eng = ServeEngine("gpt2", GPT2_CFG, gpt2_params, cfg, eos_id=eos)
    short = eng.submit(probe, max_new_tokens=8)
    long_ = eng.submit(other, max_new_tokens=12)
    while short.state == "active" or short.state == "queued":
        eng.step()
    assert short.state == "finished" and short.tokens == row[:j + 1]
    assert short.tokens[-1] == eos and not short.blocks
    assert eng._slots[short.slot] is None and long_.state == "active"
    eng.drain()
    assert long_.tokens == far and len(long_.tokens) == 12
    h = eng.health()
    assert h["eos_discarded_tokens"] == 1
    assert_run_ahead_accounting(eng, min_uploaded=2)
    eng.close()


@pytest.mark.parametrize("slots", [1, 2])
def test_length_end_is_foreseen_to_the_token(gpt2_params, slots):
    """A request ending by length holds exactly `max_new_tokens` tokens
    and its row never runs a step past its last position: with an EOS
    configured that nobody emits, nothing is discarded — and with one
    slot the engine dispatches exactly sum(max_new - 1) decode steps."""
    eng = ServeEngine(
        "gpt2", GPT2_CFG, gpt2_params,
        ServeConfig(num_slots=slots, block_T=4, num_blocks=32,
                    max_prompt=16, max_new_tokens=12), eos_id=210)
    rng = np.random.default_rng(5)
    reqs = [eng.submit(list(rng.integers(1, 200, n)), max_new_tokens=m)
            for n, m in ((5, 1), (3, 2), (8, 12), (4, 5), (6, 3))]
    eng.drain()
    for r in reqs:
        want = oracle("gpt2", gpt2_params, r)
        assert 210 not in want
        assert r.state == "finished" and r.tokens == want
        assert len(r.tokens) == r.max_new_tokens
    h = eng.health()
    assert h["eos_discarded_tokens"] == 0
    assert h["steps_carried"] + h["steps_uploaded"] == h["decode_steps"]
    if slots == 1:
        assert h["decode_steps"] == sum(r.max_new_tokens - 1 for r in reqs)
    assert eng.idle and eng._inflight is None and eng.alloc.in_use == 0
    eng.close()


def test_close_and_drain_leave_nothing_in_flight(gpt2_params, tmp_path):
    """drain() ends idle with no step queued; close() on an engine
    abandoned mid-flight reads its step out first; and the counters
    ride `serve_stats` and /metrics."""
    from mobilefinetuner_tpu.core.metrics_http import MetricsRegistry
    stream = str(tmp_path / "s.jsonl")
    eng = ServeEngine(
        "gpt2", GPT2_CFG, gpt2_params,
        ServeConfig(num_slots=2, block_T=4, num_blocks=32, max_prompt=16,
                    max_new_tokens=12, stats_every=2),
        telemetry=Telemetry(stream))
    reg = MetricsRegistry()
    eng.telemetry.add_observer(reg.observe)
    rng = np.random.default_rng(8)
    for n in (5, 9, 3):
        eng.submit(list(rng.integers(1, 200, n)), max_new_tokens=9)
    eng.drain()
    assert_run_ahead_accounting(eng, min_uploaded=2)
    left = eng.submit(list(rng.integers(1, 200, 7)), max_new_tokens=9)
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None and left.state == "active"
    n_before = len(left.tokens)
    eng.close()
    assert eng._inflight is None and len(left.tokens) == n_before + 1
    assert left.tokens == oracle("gpt2", gpt2_params, left)[:n_before + 1]
    with open(stream) as f:
        recs = [json.loads(l) for l in f.read().splitlines() if l.strip()]
    for rec in recs:
        assert validate_event(rec) is None, (rec, validate_event(rec))
    stats = [r for r in recs if r["event"] == "serve_stats"]
    assert stats and all(
        r["steps_carried"] + r["steps_uploaded"] == r["step"]
        and r["eos_discarded_tokens"] == 0 for r in stats)
    assert stats[-1]["steps_carried"] > 0
    text = reg.render()
    for name in ("steps_carried", "steps_uploaded", "eos_discarded_tokens"):
        assert f"mft_serve_{name} " in text


def test_carried_and_stale_turns_share_one_executable(gpt2_params):
    """The arrays a dispatch hands back and the ones the host places
    must reach the decode step alike: after the first stale and the
    first carried turn no turn of either kind — page appends, turnovers,
    a cancel — traces or compiles anything."""
    from fixtures import backend_compiles
    eng = ServeEngine(
        "gpt2", GPT2_CFG, gpt2_params,
        ServeConfig(num_slots=2, block_T=4, num_blocks=64, max_prompt=16,
                    max_new_tokens=12, sampling=True))
    rng = np.random.default_rng(13)
    prompt = lambda n: list(rng.integers(1, 200, n))
    for n in (5, 9, 3):                  # warm: every program, both turns
        eng.submit(prompt(n), max_new_tokens=6)
    eng.drain()
    assert eng.steps_carried > 0 and eng.steps_uploaded > 0
    carried0, uploaded0 = eng.steps_carried, eng.steps_uploaded
    traces0 = eng.total_traces()
    with backend_compiles() as compiles:
        reqs = [eng.submit(prompt(n), max_new_tokens=m, temperature=t,
                           seed=n)
                for n, m, t in ((4, 12, 0.0), (11, 7, 0.8), (6, 12, 0.0),
                                (2, 9, 0.0), (13, 5, 0.7))]
        eng.step()
        eng.step()
        eng.cancel(eng.active[0])
        eng.drain()
        assert compiles == []
    assert eng.total_traces() == traces0
    assert eng.trace_counts["decode_step"] == 1
    assert eng.steps_carried - carried0 >= 8
    assert eng.steps_uploaded - uploaded0 >= 4
    assert all(r.done for r in reqs)
    eng.close()


def test_cpu_e2e_serve_bench_smoke(gpt2_params, tmp_path):
    """Satellite acceptance: a deterministic seeded arrival schedule
    through the REAL serve loop in-process (tools/serve_bench.py's
    engine + load generator), asserting (a) run_start..run_end
    telemetry with schema-valid per-request lifecycle events, (b)
    oracle-equal outputs, (c) the report tool's TTFT/TPOT/req_s
    section."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import serve_bench
    import telemetry_report
    stream = str(tmp_path / "serve.jsonl")
    eng = ServeEngine(
        "gpt2", GPT2_CFG, gpt2_params,
        ServeConfig(num_slots=2, block_T=8, num_blocks=32, max_prompt=16,
                    max_new_tokens=8),
        telemetry=Telemetry(stream))
    done, elapsed = serve_bench.run_load(
        eng, [], rate=200.0, n_requests=5, seed=4, prompt_lo=2,
        prompt_hi=9, max_new=6)
    row = serve_bench.row_from("tiny_smoke", eng, done, elapsed,
                               rate=200.0, adapters=0)
    eng.close()
    assert len(done) == 5 and row["req_s"] > 0
    assert row["ttft_ms"]["p50"] is not None
    assert row["tpot_ms"]["p99"] is not None
    for r in done:                         # oracle-equal outputs
        assert r.tokens == oracle("gpt2", gpt2_params, r), f"req {r.id}"
    # determinism: same seed -> same prompts -> same tokens
    eng2 = ServeEngine(
        "gpt2", GPT2_CFG, gpt2_params,
        ServeConfig(num_slots=2, block_T=8, num_blocks=32, max_prompt=16,
                    max_new_tokens=8))
    done2, _ = serve_bench.run_load(eng2, [], rate=200.0, n_requests=5,
                                    seed=4, prompt_lo=2, prompt_hi=9,
                                    max_new=6)
    eng2.close()
    assert [r.tokens for r in done2] == [r.tokens for r in done]

    with open(stream) as f:
        recs = [json.loads(l) for l in f.read().splitlines() if l.strip()]
    for rec in recs:
        assert validate_event(rec) is None, (rec, validate_event(rec))
    kinds = [r["event"] for r in recs]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    phases = {}
    for rec in recs:
        if rec["event"] == "request":
            phases.setdefault(rec["id"], []).append(rec["phase"])
    assert len(phases) == 5
    for seq in phases.values():
        assert seq == ["enqueue", "admit", "first_token", "finish"]
    fin = [r for r in recs if r.get("phase") == "finish"]
    assert all(r["ttft_ms"] > 0 and r["new_tokens"] == 6 for r in fin)
    assert all(r["tpot_ms"] is not None for r in fin)

    s = telemetry_report.summarize(recs)
    assert s["requests"]["finished"] == 5
    assert s["requests"]["ttft_ms"]["p50"] > 0
    assert s["requests"]["tpot_ms"]["p95"] is not None
    assert s["requests"]["req_s"] > 0
    assert telemetry_report.main([stream]) == 0
