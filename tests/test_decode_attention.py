"""ops/decode_attention.py vs the XLA decode einsum path (the oracle).

The kernel is benched OUT of models/generate.py on the current platform
(a no-op pallas_call costs ~43 us there, so L per-layer calls exceed the
whole XLA attention cost — DESIGN.md §10a), but it is kept as tested
infrastructure to re-measure against future runtimes, like ops/fused_ce.
These tests pin its numerics to the exact einsum semantics generate.py
uses (storage-dtype operands, f32 accumulation, NEG_INF masking,
softmax-then-cast context weights)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mobilefinetuner_tpu.ops.decode_attention import (decode_attention,
                                                      decode_eligible,
                                                      live_chunk_share,
                                                      paged_attention,
                                                      paged_decode_attention,
                                                      paged_eligible,
                                                      paged_plan,
                                                      pick_kvb, pick_pages,
                                                      xla_reference)
from mobilefinetuner_tpu.serve.paged_kv import gather_pages, pool_shape


def make(B, KV, G, T, D, dtype, seed=0):
    kq, kk, kv, km = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (B, KV, G, D), dtype)
    kc = jax.random.normal(kk, (B, KV, T, D), dtype)
    vc = jax.random.normal(kv, (B, KV, T, D), dtype)
    # left-padding-style mask plus scattered invalid columns, but the
    # last column (the current token) always attendable — generate.py's
    # invariant that makes fully-masked rows impossible
    ok = jax.random.bernoulli(km, 0.7, (B, T)).at[:, -1].set(True)
    return q, kc, vc, ok


@pytest.mark.parametrize("shape,dtype", [
    ((2, 12, 1, 64, 64), jnp.float32),    # GPT-2 head layout
    ((2, 12, 1, 64, 64), jnp.bfloat16),
    ((2, 1, 4, 48, 256), jnp.float32),    # Gemma GQA layout
    ((3, 2, 2, 40, 32), jnp.bfloat16),    # multi-kv-head GQA
])
def test_matches_xla_reference(shape, dtype):
    B, KV, G, T, D = shape
    q, kc, vc, ok = make(B, KV, G, T, D, dtype)
    scale = D ** -0.5
    assert decode_eligible(KV, T, D, jnp.dtype(dtype).itemsize)
    got = decode_attention(q, kc, vc, ok, scale)
    want = xla_reference(q, kc, vc, ok, scale)
    assert got.dtype == jnp.float32
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


def test_left_padding_mask_respected():
    """Masked-out columns contribute nothing: shuffling their K/V rows
    must not change the output."""
    B, KV, G, T, D = 2, 4, 1, 32, 64
    q, kc, vc, ok = make(B, KV, G, T, D, jnp.float32)
    ok = jnp.broadcast_to(jnp.arange(T)[None, :] >= 8, (B, T))
    base = decode_attention(q, kc, vc, ok, D ** -0.5)
    poisoned_k = kc.at[:, :, :8].set(1e6)
    poisoned_v = vc.at[:, :, :8].set(-1e6)
    got = decode_attention(q, poisoned_k, poisoned_v, ok, D ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               atol=1e-6)


def test_jittable_and_grad_free():
    B, KV, G, T, D = 2, 2, 2, 16, 32
    q, kc, vc, ok = make(B, KV, G, T, D, jnp.float32)
    f = jax.jit(lambda *a: decode_attention(*a, D ** -0.5))
    out = f(q, kc, vc, ok)
    assert out.shape == (B, KV, G, D)


def test_eligibility_gates():
    # sublane-misaligned T
    assert not decode_eligible(12, 190, 64, 2)
    # VMEM overflow: KV=1 cannot be subdivided further
    assert not decode_eligible(1, 32768, 256, 4)
    assert pick_kvb(1, 32768, 256, 4) is None
    # GPT-2 bench shape picks the whole-KV block (one fat DMA per batch)
    assert pick_kvb(12, 192, 64, 2) == 12
    # a long-cache shape falls back to fewer kv heads per program
    kvb = pick_kvb(12, 8192, 64, 4)
    assert kvb is not None and kvb < 12 and 12 % kvb == 0


# --------------------------- block-paged variants ----------------------------

def make_paged(S, KV, G, D, bT, M, NB, L, dtype, seed=0):
    rng = np.random.default_rng(seed)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (S, KV, G, D), dtype)
    # the pool's shape is serve/paged_kv.py's to say
    shape = pool_shape(NB, L, KV, bT, D)
    pool_k = jax.random.normal(kk, shape, dtype)
    pool_v = jax.random.normal(kv, shape, dtype)
    # block tables over non-trash pages; ragged per-slot lengths, plus a
    # sliding-window hole on slot 0 so FULLY-masked pages occur
    tbl = jnp.asarray(rng.integers(1, NB, (S, M)), jnp.int32)
    lens = rng.integers(1, M * bT + 1, S)
    cols = np.arange(M * bT)
    ok = cols[None, :] < lens[:, None]
    ok[0, :max(int(lens[0]) - 3, 0)] = False       # window: only last 3
    return q, pool_k, pool_v, tbl, jnp.asarray(ok)


@pytest.mark.parametrize("shape,dtype", [
    ((3, 12, 1, 64, 8, 4, 9, 2), jnp.float32),    # GPT-2 head layout
    ((3, 12, 1, 64, 8, 4, 9, 2), jnp.bfloat16),
    ((2, 1, 4, 64, 16, 3, 7, 3), jnp.float32),    # Gemma GQA layout
    ((4, 2, 2, 32, 8, 5, 11, 2), jnp.bfloat16),
    ((2, 5, 1, 64, 16, 3, 7, 2), jnp.float32),    # odd head count, D=64:
    ((2, 25, 1, 64, 16, 2, 5, 1), jnp.bfloat16),  # rows of 320 / 1600 lanes
    ((2, 2, 2, 128, 16, 3, 7, 2), jnp.float32),   # D=128 GQA
    ((3, 1, 4, 128, 8, 3, 6, 2), jnp.bfloat16),
])
def test_paged_matches_gathered_contiguous(shape, dtype):
    """paged_attention == xla_reference over the gathered contiguous
    cache (the paged read is pure indexing, not new math), and the
    pallas paged kernel == paged_attention — both for every layer index,
    under ragged lengths and fully-masked window pages."""
    S, KV, G, D, bT, M, NB, L = shape
    q, pk, pv, tbl, ok = make_paged(S, KV, G, D, bT, M, NB, L, dtype)
    scale = D ** -0.5
    assert paged_eligible(KV, G, bT, D, jnp.dtype(dtype).itemsize)
    for layer in range(L):
        got = paged_attention(q, pk, pv, tbl, layer, ok, scale)
        kc = gather_pages(pk, tbl, layer, KV).transpose(0, 3, 1, 2, 4) \
            .reshape(S, KV, M * bT, D)
        vc = gather_pages(pv, tbl, layer, KV).transpose(0, 3, 1, 2, 4) \
            .reshape(S, KV, M * bT, D)
        want = xla_reference(q, kc, vc, ok, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-6)
        kern = paged_decode_attention(q, pk, pv, tbl, layer, ok, scale)
        assert kern.dtype == jnp.float32
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(kern), np.asarray(got),
                                   atol=tol, rtol=tol)


def serve_masks(pos, M, bT, window=None):
    """The decode step's masks for slots at `pos`: columns 0..pos, and
    under a window the last `window` of them."""
    cols = np.arange(M * bT)
    pos = np.asarray(pos)[:, None]
    ok = cols[None, :] <= pos
    if window is not None:
        ok &= (pos - cols[None, :]) < window
    return jnp.asarray(ok)


# (S, KV, G, D, bT, M, NB, L), P, each slot's position, window
CHUNK_CASES = {
    "ends_mid_page": ((3, 2, 1, 64, 8, 8, 40, 2), 4, [3, 20, 45], None),
    "ends_at_a_chunk_edge": ((3, 2, 1, 64, 8, 8, 40, 2), 4,
                             [31, 32, 63], None),
    "one_token": ((2, 2, 1, 64, 8, 8, 40, 1), 4, [0, 0], None),
    "full_table": ((2, 2, 1, 64, 8, 8, 40, 1), 4, [63, 63], None),
    "M_not_a_multiple_of_P": ((3, 2, 1, 64, 8, 7, 40, 2), 4,
                              [55, 30, 33], None),
    # (the second: a row of 64 lanes, no whole lane tile, which only the
    # pipeline's block operands can bring, a page a step)
    "P_1": ((3, 2, 1, 64, 8, 6, 40, 1), 1, [47, 9, 17], None),
    "P_1_blocks": ((3, 2, 1, 32, 8, 6, 40, 1), 1, [47, 9, 17], None),
    "P_M": ((3, 2, 1, 64, 8, 8, 40, 1), 8, [60, 9, 17], None),
    # Gemma's heads, and a window whose first live page (page 10 of slot
    # 0, page 4 of slot 1) lies past chunk 0; slot 2 is inside its window
    "gqa_window_past_chunk_0": ((3, 1, 4, 128, 8, 16, 60, 2), 4,
                                [100, 50, 9], 20),
    # slot 1 is idle as the engine leaves it: table all trash, position 0
    "idle_slot": ((3, 2, 1, 64, 8, 8, 40, 1), 4, [40, 0, 13], None),
}


def chunk_case(name, dtype=jnp.float32):
    """(q, pool_k, pool_v, tbl, ok, P, live) of a case: tables as the
    engine keeps them — a slot's pages up to its position, trash past
    them — with `live` the pages inside each slot's range [lo, hi)."""
    (S, KV, G, D, bT, M, NB, L), P, pos, window = CHUNK_CASES[name]
    q, pk, pv, tbl, _ = make_paged(S, KV, G, D, bT, M, NB, L, dtype)
    ok = serve_masks(pos, M, bT, window)
    idx = np.arange(M)[None, :]
    hi = np.asarray(pos)[:, None] // bT + 1
    lo = 0 if window is None else \
        np.maximum(np.asarray(pos)[:, None] - window + 1, 0) // bT
    if name == "idle_slot":
        hi[1] = 0
    # pages before a window stay in the table (the engine frees none)
    tbl = jnp.where(jnp.asarray(idx < hi), tbl, 0)
    return q, pk, pv, tbl, ok, P, (idx >= lo) & (idx < hi), bT


@pytest.mark.parametrize("name", sorted(CHUNK_CASES))
def test_paged_chunks_match_the_oracle(name):
    """The kernel at P pages a grid step against paged_attention, at
    the edges of the live range: every layer, f32 and bf16."""
    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)):
        q, pk, pv, tbl, ok, P, _, bT = chunk_case(name, dtype)
        plan = paged_plan(ok[None], bT, P)
        assert plan.ok.shape[2:] == (-(-tbl.shape[1] // P), 1, P * bT)
        for layer in range(pk.shape[1]):
            want = paged_attention(q, pk, pv, tbl, layer, ok, q.shape[-1]
                                   ** -0.5)
            got = paged_decode_attention(q, pk, pv, tbl, layer, plan,
                                         q.shape[-1] ** -0.5)
            assert np.isfinite(np.asarray(got)).all()
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=tol, rtol=tol)


@pytest.mark.parametrize("name", sorted(CHUNK_CASES))
def test_paged_plan_ranges(name):
    """The plan's [lo, hi) is the span of pages with an attendable
    column (an idle slot's is its trash page, [0, 1)), two masks make
    two ranges, and the host's share counts the same live chunks."""
    q, pk, pv, tbl, ok, P, live, bT = chunk_case(name)
    (_, _, _, _, _, M, _, _), _, pos, window = CHUNK_CASES[name]
    full = serve_masks(pos, M, bT)
    plan = paged_plan(jnp.stack([full, ok]), bT, P)
    rng = np.asarray(plan.rng)
    assert rng.shape == (4, len(pos))
    for s, p in enumerate(pos):
        want_lo = 0 if window is None else max(p - window + 1, 0) // bT
        assert tuple(rng[:, s]) == (0, p // bT + 1, want_lo, p // bT + 1)
    chunks = rng[3] - 1 - (rng[3] - 1) % P - (rng[2] - rng[2] % P)
    assert live_chunk_share(np.asarray(pos), bT, M, P, window) == pytest.approx(
        (chunks // P + 1).sum() / (len(pos) * -(-M // P)))


def test_paged_trash_pages_never_leak():
    """Columns routed to the trash page (idle padding in a block table)
    must contribute nothing even when the trash page holds garbage."""
    S, KV, G, D, bT, M, NB, L = 2, 2, 1, 16, 8, 3, 6, 1
    q, pk, pv, tbl, _ = make_paged(S, KV, G, D, bT, M, NB, L, jnp.float32)
    tbl = tbl.at[:, 2].set(0)                     # last page -> trash
    ok = jnp.asarray(np.arange(M * bT)[None, :] < 2 * bT)
    ok = jnp.broadcast_to(ok, (S, M * bT))
    base = paged_attention(q, pk, pv, tbl, 0, ok, D ** -0.5)
    poisoned_k = pk.at[0].set(1e6)
    poisoned_v = pv.at[0].set(-1e6)
    got = paged_attention(q, poisoned_k, poisoned_v, tbl, 0, ok,
                          D ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               atol=1e-6)
    kern = paged_decode_attention(q, poisoned_k, poisoned_v, tbl, 0, ok,
                                  D ** -0.5)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(base),
                               atol=1e-5)


@pytest.mark.parametrize("name", sorted(set(CHUNK_CASES) - {"idle_slot"}))
def test_paged_dead_pages_never_leak(name):
    """NaN in EVERY page outside a slot's live range — the trash page,
    the pages before a window, and stale pages the table still names
    inside a live chunk alike — leaves the kernel's output bit-equal:
    the range says where work cannot be, and no grid step fetches a
    page from there. (The gather oracle reads them all: 0 * NaN.)"""
    q, pk, pv, tbl, ok, P, live, bT = chunk_case(name)
    # stale pages: the table's dead entries name real blocks again
    rng = np.random.default_rng(1)
    stale = jnp.asarray(rng.integers(1, pk.shape[0], tbl.shape), jnp.int32)
    tbl = jnp.where(jnp.asarray(live) | (jnp.arange(tbl.shape[1]) % 2 == 0),
                    tbl, stale)
    dead = np.ones(pk.shape[0], bool)
    dead[np.asarray(tbl)[live]] = False
    assert dead[0] and dead.sum() > 1
    nan = lambda pool: pool.at[jnp.asarray(np.flatnonzero(dead))].set(jnp.nan)
    plan = paged_plan(ok[None], bT, P)
    scale = q.shape[-1] ** -0.5
    want = paged_decode_attention(q, pk, pv, tbl, 0, plan, scale)
    got = paged_decode_attention(q, nan(pk), nan(pv), tbl, 0, plan, scale)
    assert np.isfinite(np.asarray(want)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_validation():
    S, KV, G, D, bT, M, NB, L = 2, 2, 1, 16, 8, 2, 5, 1
    q, pk, pv, tbl, ok = make_paged(S, KV, G, D, bT, M, NB, L,
                                    jnp.float32)
    with pytest.raises(ValueError, match="dtype"):
        paged_decode_attention(q.astype(jnp.bfloat16), pk, pv, tbl, 0,
                               ok, 1.0)
    assert not paged_eligible(KV, G, bT=12, D=D, itemsize=4)  # misaligned
    assert not paged_eligible(KV=1, G=4, bT=512, D=4096, itemsize=4)


def test_vmem_gate_charges_gqa_terms():
    """Regression for the G-blind budget: the old estimate charged only
    the K/V blocks plus a flat T*D*4 term, so a large-G GQA shape whose
    [KVB, G, D] q/ctx blocks and [G, T] score rows dominate VMEM passed
    the gate and would overflow at runtime. The gate must now count
    kvb*G*D*(itemsize+4) and G*T*4."""
    # KV=1, T=8192, D=64, bf16: K/V terms alone need ~6.3 MB — admitted
    # with or without a moderate G...
    assert pick_kvb(1, 8192, 64, 2) == 1
    assert pick_kvb(1, 8192, 64, 2, G=8) == 1
    # ...but at G=256 the [G, T] f32 score rows alone add 8 MB: the OLD
    # G-blind estimate still said kvb=1 (it cannot subdivide KV=1 and
    # charged nothing for G); the tightened gate must refuse.
    assert pick_kvb(1, 8192, 64, 2, G=256) is None
    assert not decode_eligible(1, 8192, 64, 2, G=256)
    # G must also shrink the picked block when KV is divisible: the
    # per-program q/ctx blocks scale with kvb*G
    big = pick_kvb(16, 2048, 256, 2)
    small = pick_kvb(16, 2048, 256, 2, G=64)
    assert big is not None and small is not None and small <= big
    # the paged kernel's gate charges the P pages a grid step folds
    # (pick_pages): 256 score columns a step where VMEM and the table
    # allow — md_batchgen_long's 16 pages of 16 tokens — fewer where
    # the table is short or a page long, ...
    assert pick_pages(16, 1, 16, 64, 2, M=64) == 16
    assert pick_pages(16, 1, 16, 64, 2, M=6) == 4
    assert [pick_pages(16, 1, bT, 64, 2, M=64) for bT in (128, 256)] \
        == [2, 1]
    # ... one where a page row fills no whole lane tile (25 heads of
    # 64; 3 of 12 on each of four shards, but 6 on each of two), ...
    assert pick_pages(25, 1, 16, 64, 2, M=64) == 1
    assert [pick_pages(12, 1, 16, 64, 2, M=64, tp=tp) for tp in (4, 2)] \
        == [1, 16]
    # ... and as many as the two K+V chunk buffers leave room for
    # beside the G-dependent accumulators: 36 heads of 128 at a
    # 128-token page hold two pages a step (11.4 MB), 40 hold one
    # (12.9), and a larger G takes pages away before it takes the kernel
    assert [pick_pages(KV, 1, 128, 128, 2, M=4) for KV in (36, 40)] == [2, 1]
    assert paged_eligible(40, 1, 128, 128, 2)
    assert [pick_pages(32, G, 64, 128, 2, M=64) for G in (2, 4)] == [4, 2]
    assert not paged_eligible(32, 8, 64, 128, 2)
