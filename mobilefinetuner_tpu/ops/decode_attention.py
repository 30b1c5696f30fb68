"""Pallas TPU decode attention: fused M=1 score+softmax+context kernel.

Single-token decode attention is bandwidth-bound: per layer it reads the
whole [B, H, T, D] K/V cache to produce one context row per head. The
XLA path (models/generate.py decode_step) lowers the two M=1 einsums +
softmax to VPU kLoop fusions that read the cache at ~245 GB/s on v5e
(~30% of the ~819 GB/s peak — a layout/emitter limit at M=1 shapes,
DESIGN.md §10); two XLA-level attempts to reach the MXU broke the cache's
dynamic-update-slice aliasing and regressed. This kernel attacks the same
floor from below: one pallas_call per layer streams each (batch,
kv-head-block)'s K and V cache slices through VMEM exactly once as whole
contiguous DMAs, computes scores + masked softmax + context in VMEM, and
writes the [G, D] context rows. The cache slices stay in their storage
dtype end to end (f32 accumulation via preferred_element_type, like the
XLA path), so the kernel moves the same bytes — just at DMA rate instead
of kLoop rate.

Shapes (GQA-general; GPT-2 is the G=1 case):
  q        [B, KV, G, D]   current-token queries, grouped by kv head
  k_cache  [B, KV, T, D]   T = P + N cache columns (whole-T VMEM blocks)
  v_cache  [B, KV, T, D]
  ok       [B, T]          attendable columns (validity AND sliding
                           window — caller composes, so Gemma's per-layer
                           global/local choice stays outside)
  -> ctx   [B, KV, G, D]   float32

Design notes:
  - whole-T blocks, no inner k-loop: decode caches are small (T·D ≤ ~1M
    elements at the supported sizes), so online softmax is unnecessary —
    the full [G, T] score row lives in registers/VMEM;
  - KVB kv-heads per program (largest divisor of KV fitting the VMEM
    budget): fewer, larger grid steps amortize per-program overhead when
    KV is large (GPT-2: 12 heads of [T, 64]) and keep DMAs big;
  - masked-out columns get NEG_INF scores; exp(NEG_INF - m) underflows to
    exactly 0, so no second mask pass is needed. A fully-masked row
    cannot occur (the current token's own column is always attendable);
  - no backward: generation is inference-only (the training path uses
    ops/flash_attention.py, which IS differentiable).

The XLA einsum path remains the oracle and the fallback for ineligible
shapes (T not sublane-aligned, VMEM overflow) and non-TPU backends
(interpret mode covers CPU tests).

Reference provenance: the reference framework's only KV-cache decode sits
in its excluded legacy tree (legacy/transformer/kv_cache.cpp, SURVEY.md
§2.10); this kernel is the TPU-native mechanical upgrade of that
capability (round-5 verdict item 1).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_VMEM_BUDGET = 12 * 2 ** 20
_LANE = 128
# columns of one score block of the paged kernel (pick_pages)
_CHUNK_COLS = 256


from mobilefinetuner_tpu.ops import pallas_util
from mobilefinetuner_tpu.ops.pallas_util import tpu_call_params


def xla_reference(q, k_cache, v_cache, ok, scale):
    """The models/generate.py decode_step attention, verbatim semantics —
    the oracle the kernel is tested against and the comparison the
    microbench tool prices. ONE shared copy so the tests and the tool
    cannot drift from each other (generate.py keeps its own inline copy
    because its buffer structure is perf-fragile — DESIGN.md §10)."""
    s = jnp.einsum("bkgd,bktd->bkgt", q, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgt,bktd->bkgd", p.astype(v_cache.dtype), v_cache,
                      preferred_element_type=jnp.float32)


def shard_heads(KV: int, G: int, tp: int = 1):
    """Per-shard (KV, G) head counts under tp-way tensor parallelism —
    the ONE place the serve mesh's head-axis choice lives (the VMEM
    gates below and serve/sharding.ServeSharding both consult it, so
    the eligibility math can never disagree with the placement):

      KV % tp == 0  the pool's KV-head axis shards — each shard's
                    kernel sees KV // tp heads of its own page slice;
      G % tp == 0   (KV indivisible, GQA) the query-group axis shards —
                    each shard attends the WHOLE (replicated) pool with
                    G // tp query groups per KV head;
      neither       heads replicate: every shard pays the global counts.
    """
    tp = int(tp or 1)
    if tp > 1:
        if KV % tp == 0:
            return KV // tp, G
        if G % tp == 0:
            return KV, G // tp
    return KV, G


def pick_kvb(KV: int, T: int, D: int, itemsize: int, G: int = 1,
             tp: int = 1):
    """Largest divisor of KV whose double-buffered K+V whole-T blocks fit
    the VMEM budget, or None (caller falls back to XLA). Resident per grid
    step: 2 (K, V) x 2 (double buffer) x [KVB, T, D] storage-dtype
    blocks; the [KVB, G, D] q input and f32 ctx output blocks; the
    per-head [G, T] f32 score/prob rows; plus one T·D·4 slack term for
    the compiler's elementwise temps. The G-dependent terms keep large-G
    GQA shapes from passing the gate and overflowing VMEM at runtime
    (before them, only the K/V blocks were charged). tp > 1 charges the
    PER-SHARD head counts (shard_heads): under the serve mesh each
    shard's kernel streams only its own slice, so charging global heads
    would falsely gate the Pallas path off as tp grows."""
    KV, G = shard_heads(KV, G, tp)
    for kvb in range(KV, 0, -1):
        if KV % kvb:
            continue
        need = (4 * kvb * T * D * itemsize     # K+V, double-buffered
                + kvb * G * D * (itemsize + 4)  # q block + f32 ctx block
                + G * T * 4                     # [G, T] score/prob rows
                + T * D * 4)                    # elementwise-temp slack
        if need <= _VMEM_BUDGET:
            return kvb
    return None


def decode_eligible(KV: int, T: int, D: int, itemsize: int,
                    G: int = 1, tp: int = 1) -> bool:
    """T must be sublane-aligned (whole-T blocks are statically indexed,
    but the [T, D] tile still wants 8-row alignment); VMEM must fit
    (per-shard head counts when tp > 1 — see pick_kvb)."""
    return T % 8 == 0 and pick_kvb(KV, T, D, itemsize, G, tp) is not None


def _decode_kernel(q_ref, k_ref, v_ref, ok_ref, o_ref, *, scale, kvb):
    ok = ok_ref[0] > 0                                    # [1, T] (lanes)
    for j in range(kvb):                                  # static unroll
        k = k_ref[0, j]                                   # [T, D] storage
        v = v_ref[0, j]
        q = q_ref[0, j].astype(k.dtype)                   # [G, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G, T]
        s = jnp.where(ok, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)                                # masked -> 0
        l = jnp.sum(p, axis=-1, keepdims=True)
        o_ref[0, j] = jax.lax.dot_general(
            (p / l).astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [G, D] f32


# --------------------------- block-paged variants ----------------------------
#
# The serving subsystem (serve/engine.py, DESIGN.md §16) replaces the
# per-request contiguous [B, KV, T, D] cache with one shared block pool
# of token-major pages (the layout contract: serve/paged_kv.py's module
# docstring): request r's logical column t lives at physical block
# tbl[r, t // bT], row t % bT. Two readers of that layout:
#
#   paged_attention       XLA oracle: gather the slot's pages into a
#                         contiguous [S, M, bT, KV, D] view, then the
#                         same masked-softmax einsums as xla_reference.
#                         The gather MATERIALIZES the active cache once
#                         per layer per step — correct everywhere (it is
#                         what the CPU tests and the serve engine's
#                         default path run), but it moves the cache
#                         bytes twice.
#   paged_decode_attention Pallas kernel: the block table and each
#                         slot's live page range ride in as scalar-
#                         prefetch operands, and the kernel walks P
#                         pages a grid step inside the live range: a
#                         CHUNK of P consecutive table entries, copied
#                         straight from the pool by the kernel itself
#                         (no materialized per-slot copy), the next
#                         chunk while this one is folded into an
#                         online-softmax accumulator; the steps past a
#                         slot's last live page do nothing. P comes
#                         from the shapes (pick_pages). The mask cut
#                         into chunks and the ranges are a PagedPlan,
#                         made once a decode step (paged_plan), not
#                         once a layer. This is the block-table-
#                         indexed upgrade of _decode_kernel: same VMEM
#                         streaming story, indirect page addressing
#                         instead of whole-T blocks.
#
# What the chip said (PERF.md §6, PR 30; gpt2-medium's 16 heads of 64,
# 16 slots, tables of 64 pages of 16 tokens): a block operand of the
# Pallas pipeline costs ~0.1 us at EVERY grid step, whether its block
# index moved or not, so one page a step through two block operands was
# 484 us a layer of which the pages' bytes were 44; P block operands a
# pool leave that product where it was (~230 us at any P); the kernel's
# own copies pay per page moved: 69 us a layer at P = 16.


def paged_attention(q, pool_k, pool_v, tbl, layer, ok, scale):
    """Block-paged decode attention, XLA path (the kernel's oracle).

    q       [S, KV, G, D]    current-token queries per slot
    pool_k  the shared block pools, all layers (serve/paged_kv.py's
    pool_v  layout: token-major pages [bT, KV * D])
    tbl     [S, M] int32     per-slot block table (unused rows -> the
                             reserved trash block 0; masked by ok)
    layer   scalar int32     which layer's pages to read
    ok      [S, M*bT] bool   attendable logical columns (occupancy AND
                             any sliding window — caller composes)
    -> ctx  [S, KV, G, D] float32
    """
    from mobilefinetuner_tpu.serve.paged_kv import gather_pages
    S, KV, G, D = q.shape
    kc = gather_pages(pool_k, tbl, layer, KV)    # [S, M, bT, KV, D]
    vc = gather_pages(pool_v, tbl, layer, KV)
    M, bT = kc.shape[1:3]
    s = jnp.einsum("skgd,smtkd->skgmt", q, kc,
                   preferred_element_type=jnp.float32) * scale
    s = s.reshape(S, KV, G, M * bT)
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).reshape(S, KV, G, M, bT)
    return jnp.einsum("skgmt,smtkd->skgd", p.astype(vc.dtype), vc,
                      preferred_element_type=jnp.float32)


def _paged_need(P: int, KV: int, G: int, bT: int, D: int,
                itemsize: int) -> int:
    """VMEM bytes the paged kernel holds at P pages a grid step (local
    head counts)."""
    row, R = KV * D, KV * G
    return (4 * P * bT * row * itemsize         # K+V pages, double-buffered
            + R * row * itemsize                # block-diagonal queries
            + 3 * R * row * 4                   # o accumulator + p@v temps
            + 2 * G * row * (itemsize + 4)      # q rows in, f32 ctx rows out
            + 3 * R * max(P * bT, _LANE) * 4)   # scores, p, m/l


def paged_eligible(KV: int, G: int, bT: int, D: int,
                   itemsize: int, tp: int = 1) -> bool:
    """One page pair (K+V, double-buffered), the per-slot block-diagonal
    queries and the [G*KV, KV*D] f32 accumulator with its temps must
    fit VMEM; bT must be sublane-aligned. (That is the kernel at ONE
    page a grid step, its smallest; pick_pages says how many it takes.)
    tp > 1 charges PER-SHARD head counts (shard_heads): the sharded
    serve path runs the kernel under shard_map on each shard's pool
    slice, so the VMEM bill is the local one — global counts would be
    both too strict (KV-sharded pools) and, were the budget ever raised
    per-shard, unsafely lax the other way.

    The VMEM term tracks what the installed v5e compiler accepts
    (tests/test_aot_tpu.py compiles both sides of it: every shape this
    admits compiles, and the over-budget shapes it refuses are refused
    by Mosaic with RESOURCE_EXHAUSTED). bT % 8 is POLICY, not a
    compiler limit — Mosaic pads a ragged page, but so does the pool's
    HBM tiling, and a page that wastes its tile is a bad ServeConfig,
    not a fast path."""
    KV, G = shard_heads(KV, G, tp)
    return bT % 8 == 0 and _paged_need(1, KV, G, bT, D,
                                       itemsize) <= _VMEM_BUDGET


def _chunk_fits(P: int, KV: int, G: int, bT: int, D: int,
                itemsize: int) -> bool:
    """May a grid step fold P > 1 pages (local head counts)? The page
    row fills whole lane tiles, so the kernel's own copies can bring
    it, and the chunk's buffers fit VMEM."""
    return (KV * D) % _LANE == 0 and _paged_need(
        P, KV, G, bT, D, itemsize) <= _VMEM_BUDGET


def pick_pages(KV: int, G: int, bT: int, D: int, itemsize: int, M: int,
               tp: int = 1) -> int:
    """P, the pages of one slot a grid step of the paged kernel folds:
    the largest power of two, at most the table's M entries, whose
    score block [G*KV, P*bT] is no wider than _CHUNK_COLS columns and
    whose two K+V chunk buffers fit _VMEM_BUDGET beside the
    accumulators. Read off the shapes like pick_kvb — the same kernel
    wants another P at another width. P = 1 is the one-page kernel: a
    page of _CHUNK_COLS tokens or more, a chunk VMEM cannot hold, or a
    page row that does not fill whole lane tiles (25 heads of 64; 3 of
    a shard's) — the kernel's own copies cannot cut such a page out of
    the pool's tiled HBM buffer, so there the pipeline brings it, a
    page a grid step. Per-shard head counts under tp, like
    paged_eligible."""
    KV, G = shard_heads(KV, G, tp)
    P = 1
    while (2 * P <= M and 2 * P * bT <= _CHUNK_COLS
           and _chunk_fits(2 * P, KV, G, bT, D, itemsize)):
        P *= 2
    return P


def resolve_paged_impl(attn_impl: str, KV: int, G: int, bT: int, D: int,
                       itemsize: int, tp: int = 1) -> str:
    """The paged-decode `auto` rule, ONE copy: both families' decode
    steps (models/generate.py) pick their attend through it and the
    serve engine stamps its answer into the run_start manifest, so the
    stream says which path served. "pallas" on a TPU backend at an
    eligible shape, else the gather oracle "xla"; a forced impl
    resolves to itself."""
    if attn_impl != "auto":
        return attn_impl
    on_tpu = jax.default_backend() == "tpu"
    return "pallas" if on_tpu and paged_eligible(
        KV, G, bT, D, itemsize, tp) else "xla"


class PagedPlan(NamedTuple):
    """What the paged kernel takes in place of the boolean mask, made
    ONCE a decode step (paged_plan) and shared by every layer: the K
    masks a step's layers attend under (one, or two where a family has
    windowed layers), each cut into the kernel's chunks, and each
    slot's live page range under each."""
    ok: jax.Array    # [K, S, C, 1, P*bT] int32: chunk c's columns of mask k
    rng: jax.Array   # [2K, S] int32: rows 2k, 2k+1 = mask k's first live
    #                  page and one past its last (lo < hi, always)
    kind: jax.Array  # [1] int32: the mask THIS layer attends under

    def of_kind(self, kind) -> "PagedPlan":
        return self._replace(kind=jnp.asarray(kind, jnp.int32).reshape(1))


def paged_plan(oks, bT: int, pages: int) -> PagedPlan:
    """oks [K, S, M*bT] bool -> the kernel's plan at `pages` pages a grid
    step. A page is live where any of its columns is attendable; [lo,
    hi) spans a slot's live pages (pages between them may be dead: the
    range says where work cannot be, the mask stays the truth). A slot
    with no attendable column at all gets [0, 1) — its row is garbage,
    as it always was, but the kernel has a page to walk. The table's
    tail past M, where M is no multiple of `pages`, is masked."""
    K, S, T = oks.shape
    M, C = T // bT, -(-T // (bT * pages))
    live = oks.reshape(K, S, M, bT).any(-1)                   # [K, S, M]
    idx = jnp.arange(M, dtype=jnp.int32)
    hi = jnp.max(jnp.where(live, idx + 1, 1), axis=-1)        # [K, S]
    lo = jnp.minimum(jnp.min(jnp.where(live, idx, M), axis=-1), hi - 1)
    ok = jnp.pad(oks.astype(jnp.int32),
                 ((0, 0), (0, 0), (0, C * pages * bT - T)))
    # the mask rides as [.., C, 1, P*bT] so a chunk's (1, P*bT) block
    # spans the array's last two dims whole — the TPU lowering takes a
    # block whose trailing dims are (8k, 128k) or the full extent, and a
    # (1, P*bT) window into [S, M*bT] is neither
    return PagedPlan(ok.reshape(K, S, C, 1, pages * bT),
                     jnp.stack([lo, hi], axis=1).reshape(2 * K, S),
                     jnp.zeros((1,), jnp.int32))


def live_chunk_share(pos, bT: int, M: int, pages: int, window=None):
    """Of the (slot, chunk) grid steps the kernel makes for slots at the
    host's positions `pos` (numpy, [S]), the share that hold a live page
    — paged_plan's ranges, reckoned from the positions alone: columns
    0..pos attendable, the last `window` of them on a windowed layer."""
    lo = 0 if window is None else np.maximum(pos - window + 1, 0) // bT
    live = (pos // bT) // pages - lo // pages + 1
    return live.sum() / (len(pos) * -(-M // pages))


def _paged_kernel(tbl_ref, lyr_ref, kind_ref, rng_ref, q_ref, pk_ref,
                  pv_ref, ok_ref, o_ref, q_bd, o_acc, m_acc, l_acc, *bufs,
                  scale, kv, g, d, pages):
    """Grid (S, C): slot-major, chunks of `pages` table entries inner —
    the accumulators carry one slot's online softmax across its chunks.
    A page is [bT, KV*D] (all heads of a token in one row), so the
    queries are laid out BLOCK-DIAGONALLY against it, once per slot:
    row (gi, j) of q_bd holds query group gi of head j in head j's
    lanes [j*D, (j+1)*D) and zeros elsewhere (they add exact zeros to
    the f32 accumulation). Every head's scores over a whole chunk are
    then ONE matmul, with no per-head slicing of a row, and row (gi, j)
    of the accumulator holds head j's context in its own lanes (the
    rest is the cross-head product nobody reads).

    Who brings a chunk. With `bufs` (k_buf, v_buf, sem, turn) the pools
    stay in HBM and the kernel copies pages itself: a live chunk's live
    pages land end to end in one of two [P*bT, KV*D] buffers per pool,
    and while a chunk is folded the NEXT live chunk — this slot's, or
    the next slot's first — is already on its way into the other
    (`turn` says which is which; the call's first live chunk starts its
    own). Copies cost the pages they move; a block operand of the
    pipeline costs ~0.1 us at EVERY grid step, which at a page a step
    was the whole of this kernel's time. Without `bufs` (pick_pages:
    rows that do not fill lane tiles) pk_ref / pv_ref are the
    pipeline's (1, 1, bT, KV*D) blocks, a page a grid step.

    A chunk outside the slot's live page range does nothing: no copy,
    no body. Inside a live chunk the mask decides, and a dead page
    there is not copied either: its rows of the buffer keep what an
    earlier page left (or the zeros v_buf starts with), masked, so what
    a dead page holds — NaN included — never reaches the chip.
    Probabilities are re-masked after the exp, so the
    NEG_INF-vs-NEG_INF cancellation in `s - m` cannot resurrect dead
    columns. ok_ref is the chunk's (1, 1, 1, 1, P*bT) block of the
    plan's mask."""
    s_, c = pl.program_id(0), pl.program_id(1)
    S, M = tbl_ref.shape
    mask = 2 * kind_ref[0]              # this layer's rows of the ranges
    first_page = lambda s: rng_ref[mask, s]
    end_page = lambda s: rng_ref[mask + 1, s]
    c_hi = (end_page(s_) - 1) // pages + 1

    def page_copies(s, c, buf):
        """(is it live, its K and V copies) of each page of slot s's
        chunk c into buffer `buf` — built alike to start and to wait."""
        k_buf, v_buf, sem, _ = bufs
        bt = k_buf.shape[1] // pages
        lo, hi = first_page(s), end_page(s)
        for i in range(pages):                          # static unroll
            j = c * pages + i
            blk = tbl_ref[s, jnp.minimum(j, M - 1)]
            yield (j >= lo) & (j < hi), [
                pltpu.make_async_copy(
                    pool.at[blk, lyr_ref[0]],
                    dst.at[buf, pl.ds(i * bt, bt)], sem.at[n, buf])
                for n, (pool, dst) in enumerate(((pk_ref, k_buf),
                                                 (pv_ref, v_buf)))]

    def each_live(s, c, buf, do):
        for live, copies in page_copies(s, c, buf):
            @pl.when(live)
            def _():
                for cp in copies:
                    do(cp)

    def fetched():
        """This chunk's K and V, [P*bT, KV*D] storage each; the next
        live chunk's copies start before this one's are waited for."""
        k_buf, v_buf, _, turn = bufs
        buf = turn[0]
        turn[0] = 1 - buf
        more = c + 1 < c_hi

        @pl.when(more | (s_ + 1 < S))
        def _next():
            s_n = jnp.where(more, s_, jnp.minimum(s_ + 1, S - 1))
            each_live(s_n, jnp.where(more, c + 1, first_page(s_n) // pages),
                      1 - buf, lambda cp: cp.start())

        each_live(s_, c, buf, lambda cp: cp.wait())
        return k_buf[buf], v_buf[buf]

    def own(gi):
        """Group gi's rows (gi, j), each in its own head j's lanes —
        needed at a slot's first and last chunk only. (Compares and one
        multiply: the VPU has no integer divide.)"""
        j = jax.lax.broadcasted_iota(jnp.int32, o_acc.shape, 0) - gi * kv
        col = jax.lax.broadcasted_iota(jnp.int32, o_acc.shape, 1) - j * d
        return (j >= 0) & (j < kv) & (col >= 0) & (col < d)

    if bufs:
        @pl.when((s_ == 0) & (c == 0))
        def _first():
            k_buf, v_buf, _, turn = bufs
            turn[0] = 0
            v_buf[...] = jnp.zeros_like(v_buf)
            each_live(0, first_page(0) // pages, 0, lambda cp: cp.start())

    @pl.when(c == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)
        # (selects run in f32: Mosaic will not relayout the 32-bit
        # mask onto a packed 16-bit operand)
        bd = jnp.zeros(o_acc.shape, jnp.float32)
        for gi in range(g):                             # static unroll
            q = q_ref[0, pl.ds(gi, 1), :].astype(jnp.float32)  # [1, KV*D]
            bd = jnp.where(own(gi), q, bd)
        q_bd[...] = bd.astype(q_bd.dtype)

    @pl.when((c >= first_page(s_) // pages) & (c < c_hi))
    def _fold():
        k, v = fetched() if bufs else (pk_ref[0, 0], pv_ref[0, 0])
        ok = ok_ref[0, 0, 0] > 0                        # [1, P*bT] (lanes)
        s = jax.lax.dot_general(
            q_bd[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G*KV, P*bT]
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_acc[...]                             # [G*KV, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)      # [G*KV, P*bT]
        o_acc[...] = alpha * o_acc[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [G*KV, KV*D]
        l_acc[...] = alpha * l_acc[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_acc[...] = m_new

    @pl.when(c == pl.num_programs(1) - 1)
    def _finish():
        # the current token's own column is always attendable, so l > 0.
        # Keep each row's own head block and fold group gi's rows into
        # ctx row gi: [G, KV*D], heads side by side like the queries
        o = o_acc[...] / l_acc[...]
        for gi in range(g):                             # static unroll
            o_ref[0, pl.ds(gi, 1), :] = jnp.sum(
                jnp.where(own(gi), o, 0.0), axis=0, keepdims=True)


def paged_decode_attention(q, pool_k, pool_v, tbl, layer, ok, scale):
    """Pallas block-paged decode attention (shapes as paged_attention;
    `ok` is the boolean mask, or the step's PagedPlan where the caller
    made one for all its layers — models/generate.decode_step_paged).
    The block table, the layer index and the slots' live page ranges
    are scalar-prefetch operands: a grid step is one slot's CHUNK of P
    consecutive table entries inside its live range, and the kernel
    reads tbl to copy each of the chunk's live pages [bT, KV*D] out of
    the pool as it lies there (serve/paged_kv.py's layout), the next
    chunk's while this one is folded — the live cache is read once, at
    DMA rate, with no gathered per-slot copy and no relayout, and the
    table's dead part costs an empty grid step a chunk. P is the plan's
    (pick_pages, where this makes the plan). Queries go in and contexts
    come out as rows of the same kind, [S, G, KV*D]. Caller must have
    checked paged_eligible."""
    S, KV, G, D = q.shape
    bT, row = pool_k.shape[2:]
    M = tbl.shape[1]
    itemsize = pool_k.dtype.itemsize
    if q.dtype != pool_k.dtype:
        raise ValueError(
            f"paged_decode_attention requires q.dtype == pool dtype "
            f"(got {q.dtype} vs {pool_k.dtype})")
    if row != KV * D:
        raise ValueError(
            f"pool rows hold {row} values, the queries want KV*D = "
            f"{KV}*{D} (pool layout: serve/paged_kv.py)")
    plan = ok if isinstance(ok, PagedPlan) else paged_plan(
        ok[None], bT, pick_pages(KV, G, bT, D, itemsize, M))
    C, P = plan.ok.shape[2], plan.ok.shape[-1] // bT
    copies = row % _LANE == 0
    if not paged_eligible(KV, G, bT, D, itemsize) or (
            P > 1 and not _chunk_fits(P, KV, G, bT, D, itemsize)):
        raise ValueError(
            f"paged_decode_attention ineligible for KV={KV}, G={G}, "
            f"bT={bT}, D={D} at {P} pages a step (check paged_eligible "
            f"before calling; pick_pages for P)")
    kernel = functools.partial(_paged_kernel, scale=scale, kv=KV, g=G, d=D,
                               pages=P)

    def live_chunk(s, c, kind, rng):
        # a dead step repeats the nearest live chunk's blocks: no copy
        lo, hi = rng[2 * kind[0], s], rng[2 * kind[0] + 1, s]
        return jnp.clip(c, lo // P, (hi - 1) // P)

    rows = pl.BlockSpec((1, G, row), lambda s, c, *_: (s, 0, 0))
    pool = pl.BlockSpec(memory_space=pltpu.HBM) if copies else pl.BlockSpec(
        (1, 1, bT, row), lambda s, c, tbl, lyr, kind, rng: (
            tbl[s, live_chunk(s, c, kind, rng)], lyr[0], 0, 0))
    chunk_bufs = [
        pltpu.VMEM((2, P * bT, row), pool_k.dtype),   # K chunks, two
        pltpu.VMEM((2, P * bT, row), pool_v.dtype),   # V chunks, two
        pltpu.SemaphoreType.DMA((2, 2)),              # (K | V, buffer)
        pltpu.SMEM((1,), jnp.int32),                  # the buffer in turn
    ] if copies else []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,        # tbl, layer, plan.kind, plan.rng
        grid=(S, C),
        in_specs=[rows, pool, pool, pl.BlockSpec(
            (1, 1, 1, 1, P * bT), lambda s, c, tbl, lyr, kind, rng: (
                kind[0], s, live_chunk(s, c, kind, rng), 0, 0))],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((G * KV, row), pool_k.dtype),  # block-diagonal q
            pltpu.VMEM((G * KV, row), jnp.float32),   # o accumulator
            pltpu.VMEM((G * KV, 1), jnp.float32),     # running max
            pltpu.VMEM((G * KV, 1), jnp.float32),     # running sum
            *chunk_bufs],
    )
    # no dimension_semantics here: both dimensions must stay sequential
    # (the accumulators carry across a slot's chunks, the copies in
    # flight across slots), which is the compiler's default for
    # grid_spec-style calls
    ctx = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, G, row), jnp.float32),
        interpret=pallas_util.interpret_mode(),
    )(tbl.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      plan.kind, plan.rng,
      q.transpose(0, 2, 1, 3).reshape(S, G, row), pool_k, pool_v, plan.ok)
    return ctx.reshape(S, G, KV, D).transpose(0, 2, 1, 3)


def sharded_paged_attend(shardings):
    """paged_decode_attention under a serve (dp, tp) mesh, via shard_map.

    pallas_call is a custom call GSPMD cannot partition, so the sharded
    serve path wraps the UNCHANGED kernel in jax.shard_map and
    hands each shard its own operands:

      pool_k/pool_v  KV/tp whole heads of every page row when the KV
                     axis shards (serve/paged_kv.pool_partition_spec:
                     each shard DMAs only its own lanes of a page), the
                     whole pool otherwise (replicated);
      q / ctx        [S/dp, KV', G', D] — whichever head axis the
                     engine shards (shard_heads), slots split over dp;
      tbl / ok       replicated across tp (every shard walks the same
                     block tables and live ranges), split over dp with
                     their slots — `ok` the boolean mask or the step's
                     PagedPlan, whose P is reckoned from the LOCAL head
                     counts (pick_pages(tp=)), like the VMEM gate;
      layer          replicated scalar.

    Inside the body the kernel re-checks its VMEM bill on its LOCAL
    shapes (the division already happened), so the gate and the
    partitioning can never disagree.

    `shardings` is a serve/sharding.ServeSharding (duck-typed: mesh /
    dp / tp / kv_shards / g_shards). Returns an attend(q, pool_k,
    pool_v, tbl, layer, ok, scale) drop-in for paged_decode_attention.
    """
    from jax.sharding import PartitionSpec as P
    from mobilefinetuner_tpu.serve.paged_kv import pool_partition_spec

    sh = shardings
    dp = "dp" if sh.dp > 1 else None
    kv_ax = "tp" if sh.kv_shards > 1 else None
    g_ax = "tp" if sh.g_shards > 1 else None
    q_spec = P(dp, kv_ax, g_ax, None)
    pool_spec = pool_partition_spec(sh.kv_shards > 1)
    plan_spec = PagedPlan(P(None, dp), P(None, dp), P())

    def attend(q, pool_k, pool_v, tbl, layer, ok, scale):
        if not isinstance(ok, PagedPlan):
            _, KV, G, D = q.shape
            bT = pool_k.shape[2]
            ok = paged_plan(ok[None], bT, pick_pages(
                KV, G, bT, D, pool_k.dtype.itemsize, tbl.shape[1], sh.tp))

        def local(q_, pk_, pv_, tbl_, lyr_, plan_):
            return paged_decode_attention(q_, pk_, pv_, tbl_, lyr_, plan_,
                                          scale)

        # check_vma=False: the replicated-output proof doesn't see
        # through the kernel's custom call; the body is deterministic
        # per shard, so unmentioned axes are replicated by construction
        fn = jax.shard_map(local, mesh=sh.mesh,
                           in_specs=(q_spec, pool_spec, pool_spec,
                                     P(dp, None), P(), plan_spec),
                           out_specs=q_spec, check_vma=False)
        return fn(q, pool_k, pool_v, tbl,
                  jnp.asarray(layer, jnp.int32), ok)

    return attend


def decode_attention(q, k_cache, v_cache, ok, scale):
    """Fused decode attention over a whole KV cache (shapes above).
    Caller must have checked decode_eligible for these shapes."""
    B, KV, G, D = q.shape
    T = k_cache.shape[2]
    if q.dtype != k_cache.dtype:
        # the kernel casts q to the cache dtype before the score dot
        # (generate.py always has them equal); a silent downcast of f32
        # queries against a bf16 cache would diverge from xla_reference
        raise ValueError(
            f"decode_attention requires q.dtype == cache dtype "
            f"(got {q.dtype} vs {k_cache.dtype})")
    kvb = pick_kvb(KV, T, D, k_cache.dtype.itemsize, G)
    if kvb is None or T % 8 != 0:
        raise ValueError(
            f"decode_attention ineligible for KV={KV}, T={T}, D={D}, "
            f"G={G}, itemsize={k_cache.dtype.itemsize} (check "
            f"decode_eligible before calling)")
    kernel = functools.partial(_decode_kernel, scale=scale, kvb=kvb)
    ok2 = ok.astype(jnp.int32).reshape(B, 1, T)
    return pl.pallas_call(
        kernel,
        grid=(B, KV // kvb),
        in_specs=[
            pl.BlockSpec((1, kvb, G, D), lambda b, k: (b, k, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, kvb, T, D), lambda b, k: (b, k, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, kvb, T, D), lambda b, k: (b, k, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, T), lambda b, k: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, kvb, G, D), lambda b, k: (b, k, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), jnp.float32),
        **tpu_call_params("parallel", "parallel"),
    )(q, k_cache, v_cache, ok2)
