"""Pallas TPU flash attention: fused, O(S) HBM, differentiable, block-sparse.

Replaces the reference's forward-only streaming-softmax attention
(reference: core/memory_efficient_attention.{h,cpp} — FlashAttention-style
two-pass row streaming, scalar loops, registers NO backward node, SURVEY.md
§2.12.1) with a TPU-native block kernel that IS differentiable: a
custom_vjp whose forward saves only (out, logsumexp) and whose backward
recomputes probabilities blockwise — activation memory stays O(B·H·S·D),
never O(B·H·S²), in HBM.

Design (sized for the fine-tuning regime S ≤ ~2k, D ≤ 256):
  - forward grid (B, Hq, S/BQ), all dims parallel; each program owns one
    [BQ, D] query block and loops over key blocks with ONLINE softmax,
    visiting only blocks the mask can reach: causal skips the strictly-
    upper-triangular blocks (~2× FLOPs) and a sliding window w visits
    O(w/BK + 1) blocks per query block, so Gemma's local layers cost
    O(S·w), not O(S²);
  - K/V for the (batch, kv-head) live whole in VMEM (S·D·4B ≤ ~2 MB at
    S=2048 D=256); the k-loop slices them in VMEM — block skipping saves
    MXU FLOPs, which dominate at these shapes;
  - GQA by BlockSpec index mapping: q-head h reads kv-head h // group —
    K/V are never materialized per-q-head (the reference materializes via
    repeat_kv_heads, core/ops.cpp:2072);
  - causal + sliding-window + key-padding masks built from broadcasted
    iotas inside the kernel;
  - backward has TWO implementations behind a selector (resolve_bwd_impl;
    'auto' picks merged whenever its VMEM accounting fits, the split pair
    remains the parity oracle and the large-shape fallback):
      merged (default): ONE kernel, grid (B, Hq, S/BK) with only the
             innermost key-block dim sequential. Each program owns one
             [BK, D] key block, loops over the q-blocks that can see it
             (causal: qi ≥ ki·BK/BQ; window: qi·BQ < ki·BK+BK+w), and
             computes dK/dV *and* the dQ contributions from ONE
             recomputation of (s, p, dp, ds) — the split pair recomputes
             those twice (7 tile matmuls vs 5, ~29% of backward MXU
             work), reads K/V/dO/LSE from HBM twice, and costs twice the
             kernel launches (the S=1024 GPT-2s step runs 24 backward
             launches split, 12 merged). dQ accumulates in an f32 VMEM
             scratch slab across the sequential key-block steps and is
             written once on the last step; GQA emits per-q-head dK/dV
             partials that XLA group-sums (free when Hq == Hkv).
      split (oracle):
       dQ:   grid (B, Hq, S/BQ), ALL dims parallel, same skipping k-loop
             as the forward;
       dK/dV: grid (B, S/BK, Hq) with only the innermost head dim
             sequential (fully parallel when Hq == Hkv); accumulates the
             G q-heads of a kv-head over consecutive innermost steps.
    Δ = rowsum(dO ∘ O) is precomputed in XLA (one fused elementwise pass).

For shapes the kernel doesn't support (S not a multiple of the block, tiny
D, explicit attn_mask matrices), ops/attention.py's XLA path is the
fallback — same numerics, same mask semantics (it is the oracle the kernel
is tested against).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mobilefinetuner_tpu.ops import pallas_util
from mobilefinetuner_tpu.ops.pallas_util import tpu_call_params

NEG_INF = -1e30


def _valid_blocks(S: int, block_q: int,
                  block_k: int) -> Optional[tuple]:
    """Largest hardware-valid (block_q, block_k) <= requested, or None
    (caller falls back to XLA).

    Mosaic constraints on v5e (verified by compiling): query-side dynamic
    slices hit the SUBLANE dim (8-aligned offsets -> block_q % 8 == 0);
    the key-padding row [1, S] is sliced on the LANE dim (128-aligned ->
    block_k % 128 == 0). A single whole-S block is exempt: the kernels
    index it statically (no dynamic slice), which keeps short/odd S
    (e.g. 64, 192) on the kernel exactly as the pre-block-loop version did.
    Whole-S fallback is capped at 1024 so [BQ, S] scores stay VMEM-sized.
    """
    bq = bk = None
    for b in (block_q, 512, 384, 256, 128):
        if b <= block_q and b <= S and S % b == 0 and b % 8 == 0:
            bq = b
            break
    if bq is None and S <= 1024 and S % 8 == 0:
        bq = S
    for b in (block_k, 512, 384, 256, 128):
        if b <= block_k and b <= S and S % b == 0 and b % 128 == 0:
            bk = b
            break
    if bk is None and S <= 1024 and S % 8 == 0:
        bk = S  # single block: static path, no alignment constraint
    if bq is None or bk is None:
        return None
    return bq, bk


def _kv_block_bounds(row0, block_q, block_k, n_kv_blocks, causal, window):
    """[lo, hi) k-block range reachable from query rows
    [row0, row0+block_q): causal caps hi at the diagonal block; a sliding
    window lifts lo to the first block any row can still see."""
    if causal:
        hi = jnp.minimum(n_kv_blocks,
                         (row0 + block_q - 1) // block_k + 1)
    else:
        hi = n_kv_blocks
    if window is not None:
        lo = jnp.maximum(0, (row0 - window + 1) // block_k)
    else:
        lo = 0
    return lo, hi


def _block_mask(row0, col0, block_q, block_k, causal, window, pad_blk):
    """[BQ, BK] bool attend-mask for one (q-block, k-block) tile."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + col0
    mask = pad_blk > 0                    # key padding [1|BQ, BK]
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    return mask


def _keep_mask(seed, b, h, row0, col0, block_q, block_k, p_drop):
    """[BQ, BK] bool keep-mask for attention dropout on one tile.

    Counter-based: a lowbias32-style integer mix of (seed, batch, head,
    global row, global col) — each (b, h, i, j) cell's bit is a pure
    function of its coordinates, so the forward and BOTH backward kernels
    regenerate identical masks regardless of their different tile
    iteration orders, with no [S, S] mask ever materialized. Plain 32-bit
    jnp arithmetic (wrapping int32 mul/xor/shift), so hardware and
    interpret mode agree bit-for-bit and the tests' numpy reimplementation
    is exact (tests/test_flash_attention.py)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + col0
    x = (seed ^ (b * jnp.int32(-1640531527))        # 0x9E3779B9
         ^ (h * jnp.int32(-2048144789)))            # 0x85EBCA6B
    z = (x + rows * jnp.int32(-1028477387)          # 0xC2B2AE35
         + cols * jnp.int32(668265263))             # 0x27D4EB2F
    z = z ^ ((z >> 16) & 0xFFFF)
    z = z * jnp.int32(0x7FEB352D)
    z = z ^ ((z >> 15) & 0x1FFFF)
    z = z * jnp.int32(-2073254261)                  # 0x846CA68B
    z = z ^ ((z >> 16) & 0xFFFF)
    # uniform u24 from the high bits; keep iff below the keep threshold
    u24 = (z >> 8) & 0xFFFFFF
    thresh = jnp.int32(round((1.0 - p_drop) * (1 << 24)))
    return u24 < thresh


# --------------------------------- forward ----------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, pad_ref, seed_ref, o_ref, lse_ref, *,
                scale, block_q, block_k, causal, window, S, p_drop):
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    row0 = qi * block_q
    q = q_ref[0, 0].astype(jnp.float32)           # [BQ, D]
    D = v_ref.shape[-1]                           # the output's width

    def step(col0, k, v, pad, carry):
        m, l, acc = carry
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _block_mask(row0, col0, block_q, k.shape[0], causal, window,
                           pad)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)        # [BQ, BK]
        alpha = jnp.exp(m - m_new)
        # HF probs-dropout semantics: the softmax DENOMINATOR sums the
        # undropped probs (l), only the value accumulation sees the
        # dropped+rescaled weights — out = dropout(softmax(s)) @ v.
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if p_drop > 0.0:
            keep = _keep_mask(seed_ref[0], b, h, row0, col0, block_q,
                              k.shape[0], p_drop)
            pv = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - p_drop))
        else:
            pv = p
        acc = acc * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((block_q, 1), NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32),
            jnp.zeros((block_q, D), jnp.float32))
    if block_k == S:
        # single whole-S block: static indexing (no alignment constraint)
        m, l, acc = step(0, k_ref[0, 0].astype(jnp.float32),
                         v_ref[0, 0].astype(jnp.float32), pad_ref[0],
                         init)
    else:
        nK = S // block_k
        lo, hi = _kv_block_bounds(row0, block_q, block_k, nK, causal,
                                  window)

        def body(ki, carry):
            col0 = ki * block_k
            return step(
                col0,
                k_ref[0, 0, pl.ds(col0, block_k), :].astype(jnp.float32),
                v_ref[0, 0, pl.ds(col0, block_k), :].astype(jnp.float32),
                pad_ref[0, :, pl.ds(col0, block_k)], carry)
        m, l, acc = jax.lax.fori_loop(lo, hi, body, init)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l_safe)            # [BQ, 1]


def _fwd(q, k, v, padding_mask, seed, *, scale, causal, window, block_q,
         block_k, p_drop=0.0):
    B, Hq, S, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[3]
    G = Hq // Hkv
    grid = (B, Hq, S // block_q)
    pad3 = padding_mask.reshape(B, 1, S)
    kernel = functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal,
                               window=window, S=S, p_drop=p_drop)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // G, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, Dv), lambda b, h, i: (b, h // G, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S), lambda b, h, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv),
                         lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, S, 1), jnp.float32),
        ],
        **tpu_call_params("parallel", "parallel", "parallel",
                          vmem_limit_bytes=_kv_vmem_limit(
                              S, D, Dv, q.dtype.itemsize)),
    )
    # semantic trace annotation: the kernel shows up as attention/flash_fwd
    # in profiler traces and HLO metadata (DESIGN.md §13)
    with jax.named_scope("attention"), jax.named_scope("flash_fwd"):
        out, lse = call(q, k, v, pad3, seed)
    return out, lse


# --------------------------------- backward ---------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, pad_ref, seed_ref, lse_ref, delta_ref,
               do_ref, dq_ref, *, scale, block_q, block_k, causal, window,
               S, p_drop):
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    row0 = qi * block_q
    q = q_ref[0, 0].astype(jnp.float32)            # [BQ, D]
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                            # [BQ, 1]
    delta = delta_ref[0, 0]                        # [BQ, 1]
    D = q.shape[-1]

    def step(col0, k, v, pad, dq):
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _block_mask(row0, col0, block_q, k.shape[0], causal, window,
                           pad)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)          # [BQ, BK]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if p_drop > 0.0:
            # regenerate the forward's keep mask for this tile; with
            # probs-dropout, Δ = rowsum(dO∘O) already equals
            # Σ_k p_ik·(m/keep·dp)_ik, so ds = p∘(dp∘m/keep − Δ)
            keep = _keep_mask(seed_ref[0], b, h, row0, col0, block_q,
                              k.shape[0], p_drop)
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - p_drop))
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((block_q, D), jnp.float32)
    if block_k == S:
        dq = step(0, k_ref[0, 0].astype(jnp.float32),
                  v_ref[0, 0].astype(jnp.float32), pad_ref[0], dq0)
    else:
        nK = S // block_k
        lo, hi = _kv_block_bounds(row0, block_q, block_k, nK, causal,
                                  window)

        def body(ki, dq):
            col0 = ki * block_k
            return step(
                col0,
                k_ref[0, 0, pl.ds(col0, block_k), :].astype(jnp.float32),
                v_ref[0, 0, pl.ds(col0, block_k), :].astype(jnp.float32),
                pad_ref[0, :, pl.ds(col0, block_k)], dq)
        dq = jax.lax.fori_loop(lo, hi, body, dq0)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _q_block_bounds(col0, block_q, block_k, nQ, causal, window):
    """[qlo, qhi) q-block range that can see key block at col0 — the
    transpose of _kv_block_bounds, shared by the split dK/dV kernel and
    the merged one-pass kernel so the visibility arithmetic cannot
    drift between them."""
    if causal:
        qlo = col0 // block_q
    else:
        qlo = 0
    if window is not None:
        qhi = jnp.minimum(nQ, (col0 + block_k + window - 2) // block_q + 1)
    else:
        qhi = nQ
    return qlo, qhi


def _bwd_tile(qb, dob, lseb, deltab, k, v, pad, seed, b, h, row0, col0,
              block_q, block_k, scale, causal, window, p_drop):
    """One (q-block, k-block) backward tile — the single recomputation of
    (s, p, dp, ds) both backward implementations share. Returns
    (pv, ds): pv is the dropped+rescaled probs feeding dV (pvᵀ·dO), ds
    feeds dK (dsᵀ·q) and dQ (ds·k)."""
    s = jax.lax.dot_general(qb, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _block_mask(row0, col0, block_q, block_k, causal, window, pad)
    p = jnp.where(mask, jnp.exp(s - lseb), 0.0)             # [BQ, BK]
    dp = jax.lax.dot_general(dob, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if p_drop > 0.0:
        keep = _keep_mask(seed, b, h, row0, col0, block_q, block_k,
                          p_drop)
        inv_keep = 1.0 / (1.0 - p_drop)
        pv = jnp.where(keep, p, 0.0) * inv_keep      # dropped+rescaled p̃
        dp = jnp.where(keep, dp, 0.0) * inv_keep
    else:
        pv = p
    ds = p * (dp - deltab) * scale                          # [BQ, BK]
    return pv, ds


def _dkv_kernel(q_ref, k_ref, v_ref, pad_ref, seed_ref, lse_ref, delta_ref,
                do_ref, dk_ref, dv_ref, *, scale, block_q, block_k, causal,
                window, S, G, p_drop):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    h = pl.program_id(2)
    col0 = ki * block_k
    k = k_ref[0, 0].astype(jnp.float32)            # [BK, D]
    v = v_ref[0, 0].astype(jnp.float32)
    pad = pad_ref[0]                               # [1, BK]
    D = k.shape[-1]
    # q-blocks that can see this key block (transpose of the fwd bounds)
    qlo, qhi = _q_block_bounds(col0, block_q, block_k, S // block_q,
                               causal, window)

    def body(qi, carry):
        dk, dv = carry
        row0 = qi * block_q
        qb = q_ref[0, 0, pl.ds(row0, block_q), :].astype(jnp.float32)
        dob = do_ref[0, 0, pl.ds(row0, block_q), :].astype(jnp.float32)
        pv, ds = _bwd_tile(
            qb, dob, lse_ref[0, 0, pl.ds(row0, block_q), :],
            delta_ref[0, 0, pl.ds(row0, block_q), :], k, v, pad,
            seed_ref[0], b, h, row0, col0, block_q, block_k, scale,
            causal, window, p_drop)
        dv = dv + jax.lax.dot_general(
            pv, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [BK, D]
        dk = dk + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        qlo, qhi, body, (jnp.zeros((block_k, D), jnp.float32),
                         jnp.zeros(v.shape, jnp.float32)))

    if G == 1:
        dk_ref[0, 0] = dk
        dv_ref[0, 0] = dv
    else:
        # accumulate the G q-heads of this kv-head across the CONSECUTIVE
        # innermost (sequential) head steps; first head of a group inits
        @pl.when(h % G == 0)
        def _init():
            dk_ref[0, 0] = dk
            dv_ref[0, 0] = dv

        @pl.when(h % G != 0)
        def _acc():
            dk_ref[0, 0] += dk
            dv_ref[0, 0] += dv


def _dkvq_kernel(q_ref, k_ref, v_ref, pad_ref, seed_ref, lse_ref, delta_ref,
                 do_ref, dq_ref, dk_ref, dv_ref, dq_acc, *, scale, block_q,
                 block_k, causal, window, S, p_drop):
    """Merged one-pass backward: dK, dV AND the dQ contributions of one
    [BK, D] key block from a single recomputation of (s, p, dp, ds).

    Grid (B, Hq, S/BK), key-block dim innermost and SEQUENTIAL: the dQ
    slab for (b, h) accumulates in the f32 VMEM scratch `dq_acc` across
    the consecutive key-block steps (zeroed at ki == 0, flushed to the
    output in q.dtype at the last step), so dQ is read-modify-written in
    VMEM only — never round-tripped through HBM per key block. dK/dV are
    emitted per Q-HEAD ([B, Hq, S, D] partials); the GQA group-sum
    happens in XLA outside (one fused reduction, a no-op when G == 1)
    because the per-kv-head blocks would otherwise be revisited
    non-consecutively across the outer head dim, which Pallas TPU output
    residency does not allow."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    ki = pl.program_id(2)
    col0 = ki * block_k
    k = k_ref[0, 0].astype(jnp.float32)            # [BK, D]
    v = v_ref[0, 0].astype(jnp.float32)
    pad = pad_ref[0]                               # [1, BK]
    D = k.shape[-1]
    nK = S // block_k

    @pl.when(ki == 0)
    def _zero():
        dq_acc[...] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

    qlo, qhi = _q_block_bounds(col0, block_q, block_k, S // block_q,
                               causal, window)

    def body(qi, carry):
        dk, dv = carry
        row0 = qi * block_q
        qb = q_ref[0, 0, pl.ds(row0, block_q), :].astype(jnp.float32)
        dob = do_ref[0, 0, pl.ds(row0, block_q), :].astype(jnp.float32)
        pv, ds = _bwd_tile(
            qb, dob, lse_ref[0, 0, pl.ds(row0, block_q), :],
            delta_ref[0, 0, pl.ds(row0, block_q), :], k, v, pad,
            seed_ref[0], b, h, row0, col0, block_q, block_k, scale,
            causal, window, p_drop)
        dv = dv + jax.lax.dot_general(
            pv, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [BK, D]
        dk = dk + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # the pass the split pair duplicates: dQ rows reuse THIS tile's ds
        dq_acc[pl.ds(row0, block_q), :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        qlo, qhi, body, (jnp.zeros((block_k, D), jnp.float32),
                         jnp.zeros(v.shape, jnp.float32)))
    dk_ref[0, 0] = dk
    dv_ref[0, 0] = dv

    @pl.when(ki == nK - 1)
    def _flush():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


_BWD_VMEM_BUDGET = 12 * 2 ** 20
# from LONG_S on, the backward kernels' whole-S operands outgrow the
# 16 MiB of scoped VMEM a kernel gets by default (see merged_bwd_fits)
_LONG_S = 4096
_BWD_VMEM_LONG = 48 * 2 ** 20


def _bwd_laid_out(S: int, D: int, block_k: int, itemsize: int,
                  Dv: Optional[int] = None) -> int:
    """Bytes the merged backward's operands take in VMEM as the compiler
    lays them out: widths padded to 128 lanes, an [S, 1] float32 column
    (lse, delta) one 128-lane row per query, and every pipelined operand
    twice for its two buffers. The split pair's dK/dV kernel holds the
    same whole-S q, dO, lse and delta, less the dQ slabs. D is q's and
    k's width, Dv v's and dO's (default: D)."""
    W = -(-D // 128) * 128
    Wv = W if Dv is None else -(-Dv // 128) * 128
    return (2 * S * (W + Wv) * itemsize   # q + dO slabs
            + S * W * 4                   # dq f32 scratch accumulator
            + 2 * S * W * itemsize        # dq output block
            + 2 * 2 * S * 128 * 4         # lse + delta columns
            + 2 * block_k * (W + Wv) * (itemsize + 4))  # K/V in, dK/dV out


def _bwd_vmem_limit(S: int, D: int, block_k: int, itemsize: int,
                    Dv: Optional[int] = None):
    """vmem_limit_bytes of a backward kernel that holds whole-S operands:
    None below LONG_S (the default every shape there has compiled and run
    with), else the operands as laid out plus 12 MiB for the [BQ, BK]
    float32 tile temporaries (s, p, dp, ds, the matmul results), capped
    at 96 of the chip's 128 MiB."""
    if S < _LONG_S:
        return None
    return min(_bwd_laid_out(S, D, block_k, itemsize, Dv) + 12 * 2 ** 20,
               96 * 2 ** 20)


def _kv_vmem_limit(S: int, D: int, Dv: int, itemsize: int):
    """vmem_limit_bytes of a kernel that holds a (batch, kv-head)'s
    WHOLE-S K and V (the forward and the split pair's dQ kernel): None
    while their two buffers each stay under 12 MiB as laid out — every
    shape up to S = 8192 at D = 128 in bf16, which compiled and ran with
    the default — else that much plus 12 MiB for the tile temporaries
    (S = 16,384 at 256/128 lanes: 24 MiB of K and V; S = 8192 at
    256/128 lanes is 12 MiB to the byte and the default refuses it,
    compiled for a described v5e inside the Xing4.0 train step, PR 33)."""
    lanes = (-(-D // 128) + -(-Dv // 128)) * 128
    kv = 2 * S * lanes * itemsize
    return None if kv < 12 * 2 ** 20 else kv + 12 * 2 ** 20


def merged_bwd_fits(S: int, D: int, block_k: int, itemsize: int,
                    Dv: Optional[int] = None) -> bool:
    """VMEM accounting for one merged-backward program: whole-S q/dO
    slabs + the f32 dQ accumulator + the dQ output block + lse/Δ rows
    resident for the whole (b, h) sweep, plus double-buffered K/V input
    and dK/dV output blocks.

    Below S = 4096 this is the plain count every shape so far has
    compiled and run under (S ≤ 2048, D ≤ 256): verdicts and compiler
    params unchanged. From 4096 on that count admits what the compiler
    refuses — compiled for a described v5e inside the AFMoE train step
    at S = 8192, D = 128 (PR 27), the merged kernel "ran out of memory
    in memory space vmem", and the split pair's dK/dV kernel, which
    holds the same slabs, would too: the [S, 1] columns alone are 2 x 2
    x 4 MiB as laid out. There the operands are counted as laid out
    (`_bwd_laid_out`), the backward kernels ask for that much VMEM plus
    their tile temporaries (`_bwd_vmem_limit`), and the merged kernel is
    taken while the count stays under 48 MiB — 33.5 MiB at S = 8192,
    D = 128 in bf16; 88 MiB at S = 16,384 with q/k at 256 lanes and v
    at 128 (latent attention, PR 31), which takes the split pair."""
    Dv = D if Dv is None else Dv
    if S >= _LONG_S:
        return _bwd_laid_out(S, D, block_k, itemsize, Dv) <= _BWD_VMEM_LONG
    need = (S * (D + Dv) * itemsize       # q + dO slabs
            + S * D * 4                   # dq f32 scratch accumulator
            + S * D * itemsize            # dq output block
            + 2 * S * 4                   # lse + delta rows
            + 2 * block_k * (D + Dv) * itemsize   # K/V blocks, 2 buffers
            + 2 * block_k * (D + Dv) * 4)  # dk/dv out blocks, 2 buffers
    return need <= _BWD_VMEM_BUDGET


def resolve_bwd_impl(S: int, D: int, block_k: int, itemsize: int,
                     Dv: Optional[int] = None) -> str:
    """The backward 'auto' rule, mirroring ops/attention.resolve_impl:
    the merged one-pass kernel whenever its VMEM accounting fits — it
    does for every bf16 training shape the forward dispatches today
    (S ≤ 2048 at D ≤ 256) and for f32 up to S=2048 at D=64; f32
    Gemma-shaped S=2048 D=256 slabs exceed the budget and take the split
    FlashAttention-2 pair. Kept as ONE function so the vjp and the tests
    force paths through the same gate."""
    return "merged" if merged_bwd_fits(S, D, block_k, itemsize, Dv) \
        else "split"


def _bwd_merged(scale, causal, window, block_q, block_k, p_drop, q, k, v,
                pad3, seed, lse, delta, do):
    B, Hq, S, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[3]
    G = Hq // Hkv
    kernel = functools.partial(
        _dkvq_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, S=S, p_drop=p_drop)
    call = pl.pallas_call(
        kernel,
        grid=(B, Hq, S // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i: (b, h // G, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, i: (b, h // G, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, S, 1), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, 1), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, Dv), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, S, Dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32)],
        **tpu_call_params("parallel", "parallel", "arbitrary",
                          vmem_limit_bytes=_bwd_vmem_limit(
                              S, D, block_k, q.dtype.itemsize, Dv)),
    )
    with jax.named_scope("attention"), jax.named_scope("flash_bwd_merged"):
        dq, dk_p, dv_p = call(q, k, v, pad3, seed, lse, delta, do)
    if G > 1:
        dk = dk_p.reshape(B, Hkv, G, S, D).sum(axis=2)
        dv = dv_p.reshape(B, Hkv, G, S, Dv).sum(axis=2)
    else:
        dk, dv = dk_p, dv_p
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None


def _bwd(scale, causal, window, block_q, block_k, res, g, dlse=None,
         p_drop=0.0, bwd_impl="auto"):
    q, k, v, padding_mask, seed, out, lse = res
    do = g
    B, Hq, S, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[3]
    G = Hq // Hkv
    pad3 = padding_mask.reshape(B, 1, S)
    # Δ = rowsum(dO ∘ O): one fused XLA pass, shared by every kernel.
    # A joint (out, lse) cotangent (the ring-attention partials) folds in
    # exactly here: ∂lse/∂s_ij = p_ij, so ds_ij = p_ij(dO·v_j − Δ_i +
    # dlse_i) — i.e. Δ ← Δ − dlse, with dv untouched (∂lse/∂v = 0). The
    # kernels themselves are unchanged, so the folding works identically
    # for the merged and split backward implementations.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    if bwd_impl == "auto":
        bwd_impl = resolve_bwd_impl(S, D, block_k, q.dtype.itemsize, Dv)
    if bwd_impl == "merged":
        return _bwd_merged(scale, causal, window, block_q, block_k,
                           p_drop, q, k, v, pad3, seed, lse, delta, do)
    assert bwd_impl == "split", bwd_impl

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, S=S, p_drop=p_drop)
    dq_call = pl.pallas_call(
        dq_kernel,
        grid=(B, Hq, S // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // G, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, Dv), lambda b, h, i: (b, h // G, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S), lambda b, h, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, Dv),
                         lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        **tpu_call_params("parallel", "parallel", "parallel",
                          vmem_limit_bytes=_kv_vmem_limit(
                              S, D, Dv, q.dtype.itemsize)),
    )
    with jax.named_scope("attention"), jax.named_scope("flash_bwd_dq"):
        dq = dq_call(q, k, v, pad3, seed, lse, delta, do)

    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, S=S, G=G, p_drop=p_drop)
    # head dim innermost: a kv-head's G q-heads hit the same dk/dv block on
    # consecutive steps (safe accumulate); fully parallel when G == 1
    dkv_call = pl.pallas_call(
        dkv_kernel,
        grid=(B, S // block_k, Hq),
        in_specs=[
            pl.BlockSpec((1, 1, S, D), lambda b, i, h: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, i, h: (b, h // G, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, i, h: (b, h // G, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k), lambda b, i, h: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, S, 1), lambda b, i, h: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, 1), lambda b, i, h: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, Dv), lambda b, i, h: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, i, h: (b, h // G, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, i, h: (b, h // G, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, S, Dv), jnp.float32),
        ],
        **tpu_call_params("parallel", "parallel",
                          "parallel" if G == 1 else "arbitrary",
                          vmem_limit_bytes=_bwd_vmem_limit(
                              S, D, block_k, q.dtype.itemsize, Dv)),
    )
    with jax.named_scope("attention"), jax.named_scope("flash_bwd_dkv"):
        dk, dv = dkv_call(q, k, v, pad3, seed, lse, delta, do)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None


# ------------------------------- public API ---------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, padding_mask, seed, scale, causal, window, block_q,
           block_k, p_drop, bwd_impl):
    out, _ = _fwd(q, k, v, padding_mask, seed, scale=scale, causal=causal,
                  window=window, block_q=block_q, block_k=block_k,
                  p_drop=p_drop)
    return out


def _flash_fwd(q, k, v, padding_mask, seed, scale, causal, window, block_q,
               block_k, p_drop, bwd_impl):
    out, lse = _fwd(q, k, v, padding_mask, seed, scale=scale, causal=causal,
                    window=window, block_q=block_q, block_k=block_k,
                    p_drop=p_drop)
    return out, (q, k, v, padding_mask, seed, out, lse)


def _flash_bwd(scale, causal, window, block_q, block_k, p_drop, bwd_impl,
               res, g):
    return _bwd(scale, causal, window, block_q, block_k, res, g,
                p_drop=p_drop, bwd_impl=bwd_impl)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_lse(q, k, v, padding_mask, seed, scale, causal, window, block_q,
               block_k, bwd_impl):
    """(out, lse) with gradients through BOTH outputs — the online-softmax
    partial for ring attention's cross-device merge. No dropout: partials
    compose across devices, and dropout on a renormalized merge would
    change semantics — the ring path is eval/long-context training where
    attention dropout is off."""
    return _fwd(q, k, v, padding_mask, seed, scale=scale, causal=causal,
                window=window, block_q=block_q, block_k=block_k)


def _flash_lse_fwd(q, k, v, padding_mask, seed, scale, causal, window,
                   block_q, block_k, bwd_impl):
    out, lse = _fwd(q, k, v, padding_mask, seed, scale=scale, causal=causal,
                    window=window, block_q=block_q, block_k=block_k)
    return (out, lse), (q, k, v, padding_mask, seed, out, lse)


def _flash_lse_bwd(scale, causal, window, block_q, block_k, bwd_impl, res,
                   g):
    do, dlse = g
    return _bwd(scale, causal, window, block_q, block_k, res, do,
                dlse=dlse, bwd_impl=bwd_impl)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


HEAD_SIZES = (64, 128, 256)


def kernel_eligible(S: int, D: int, Dv: Optional[int] = None) -> bool:
    """Do the kernels (at their default blocks) serve a [.., S, D]
    operand (v at [.., S, Dv], default D), or would flash_attention /
    flash_attention_partial fall back? The dispatchers that wrap them in
    shard_map ask this OUTSIDE it, where the decision must be static:
    the ring on its shard's S, ops/attention.attention on the whole S
    (it shards the batch only)."""
    return D in HEAD_SIZES and (Dv is None or Dv in HEAD_SIZES) \
        and _valid_blocks(S, 512, 512) is not None


def flash_attention_partial(q, k, v, padding_mask=None, *,
                            scale: Optional[float] = None,
                            is_causal: bool = True,
                            sliding_window: Optional[int] = None,
                            block_q: int = 512, block_k: int = 512,
                            bwd_impl: str = "auto"):
    """Partial-attention stats (out, lse) for online-softmax composition
    (parallel/ring_attention.py), or None when the shape is not
    kernel-eligible (caller falls back to its dense path).

    Unlike flash_attention, causal and sliding_window are INDEPENDENT
    here: a ring hop t attends its queries against a K/V chunk sitting
    t·S_chunk rows earlier, which is a non-causal band mask — expressed
    as is_causal=False with sliding_window = window − t·S_chunk (negative
    values shift the band above the local diagonal; the block-bounds and
    mask arithmetic handle them as-is). Differentiable w.r.t. q/k/v
    through BOTH out and lse (see _bwd's Δ−dlse folding)."""
    if bwd_impl not in ("auto", "merged", "split"):
        raise ValueError(f"bwd_impl must be 'auto', 'merged' or 'split', "
                         f"got {bwd_impl!r}")
    B, Hq, S, D = q.shape
    if D not in HEAD_SIZES or v.shape[3] not in HEAD_SIZES \
            or k.shape[2] != S:
        return None
    picked = _valid_blocks(S, block_q, block_k)
    if (pallas_util.interpret_mode() and S % block_q == 0
            and S % block_k == 0):
        picked = (block_q, block_k)
    if picked is None:
        return None
    block_q, block_k = picked
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if padding_mask is None:
        pad = jnp.ones((B, S), jnp.float32)
    else:
        pad = padding_mask.astype(jnp.float32)
    return _flash_lse(q, k, v, pad, jnp.zeros((1,), jnp.int32),
                      float(scale), bool(is_causal),
                      None if sliding_window is None
                      else int(sliding_window),
                      int(block_q), int(block_k), str(bwd_impl))


def flash_attention(q, k, v, *,
                    scale: Optional[float] = None,
                    is_causal: bool = True,
                    sliding_window: Optional[int] = None,
                    padding_mask: Optional[jnp.ndarray] = None,
                    attn_mask: Optional[jnp.ndarray] = None,
                    logits_dtype=jnp.float32,
                    attn_dropout: float = 0.0,
                    attn_dropout_rng: Optional[jnp.ndarray] = None,
                    block_q: int = 512,
                    block_k: int = 512,
                    bwd_impl: str = "auto") -> jnp.ndarray:
    """Drop-in for ops.attention.dot_product_attention (same signature).

    attn_mask (a precomputed [S, S] matrix) has no blockwise structure the
    kernel can exploit, so that case falls back to the XLA path — model code
    passes is_causal/sliding_window instead (gemma3 selects masks per layer
    by flags, not matrices, when using the flash impl).

    attn_dropout (train-mode probs dropout, HF semantics): generated
    INSIDE the kernels from a counter-based hash of (seed, b, h, row, col)
    (_keep_mask) — no [.., S, S] mask is ever materialized, and the
    backward kernels regenerate the identical mask from the same seed. The
    keep decisions come from a different (hash-based) generator than the
    XLA path's jax.random stream, so the two impls agree in DISTRIBUTION,
    not per-mask — exactly like the reference's RNG vs ours. Dropout=0 or
    rng=None compiles the dropout-free kernels (p_drop is static).

    bwd_impl selects the backward kernel implementation: 'auto' (the
    merged one-pass dK/dV+dQ kernel whenever resolve_bwd_impl's VMEM
    accounting admits it), 'merged', or 'split' (the FlashAttention-2
    two-kernel pair — the parity oracle and large-shape fallback).

    Default blocks are 512×512 (clamped to S): measured on TPU v5e,
    large blocks amortize the k-loop — every smaller block combination
    swept at S <= 512 (r4: 256x512 down to 64x128) only added
    per-program overhead. The kernel wins end-to-end from S >= 512 at
    D=64 (+20% on the GPT-2s train step) and from S >= 2048 at D=256;
    below that XLA's fused attention keeps the edge (thresholds in
    attention() 'auto' / resolve_impl).
    """
    from mobilefinetuner_tpu.ops.attention import dot_product_attention
    if bwd_impl not in ("auto", "merged", "split"):
        raise ValueError(f"bwd_impl must be 'auto', 'merged' or 'split', "
                         f"got {bwd_impl!r}")
    B, Hq, S, D = q.shape
    # sliding_window implies causal in the oracle's mask semantics
    # (attention.causal_mask is always causal when a window is given);
    # mirror that so kernel and fallback never diverge
    is_causal = is_causal or sliding_window is not None
    picked = _valid_blocks(S, block_q, block_k)
    if (pallas_util.interpret_mode() and S % block_q == 0
            and S % block_k == 0):
        # interpret mode has no Mosaic alignment constraints; honor the
        # requested blocks so tests can exercise the multi-block loop at
        # small S (the hardware path is still dispatched via _valid_blocks)
        picked = (block_q, block_k)
    if picked is not None:
        block_q, block_k = picked
    if (attn_mask is not None or picked is None
            or D not in HEAD_SIZES or v.shape[3] not in HEAD_SIZES):
        return dot_product_attention(
            q, k, v, scale=scale, is_causal=is_causal,
            sliding_window=sliding_window, padding_mask=padding_mask,
            attn_mask=attn_mask, logits_dtype=logits_dtype,
            attn_dropout=attn_dropout,
            attn_dropout_rng=attn_dropout_rng)
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if padding_mask is None:
        pad = jnp.ones((B, S), jnp.float32)
    else:
        pad = padding_mask.astype(jnp.float32)
    # graftlint: disable=sync-hazard(attn_dropout is a concrete Python config scalar at trace time, never a tracer)
    p_drop = float(attn_dropout) if attn_dropout_rng is not None else 0.0
    if p_drop > 0.0:
        seed = jax.lax.bitcast_convert_type(
            jax.random.bits(attn_dropout_rng, (1,), jnp.uint32), jnp.int32)
    else:
        seed = jnp.zeros((1,), jnp.int32)
    return _flash(q, k, v, pad, seed, float(scale), bool(is_causal),
                  None if sliding_window is None else int(sliding_window),
                  int(block_q), int(block_k), p_drop, str(bwd_impl))
