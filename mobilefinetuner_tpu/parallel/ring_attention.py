"""Ring attention: exact attention over sequence-sharded activations.

The long-context scale-out lever the reference cannot have (it is
single-device; its only long-context tools are O(S)-memory streaming
softmax and Gemma's sliding window — SURVEY.md §2.11/§5). Here the
sequence axis is sharded across mesh devices and K/V chunks rotate around
the ring with `lax.ppermute` while each device keeps its Q shard and
accumulates ONLINE-softmax partial results — attention memory per device
stays O(S_local · S_local) for scores and O(S_local · D) for K/V, so
context length scales linearly with the number of devices, and each
rotation's communication can overlap the previous chunk's compute (XLA's
latency-hiding scheduler; collectives ride ICI).

Semantics match ops.attention.dot_product_attention exactly (causal,
sliding window implies causal, GQA via Hkv < Hq, key-padding mask) — the
parity and gradient tests run both on a virtual 8-device CPU mesh
(tests/test_ring_attention.py). Differentiable end to end: the ring is a
`lax.scan` over static mesh-size steps inside `shard_map`, so reverse-mode
AD runs the rotation backwards with the transposed permutation.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _chunk_attend(q, k, v, pad, row0, col0, scale, causal, window):
    """Partial attention of a local Q shard against one K/V chunk at
    global column offset col0; returns (m, l, acc) online-softmax stats.
    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D]; pad: [B, Sk]."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Sq, D).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale

    rows = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1) + col0
    mask = jnp.ones((Sq, Sk), jnp.bool_)
    if causal:
        mask = mask & (cols <= rows)
    if window is not None:
        mask = mask & (cols > rows - window)
    mask = mask[None, None, None] & (pad > 0)[:, None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)                 # [B,Hkv,G,Sq,1]
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return m, l, acc


def _merge_lse(lse1, o1, lse2, o2):
    """Online-softmax merge in (lse, out) form: logaddexp the lse's, and
    weight each partial output by exp(lse_c − lse_new). NEG_INF is a
    finite sentinel, so fully-masked partials merge to weight 0 without
    producing NaN (−inf − −inf)."""
    lse = jnp.logaddexp(lse1, lse2)
    return lse, (o1 * jnp.exp(lse1 - lse) + o2 * jnp.exp(lse2 - lse))


def _ring_hops(n: int, window, Sq: int) -> int:
    """How many rotations the ring actually needs. Causal-only: n−1 (every
    earlier chunk is visible). A sliding window w only reaches rows up to
    w−1 columns back. Hop t's NEAREST cell (local row 0 vs the chunk's
    last column) is (t−1)·Sq + 1 rows back, so hop t has visible cells
    iff (t−1)·Sq + 1 ≤ w−1, i.e. t ≤ (w−2)//Sq + 1 — chunks past that
    never travel, saving both compute AND ppermute traffic. w=1 (self
    only) needs 0 hops: floor division of the negative numerator handles
    it, and the max() guards the clamp."""
    if window is None:
        return n - 1
    return min(n - 1, max(0, (int(window) - 2) // Sq + 1))


def _ring_shard_flash(q, k, v, pad, *, axis, n, scale, window):
    """Flash-kernel ring body: per-device memory is O(Sq·D) — scores only
    ever exist blockwise in VMEM (ops/flash_attention.py), never as a
    [.., Sq, Sk] tensor in HBM. The hop loop is unrolled so each hop's
    mask is STATIC: hop t's chunk sits exactly t·Sq rows behind the local
    queries, so the diagonal hop is plain causal(+window) and hop t ≥ 1 is
    the non-causal band sliding_window = window − t·Sq (None = fully
    visible). Wrap-around chunks (from devices AHEAD of this one) are
    future tokens: computed in lockstep (SPMD — skipping wouldn't free the
    step) and merged with weight 0 via an lse of NEG_INF. Gradients flow
    through both out and lse of every partial (flash_attention_partial's
    joint custom_vjp), so reverse-mode AD of the merge tree is exact —
    and each partial's backward dispatches through the same
    resolve_bwd_impl selector as the plain kernel, so a kernel-eligible
    ring shard runs the merged one-pass dK/dV+dQ kernel per hop (half
    the backward launches per rotation; the dlse cotangent folds into Δ
    before the kernel, identically for either backward impl)."""
    from mobilefinetuner_tpu.ops.flash_attention import \
        flash_attention_partial

    # n arrives STATIC from the caller (mesh.shape[axis]): the hop loop
    # is unrolled over it, so it cannot be a traced axis_size
    idx = jax.lax.axis_index(axis)
    B, Hq, Sq, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    out, lse = flash_attention_partial(q, k, v, pad, scale=scale,
                                       is_causal=True,
                                       sliding_window=window)
    out = out.astype(jnp.float32)
    kc, vc, pc = k, v, pad
    for t in range(1, _ring_hops(n, window, Sq) + 1):
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        pc = jax.lax.ppermute(pc, axis, perm)
        weff = None if window is None else int(window) - t * Sq
        o_c, lse_c = flash_attention_partial(q, kc, vc, pc, scale=scale,
                                             is_causal=False,
                                             sliding_window=weff)
        # hop t carries the chunk of device idx−t; idx < t means it wrapped
        # around from a device ahead — causally invisible
        lse_c = jnp.where(idx >= t, lse_c, NEG_INF)
        lse, out = _merge_lse(lse, out, lse_c,
                              o_c.astype(jnp.float32))
    return out.astype(q.dtype)


def _ring_shard(q, k, v, pad, *, axis, n, scale, causal, window):
    """Runs on each device inside shard_map: local Q stays, K/V/pad
    rotate; online-softmax merge across the n (static, from mesh.shape)
    ring steps."""
    idx = jax.lax.axis_index(axis)
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    row0 = idx * Sq
    perm = [(i, (i + 1) % n) for i in range(n)]

    def merge(stats, chunk):
        m, l, acc = stats
        m_c, l_c, a_c = chunk
        m_new = jnp.maximum(m, m_c)
        a1 = jnp.exp(m - m_new)
        a2 = jnp.exp(m_c - m_new)
        return m_new, l * a1 + l_c * a2, acc * a1 + a_c * a2

    def step(carry, _):
        # rotate FIRST: the local chunk was attended before the scan, so
        # only n-1 rotations happen — no trailing ppermute whose result
        # would be thrown away
        k_cur, v_cur, pad_cur, src, m, l, acc = carry
        k_cur = jax.lax.ppermute(k_cur, axis, perm)
        v_cur = jax.lax.ppermute(v_cur, axis, perm)
        pad_cur = jax.lax.ppermute(pad_cur, axis, perm)
        src = (src - 1) % n
        chunk = _chunk_attend(q, k_cur, v_cur, pad_cur, row0, src * Sq,
                              scale, causal, window)
        m, l, acc = merge((m, l, acc), chunk)
        return (k_cur, v_cur, pad_cur, src, m, l, acc), None

    m0, l0, acc0 = _chunk_attend(q, k, v, pad, row0, idx * Sq, scale,
                                 causal, window)
    (_, _, _, _, m, l, acc), _ = jax.lax.scan(
        step, (k, v, pad, idx, m0, l0, acc0), None, length=n - 1)
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *,
                   axis: str = "fsdp",
                   batch_axis: Optional[str] = "data",
                   scale: Optional[float] = None,
                   is_causal: bool = True,
                   sliding_window: Optional[int] = None,
                   padding_mask: Optional[jnp.ndarray] = None):
    """Exact attention with the sequence axis sharded over `mesh[axis]`.

    q: [B, Hq, S, D]; k, v: [B, Hkv, S, D]; padding_mask: [B, S] (1 =
    real token). S must divide by the axis size. The batch axis shards
    over `batch_axis` when the mesh has it (each data group rings over
    its OWN batch shard — without this, every group would all-gather and
    redundantly attend over the global batch). Returns [B, Hq, S, D]
    sharded the same way. Call under jit (or eagerly); shard_map handles
    the placement.
    """
    B, Hq, S, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    is_causal = bool(is_causal) or sliding_window is not None
    if padding_mask is None:
        padding_mask = jnp.ones((B, S), jnp.float32)
    pad = padding_mask.astype(jnp.float32)

    ba = batch_axis if (batch_axis in mesh.axis_names) else None
    spec_s = P(ba, None, axis, None)     # batch + sequence sharded
    spec_p = P(ba, axis)
    window = None if sliding_window is None else int(sliding_window)
    # Flash-kernel ring body when the LOCAL shard shape is kernel-eligible
    # (per-device scores stay blockwise in VMEM, O(Sq·D) HBM); the dense
    # body is the fallback oracle for tiny/odd shapes and non-causal use.
    from mobilefinetuner_tpu.ops.flash_attention import \
        kernel_eligible
    Sq = S // mesh.shape[axis]
    if is_causal and kernel_eligible(Sq, D):
        fn = partial(_ring_shard_flash, axis=axis, n=mesh.shape[axis],
                     scale=float(scale), window=window)
    else:
        fn = partial(_ring_shard, axis=axis, n=mesh.shape[axis],
                     scale=float(scale), causal=is_causal, window=window)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(spec_s, spec_s, spec_s, spec_p),
        out_specs=spec_s,
        check_vma=False,
    )(q, k, v, pad)
