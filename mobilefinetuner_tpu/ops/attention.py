"""Attention: XLA reference implementation with causal + sliding-window +
padding masks and GQA. A Pallas flash kernel (ops/flash_attention.py) is the
fast path; this module is the always-correct fallback and the numerics oracle
the kernel is tested against.

Replaces the reference's two attention paths
(operators/finetune_ops/core/memory_efficient_attention.cpp — forward-only
streaming softmax — and the per-model scalar score loops in
graph/gpt2_model.cpp:669-711 / graph/gemma_model.cpp:358-520). Unlike the
reference's memory-efficient path, this one is differentiable (SURVEY.md
§2.12.1: the reference's GPT-2 default attention severs the autograd graph;
we do NOT replicate that bug — JAX autodiff covers every path).

Everything here is jit-traceable with static shapes: masks are built with
broadcasted iotas (no data-dependent control flow).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def causal_mask(q_len: int, kv_len: int,
                sliding_window: Optional[int] = None) -> jnp.ndarray:
    """[q_len, kv_len] bool mask, True = attend.

    Causal: key j visible to query i iff j <= i (+ offset when kv_len >
    q_len, i.e. with a prefix/KV cache). Sliding window additionally
    requires j > i - window (reference: gemma_model.h:145
    `build_sliding_mask`, window default 512 = gemma_model.h:26).
    """
    offset = kv_len - q_len
    qi = jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 0) + offset
    kj = jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 1)
    mask = kj <= qi
    if sliding_window is not None:
        mask &= kj > qi - sliding_window
    return mask


def dot_product_attention(
        q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
        *,
        scale: Optional[float] = None,
        is_causal: bool = True,
        sliding_window: Optional[int] = None,
        padding_mask: Optional[jnp.ndarray] = None,
        attn_mask: Optional[jnp.ndarray] = None,
        logits_dtype=jnp.float32,
        attn_dropout: float = 0.0,
        attn_dropout_rng: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Scaled dot-product attention with GQA.

    q: [B, Hq, S, D]; k: [B, Hkv, S, D], v: [B, Hkv, S, Dv] (Dv = D but
    for latent attention) with Hq % Hkv == 0 — GQA is
    expressed by reshaping q into [B, Hkv, G, S, D] groups rather than
    materializing repeated K/V heads (the reference materializes via
    `repeat_kv_heads`, core/ops.cpp:2072; on TPU the einsum broadcast keeps
    K/V in their small layout and saves HBM).
    padding_mask: [B, S] bool/0-1, True/1 = real token.
    attn_mask: precomputed [q, k] bool mask (True = attend) used INSTEAD of
    the causal/sliding construction (e.g. Gemma's per-layer selected mask);
    combined with padding_mask if both given.
    scale: default 1/sqrt(D). (Gemma uses query_pre_attn_scalar^-0.5 —
    pass it explicitly; gemma_model.h:33.)
    """
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    qg = q.reshape(B, Hkv, G, S, D)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=logits_dtype)
    scores = scores.astype(logits_dtype) * jnp.asarray(scale, logits_dtype)

    neg = jnp.asarray(jnp.finfo(logits_dtype).min, logits_dtype)
    if attn_mask is not None:
        scores = jnp.where(attn_mask[None, None, None, :, :], scores, neg)
    elif is_causal or sliding_window is not None:
        m = causal_mask(S, S, sliding_window if sliding_window else None)
        scores = jnp.where(m[None, None, None, :, :], scores, neg)
    if padding_mask is not None:
        pm = padding_mask.astype(bool)
        scores = jnp.where(pm[:, None, None, None, :], scores, neg)

    probs = jax.nn.softmax(scores, axis=-1)
    # dropout on attention weights, HF train-mode semantics
    # (reference: core/ops.cpp:2670 applied to probs)
    from mobilefinetuner_tpu.ops.dropout import inverted_dropout
    probs = inverted_dropout(probs, attn_dropout, attn_dropout_rng)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32).astype(v.dtype)
    return out.reshape(B, Hq, S, v.shape[-1])


def resolve_impl(S: int, D: int) -> str:
    """The 'auto' dispatch rule, from TPU v5e measurements: the flash
    kernel wins from S >= 512 at small head dim (GPT-2, D=64) and from
    S >= 2048 at large head dim (Gemma GQA layout, D=256), thanks to
    causal/sliding-window block skipping. Round-4 retune, measured
    END-TO-END on the train step, on an installation that no longer
    exists (its attention microbench could not resolve ops this small):
    GPT-2s S=512 flash 119.8k vs xla 99.7k tok/s (+20%), S=256 flash
    121.3k vs xla 136.6k (-11%, XLA keeps it); Gemma-270M S=512 flash
    44.2k vs xla 47.1k (-6%, threshold stays 2048; S=1024 was
    0.92-0.98x in round 3). With train-mode attention
    dropout the gap explodes (4.6x at S=1024, 6.6x at S=2048): the XLA
    path materializes + RNGs a [B, H, S, S] probs mask while the kernel
    hashes its keep bits in-register (flash_attention.py _keep_mask).
    Shared by attention() and the model blocks that branch on the impl
    themselves (models/gemma3.py) — retune in ONE place.
    """
    return "flash" if S >= (512 if D <= 128 else 2048) else "xla"


def _flash_over_batch(mesh, axes, q, k, v, *, padding_mask=None,
                      attn_dropout_rng=None, **kwargs):
    """flash_attention under shard_map over the batch axes of `mesh`.

    A Mosaic kernel cannot be GSPMD-partitioned, but attention is
    independent per sequence and the training meshes shard activations on
    the batch axis only, so every device runs the unchanged kernels on its
    own sequences and no collective is needed. The custom VJP sits inside
    the wrapped function: reverse mode transposes the shard_map and the
    backward kernels run per shard the same way (as ring attention's flash
    body does, whose check_vma choice this follows).

    In-kernel dropout hashes (seed, b, h, row, col) with b the LOCAL batch
    index, so the shard's position on the batch axes is folded into the
    key: two shards never draw the same mask for their k-th sequence, and
    the backward — same key, same position — regenerates the forward's."""
    from jax.sharding import PartitionSpec as P
    from mobilefinetuner_tpu.ops.flash_attention import flash_attention
    B, _, S, _ = q.shape
    if padding_mask is None:
        padding_mask = jnp.ones((B, S), jnp.float32)
    heads = P(axes, None, None, None)
    operands, specs = [q, k, v, padding_mask], [heads] * 3 + [P(axes, None)]
    if attn_dropout_rng is not None:
        operands.append(attn_dropout_rng)
        specs.append(P())

    def per_shard(q, k, v, pad, *rng):
        key = (jax.random.fold_in(rng[0], jax.lax.axis_index(axes))
               if rng else None)
        return flash_attention(q, k, v, padding_mask=pad,
                               attn_dropout_rng=key, **kwargs)

    return jax.shard_map(per_shard, mesh=mesh, in_specs=tuple(specs),
                         out_specs=heads, check_vma=False)(*operands)


def _pad_qk(q, k, kwargs, head_sizes):
    """q and k at a width between the kernels' head sizes (latent
    attention's 128 + 64 rotary = 192) are zero-padded to the next one:
    exact — a zero column adds nothing to q·k — with the scale fixed at
    the TRUE width first. v keeps its own width (the kernels take two).
    What it costs: the extra columns' HBM bytes on q, k, dq, dk; no MXU
    time where the true width already fills the same 128-lane tiles
    (192 -> 256)."""
    D = q.shape[3]
    wider = [d for d in head_sizes if d > D]
    if D in head_sizes or not wider:
        return q, k, kwargs
    if kwargs.get("scale") is None:
        kwargs = dict(kwargs, scale=1.0 / (D ** 0.5))
    pad = [(0, 0)] * 3 + [(0, min(wider) - D)]
    return jnp.pad(q, pad), jnp.pad(k, pad), kwargs


def attention(q, k, v, *, impl: str = "auto", mesh=None, **kwargs):
    """Dispatch between the XLA reference and the Pallas flash kernel.

    impl='auto' picks per shape (resolve_impl); 'flash' / 'xla' force the
    respective path.

    mesh: the mesh the enclosing step is partitioned over, batch sharded
    over its ("data", "fsdp") axes (None or one device: no partitioning).
    Over more than one device the flash kernels run under shard_map on
    each device's own sequences (_flash_over_batch) — taken only where
    the kernels are eligible for (S, D), which batch sharding does not
    change, and the batch divides by the product of the batch axes
    (shard_map refuses an uneven split where GSPMD pads); otherwise the
    XLA path, which GSPMD partitions like any other op.
    """
    if not (kwargs.get("attn_dropout", 0.0) > 0.0
            and kwargs.get("attn_dropout_rng") is not None):
        kwargs.pop("attn_dropout", None)
        kwargs.pop("attn_dropout_rng", None)
    # (train-mode probs dropout is supported by BOTH impls: the flash
    # kernels generate the mask in-kernel from a counter-based hash —
    # see flash_attention.py _keep_mask — so dropout no longer forces the
    # XLA path)
    if impl == "auto":
        impl = resolve_impl(q.shape[2], q.shape[3])
    if impl == "flash":
        try:
            from mobilefinetuner_tpu.ops import flash_attention
        except ImportError as e:
            raise NotImplementedError(
                "attention_impl='flash' requires the Pallas kernel "
                "(ops/flash_attention.py); use attention_impl='xla'") from e
        q, k, kwargs = _pad_qk(q, k, kwargs, flash_attention.HEAD_SIZES)
        if mesh is None or mesh.size == 1:
            return flash_attention.flash_attention(q, k, v, **kwargs)
        # the batch axes as parallel/mesh.batch_sharding states them
        from mobilefinetuner_tpu.parallel.mesh import batch_sharding
        axes = tuple(batch_sharding(mesh).spec[0])
        n = math.prod(mesh.shape[a] for a in axes)
        if (q.shape[0] % n == 0 and kwargs.get("attn_mask") is None
                and flash_attention.kernel_eligible(*q.shape[2:],
                                                    v.shape[3])):
            return _flash_over_batch(mesh, axes, q, k, v, **kwargs)
    # backward-impl selection is a flash-kernel knob; the XLA path has
    # one backward (jax autodiff)
    kwargs.pop("bwd_impl", None)
    return dot_product_attention(q, k, v, **kwargs)
