"""Rotary position embeddings (RoPE), HF-Gemma/LLaMA rotate-half convention.

Reference: operators/finetune_ops/core/ops.cpp:2151 `apply_rope` and the
Gemma dual-theta selection (graph/gemma_model.cpp:579-625): global layers use
theta=1e6, sliding-window layers theta=1e4 (SURVEY.md §2.5).

Computed in fp32 for accuracy, cast back to the input dtype.

YaRN (arXiv 2309.00071, in DeepSeek-V3's form, `rope_scaling.type:
yarn`): a frequency that turns more than `beta_fast` times over the
original context keeps its value, one that turns fewer than `beta_slow`
times is divided by `factor` (positions interpolated), and between the
two a linear ramp over the frequency's INDEX blends them
(`yarn_inv_freq`); attention's softmax scale is multiplied by
`yarn_mscale(factor, mscale_all_dim)` squared by the model, and cos/sin
by the ratio of the two mscales here (1 where they are equal).
"""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """0.1 · mscale · ln(factor) + 1 (1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, scaling: dict) -> jnp.ndarray:
    """[head_dim / 2] inverse frequencies: 1 / theta^(2i/d) below the
    correction range's low index, that over `factor` above its high
    index, a linear ramp between. The range: the index at which a
    frequency turns r times over the original length L is
    d · ln(L / (2 pi r)) / (2 ln theta); low = floor at r = beta_fast,
    high = ceil at r = beta_slow, clipped to [0, d - 1]."""
    d, L = head_dim, scaling["original_max_position_embeddings"]
    at = lambda r: d * math.log(L / (r * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(at(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(at(scaling.get("beta_slow", 1))), d - 1)
    extra = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return extra / scaling["factor"] * ramp + extra * (1.0 - ramp)


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float,
                 scaling: Optional[dict] = None
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables [..., S, head_dim] for integer positions [..., S]
    ([S] shared across the batch, or [B, S] per-row, e.g. mask-derived
    positions for left-padded batches).

    HF convention: inv_freq over even dims, each frequency repeated across
    the two halves (rotate_half pairing dim i with dim i + head_dim/2).
    scaling: a `rope_scaling` dict of type yarn, or None.
    """
    m = 1.0
    if scaling is None:
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    else:
        inv_freq = yarn_inv_freq(head_dim, theta, scaling)
        m = yarn_mscale(scaling["factor"], scaling.get("mscale", 1)) \
            / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0))
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    if m != 1.0:
        return jnp.cos(emb) * m, jnp.sin(emb) * m
    return jnp.cos(emb), jnp.sin(emb)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray,
               sin: jnp.ndarray) -> jnp.ndarray:
    """x: [B, H, S, D]; cos/sin: [S, D] or [B, S, D] → same shape/dtype.

    expand_dims inserts the head axis: [S,D]->[1,S,D] (broadcast over B,H),
    [B,S,D]->[B,1,S,D] (broadcast over H)."""
    orig = x.dtype
    xf = x.astype(jnp.float32)
    c = jnp.expand_dims(cos, -3)
    s = jnp.expand_dims(sin, -3)
    out = xf * c + _rotate_half(xf) * s
    return out.astype(orig)
