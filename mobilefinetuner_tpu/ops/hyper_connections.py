"""Manifold-constrained hyper-connections (mHC, arXiv 2512.24880, over
Hyper-Connections, arXiv 2409.19606): the residual path of a model whose
stream is n rows a token instead of one.

For one sublayer F with its own frozen phi [n·C, 2n + n²], b [2n + n²]
and three scalars alpha, and the stream x [n, ..., C] (the row axis
FIRST, any token axes between):

  x^ = vec(x) / rms(vec(x))        float32, over all n·C values a token
  [H~pre | H~post | H~res] = alpha (.) (x^ phi) + b
  H_pre = sigmoid(H~pre) [n];  H_post = 2 sigmoid(H~post) [n]
  H_res = Sinkhorn(clip(H~res, lo, hi)) [n, n]: M = exp(.), then `iters`
          rounds of (each row over its sum + eps, then each column over
          its sum + eps) — doubly stochastic in the limit
  u = H_pre x   [..., C]           (`pre_mix`: what the sublayer reads)
  x' = H_res x + H_post^T y        (`post_mix`: y = F(norm(u)) written
                                    back into the n rows)

Layout: every coefficient keeps the TOKEN axes minor — H_pre, H_post are
[n, ...], H_res [n, n, ...] — so the Sinkhorn rounds are element-wise
passes over [n, n, T] arrays with T on the lanes; a [T, n, n] layout
would give each token's 4 × 4 matrix a lane tile of its own. The
coefficients and both mixes' sums are float32; the stream and u are the
caller's compute dtype. The phi product runs at full float32 precision —
in one MXU pass where the stream and phi are both bfloat16 values as
they arrive (their products are exact in float32), in the multi-pass
float32 form otherwise.

Device scopes (the caller's `attention` / `mlp` outside): `mhc_coeff`
(norm, phi product, sigmoids, Sinkhorn), `mhc_pre`, `mhc_post`.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sinkhorn(logits, iters: int, eps: float, lo: float, hi: float):
    """[n, n, ...] float32 -> the same shape: exp of the clipped logits,
    then `iters` rounds of row (axis 1 summed) then column (axis 0
    summed) normalisation, `eps` added to each sum."""
    m = jnp.exp(jnp.clip(logits.astype(F32), lo, hi))
    for _ in range(iters):
        m = m / (m.sum(1, keepdims=True) + eps)
        m = m / (m.sum(0, keepdims=True) + eps)
    return m


def coefficients(x, hc: Dict[str, jax.Array], *, iters: int, eps: float,
                 clamp: Tuple[float, float], norm_eps: float):
    """x [n, ..., C] -> (H_pre [n, ...], H_post [n, ...], H_res
    [n, n, ...], row_err): float32; row_err the largest |row sum − 1| of
    H_res (a scalar: says the rounds converged)."""
    with jax.named_scope("mhc_coeff"):
        n, C = x.shape[0], x.shape[-1]
        K = 2 * n + n * n
        xf = x.astype(F32)
        ms = (xf * xf).mean((0, -1))                         # [...]
        phi = hc["phi"].reshape(n, C, K)
        if x.dtype == phi.dtype == jnp.bfloat16:
            # both operands are bf16 values already: one MXU pass gives
            # their exact products, summed in float32
            raw = jnp.einsum("j...c,jck->k...", x, phi,
                             preferred_element_type=F32)
        else:
            raw = jnp.einsum("j...c,jck->k...", xf, phi.astype(F32),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=F32)      # [K, ...]
        a = hc["alpha"].astype(F32)
        alpha = jnp.concatenate([jnp.broadcast_to(a[0], (n,)),
                                 jnp.broadcast_to(a[1], (n,)),
                                 jnp.broadcast_to(a[2], (n * n,))])
        tok = (slice(None),) + (None,) * (raw.ndim - 1)
        h = alpha[tok] * (raw * jax.lax.rsqrt(ms + norm_eps)) \
            + hc["b"].astype(F32)[tok]
        pre = jax.nn.sigmoid(h[:n])
        post = 2.0 * jax.nn.sigmoid(h[n:2 * n])
        res = sinkhorn(h[2 * n:].reshape(n, n, *raw.shape[1:]), iters, eps,
                       *clamp)
        row_err = jnp.abs(res.sum(1) - 1.0).max()
        return pre, post, res, row_err


def pre_mix(x, pre):
    """u = H_pre x: [n, ..., C], [n, ...] -> [..., C] in x's dtype."""
    with jax.named_scope("mhc_pre"):
        return (x.astype(F32) * pre[..., None]).sum(0).astype(x.dtype)


def post_mix(x, y, post, res):
    """x' = H_res x + H_post^T y: x [n, ..., C], y [..., C], post
    [n, ...], res [n, n, ...] -> [n, ..., C] in x's dtype."""
    with jax.named_scope("mhc_post"):
        xf = x.astype(F32)
        mixed = (res[..., None] * xf[None]).sum(1)           # rows i
        return (mixed + post[..., None] * y.astype(F32)[None]) \
            .astype(x.dtype)


def init(key, n: int, C: int, dtype=F32, std: float = 0.02,
         alpha: float = 0.01, res_logit: float = 8.0):
    """phi N(0, std) in `dtype`; b (float32) so that at alpha -> 0
    H_pre = 1/n (logit(1/n)), H_post = 1 (logit 0), H_res near the
    identity (`res_logit` on the diagonal, 0 off it: exp(8) = 2,981 to
    1); alpha three scalars of `alpha`. Near the identity Sinkhorn
    converges SLOWLY (the second singular value of the limit is close to
    1), so what `row_err` reads after 20 rounds is about half the
    imbalance alpha · x^ phi put in, which scales with the off-diagonal
    mass: 8e-4 at res_logit 6 and 1.5e-4 at 8 at the published widths
    (chip, PR 33)."""
    import math
    K = 2 * n + n * n
    b = jnp.concatenate([
        jnp.full((n,), -math.log(n - 1.0) if n > 1 else 10.0, F32),
        jnp.zeros((n,), F32),
        (res_logit * jnp.eye(n, dtype=F32)).reshape(-1)])
    return {"phi": (jax.random.normal(key, (n * C, K)) * std).astype(dtype),
            "b": b, "alpha": jnp.full((3,), alpha, F32)}
