"""Mamba's selective scan (arXiv:2312.00752, S6) and the short causal
convolution in front of it.

Per channel c of d_in and state index n of N, with a float32 state
h in R^{d_in x N}, step size Delta_t in R^{d_in} (> 0), A = -exp(A_log)
in R^{d_in x N}, input-dependent B_t, C_t in R^N:

    h_t = exp(Delta_t . A) . h_{t-1} + (Delta_t . u_t) (x) B_t
    y_t = h_t C_t + D . u_t

The decay differs per channel AND per state index, so the recurrence has
no matrix-product (SSD) form: each of the d_in x N scalars runs its own
first-order linear recurrence.

`ssm_recurrent` is that, token by token under a `lax.scan`: the oracle
of the unit tests. `selective_scan` is the form that trains (from a
given state, so that a caller can run a long sequence piece by piece): a
`lax.scan` over chunks of `chunk` tokens whose body is `jax.checkpoint`ed.
Within a chunk the `chunk` steps are unrolled, the state laid out
[B, N, d_in] (d_in on the 128-lane axis, N on sublanes), the chunk's
states stacked to [B, chunk, N, d_in] and read out against C in one
reduction. The forward keeps only the state that enters each chunk
([S / chunk, B, N, d_in] float32: 84 MB a layer at S = 16,384, d_in =
5,120, chunk = 64); the backward recomputes a chunk from its entry state
and differentiates through it, so no [S, d_in, N] tensor ever exists
(5.4 GB at those sizes). Everything from Delta, B, C and the state to y
is float32 (elementwise products and sums, no matrix unit); u arrives in
the activations' dtype. A length that is not whole chunks is padded at
its end with Delta = 0 (decay 1, input 0: the state passes unchanged)
and the padding's outputs dropped.

Layout: u, Delta [B, S, d_in], B, C [B, S, N], A_log [d_in, N], D
[d_in] — as Mamba's reference code holds them, time-major inside.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.ops.kda import causal_conv

F32 = jnp.float32

__all__ = ["causal_conv", "conv_silu", "ssm_recurrent", "selective_scan"]


def conv_silu(x, w, b):
    """SiLU(causal depthwise conv(x) + b): x [B, S, C], taps w [K, C],
    bias b [C]; float32 inside, x's dtype out."""
    y = causal_conv(x, w).astype(F32) + b.astype(F32)
    return jax.nn.silu(y).astype(x.dtype)


def _decay_matrix(A_log):
    """A^T = -exp(A_log)^T [N, d_in], float32."""
    return -jnp.exp(A_log.astype(F32)).T


def ssm_recurrent(u, delta, A_log, B, C, D, h0=None):
    """The recurrence token by token: (y [B, S, d_in] float32, the last
    state [B, N, d_in] float32)."""
    Bt, S, di = u.shape
    At = _decay_matrix(A_log)
    if h0 is None:
        h0 = jnp.zeros((Bt, At.shape[0], di), F32)
    tm = lambda x: jnp.swapaxes(x.astype(F32), 0, 1)

    def step(h, xs):
        ut, dt, bt, ct = xs
        h = jnp.exp(dt[:, None, :] * At) * h \
            + (dt * ut)[:, None, :] * bt[:, :, None]
        return h, (h * ct[:, :, None]).sum(1)

    h, y = jax.lax.scan(step, h0, (tm(u), tm(delta), tm(B), tm(C)))
    return jnp.swapaxes(y, 0, 1) + u.astype(F32) * D.astype(F32), h


def selective_scan(u, delta, A_log, B, C, D, *, chunk: int = 64, h0=None):
    """The chunked scan (module docstring), from the state h0 [B, N, d_in]
    (zeros by default): (y [B, S, d_in] float32, the last state, the
    largest |h| at a chunk boundary, a float32 scalar)."""
    Bt, S, di = u.shape
    N = A_log.shape[1]
    pad = (-S) % chunk
    nc = (S + pad) // chunk
    At = _decay_matrix(A_log)

    def chunks(x):      # [B, S, ...] -> [nc, B, chunk, ...], zero-padded
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return jnp.swapaxes(x.reshape(Bt, nc, chunk, *x.shape[2:]), 0, 1)

    @jax.checkpoint
    def body(h, xs):
        uc, dc, bc, cc = xs
        uc, dc = uc.astype(F32), dc.astype(F32)
        hs = []
        for t in range(chunk):
            h = jnp.exp(dc[:, t, None, :] * At) * h \
                + (dc[:, t] * uc[:, t])[:, None, :] * bc[:, t, :, None]
            hs.append(h)
        y = (jnp.stack(hs, 1) * cc.astype(F32)[..., None]).sum(2)
        return h, (y, jnp.abs(h).max())

    if h0 is None:
        h0 = jnp.zeros((Bt, N, di), F32)
    h, (y, absmax) = jax.lax.scan(
        body, h0.astype(F32), (chunks(u), chunks(delta), chunks(B),
                               chunks(C)))
    y = jnp.swapaxes(y, 0, 1).reshape(Bt, nc * chunk, di)[:, :S]
    return y + u.astype(F32) * D.astype(F32), h, absmax.max()
