"""The comparison that decides `correct`, and its tolerances.

Random weights put many logits within rounding of each other, so the
comparison is on logits and never on sampled tokens. The measure is the
largest absolute difference over the largest absolute reference value
(`rel_max`), which does not blow up on logits near zero.

logit_tol(n_layer) = 6e-3 x sqrt(n_layer). The program computes in bf16
(8 bits of mantissa, relative rounding 2^-9 = 2e-3 per operation) and
accumulates in f32; the reference is f32 at "highest" precision. The
error random-walks through the blocks, so it grows with the square root
of the depth, and the measure is a maximum over millions of logits.
Measured on the chip, bf16 against the reference (PR 22): 1.2e-2 at
gpt2-medium's 24 layers (limit 2.9e-2), 1.7e-2 to 2.1e-2 over four seeds
at gpt2-xl's 48 layers (limit 4.2e-2); PR 21 measured 1.08e-2 at GPT-2
small. About twice what bf16 gives, at every depth. It is tight enough
that a lower precision fails: fp8 (e4m3, 3 bits of mantissa instead of
7) rounds sixteen times as coarsely and lands five times over the limit
already at two layers (4.6e-2 against 8.5e-3, benchmark/tests), and a
dropped term (a missing adapter, bias or position table) moves logits
by the order of the logits themselves.

LOSS_TOL = 2e-2 (absolute, nats): the mean of ~1,000 per-token errors
of the size above, near ln(50257) = 10.8; bf16 gives a few 1e-3.

GRAD_TOL = 8e-2 on rel_max of a named leaf's gradient: the backward
pass doubles the depth the rounding crosses, and the adapters' gradients
are sums over all tokens of products of two bf16-rounded factors; bf16
measured here gives 1e-2 to 3e-2. The cosine of the two gradients must
also exceed 0.995, which a sign or scale fault cannot meet.
"""
from __future__ import annotations

import numpy as np

LOGIT_TOL_PER_SQRT_LAYER = 6e-3
LOSS_TOL = 2e-2
GRAD_TOL = 8e-2
GRAD_COS = 0.995


def logit_tol(n_layer: int) -> float:
    return LOGIT_TOL_PER_SQRT_LAYER * float(n_layer) ** 0.5


def rel_max(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want))
                 / max(np.max(np.abs(want)), 1e-30))


def cosine(got, want) -> float:
    g = np.asarray(got, np.float64).ravel()
    w = np.asarray(want, np.float64).ravel()
    return float(g @ w / max(np.linalg.norm(g) * np.linalg.norm(w), 1e-30))


def verdict(checks: dict) -> dict:
    """checks: name -> (value, limit, 'max'|'min'). Returns
    {"ok": bool, "checks": {name: {"value","limit","ok"}}}."""
    out, ok = {}, True
    for name, (value, limit, sense) in checks.items():
        good = bool(np.isfinite(value)) and (
            value <= limit if sense == "max" else value >= limit)
        out[name] = {"value": float(value), "limit": limit, "ok": good}
        ok &= good
    return {"ok": ok, "checks": out}
