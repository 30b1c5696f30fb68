"""The limits of the comparison that decides `correct` for a cell whose
model routes tokens to experts (harness/train_child_afmoe.py), beside
the ones it shares with harness/correct.py (loss 2e-2 nats, gradient
rel-max 8e-2 and cosine >= 0.995 of a named adapter leaf: statements
about bf16 against float32 that carry over; read here, on the chip at
the published widths over eleven seeds, PR 27: loss 1e-5 to 1.0e-4, gradient
rel-max 5e-3 to 1.4e-2, cosine 0.9998 to 0.99998).

Two comparisons, because bf16 activations flip a top-k choice where the
k-th and (k+1)-th scores lie within rounding, as they flip an argmax
over logits:

1. FORCED routing: the reference runs with the program's own choices
   (`idx` of every expert layer) and logits, loss and gradients are
   compared. LOGIT_TOL_RMS bounds max |got - want| over the RMS of the
   reference's logits. Why another measure than correct.logit_tol: that
   limit is relative to the LARGEST reference logit and was measured on
   GPT-2's logits (a tied head over LayerNorm output); this family's
   come from an untied N(0, 0.02) head over an RMSNorm, and their
   largest is the luckiest of 2 x 64 x 25,024 Gaussians (5.3 to 5.9 RMS
   over the seeds read) — a limit in RMS units does not move with it.
   The two readings it lies between (my chip runs, PR 27, published
   widths, S = 8192): the program in bf16 0.030 to 0.034 over eight
   seeds (0.052 under a first weight initialisation whose sublayers
   entered the residual stream at RMS 1); the reference itself with
   every matrix rounded to an 8-bit float (e4m3, the nearest precision
   below the one the configuration states; tools/
   lower_precision_afmoe.py) 0.291 and 0.257 on two seeds. 0.12 is 3.5 times the first and 2.4
   times under the second. At the CPU tests' tiny size they read 0.063
   and 0.98.

2. FREE routing: at every expert layer the reference, fed by the same
   routing history, also scores for itself. Every assignment the
   program made that the reference's own top-k does not hold must be a
   NEAR-TIE: the reference's biased score of the program's expert lies
   at most ROUTE_GAP_TOL under the reference's own k-th. The size of a
   flip: the router's logit is a 3,072-term sum over m (unit RMS after
   the pre-MLP norm) and W_r ~ N(0, 0.02), standard deviation 1.1; the
   program's m carries bf16's 2^-9 relative rounding per operation,
   accumulated over the layers below, so its scores (sigmoid' <= 1/4)
   sit a few 1e-3 from the reference's. Read: largest gap 2.2e-3 to
   3.3e-3 over eight seeds (6.9e-3 under the first initialisation),
   share of assignments that disagree 0.49 % to 0.62 % (0.91 %). A
   wrong router (bias added to the weights, softmax for sigmoid, top-k
   over the held experts only) puts gaps of the order of the score
   spread itself (0.2) and shares of tens of percent, or leaves the
   choice alone and fails comparison 1 through the weights
   (tests/test_afmoe.py shows each at the small size). The limits leave
   the largest reading a factor of four and five: the largest gap is the
   extreme of 260,000 assignments, and one run over a limit refuses a
   PR. They are not what refuses a lower precision — the logits are:
   the float8 reference reads a share of 4.7 % and a gap of 1.9e-2,
   under both, beside its 0.26 to 0.29 RMS on the logits.
"""
from __future__ import annotations

import numpy as np

from .correct import GRAD_COS, GRAD_TOL, LOSS_TOL, cosine, rel_max, verdict  # noqa: F401

LOGIT_TOL_RMS = 0.12
ROUTE_GAP_TOL = 0.03
ROUTE_SHARE_TOL = 0.05


def rms_err(got, want) -> float:
    """max |got - want| over the root mean square of `want`."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want))
                 / max(np.sqrt(np.mean(want * want)), 1e-30))


def routing_disagreement(prog_idx, ref_biased) -> dict:
    """prog_idx [..., T, k] the program's choices; ref_biased [..., T, E]
    the reference's biased scores under its own routing. Returns the
    share of the program's assignments the reference's top-k does not
    hold, and the largest gap of such an assignment under the
    reference's k-th score (0 where none disagrees)."""
    idx = np.asarray(prog_idx).reshape(-1, np.shape(prog_idx)[-1])
    sc = np.asarray(ref_biased, np.float64).reshape(-1,
                                                    np.shape(ref_biased)[-1])
    k = idx.shape[1]
    kth = np.sort(sc, axis=-1)[:, -k]                      # [T]
    chosen = np.take_along_axis(sc, idx, axis=-1)           # [T, k]
    gap = kth[:, None] - chosen
    out = gap > 0              # under the cut: not in the reference's top-k
    return {"share": float(out.mean()),
            "max_gap": float(gap[out].max()) if out.any() else 0.0}
