"""The limits of the comparison that decides `correct` for
`ling_lora_long` (harness/train_child_ling.py): the Ling hybrid in bf16
on the chip against reference/ling_hybrid.py in float32 at "highest",
at the published widths and the timed length (16,384).

As harness/correct_moe.py (whose measures are imported): the reference
runs UNDER THE PROGRAM'S ROUTING and logits, loss and two adapter
gradients are compared; in the same pass it scores every expert layer
for itself, and what the two routings disagree on must be near-ties, and
few. What differs here:

- the routing is group-limited, so a disagreement has two kinds: an
  expert outside the reference's top-k WITHIN a group both kept (gap =
  the reference's k-th selection score minus that expert's), and an
  expert in a group the reference did not keep (gap = the reference's
  last kept group's score minus that group's, halved: a group's score is
  a sum of two expert scores). `routing_disagreement_grouped` reports the
  share of assignments of either kind and the largest gap.
- the limits and the two readings each lies between are beside each
  constant below and, seed by seed, in PERF.md §6 (PR 31); the second
  reading is the reference itself in a lower precision against itself
  (tools/lower_precision_ling.py, through `train_child_ling.checks_of`
  and `verdict`, the tail gradient included): the delta rule's state
  carried in bfloat16, the log-decay kept in bfloat16, the router in
  bfloat16, every matrix in an 8-bit float. The state and the router in
  bfloat16 are refused by the share of routing disagreements and by
  nothing else; the log-decay in bfloat16 moves every reading by a
  thirtieth of the bf16 program's own distance, and no limit can
  separate it. benchmark/tests/test_ling_cell.py holds the chip's
  readings and runs them through these limits.
"""
from __future__ import annotations

import numpy as np

from .correct import GRAD_COS, LOSS_TOL, cosine, rel_max, verdict  # noqa: F401
from .correct_moe import rms_err  # noqa: F401

# Every limit below lies between two readings on the chip at the published
# widths and 16,384 tokens (PERF.md section 6, PR 31, has each seed): the
# bf16 program against the float32 reference over twelve seeds, and the
# reference itself in a lower precision against itself on three seeds
# (tools/lower_precision_ling.py; "state" = the delta rule's state
# carried in bf16).
#
# max |got - want| over the RMS of the reference's logits, forced routing:
# the program 0.087 to 0.107 (0.193 while the chunked rule still took bf16
# operands in its products with the state: ops/kda.py); every matrix in e4m3
# 1.07 and 1.14. State in bf16 0.096, 0.114 and 0.118, OVERLAPPING the
# program's own range: this limit cannot tell a bf16-carried state (gate
# 0.003 to 0.004, router 0.001 to 0.002). Three times Trinity's reading
# (0.03): q^ and k^ reach the rule rounded to bf16 and their rounding is
# carried by a state that forgets slowly, seven pre-norm layers deep with no
# depth scaling.
LOGIT_TOL_RMS = 0.30
# free routing: the share of the program's assignments outside the
# reference's own selection (of 1.5 M: two sequences, six expert layers,
# top-8), and the largest gap of one (an expert's score under the
# reference's k-th, or half a dropped group's score under the last kept
# group's). The share: the program 1.937 % to 1.968 % over twelve seeds; the
# state in bf16 2.548 %, 2.573 % and 2.634 %, the router in bf16 2.719 %,
# 2.737 % and 2.754 %, e4m3 18.7 % (the gate in bf16 1.94 %: no reading
# moves); the program with bf16 operands in the products with the state
# 3.50 %. The limit lies between: the one number that separates a bf16 state and
# a bf16 router (the last seed's controls ran under this limit on the chip
# and were refused by it alone). The gap: the program 0.038 to 0.049; state
# 0.041 to 0.048, router 0.039 to 0.048 (inside); e4m3 0.134 and 0.153.
ROUTE_SHARE_TOL = 0.0225
ROUTE_GAP_TOL = 0.10
# an adapter leaf's gradient over the last two layers: rel-max, and the
# cosine under harness/correct.py's shared limit (0.995). The program,
# twelve seeds for `kda_q_proj.B`: rel-max 0.031 to 0.090, cosine 0.99851 to
# 0.99875; four seeds for the latent layer's leaves BELOW its attention
# call, `mla_q_proj.B` (through the flash backward's dQ) 0.041 to 0.050 and
# 0.99874 to 0.99880, `mla_kv_b_proj.B` (through dK and dV) 0.039 to 0.051
# and 0.99884 to 0.99894. The state in bf16 0.030 to 0.045 and 0.99875 to
# 0.99942: no worse than the program's own, so the gradient cannot tell it
# either. rel-max is five times GPT-2's and Trinity's readings, for the
# reason given at the logits, so correct.py's 8e-2 (set on those) leaves no
# room here; a sign, scale or indexing fault reads a cosine under 0.9
# (tests/test_ling_hybrid.py plants three in the flash backward), and the
# chunked rule with bf16 operands in its products with the state read 0.9937
# (seed 3100000001, `correct` false).
GRAD_TOL = 0.25


def routing_disagreement_grouped(prog_idx, ref_biased, ref_group_score,
                                 topk_group: int) -> dict:
    """prog_idx [..., T, k]: the program's choices; ref_biased [..., T, E]
    the reference's selection scores (bias added, -inf outside the groups
    IT kept); ref_group_score [..., T, n_group]. Returns the share of the
    program's assignments the reference's top-k does not hold and the
    largest gap of such an assignment (0 where none disagrees)."""
    idx = np.asarray(prog_idx).reshape(-1, np.shape(prog_idx)[-1])
    sc = np.asarray(ref_biased, np.float64) \
        .reshape(-1, np.shape(ref_biased)[-1])
    gs = np.asarray(ref_group_score, np.float64) \
        .reshape(-1, np.shape(ref_group_score)[-1])
    k, per = idx.shape[1], sc.shape[1] // gs.shape[1]
    kth = np.sort(sc, axis=-1)[:, -k]                       # [T]
    chosen = np.take_along_axis(sc, idx, axis=-1)            # [T, k]
    dropped = np.isneginf(chosen)                # group not kept by the ref
    last_kept = np.sort(gs, axis=-1)[:, -topk_group]
    group_gap = (last_kept[:, None]
                 - np.take_along_axis(gs, idx // per, axis=-1)) / 2.0
    gap = np.where(dropped, group_gap, kth[:, None] - np.where(
        dropped, 0.0, chosen))
    out = dropped | (gap > 0)
    return {"share": float(out.mean()),
            "max_gap": float(gap[out].max()) if out.any() else 0.0,
            "group_share": float(dropped.mean())}
