"""The limits of the comparison that decides `correct` for
`phi4flash_lora_long` (harness/train_child_phi4flash.py): Phi-4-mini-
flash-reasoning in bf16 on the chip against reference/phi4flash.py in
float32 at "highest", at the published widths, the whole model, and the
timed length (16,384) for logits and loss; one step of the CLI's own
train step at the traffic file's `grad_seq_len` (4,096).

What is compared, and how each reading is measured (the measures are
harness/correct.py's and correct_moe.py's, imported, and `norm_gap`):

- `logits_rms`: max |got - want| over the RMS of the reference's logits
  at 64 positions spread over 16,384, of two sequences (correct_moe's
  measure: the largest of 2 x 64 x 200,064 reference logits is a lucky
  draw; a limit in RMS units does not move with it);
- `loss`: |got - want| of the mean next-token loss over all 200,064
  rows, nats;
- `grad_cos:<leaf>`: the cosine of the gradient the train step computed
  and the reference's, for four adapter leaves that lie BELOW the
  family's mechanisms (the traffic file's `correct.note`);
- `update_cos:<leaf>` and `update_norm:<leaf>`: the leaf's change in
  that step against the reference's update (Adam's first step on its
  float32 gradient at the cell's lr): their cosine, and how far the
  ratio of their norms is from 1.

Each limit lies between two readings on the chip (PERF.md section 6,
the phi4flash cell's entry, has each seed; benchmark/tests/
test_phi4flash_cell.py holds them and runs them through these limits):
the bf16 program against the float32 reference over the seeds there, and
a control or a planted fault at least three times further off
(tools/lower_precision_phi4flash.py, through
`train_child_phi4flash.checks_of` and `verdict`): the reference with
the scan's state and decay rounded to bfloat16 every step (`state`),
the reference with the cross-decoder's reads of the memory and the
shared keys and values detached from the gradient (`detached`), and the
program's own step with its update dropped (`no_update`). A leaf's
gradient rel-max is printed beside the verdict and not compared: no
reading of it lies three times from the program's (PERF.md).
"""
from __future__ import annotations

import numpy as np

from .correct import cosine, rel_max, verdict  # noqa: F401
from .correct_moe import rms_err  # noqa: F401

# Readings: TPU v5 lite (v5e), the program on seeds 4000000021 to
# ...24, ...31, ...41, ...42 (the update readings on ...41 and ...42 only:
# the first five seeds ran before the check read a train step).

# max |got - want| over the RMS of the reference's logits. The program
# 0.285 to 0.346 (32 layers of bf16 rounding, nine scans among them,
# into a 200,064-row tied head); the scan's state and decay in bfloat16
# 3.147 and 3.196 (a slow channel's decay exp(-Delta A) near 0.999 rounds
# to 1, and its state never decays over 16,384 tokens). 1.7 times the
# program's largest, 5.2 times under the control's smaller.
LOGIT_TOL_RMS = 0.6
# the mean loss, nats: the program 4.1e-5 to 6.7e-4 (the mean over
# 32,766 tokens of each token's bf16 error, near ln(200,064) = 12.2, of
# either sign); the scan's state and decay in bfloat16 2.57e-3 on ...21
# and 5.8e-4 on ...41, where the logits refuse it. 2.2 times the
# program's largest, 1.7 times under the control's larger.
LOSS_TOL = 1.5e-3
# an adapter leaf's gradient, cosine. The program 0.99163 to 0.99738
# (k_proj.B@8 the smallest on every seed: 0.99528 to 0.99544, and 0.99163
# on ...42; the same step in float32 reads 1 - cos under 5e-7 on ...41
# and 5.3e-10 on ...42, so the gap is bf16 rounding, summed over the
# eight layers that read layer 17's keys); the cross-decoder's reads
# detached (`detached`) 0.437 to 0.628 on the three leaves below them.
# 1 - cos: the program's worst 0.0084, the limit 0.05, the fault 0.37 and
# more. The scan's state in bfloat16 reads 0.9913 to 0.9944, which
# passes: the logits refuse it.
GRAD_COS = 0.95
# an adapter leaf's change in one Adam step, cosine against the
# reference's. Adam's first step is -lr g / (|g| + eps), close to -lr
# sign(g), so the cosine is one less twice the share of elements whose
# gradient's sign the bf16 rounding flips: the program 0.937 to 0.954
# (float32: 0.99999); a step that moves nothing reads 0, the detached
# reads 0.27 to 0.63. 1 - cos: the program's worst 0.063, the limit 0.25,
# no update 1.
UPDATE_COS = 0.75
# | |program's change| / |reference's update| - 1 |: Adam's first step
# moves each element by lr but where |g| is near eps, so both norms are
# about lr sqrt(n): the program at most 0.0012 (float32 0.0008: the
# step's clip by 0.65 puts a few more elements near eps; the reference's
# update is unclipped); a step that moves nothing reads 1.
UPDATE_NORM = 0.1


def norm_gap(got, want) -> float:
    """| |got| / |want| - 1 |; inf where `got` is not finite."""
    g = np.asarray(got, np.float64)
    if not np.isfinite(g).all():
        return float("inf")
    return abs(float(np.linalg.norm(g))
               / max(float(np.linalg.norm(np.asarray(want, np.float64))),
                     1e-30) - 1.0)
