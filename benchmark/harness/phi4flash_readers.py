"""Readers of the per-layer metrics of Phi-4-mini-flash-reasoning's train
cell (`phi4flash_lora_long`): the device time under the family's named
scopes (`ssm_proj`, `ssm_conv`, `ssm_scan`, `gmu`, `diff_attn`,
`diff_attn_proj`: models/phi4flash.py) and the counters of its
`step_stats` (`ssm_state_absmax`). Same contract as harness/readers.py:
`read(obs, trace, ctx)` returns the value, or None where the program
wrote no such counter or scope (a checkout without the family).

The device-trace readers count a step as one execution of the train
step's program that the trace holds whole (`_step_windows`), and only
the ops inside such executions: the profiler's window opens and closes
inside steps, and a cut step holds a part of the step that is not its
share of any scope.
"""
from __future__ import annotations

import bisect
import collections
import re

from . import flops_phi4flash as fl, stats, xplane

SSM_SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan")
SCOPES = SSM_SCOPES + ("gmu", "diff_attn_proj", "diff_attn", "mlp",
                       "loss", "embed")


def _ours(obs, ctx) -> bool:
    return obs["kind"] == "train_cli" \
        and ctx["config"].get("model_type") == "phi4flash"


def _counter(obs, name: str):
    xs = [f[name] for f in obs["flushes"] if f.get(name) is not None]
    return stats.median(xs) if xs else None


def _remat(ctx) -> bool:
    return "--remat" in ctx["traffic"]["argv"]


def _step_windows(plane) -> list:
    """[(start, end)] of the train step's executions on a device that the
    trace holds whole: the program ("XLA Modules") with the most device
    time, its executions at least 0.9 of its longest — one that the
    profiler's start or stop cut is shorter, or missing."""
    runs = collections.defaultdict(list)
    for line in plane.lines:
        if line.name == xplane.MODULES_LINE:
            for e in line.events:
                if e.dur > 0:
                    runs[re.sub(r"\(.*", "", e.name)].append(e)
    if not runs:
        return []
    step = max(runs.values(), key=lambda es: sum(e.dur for e in es))
    longest = max(e.dur for e in step)
    return sorted((e.start, e.start + e.dur) for e in step
                  if e.dur >= 0.9 * longest)


def _per_step(trace, pattern: str = None):
    """(device seconds a step of the ops matching `pattern` — of all ops'
    busy time where None — inside the whole steps, whole steps), averaged
    over the chips; (None, 0) where the trace holds no whole step."""
    rx = re.compile(pattern) if pattern else None
    secs, steps = [], []
    for p in xplane.device_planes(trace):
        wins = _step_windows(p)
        if not wins:
            continue
        starts = [w[0] for w in wins]

        def inside(e):
            k = bisect.bisect_right(starts, e.start) - 1
            return k >= 0 and e.start < wins[k][1]

        evs = [e for e in xplane.op_events(p, leaves_only=rx is not None)
               if e.dur > 0 and inside(e)]
        if rx is None:
            ns = xplane.total(xplane.union(
                [(e.start, e.start + e.dur) for e in evs]))
        else:
            ns = sum(e.dur for e in evs
                     if rx.search(xplane.op_name(e)) or rx.search(e.text))
        secs.append(ns / 1e9 / len(wins))
        steps.append(len(wins))
    if not secs:
        return None, 0
    return sum(secs) / len(secs), min(steps)


def mfu_required_phi4flash(obs, trace, ctx):
    """tokens/s x required FLOPs a token / peak; recomputed operations do
    not count. The share of the whole step's peak."""
    if not _ours(obs, ctx):
        return None
    tps = ctx["e2e"].get("train_tokens_per_s_per_chip")
    if tps is None:
        return None
    tr = ctx["traffic"]
    per_step = fl.required_train_flops(
        ctx["config"], obs["B"], obs["S"],
        n_lora=fl.lora_params(ctx["config"], tr["lora"]["rank"],
                              tr["lora"]["targets"]))
    notes = obs.setdefault("notes", {})
    notes["mfu_required_phi4flash"] = {
        "required_gflop_per_token": per_step / (obs["B"] * obs["S"]) / 1e9}
    notes["ssm_state_absmax"] = _counter(obs, "ssm_state_absmax")
    return 100.0 * tps * per_step / (obs["B"] * obs["S"]) \
        / ctx["peaks"]["flops_bf16"]


def ssm_share_of_step(obs, trace, ctx):
    """Device time under the ssm_* scopes over the device's busy time,
    both inside the steps the trace holds whole; seconds a step under
    every scope of the family go to the notes."""
    if trace is None or not _ours(obs, ctx):
        return None
    t_ssm, steps = _per_step(trace, "|".join(SSM_SCOPES))
    busy, _ = _per_step(trace)
    if not t_ssm or not busy:
        return None
    obs.setdefault("notes", {})["phi4flash_scope_s_per_step"] = {
        "whole_steps_in_trace": steps,
        **{s: _per_step(trace, rf"\b{s}\b")[0] for s in SCOPES},
        "busy": busy}
    return 100.0 * t_ssm / busy


def ssm_scan_roofline(obs, trace, ctx):
    """Least time the chip could take for the selective scan's work of
    one step (FLOPs / peak vs bytes / bandwidth, from shapes: ONE forward
    and ONE backward, harness/flops_phi4flash.ssm_scan_work) over the
    device time a whole step of the ops under `ssm_scan` — whatever
    implements the scan and whatever it recomputes."""
    if trace is None or not _ours(obs, ctx):
        return None
    t, _ = _per_step(trace, "ssm_scan")
    if not t:
        return None
    work = fl.ssm_scan_work(ctx["config"], obs["B"], obs["S"])
    least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                       ctx["peaks"])
    obs.setdefault("notes", {})["ssm_scan_roofline_bound"] = bound
    return 100.0 * least / t


def flash_diff_roofline(obs, trace, ctx, *, fwd: str, bwd: str):
    """The flash kernels of differential attention (two maps a layer,
    causal over S or the window's pairs) against their FLOPs and bytes:
    time a step = the kernels' time in the steps the trace holds whole,
    forward (`fwd`) and every kernel the backward is made of (`bwd`, a
    regex: the merged kernel or the split pair)."""
    if trace is None or not _ours(obs, ctx):
        return None
    t_f, _ = _per_step(trace, fwd)
    t_b, _ = _per_step(trace, bwd)
    if not t_f or not t_b:
        return None
    work = fl.flash_diff_work(ctx["config"], obs["B"], obs["S"],
                              remat=_remat(ctx))
    least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                       ctx["peaks"])
    obs.setdefault("notes", {})["flash_diff_roofline_bound"] = bound
    return 100.0 * least / (t_f + t_b)
