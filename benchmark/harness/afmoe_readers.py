"""Readers of the per-layer metrics of the AFMoE family's train cell
(`trinity_lora_s8192`): what the program counts about its expert layers
(`moe_assign_here`, `moe_load_max_over_mean` in every `step_stats`) and
the device time under its named scopes (`moe_router`, `moe_dispatch`,
`moe_experts`, `moe_shared`, `moe_combine`, `attn_window`, `attn_full`;
the flash kernels keep their names). Same contract as harness/readers.py:
`read(obs, trace, ctx)` returns the value, or None where the program
wrote no such counter or scope — the metric is then left out of the line.
"""
from __future__ import annotations

from . import flops_afmoe as fl, stats, xplane

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_shared",
              "moe_combine")
ATTN_SCOPES = ("attn_window", "attn_full")


def _ours(obs, ctx) -> bool:
    """A train cell of this family (every reader is tried on every cell
    by benchmark/rehearse.py)."""
    return obs["kind"] == "train_cli" \
        and ctx["config"].get("model_type") == "afmoe"


def _counter(obs, name: str):
    """Median over the window's flushes of a step_stats counter."""
    xs = [f[name] for f in obs["flushes"] if f.get(name) is not None]
    return stats.median(xs) if xs else None


def _shape(ctx):
    tr = ctx["traffic"]
    return {"remat": "--remat" in tr["argv"],
            "n_lora": fl.lora_params(ctx["config"], tr["lora"]["rank"],
                                     tr["lora"]["targets"])}


def _steps_in_trace(trace, ctx) -> float:
    """Optimizer steps the trace holds, from the flash forward's calls
    (one a layer, two under remat): a step cut by the trace's edge
    counts by the part of it that is there."""
    _, calls = xplane.op_time(trace, "flash_fwd")
    per_step = ctx["config"]["num_hidden_layers"] \
        * (2 if _shape(ctx)["remat"] else 1)
    return calls / per_step


def mfu_required_afmoe(obs, trace, ctx):
    """tokens/s x required FLOPs a token / peak, the routed experts
    counted by the MEASURED assignments to held experts; recomputed
    operations do not count. The share of the whole step's peak."""
    if not _ours(obs, ctx):
        return None
    tps = ctx["e2e"].get("train_tokens_per_s_per_chip")
    assign = _counter(obs, "moe_assign_here")
    if tps is None or assign is None:
        return None
    per_step = fl.required_train_flops(
        ctx["config"], obs["B"], obs["S"], n_lora=_shape(ctx)["n_lora"],
        assign_here=assign)
    obs.setdefault("notes", {})["mfu_required_afmoe"] = {
        "moe_assign_here": assign,
        "required_gflop_per_token": per_step / (obs["B"] * obs["S"]) / 1e9}
    return 100.0 * tps * per_step / (obs["B"] * obs["S"]) \
        / ctx["peaks"]["flops_bf16"]


def moe_share_of_step(obs, trace, ctx):
    """Device time under the moe_* scopes over the device's busy time;
    seconds a step under each inner scope go to the notes."""
    if trace is None or not _ours(obs, ctx):
        return None
    t_moe, calls = xplane.op_time(trace, "|".join(MOE_SCOPES))
    b = xplane.busy(trace)
    if not calls or not b:
        return None
    steps = _steps_in_trace(trace, ctx)
    if steps:
        obs.setdefault("notes", {})["afmoe_scope_s_per_step"] = {
            "steps_in_trace": steps,
            **{s: xplane.op_time(trace, s)[0] / steps
               for s in MOE_SCOPES + ATTN_SCOPES},
            "busy": b["busy_s"] / steps}
    return 100.0 * t_moe / b["busy_s"]


def expert_gmm_roofline(obs, trace, ctx):
    """Least time the chip could take for the grouped products' work of
    one step (FLOPs / peak vs bytes / bandwidth; from shapes and the
    measured assignments) over the device time a step of the ops under
    `moe_experts` — the same work whatever implements the product."""
    if trace is None or not _ours(obs, ctx):
        return None
    assign = _counter(obs, "moe_assign_here")
    if assign is None:
        return None
    t, calls = xplane.op_time(trace, "moe_experts")
    steps = _steps_in_trace(trace, ctx)
    if not calls or not steps:
        return None
    work = fl.gmm_work(ctx["config"], assign, remat=_shape(ctx)["remat"])
    least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                       ctx["peaks"])
    obs.setdefault("notes", {})["expert_gmm_roofline_bound"] = bound
    return 100.0 * least / (t / steps)


def flash_mixed_roofline(obs, trace, ctx, *, fwd: str, bwd: str):
    """As readers.flash_roofline_share, for head size D with GQA and the
    window's pairs on sliding layers: time a step = mean duration of a
    kernel call x calls a step (the mean runs over both kinds of layer in
    the ratio they occur)."""
    if trace is None or not _ours(obs, ctx):
        return None
    cfg, remat = ctx["config"], _shape(ctx)["remat"]
    t_f, n_f = xplane.op_time(trace, fwd)
    t_b, n_b = xplane.op_time(trace, bwd)
    if not n_f or not n_b:
        return None
    L = cfg["num_hidden_layers"]
    per_step = t_f / n_f * L * (2 if remat else 1) + t_b / n_b * L
    work = fl.flash_mixed_work(cfg, obs["B"], obs["S"], remat=remat)
    least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                       ctx["peaks"])
    obs.setdefault("notes", {})["flash_mixed_roofline_bound"] = bound
    return 100.0 * least / per_step


def moe_load_max_over_mean(obs, trace, ctx):
    """The fullest held expert's rows over the mean, worst expert layer
    (the program's counter), median over the window's flushes."""
    return _counter(obs, "moe_load_max_over_mean") if _ours(obs, ctx) \
        else None
