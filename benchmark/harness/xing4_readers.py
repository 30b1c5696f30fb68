"""Readers of the per-layer metrics of Xing4.0's train cell
(`xing_lora_long`): the device time under the family's named scopes
(`mhc_coeff`, `mhc_pre`, `mhc_post`, `mla_proj`, `attn_latent`,
`mla_out`, `mtp`, and the `moe_*` scopes it shares with the other expert
families) and the counters of its `step_stats`. Same contract as
harness/readers.py: `read(obs, trace, ctx)` returns the value, or None
where the program wrote no such counter or scope — as a program without
the family does.

The other families' metrics of the shared layers answer only for their
own `model_type`; the readings at this family's shapes are metrics of
their own here (`*_xing4`).
"""
from __future__ import annotations

from . import flops_xing4 as fl, stats, xplane

MHC_SCOPES = ("mhc_coeff", "mhc_pre", "mhc_post")
MLA_SCOPES = ("mla_proj", "attn_latent", "mla_out")
MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_shared",
              "moe_combine")
# the module's scope as a path element ("mtp" alone would match any name
# that holds the letters)
MTP_RE = r"[/(]mtp[/)]"
COUNTERS = ("moe_assign_here", "moe_load_max_over_mean", "moe_dropped",
            "hc_res_row_err", "loss_main", "loss_mtp")


def _ours(obs, ctx) -> bool:
    return obs["kind"] == "train_cli" \
        and ctx["config"].get("model_type") == "xing4_0"


def _counter(obs, name: str):
    xs = [f[name] for f in obs["flushes"] if f.get(name) is not None]
    return stats.median(xs) if xs else None


def _shape(ctx):
    tr = ctx["traffic"]
    return {"remat": "--remat" in tr["argv"],
            "n_lora": fl.lora_params(ctx["config"], tr["lora"]["rank"],
                                     tr["lora"]["targets"])}


def _steps_in_trace(trace, ctx) -> float:
    """Optimizer steps the trace holds, from the flash forward's calls
    (one a block, two under remat)."""
    _, calls = xplane.op_time(trace, "flash_fwd")
    per_step = fl.block_counts(ctx["config"])["blocks"] \
        * (2 if _shape(ctx)["remat"] else 1)
    return calls / per_step if per_step else 0.0


def mfu_required_xing4(obs, trace, ctx):
    """tokens/s x required FLOPs a token / peak, the routed experts by
    the MEASURED assignments, the head twice; recomputed operations do
    not count. The share of the whole step's peak."""
    if not _ours(obs, ctx):
        return None
    tps = ctx["e2e"].get("train_tokens_per_s_per_chip")
    assign = _counter(obs, "moe_assign_here")
    if tps is None or assign is None:
        return None
    per_step = fl.required_train_flops(
        ctx["config"], obs["B"], obs["S"], n_lora=_shape(ctx)["n_lora"],
        assign_here=assign)
    notes = obs.setdefault("notes", {})
    notes["mfu_required_xing4"] = {
        "moe_assign_here": assign,
        "required_gflop_per_token": per_step / (obs["B"] * obs["S"]) / 1e9}
    notes["xing4_counters"] = {c: _counter(obs, c) for c in COUNTERS}
    return 100.0 * tps * per_step / (obs["B"] * obs["S"]) \
        / ctx["peaks"]["flops_bf16"]


def mhc_share_of_step(obs, trace, ctx):
    """Device time under the mhc_* scopes over the device's busy time;
    seconds a step under every inner scope of the family go to the
    notes."""
    if trace is None or not _ours(obs, ctx):
        return None
    t_mhc, calls = xplane.op_time(trace, "|".join(MHC_SCOPES))
    b = xplane.busy(trace)
    if not calls or not b:
        return None
    steps = _steps_in_trace(trace, ctx)
    if steps:
        obs.setdefault("notes", {})["xing4_scope_s_per_step"] = {
            "steps_in_trace": steps,
            **{s: xplane.op_time(trace, s)[0] / steps
               for s in MHC_SCOPES + MLA_SCOPES + MOE_SCOPES},
            "mtp": xplane.op_time(trace, MTP_RE)[0] / steps,
            "loss": xplane.op_time(trace, r"[/(]loss[/)]")[0] / steps,
            "busy": b["busy_s"] / steps}
    return 100.0 * t_mhc / b["busy_s"]


def mhc_mix_roofline(obs, trace, ctx):
    """Least time the chip could take to move the bytes the stream's
    mixing must move in one step (flops_xing4.mhc_mix_work, from shapes
    alone) over the device time a step under `mhc_coeff` + `mhc_pre` +
    `mhc_post`."""
    if trace is None or not _ours(obs, ctx):
        return None
    t, calls = xplane.op_time(trace, "|".join(MHC_SCOPES))
    steps = _steps_in_trace(trace, ctx)
    if not calls or not steps:
        return None
    work = fl.mhc_mix_work(ctx["config"], obs["B"], obs["S"])
    least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                       ctx["peaks"])
    obs.setdefault("notes", {})["mhc_mix_roofline_bound"] = bound
    return 100.0 * least / (t / steps)


def mtp_share_of_step(obs, trace, ctx):
    """Device time under `mtp` (the module's merge, block, norm and its
    loss chunks) over the device's busy time."""
    if trace is None or not _ours(obs, ctx):
        return None
    t, calls = xplane.op_time(trace, MTP_RE)
    b = xplane.busy(trace)
    if not calls or not b:
        return None
    return 100.0 * t / b["busy_s"]


def flash_latent_roofline_xing4(obs, trace, ctx, *, fwd: str, bwd: str):
    """As ling_readers.flash_latent_roofline, at this cell's shapes and
    calls (a latent layer in every block): time a step = mean duration
    of a forward call x calls a step + the backward kernels' time a
    step."""
    if trace is None or not _ours(obs, ctx):
        return None
    cfg, remat = ctx["config"], _shape(ctx)["remat"]
    t_f, n_f = xplane.op_time(trace, fwd)
    t_b, n_b = xplane.op_time(trace, bwd)
    steps = _steps_in_trace(trace, ctx)
    if not n_f or not n_b or not steps:
        return None
    L = fl.block_counts(cfg)["blocks"]
    per_step = t_f / n_f * L * (2 if remat else 1) + t_b / steps
    work = fl.flash_latent_work(cfg, obs["B"], obs["S"], remat=remat)
    least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                       ctx["peaks"])
    obs.setdefault("notes", {})["flash_latent_roofline_xing4_bound"] = bound
    return 100.0 * least / per_step


def expert_gmm_roofline_xing4(obs, trace, ctx):
    """As afmoe_readers.expert_gmm_roofline, at K 3584 / N 1024 over 64
    held experts: least time for the grouped products' work of one step
    over the device time a step under `moe_experts`."""
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
    obs.setdefault("notes", {})["expert_gmm_roofline_xing4_bound"] = bound
    return 100.0 * least / (t / steps)


def moe_load_max_over_mean_xing4(obs, trace, ctx):
    """The fullest expert's rows over the mean, worst expert block (the
    program's counter), median over the window's flushes."""
    return _counter(obs, "moe_load_max_over_mean") if _ours(obs, ctx) \
        else None
