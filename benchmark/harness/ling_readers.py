"""Readers of the per-layer metrics of the Ling hybrid's train cell
(`ling_lora_long`): the device time under the family's named scopes
(`kda_proj_conv`, `kda_gate`, `kda_scan`, `kda_out`, `mla_proj`,
`attn_latent`, `mla_out`, and the `moe_*` scopes it shares with the
AFMoE family) and the counters of its `step_stats`. Same contract as
harness/readers.py: `read(obs, trace, ctx)` returns the value, or None
where the program wrote no such counter or scope.

The AFMoE family's metrics of the shared expert layer
(`moe_share_of_step`, `expert_gmm_roofline`, `moe_load_max_over_mean`)
answer only for `model_type: afmoe` and are not listed for this cell; the
same readings at this family's widths go to a traced run's `notes`
(`ling_scope_s_per_step`, `ling_expert_gmm`, `ling_moe_counters`).
"""
from __future__ import annotations

from . import flops_ling as fl, stats, xplane

KDA_SCOPES = ("kda_proj_conv", "kda_gate", "kda_scan", "kda_out")
MLA_SCOPES = ("mla_proj", "attn_latent", "mla_out")
MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_shared",
              "moe_combine")
COUNTERS = ("moe_assign_here", "moe_load_max_over_mean", "moe_dropped",
            "moe_groups_here")


def _ours(obs, ctx) -> bool:
    return obs["kind"] == "train_cli" \
        and ctx["config"].get("model_type") == "ling_hybrid"


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
    (one a latent layer, two under remat)."""
    _, calls = xplane.op_time(trace, "flash_fwd")
    per_step = fl.layer_counts(ctx["config"])["mla"] \
        * (2 if _shape(ctx)["remat"] else 1)
    return calls / per_step if per_step else 0.0


def mfu_required_ling(obs, trace, ctx):
    """tokens/s x required FLOPs a token / peak, the routed experts by
    the MEASURED assignments; recomputed operations do not count. The
    share of the whole step's peak."""
    if not _ours(obs, ctx):
        return None
    tps = ctx["e2e"].get("train_tokens_per_s_per_chip")
    assign = _counter(obs, "moe_assign_here")
    if tps is None or assign is None:
        return None
    per_step = fl.required_train_flops(
        ctx["config"], obs["B"], obs["S"], n_lora=_shape(ctx)["n_lora"],
        assign_here=assign)
    obs.setdefault("notes", {})["mfu_required_ling"] = {
        "moe_assign_here": assign,
        "required_gflop_per_token": per_step / (obs["B"] * obs["S"]) / 1e9}
    counters = {c: _counter(obs, c) for c in COUNTERS}
    # the count of (token, expert layer) pairs whose kept groups include
    # one held here, as a share of all such pairs of a step
    here = counters["moe_groups_here"]
    counters["moe_groups_here_share"] = None if here is None else here / (
        obs["B"] * obs["S"] * fl.layer_counts(ctx["config"])["moe"])
    obs["notes"]["ling_moe_counters"] = counters
    return 100.0 * tps * per_step / (obs["B"] * obs["S"]) \
        / ctx["peaks"]["flops_bf16"]


def kda_share_of_step(obs, trace, ctx):
    """Device time under the kda_* scopes over the device's busy time;
    seconds a step under every inner scope of the family, and the
    grouped products' roofline share, go to the notes."""
    if trace is None or not _ours(obs, ctx):
        return None
    t_kda, calls = xplane.op_time(trace, "|".join(KDA_SCOPES))
    b = xplane.busy(trace)
    if not calls or not b:
        return None
    steps = _steps_in_trace(trace, ctx)
    notes = obs.setdefault("notes", {})
    if steps:
        notes["ling_scope_s_per_step"] = {
            "steps_in_trace": steps,
            **{s: xplane.op_time(trace, s)[0] / steps
               for s in KDA_SCOPES + MLA_SCOPES + MOE_SCOPES},
            "busy": b["busy_s"] / steps}
        assign = _counter(obs, "moe_assign_here")
        t_gmm = xplane.op_time(trace, "moe_experts")[0] / steps
        if assign is not None and t_gmm > 0:
            work = fl.gmm_work(ctx["config"], assign,
                               remat=_shape(ctx)["remat"])
            least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                               ctx["peaks"])
            notes["ling_expert_gmm"] = {
                "roofline_share_pct": 100.0 * least / t_gmm, "bound": bound,
                "seconds_per_step": t_gmm}
    return 100.0 * t_kda / b["busy_s"]


def kda_scan_roofline(obs, trace, ctx):
    """Least time the chip could take for the chunked delta rule's work
    of one step (FLOPs / peak vs bytes / bandwidth, from shapes) over the
    device time a step of the ops under `kda_scan` — forward and backward
    of the rule, whatever implements it and whatever it recomputes (the
    work counts one forward and the backward at twice that)."""
    if trace is None or not _ours(obs, ctx):
        return None
    t, calls = xplane.op_time(trace, "kda_scan")
    steps = _steps_in_trace(trace, ctx)
    if not calls or not steps:
        return None
    work = fl.kda_scan_work(ctx["config"], obs["B"], obs["S"])
    least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                       ctx["peaks"])
    obs.setdefault("notes", {})["kda_scan_roofline_bound"] = bound
    return 100.0 * least / (t / steps)


def flash_latent_roofline(obs, trace, ctx, *, fwd: str, bwd: str):
    """As afmoe_readers.flash_mixed_roofline, for latent attention: q.k
    over nope + rope, v over v_head_dim, causal pairs, the padding of q
    and k not counted as work. Time a step = mean duration of a kernel
    call x calls a step, over every kernel the backward is made of
    (`bwd` is a regex: the merged kernel, or the split pair)."""
    if trace is None or not _ours(obs, ctx):
        return None
    cfg, remat = ctx["config"], _shape(ctx)["remat"]
    t_f, n_f = xplane.op_time(trace, fwd)
    t_b, n_b = xplane.op_time(trace, bwd)
    steps = _steps_in_trace(trace, ctx)
    if not n_f or not n_b or not steps:
        return None
    L = fl.layer_counts(cfg)["mla"]
    per_step = t_f / n_f * L * (2 if remat else 1) + t_b / steps
    work = fl.flash_latent_work(cfg, obs["B"], obs["S"], remat=remat)
    least, bound = fl.roofline_seconds(work["flops"], work["bytes"],
                                       ctx["peaks"])
    obs.setdefault("notes", {})["flash_latent_roofline_bound"] = bound
    return 100.0 * least / per_step
