"""The readers of the per-layer metrics. A layer metric is a file
benchmark/layer_metrics/<name>.json that names its reader
("harness.readers:function", or a module a later PR adds) and the
reader's arguments; `read(obs, trace, ctx, **args)` returns the value,
or None where there is nothing to read — the harness then leaves the
metric out of the line.

obs   : the runner's observations (samples, counters, program events)
trace : the loaded profiler trace (harness/xplane.Trace) or None
ctx   : {"peaks", "config" (the configuration file), "traffic", "e2e"}
"""
from __future__ import annotations

from . import flops, stats, xplane


def _serve(obs):
    return obs["kind"] == "serve_engine"


def _train(obs):
    return obs["kind"] == "train_cli"


# --------------------------- entry points / serve loop ----------------------

def serve_sample(obs, trace, ctx, *, key: str, q: float):
    """q-th percentile of one of the serve driver's sample lists, where
    the window holds enough samples for it (stats.tail)."""
    if not _serve(obs):
        return None
    from .serve_engine import samples
    return stats.tail(samples(obs)[key], q)


def decode_step_ms(obs, trace, ctx):
    """Median host time around an `engine.step()` that admitted nothing
    (it ends in the engine's own host sync of the step's tokens)."""
    if not _serve(obs):
        return None
    xs = [(t1 - t0) * 1e3 for t0, t1, admitted, *_ in obs["steps"]
          if admitted == 0]
    return stats.median(xs) if xs else None


def slot_occupancy(obs, trace, ctx):
    if not _serve(obs) or not obs["steps"]:
        return None
    return 100.0 * sum(s[3] for s in obs["steps"]) / (
        len(obs["steps"]) * obs["num_slots"])


# --------------------------- train loop -------------------------------------

def train_step_ms(obs, trace, ctx):
    if not _train(obs) or not obs["flushes"]:
        return None
    return stats.median([f["step_time_ms"] for f in obs["flushes"]])


def train_host_wait_share(obs, trace, ctx):
    if not _train(obs) or not obs["flushes"]:
        return None
    return 100.0 * sum(f["host_wait_ms"] for f in obs["flushes"]) / sum(
        f["step_time_ms"] for f in obs["flushes"])


def train_stall_share(obs, trace, ctx):
    """What the median interval of `train_tokens_per_s_per_chip` leaves
    out: the share of the window's whole-interval time above n x the
    median interval (all tokens over all time is lower than the judged
    rate by this share). Intervals the profiler's own start or stop falls
    into are left out of both: they stall for the trace, not for the
    program."""
    if not _train(obs) or not obs["interval_s"]:
        return None
    every, prof = obs["log_interval"], obs.get("profile_steps")
    # a flush whose `step` is f covers the 0-based steps f-every .. f-1
    ds = [d for d, f in zip(obs["interval_s"], obs["flushes"])
          if not (prof and f["step"] - every <= prof[1]
                  and f["step"] - 1 >= prof[0])]
    if not ds:
        return None
    return 100.0 * (sum(ds) - len(ds) * stats.median(ds)) / sum(ds)


# --------------------------- admission --------------------------------------

def peak_hbm_gb(obs, trace, ctx):
    """train: the child's admission estimate (`mem_check.est_mb`);
    serve: memory_stats()["peak_bytes_in_use"] after the window."""
    if _train(obs):
        mb = obs["mem_check"].get("est_mb")
        return None if mb is None else mb * 2 ** 20 / 1e9
    b = obs.get("memory_peak_bytes")
    return b / 1e9 if b else None


# --------------------------- model programs ---------------------------------

def _train_shape(ctx):
    tr = ctx["traffic"]
    lora = tr.get("lora")
    n_lora = flops.lora_params(ctx["config"], lora["rank"],
                               lora["targets"]) if lora else 0
    return dict(full_ft=not lora, n_lora=n_lora)


def mfu_required(obs, trace, ctx):
    """tokens/s x required FLOPs per token / (chips x peak); recomputed
    operations do not count."""
    tps = ctx["e2e"].get("train_tokens_per_s_per_chip")
    if not _train(obs) or tps is None:
        return None
    per_step = flops.required_train_flops(ctx["config"], obs["B"], obs["S"],
                                          **_train_shape(ctx))
    per_token = per_step / (obs["B"] * obs["S"])
    return 100.0 * tps * per_token / ctx["peaks"]["flops_bf16"]


def window_compiles(obs, trace, ctx):
    if _train(obs):
        return float(obs["window_compiles"])
    return float(max(obs["window_traces"], obs["window_backend_compiles"]))


# --------------------------- kernels (trace) --------------------------------

def flash_roofline_share(obs, trace, ctx, *, fwd: str, bwd: str):
    """Least time the chip could take for the flash kernels' work of one
    optimizer step (FLOPs / peak vs bytes / bandwidth, from shapes)
    over their device time per step. Time per step = mean duration of a
    kernel call x calls per step (L forward calls, twice under remat,
    and L merged-backward calls), so a step cut by the trace's edge
    does not bias it."""
    if not _train(obs) or trace is None:
        return None
    cfg = ctx["config"]
    remat = "--remat" in ctx["traffic"]["argv"]
    t_f, n_f = xplane.op_time(trace, fwd)
    t_b, n_b = xplane.op_time(trace, bwd)
    if not n_f or not n_b:
        return None
    L = cfg["n_layer"]
    per_step = t_f / n_f * L * (2 if remat else 1) + t_b / n_b * L
    work = flops.flash_kernel_work(cfg, obs["B"] // obs["chips"], obs["S"],
                                   remat=remat)
    least, bound = flops.roofline_seconds(work["flops"], work["bytes"],
                                          ctx["peaks"])
    obs.setdefault("notes", {})["flash_roofline_bound"] = bound
    return 100.0 * least / per_step


def paged_attn_roofline_share(obs, trace, ctx, *, match: str):
    """Cache bytes the traced decode steps had to read / bandwidth, over
    the device time of the decode step's attention ops."""
    if not _serve(obs) or trace is None or not obs.get("trace_steps"):
        return None
    t_attn, calls = xplane.op_time(trace, match)
    if not calls:
        return None
    L = ctx["config"]["n_layer"]
    per_step = t_attn / calls * L
    ctx_tokens = stats.percentile([s[4] for s in obs["trace_steps"]], 50)
    need = flops.paged_attention_bytes(ctx["config"], ctx_tokens)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / per_step


# --------------------------- placement / device (trace) ---------------------

def collective_exposed_share(obs, trace, ctx):
    if trace is None:
        return None
    c = xplane.collective_exposed(trace)
    if not c or not c["collective_s"]:
        return None
    return 100.0 * c["exposed_s"] / c["window_s"]


def device_idle_share(obs, trace, ctx):
    if trace is None:
        return None
    b = xplane.busy(trace)
    return 100.0 * b["idle_share_worst"] if b else None
