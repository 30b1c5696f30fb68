"""Runner `serve_engine`: a `ServeEngine` built the way
tools/serve_bench.build_engine builds it, driven in-process through
`submit` / `step` by the benchmark's own open-loop driver.

What is the program's: the engine, its compiled programs, its request
objects. What is the benchmark's: the schedule, every clock reading, the
token and request accounting, the comparison with the reference.
Latencies are timed from the instant a request was DUE, not from
`Request.enqueue_t` (the time of `submit`): a stalled loop delays the
submit too, and would hide the wait it causes.
"""
from __future__ import annotations

import glob
import os
import time

import numpy as np

from . import common, correct, stats, traffic as traffic_mod
from .common import BenchError, say

LORA_TARGETS = ("attn_qkv", "attn_proj", "mlp_fc_in", "mlp_fc_out")
TRACE_SECONDS = 3.0         # the traced span of a --trace 1 run
MIN_WARM_DECODE_STEPS = 3


# --------------------------- build ------------------------------------------

def model_config(cfgfile: dict):
    from mobilefinetuner_tpu.core.config import GPT2Config
    return GPT2Config(
        vocab_size=cfgfile["vocab_size"], n_positions=cfgfile["n_positions"],
        n_embd=cfgfile["n_embd"], n_layer=cfgfile["n_layer"],
        n_head=cfgfile["n_head"],
        layer_norm_epsilon=cfgfile["layer_norm_epsilon"])


def seeded_adapter(config, ad: dict, key):
    """One adapter of the shape the file states ({"rank", "alpha",
    "targets"}) with BOTH factors N(0, 0.02) — a fresh adapter's B is
    zero, which would make every tenant the base model and a comparison
    blind to A. Traceable: call it under jit."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.lora.lora import LoRASpec, init_lora_gpt2
    spec = LoRASpec(rank=ad["rank"], alpha=float(ad["alpha"]),
                    targets=list(ad.get("targets", LORA_TARGETS)))
    lora = init_lora_gpt2(config, spec, key)
    leaves, td = jax.tree.flatten(lora)
    keys = jax.random.split(jax.random.fold_in(key, 1000), len(leaves))
    return jax.tree.unflatten(td, [
        l if l.ndim == 0
        else 0.02 * jax.random.normal(k, l.shape, jnp.float32)
        for l, k in zip(leaves, keys)])


def make_weights(config, seed: int, ad: dict):
    """Base weights and the resident adapters, on the device, in ONE
    jitted call from the seed. The base is float32 because that is what
    `build_engine` hands the engine today (the engine casts to its
    compute dtype inside every step; PERF.md section 5)."""
    import jax
    from mobilefinetuner_tpu.models import gpt2

    def draw(key):
        kp, ka = jax.random.split(key)
        return gpt2.init_params(config, kp), [
            seeded_adapter(config, ad, jax.random.fold_in(ka, i))
            for i in range(ad["resident"])]

    return jax.jit(draw)(jax.random.PRNGKey(seed))


def build_engine(cell: dict, seed: int):
    """(engine, adapter names, adapter trees, params)."""
    from mobilefinetuner_tpu.core.telemetry import Telemetry
    from mobilefinetuner_tpu.serve import (AdapterBank, ServeConfig,
                                           ServeEngine)
    tr = cell["traffic_file"]
    config = model_config(cell["config_file"])
    params, trees = make_weights(config, seed, tr["adapters"])
    names = [f"tenant{i}" for i in range(len(trees))]
    bank = AdapterBank(trees[0], capacity=len(trees)) if trees else None
    e = tr["engine"]
    cfg = ServeConfig(
        num_slots=e["num_slots"], block_T=e["block_T"],
        num_blocks=e["num_blocks"], max_prompt=e["max_prompt"],
        max_new_tokens=e["max_new_tokens"],
        dtype=e.get("dtype", "bfloat16"),
        attn_impl=e.get("attn_impl", "auto"),
        lora_impl=e.get("lora_impl", "auto"), on_step_error="raise",
        prefix_cache=bool(e.get("prefix_cache", False)),
        max_prompt_chunked=int(e.get("max_prompt_chunked", 0)))
    eng = ServeEngine("gpt2", config, params, cfg, bank=bank,
                      telemetry=Telemetry(""))
    for n, t in zip(names, trees):
        eng.load_adapter(n, t)
    return eng, names, trees, params


# --------------------------- correct ----------------------------------------

def check_logits(eng, cfgfile, params, names, trees, sched, seed: int,
                 n_req: int = 4, n_dec: int = 8) -> dict:
    """The engine's own model programs against the plain reference:
    `gpt2_prefill` and `gpt2_decode_step_paged` jitted with the
    engine's settings, routing and slot count (as chip_smoke.py's
    children obtain logits), for `n_req` requests of the cell's own
    schedule — adapters among them — at the last prompt position and
    `n_dec` teacher-forced decode positions, against the reference's
    full forward over prompt + forced tokens. A small pool of their own
    (n_req worst-case requests) stands in for the engine's pool, which
    the serve loop donates."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.models.generate import (gpt2_decode_step_paged,
                                                     gpt2_prefill)
    from mobilefinetuner_tpu.serve.paged_kv import (TRASH_BLOCK, init_pools,
                                                    write_prompt_blocks)
    from reference import gpt2 as ref
    cfg, conf, dt = eng.cfg, eng.config, eng.dtype
    S, M, bT = cfg.num_slots, eng.M, cfg.block_T
    L, KV, D = eng._pool_dims
    # the first requests of the schedule that carry an adapter, and the
    # first on the base route
    with_ad = [i for i, a in enumerate(sched["adapter"]) if a >= 0]
    base = [i for i, a in enumerate(sched["adapter"]) if a < 0]
    picks = (with_ad[:n_req - 1] + base[:1] + with_ad[n_req - 1:]
             + base[1:])[:n_req]
    rng = np.random.default_rng([int(seed), 0xC0])
    forced = rng.integers(1, conf.vocab_size - 1, (n_req, n_dec))
    pool_k, pool_v = init_pools(n_req * M + 1, L, KV, bT, D, dt)
    bank_tree = eng.bank.tree if eng.bank else None

    def prefill_py(params, bank, ids, mask, aid):
        return gpt2_prefill(conf, params, ids, mask, compute_dtype=dt,
                            lora=eng._route(bank, aid),
                            lora_impl=cfg.lora_impl)

    def step_py(params, bank, pk, pv, tok, pos, tbl, aid):
        return gpt2_decode_step_paged(
            conf, params, pk, pv, tok, pos, tbl,
            lora=eng._route(bank, aid), compute_dtype=dt,
            attn_impl=cfg.attn_impl, lora_impl=cfg.lora_impl)

    prefill = jax.jit(prefill_py)
    step = jax.jit(step_py, donate_argnums=(2, 3))
    write = jax.jit(write_prompt_blocks, donate_argnums=(0, 1))
    tok = np.zeros(S, np.int32)
    pos = np.zeros(S, np.int32)
    tbl = np.full((S, M), TRASH_BLOCK, np.int32)
    aid = np.full(S, eng.bank.base_slot if eng.bank else 0, np.int32)
    got = np.zeros((n_req, n_dec + 1, conf.vocab_size), np.float32)
    want = np.zeros_like(got)
    for j, i in enumerate(picks):
        prompt = sched["prompts"][i]
        P = len(prompt)
        a = sched["adapter"][i]
        aid[j] = eng.bank.slot(names[a]) if a >= 0 else aid[j]
        ids = np.zeros((1, cfg.max_prompt), np.int32)
        mask = np.zeros((1, cfg.max_prompt), np.int32)
        ids[0, :P], mask[0, :P] = prompt, 1
        logits, (k, v) = prefill(params, bank_tree, ids, mask,
                                 aid[j:j + 1])
        got[j, 0] = np.asarray(logits[0])
        blocks = 1 + j * M + np.arange(M)             # this row's pages
        tbl[j] = blocks
        ids_b = np.full(cfg.max_prompt // bT, TRASH_BLOCK, np.int32)
        n_pages = -(-P // bT)
        ids_b[:n_pages] = blocks[:n_pages]
        pool_k, pool_v = write(pool_k, pool_v, k[:, 0], v[:, 0], ids_b)
        pos[j] = P
        seq = np.asarray(prompt + forced[j].tolist(), np.int32)[None]
        lora = None if a < 0 else trees[a]
        want[j] = np.asarray(ref.forward(cfgfile, params, seq, lora)[
            0, P - 1:P + n_dec])
    for t in range(n_dec):
        tok[:n_req] = forced[:, t]
        logits, pool_k, pool_v = step(params, bank_tree, pool_k, pool_v,
                                      tok, pos, tbl, aid)
        got[:, t + 1] = np.asarray(logits[:n_req])
        pos[:n_req] += 1
    del pool_k, pool_v
    tol = correct.logit_tol(cfgfile["n_layer"])
    out = correct.verdict({
        "prefill_logits": (correct.rel_max(got[:, 0], want[:, 0]), tol,
                           "max"),
        "decode_logits": (correct.rel_max(got[:, 1:], want[:, 1:]), tol,
                          "max")})
    out["requests"] = [int(i) for i in picks]
    out["adapters"] = [int(sched["adapter"][i]) for i in picks]
    return out


# --------------------------- the driver -------------------------------------

class Driver:
    """The open loop. One thread: `submit` what is due, else `step`."""

    def __init__(self, eng, names, sched):
        self.eng, self.names, self.sched = eng, names, sched
        self.n = len(sched["prompts"])
        self.i = 0                  # next request of the schedule
        self.reqs = {}              # id(request) -> record
        self.finished_tokens = 0
        self.n_done = 0
        self.records = []           # one per submitted request
        self.steps = []             # (t0, t1, admitted, active, ctx_tokens,
                                    #  tokens_total, queue_depth)
        self.t_origin = None

    def start(self):
        self.t_origin = time.perf_counter()
        self.due = self.t_origin + np.asarray(self.sched["due_s"])

    def _submit_due(self, now: float):
        import jax
        s, eng = self.sched, self.eng
        while self.i < self.n and self.due[self.i] <= now:
            i = self.i
            a = s["adapter"][i]
            with jax.profiler.TraceAnnotation("bench.submit"):
                r = eng.submit(s["prompts"][i], max_new_tokens=s["max_new"][i],
                               adapter=self.names[a] if a >= 0 else None)
            rec = {"i": i, "req": r, "due": float(self.due[i]),
                   "submit": time.perf_counter(), "first": None,
                   "done": None, "want": s["max_new"][i]}
            self.records.append(rec)
            self.reqs[id(r)] = rec
            self.i += 1

    def total_tokens(self, active) -> int:
        return self.finished_tokens + sum(len(r.tokens) for r in active)

    def turn(self, t_stop: float) -> bool:
        """One turn of the loop; False once the clock passed t_stop."""
        import jax
        eng = self.eng
        now = time.perf_counter()
        if now >= t_stop:
            return False
        self._submit_due(now)
        if eng.idle:
            if self.i >= self.n:
                raise BenchError("the schedule ran out inside the window")
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(min(max(self.due[self.i] - now, 0.0), 0.002))
            return True
        q0 = len(eng.queue)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            done = eng.step()
        t1 = time.perf_counter()
        active = eng.active
        for r in (*active, *done):
            rec = self.reqs.get(id(r))
            if rec is not None and rec["first"] is None and r.tokens:
                rec["first"] = t1
        for r in done:
            rec = self.reqs.pop(id(r), None)
            if rec is not None:
                rec["done"] = t1
                self.n_done += 1
                self.finished_tokens += len(r.tokens)
        ctx = sum(len(r.prompt) + len(r.tokens) for r in active)
        self.steps.append((t0, t1, q0 - len(eng.queue), len(active), ctx,
                           self.total_tokens(active), len(eng.queue)))
        return True

    def run_until(self, t_stop: float = float("inf"), finished: int = 0):
        """Turn the loop until the clock passes t_stop or `finished`
        requests have reached a terminal state."""
        while self.turn(t_stop):
            if finished and self.n_done >= finished:
                break


def trace_counts(eng) -> int:
    return int(sum(eng.trace_counts.values()))


class CompileCounter:
    """Backend compilations seen by jax.monitoring in this process."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *_a, **_k):
        if event.endswith("backend_compile_duration"):
            self.n += 1


# --------------------------- one run ----------------------------------------

def observe(cell: dict, seed: int, seconds: float, trace: bool,
            rehearse: bool = False) -> dict:
    """Set up, warm up, measure; returns the run's observations (the
    samples and counters every metric is read from)."""
    import jax
    from mobilefinetuner_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()      # the env's directory, else <checkout>/.jax_cache
    device = common.require_chip(cell["chips"], rehearse)
    compiles = CompileCounter()
    tr, cfgfile = cell["traffic_file"], cell["config_file"]
    t_phase = {"import_and_device": time.perf_counter()
               - common.T_PROCESS_START}
    eng, names, trees, params = build_engine(cell, seed)
    jax.block_until_ready(eng.pool_k)
    t_phase["weights_and_engine"] = time.perf_counter() \
        - common.T_PROCESS_START
    say("engine built", attn=eng.attn_impl_resolved,
        lora=eng.lora_impl_resolved, mem_check=eng.mem_check.event())
    # the schedule: warm-up + window + margin, from the seed
    warm = tr["warmup"]
    arr = tr["arrivals"]
    if arr["process"] == "backlog":
        n = int(arr["backlog_requests"])
    else:
        n = int(arr["rate_per_s"] * arr.get("high", 1.0)
                * (warm.get("seconds", 0) + seconds) * 1.3) + 64
    sched = traffic_mod.serve_schedule(tr, eng.config.vocab_size, seed, n)
    say("schedule", requests=n, digest=traffic_mod.schedule_digest(sched))
    verdict = check_logits(eng, cfgfile, params, names, trees, sched, seed)
    say("correct: logits against the reference", **verdict)
    t_phase["correct"] = time.perf_counter() - common.T_PROCESS_START
    drv = Driver(eng, names, sched)
    drv.start()
    if "finished_requests" in warm:
        drv.run_until(finished=int(warm["finished_requests"]))
    else:
        drv.run_until(t_stop=drv.t_origin + float(warm["seconds"]))
    # every program of the loop has run before the window opens: the
    # first turns compile prefill, page write and the decode step
    while sum(1 for st in drv.steps if st[2] == 0) < MIN_WARM_DECODE_STEPS:
        drv.turn(float("inf"))
    # ---- the window ----------------------------------------------------
    traces0, comp0 = trace_counts(eng), compiles.n
    k0 = len(drv.steps)
    t_open = time.perf_counter()
    tokens0 = drv.total_tokens(eng.active)
    setup_s = t_open - common.T_PROCESS_START
    t_phase["warmup_traffic"] = setup_s
    trace_dir, t_trace, trace_steps = None, None, []
    trace_s = TRACE_SECONDS if trace else 0.0
    # a traced run keeps the two apart: the sample-based metrics come
    # from an untraced window, the device metrics from a traced span
    # AFTER it (starting and stopping the profiler stalls the loop)
    drv.run_until(t_stop=t_open + max(seconds - trace_s, 1.0))
    k1 = len(drv.steps)
    t_close = drv.steps[-1][1] if k1 > k0 else t_open
    tokens1 = drv.steps[-1][5] if k1 > k0 else tokens0
    if trace:
        trace_dir = os.path.join(common.WORK, "traces",
                                 f"{cell['name']}-seed{seed}")
        for old in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True):
            os.remove(old)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host spans are ours: bench.*
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_tr0 = time.perf_counter()
        drv.run_until(t_stop=t_tr0 + trace_s)
        t_tr1 = time.perf_counter()
        jax.profiler.stop_trace()
        t_trace, trace_steps = (t_tr0, t_tr1), drv.steps[k1:]
    window_traces = trace_counts(eng) - traces0
    window_compiles = compiles.n - comp0
    obs = {
        "kind": "serve_engine", "device": device, "setup_s": setup_s,
        "setup_phases": t_phase, "t_open": t_open, "t_close": t_close,
        "window_s": t_close - t_open,
        "tokens_in_window": tokens1 - tokens0,
        "steps": drv.steps[k0:k1], "trace_steps": trace_steps,
        "records": drv.records, "verdict": verdict,
        "window_traces": window_traces,
        "window_backend_compiles": window_compiles,
        "num_slots": eng.cfg.num_slots, "trace_dir": trace_dir,
        "trace_span": t_trace, "attn_impl": eng.attn_impl_resolved,
        "lora_impl": eng.lora_impl_resolved,
        # as the allocator measured it; what the compiler plans for the
        # decode step is an estimate and stays in tools/size_serve.py
        "memory_peak_bytes": common.memory_peak_bytes(),
        "cell": cell, "seed": seed,
    }
    return obs


def ttft_ms(rec: dict):
    """Due -> first token on the driver's clock, or None."""
    return None if rec["first"] is None \
        else (rec["first"] - rec["due"]) * 1e3


def tpot_ms(rec: dict):
    """Mean gap after the first token of a finished request, or None."""
    n = len(rec["req"].tokens)
    if rec["done"] is None or n < 2 or rec["req"].state != "finished":
        return None
    return (rec["done"] - rec["first"]) / (n - 1) * 1e3


def samples(obs: dict) -> dict:
    """Latency samples of the window, from the benchmark's own clock.

    ttft : due -> first token, of requests whose first token fell in
           the window;  tpot : per request, mean gap after the first
           token, of requests that finished in the window;
    late : submit - due, of requests submitted in the window."""
    lo, hi = obs["t_open"], obs["t_close"]
    inside = lambda t: t is not None and lo < t <= hi
    recs = obs["records"]
    ttft = [ttft_ms(r) for r in recs if inside(r["first"])]
    tpot = [tpot_ms(r) for r in recs
            if inside(r["done"]) and tpot_ms(r) is not None]
    late = [(r["submit"] - r["due"]) * 1e3 for r in recs
            if inside(r["submit"])]
    terminal = [r for r in recs if inside(r["done"])]
    failed = [r for r in terminal if r["req"].state != "finished"]
    # served in the window: held a slot at some time inside it, or
    # reached a terminal state in it (a reject never holds a slot)
    served = [r for r in recs if inside(r["done"]) or (
        r["first"] is not None and r["first"] <= hi
        and (r["done"] is None or r["done"] > lo))]
    short = [r for r in terminal if r["req"].state == "finished"
             and len(r["req"].tokens) != r["want"]]
    return {"ttft_ms": ttft, "tpot_ms": tpot, "late_ms": late,
            "terminal": len(terminal), "served": len(served),
            "failed": len(failed), "wrong_length": len(short)}


TAILS = (90, 95)             # end-to-end tails a cell may list


def end_to_end(obs: dict) -> dict:
    """Every end-to-end value this runner can give; run.py keeps the
    ones BENCHMARK.json lists for the cell. A tail `ttft_p<q>_ms` /
    `tpot_p<q>_ms` is given only where the window holds ten samples
    beyond it (stats.tail): a listed cell whose window holds fewer
    prints no result, so a cell lists the tail its traffic supports."""
    s = samples(obs)
    out = {"setup_s": obs["setup_s"]}
    if obs["window_s"] > 0:
        out["serve_tokens_per_s"] = obs["tokens_in_window"] / obs["window_s"]
    for key in ("ttft", "tpot"):
        xs = s[f"{key}_ms"]
        say(f"{key}: {len(xs)} samples", highest_supported_tail=(
            stats.highest_supported_percentile(len(xs))))
        for q in TAILS:
            v = stats.tail(xs, q)
            if v is not None:
                out[f"{key}_p{q}_ms"] = v
    return out


def in_window_correct(obs: dict) -> dict:
    s = samples(obs)
    return {"attempted": s["served"], "failed": s["failed"],
            "finished_in_window": s["terminal"] - s["failed"],
            "ok": s["failed"] == 0 and s["wrong_length"] == 0
            and obs["window_traces"] == 0
            and obs["window_backend_compiles"] == 0
            and s["served"] > 0 and obs["tokens_in_window"] > 0,
            "wrong_length": s["wrong_length"],
            "window_traces": obs["window_traces"],
            "window_backend_compiles": obs["window_backend_compiles"]}
