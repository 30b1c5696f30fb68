"""Serving-SLO ground truth: seeded Poisson open-loop load over the
real serve engine (serve/engine.py) — and, since round 14, the serve
FAULT-INJECTION harness (DESIGN.md §19).

Open-loop means arrivals do NOT wait for the service: request k arrives
at its scheduled time whether or not the engine is keeping up, so queue
buildup — the thing a closed-loop "send, wait, send" bench structurally
cannot show — lands in the TTFT tail exactly as it would in production.
The arrival schedule is seeded (exponential inter-arrival gaps), so a
row is reproducible end to end: same seed, same prompts, same adapter
routing, same admission order.

Every request's lifecycle rides the telemetry `request` events
(--telemetry_out), so tools/telemetry_report.py renders the same
TTFT/TPOT percentiles this tool prints — one measurement, two readers.

`--inject` drives the robustness layers end to end under load, the way
multihost_smoke's --inject proves the fleet controller:

  step_error:<n>        raise out of decode step n's dispatch — the
                        engine must fail only the in-flight requests
                        and keep serving the queue (crash containment)
  hang:<n>[:<s>]        wedge step n for <s> seconds — the attached
                        HangWatchdog (--watchdog) must fire a `hang`
                        event while the run completes
  slow_step:<n>:<ms>    one straggler step (latency-tail realism)
  adapter_load_fail     a tenant upload with a mismatched template —
                        the bank must refuse it without disturbing the
                        resident tenants

`--max_queue/--deadline_ms/--shed_policy` engage bounded admission and
per-request deadlines under the same load; SIGTERM during a run drains
gracefully (finish in-flight, reject the queue with reason=shutdown,
run_end{reason=preempted}); a second SIGTERM cancels in-flight.

Usage:
  python tools/serve_bench.py                        # GPT-2 small, k=1
  python tools/serve_bench.py --gemma --adapters 8   # Gemma-270M, k=8
  python tools/serve_bench.py --out BENCH_SERVE_r11.json --rate 4 8
  python tools/serve_bench.py --inject step_error:20 --max_queue 16 \
      --deadline_ms 2000 --stats_every 25            # fault harness
  python tools/serve_bench.py --prefix_cache 1 --prefix_pool 4 \
      --max_prompt_chunked 128 --sampling 1          # traffic scale
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])
sys.path.insert(0, __file__.rsplit("/", 1)[0])

import jax
import jax.numpy as jnp
import numpy as np

# one rank convention, two readers: the percentiles this tool prints
# must be the ones telemetry_report computes over the same stream
from telemetry_report import percentile


class InjectedStepError(RuntimeError):
    """The fault harness's synthetic step-dispatch failure — a distinct
    type so telemetry attributes the contained error to the injection
    (request{phase=error, reason=InjectedStepError}) and a real crash
    can never hide behind an injected one."""


def install_inject(engine, spec: str, hang_s: float = 2.0):
    """Arm one fault on the engine's step_hook seam (fires ONCE: the
    step counter does not advance on a contained failure, so an
    unlatched hook would re-fire forever). Returns the fired-latch
    list (empty until the fault triggers) so the caller can FAIL the
    run when an armed fault never fired — a spec naming a step the run
    never reaches must not silently pass as "containment proven".
    Spec grammar: step_error:<n> | hang:<n>[:<s>] | slow_step:<n>:<ms>
    | adapter_load_fail (handled by inject_adapter_load_fail — it
    needs the bank, not the step loop; returns None here)."""
    if not spec or spec == "adapter_load_fail":
        return None
    parts = spec.split(":")
    kind, fired = parts[0], []

    def once(step, n):
        if step == n and not fired:
            fired.append(step)
            return True
        return False

    if kind == "step_error":
        n = int(parts[1])

        def hook(step):
            if once(step, n):
                raise InjectedStepError(
                    f"injected step_error at decode step {n}")
    elif kind == "hang":
        n = int(parts[1])
        s = float(parts[2]) if len(parts) > 2 else hang_s

        def hook(step):
            if once(step, n):
                time.sleep(s)   # wedge: the watchdog's deadline expires
    elif kind == "slow_step":
        n, ms = int(parts[1]), float(parts[2])

        def hook(step):
            if once(step, n):
                time.sleep(ms / 1000.0)
    else:
        raise SystemExit(f"unknown --inject spec {spec!r}")
    engine.step_hook = hook
    return fired


def inject_adapter_load_fail(engine) -> str:
    """Offer the bank a structurally-wrong adapter (rank bumped) the
    way a corrupt tenant upload would: the load must be REFUSED with a
    named error, resident tenants undisturbed. Returns the error text
    (empty = the bank accepted it, which is the failure)."""
    import jax
    from mobilefinetuner_tpu.lora.lora import (LoRASpec, init_lora_gemma3,
                                               init_lora_gpt2)
    init = (init_lora_gpt2 if engine.family == "gpt2"
            else init_lora_gemma3)
    bad = init(engine.config, LoRASpec(rank=16, alpha=32.0),
               jax.random.PRNGKey(99))
    try:
        engine.load_adapter("corrupt_tenant", bad)
    except ValueError as e:
        return str(e)
    return ""


def rand_adapters(family, config, k: int, seed: int = 0):
    """k seeded random adapters (B pushed off zero so each tenant's
    outputs actually differ)."""
    from mobilefinetuner_tpu.lora.lora import (LoRASpec, init_lora_gemma3,
                                               init_lora_gpt2)
    init = init_lora_gpt2 if family == "gpt2" else init_lora_gemma3
    out = []
    for i in range(k):
        lora = init(config, LoRASpec(rank=4, alpha=8.0),
                    jax.random.PRNGKey(seed + i))
        leaves, td = jax.tree.flatten(lora)
        keys = jax.random.split(jax.random.PRNGKey(seed + 100 + i),
                                len(leaves))
        out.append(jax.tree.unflatten(td, [
            l if l.ndim == 0 else 0.02 * jax.random.normal(kk, l.shape)
            for l, kk in zip(leaves, keys)]))
    return out


def build_engine(model: str, num_slots: int, block_T: int,
                 num_blocks: int, max_prompt: int, max_new: int,
                 adapters: int, dtype: str, telemetry_out: str = "",
                 seed: int = 0, max_queue: int = 0,
                 shed_policy: str = "reject",
                 on_step_error: str = "fail_active",
                 stats_every: int = 0, watchdog=None,
                 hbm_cap_mb: int = 0, hbm_headroom: float = 0.1,
                 trace_spans: bool = False, metrics_port: int = 0,
                 metrics_addr: str = "127.0.0.1",
                 mesh_dp: int = 1, mesh_tp: int = 1,
                 prefix_cache: bool = False, max_prompt_chunked: int = 0,
                 sampling: bool = False, host: int = 0):
    """model: gpt2s | gemma270m | tiny-gpt2 | tiny-gemma. The tiny
    modes are the CPU contract/smoke path (tests/test_serve.py).

    `host` stamps the telemetry envelope (round 22): a router replica
    writes shard_path(base, k) with host=k so the fleet merge key
    (host, seq) stays collision-free across replicas.

    metrics_port > 0 serves the live OpenMetrics endpoint
    (core/metrics_http.py) over the engine's telemetry emit path, with
    /healthz riding engine.health(); the server lands on
    `engine.metrics_server` (run_rows closes it). Everything is
    host-side bookkeeping — a scrape can never cost a retrace
    (tests/test_observability.py pins it under live load)."""
    from mobilefinetuner_tpu.core.config import GPT2Config, Gemma3TextConfig
    from mobilefinetuner_tpu.core.telemetry import Telemetry
    from mobilefinetuner_tpu.models import gemma3, gpt2
    from mobilefinetuner_tpu.serve import (AdapterBank, ServeConfig,
                                           ServeEngine)
    if model == "gpt2s":
        config, family = GPT2Config.gpt2_small(), "gpt2"
    elif model == "gemma270m":
        config, family = Gemma3TextConfig.gemma3_270m(), "gemma"
    elif model == "tiny-gpt2":
        config, family = GPT2Config.tiny(), "gpt2"
    elif model == "tiny-gemma":
        config, family = Gemma3TextConfig.tiny(), "gemma"
    else:
        raise SystemExit(f"unknown model {model!r}")
    mod = gpt2 if family == "gpt2" else gemma3
    params = mod.init_params(config, jax.random.PRNGKey(seed))
    bank = None
    names = []
    if adapters:
        trees = rand_adapters(family, config, adapters, seed)
        bank = AdapterBank(trees[0], capacity=adapters)
        names = [f"tenant{i}" for i in range(adapters)]
    cfg = ServeConfig(num_slots=num_slots, block_T=block_T,
                      num_blocks=num_blocks, max_prompt=max_prompt,
                      max_new_tokens=max_new, dtype=dtype,
                      max_queue=max_queue, shed_policy=shed_policy,
                      on_step_error=on_step_error,
                      stats_every=stats_every,
                      hbm_cap_mb=hbm_cap_mb, hbm_headroom=hbm_headroom,
                      trace_spans=trace_spans,
                      mesh_dp=mesh_dp, mesh_tp=mesh_tp,
                      prefix_cache=prefix_cache,
                      max_prompt_chunked=max_prompt_chunked,
                      sampling=sampling)
    tel = Telemetry(telemetry_out, host=host)
    registry = None
    if metrics_port > 0:
        # observer attached BEFORE the engine builds, so run_start and
        # the build-time mem_check land in the registry too
        from mobilefinetuner_tpu.core.metrics_http import MetricsRegistry
        registry = MetricsRegistry()
        tel.add_observer(registry.observe)
    eng = ServeEngine(family, config, params, cfg, bank=bank,
                      telemetry=tel, watchdog=watchdog)
    eng.metrics_server = None
    if registry is not None:
        from mobilefinetuner_tpu.core.metrics_http import MetricsServer
        eng.metrics_server = MetricsServer(
            registry, port=metrics_port, addr=metrics_addr,
            health_fn=eng.health)
        print(f"metrics endpoint: http://{eng.metrics_server.addr}:"
              f"{eng.metrics_server.port}/metrics (+ /healthz)")
    if adapters:
        for n, t in zip(names, trees):
            eng.load_adapter(n, t)
    return eng, names


def gen_schedule(vocab: int, block_T: int, rate: float,
                 n_requests: int, seed: int, prompt_lo: int,
                 prompt_hi: int, names, prefix_pool: int = 0,
                 prefix_frac: float = 0.7, sampling: bool = False):
    """The seeded open-loop workload, decoupled from the engine so the
    in-process path (run_load) and the HTTP router path
    (run_router_rows, round 22) drive the IDENTICAL schedule: same
    seed => same arrival gaps, prompt contents, tenant routing and
    sampling knobs — a router row and its single-engine baseline
    differ only in serving topology. Returns (gaps, prompts, route,
    samp) with pure-python ints (the prompts must survive json).

    prefix_pool > 0 makes the workload SHARED-PREFIX shaped (round 21):
    a seeded pool of that many full-block prefixes, and each request
    opens with a pool member with probability prefix_frac (its suffix
    stays per-request random) — the multi-turn/system-prompt traffic a
    prefix cache earns its keep on. The prefixes span whole pages (the
    cache's unit of reuse): as many whole blocks as fit under the
    shortest prompt, leaving at least one unique-suffix token."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, n_requests)
    lens = rng.integers(prompt_lo, prompt_hi + 1, n_requests)
    if prefix_pool > 0:
        bT = block_T
        plen = max(bT, ((prompt_lo - 1) // bT) * bT)
        pool = [[int(v) for v in rng.integers(1, vocab, plen)]
                for _ in range(prefix_pool)]
        hit = rng.random(n_requests) < prefix_frac
        pick = rng.integers(0, prefix_pool, n_requests)
        prompts = [
            (pool[int(pick[i])] if hit[i] else
             [int(v) for v in rng.integers(1, vocab, plen)])
            + [int(v) for v in
               rng.integers(1, vocab, max(int(lens[i]) - plen, 1))]
            for i in range(n_requests)]
    else:
        prompts = [[int(v) for v in rng.integers(1, vocab, int(n))]
                   for n in lens]
    seeds = rng.integers(0, 2**31, n_requests)
    samp = (lambda i: {"temperature": 0.8, "top_k": 40, "top_p": 0.95,
                       "seed": int(seeds[i])}) if sampling \
        else (lambda i: {})
    route = ([names[int(i)] for i in
              rng.integers(0, len(names), n_requests)]
             if names else [None] * n_requests)
    return gaps, prompts, route, samp


def run_load(engine, names, rate: float, n_requests: int, seed: int,
             prompt_lo: int, prompt_hi: int, max_new: int,
             deadline_ms=None, prefix_pool: int = 0,
             prefix_frac: float = 0.7, sampling: bool = False):
    """Drive one open-loop Poisson run; returns (terminal requests,
    elapsed seconds). Deterministic given the seed: arrivals, prompt
    contents/lengths, and tenant routing all come from one rng.
    Drain-aware: when a SIGTERM flips the engine into draining, the
    unsubmitted remainder of the schedule is dropped (the clients went
    away with the pod) and the loop runs the in-flight requests out; a
    second signal (KeyboardInterrupt out of step()) cancels in-flight.
    Rejected-at-submit requests (bounded queue, shutdown) are included
    in the returned list — filter on `.state` for completions.

    The workload comes from gen_schedule (prefix_pool shapes it into
    shared-prefix traffic; sampling=True submits each request with a
    seeded per-request PRNG and a fixed softmax temperature, so a
    sampled row is as reproducible as a greedy one)."""
    gaps, prompts, route, samp = gen_schedule(
        engine.config.vocab_size, engine.cfg.block_T, rate,
        n_requests, seed, prompt_lo, prompt_hi, names,
        prefix_pool=prefix_pool, prefix_frac=prefix_frac,
        sampling=sampling)
    t0 = time.perf_counter()
    arrivals = t0 + np.cumsum(gaps)
    done, submitted, i = [], [], 0
    try:
        while i < n_requests or not engine.idle:
            now = time.perf_counter()
            if engine.draining:
                i = n_requests
            while i < n_requests and arrivals[i] <= now:
                submitted.append(
                    engine.submit(prompts[i], max_new_tokens=max_new,
                                  adapter=route[i],
                                  deadline_ms=deadline_ms, **samp(i)))
                i += 1
            if engine.idle:
                if i < n_requests:
                    time.sleep(min(max(arrivals[i] - now, 0.0), 0.05))
                continue
            done.extend(engine.step())
    except KeyboardInterrupt:
        # second signal mid-drain: the operator wants out NOW — cancel
        # what is still in flight (partial output stays on the request)
        for req in list(engine.active):
            engine.cancel(req)
        engine.begin_shutdown()
    # census over SUBMITTED ∪ step-returned, not just step-returned:
    # submit-time terminals (queue_full/shutdown rejects, and shed
    # victims — terminated inside a LATER request's submit) and the
    # KeyboardInterrupt cancels above never come back from step()
    by_id = {r.id: r for r in done}
    by_id.update({r.id: r for r in submitted if r.done})
    return (sorted(by_id.values(), key=lambda r: r.id),
            time.perf_counter() - t0)


def row_from(config_name: str, engine, done, elapsed: float,
             rate: float, adapters: int) -> dict:
    fin = [r for r in done if r.state == "finished"]
    ttfts = sorted(r.ttft_ms for r in fin if r.ttft_ms is not None)
    tpots = sorted(r.tpot_ms for r in fin if r.tpot_ms is not None)
    gen_tokens = sum(len(r.tokens) for r in done)
    pct = lambda v: {"p50": percentile(v, 50), "p95": percentile(v, 95),
                     "p99": percentile(v, 99)}
    chips = engine.cfg.mesh_dp * engine.cfg.mesh_tp
    gen_tok_s = round(gen_tokens / elapsed, 1) if elapsed > 0 else None
    return {
        "config": config_name,
        "offered_rps": rate,
        "requests": len(fin),
        "elapsed_s": round(elapsed, 3),
        "req_s": round(len(fin) / elapsed, 3) if elapsed > 0 else None,
        "gen_tok_s": gen_tok_s,
        # mesh shape + per-chip throughput: the "is tp paying for
        # itself" number bench_compare tracks across mesh rows
        "mesh": [engine.cfg.mesh_dp, engine.cfg.mesh_tp],
        "tok_s_per_chip": (round(gen_tok_s / chips, 1)
                           if gen_tok_s is not None else None),
        "ttft_ms": pct(ttfts),
        "tpot_ms": pct(tpots),
        # round 14: where the non-finishers went (the SLO denominator a
        # load-shed/deadline policy is judged by) + the loop vitals
        "terminal": {s: sum(1 for r in done if r.state == s)
                     for s in ("finished", "cancelled", "rejected",
                               "timeout", "error")},
        "health": engine.health(),
        "adapters_resident": adapters,
        "num_slots": engine.cfg.num_slots,
        "block_T": engine.cfg.block_T,
        "num_blocks": engine.cfg.num_blocks,
        "decode_steps": engine.decode_steps,
        "traces": dict(engine.trace_counts),
        # round 21: the prefix-reuse and sampling row shape. hit_rate /
        # cow are None-safe: a cache-off row carries nulls, so the
        # contract test can pin the schema either way
        "sampling": bool(engine.cfg.sampling),
        "prefix_cache": bool(engine.cfg.prefix_cache),
        "prefix_hit_rate": (engine.prefix.hit_rate
                            if engine.prefix is not None else None),
        "cow_copies": (engine.cow_copies
                       if engine.prefix is not None else None),
    }


def run_router_rows(model: str, rates, n_requests: int, adapters: int,
                    replicas: int, telemetry_out: str,
                    num_slots: int = 8, block_T: int = 16,
                    num_blocks: int = 256, max_prompt: int = 64,
                    max_new: int = 32, dtype: str = "bfloat16",
                    seed: int = 0, prompt_lo: int = 8,
                    prompt_hi: int = 0, max_queue: int = 0,
                    shed_policy: str = "reject", stats_every: int = 10,
                    prefix_cache: bool = False,
                    max_prompt_chunked: int = 0, sampling: bool = False,
                    prefix_pool: int = 0, prefix_frac: float = 0.7,
                    deadline_ms=None, scrape_s: float = 0.1,
                    collect_s: float = 0.02,
                    startup_timeout_s: float = 300.0,
                    settle_timeout_s: float = 600.0) -> list:
    """Round 22: the same seeded open-loop Poisson load, driven over
    HTTP through tools/serve_router.py with `replicas` engine
    processes behind it. One router subprocess per call (one compile
    per replica, amortised across the rates); per rate, one FLEET row
    (goodput, TTFT/TPOT/queue-wait percentiles over ALL replicas,
    terminal census, routing-decision histogram from the router's own
    `route` events, per-replica prefix-cache hit rate) plus one row
    per replica — the load-imbalance and per-tenant-locality story a
    fleet-level mean hides. (stamp_baseline adds the single-engine
    p99 ratio afterwards — the baseline engine needs the chip, so it
    runs once the fleet is down.)

    The calling process must not have initialised a JAX backend: the
    replicas need the chip(s), and a chip belongs to one process.

    Exact accounting is the contract here, same as the kill-replica
    e2e: every rid the router acked MUST settle through /collect
    before the rate's row is built — a missing rid fails the bench."""
    import signal
    import subprocess
    import serve_router as sr              # sibling tool (no jax)
    from mobilefinetuner_tpu.parallel.host_devices import \
        backend_initialized
    if backend_initialized():
        # a chip belongs to one process: a parent that holds it makes
        # every replica fail or hang at backend start
        raise SystemExit("--router: this process initialised a JAX "
                         "backend before starting the replica fleet")
    from telemetry_report import load_events
    from mobilefinetuner_tpu.core.config import (GPT2Config,
                                                 Gemma3TextConfig)
    prompt_hi = prompt_hi or max_prompt
    vocab = {"gpt2s": GPT2Config.gpt2_small,
             "gemma270m": Gemma3TextConfig.gemma3_270m,
             "tiny-gpt2": GPT2Config.tiny,
             "tiny-gemma": Gemma3TextConfig.tiny}[model]().vocab_size
    names = [f"tenant{i}" for i in range(adapters)]
    spec = {"model": model, "num_slots": num_slots, "block_T": block_T,
            "num_blocks": num_blocks, "max_prompt": max_prompt,
            "max_new": max_new, "adapters": adapters, "dtype": dtype,
            "seed": seed, "max_queue": max_queue,
            "shed_policy": shed_policy, "stats_every": stats_every,
            "trace_spans": True, "prefix_cache": prefix_cache,
            "max_prompt_chunked": max_prompt_chunked,
            "sampling": sampling}
    base = telemetry_out
    proc = subprocess.Popen(
        [sys.executable, sr.__file__, "--telemetry", base,
         "--replicas", str(replicas),
         "--engine_json", json.dumps(spec),
         "--scrape_s", str(scrape_s), "--collect_s", str(collect_s)])
    url = None

    def collect(results):
        try:
            _, obj = sr._http_json("POST", url + "/collect", {},
                                   timeout=10.0)
        except OSError:
            return
        for r in obj.get("done", ()):
            if isinstance(r.get("rid"), int):
                results[r["rid"]] = r

    pct = lambda v: {"p50": percentile(v, 50), "p95": percentile(v, 95),
                     "p99": percentile(v, 99)}
    census = lambda rs: {s: sum(1 for r in rs if r["state"] == s)
                         for s in ("finished", "cancelled", "rejected",
                                   "timeout", "error")}
    rows = []
    try:
        deadline = time.time() + startup_timeout_s
        while True:
            if proc.poll() is not None:
                raise SystemExit(f"--router: router exited "
                                 f"rc={proc.returncode} during startup")
            if time.time() > deadline:
                raise SystemExit("--router: router never became ready")
            pf = sr.read_port_file(base, 0)
            if pf:
                try:
                    code, _ = sr._http_json(
                        "GET", f"http://127.0.0.1:{pf['port']}/healthz",
                        timeout=2.0)
                except OSError:
                    code = 0
                if code == 200:
                    url = f"http://127.0.0.1:{pf['port']}"
                    break
            time.sleep(0.2)
        # /healthz goes 200 at the FIRST ready replica; wait for the
        # whole fleet so the warmup below reaches every engine
        while time.time() < deadline:
            try:
                _, fl = sr._http_json("GET", url + "/fleet",
                                      timeout=2.0)
            except OSError:
                fl = {}
            if sum(1 for r in fl.get("replicas", {}).values()
                   if r.get("status") == "ok") >= replicas:
                break
            time.sleep(0.2)
        # warmup OUTSIDE the measured window: enough requests that the
        # inflight-aware placement touches every replica, so each
        # engine compiles prefill + step before a measured arrival
        warm, results = [], {}
        for _ in range(2 * replicas):
            code, obj = sr._http_json(
                "POST", url + "/submit",
                {"prompt": [1] * prompt_lo,
                 "max_new_tokens": min(2, max_new),
                 **({"adapter": names[0]} if names else {})},
                timeout=30.0)
            if isinstance(obj.get("rid"), int):
                warm.append(obj["rid"])
        deadline = time.time() + startup_timeout_s
        while not set(warm) <= set(results):
            if time.time() > deadline:
                raise SystemExit("--router: warmup never settled")
            collect(results)
            time.sleep(0.05)
        for rate in rates:
            gaps, prompts, route, samp = gen_schedule(
                vocab, block_T, rate, n_requests, seed, prompt_lo,
                prompt_hi, names, prefix_pool=prefix_pool,
                prefix_frac=prefix_frac, sampling=sampling)
            results, rids, i = {}, [], 0
            t0 = time.perf_counter()
            arrivals = t0 + np.cumsum(gaps)
            while i < n_requests:
                now = time.perf_counter()
                while i < n_requests and arrivals[i] <= now:
                    payload = {"prompt": prompts[i],
                               "max_new_tokens": max_new, **samp(i)}
                    if route[i]:
                        payload["adapter"] = route[i]
                    if deadline_ms:
                        payload["deadline_ms"] = deadline_ms
                    try:
                        _, obj = sr._http_json(
                            "POST", url + "/submit", payload,
                            timeout=30.0)
                    except OSError:
                        obj = {}
                    # a 503 reject still carries the rid (it settles
                    # through /collect as a rejected row — the census
                    # counts it, exactly like a direct-path reject)
                    if isinstance(obj.get("rid"), int):
                        rids.append(obj["rid"])
                    i += 1
                collect(results)
                if i < n_requests:
                    time.sleep(min(max(
                        arrivals[i] - time.perf_counter(), 0.0), 0.02))
            want = set(rids)
            deadline = time.time() + settle_timeout_s
            while not want <= set(results):
                if time.time() > deadline:
                    raise SystemExit(
                        f"--router: {len(want - set(results))} rids "
                        f"never settled — exact accounting violated")
                collect(results)
                time.sleep(0.03)
            elapsed = time.perf_counter() - t0
            res = [results[r] for r in sorted(want)]
            name = (f"router{replicas}_{model}_serve_"
                    f"k{max(adapters, 1)}_r{rate:g}")
            if max_prompt_chunked:
                name += f"_chunk{max_prompt_chunked}"
            if prefix_pool:
                name += (f"_prefix{prefix_pool}" if prefix_cache
                         else f"_prefix{prefix_pool}off")
            if sampling:
                name += "_sampled"
            fin = [r for r in res if r["state"] == "finished"]
            gen_tokens = sum(int(r.get("new_tokens") or 0) for r in res)
            # the routing-decision histogram comes from the router's
            # OWN stream (every decision is a route event), scoped to
            # this rate's rids; per-replica placement from the settle
            # rows (failover rids count where they actually landed)
            decisions = {}
            for e in load_events(base)[0]:
                if e["event"] == "route" and e.get("rid") in want:
                    p = e.get("policy", "?")
                    decisions[p] = decisions.get(p, 0) + 1
            per_replica = {}
            for r in res:
                if r.get("replica") is not None:
                    k = str(r["replica"])
                    per_replica[k] = per_replica.get(k, 0) + 1
            hit = {}
            for k in range(1, replicas + 1):
                p = sr.shard_path(base, k)
                ss = ([e for e in load_events(p)[0]
                       if e["event"] == "serve_stats"]
                      if os.path.exists(p) else [])
                hit[str(k)] = (ss[-1].get("prefix_hit_rate")
                               if ss else None)
            row = {
                "config": name, "offered_rps": rate,
                "replicas": replicas, "requests": len(fin),
                "elapsed_s": round(elapsed, 3),
                "req_s": (round(len(fin) / elapsed, 3)
                          if elapsed > 0 else None),
                "gen_tok_s": (round(gen_tokens / elapsed, 1)
                              if elapsed > 0 else None),
                "ttft_ms": pct(sorted(r["ttft_ms"] for r in fin
                                      if r["ttft_ms"] is not None)),
                "tpot_ms": pct(sorted(r["tpot_ms"] for r in fin
                                      if r["tpot_ms"] is not None)),
                "queue_ms": pct(sorted(r["queue_ms"] for r in fin
                                       if r["queue_ms"] is not None)),
                "terminal": census(res),
                "routing": decisions,
                "requests_per_replica": per_replica,
                "prefix_hit_rate": hit,
                "adapters_resident": adapters,
                "sampling": bool(sampling),
                "prefix_cache": bool(prefix_cache),
            }
            rows.append(row)
            fmt = lambda v: "n/a" if v is None else f"{v:.0f}"
            print(f"{name}: {row['req_s']} req/s "
                  f"({row['gen_tok_s']} tok/s) over {replicas} "
                  f"replicas, TTFT p50/p99 = "
                  f"{fmt(row['ttft_ms']['p50'])}/"
                  f"{fmt(row['ttft_ms']['p99'])} ms, routing "
                  f"{decisions}, spread {per_replica}")
            for k in sorted(int(k) for k in per_replica):
                sub = [r for r in res if r.get("replica") == k]
                fin_k = [r for r in sub if r["state"] == "finished"]
                rows.append({
                    "config": f"{name}_replica{k}",
                    "offered_rps": rate, "replica": k,
                    "requests": len(fin_k),
                    "ttft_ms": pct(sorted(
                        r["ttft_ms"] for r in fin_k
                        if r["ttft_ms"] is not None)),
                    "tpot_ms": pct(sorted(
                        r["tpot_ms"] for r in fin_k
                        if r["tpot_ms"] is not None)),
                    "terminal": census(sub),
                    "prefix_hit_rate": hit.get(str(k)),
                })
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    print(f"router stream: {base} (replay with "
          f"tools/trace_export.py {base} --router)")
    return rows


def stamp_baseline(rows: list, baseline: dict) -> None:
    """Give each fleet row (one per rate) its single-engine TTFT p99
    (`baseline`: rate -> p99 ms from run_rows over the identical
    gen_schedule workload) and the ratio bench_compare tracks."""
    for row in rows:
        base = baseline.get(row["offered_rps"])
        if "replicas" not in row or base is None:
            continue
        row["baseline_ttft_p99_ms"] = base
        if row["ttft_ms"]["p99"] is not None and base:
            row["ttft_p99_vs_baseline"] = round(
                row["ttft_ms"]["p99"] / base, 3)
            print(f"{row['config']}: TTFT p99 vs 1-engine "
                  f"x{row['ttft_p99_vs_baseline']}")


def rows_with_errors(rows: list) -> list:
    """Configs whose census holds a request in state `error`. With no
    fault injected nothing may end there: an error is then a step that
    could not run (a kernel the compiler refused, an OOM), contained by
    on_step_error=fail_active — a run that answered nothing must not
    exit 0."""
    return [r["config"] for r in rows
            if (r.get("terminal") or {}).get("error")]


def run_rows(model: str, rates, n_requests: int, adapters: int,
             num_slots: int = 8, block_T: int = 16, num_blocks: int = 256,
             max_prompt: int = 64, max_new: int = 32, dtype: str =
             "bfloat16", seed: int = 0, prompt_lo: int = 8,
             prompt_hi: int = 0, telemetry_out: str = "",
             max_queue: int = 0, shed_policy: str = "reject",
             on_step_error: str = "fail_active", deadline_ms=None,
             stats_every: int = 0, inject: str = "", drain: bool = True,
             watchdog_mode: int = 0, watchdog_min_s: float = 60.0,
             hbm_cap_mb: int = 0, hbm_headroom: float = 0.1,
             trace_spans: bool = False, metrics_port: int = 0,
             metrics_addr: str = "127.0.0.1",
             mesh_dp: int = 1, mesh_tp: int = 1,
             prefix_cache: bool = False, max_prompt_chunked: int = 0,
             sampling: bool = False, prefix_pool: int = 0,
             prefix_frac: float = 0.7) -> list:
    """One engine, one warmup request, then one row per offered rate.
    `drain` arms the SIGTERM PreemptionGuard; `inject` fires its fault
    during the FIRST rate's run (the spec names an absolute decode
    step)."""
    from mobilefinetuner_tpu.core.telemetry import HangWatchdog
    prompt_hi = prompt_hi or max_prompt
    wd = None
    if watchdog_mode:
        wd = HangWatchdog(mult=10.0, min_deadline_s=watchdog_min_s,
                          grace_s=max(watchdog_min_s, 5.0),
                          abort=watchdog_mode == 2)
    eng, names = build_engine(model, num_slots, block_T, num_blocks,
                              max_prompt, max_new, adapters, dtype,
                              telemetry_out=telemetry_out, seed=seed,
                              max_queue=max_queue, shed_policy=shed_policy,
                              on_step_error=on_step_error,
                              stats_every=stats_every, watchdog=wd,
                              hbm_cap_mb=hbm_cap_mb,
                              hbm_headroom=hbm_headroom,
                              trace_spans=trace_spans,
                              metrics_port=metrics_port,
                              metrics_addr=metrics_addr,
                              mesh_dp=mesh_dp, mesh_tp=mesh_tp,
                              prefix_cache=prefix_cache,
                              max_prompt_chunked=max_prompt_chunked,
                              sampling=sampling)
    if wd is not None:
        wd.on_hang = lambda p: eng.telemetry.emit("hang", **p)
        wd.stacks_file = (eng.telemetry.path + ".stacks"
                          if eng.telemetry.path else wd.stacks_file)
        wd.start()
    if drain:
        eng.install_preemption()
    # warmup: compile prefill + step outside the measured window
    eng.submit([1] * prompt_lo, max_new_tokens=min(2, max_new),
               adapter=names[0] if names else None)
    eng.drain()
    # r21 warmup: the reuse/chunk executables compile LAZILY (one per
    # bucket width, plus the full-hit COW re-feed) — trace each here or
    # its first use lands in a measured row's TTFT tail
    if eng.prefix is not None:
        head = [7] * block_T
        eng.submit(head, max_new_tokens=1)
        eng.drain()                    # registers the head page
        eng.submit(list(head), max_new_tokens=1)
        eng.drain()                    # full hit -> COW re-feed program
        for w in eng.chunk_buckets:
            # a hit on the head page + an s-token suffix dispatches the
            # smallest bucket covering s; s caps at the widest suffix a
            # hit can leave, which is also the widest REACHABLE width
            s = min(w, (max_prompt_chunked or max_prompt) - block_T)
            if s > 0:
                eng.submit(head + [11] * s, max_new_tokens=1)
                eng.drain()
    if max_prompt_chunked:
        widest = eng.chunk_buckets[-1]
        for w in eng.chunk_buckets:
            # widest-until-covered walk: a (widest + w)-token prompt
            # ends its walk on bucket w
            n = widest + w
            if max_prompt < n <= max_prompt_chunked:
                eng.submit([13] * n, max_new_tokens=1)
                eng.drain()
    warm_traces = eng.total_traces()
    if inject == "adapter_load_fail":
        err = inject_adapter_load_fail(eng)
        if not err:
            # the harness MUST fail loudly when the injected fault is
            # not handled — a CI caller keys on the exit status
            if wd is not None:
                wd.stop()
            eng.close()
            raise SystemExit(
                "--inject adapter_load_fail: the bank ACCEPTED a "
                "structurally-wrong adapter — validation regressed")
        print(f"inject adapter_load_fail: REFUSED ({err[:60]}...)")
        fired = None
    else:
        fired = install_inject(eng, inject)
    rows = []
    try:
        for rate in rates:
            counts0 = dict(eng.counts)   # scope the row's census to
            # THIS run: health()'s counters are engine-lifetime
            pages0 = eng.alloc.pages_allocated
            ht0, lt0 = ((eng.prefix.hit_tokens, eng.prefix.lookup_tokens)
                        if eng.prefix is not None else (0, 0))
            done, elapsed = run_load(eng, names, rate, n_requests, seed,
                                     prompt_lo, prompt_hi, max_new,
                                     deadline_ms=deadline_ms,
                                     prefix_pool=prefix_pool,
                                     prefix_frac=prefix_frac,
                                     sampling=sampling)
            name = f"{model}_serve_k{max(adapters, 1)}_r{rate:g}"
            if mesh_dp * mesh_tp > 1:
                name += f"_mesh{mesh_dp}x{mesh_tp}"
            if max_prompt_chunked:
                name += f"_chunk{max_prompt_chunked}"
            # the workload suffix also records whether REUSE was on, so
            # a cache-on vs cache-off A/B lands as two bench_compare
            # rows instead of one colliding config key
            if prefix_pool:
                name += (f"_prefix{prefix_pool}" if prefix_cache
                         else f"_prefix{prefix_pool}off")
            if sampling:
                name += "_sampled"
            row = row_from(name, eng, done, elapsed, rate, adapters)
            if eng.prefix is not None:
                # scope the (token-weighted) hit rate to THIS row's
                # lookups — engine-lifetime includes the warmup's
                lt = eng.prefix.lookup_tokens - lt0
                row["prefix_hit_rate"] = (
                    round((eng.prefix.hit_tokens - ht0) / lt, 4)
                    if lt else None)
            nfin = max(row["requests"], 1)
            # pages ALLOCATED this row (prefix hits acquire, not alloc)
            # per finished request — the KV-cost-of-reuse observable
            row["kv_pages_per_req"] = round(
                (eng.alloc.pages_allocated - pages0) / nfin, 2)
            row["health"]["counts"] = {
                k: int(eng.counts.get(k, 0)) - counts0.get(k, 0)
                for k in row["health"]["counts"]}
            row["new_traces_after_warmup"] = \
                eng.total_traces() - warm_traces
            if inject:
                row["inject"] = inject
            rows.append(row)
            # percentiles may be None (e.g. max_new=1 leaves no post-
            # first-token cadence, so every tpot is None)
            fmt = lambda v, spec="0f": ("n/a" if v is None
                                        else f"{v:.{spec}}")
            term = row["terminal"]
            faults = ", ".join(f"{k} {v}" for k, v in term.items()
                               if k != "finished" and v)
            reuse = ""
            if row["prefix_hit_rate"] is not None:
                reuse = (f", hit_rate {row['prefix_hit_rate']:.2f} "
                         f"(cow {row['cow_copies']}, "
                         f"{row['kv_pages_per_req']:.1f} pages/req)")
            print(f"{name}: {row['req_s']} req/s "
                  f"({row['gen_tok_s']} tok/s), "
                  f"TTFT p50/p99 = {fmt(row['ttft_ms']['p50'])}/"
                  f"{fmt(row['ttft_ms']['p99'])} ms, TPOT p50 = "
                  f"{fmt(row['tpot_ms']['p50'], '1f')} ms, "
                  f"{row['new_traces_after_warmup']} retraces{reuse}"
                  + (f" [{faults}]" if faults else ""))
            if eng.draining:
                print(f"{name}: DRAINED (SIGTERM) — remaining rates "
                      f"skipped")
                break
    finally:
        if wd is not None:
            wd.stop()
        if getattr(eng, "metrics_server", None) is not None:
            eng.metrics_server.close()
        eng.close()
    if fired is not None and not fired:
        # the armed fault never triggered (step already consumed by
        # warmup, or past the run's reach): the robustness claim was
        # NOT exercised — fail the harness, don't report a clean row
        raise SystemExit(
            f"--inject {inject}: the armed fault never fired "
            f"(run ended at decode step "
            f"{rows[-1]['decode_steps'] if rows else 0}) — "
            f"nothing was proven")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2s",
                    choices=["gpt2s", "gemma270m", "tiny-gpt2",
                             "tiny-gemma"])
    ap.add_argument("--gemma", action="store_true",
                    help="shorthand for --model gemma270m")
    ap.add_argument("--rate", type=float, nargs="*", default=[4.0],
                    help="offered load(s), requests/second")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--adapters", type=int, default=0,
                    help="resident LoRA tenants (0 = base only)")
    ap.add_argument("--num_slots", type=int, default=8)
    ap.add_argument("--block_T", type=int, default=16)
    ap.add_argument("--num_blocks", type=int, default=256)
    ap.add_argument("--max_prompt", type=int, default=64)
    ap.add_argument("--max_new", type=int, default=32)
    ap.add_argument("--prompt_lo", type=int, default=8)
    ap.add_argument("--prompt_hi", type=int, default=0,
                    help="prompt-length ceiling for the workload "
                         "(0 = max_prompt); raise past max_prompt "
                         "with --max_prompt_chunked to offer the "
                         "long-prompt mix chunked admission absorbs")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--mesh", default="1,1",
                    help="serve the engine over a (dp, tp) device mesh "
                         "(serve/sharding.py): 'dp,tp', e.g. '1,4' = "
                         "4-way tensor parallel. Rows gain mesh + "
                         "tok_s_per_chip and a _mesh{dp}x{tp} config "
                         "suffix. On CPU (JAX_PLATFORMS=cpu) the "
                         "8-virtual-device platform is forced "
                         "automatically")
    # --- traffic-scale serving (round 21, DESIGN.md §26) --------------
    ap.add_argument("--prefix_cache", type=int, default=0, choices=[0, 1],
                    help="1 = shared-prefix KV reuse: hashed full-block "
                         "prompt prefixes map refcounted pages, finished "
                         "requests' pages park as a reclaimable cache")
    ap.add_argument("--prefix_pool", type=int, default=0,
                    help="shape the workload around N seeded shared "
                         "prefixes (each request opens with a pool "
                         "member with probability --prefix_frac); 0 = "
                         "fully random prompts. Rows gain a _prefixN "
                         "config suffix")
    ap.add_argument("--prefix_frac", type=float, default=0.7,
                    help="fraction of requests that open with a pool "
                         "prefix when --prefix_pool is set")
    ap.add_argument("--max_prompt_chunked", type=int, default=0,
                    help="TRUE prompt cap under chunked admission "
                         "(block_T multiple > max_prompt): longer "
                         "prompts prefill in static-bucket chunks "
                         "interleaved with decode steps. 0 = off "
                         "(prompts beyond max_prompt reject with "
                         "reason=prompt_too_long)")
    ap.add_argument("--sampling", type=int, default=0, choices=[0, 1],
                    help="1 = per-request temperature/top-k/top-p "
                         "sampling with seeded per-slot PRNG keys "
                         "(same seed => same tokens); rows gain a "
                         "_sampled config suffix")
    # --- serve-fleet routing (round 22, DESIGN.md §27) ----------------
    ap.add_argument("--router", type=int, default=0,
                    help="drive the SAME open-loop load over HTTP "
                         "through tools/serve_router.py with this "
                         "many engine replica processes (0 = direct "
                         "in-process engine). Emits one fleet row per "
                         "rate plus per-replica rows; --telemetry_out "
                         "becomes the router stream base — replay the "
                         "session with tools/trace_export.py --router")
    ap.add_argument("--router_baseline", type=int, default=0,
                    choices=[0, 1],
                    help="with --router: first run the identical "
                         "workload on ONE in-process engine and stamp "
                         "the fleet row with baseline_ttft_p99_ms + "
                         "the ttft_p99_vs_baseline ratio")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry_out", default="")
    ap.add_argument("--out", default="",
                    help="append rows to this JSON artifact")
    ap.add_argument("--run_registry", default="",
                    help="append-only run registry stream (core/"
                         "run_registry.py): one crash-safe record per "
                         "bench invocation; default $MFT_RUN_REGISTRY, "
                         "empty = off")
    # --- robustness / fault harness (round 14, DESIGN.md §19) ---------
    ap.add_argument("--max_queue", type=int, default=0,
                    help="bounded admission: cap the FCFS queue; "
                         "over-limit submits reject with "
                         "reason=queue_full (0 = unbounded)")
    ap.add_argument("--shed_policy", default="reject",
                    choices=["reject", "deadline"],
                    help="on a full queue: reject the newest arrival, "
                         "or shed the queued request closest to "
                         "blowing its deadline")
    ap.add_argument("--deadline_ms", type=float, default=0.0,
                    help="per-request end-to-end deadline; expired "
                         "queued requests never prefill, active ones "
                         "stop at the next step boundary (0 = none)")
    ap.add_argument("--on_step_error", default="fail_active",
                    choices=["fail_active", "raise"],
                    help="contain a step-dispatch exception (fail the "
                         "in-flight requests, keep serving) or re-raise "
                         "after containing")
    ap.add_argument("--hbm_cap_mb", type=int, default=0,
                    help="memory-admission capacity override for the "
                         "engine's build-time preflight (DESIGN.md "
                         "§21); an infeasible num_blocks/num_slots "
                         "is refused with the max feasible values "
                         "named. 0 = auto")
    ap.add_argument("--hbm_headroom", type=float, default=0.1,
                    help="admission margin for the build preflight")
    ap.add_argument("--stats_every", type=int, default=0,
                    help="emit a serve_stats health snapshot every N "
                         "decode steps (0 = off)")
    # --- live observability (round 17, DESIGN.md §22) -----------------
    ap.add_argument("--trace_spans", type=int, default=0, choices=[0, 1],
                    help="1 = emit per-request queue/prefill/decode "
                         "`span` events (track req:<id>) into the "
                         "telemetry stream; tools/trace_export.py "
                         "renders the session as one Perfetto timeline")
    ap.add_argument("--metrics_port", type=int, default=0,
                    help="serve a live OpenMetrics /metrics endpoint + "
                         "/healthz (engine.health()) on this port, fed "
                         "from the engine's telemetry emit path "
                         "(core/metrics_http.py); scraping can never "
                         "cost a retrace. 0 = off")
    ap.add_argument("--metrics_addr", default="127.0.0.1",
                    help="bind address for --metrics_port (loopback by "
                         "default)")
    ap.add_argument("--inject", default="",
                    help="fault harness: step_error:<n> | hang:<n>[:<s>]"
                         " | slow_step:<n>:<ms> | adapter_load_fail")
    ap.add_argument("--drain", type=int, default=1, choices=[0, 1],
                    help="arm SIGTERM graceful drain (finish in-flight, "
                         "reject queue with reason=shutdown, "
                         "run_end{reason=preempted}; second signal "
                         "cancels in-flight)")
    ap.add_argument("--watchdog", type=int, default=0, choices=[0, 1, 2],
                    help="hang watchdog over the serve loop: 1 = report "
                         "(`hang` event) and keep waiting, 2 = report "
                         "then abort (exit 113)")
    ap.add_argument("--watchdog_min_s", type=float, default=60.0,
                    help="watchdog deadline floor (and pre-first-step "
                         "grace) in seconds")
    args = ap.parse_args(argv)
    from mobilefinetuner_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    model = "gemma270m" if args.gemma else args.model
    if args.inject == "adapter_load_fail" and not args.adapters:
        raise SystemExit("--inject adapter_load_fail needs --adapters k")
    try:
        mesh_dp, mesh_tp = (int(v) for v in args.mesh.split(","))
    except ValueError:
        raise SystemExit(f"--mesh must be 'dp,tp', got {args.mesh!r}")
    if mesh_dp * mesh_tp > 1 \
            and os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # the CPU contract path: virtual devices must exist before the
        # first backend init (tests/conftest.py does the same)
        from mobilefinetuner_tpu.parallel.host_devices import \
            force_host_devices
        force_host_devices(max(8, mesh_dp * mesh_tp))
    # run registry (core/run_registry.py, DESIGN.md §28): one
    # crash-safe record per bench invocation. Admission rejects and
    # fault-harness aborts finalize with the exception's name via the
    # handle's __exit__; a SIGKILL mid-run settles to "interrupted"
    # on the next registry open.
    import contextlib
    from mobilefinetuner_tpu.core.run_registry import RunRegistry
    _reg = RunRegistry.from_args(args)
    run_rec = _reg.begin(
        "serve", "serve_bench", config=vars(args),
        # a --router parent stays off the backend until its fleet is
        # down (the replicas own the chips), so it cannot name one here
        platform=None if args.router > 0 else jax.devices()[0].platform,
        mesh=({"data": mesh_dp, "model": mesh_tp}
              if mesh_dp * mesh_tp > 1 else None),
        artifacts=[p for p in (args.telemetry_out, args.out)
                   if p]) if _reg else None
    with run_rec if run_rec is not None else contextlib.nullcontext():
        if args.router > 0:
            if args.inject:
                raise SystemExit("--router composes with --inject only by "
                                 "killing replica processes (see the "
                                 "kill-one-replica e2e); drop --inject")
            if mesh_dp * mesh_tp > 1:
                raise SystemExit("--router replicas are single-host "
                                 "engines (data parallelism IS the "
                                 "replica set); drop --mesh")
            base = args.telemetry_out
            if not base:
                import tempfile
                base = os.path.join(
                    tempfile.mkdtemp(prefix="serve_fleet_"), "fleet.jsonl")
                print(f"--router: telemetry stream at {base} "
                      f"(pass --telemetry_out to choose)")
            rows = run_router_rows(
                model, args.rate, args.requests, args.adapters,
                args.router, base, num_slots=args.num_slots,
                block_T=args.block_T, num_blocks=args.num_blocks,
                max_prompt=args.max_prompt, max_new=args.max_new,
                dtype=args.dtype, seed=args.seed,
                prompt_lo=args.prompt_lo, prompt_hi=args.prompt_hi,
                max_queue=args.max_queue, shed_policy=args.shed_policy,
                stats_every=args.stats_every or 10,
                prefix_cache=bool(args.prefix_cache),
                max_prompt_chunked=args.max_prompt_chunked,
                sampling=bool(args.sampling),
                prefix_pool=args.prefix_pool,
                prefix_frac=args.prefix_frac,
                deadline_ms=args.deadline_ms or None)
            if args.router_baseline:
                # AFTER the fleet is down: the in-process baseline
                # engine needs the chip the replicas were holding
                brows = run_rows(
                    model, args.rate, args.requests, args.adapters,
                    num_slots=args.num_slots, block_T=args.block_T,
                    num_blocks=args.num_blocks, max_prompt=args.max_prompt,
                    max_new=args.max_new, dtype=args.dtype, seed=args.seed,
                    prompt_lo=args.prompt_lo, prompt_hi=args.prompt_hi,
                    max_queue=args.max_queue, shed_policy=args.shed_policy,
                    deadline_ms=args.deadline_ms or None,
                    prefix_cache=bool(args.prefix_cache),
                    max_prompt_chunked=args.max_prompt_chunked,
                    sampling=bool(args.sampling),
                    prefix_pool=args.prefix_pool,
                    prefix_frac=args.prefix_frac)
                stamp_baseline(rows, {r["offered_rps"]: r["ttft_ms"]["p99"]
                                      for r in brows})
                rows = brows + rows
        else:
            rows = run_rows(model, args.rate, args.requests, args.adapters,
                            num_slots=args.num_slots, block_T=args.block_T,
                            num_blocks=args.num_blocks,
                            max_prompt=args.max_prompt, max_new=args.max_new,
                            dtype=args.dtype, seed=args.seed,
                            prompt_lo=args.prompt_lo, prompt_hi=args.prompt_hi,
                            telemetry_out=args.telemetry_out,
                            max_queue=args.max_queue,
                            shed_policy=args.shed_policy,
                            on_step_error=args.on_step_error,
                            deadline_ms=args.deadline_ms or None,
                            stats_every=args.stats_every, inject=args.inject,
                            drain=bool(args.drain),
                            watchdog_mode=args.watchdog,
                            watchdog_min_s=args.watchdog_min_s,
                            hbm_cap_mb=args.hbm_cap_mb,
                            hbm_headroom=args.hbm_headroom,
                            trace_spans=bool(args.trace_spans),
                            metrics_port=args.metrics_port,
                            metrics_addr=args.metrics_addr,
                            mesh_dp=mesh_dp, mesh_tp=mesh_tp,
                            prefix_cache=bool(args.prefix_cache),
                            max_prompt_chunked=args.max_prompt_chunked,
                            sampling=bool(args.sampling),
                            prefix_pool=args.prefix_pool,
                            prefix_frac=args.prefix_frac)
        if args.out:
            art = {"device": jax.devices()[0].device_kind,
                   "jax": jax.__version__, "rows": []}
            if os.path.exists(args.out):
                with open(args.out) as f:
                    art = json.load(f)
            art["rows"].extend(rows)
            tmp = args.out + ".tmp"
            with open(tmp, "w") as f:
                json.dump(art, f, indent=1)
            os.replace(tmp, args.out)
        bad = [] if args.inject else rows_with_errors(rows)
        if bad:
            raise SystemExit(
                f"FAILED: requests ended in state `error` with no fault "
                f"injected, in {bad} — a step could not run")
        return 0


if __name__ == "__main__":
    sys.exit(main())
