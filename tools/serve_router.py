"""Serve-fleet front door: metrics-driven routing over N engine
replicas (ROADMAP item 2's remainder, DESIGN.md §27).

One process per replica runs a single-threaded ServeEngine behind the
round-17 MetricsServer (`--serve_replica` mode below): /metrics and
/healthz as before, plus two JSON data-plane routes — POST /submit
queues a request for the engine's main loop, POST /collect drains the
terminal results the loop has produced. The ROUTER process supervises
those replicas with the r13 fleet-controller restart/backoff machinery,
scrapes every replica's /metrics + /healthz on a cadence, and places
each arriving request by policy:

  affinity      the request names an adapter and some healthy replica
                holds it resident — route least-loaded WITHIN those
                (LoRAFusion's job-level batching instinct: tenants keep
                hitting warm banks and warm prefix caches)
  least_loaded  no adapter (or nobody holds it): least queue_depth +
                active over every fresh, non-draining snapshot
  failover      the chosen replica refused or was unreachable (it died
                between scrape and forward, or is mid-drain) — walk the
                remaining candidates; also stamped on requests re-routed
                off a dead replica
  reject        no routable replica at all: the router answers 503

Every decision is a `route` telemetry event in the ROUTER's stream
(`<base>` — the coordinator shard; replicas write `<base>.host<k>`,
controller events `<base>.controller`), and every routed request gets a
fleet-wide `rid` that rides submit() into the replica's `request`
events and `req:<id>` span track, so `trace_export --router` can join
the router's queue/route spans to the replica-side lifecycle in one
Perfetto timeline.

Replica death is settled from the SHARD, not from memory: Telemetry
flushes per event, so a SIGKILLed replica's shard still names every
request it terminated. The router tails each shard live
(ServeShardTail); on an exit, inflight rids the shard settled are
delivered from the shard record, and ONLY the remainder is re-routed
to survivors — no request is lost and none can double-terminate.

The router serves its own MetricsRegistry: per-replica labeled gauges
(`mft_fleet_*{replica="k"}`) refreshed by the scrape, fleet-level
TTFT/TPOT/queue-wait histograms folded from collected results, and the
`route` decision counter from its own stream.

Usage:
  python tools/serve_router.py --telemetry /tmp/fleet.jsonl \\
      --replicas 2 --engine_json '{"model": "tiny-gpt2"}' --port 0
  # front door: POST /submit {"prompt": [...], "adapter": "tenant0"}
  #             POST /collect {} -> {"done": [...]}
  #             GET  /fleet -> supervision snapshot (pids, ports)
"""

from __future__ import annotations

import argparse
import json
import os
import shlex
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, __file__.rsplit("/", 2)[0])
sys.path.insert(0, __file__.rsplit("/", 1)[0])

from fleet_controller import FleetController, ShardTail, _W  # noqa: E402

from mobilefinetuner_tpu.core.metrics_http import (MetricsRegistry,  # noqa: E402
                                                   MetricsServer)
from mobilefinetuner_tpu.core.preempt import EXIT_PREEMPTED  # noqa: E402
from mobilefinetuner_tpu.core.telemetry import (Telemetry,  # noqa: E402
                                                shard_path)
from mobilefinetuner_tpu.core.trace import Tracer  # noqa: E402

# lock-discipline declaration (core/static_checks.py, DESIGN.md §24).
# Three shared surfaces, one lock each:
#   ReplicaGateway  HTTP handler threads push submits / pop results;
#                   the engine's single main thread pumps between them
#   ScrapeCache     the scrape thread writes snapshots; handler threads
#                   (routing decisions) and the main loop read them
#   RouterCore      handler threads stamp rids and track inflight; the
#                   collector thread and the supervision loop resolve
GRAFT_SHARED_STATE = {
    "ReplicaGateway": {
        "lock": "_lock",
        "guarded": ["_inbox", "_outbox", "_draining"],
        "locked_helpers": [],
        "channels": [],
        "note": "submit/collect ride the MetricsServer handler threads; "
                "pump() is the engine main loop's only touchpoint",
    },
    "ScrapeCache": {
        "lock": "_lock",
        "guarded": ["_snap"],
        "locked_helpers": [],
        "channels": [],
        "note": "whole-snapshot copies in and out; readers never see a "
                "half-written replica entry",
    },
    "RouterCore": {
        "lock": "_lock",
        "guarded": ["_next_rid", "_inflight", "_results", "_closed",
                    "routed"],
        "locked_helpers": [],
        "channels": [],
        "note": "the rid counter and the inflight/results maps are the "
                "exact-accounting invariant: a rid moves inflight -> "
                "results exactly once, whichever thread settles it",
    },
}

_TERMINAL_PHASES = ("finish", "cancel", "reject", "timeout", "error")

DEFAULT_ENGINE_SPEC = {
    # tiny-gpt2 has n_positions=64: max_prompt + max_new must fit
    "model": "tiny-gpt2", "num_slots": 4, "block_T": 16,
    "num_blocks": 64, "max_prompt": 32, "max_new": 16, "adapters": 0,
    "dtype": "float32", "seed": 0, "max_queue": 0,
    "shed_policy": "reject", "on_step_error": "fail_active",
    # serve_stats on a cadence (the scrape's gauge source) and request
    # spans on (the --router timeline's replica half) by default
    "stats_every": 10, "trace_spans": True,
    "prefix_cache": False, "max_prompt_chunked": 0, "sampling": False,
}


# --------------------------- small plumbing ---------------------------------

def _http_json(method: str, url: str, payload=None, timeout: float = 5.0
               ) -> Tuple[int, dict]:
    """One JSON round trip; non-2xx responses return their code + body
    instead of raising (a draining replica's 503 carries information)."""
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            body = r.read().decode()
            code = r.status
    except urllib.error.HTTPError as e:
        body = e.read().decode()
        code = e.code
    try:
        obj = json.loads(body) if body else {}
    except json.JSONDecodeError:
        obj = {}
    return code, obj if isinstance(obj, dict) else {}


def _http_text(url: str, timeout: float = 5.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def port_file(base: str, host: int) -> str:
    """Where process `host` publishes its bound HTTP port (0 = the
    router's front door, k >= 1 a replica) — ports are ephemeral by
    default, so discovery rides the telemetry base path."""
    return f"{base}.port{host}"


def write_port_file(base: str, host: int, port: int) -> None:
    tmp = port_file(base, host) + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"host": host, "port": port, "pid": os.getpid()}, f)
    os.replace(tmp, port_file(base, host))


def read_port_file(base: str, host: int) -> Optional[dict]:
    try:
        with open(port_file(base, host)) as f:
            obj = json.load(f)
        return obj if isinstance(obj, dict) and "port" in obj else None
    except (OSError, json.JSONDecodeError):
        return None


def parse_serve_gauges(text: str) -> Dict[str, float]:
    """The scrape's half of the round-17 exposition contract: pull the
    unlabeled `mft_serve_*` gauge samples out of an OpenMetrics body
    (the engine's loop vitals — queue depth, occupancy, free pages,
    p95 step ms, r21 cache counters)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, val = line.rpartition(" ")
        if name.startswith("mft_serve_") and "{" not in name:
            try:
                out[name[len("mft_serve_"):]] = float(val)
            except ValueError:
                pass
    return out


# --------------------------- replica side -----------------------------------

class ReplicaGateway:
    """The replica's HTTP data plane. The engine stays single-threaded:
    handler threads only queue submits into `_inbox` and drain results
    from `_outbox`; the main loop's `pump()` moves everything between
    the lists and the engine."""

    def __init__(self):
        self._lock = threading.Lock()
        self._inbox: List[dict] = []
        self._outbox: List[dict] = []
        self._draining = False

    # -- HTTP routes (handler threads) ---------------------------------------

    def route_submit(self, payload) -> Tuple[int, dict]:
        if not isinstance(payload, dict) or "prompt" not in payload:
            return 400, {"accepted": False, "reason": "bad_request"}
        with self._lock:
            if self._draining:
                return 503, {"accepted": False, "draining": True,
                             "reason": "shutdown"}
            self._inbox.append(payload)
        return 200, {"accepted": True, "rid": payload.get("rid")}

    def route_collect(self, payload) -> Tuple[int, dict]:
        with self._lock:
            out, self._outbox = self._outbox, []
        return 200, {"done": out}

    # -- main-loop side -------------------------------------------------------

    def begin_drain(self) -> None:
        with self._lock:
            self._draining = True

    def outbox_size(self) -> int:
        with self._lock:
            return len(self._outbox)

    @staticmethod
    def summarize(req) -> dict:
        """The collect-row shape: everything the router needs to settle
        a rid and fold fleet SLO histograms, nothing engine-internal."""
        return {
            "rid": req.rid, "id": req.id, "state": req.state,
            "reason": req.reason, "adapter": req.adapter,
            "prompt_tokens": len(req.prompt),
            "new_tokens": len(req.tokens),
            "ttft_ms": req.ttft_ms, "tpot_ms": req.tpot_ms,
            "queue_ms": ((req.admit_t - req.enqueue_t) * 1000.0
                         if req.admit_t else None),
        }

    def push(self, reqs) -> None:
        rows = [self.summarize(r) for r in reqs if r.done]
        if rows:
            with self._lock:
                self._outbox.extend(rows)

    def pump(self, eng) -> bool:
        """One main-loop beat: drain the inbox into submit(), one
        step() when work is pending, terminal results to the outbox.
        Returns whether anything moved (the idle loop sleeps)."""
        with self._lock:
            batch, self._inbox = self._inbox, []
        term = []
        for p in batch:
            try:
                req = eng.submit(
                    p["prompt"],
                    max_new_tokens=int(p.get("max_new_tokens") or 0),
                    adapter=p.get("adapter"),
                    deadline_ms=p.get("deadline_ms"),
                    temperature=float(p.get("temperature") or 0.0),
                    top_k=int(p.get("top_k") or 0),
                    top_p=float(p.get("top_p") if p.get("top_p")
                                is not None else 1.0),
                    seed=int(p.get("seed") or 0),
                    rid=p.get("rid"))
                if req.done:   # submit-time reject (queue_full, ...)
                    term.append(req)
            except (ValueError, KeyError, RuntimeError) as e:
                # a malformed payload fails ONE request, not the
                # replica; no engine record exists, so synthesize the
                # settle row here
                with self._lock:
                    self._outbox.append({
                        "rid": p.get("rid"), "id": None,
                        "state": "error", "reason": type(e).__name__,
                        "adapter": p.get("adapter"), "prompt_tokens": 0,
                        "new_tokens": 0, "ttft_ms": None,
                        "tpot_ms": None, "queue_ms": None})
        moved = bool(batch)
        if not eng.idle:
            term.extend(eng.step())
            moved = True
        self.push(term)
        return moved


def replica_main(args) -> int:
    """`--serve_replica`: one engine process under the router's
    supervision. Builds the engine via serve_bench.build_engine (one
    construction path for bench and fleet), writes its shard at
    shard_path(base, host) with host=<k> envelope stamps, serves
    /metrics + /healthz + /submit + /collect on one ephemeral port
    published through the port file, and drains on SIGTERM exactly
    like a directly-driven engine (queue rejected reason=shutdown,
    in-flight decoded out, run_end{exit=preempted}, exit code 75)."""
    if args.pin_chip:
        # replica k owns chip k-1 of the host, and no other: a chip
        # belongs to one process at a time (set before jax starts)
        from mobilefinetuner_tpu.parallel.host_devices import pin_tpu_chip
        pin_tpu_chip(args.host - 1)
    import serve_bench  # imports jax — replica processes only
    from mobilefinetuner_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    spec = dict(DEFAULT_ENGINE_SPEC)
    with open(args.engine_json) as f:
        spec.update(json.load(f))
    unknown = set(spec) - set(DEFAULT_ENGINE_SPEC)
    if unknown:
        raise SystemExit(f"unknown engine spec keys: {sorted(unknown)}")
    base, k = args.telemetry, args.host
    eng, names = serve_bench.build_engine(
        telemetry_out=shard_path(base, k), host=k, **spec)
    registry = MetricsRegistry()
    eng.telemetry.add_observer(registry.observe)
    # run_start went out while the engine was built, before the observer
    registry.set_gauge("mft_serve_paged_pages_per_step",
                       eng.paged_pages_per_step)
    gw = ReplicaGateway()

    def health():
        # engine.health() already leads with status=draining when
        # admissions are closed — metrics_http turns that into the 503
        # the router's scrape keys on; replica identity and the
        # resident-adapter set ride along for affinity scoring
        return {**eng.health(), "replica": k, "adapters": list(names)}

    server = MetricsServer(registry, port=args.port, addr=args.addr,
                           health_fn=health,
                           routes={"/submit": gw.route_submit,
                                   "/collect": gw.route_collect})
    write_port_file(base, k, server.port)
    guard = eng.install_preemption()
    try:
        while not guard.triggered:
            if not gw.pump(eng) and eng.idle:
                time.sleep(0.002)
        # drain: close the HTTP intake first (new submits 503), then
        # the engine path — queued remainder rejects, in-flight decodes
        # to completion; a second signal escalates out of drain()
        gw.begin_drain()
        gw.push(eng.begin_shutdown())
        try:
            gw.push(eng.drain())
        except KeyboardInterrupt:
            active = list(eng.active)
            for req in active:
                eng.cancel(req)
            gw.push(active)
        # linger briefly so the router's collector can pick up the
        # final rows over HTTP (the shard tail is the fallback if not)
        deadline = time.time() + args.linger_s
        while time.time() < deadline and gw.outbox_size():
            time.sleep(0.02)
    finally:
        server.close()
        eng.close()
        try:
            os.remove(port_file(base, k))
        except OSError:
            pass
    return EXIT_PREEMPTED if guard.triggered else 0


# --------------------------- router side ------------------------------------

def probe_devices(timeout_s: float = 180.0) -> dict:
    """{"platform", "count"} of the accelerator the replicas will find,
    asked in a THROWAWAY child: the router must never initialise a
    backend itself (it would hold the chip its replicas need), and the
    child has released it by the time it exits. With JAX_PLATFORMS=cpu
    there is nothing to contend for and no child is started."""
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return {"platform": "cpu", "count": 0}
    code = ("import jax, json; d = jax.devices(); print(json.dumps("
            "{'platform': d[0].platform, 'count': len(d)}))")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         timeout=timeout_s).stdout
    return json.loads(out.strip().splitlines()[-1])


class ServeShardTail(ShardTail):
    """The r13 shard tail, extended with the serve-fleet fact the
    death-settlement protocol needs: which rids this replica already
    TERMINATED (Telemetry flushes per event, so the shard is durable
    ground truth at SIGKILL — anything it settled must be delivered,
    never re-routed)."""

    def __init__(self, path: str):
        super().__init__(path)
        self.terminal: Dict[int, dict] = {}

    def _see(self, rec: dict) -> None:
        super()._see(rec)
        if rec.get("event") == "request" \
                and isinstance(rec.get("rid"), int) \
                and rec.get("phase") in _TERMINAL_PHASES:
            self.terminal[rec["rid"]] = rec


_PHASE_STATE = {"finish": "finished", "cancel": "cancelled",
                "reject": "rejected", "timeout": "timeout",
                "error": "error"}


def row_from_shard(rec: dict) -> dict:
    """Rebuild a collect-row from a shard `request` record (the
    death-settlement path: the replica died before /collect returned
    this result, but its flushed shard already has the terminal)."""
    return {
        "rid": rec.get("rid"), "id": rec.get("id"),
        "state": _PHASE_STATE.get(rec.get("phase"), "error"),
        "reason": rec.get("reason"), "adapter": rec.get("adapter"),
        "prompt_tokens": rec.get("prompt_tokens"),
        "new_tokens": rec.get("new_tokens") or 0,
        "ttft_ms": rec.get("ttft_ms"), "tpot_ms": rec.get("tpot_ms"),
        "queue_ms": rec.get("queue_ms"),
    }


class ScrapeCache:
    """Latest per-replica scrape snapshot, one lock. A snapshot is one
    dict: {t, port, status, draining, adapters, queue_depth, active,
    occupancy, free_blocks, p95_step_ms, ...} — routing reads a
    whole-cache copy and never blocks the scraper."""

    def __init__(self):
        self._lock = threading.Lock()
        self._snap: Dict[int, dict] = {}

    def put(self, replica: int, snap: dict) -> None:
        with self._lock:
            self._snap[replica] = snap

    def drop(self, replica: int) -> None:
        with self._lock:
            self._snap.pop(replica, None)

    def snapshot(self) -> Dict[int, dict]:
        with self._lock:
            return dict(self._snap)


class RouterCore:
    """Placement decisions + the exact-accounting rid ledger.

    A rid is stamped under the lock, lives in `_inflight` while some
    replica owns it, and moves to `_results` exactly once — settled by
    the collector thread (HTTP /collect), by the supervision loop
    (shard record of a dead replica), or by the router itself (reject).
    `deliver` ignores duplicates, so the shard-settlement path and a
    late /collect row can race without double-terminating."""

    def __init__(self, tel: Telemetry, tracer: Tracer,
                 registry: MetricsRegistry, cache: ScrapeCache,
                 max_age_s: float):
        self._lock = threading.Lock()
        self._next_rid = 0
        self._inflight: Dict[int, dict] = {}
        self._results: Dict[int, dict] = {}
        self._closed = False
        self.routed = 0
        self.tel = tel
        self.tracer = tracer
        self.registry = registry
        self.cache = cache
        self.max_age_s = max_age_s

    # -- intake state ---------------------------------------------------------

    def close_intake(self) -> None:
        with self._lock:
            self._closed = True

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed

    def counts(self) -> dict:
        with self._lock:
            return {"routed": self.routed,
                    "inflight": len(self._inflight),
                    "results_pending": len(self._results)}

    # -- decision -------------------------------------------------------------

    def _candidates(self, now: float) -> List[Tuple[int, dict]]:
        return [(k, s) for k, s in sorted(self.cache.snapshot().items())
                if s.get("status") == "ok" and not s.get("draining")
                and now - s.get("t", 0.0) <= self.max_age_s]

    def _place(self, rid: int, payload: dict, forced_policy: str = ""
               ) -> Tuple[int, dict]:
        """Decide + forward. Returns the HTTP answer for /submit; on
        reject the rid is settled here (every stamped rid gets exactly
        one result, routable or not)."""
        t_in = time.perf_counter()
        now = time.time()
        cands = self._candidates(now)
        adapter = payload.get("adapter")
        pool, policy = cands, "least_loaded"
        if adapter is not None:
            aff = [(k, s) for k, s in cands
                   if adapter in (s.get("adapters") or ())]
            if aff:
                pool, policy = aff, "affinity"
        if forced_policy:
            policy = forced_policy
        # load = the replica's OWN report (queue + active at scrape
        # time) PLUS the requests this router placed there since — the
        # snapshot is stale by up to scrape_s, and without the inflight
        # term a burst between scrapes would all land on one replica
        with self._lock:
            owned: Dict[int, int] = {}
            for info in self._inflight.values():
                r = info.get("replica")
                owned[r] = owned.get(r, 0) + 1
        order = sorted(pool, key=lambda ks:
                       (ks[1].get("queue_depth") or 0)
                       + (ks[1].get("active") or 0)
                       + owned.get(ks[0], 0))
        t_decide = time.perf_counter()
        chosen, snap = None, None
        for k, s in order:
            try:
                code, obj = _http_json(
                    "POST", f"http://127.0.0.1:{s['port']}/submit",
                    dict(payload, rid=rid), timeout=5.0)
            except OSError:
                code, obj = 0, {}
            if code == 200 and obj.get("accepted"):
                chosen, snap = k, s
                break
            # refused or unreachable: the snapshot lied (death or drain
            # since the last scrape) — walk the rest as failover
            policy = "failover"
        if chosen is None:
            self.tel.emit("route", rid=rid, replica=None,
                          policy="reject", adapter=adapter,
                          queue_depth=None, occupancy=None,
                          scrape_age_ms=None, candidates=len(cands))
            self.deliver(rid, None, {
                "rid": rid, "id": None, "state": "rejected",
                "reason": "no_replica", "adapter": adapter,
                "prompt_tokens": len(payload.get("prompt") or []),
                "new_tokens": 0, "ttft_ms": None, "tpot_ms": None,
                "queue_ms": None})
            return 503, {"accepted": False, "rid": rid,
                         "reason": "no_replica"}
        t_ack = time.perf_counter()
        with self._lock:
            self._inflight[rid] = {"replica": chosen,
                                   "payload": payload, "t": now}
            self.routed += 1
        self.tel.emit("route", rid=rid, replica=chosen, policy=policy,
                      adapter=adapter,
                      queue_depth=snap.get("queue_depth"),
                      occupancy=snap.get("occupancy"),
                      scrape_age_ms=round(
                          (now - snap.get("t", now)) * 1000.0, 3),
                      candidates=len(cands))
        # the router half of the request timeline: ingress->decision
        # ("queue") and decision->forward-ack ("route") on the rid's
        # own track, reconciled against the replica's req:<id> spans
        # by trace_export --router
        self.tracer.emit_span("queue", f"req:{rid}", t_in,
                              (t_decide - t_in) * 1000.0,
                              rid=rid, replica=chosen)
        self.tracer.emit_span("route", f"req:{rid}", t_decide,
                              (t_ack - t_decide) * 1000.0,
                              rid=rid, replica=chosen, policy=policy)
        return 200, {"accepted": True, "rid": rid, "replica": chosen,
                     "policy": policy}

    # -- HTTP routes (handler threads) ---------------------------------------

    def route_submit(self, payload) -> Tuple[int, dict]:
        if not isinstance(payload, dict) or "prompt" not in payload:
            return 400, {"accepted": False, "reason": "bad_request"}
        with self._lock:
            if self._closed:
                return 503, {"accepted": False, "draining": True,
                             "reason": "shutdown"}
            rid = self._next_rid
            self._next_rid += 1
        return self._place(rid, payload)

    def route_collect(self, payload) -> Tuple[int, dict]:
        with self._lock:
            out = [self._results[r] for r in sorted(self._results)]
            self._results.clear()
            pending = len(self._inflight)
        return 200, {"done": out, "inflight": pending}

    # -- settlement -----------------------------------------------------------

    def deliver(self, rid, replica, row: dict) -> bool:
        """Settle one rid (idempotent: the first settle wins). Folds
        the fleet SLO histograms the router's /metrics exposes."""
        if not isinstance(rid, int):
            return False
        with self._lock:
            self._inflight.pop(rid, None)
            if rid in self._results:
                return False
            self._results[rid] = dict(row, rid=rid, replica=replica)
        self.registry.inc("mft_fleet_requests",
                          state=str(row.get("state")))
        if row.get("state") == "finished":
            self.registry.observe_hist("mft_fleet_ttft_ms",
                                       row.get("ttft_ms"))
            self.registry.observe_hist("mft_fleet_tpot_ms",
                                       row.get("tpot_ms"))
            self.registry.observe_hist("mft_fleet_queue_wait_ms",
                                       row.get("queue_ms"))
        return True

    def take_inflight(self, replica: int) -> Dict[int, dict]:
        """Pop every inflight rid owned by `replica` (its death is
        being settled); the caller delivers or re-routes each."""
        with self._lock:
            mine = {rid: info for rid, info in self._inflight.items()
                    if info.get("replica") == replica}
            for rid in mine:
                del self._inflight[rid]
        return mine

    def reroute(self, rid: int, payload: dict) -> None:
        """Re-place an orphaned rid on a survivor (policy=failover,
        SAME rid — the replica-side lifecycle restarts, the fleet-wide
        identity does not)."""
        self._place(rid, payload, forced_policy="failover")


class ServeRouter:
    """The router process: front-door HTTP + scrape/collect threads +
    the supervision loop (a FleetController with serve-aware shard
    tails and replica workers keyed 1..k)."""

    def __init__(self, args):
        self.args = args
        base = args.telemetry
        self.base = base
        # replica launch spec rides a FILE, not the cmd template — the
        # controller formats cmd with str.format, and JSON braces in
        # the template would be parsed as fields
        spec = dict(DEFAULT_ENGINE_SPEC)
        if args.engine_json:
            raw = (args.engine_json if args.engine_json.lstrip()
                   .startswith("{") else open(args.engine_json).read())
            spec.update(json.loads(raw))
        unknown = set(spec) - set(DEFAULT_ENGINE_SPEC)
        if unknown:
            raise SystemExit(
                f"unknown engine spec keys: {sorted(unknown)}")
        self.spec = spec
        # one process per chip: on a TPU host replica k pins chip k-1,
        # and a fleet larger than the host's chip count is refused HERE
        # — unpinned or oversubscribed replicas would fail or hang at
        # backend start, long after the router reported itself up
        dev = probe_devices()
        pin = dev["platform"] == "tpu"
        if pin and args.replicas > dev["count"]:
            raise SystemExit(
                f"--replicas {args.replicas} exceeds this host's "
                f"{dev['count']} TPU chip(s): one replica process per "
                f"chip")
        self.spec_path = f"{base}.engcfg.json"
        with open(self.spec_path, "w") as f:
            json.dump(spec, f)
        cmd = (f"{shlex.quote(sys.executable)} "
               f"{shlex.quote(os.path.abspath(__file__))} "
               f"--serve_replica --host {{host}} "
               f"--telemetry {shlex.quote(base)} "
               f"--engine_json {shlex.quote(self.spec_path)} "
               f"--port 0 --linger_s {args.linger_s}"
               + (" --pin_chip" if pin else ""))
        self.fc = FleetController(argparse.Namespace(
            telemetry=base, cmd=cmd, hosts=args.replicas,
            restart_budget=args.restart_budget,
            backoff_s=args.backoff_s, resume_flags="",
            resume_first=False, allow_shrink=False, min_hosts=1,
            kill_on_hang=0, drain_timeout_s=args.drain_timeout_s,
            poll_s=args.poll_s, max_wall_s=args.max_wall_s))
        # replicas are hosts 1..k (host 0 is the router's own shard);
        # re-key the controller's worker table accordingly, with the
        # serve-aware tail that tracks per-rid terminals
        self.fc.workers = {
            h: _W(h, ServeShardTail(shard_path(base, h)))
            for h in range(1, args.replicas + 1)}
        self.tel = Telemetry(base, host=0)
        self.tracer = Tracer(sink=self.tel.emit)
        self.registry = MetricsRegistry()
        self.tel.add_observer(self.registry.observe)
        self.cache = ScrapeCache()
        self.core = RouterCore(self.tel, self.tracer, self.registry,
                               self.cache, args.scrape_max_age_s)
        self._stop = threading.Event()
        self.server: Optional[MetricsServer] = None

    # -- scrape ---------------------------------------------------------------

    def scrape_once(self) -> None:
        for h, w in self.fc.workers.items():
            pf = read_port_file(self.base, h)
            if pf is None:
                self.cache.drop(h)
                self.registry.set_gauge("mft_fleet_up", 0,
                                        replica=str(h))
                continue
            port = pf["port"]
            try:
                code, hz = _http_json(
                    "GET", f"http://127.0.0.1:{port}/healthz",
                    timeout=self.args.scrape_timeout_s)
                gauges = parse_serve_gauges(_http_text(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=self.args.scrape_timeout_s))
            except OSError:
                self.cache.drop(h)
                self.registry.set_gauge("mft_fleet_up", 0,
                                        replica=str(h))
                continue
            snap = {
                "t": time.time(), "port": port,
                "status": hz.get("status", "ok" if code == 200
                                 else "unreachable"),
                "draining": bool(hz.get("draining")),
                "adapters": hz.get("adapters") or [],
                "queue_depth": hz.get("queue_depth"),
                "active": hz.get("active"),
                "occupancy": hz.get("occupancy"),
                "free_blocks": hz.get("free_blocks"),
                "p95_step_ms": hz.get("p95_step_ms"),
            }
            self.cache.put(h, snap)
            self.registry.set_gauge("mft_fleet_up",
                                    1 if snap["status"] == "ok" else 0,
                                    replica=str(h))
            for f in ("queue_depth", "active", "occupancy",
                      "free_blocks", "p95_step_ms", "prefix_hit_rate",
                      "cow_copies", "blocks_in_use", "pool_occupancy"):
                v = gauges.get(f)
                if v is None and f in snap:
                    v = snap[f]
                self.registry.set_gauge(f"mft_fleet_{f}", v,
                                        replica=str(h))

    def _scrape_loop(self) -> None:
        while not self._stop.is_set():
            self.scrape_once()
            self._stop.wait(self.args.scrape_s)

    # -- collect --------------------------------------------------------------

    def collect_once(self) -> int:
        settled = 0
        for h, snap in sorted(self.cache.snapshot().items()):
            try:
                _, obj = _http_json(
                    "POST",
                    f"http://127.0.0.1:{snap['port']}/collect", {},
                    timeout=self.args.scrape_timeout_s)
            except OSError:
                continue
            for row in obj.get("done") or []:
                if self.core.deliver(row.get("rid"), h, row):
                    settled += 1
        return settled

    def _collect_loop(self) -> None:
        while not self._stop.is_set():
            self.collect_once()
            self._stop.wait(self.args.collect_s)

    # -- supervision ----------------------------------------------------------

    def settle_replica_down(self, w: _W) -> None:
        """A replica process exited (crash, SIGKILL, drain): the shard
        is ground truth. Deliver every inflight rid the shard already
        terminated; re-route the rest to survivors under the SAME rid.
        Runs BEFORE handle_exit so the restart policy sees a settled
        ledger."""
        self.cache.drop(w.host)
        try:
            os.remove(port_file(self.base, w.host))
        except OSError:
            pass
        orphans = self.core.take_inflight(w.host)
        rerouted = delivered = 0
        for rid, info in sorted(orphans.items()):
            rec = w.tail.terminal.get(rid)
            if rec is not None:
                self.core.deliver(rid, w.host, row_from_shard(rec))
                delivered += 1
            else:
                self.core.reroute(rid, info["payload"])
                rerouted += 1
        if orphans:
            print(f"router: replica {w.host} down with "
                  f"{len(orphans)} inflight — {delivered} settled "
                  f"from shard, {rerouted} rerouted", flush=True)

    def health(self) -> dict:
        snaps = self.cache.snapshot()
        ready = sorted(k for k, s in snaps.items()
                       if s.get("status") == "ok"
                       and not s.get("draining"))
        status = ("draining" if self.core.closed
                  else "ok" if ready else "starting")
        return {"status": status, "replicas": self.args.replicas,
                "ready": ready, **self.core.counts()}

    def fleet_info(self, payload) -> Tuple[int, dict]:
        snaps = self.cache.snapshot()
        reps = {}
        for h, w in sorted(self.fc.workers.items()):
            s = snaps.get(h) or {}
            reps[str(h)] = {
                "pid": (w.proc.pid if w.proc is not None else None),
                "port": s.get("port"),
                "status": s.get("status"),
                "attempts": w.attempts,
                "terminal_seen": len(w.tail.terminal),
            }
        return 200, {"replicas": reps, **self.core.counts()}

    # -- lifecycle ------------------------------------------------------------

    def run(self) -> int:
        args = self.args
        self.tel.emit("run_start", jax_version="n/a", mesh_shape=None,
                      process_count=args.replicas + 1, process_index=0,
                      device_kind="router", device_count=0,
                      config={"replicas": args.replicas,
                              "engine": self.spec,
                              "scrape_s": args.scrape_s,
                              "scrape_max_age_s": args.scrape_max_age_s})
        self.server = MetricsServer(
            self.registry, port=args.port, addr=args.addr,
            health_fn=self.health,
            routes={"/submit": self.core.route_submit,
                    "/collect": self.core.route_collect,
                    "/fleet": self.fleet_info})
        write_port_file(self.base, 0, self.server.port)
        print(f"router: front door http://{self.server.addr}:"
              f"{self.server.port} (replicas {args.replicas})",
              flush=True)
        threads = [threading.Thread(target=self._scrape_loop,
                                    name="router-scrape", daemon=True),
                   threading.Thread(target=self._collect_loop,
                                    name="router-collect", daemon=True)]
        t0 = time.perf_counter()
        try:
            for w in self.fc.workers.values():
                self.fc.spawn(w)
                self.fc.record("launch", worker=w.host)
            for t in threads:
                t.start()
            while not self.fc.guard.triggered:
                if args.max_wall_s and \
                        time.perf_counter() - t0 > args.max_wall_s:
                    break
                for w in self.fc.workers.values():
                    if w.done or w.lost:
                        continue
                    if w.proc is None:
                        if w.relaunch_at is not None:
                            self.fc.maybe_relaunch(w)
                        continue
                    w.tail.poll()
                    rc = w.proc.poll()
                    if rc is not None:
                        w.tail.poll()  # the exit's flushed tail
                        self.settle_replica_down(w)
                        self.fc.handle_exit(w, rc)
                time.sleep(args.poll_s)
            return self.shutdown()
        finally:
            self._stop.set()
            self.tel.close()
            if self.server is not None:
                self.server.close()
            try:
                os.remove(port_file(self.base, 0))
            except OSError:
                pass

    def shutdown(self) -> int:
        """Drain the fleet: intake closed (front door answers 503),
        replicas SIGTERMed (their own drain contract finishes in-flight
        work), then every still-inflight rid settled from the flushed
        shards — exact accounting holds through shutdown."""
        self.core.close_intake()
        self.fc.record("drain",
                       reason=self.fc.guard.signal_name or "SIGTERM")
        self.fc.signal_all(signal.SIGTERM)
        self.fc.wait_all(self.args.drain_timeout_s)
        # one last HTTP sweep happens implicitly via the collector up
        # to _stop; the authoritative sweep is the shard tails
        for w in self.fc.workers.values():
            w.tail.poll()
            for rid, info in sorted(
                    self.core.take_inflight(w.host).items()):
                rec = w.tail.terminal.get(rid)
                self.core.deliver(
                    rid, w.host,
                    row_from_shard(rec) if rec is not None else {
                        "rid": rid, "id": None, "state": "cancelled",
                        "reason": "shutdown", "adapter":
                        info["payload"].get("adapter"),
                        "prompt_tokens": len(
                            info["payload"].get("prompt") or []),
                        "new_tokens": 0, "ttft_ms": None,
                        "tpot_ms": None, "queue_ms": None})
        counts = self.core.counts()
        self.tel.emit("run_end", steps=counts["routed"],
                      wall_s=round(time.time() - self.fc.t0, 3),
                      exit="preempted" if self.fc.guard.triggered
                      else "ok", goodput=None,
                      reason="preempted" if self.fc.guard.triggered
                      else None)
        self.fc.record("stop",
                       reason=f"drained: {counts['routed']} routed")
        self.fc.guard.uninstall()
        self.fc.tel.close()
        return 0


# --------------------------- entry point ------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="serve_router",
        description="metrics-driven router over N serve-engine "
                    "replicas (DESIGN.md §27)")
    ap.add_argument("--telemetry", required=True,
                    help="telemetry base: router stream at <base>, "
                         "replica shards at <base>.host<k>, controller "
                         "events at <base>.controller, port files at "
                         "<base>.port<k>")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--engine_json", default="",
                    help="replica engine spec: inline JSON or a path "
                         "(keys = serve_bench.build_engine args; "
                         "defaults are the tiny CPU engine)")
    ap.add_argument("--port", type=int, default=0,
                    help="front-door port (0 = ephemeral; the bound "
                         "port is published at <base>.port0)")
    ap.add_argument("--addr", default="127.0.0.1")
    ap.add_argument("--scrape_s", type=float, default=0.2,
                    help="replica /metrics + /healthz scrape cadence")
    ap.add_argument("--scrape_max_age_s", type=float, default=5.0,
                    help="snapshots older than this are not routable")
    ap.add_argument("--scrape_timeout_s", type=float, default=2.0)
    ap.add_argument("--collect_s", type=float, default=0.05,
                    help="replica /collect poll cadence")
    ap.add_argument("--restart_budget", type=int, default=3)
    ap.add_argument("--backoff_s", type=float, default=0.25)
    ap.add_argument("--drain_timeout_s", type=float, default=20.0)
    ap.add_argument("--poll_s", type=float, default=0.02)
    ap.add_argument("--max_wall_s", type=float, default=0.0,
                    help="safety net: drain past this wall time "
                         "(0 = run until SIGTERM)")
    ap.add_argument("--linger_s", type=float, default=0.5,
                    help="replica drain: wait this long for the final "
                         "outbox to be collected over HTTP before "
                         "exiting (the shard is the fallback)")
    # replica mode (spawned by the router; not for direct use)
    ap.add_argument("--serve_replica", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--host", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--pin_chip", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.serve_replica:
        if args.host < 1:
            ap.error("--serve_replica needs --host >= 1")
        if not args.engine_json or not os.path.exists(args.engine_json):
            ap.error("--serve_replica needs --engine_json <path>")
        return replica_main(args)
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    return ServeRouter(args).run()


if __name__ == "__main__":
    sys.exit(main())
