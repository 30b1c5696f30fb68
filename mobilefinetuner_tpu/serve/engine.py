"""Continuous-batching serve loop: static slots, paged KV, adapter routing.

The scheduler's unit of work is one DECODE STEP over `num_slots` static
batch slots. Requests are admitted into free slots (one prefill each)
and evicted the step they finish — occupancy changes every step, but
every array the compiled step sees keeps its shape:

    tok [S]      the token each slot feeds this step
    pos [S]      its cache position (tokens already cached)
    tbl [S, M]   per-slot block tables into the shared page pool
    aid [S]      per-slot adapter index into the resident bank

Idle slots are not branches, they are DATA: pos=0, tbl=trash, and their
outputs are ignored on the host. That is the compile-stability
invariant the whole design serves — after warmup (one prefill trace +
one step trace) admissions, evictions, and adapter hot-swaps reuse the
same two executables (tests/test_serve.py asserts <= 2 traces after
warmup; `trace_counts` is the observable).

Greedy decode (temperature 0) is the bit-exact oracle: per-request
outputs are token-identical to batch-at-a-time generate() with the
same adapter (the paged-vs-contiguous parity suite) — deterministic
outputs are what make a serving rollout auditable. Round 21 adds
per-slot SAMPLING as data: temperature/top-k/top-p and a seeded
per-request PRNG key ride the slot arrays, the key is folded with the
emitted token's ABSOLUTE position (so cache on/off and chunked/
unchunked admission draw the identical stream for the same seed), and
rows with temperature <= 0 still take the greedy argmax inside the
same compiled step — one executable serves mixed greedy/sampled slots.

Round 21 (DESIGN.md §26) scales the plane to shared traffic:

  - shared-prefix KV reuse: full prompt blocks are chain-hashed
    (content + KV-producing weight identity) into a PrefixCache;
    requests with a common prefix map the SAME refcounted
    physical pages, copy-on-write at the divergence block, freed on
    last reference — pages whose contents are still cached PARK
    (reclaimable LRU-first) instead of freeing, so a finished
    request's prompt pages become the next request's prefix hit;
  - chunked prefill admission: prompts beyond max_prompt (up to
    max_prompt_chunked) — and cache-hit suffixes — prefill in static
    bucket-width chunks under a per-step() token budget of ONE widest
    bucket across the engine, so a long prompt costs the residents
    bounded TPOT jitter, never a head-of-line stall (while concurrent
    short suffixes share a step instead of serializing); chunk widths
    come from a static bucket set, one trace per width, never one per
    prompt length;
  - submit() rejects (reason=prompt_too_long) only beyond the TRUE
    cap max(max_prompt, max_prompt_chunked); everything else queues.

Scheduling policy is FCFS with conservative page reservation: a request
is admitted only when its worst case (prompt + max_new_tokens pages)
fits what the pool has left after every resident's own worst case.
Pages are still handed out LAZILY (alloc at admission for the prompt,
append on page-boundary crossings), so short/eos-early requests return
their tail reservation without ever touching it; the reservation only
guarantees `append` cannot fail mid-flight — there is no preemption
path to need.

Round 14 (DESIGN.md §19) hardens the loop for production traffic — the
serve-side mirror of the training path's sensors-to-recovery discipline:

  - bounded admission: `max_queue` caps the FCFS queue; over-limit
    submits terminate with `request{phase=reject, reason=queue_full}`,
    or `shed_policy="deadline"` drops the queued request closest to
    blowing its own deadline instead of the newest arrival;
  - per-request deadlines: `submit(..., deadline_ms=)` — queued
    requests past deadline are timed out WITHOUT ever prefilling,
    active ones are cancelled at the next step boundary with their
    partial output intact (phase=timeout, slot + pages released);
  - crash containment: a step-dispatch exception fails only the
    in-flight requests (phase=error, reason=<exception type>), resets
    slots and the page pool to a clean empty state, and — under the
    default `on_step_error="fail_active"` — keeps serving the queue;
  - graceful drain: `install_preemption()` arms a
    core/preempt.PreemptionGuard; the first SIGTERM stops admissions,
    rejects the queued remainder (reason=shutdown), finishes in-flight
    requests, and close() records `run_end{exit=preempted,
    reason=preempted}`; a second signal escalates (KeyboardInterrupt)
    so the caller cancels in-flight;
  - health: `health()` snapshots queue depth / occupancy / page
    headroom / rolling p95 step latency / terminal-state counters,
    emitted as cadenced `serve_stats` events under `stats_every`.

Every terminal transition goes through ONE bookkeeping path
(`_terminal`), so a request emits exactly one terminal `request` phase
and releases exactly the pages it allocated — the leak-accounting
invariant tests/test_serve_robustness.py asserts after every injected
fault. None of this touches the compiled programs: rejects, timeouts,
sheds, containment, and drain are host-side bookkeeping, so the ≤2
post-warmup trace invariant holds across every fault path.

PR 34 (DESIGN.md §16): the loop runs ONE DECODE STEP AHEAD of the host.
The compiled step hands back its own next inputs (`nxt`, `pos + 1`), so
a steady turn dispatches step k+1 from device arrays and only then
reads step k's tokens; slot state the host touches (admission, finish,
release, cancel, expiry, containment, shutdown) marks the carried state
stale, and a stale turn folds the step in flight in first and feeds the
next dispatch from the host arrays, as every turn did before. Same
executable, same tokens; `steps_carried` / `steps_uploaded` /
`eos_discarded_tokens` in health() say how often it engages.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from mobilefinetuner_tpu.core.preempt import PreemptionGuard
from mobilefinetuner_tpu.core.telemetry import (HangWatchdog, Telemetry,
                                                run_manifest)
from mobilefinetuner_tpu.lora.lora import assign_adapters
from mobilefinetuner_tpu.models.family import get_family
from mobilefinetuner_tpu.models.generate import (decode_step_paged, prefill,
                                                 prefill_chunk,
                                                 sample_per_row)
from mobilefinetuner_tpu.serve.adapters import AdapterBank
from mobilefinetuner_tpu.serve.paged_kv import (TRASH_BLOCK, BlockAllocator,
                                                OutOfBlocks, blocks_for,
                                                init_pools, pool_layout,
                                                write_prompt_blocks)
from mobilefinetuner_tpu.serve.prefix_cache import PrefixCache, chain_keys

# lock-discipline declaration (core/static_checks.py, DESIGN.md §24):
# the engine is single-threaded BY DESIGN — every mutation happens on
# the serve loop's thread. health() is read from metrics_http handler
# threads, but it only snapshots scalar counters/gauges (torn reads are
# benign: no invariant spans two fields), and the HangWatchdog pet
# rides telemetry's own lock. Any future cross-thread MUTABLE state
# must be declared guarded here, with a real lock.
GRAFT_SHARED_STATE = {
    "ServeEngine": {
        "lock": "_health_lock",
        "guarded": ["_step_ms"],
        "channels": [],
        "note": "single-threaded step loop; health() runs on "
                "metrics_http handler threads (r17) — its deque "
                "iteration shares _health_lock with the loop's append; "
                "every other health() read is a scalar-only snapshot "
                "by contract",
    },
}


def _leaf_dtype(x) -> np.dtype:
    return np.dtype(getattr(x, "dtype", np.float32))


def _is_float(x) -> bool:
    return jnp.issubdtype(_leaf_dtype(x), jnp.floating)


def _resident_params(params, dtype, shardings=None):
    """The base tree as the engine holds it: every floating leaf in
    `dtype`, on `shardings`' placement (a matching tree; None = the
    default device). Leaf by leaf, so beside the cast tree the device
    never holds more than ONE leaf's other-dtype form on the engine's
    account; a host (numpy) leaf is cast on the host and only its cast
    form is transferred. A leaf that already is a device array of
    `dtype` is kept as it is — at float32 an f32 tree costs no copy
    and no op."""
    def place(x, s=None):
        if _is_float(x) and _leaf_dtype(x) != dtype:
            if not isinstance(x, jax.Array):
                # graftlint: disable=sync-hazard(build-time, host leaves only: no device buffer is read)
                x = np.asarray(x)
            x = x.astype(dtype)
        return jnp.asarray(x) if s is None else jax.device_put(x, s)

    if shardings is None:
        return jax.tree.map(place, params)
    return jax.tree.map(place, params, shardings)


@dataclasses.dataclass
class ServeConfig:
    """Engine shape knobs — all STATIC: together they fix the compiled
    prefill/step programs and the pool's HBM footprint."""
    num_slots: int = 8        # concurrent requests per decode step
    block_T: int = 16         # tokens per KV page (sublane-aligned)
    num_blocks: int = 512     # pool pages incl. the reserved trash page
    max_prompt: int = 64      # prompts right-padded to this (block_T mult)
    max_new_tokens: int = 64  # per-request generation cap
    dtype: str = "float32"    # compute + cache dtype, and the dtype
                              # the base weights are HELD in (cast
                              # once at build)
    attn_impl: str = "auto"   # auto | xla | pallas (paged attention path)
    lora_impl: str = "auto"   # auto | naive | fused (models/lora_apply)
    # --- robustness knobs (round 14, DESIGN.md §19) — host-side policy
    # only: none of these reach a traced program, so changing them can
    # never cost a retrace
    max_queue: int = 0        # FCFS queue cap; 0 = unbounded
    shed_policy: str = "reject"   # reject the newest arrival, or
                                  # "deadline": shed the queued request
                                  # closest to blowing its deadline
    on_step_error: str = "fail_active"  # contain a step-dispatch
                                  # exception (fail in-flight, keep
                                  # serving) or "raise" after containing
    stats_every: int = 0      # serve_stats cadence (decode steps); 0=off
    # --- observability (round 17, DESIGN.md §22) ---------------------
    trace_spans: bool = False  # emit queue/prefill/decode `span` events
                              # per request (track "req:<id>") into the
                              # telemetry stream — tools/trace_export.py
                              # renders a serve session as one Perfetto
                              # timeline. Host-side only: span emission
                              # can never cost a retrace.
    # --- memory admission (round 16, core/memory_guard.py) ----------
    hbm_cap_mb: int = 0       # capacity override MB; 0 = auto (the
                              # backend's bytes_limit, else the
                              # device-kind HBM table) — tests drive
                              # the refusal deterministically with it
    hbm_headroom: float = 0.1  # admission margin (same meaning as the
                              # train path's --hbm_headroom)
    # --- mesh sharding (round 20, serve/sharding.py) ----------------
    # the serving step runs under a (dp, tp) device mesh when
    # mesh_dp * mesh_tp > 1: tp shards heads + MLP hidden (and the KV
    # pool's head axis when divisible), dp shards the slot axis;
    # (1, 1) — the default — is the unsharded single-chip engine,
    # bit-for-bit the pre-r20 program. Static: the mesh shape is part
    # of the compiled programs' identity.
    mesh_dp: int = 1
    mesh_tp: int = 1
    # --- traffic-scale serving (round 21, DESIGN.md §26) -------------
    prefix_cache: bool = False  # shared-prefix KV reuse: chain-hash
                              # full prompt blocks, refcount pages,
                              # copy-on-write at the divergence block
    max_prompt_chunked: int = 0  # the TRUE prompt cap under chunked
                              # admission (block_T multiple >
                              # max_prompt); 0 disables chunk-only
                              # admission — prompts beyond max_prompt
                              # then reject with reason=prompt_too_long
    chunk_buckets: tuple = ()  # static chunk widths (block_T
                              # multiples); () auto-derives doubling
                              # widths capped at max_prompt — the
                              # per-dispatch prefill budget the pool
                              # was sized for — so a long prompt walks
                              # SEVERAL chunks with decode steps
                              # between them (bounded in-flight TPOT),
                              # instead of one cap-wide stall. Each
                              # width is ONE compiled executable —
                              # widths bucket, prompt lengths never
                              # retrace.
    sampling: bool = False    # per-slot temperature/top-k/top-p +
                              # seeded PRNG keys ride the slot arrays
                              # as data; False keeps every program
                              # bit-identical to the greedy-only engine

    @property
    def true_cap(self) -> int:
        """The engine's REAL prompt ceiling: max_prompt one-shot, or
        max_prompt_chunked when chunked admission extends it."""
        return max(self.max_prompt, self.max_prompt_chunked)

    def validate(self) -> None:
        from mobilefinetuner_tpu.models.lora_apply import \
            validate_lora_impl
        validate_lora_impl(self.lora_impl)
        if self.max_prompt % self.block_T:
            raise ValueError(
                f"max_prompt ({self.max_prompt}) must be a multiple of "
                f"block_T ({self.block_T})")
        if self.num_slots < 1 or self.max_new_tokens < 1:
            raise ValueError("num_slots and max_new_tokens must be >= 1")
        if self.max_queue < 0 or self.stats_every < 0:
            raise ValueError("max_queue and stats_every must be >= 0")
        if self.shed_policy not in ("reject", "deadline"):
            raise ValueError(
                f"shed_policy must be 'reject' or 'deadline', got "
                f"{self.shed_policy!r}")
        if self.on_step_error not in ("fail_active", "raise"):
            raise ValueError(
                f"on_step_error must be 'fail_active' or 'raise', got "
                f"{self.on_step_error!r}")
        if self.mesh_dp < 1 or self.mesh_tp < 1:
            raise ValueError(
                f"mesh_dp and mesh_tp must be >= 1, got "
                f"({self.mesh_dp}, {self.mesh_tp})")
        if self.mesh_dp > 1 and self.num_slots % self.mesh_dp:
            raise ValueError(
                f"num_slots ({self.num_slots}) must be a multiple of "
                f"mesh_dp ({self.mesh_dp}): the slot axis is the dp "
                f"batch axis")
        if self.max_prompt_chunked:
            if self.max_prompt_chunked % self.block_T:
                raise ValueError(
                    f"max_prompt_chunked ({self.max_prompt_chunked}) "
                    f"must be a multiple of block_T ({self.block_T})")
            if self.max_prompt_chunked <= self.max_prompt:
                raise ValueError(
                    f"max_prompt_chunked ({self.max_prompt_chunked}) "
                    f"must exceed max_prompt ({self.max_prompt}) — "
                    f"prompts within max_prompt prefill one-shot")
        for w in self.chunk_buckets:
            if w < 1 or w % self.block_T:
                raise ValueError(
                    f"chunk_buckets entries must be positive block_T "
                    f"({self.block_T}) multiples, got {w}")
        # the pool must hold at least one worst-case request, or FCFS
        # admission can never fire and drain() spins forever
        worst = blocks_for(self.true_cap + self.max_new_tokens - 1,
                           self.block_T)
        if self.num_blocks - 1 < worst:
            raise ValueError(
                f"num_blocks={self.num_blocks} cannot hold one "
                f"worst-case request: true prompt cap ({self.true_cap})"
                f" + max_new_tokens - 1 columns need {worst} pages "
                f"plus the reserved trash page (have "
                f"{self.num_blocks - 1} allocatable)")


@dataclasses.dataclass
class Request:
    """One generation request and its telemetry timeline."""
    id: int
    prompt: List[int]
    max_new_tokens: int
    adapter: Optional[str] = None      # resident bank name; None = base
    # lifecycle: queued -> active -> one of the TERMINAL states
    # (finished | cancelled | rejected | timeout | error); queued
    # requests can reach rejected/timeout without ever becoming active
    state: str = "queued"
    reason: Optional[str] = None       # terminal detail (REQUEST_REASONS
                                       # policy string, or the exception
                                       # type name on state=error)
    rid: Optional[int] = None          # round-22 fleet-wide request id a
                                       # router stamped at ingress; rides
                                       # every request event + span so
                                       # trace_export --router joins the
                                       # two process timelines. None on
                                       # direct submits.
    tokens: List[int] = dataclasses.field(default_factory=list)
    enqueue_t: float = 0.0
    admit_t: float = 0.0
    first_token_t: float = 0.0
    finish_t: float = 0.0
    deadline_t: float = 0.0            # absolute perf_counter deadline
                                       # (enqueue_t + deadline_ms); 0=none
    # round-21 sampling state (rejected at submit() unless the engine
    # was built with cfg.sampling): temperature 0 = the greedy oracle
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    # engine-internal
    slot: int = -1
    aid: int = 0
    blocks: List[int] = dataclasses.field(default_factory=list)
    worst_blocks: int = 0
    # round-21 chunked-admission / prefix-hit state
    prefill_pos: int = 0               # prompt tokens already cached
    prefilling: bool = False           # suffix chunks still pending
    awaiting_first: bool = False       # full prefix hit re-feed: the
                                       # next decode step emits token 1
    cache_keys: List[bytes] = dataclasses.field(default_factory=list,
                                                repr=False)

    TERMINAL = ("finished", "cancelled", "rejected", "timeout", "error")

    @property
    def done(self) -> bool:
        return self.state in self.TERMINAL

    @property
    def ttft_ms(self) -> Optional[float]:
        if not self.first_token_t:
            return None
        return (self.first_token_t - self.enqueue_t) * 1000.0

    @property
    def tpot_ms(self) -> Optional[float]:
        """Mean per-token latency AFTER the first token (the streaming
        cadence a client sees)."""
        if not self.finish_t or len(self.tokens) < 2:
            return None
        return ((self.finish_t - self.first_token_t)
                / (len(self.tokens) - 1) * 1000.0)


@dataclasses.dataclass
class _InFlight:
    """A decode step dispatched and not yet folded into the host's
    state: its tokens (a device array whose copy to the host was
    started at dispatch), the rows it decoded — (request, slot) as they
    stood when it was queued — and the dispatch's clock reading."""
    nxt: jax.Array
    rows: List[tuple]
    t0: float


class ServeEngine:
    """The serving loop. Drive it with submit() + step() (or drain());
    close() terminates the telemetry stream.

    family: "gpt2" | "gemma"; params: the frozen base tree — any
    float dtype, host or device leaves; the engine holds its own copy
    in cfg.dtype (`_resident_params`), never the tree it was given;
    bank: optional AdapterBank for multi-tenant routing;
    telemetry: optional core.telemetry.Telemetry (emits run_start /
    per-request `request` events / run_end).
    """

    def __init__(self, family: str, config, params,
                 cfg: Optional[ServeConfig] = None,
                 bank: Optional[AdapterBank] = None,
                 telemetry: Optional[Telemetry] = None,
                 eos_id: Optional[int] = None, pad_id: int = 0,
                 watchdog: Optional[HangWatchdog] = None):
        cfg = cfg or ServeConfig()
        cfg.validate()
        fam = get_family(family)
        shape = fam.dims(config)
        L, KV, D = shape.L, shape.KV, shape.D
        if shape.cap is not None \
                and cfg.true_cap + cfg.max_new_tokens > shape.cap:
            raise ValueError(
                f"prompt cap + max_new_tokens = "
                f"{cfg.true_cap + cfg.max_new_tokens} exceeds "
                f"n_positions={shape.cap}")
        self.family, self.config, self.cfg = family, config, cfg
        self.bank = bank
        self.eos_id, self.pad_id = eos_id, pad_id
        self.dtype = jnp.dtype(cfg.dtype)
        # (dp, tp) mesh placement (round 20, serve/sharding.py):
        # ServeSharding owns every NamedSharding decision — weights
        # column/row-parallel, KV pool per-shard head slices, bank
        # block-diagonal. None = the unsharded single-chip engine.
        self.sharding = None
        if cfg.mesh_dp * cfg.mesh_tp > 1:
            from mobilefinetuner_tpu.serve.sharding import ServeSharding
            self.sharding = ServeSharding.build(
                family, config, cfg.mesh_dp, cfg.mesh_tp)
            # the one-shot prefill is GSPMD-partitioned over the mesh,
            # where a Mosaic kernel outside shard_map cannot go: a
            # prompt cap long enough for the flash rule (S >= 512) takes
            # the XLA attention instead (the decode step's paged kernel
            # is wrapped — sharded_paged_attend)
            from mobilefinetuner_tpu.ops.pallas_util import \
                partitioned_impl
            config = self.config = dataclasses.replace(
                config, attention_impl=partitioned_impl(
                    "attention_impl", config.attention_impl, "flash",
                    "xla", cfg.mesh_dp * cfg.mesh_tp))

        S = cfg.num_slots
        # block tables are sized for the TRUE cap (== max_prompt when
        # chunking is off, so the decode program's shape — and its
        # pinned compiled contract — is unchanged on legacy configs)
        self.M = blocks_for(cfg.true_cap + cfg.max_new_tokens - 1,
                            cfg.block_T)
        # ---- memory admission at BUILD (round 16, DESIGN.md §21):
        # params + adapter bank + both KV pools are the engine's static
        # HBM footprint — refuse an infeasible num_blocks/num_slots
        # BEFORE anything lands on device (the sizes come from the
        # input trees' SHAPES: a params-dominated over-capacity config
        # must be refused by name, not crash in the placement below),
        # naming the max feasible values so the retry is a calculation.
        # The base weights count at the dtype the engine will HOLD them
        # in (_resident_params below), not the one they arrive in.
        from mobilefinetuner_tpu.core import memory_guard as mg
        per_block_mb = (2 * L * KV * cfg.block_T * D
                        * self.dtype.itemsize) / 2 ** 20
        self.pool_mb = per_block_mb * cfg.num_blocks

        def tree_mb(t, held=None):
            """MB of a tree's leaves as they are, or its floating leaves
            at the width of `held` (the dtype they will be held in)."""
            return sum(
                int(np.prod(np.shape(x)))
                * (held if held is not None and _is_float(x)
                   else _leaf_dtype(x)).itemsize
                for x in jax.tree.leaves(t)) / 2 ** 20

        params_mb = tree_mb(params, held=self.dtype)
        bank_mb = tree_mb(bank.tree) if bank is not None else 0.0
        self.mem_check = mg.analytic_check(
            params_mb + bank_mb + self.pool_mb, cap_mb=cfg.hbm_cap_mb,
            headroom=cfg.hbm_headroom)
        if self.mem_check.verdict == "over":
            budget = (self.mem_check.cap_mb * (1 - cfg.hbm_headroom)
                      - params_mb - bank_mb)
            max_blocks = max(int(budget // per_block_mb), 0)
            max_slots = max((max_blocks - 1) // self.M, 0)
            raise mg.MemoryAdmissionError(
                f"serve config refused at build: "
                f"{self.mem_check.describe()} (params "
                f"{params_mb:.0f} MB + adapter bank {bank_mb:.0f} MB "
                f"+ KV pool {self.pool_mb:.0f} MB). Max feasible "
                f"num_blocks={max_blocks} "
                f"({per_block_mb:.2f} MB/page), which serves at most "
                f"num_slots={max_slots} worst-case requests of "
                f"{self.M} pages each", check=self.mem_check)
        # ---- the base weights, RESIDENT in the engine's dtype: cast
        # once here, so no compiled program re-casts the tree per call
        # (their own casts to compute_dtype are then no operation). The
        # span is in a profiler trace of the build; its stream record
        # follows run_start, which stays the stream's first event.
        from mobilefinetuner_tpu.core.trace import NULL_TRACER
        sh = self.sharding
        cast_t0 = time.perf_counter()
        with NULL_TRACER.span("serve.weight_cast"):
            self.params = _resident_params(
                params, self.dtype,
                None if sh is None else sh.param_shardings(params))
            del params          # the engine keeps its own tree only
            # graftlint: disable=sync-hazard(build-time, once: weight_cast_ms must hold the cast, not its dispatch)
            jax.block_until_ready(self.params)
        cast_ms = (time.perf_counter() - cast_t0) * 1000.0
        if sh is not None:
            # every host-born array a compiled program sees must be
            # COMMITTED to the mesh, or jit refuses to mix placements
            # graftlint: disable=sync-hazard(host-born numpy coerced on its way INTO device_put; no device buffer is read)
            self._dev = lambda a: jax.device_put(np.asarray(a), sh.repl)
            if bank is not None:
                bank.place(sh.bank_shardings(bank.tree), sh.put_repl)
        else:
            self._dev = jnp.asarray
        self.alloc = BlockAllocator(cfg.num_blocks)
        # shared-prefix reuse (round 21): the cache owns the key<->page
        # maps, the allocator the refcounts/parking — None = every page
        # private, the pre-r21 allocator arithmetic exactly
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(self.alloc, cfg.block_T)
            if cfg.prefix_cache else None)
        self.cow_copies = 0
        # adapter hot-swap generations: part of the KV identity hashed
        # into prefix keys, so a reloaded tenant's stale cache entries
        # become unreachable (they drain via LRU parking, never served)
        self._adapter_gen: collections.Counter = collections.Counter()
        # static chunk widths (sorted): smallest bucket covering the
        # remaining suffix wins, else the largest rides repeated steps
        self.chunk_buckets: tuple = tuple(sorted(set(cfg.chunk_buckets)))
        if not self.chunk_buckets:
            # widths cap at max_prompt (block-rounded), NOT true_cap:
            # max_prompt is the one-dispatch prefill budget the
            # operator sized, so longer prompts ride it in slices —
            # per-step work stays bounded and decode interleaves
            cap = blocks_for(cfg.max_prompt, cfg.block_T) * cfg.block_T
            w, ws = cfg.block_T, []
            while w < cap:
                ws.append(w)
                w *= 2
            ws.append(cap)
            self.chunk_buckets = tuple(sorted(set(ws)))
        self._pool_dims = (L, KV, D)   # for the containment pool reset
        self.pool_k, self.pool_v = self._init_pools()
        self._tok = np.zeros(S, np.int32)
        self._pos = np.zeros(S, np.int32)
        self._tbl = np.full((S, self.M), TRASH_BLOCK, np.int32)
        self._aid = np.zeros(S, np.int32)
        # round-21 per-slot sampling state — DATA, not branches: rows
        # with temperature <= 0 take the greedy argmax inside the same
        # compiled step (idle slots and greedy requests alike)
        self._temp = np.zeros(S, np.float32)
        self._topk = np.zeros(S, np.int32)
        self._topp = np.ones(S, np.float32)
        self._key = np.zeros((S, 2), np.uint32)
        self._slots: List[Optional[Request]] = [None] * S
        self.queue: collections.deque = collections.deque()
        self.decode_steps = 0          # decode steps DISPATCHED
        # --- run-ahead state (PR 34): the loop dispatches decode step
        # k+1 before it reads step k's tokens. `_stale` says the host
        # touched slot state since the last dispatch (an admission, a
        # release): the next dispatch then takes tok/pos/tbl/aid from
        # the host arrays above; else it takes tok/pos from the last
        # dispatch's own outputs (`_carry`) and tbl/aid/sampling from
        # the device arrays kept in `_placed`, the table alone placed
        # again on a turn that appended a page. `_inflight` is the step
        # dispatched and not yet folded into the host's state.
        self._stale = True
        self._tbl_dirty = False        # a page was appended since
        self._carry: tuple = ()        # (tok, pos) of the next step
        self._placed: list = []        # [tbl, aid, *sampling]
        self._inflight: Optional[_InFlight] = None
        self._t_fold = 0.0             # when the last step's tokens landed
        self.steps_carried = 0         # dispatches fed from the device
        self.steps_uploaded = 0        # dispatches fed from the host
        self.eos_discarded_tokens = 0  # row-steps run past a late EOS
        self._next_id = 0
        self._t0 = time.perf_counter()
        # --- robustness state (round 14) --------------------------------
        self.draining = False          # admissions stopped (drain/shutdown)
        self._closed = False
        self.guard: Optional[PreemptionGuard] = None
        self.watchdog = watchdog       # pet()-only: the harness owns its
                                       # lifecycle (start/stop)
        # fault-injection seam: called with decode_steps right before
        # every step dispatch, INSIDE the containment try — an exception
        # here exercises the same path a real dispatch failure takes
        # (tools/serve_bench.py --inject installs it)
        self.step_hook: Optional[Callable[[int], None]] = None
        self._step_ms: collections.deque = collections.deque(maxlen=256)
        self._health_lock = threading.Lock()
        self.counts: collections.Counter = collections.Counter()
        # True exactly while a pool-donating dispatch (_write) is in
        # flight: a failure in that window may have consumed the
        # donated buffers, so containment must treat the pools as lost
        self._pools_at_risk = False

        # --- the two compiled programs (+ the prompt-page writer) ----------
        # trace_counts is the compile-stability observable: the wrapped
        # python bodies run ONLY when jax (re)traces, so the counters
        # count executables, not calls.
        self.trace_counts: collections.Counter = collections.Counter()
        dt, impl = self.dtype, cfg.attn_impl
        l_impl = cfg.lora_impl
        conf = config
        sampling = cfg.sampling

        shd = self.sharding

        def _select(logits, pos_next, temp, topk, topp, key2):
            # key2 [R, 2] raw per-row keys, folded with the emitted
            # token's ABSOLUTE position pos_next [R] — one convention
            # across prefill/chunk/decode, so cache on/off and chunked/
            # unchunked admission draw the identical stream per seed
            folded = jax.vmap(jax.random.fold_in)(key2, pos_next)
            return sample_per_row(logits, temp, topk, topp, folded)

        def prefill_py(params, bank_tree, ids, mask, aid, *samp):
            self.trace_counts["prefill"] += 1
            lora = self._route(bank_tree, aid)
            logits, (pk, pv) = prefill(fam, conf, params, ids, mask,
                                       compute_dtype=dt, lora=lora,
                                       lora_impl=l_impl, shardings=shd)
            if sampling:
                n_real = mask.sum(-1).astype(jnp.int32)       # [1]
                tok0 = _select(logits, n_real, *samp)[0]
            else:
                tok0 = jnp.argmax(logits[0], -1).astype(jnp.int32)
            return tok0, pk[:, 0], pv[:, 0]

        def step_py(params, bank_tree, pool_k, pool_v, tok, pos, tbl,
                    aid, *samp):
            self.trace_counts["decode_step"] += 1
            lora = self._route(bank_tree, aid)
            logits, pk, pv = decode_step_paged(
                fam, conf, params, pool_k, pool_v, tok, pos, tbl,
                lora=lora, compute_dtype=dt, attn_impl=impl,
                lora_impl=l_impl, shardings=shd)
            if sampling:
                nxt = _select(logits, pos + 1, *samp)
            else:
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            # the step hands back its own next inputs: a live row (its
            # table holds a page) feeds the token it emitted at the
            # next position; an idle row stays the data it is (tok 0,
            # pos 0), however many steps are carried
            live = tbl[:, 0] != TRASH_BLOCK
            return (jnp.where(live, nxt, 0), jnp.where(live, pos + 1, 0),
                    pk, pv)

        def write_py(pool_k, pool_v, k, v, block_ids):
            self.trace_counts["write_prefill"] += 1
            return write_prompt_blocks(pool_k, pool_v, k, v, block_ids)

        def chunk_py(params, bank_tree, pool_k, pool_v, ids, start,
                     n_tok, tbl, aid, *samp):
            self.trace_counts["prefill_chunk"] += 1
            W = ids.shape[1]
            # one request's rows all route the same adapter — broadcast
            # the [1] aid to the row count so the per-row lora gather is
            # shape-identical to the decode step's
            lora = self._route(bank_tree, jnp.broadcast_to(aid, (W,)))
            logits, pk, pv = prefill_chunk(
                fam, conf, params, pool_k, pool_v, ids, start, n_tok, tbl,
                lora=lora, compute_dtype=dt, lora_impl=l_impl,
                shardings=shd)
            if sampling:
                tok = _select(logits, (start + n_tok)[None], *samp)[0]
            else:
                tok = jnp.argmax(logits[0], -1).astype(jnp.int32)
            return tok, pk, pv

        def cow_py(pool_k, pool_v, src, dst):
            self.trace_counts["cow_copy"] += 1
            pk = pool_k.at[dst].set(pool_k[src])
            pv = pool_v.at[dst].set(pool_v[src])
            return pk, pv

        # donating the pools lets XLA scatter in place (the cache never
        # has two copies); CPU ignores donation, so skip the warning.
        # Under a mesh the outputs' shardings are PINNED to the inputs'
        # (pool in == pool out): donation must hand back buffers on the
        # same placement, and warmup must not depend on what GSPMD
        # would infer for an output nobody constrained.
        donate = jax.default_backend() != "cpu"
        pool_sh = None if shd is None else shd.pool_sharding()
        cache_sh = None if shd is None else shd.cache_sharding()
        self._prefill = jax.jit(
            prefill_py,
            out_shardings=None if shd is None
            else (shd.repl, cache_sh, cache_sh))
        self._step = jax.jit(
            step_py, donate_argnums=(2, 3) if donate else (),
            out_shardings=None if shd is None
            else (shd.repl, shd.repl, pool_sh, pool_sh))
        self._write = jax.jit(
            write_py, donate_argnums=(0, 1) if donate else (),
            out_shardings=None if shd is None else (pool_sh, pool_sh))
        self._chunk = jax.jit(
            chunk_py, donate_argnums=(2, 3) if donate else (),
            out_shardings=None if shd is None
            else (shd.repl, pool_sh, pool_sh))
        self._cow = jax.jit(
            cow_py, donate_argnums=(0, 1) if donate else (),
            out_shardings=None if shd is None else (pool_sh, pool_sh))

        # the lora_impl resolution is a pure function of the engine's
        # static shapes — resolve the decode-step site once and stamp it
        # into the manifest so a reader of the stream knows which path
        # served the run (train CLIs do the same per target)
        lora_impl_resolved = None
        if bank is not None:
            from mobilefinetuner_tpu.models.lora_apply import impl_summary
            # per-target map, not one arbitrary target: d_out differs
            # across targets, so boundary shapes can resolve differently
            # per site (same convention as the train CLIs' manifest)
            dims = {name: (int(e["A"].shape[-2]), int(e["B"].shape[-1]))
                    for name, e in bank.tree["blocks"].items()}
            rank = int(next(iter(
                bank.tree["blocks"].values()))["A"].shape[-1])
            lora_impl_resolved = impl_summary(
                dims, S, rank, cfg.lora_impl, self.dtype.itemsize)
        # likewise the paged-attention path: the SAME rule the decode
        # step's trace applies (ops/decode_attention.resolve_paged_impl)
        from mobilefinetuner_tpu.ops.decode_attention import (
            pick_pages, resolve_paged_impl)
        self.attn_impl_resolved = resolve_paged_impl(
            cfg.attn_impl, KV, shape.nq // KV, cfg.block_T, D,
            self.dtype.itemsize, tp=cfg.mesh_tp)
        # and the pages the paged kernel folds a grid step (the rule the
        # decode step's trace applies: pick_pages); None where the
        # gather oracle serves, which walks no grid
        self.paged_pages_per_step = pick_pages(
            KV, shape.nq // KV, cfg.block_T, D, self.dtype.itemsize,
            self.M, cfg.mesh_tp) \
            if self.attn_impl_resolved == "pallas" else None
        # the share of layers that attend under the family's window
        # (Family.layer_ok, asked with one-element masks, as the decode
        # step asks it)
        self._window = shape.window
        self._win_layers = 0.0 if shape.window is None else 1.0 - float(
            np.mean(fam.layer_ok(fam.layer_xs(config), True, False)))
        self.lora_impl_resolved = lora_impl_resolved
        # and the pools' resident layout (serve/paged_kv.py): computed
        # from shapes alone, so a stream says which layout served
        self.pool_layout = pool_layout(cfg.num_blocks, L, KV,
                                       cfg.block_T, D)
        self.telemetry = telemetry or Telemetry("")
        # span tracing (core/trace.py). step() is split into `serve.*`
        # spans, which are in any profiler trace taken of this process;
        # `trace_spans` additionally writes them to the stream, with the
        # request lifecycle — queue/prefill/decode per request on its
        # own "req:<id>" track, pure host bookkeeping over stamps the
        # engine already takes (stream only).
        from mobilefinetuner_tpu.core.trace import Tracer
        self.tracer = Tracer(self.telemetry.emit,
                             enabled=cfg.trace_spans)
        self.telemetry.emit("run_start", **run_manifest({
            "serve_family": family, "num_slots": S,
            "block_T": cfg.block_T, "num_blocks": cfg.num_blocks,
            "max_prompt": cfg.max_prompt,
            "max_new_tokens": cfg.max_new_tokens, "dtype": cfg.dtype,
            # what the base weights are held in, their resident size
            # and what the build's one cast + placement took
            "weights_dtype": str(self.dtype),
            "weights_mb": round(tree_mb(self.params), 3),
            "weight_cast_ms": round(cast_ms, 3),
            "attn_impl": cfg.attn_impl,
            "attn_impl_resolved": self.attn_impl_resolved,
            "paged_pages_per_step": self.paged_pages_per_step,
            **self.pool_layout,
            "lora_impl": cfg.lora_impl,
            "lora_impl_resolved": lora_impl_resolved,
            "adapter_slots": bank.capacity if bank else 0,
            "max_queue": cfg.max_queue, "shed_policy": cfg.shed_policy,
            "on_step_error": cfg.on_step_error,
            "stats_every": cfg.stats_every,
            "mesh_dp": cfg.mesh_dp, "mesh_tp": cfg.mesh_tp,
            "prefix_cache": cfg.prefix_cache,
            "max_prompt_chunked": cfg.max_prompt_chunked,
            "chunk_buckets": list(self.chunk_buckets),
            "sampling": cfg.sampling}))
        # the admission verdict that let this engine build (the refusal
        # path raised before the stream existed): est vs cap is the
        # "how many more blocks/slots could this chip hold" number the
        # ROADMAP's adapter-packing and KV-sizing questions start from
        self.telemetry.emit("mem_check", **self.mem_check.event())
        self.tracer.emit_span("serve.weight_cast", "main", cast_t0, cast_ms)

    # ------------------------------------------------------------ helpers ---
    def _init_pools(self):
        """Fresh zeroed pools on their home placement (build + the
        containment reset share this so a rebuilt pool can never come
        back on the wrong devices)."""
        L, KV, D = self._pool_dims
        pk, pv = init_pools(self.cfg.num_blocks, L, KV,
                            self.cfg.block_T, D, self.dtype)
        if self.sharding is not None:
            psh = self.sharding.pool_sharding()
            pk, pv = jax.device_put(pk, psh), jax.device_put(pv, psh)
        return pk, pv

    @staticmethod
    def _route(bank_tree, aid):
        """Bank slots -> per-row lora tree (the ids-gather routing)."""
        if bank_tree is None:
            return None
        return assign_adapters(bank_tree, aid)

    @property
    def active(self) -> List[Request]:
        return [r for r in self._slots if r is not None]

    @property
    def idle(self) -> bool:
        return not self.queue and not self.active

    def total_traces(self) -> int:
        return sum(self.trace_counts.values()) + (
            self.bank.trace_count if self.bank else 0)

    def _committed_blocks(self) -> int:
        """Pages the residents may still demand (their reservations)."""
        return sum(r.worst_blocks - len(r.blocks) for r in self.active)

    def _emit_request(self, req: Request, phase: str) -> None:
        self.telemetry.emit(
            "request", id=req.id, phase=phase,
            prompt_tokens=len(req.prompt),
            adapter=req.aid if req.adapter is not None else None,
            queue_ms=((req.admit_t - req.enqueue_t) * 1000.0
                      if req.admit_t else None),
            new_tokens=len(req.tokens) or None,
            ttft_ms=req.ttft_ms, tpot_ms=req.tpot_ms, reason=req.reason,
            rid=req.rid)

    def _req_span(self, name: str, req: Request, t0: float,
                  dur_ms: float, **extra) -> None:
        """One span on the request's own `req:<id>` track. A router-
        stamped `rid` rides as an extra so trace_export --router can
        join the replica-side lifecycle to the router's route/queue
        spans without a lookup table."""
        if req.rid is not None:
            extra.setdefault("rid", req.rid)
        self.tracer.emit_span(name, f"req:{req.id}", t0, dur_ms,
                              id=req.id, **extra)

    def _terminal(self, req: Request, state: str, phase: str,
                  reason: Optional[str] = None) -> None:
        """THE terminal transition: every path out of the lifecycle
        funnels through here, so a request emits exactly one terminal
        `request` phase, is counted exactly once, and can never be
        double-terminated (the accounting invariant the robustness
        tests assert after every injected fault). The caller releases
        slot/pages FIRST (queued requests hold none)."""
        assert state in Request.TERMINAL, state
        assert not req.done, f"request {req.id} already {req.state}"
        req.state = state
        req.reason = reason
        req.finish_t = time.perf_counter()
        self.counts[state] += 1
        self._emit_request(req, phase=phase)
        if self.tracer.enabled:
            # the request's last span: decode for admitted requests
            # (admit -> terminal; partial output from a timeout/error
            # still shows its decode time), queue for ones that died
            # waiting (reject/shed/queued-timeout never prefilled)
            if req.admit_t:
                self._req_span(
                    "decode", req, req.admit_t,
                    (req.finish_t - req.admit_t) * 1000.0,
                    outcome=state)
            else:
                self._req_span(
                    "queue", req, req.enqueue_t,
                    (req.finish_t - req.enqueue_t) * 1000.0,
                    outcome=state)

    # ------------------------------------------------------------ tenancy ---
    def load_adapter(self, name: str, source, verify: bool = True) -> int:
        """Hot-swap `source` (native adapter safetensors path, or an
        already-loaded lora tree) into the resident bank under `name`.
        Replacing a resident that active/queued requests still route to
        is refused — finish or cancel them first. A file source is
        checksum-verified against its integrity manifest BEFORE the
        swap (AdapterBank.load_file): a corrupt tenant adapter raises
        CheckpointIntegrityError with the mismatch reason — recorded as
        a `ckpt_verify{ok=false}` telemetry event so the refusal is
        request-visible in the stream, never a silent load into a live
        slot."""
        if self.bank is None:
            raise RuntimeError("engine was built without an adapter bank")
        if name in self.bank.resident and self._adapter_in_use(name):
            raise RuntimeError(
                f"adapter {name!r} is routed by in-flight requests; "
                f"drain them before replacing it")
        if isinstance(source, dict):
            slot = self.bank.load(name, source)
            self._adapter_gen[name] += 1
            return slot
        from mobilefinetuner_tpu.io.safetensors_io import \
            CheckpointIntegrityError
        try:
            slot = self.bank.load_file(name, source, verify=verify)
        except CheckpointIntegrityError as e:
            self.telemetry.emit("ckpt_verify", path=str(source), ok=False,
                                reason=str(e), step=None, action="reject")
            raise
        if verify:
            self.telemetry.emit("ckpt_verify", path=str(source), ok=True,
                                reason=None, step=None, action="load")
        # the swap changes the KV-producing weights under this name:
        # bump its generation so prefix keys hashed against the old
        # weights become unreachable (stale pages drain via LRU parking)
        self._adapter_gen[name] += 1
        return slot

    def evict_adapter(self, name: str) -> int:
        if self.bank is None:
            raise RuntimeError("engine was built without an adapter bank")
        if self._adapter_in_use(name):
            raise RuntimeError(
                f"adapter {name!r} is routed by in-flight requests")
        slot = self.bank.evict(name)
        self._adapter_gen[name] += 1
        return slot

    def _kv_identity(self, req: Request) -> str:
        """The KV-producing weight identity hashed into prefix keys:
        the frozen base, or adapter name + hot-swap generation — a
        reloaded tenant can never hit another generation's pages."""
        if req.adapter is None:
            return "base"
        return f"{req.adapter}:{self._adapter_gen[req.adapter]}"

    def _adapter_in_use(self, name: str) -> bool:
        # QUEUED requests count as in-use too: submit() resolved their
        # bank slot at enqueue, so replacing/evicting the resident while
        # they wait would silently serve another tenant's weights at
        # admission (_admit additionally re-resolves the name —
        # belt-and-braces, both pinned by
        # test_serve.py::test_queued_request_pins_its_adapter)
        return any(r.adapter == name
                   for r in list(self.queue) + self.active)

    # ------------------------------------------------------------ intake ----
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 0,
               adapter: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, seed: int = 0,
               rid: Optional[int] = None) -> Request:
        """Enqueue one request (admission happens inside step()).
        `deadline_ms` is the request's end-to-end budget from now: a
        queued request past it times out without prefilling, an active
        one is cancelled at the next step boundary with partial output.
        temperature/top_k/top_p/seed (cfg.sampling engines only) ride
        the request's slot as data; temperature 0 is the greedy oracle
        and a given seed is deterministic. Under a full bounded queue
        (`max_queue`) — or a prompt beyond the true cap
        (reason="prompt_too_long"); prompts in (max_prompt, true_cap]
        route to chunked admission instead, since round 21 — the
        returned request may already be terminal (state="rejected"):
        check `.state` rather than assuming it queued."""
        if self._closed:
            raise RuntimeError(
                "submit() on a closed ServeEngine: close() already "
                "ended the telemetry stream — build a new engine")
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if (temperature or top_k or top_p != 1.0 or seed) \
                and not self.cfg.sampling:
            raise ValueError(
                "sampling parameters need a sampling-enabled engine "
                "(ServeConfig.sampling=True)")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        n_new = max_new_tokens or self.cfg.max_new_tokens
        if not 0 < n_new <= self.cfg.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {n_new} outside (0, "
                f"{self.cfg.max_new_tokens}]")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        aid = 0
        if adapter is not None:
            if self.bank is None:
                raise RuntimeError(
                    "request names an adapter but the engine has no bank")
            # resolve the slot NOW (raises KeyError if not resident) so
            # the enqueue/cancel events report the right tenant; the
            # slot cannot move while queued (in-use residents refuse
            # replacement and eviction)
            aid = self.bank.slot(adapter)
        req = Request(id=self._next_id, prompt=prompt,
                      max_new_tokens=n_new, adapter=adapter, aid=aid,
                      rid=int(rid) if rid is not None else None,
                      enqueue_t=time.perf_counter(),
                      temperature=float(temperature), top_k=int(top_k),  # graftlint: disable=sync-hazard(host submit args normalized; no device buffer is read)
                      top_p=float(top_p), seed=int(seed))  # graftlint: disable=sync-hazard(host submit args normalized; no device buffer is read)
        if deadline_ms is not None:
            req.deadline_t = req.enqueue_t + deadline_ms / 1000.0
        self._next_id += 1
        self._emit_request(req, phase="enqueue")
        if len(prompt) > self.cfg.true_cap:
            # beyond even chunked admission: a POLICY reject the caller
            # reads off .state, not a programming error — the pre-r21
            # ValueError is gone (prompts in (max_prompt, true_cap]
            # are valid chunked admissions now)
            self._terminal(req, "rejected", phase="reject",
                           reason="prompt_too_long")
            return req
        if self.draining:
            # drain in progress: admissions are closed for good
            self._terminal(req, "rejected", phase="reject",
                           reason="shutdown")
            return req
        if self.cfg.max_queue and len(self.queue) >= self.cfg.max_queue:
            victim = None
            if self.cfg.shed_policy == "deadline":
                # shed the queued request closest to blowing its own
                # deadline — it is the least likely to finish in time
                # anyway; deadline-less requests are never shed
                dl = [r for r in self.queue if r.deadline_t]
                if dl:
                    victim = min(dl, key=lambda r: r.deadline_t)
            if victim is None:
                self._terminal(req, "rejected", phase="reject",
                               reason="queue_full")
                return req
            self.queue.remove(victim)
            self._terminal(victim, "rejected", phase="reject",
                           reason="shed")
        self.queue.append(req)
        return req

    def cancel(self, req: Request) -> None:
        """Evict a queued or active request (frees its slot + pages)."""
        if req.state == "queued":
            self.queue.remove(req)
        elif req.state == "active":
            self._release(req)
        else:
            return
        self._terminal(req, "cancelled", phase="cancel")

    # ------------------------------------------------------------ the loop --
    def _samp_args(self, req: Request) -> tuple:
        """Per-request sampling params for the single-row programs
        (prefill/chunk) — empty on greedy-only engines, so those
        programs keep their pre-r21 signatures bit-for-bit."""
        if not self.cfg.sampling:
            return ()
        # graftlint: disable=sync-hazard(host scalars wrapped for the device; nothing is pulled back)
        return (self._dev(np.asarray([req.temperature], np.float32)),
                self._dev(np.asarray([req.top_k], np.int32)),  # graftlint: disable=sync-hazard(host scalars wrapped for the device; nothing is pulled back)
                self._dev(np.asarray([req.top_p], np.float32)),  # graftlint: disable=sync-hazard(host scalars wrapped for the device; nothing is pulled back)
                self._dev(np.asarray(  # graftlint: disable=sync-hazard(host scalars wrapped for the device; nothing is pulled back)
                    [[(req.seed >> 32) & 0xFFFFFFFF,
                      req.seed & 0xFFFFFFFF]], np.uint32)))

    def _admit(self, req: Request, slot: int) -> None:
        """Slot grant + path dispatch: one-shot prefill (the classic
        path — full miss within max_prompt), full-hit re-feed (every
        prompt block cached), or chunked suffix prefill (partial hit,
        or a long prompt)."""
        cfg = self.cfg
        P = len(req.prompt)
        req.worst_blocks = blocks_for(P + req.max_new_tokens - 1,
                                      cfg.block_T)
        req.slot, req.state = slot, "active"
        if self.bank is None:
            req.aid = 0
        elif req.adapter is not None:
            req.aid = self.bank.slot(req.adapter)
        else:
            req.aid = self.bank.base_slot  # zero slot: serve the base
        self._slots[slot] = req
        self._aid[slot] = req.aid
        if cfg.sampling:
            self._temp[slot] = req.temperature
            self._topk[slot] = req.top_k
            self._topp[slot] = req.top_p
            self._key[slot] = ((req.seed >> 32) & 0xFFFFFFFF,
                               req.seed & 0xFFFFFFFF)
        cached: List[int] = []
        if self.prefix is not None:
            req.cache_keys = chain_keys(req.prompt, cfg.block_T,
                                        self._kv_identity(req))
            cached = self.prefix.lookup(req.cache_keys)
            self.prefix.note_lookup(len(cached) * cfg.block_T, P)
        C = len(cached) * cfg.block_T    # cached prefix, tokens
        if C == P:                       # full hit (P a block multiple)
            self._admit_full_hit(req, cached)
        elif C == 0 and P <= cfg.max_prompt:
            self._admit_prefill(req)
        else:                            # suffix hit, or a long prompt
            self._admit_chunked(req, cached, C)

    def _admit_prefill(self, req: Request) -> None:
        """The classic ONE-SHOT prefill (the pre-r21 path, unchanged):
        full cache miss, prompt within max_prompt."""
        cfg, slot, P = self.cfg, req.slot, len(req.prompt)
        req.blocks = self.alloc.alloc(blocks_for(P, cfg.block_T))
        ids = np.full((1, cfg.max_prompt), self.pad_id, np.int32)
        mask = np.zeros((1, cfg.max_prompt), np.int32)
        ids[0, :P], mask[0, :P] = req.prompt, 1
        bank_tree = self.bank.tree if self.bank else None
        t_prefill, span = time.perf_counter(), self.tracer.span
        # the prefill is cut where the host blocks: two dispatches that
        # return at once, then the one wait of a turnover
        with span("serve.prefill", "loop"):
            with span("serve.prefill.dispatch", "loop"):
                tok0, k, v = self._prefill(
                    self.params, bank_tree, self._dev(ids), self._dev(mask),
                    # graftlint: disable=sync-hazard(host int wrapped for the device; nothing is pulled back)
                    self._dev(np.asarray([req.aid], np.int32)),
                    *self._samp_args(req))
            with span("serve.prefill.page_write", "loop"):
                # scatter the prompt pages; table rows past the prompt
                # stay trash
                block_ids = np.full(cfg.max_prompt // cfg.block_T,
                                    TRASH_BLOCK, np.int32)
                block_ids[:len(req.blocks)] = req.blocks
                # the write DONATES the pools (non-CPU): if it raises,
                # the old buffers may already be consumed — flag the
                # window so the admission containment knows one-victim
                # recovery is not enough
                self._pools_at_risk = True
                self.pool_k, self.pool_v = self._write(
                    self.pool_k, self.pool_v, k, v, self._dev(block_ids))
                self._pools_at_risk = False
            with span("serve.prefill.first_token", "loop"):
                tok0 = int(tok0)         # host sync: the first token
        now = time.perf_counter()
        req.admit_t = req.first_token_t = now
        if self.tracer.enabled:
            # queue span closes where prefill begins; prefill span runs
            # through the first-token host sync (both on the request's
            # own track, stamps the engine already takes)
            self._req_span(
                "queue", req, req.enqueue_t,
                (t_prefill - req.enqueue_t) * 1000.0)
            self._req_span(
                "prefill", req, t_prefill, (now - t_prefill) * 1000.0)
        req.tokens.append(tok0)
        self._tok[slot], self._pos[slot] = tok0, P
        self._tbl[slot] = TRASH_BLOCK
        self._tbl[slot, :len(req.blocks)] = req.blocks
        self._stale = True
        if self.prefix is not None:
            # every FULL prompt block this prefill computed is now
            # shareable (first writer wins on races); decode never
            # rewrites prompt columns, so registered pages stay
            # immutable (cache_keys has P // block_T entries: zip
            # skips the partial tail block by construction)
            for key, b in zip(req.cache_keys, req.blocks):
                self.prefix.register(key, b)
        self._emit_request(req, phase="admit")
        self._emit_request(req, phase="first_token")
        if (self.eos_id is not None and tok0 == self.eos_id) \
                or req.max_new_tokens == 1:
            self._finish(req)

    def _admit_full_hit(self, req: Request, cached: List[int]) -> None:
        """Every prompt block is cached: skip prefill entirely and
        RE-FEED the last prompt token through the decode step — slot
        pos = P-1, so the next decode writes that one column and emits
        the request's first token at position P. The rewritten column
        lands in the last shared page, so that page is COPIED first
        (copy-on-write at the divergence block): shared page contents
        are immutable by construction, whatever this request does."""
        cfg, slot, P = self.cfg, req.slot, len(req.prompt)
        # acquisition order matters: acquire (pin) every cached page
        # BEFORE alloc() could LRU-evict a parked one out from under us
        for b in cached:
            self.alloc.acquire(b)
        dst = self.alloc.alloc(1)[0]
        src = cached[-1]
        # drop our reference on the source BEFORE the copy dispatches:
        # req.blocks then lists exactly the pages containment would
        # release if the (pool-donating) copy dies. Parking preserves
        # contents and nothing allocates before the copy reads it.
        self.alloc.free([src], park=self.prefix.park)
        req.blocks = cached[:-1] + [dst]
        self._pools_at_risk = True
        self.pool_k, self.pool_v = self._cow(
            self.pool_k, self.pool_v,
            # graftlint: disable=sync-hazard(host ints wrapped for the device; nothing is pulled back)
            self._dev(np.asarray(src, np.int32)),
            self._dev(np.asarray(dst, np.int32)))  # graftlint: disable=sync-hazard(host ints wrapped for the device; nothing is pulled back)
        self._pools_at_risk = False
        self.cow_copies += 1
        now = time.perf_counter()
        req.admit_t = now
        req.awaiting_first = True
        self._tok[slot] = req.prompt[-1]
        self._pos[slot] = P - 1
        self._tbl[slot] = TRASH_BLOCK
        self._tbl[slot, :len(req.blocks)] = req.blocks
        self._stale = True
        if self.tracer.enabled:
            # no prefill span: the whole prompt came from cached pages
            self._req_span(
                "queue", req, req.enqueue_t,
                (now - req.enqueue_t) * 1000.0)
        self._emit_request(req, phase="admit")

    def _admit_chunked(self, req: Request, cached: List[int],
                       C: int) -> None:
        """Chunked admission: the uncached SUFFIX (from the first
        uncached block — the whole prompt on a miss) prefills in static
        bucket-width chunks, at most one per step(), interleaved with
        decode. The slot holds idle data (pos=0, tbl=trash) until the
        final chunk lands the first token, so the compiled step treats
        a mid-prefill request exactly like an empty slot."""
        cfg, slot, P = self.cfg, req.slot, len(req.prompt)
        for b in cached:
            self.alloc.acquire(b)        # pin before alloc() can evict
        req.blocks = list(cached) + self.alloc.alloc(
            blocks_for(P, cfg.block_T) - len(cached))
        req.prefill_pos = C
        req.prefilling = True
        req.admit_t = time.perf_counter()
        self._tok[slot] = self._pos[slot] = 0
        self._tbl[slot] = TRASH_BLOCK
        if self.tracer.enabled:
            self._req_span(
                "queue", req, req.enqueue_t,
                (req.admit_t - req.enqueue_t) * 1000.0)
        self._emit_request(req, phase="admit")

    def _prefill_chunk(self, req: Request) -> None:
        """Dispatch ONE chunk of `req`'s pending prompt suffix: the
        smallest static bucket covering the remainder (else the
        largest, and the tail rides later steps). The final chunk's
        last-row logits are the request's first token."""
        cfg, slot, P = self.cfg, req.slot, len(req.prompt)
        start = req.prefill_pos
        remaining = P - start
        W = next((w for w in self.chunk_buckets if w >= remaining),
                 self.chunk_buckets[-1])
        n_tok = min(remaining, W)
        ids = np.full((1, W), self.pad_id, np.int32)
        ids[0, :n_tok] = req.prompt[start:start + n_tok]
        tbl = np.full((1, self.M), TRASH_BLOCK, np.int32)
        tbl[0, :len(req.blocks)] = req.blocks
        bank_tree = self.bank.tree if self.bank else None
        t_chunk = time.perf_counter()
        # the chunk donates the pools: a failure here is a full-
        # containment window, same as the prompt-page write
        self._pools_at_risk = True
        tok, self.pool_k, self.pool_v = self._chunk(
            self.params, bank_tree, self.pool_k, self.pool_v,
            self._dev(ids),
            # graftlint: disable=sync-hazard(host ints wrapped for the device; nothing is pulled back)
            self._dev(np.asarray(start, np.int32)),
            self._dev(np.asarray(n_tok, np.int32)), self._dev(tbl),  # graftlint: disable=sync-hazard(host ints wrapped for the device; nothing is pulled back)
            self._dev(np.asarray([req.aid], np.int32)),  # graftlint: disable=sync-hazard(host ints wrapped for the device; nothing is pulled back)
            *self._samp_args(req))
        self._pools_at_risk = False
        req.prefill_pos += n_tok
        if self.tracer.enabled:
            self._req_span(
                "prefill", req, t_chunk,
                (time.perf_counter() - t_chunk) * 1000.0)
        if req.prefill_pos < P:
            return
        # final chunk: its last real row IS the request's first token
        tok0 = int(tok)                  # host sync
        req.prefilling = False
        req.first_token_t = time.perf_counter()
        req.tokens.append(tok0)
        self._tok[slot], self._pos[slot] = tok0, P
        self._tbl[slot] = TRASH_BLOCK
        self._tbl[slot, :len(req.blocks)] = req.blocks
        self._stale = True
        if self.prefix is not None:
            for key, b in zip(req.cache_keys, req.blocks):
                self.prefix.register(key, b)
        self._emit_request(req, phase="first_token")
        if (self.eos_id is not None and tok0 == self.eos_id) \
                or req.max_new_tokens == 1:
            self._finish(req)

    def _release(self, req: Request) -> None:
        park = self.prefix.park if self.prefix is not None else None
        self.alloc.free(req.blocks, park=park)
        req.blocks = []
        req.prefilling = req.awaiting_first = False
        s = req.slot
        if s < 0:   # admission died before the slot was taken: nothing
            return  # slot-side to clean (containment path)
        self._slots[s] = None
        self._stale = True
        self._tok[s] = self._pos[s] = self._aid[s] = 0
        self._tbl[s] = TRASH_BLOCK
        if self.cfg.sampling:
            self._temp[s], self._topk[s], self._topp[s] = 0.0, 0, 1.0
            self._key[s] = 0

    def _finish(self, req: Request) -> None:
        self._release(req)
        self._terminal(req, "finished", phase="finish")

    def _expire(self, now: float) -> List[Request]:
        """Time out every request past its deadline: queued ones are
        dropped WITHOUT ever prefilling (no trace, no pages), active
        ones at this step boundary — partial output stays on
        `req.tokens`, slot and pages are released."""
        out: List[Request] = []
        for req in [r for r in self.queue
                    if r.deadline_t and now >= r.deadline_t]:
            self.queue.remove(req)
            self._terminal(req, "timeout", phase="timeout",
                           reason="deadline")
            out.append(req)
        for req in [r for r in self.active
                    if r.deadline_t and now >= r.deadline_t]:
            self._release(req)
            self._terminal(req, "timeout", phase="timeout",
                           reason="deadline")
            out.append(req)
        return out

    def _contain_step_error(self, e: BaseException) -> List[Request]:
        """A step-dispatch exception reached the scheduler: the step's
        in-flight work is unrecoverable (and the donated pools may have
        been consumed mid-dispatch), but the ENGINE is not — fail each
        active request individually (phase=error, reason=<exception
        type>), release its slot and exactly its pages, and rebuild the
        pool arrays so the next admission starts from a clean, empty
        cache. The queue is untouched: admission resumes on the next
        step() under `on_step_error="fail_active"`. The compiled
        executables survive (containment is host-side bookkeeping), so
        recovery costs zero retraces."""
        name = type(e).__name__
        failed: List[Request] = []
        for req in self.active:
            self._release(req)
            self._terminal(req, "error", phase="error", reason=name)
            failed.append(req)
        # every active released its own pages, so the allocator is whole
        # again by construction; the pools are rebuilt because a step
        # that died after dispatch may have invalidated the donated
        # buffers (and their contents described only the dead requests)
        self.pool_k, self.pool_v = self._init_pools()
        # the step in flight died with the pools it wrote: its tokens
        # belong to requests that have just failed
        self._inflight, self._stale = None, True
        if self.prefix is not None:
            # the rebuilt pools hold NONE of the cached contents: drop
            # every mapping and parked page (the releases above just
            # parked the dead requests' shared pages — flush un-parks
            # them back to the plain free list)
            self.prefix.flush()
        self._pools_at_risk = False
        return failed

    def step(self) -> List[Request]:
        """One scheduler iteration: observe preemption, expire
        deadlines, admit what fits (unless draining), then one decode
        step for every active slot. Returns every request that reached
        a TERMINAL state on this iteration (finished, and since round
        14: timeout, error, and shutdown-rejected) — filter on
        `.state` when only completions matter.

        The loop runs ONE STEP AHEAD of the host (PR 34): a turn
        dispatches decode step k+1 and only then reads step k's tokens,
        so the host's reading, bookkeeping and the caller's own turn
        overlap the device's next step. The step's per-slot state
        stays on the device: step k+1 takes `tok` / `pos` from step k's
        outputs, and the table, adapter ids and sampling arrays from
        device arrays placed again only when the host changed them.
        The order of a turn follows from what the engine sees of its
        own state:

          steady   nothing touched slot state since the last dispatch:
                   serve.expire, serve.page_append (from the host's
                   `_pos`, advanced at dispatch), serve.upload (the
                   table, if a page was appended), serve.dispatch of
                   k+1, serve.sync + serve.emit of k;
          stale    an admission, a finish, a release, a cancel, an
                   expiry, a contained failure or a shutdown touched
                   slot state — or the step in flight completes a
                   request by LENGTH, which the host counts without
                   reading a token: serve.sync + serve.emit of k
                   first, then expire / admit, then serve.upload of
                   tok/pos/tbl/aid from the host arrays and
                   serve.dispatch of k+1, left in flight;
          fill     nothing in flight (the first step; after a drain):
                   a stale turn without its sync and emit.

        So a request's tokens reach `Request.tokens` one device step
        after the device made them, a row never runs a step past its
        last position, and a request ending by `eos_id` is seen one
        step late: the row's one extra token is discarded
        (`eos_discarded_tokens`), never appended. A slot stays taken
        until its last token is folded in, and an engine that is `idle`
        has nothing in flight. `steps_carried` / `steps_uploaded` count
        the dispatches of either kind.

        The iteration is one `serve.step` span (core/trace.py) whose
        children name its phases: serve.expire, serve.admit (one per
        admitted request; serve.prefill inside, cut into
        serve.prefill.dispatch, serve.prefill.page_write and
        serve.prefill.first_token — the one-shot prefill's two
        dispatches and its wait for the first token, the one place a
        turnover blocks on the device), serve.prefill_chunk,
        serve.page_append, serve.upload, serve.dispatch, serve.sync,
        serve.emit, serve.stats. A turn that admits nothing enters
        none of the prefill's spans."""
        with self.tracer.span("serve.step", "loop"):
            return self._step_phases()

    def _step_phases(self) -> List[Request]:
        cfg, span = self.cfg, self.tracer.span
        done: List[Request] = []
        # a turn that touches the rows of the step in flight — or
        # follows one that did — folds that step's tokens in FIRST
        if self._inflight is not None and (
                self._stale or self._turn_ends_a_row(time.perf_counter())):
            if not self._guard(self._settle, done):
                return done
        with span("serve.expire", "loop"):
            # a preemption signal is observed at the step boundary
            # (never inside a dispatch): stop admissions, reject the
            # queued remainder, let the in-flight requests finish
            if self.guard is not None and self.guard.triggered \
                    and not self.draining:
                self.telemetry.emit(
                    "preempt", step=self.decode_steps,
                    signal=self.guard.signal_name or "SIGTERM")
                done.extend(self.begin_shutdown())
            done.extend(self._expire(time.perf_counter()))
        # FCFS admission under the worst-case page reservation
        while self.queue and not self.draining:
            free = [i for i, r in enumerate(self._slots) if r is None]
            if not free:
                break
            req = self.queue[0]
            worst = blocks_for(len(req.prompt) + req.max_new_tokens - 1,
                               cfg.block_T)
            if self.alloc.free_blocks - self._committed_blocks() < worst:
                break
            self.queue.popleft()
            try:
                with span("serve.admit", "loop"):
                    self._admit(req, free[0])
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                # a failed PREFILL kills ONE request, not the engine —
                # and not the other residents' cache (the pools stay:
                # the in-flight requests are still live and their pages
                # untouched)
                self._release(req)
                self._terminal(req, "error", phase="error",
                               reason=type(e).__name__)
                done.append(req)
                if self._pools_at_risk:
                    # ...UNLESS the prompt-page WRITE died: it donates
                    # the pools, so every resident's cache is suspect —
                    # escalate to full containment (fail actives,
                    # rebuild pools)
                    done.extend(self._contain_step_error(e))
                if cfg.on_step_error == "raise":
                    raise
                continue
            if req.state == "finished":  # eos/cap hit on the first token
                done.append(req)

        # chunked prefill (round 21): dispatch chunks FCFS (oldest
        # request first) until this step's prefill-token BUDGET — one
        # widest bucket — is spent. The budget is what bounds the
        # residents' TPOT jitter; spending it on several small suffix
        # chunks (concurrent prefix hits) costs the residents the same
        # as one wide chunk, but keeps short suffixes from serializing
        # at one chunk per decode-step turn (a first-token tax measured
        # at ~1 decode step per queued hit on CPU gpt2s)
        budget = self.chunk_buckets[-1] if self.chunk_buckets else 0
        while budget > 0:
            chunking = [r for r in self.active if r.prefilling]
            if not chunking:
                break
            req = min(chunking, key=lambda r: r.id)
            remaining = len(req.prompt) - req.prefill_pos
            W = next((w for w in self.chunk_buckets if w >= remaining),
                     self.chunk_buckets[-1])
            if W > budget:
                break                    # next chunk outlives the budget
            try:
                with span("serve.prefill_chunk", "loop"):
                    self._prefill_chunk(req)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                # the chunk donates the pools: every resident's cache
                # is suspect — full containment, same as a dead step
                done.extend(self._contain_step_error(e))
                if cfg.on_step_error == "raise":
                    raise
                return done
            budget -= W
            if req.done:                 # eos/cap on the final chunk
                done.append(req)

        # slot state touched THIS turn (an admission, a final chunk, an
        # expiry) while a step was in flight: its tokens first, so the
        # host arrays the next dispatch is fed from are whole
        if self._stale and not self._guard(self._settle, done):
            return done
        # mid-prefill requests hold idle slot data: the compiled step
        # runs over every slot regardless, but only completed-prefill
        # rows advance host-side
        live = [r for r in self.active if not r.prefilling]
        if not live:
            return done
        # a slot crossing a page boundary this step takes its next page
        # (guaranteed by the admission reservation)
        with span("serve.page_append", "loop"):
            for req in live:
                j = int(self._pos[req.slot]) // cfg.block_T
                if j == len(req.blocks):
                    try:
                        req.blocks.append(self.alloc.append())
                    except OutOfBlocks as e:  # pragma: no cover — invariant
                        raise OutOfBlocks(
                            f"reservation accounting failed for request "
                            f"{req.id}: {e}") from e
                    self._tbl[req.slot, j] = req.blocks[-1]
                    self._tbl_dirty = True

        if not self._guard(self._decode_turn, done, live):
            return done
        if cfg.stats_every and self.decode_steps % cfg.stats_every == 0:
            with span("serve.stats", "loop"):
                self.emit_stats()
        return done

    def _guard(self, phase: Callable, done: List[Request], *args) -> bool:
        """Run one decode phase of the turn under crash containment;
        False where it failed and was contained (the turn ends)."""
        try:
            phase(done, *args)
            return True
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            done.extend(self._contain_step_error(e))
            if self.cfg.on_step_error == "raise":
                raise
            return False

    def _turn_ends_a_row(self, now: float) -> bool:
        """Whether this turn will let go of a row of the step in
        flight, as far as the host can tell WITHOUT that step's tokens:
        the step completes a request by length (the host counts), or a
        resident's deadline has passed."""
        return any(len(r.tokens) + 1 >= r.max_new_tokens
                   for r, s in self._inflight.rows if self._slots[s] is r) \
            or any(r.deadline_t and now >= r.deadline_t
                   for r in self.active)

    def _decode_turn(self, done: List[Request], live: List[Request]) -> None:
        """Dispatch the next decode step over `live`, THEN fold in the
        step that was in flight (a stale turn folded it in before it
        touched slot state, and has nothing left to fold)."""
        span = self.tracer.span
        if self.step_hook is not None:
            self.step_hook(self.decode_steps)
        prev, t0 = self._inflight, time.perf_counter()
        carried = not self._stale
        with span("serve.upload", "loop"):
            args = self._step_args()
        with span("serve.dispatch", "loop"):
            # the pools are donated: rebound from the dispatch at once
            # (a prefill, a chunk or the next step may need them before
            # this step's tokens are read)
            nxt, pos, self.pool_k, self.pool_v = self._step(*args)
            nxt.copy_to_host_async()
        self._carry, self._stale = (nxt, pos), False
        self._inflight = _InFlight(nxt, [(r, r.slot) for r in live], t0)
        for req in live:
            self._pos[req.slot] += 1     # as the device counts it
        self.decode_steps += 1
        if carried:
            self.steps_carried += 1
        else:
            self.steps_uploaded += 1
        if prev is not None:
            self._fold(prev, done)
            if not any(self._slots[s] is r for r, s in self._inflight.rows):
                # a late EOS ended the last row of the step just queued:
                # nobody will read it, and an idle engine has nothing
                # in flight
                self._settle(done)

    def _settle(self, done: List[Request]) -> None:
        """Fold the step in flight, where there is one, into the host's
        state, and leave nothing in flight."""
        step, self._inflight = self._inflight, None
        if step is not None:
            self._fold(step, done)

    def _fold(self, step: _InFlight, done: List[Request]) -> None:
        """A dispatched step's tokens into the host's state: wait for
        them, then append them, stamp first tokens and finish what
        ended. A row the host let go of after the step was queued (an
        EOS seen one step late, a cancel, an expiry) is skipped: its
        token is not the request's."""
        with self.tracer.span("serve.sync", "loop"):
            # graftlint: disable=sync-hazard(the serve loop's ONE host sync per decode step, taken after the NEXT step is queued: this step's tokens drive host-side scheduling)
            nxt = np.asarray(step.nxt)
        with self.tracer.span("serve.emit", "loop"):
            # the step's time as the host can see it: from its dispatch,
            # or from the landing of the step it was queued behind
            now = time.perf_counter()
            step_s = now - max(step.t0, self._t_fold)
            self._t_fold = now
            with self._health_lock:
                self._step_ms.append(step_s * 1000.0)
            if self.watchdog is not None:
                self.watchdog.pet(self.decode_steps, step_s)
            for req, s in step.rows:
                if self._slots[s] is not req:
                    if req.state == "finished":
                        self.eos_discarded_tokens += 1
                    continue
                self._tok[s] = int(nxt[s])
                req.tokens.append(int(nxt[s]))
                if req.awaiting_first:
                    # full-hit re-feed: THIS decode emitted the
                    # request's first token (the prompt never prefilled
                    # at all)
                    req.awaiting_first = False
                    req.first_token_t = time.perf_counter()
                    self._emit_request(req, phase="first_token")
                if (self.eos_id is not None
                        and req.tokens[-1] == self.eos_id) \
                        or len(req.tokens) >= req.max_new_tokens:
                    self._finish(req)
                    done.append(req)

    def _step_args(self) -> list:
        """The decode step's operands from the engine's live state:
        slot state the host touched since the last dispatch is placed
        from the host arrays, the rest are the device arrays the last
        dispatch left. Every host array is COPIED on its way: the
        device array outlives this turn, the host array is written in
        place on the next, and a backend may alias what it was given."""
        put = lambda a: self._dev(a.copy())
        if self._stale:
            self._carry = (put(self._tok), put(self._pos))
            self._placed = [None, put(self._aid)]
            if self.cfg.sampling:
                # sampling state rides AFTER the legacy args so the pool
                # donation indices (2, 3) never move
                self._placed += [put(self._temp), put(self._topk),
                                 put(self._topp), put(self._key)]
        if self._stale or self._tbl_dirty:
            self._placed[0] = put(self._tbl)
            self._tbl_dirty = False
        return [self.params, self.bank.tree if self.bank else None,
                self.pool_k, self.pool_v, *self._carry, *self._placed]

    def lower_decode_step(self):
        """The decode step lowered at the engine's live shapes, for
        INSPECTION (`.compile().as_text()` shows whether the Pallas
        kernel is in the program, `.memory_analysis()` its footprint).
        Dispatch keeps using the jit's own cache; lowering re-runs the
        Python body, so trace_counts["decode_step"] moves by one."""
        return self._step.lower(*self._step_args())

    def drain(self) -> List[Request]:
        """step() until queue and slots are empty; returns every
        request that reached a terminal state along the way, submission
        order."""
        done: List[Request] = []
        while not self.idle:
            done.extend(self.step())
        return sorted(done, key=lambda r: r.id)

    # ------------------------------------------------------------ shutdown --
    def install_preemption(
            self, guard: Optional[PreemptionGuard] = None
    ) -> PreemptionGuard:
        """Arm SIGTERM/SIGINT drain (the serve-side mirror of
        run_training's --on_preempt): the first signal is observed at
        the next step boundary — admissions stop, the queued remainder
        is rejected with reason="shutdown", in-flight requests decode
        to completion, and close() records run_end{exit=preempted,
        reason=preempted}. A SECOND signal raises KeyboardInterrupt out
        of the drain (the guard's escalation): the caller cancels
        in-flight requests and closes — the operator always outranks a
        slow drain."""
        self.guard = guard or PreemptionGuard()
        if not self.guard.installed:
            self.guard.install()
        return self.guard

    def begin_shutdown(self, reason: str = "shutdown") -> List[Request]:
        """Stop admissions for good and reject every queued request
        (they would never be admitted); in-flight requests keep
        decoding — step()/drain() finish them. Returns the rejected
        requests. Idempotent once draining."""
        self.draining = True
        self._stale = True
        out: List[Request] = []
        while self.queue:
            req = self.queue.popleft()
            self._terminal(req, "rejected", phase="reject", reason=reason)
            out.append(req)
        return out

    # ------------------------------------------------------------ health ----
    def health(self) -> dict:
        """Host-side loop vitals — what an operator (or the cadenced
        serve_stats emission) reads to see pressure building BEFORE it
        becomes rejects: queue depth, slot occupancy, page-pool
        headroom, rolling p95 step latency, and the cumulative
        terminal-state counters."""
        with self._health_lock:
            ms = sorted(self._step_ms)
        p95 = (round(ms[min(int(0.95 * len(ms)), len(ms) - 1)], 3)
               if ms else None)
        from mobilefinetuner_tpu.core.xla_stats import live_hbm_mb
        hbm = live_hbm_mb()
        return {
            # round-22 router probe: metrics_http's /healthz returns
            # 503 on any non-"ok" status, so a draining replica stops
            # attracting traffic the moment admissions close — the
            # body still carries the full dict (incl. draining: true)
            # for the router's post-mortem line
            "status": "draining" if self.draining else "ok",
            "queue_depth": len(self.queue),
            "active": len(self.active),
            "occupancy": round(len(self.active) / self.cfg.num_slots, 4),
            "free_blocks": self.alloc.free_blocks,
            "blocks_in_use": self.alloc.in_use,
            "p95_step_ms": p95,
            "decode_steps": self.decode_steps,
            "draining": self.draining,
            # round-16 HBM vitals: live device bytes (null where the
            # backend reports none) + the static pool footprint the
            # admission charged — pressure is visible BEFORE it
            # becomes an allocator failure
            "hbm_mb": round(hbm, 2) if hbm is not None else None,
            "pool_mb": round(self.pool_mb, 2),
            "mesh": [self.cfg.mesh_dp, self.cfg.mesh_tp],
            # round-21 shared-prefix vitals: token-weighted hit rate
            # (null until the first lookup / with the cache off), COW
            # page copies, and how many pages sit parked (free but
            # holding cached contents)
            "prefix_hit_rate": (self.prefix.hit_rate
                                if self.prefix is not None else None),
            "cow_copies": self.cow_copies,
            "parked_blocks": self.alloc.parked_blocks,
            "paged_live_chunk_share": self._live_chunk_share(),
            # PR 34, how often the run-ahead engages: decode dispatches
            # fed from the device's own outputs / from the host arrays
            # (they sum to decode_steps), and the row-steps run past an
            # EOS the host saw one step late (discarded, never emitted)
            "steps_carried": self.steps_carried,
            "steps_uploaded": self.steps_uploaded,
            "eos_discarded_tokens": self.eos_discarded_tokens,
            "counts": {s: int(self.counts.get(s, 0))
                       for s in Request.TERMINAL},
        }

    def _live_chunk_share(self) -> Optional[float]:
        """Of the paged kernel's (slot, chunk) grid steps of a decode
        step at the slots' present positions, the share that hold a
        live page (the rest skip their body and move nothing) — from
        the host's own position table, no device read. None where the
        gather oracle serves."""
        P = self.paged_pages_per_step
        if P is None:
            return None
        from mobilefinetuner_tpu.ops.decode_attention import \
            live_chunk_share
        w = self._win_layers
        share = (1 - w) * live_chunk_share(self._pos, self.cfg.block_T,
                                           self.M, P)
        if w:
            share += w * live_chunk_share(self._pos, self.cfg.block_T,
                                          self.M, P, self._window)
        return round(share, 4)

    def emit_stats(self) -> None:
        """One `serve_stats` snapshot into the stream (step() calls
        this every `stats_every` decode steps)."""
        h = self.health()
        self.telemetry.emit(
            "serve_stats", step=self.decode_steps,
            queue_depth=h["queue_depth"], active=h["active"],
            occupancy=h["occupancy"], free_blocks=h["free_blocks"],
            blocks_in_use=h["blocks_in_use"],
            p95_step_ms=h["p95_step_ms"], hbm_mb=h["hbm_mb"],
            pool_mb=h["pool_mb"], mesh=h["mesh"],
            prefix_hit_rate=h["prefix_hit_rate"],
            cow_copies=h["cow_copies"],
            paged_live_chunk_share=h["paged_live_chunk_share"],
            steps_carried=h["steps_carried"],
            steps_uploaded=h["steps_uploaded"],
            eos_discarded_tokens=h["eos_discarded_tokens"],
            **h["counts"])

    # ------------------------------------------------------------ teardown --
    def close(self, exit: str = "ok", reason: Optional[str] = None) -> None:
        """End the stream (idempotent). A drain that a preemption
        signal started records the r13 exit contract — run_end
        {exit=preempted, reason=preempted} — so a fleet controller
        reads a served SIGTERM exactly like a trained one."""
        if self._closed:
            return
        if self._inflight is not None:
            # closed with requests still resident (a drained engine has
            # nothing in flight): read the step out so nothing is left
            # running; what it finishes is in the stream
            self._guard(self._settle, [])
        self._closed = True
        if self.guard is not None:
            self.guard.uninstall()
        if exit == "ok" and self.guard is not None and self.guard.triggered:
            exit, reason = "preempted", "preempted"
        self.telemetry.emit(
            "run_end", steps=self.decode_steps,
            wall_s=time.perf_counter() - self._t0, exit=exit,
            goodput=None, reason=reason)
        self.telemetry.close()

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, exc_type, *_) -> None:
        # unwinding an exception is NOT a clean exit: exit="error" with
        # the exception type as reason (the old code recorded the type
        # name AS the exit, so no reader could filter on a stable value)
        if exc_type is None:
            self.close()
        else:
            self.close(exit="error", reason=exc_type.__name__)
