"""Multi-tenant LoRA training engine: k adapter jobs, ONE base forward
(DESIGN.md §23; mLoRA / LoRAFusion, PAPERS.md).

A million-user product fine-tunes thousands of personal adapters against
the SAME frozen base; running them one CLI process at a time leaves the
memory-bound LoRA step mostly idle and pays inter-job compile/init
bubbles. This engine fuses k jobs into one compiled train step:

  - the adapter bank is a stacked [k, ...] trainable tree
    (lora.stack_adapters layout); each micro-batch row carries its
    adapter id and the ids-routed `_multi_lora` forward
    (models/lora_apply.py) makes per-adapter grads fall out of the
    gather's backward — one base forward serves every tenant's rows;
  - Adam m/v/step are stacked [k, ...] with PER-SLOT step counters, LR,
    and step budgets (optim/adam.multi_adam_update,
    train/trainer.make_multi_train_step) — every per-tenant quantity is
    data, and each tenant's update is numerically the solo step's
    (k-vs-solo parity <= 1e-5, tests/test_multitenant.py);
  - tenant slots are STATIC (the r11 ServeEngine discipline): jobs
    join/leave mid-run as data — admission writes the fresh adapter into
    slot j under ONE jitted `at[j].set` with a traced index and zeroes
    the slot's optimizer state; a finished job's slot refills from the
    pending queue with ZERO retraces (`trace_counts` is the observable);
  - per-tenant data streams multiplex round-robin through per-tenant
    bounded `Prefetcher`s (TenantMux): a stalled tenant cannot starve
    the other k-1 producers or grow unbounded host memory, and the
    step loop's wait is ATTRIBUTED per tenant (wait_ms);
  - each finished adapter saves independently through io/async_ckpt.py
    (bank snapshot -> `lora.unstack_adapter` slot slice -> the SAME
    peft_io writer the solo CLIs use, manifest + lineage + optional
    PEFT export) — a bank-trained adapter is byte-identical on disk to
    a solo-trained one, so serve/AdapterBank.load_file hot-loads it
    manifest-verified with no special casing.

Telemetry rides the existing stream: `tenant` lifecycle events
(admit/save/finish/cancel), per-tenant sections in step_stats
(`tenants` field), and checkpoint events from the shared async writer.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import time
from typing import Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.core.telemetry import Telemetry, run_manifest
from mobilefinetuner_tpu.data.prefetch import Prefetcher
from mobilefinetuner_tpu.io import async_ckpt
from mobilefinetuner_tpu.lora import peft_io
from mobilefinetuner_tpu.lora.lora import (assign_adapters, stack_adapters,
                                           trainable_mask, unstack_adapter)
from mobilefinetuner_tpu.models.family import get_family
from mobilefinetuner_tpu.multitenant.jobspec import JobSpec, validate_jobs
from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_rows
from mobilefinetuner_tpu.optim.adam import init_multi_state
from mobilefinetuner_tpu.train.trainer import (TrainConfig,
                                               make_multi_train_step)

log = get_logger()

# lock-discipline declaration (core/static_checks.py, DESIGN.md §24):
# threading lives INSIDE the per-tenant Prefetchers (each has its own
# producer thread + bounded queue, declared in data/prefetch.py); the
# mux and the engine itself run entirely on the training loop's thread.
GRAFT_SHARED_STATE = {
    "TenantMux": {
        "lock": None,
        "guarded": [],
        "channels": [],
        "note": "_pf/wait_ms are consumer-thread-only; cross-thread "
                "handoff is each Prefetcher's bounded queue",
    },
    "MultiTenantEngine": {
        "lock": None,
        "guarded": [],
        "channels": [],
        "note": "single-threaded step loop over the TenantMux",
    },
}


@dataclasses.dataclass
class EngineConfig:
    """Engine shape knobs — all STATIC: together they fix the ONE
    compiled train step every tenant shares. Per-job quantities (LR,
    budget, alpha, seeds, save policy) live in JobSpec as data."""
    slots: int = 2            # concurrent adapter jobs per step
    rows_per_tenant: int = 1  # micro-batch rows each tenant contributes
    grad_accum_steps: int = 1
    seq_len: int = 128
    dtype: str = "float32"    # compute dtype
    clip_grad_norm: float = 1.0
    weight_decay: float = 0.0
    schedule: str = "cosine"  # schedule SHAPE is engine-wide (a per-job
                              # branch would retrace); peak LR / warmup /
                              # budget are per-job data
    min_lr_ratio: float = 0.1
    lora_impl: str = "auto"
    skip_nonfinite: bool = False
    prefetch: int = 2         # per-tenant bounded queue depth (0 = sync)
    flush_every: int = 10     # buffered-metrics flush cadence (steps)
    async_save: bool = True
    out_dir: str = ""         # default save root for spec-less save_path
    dropout_seed: int = 1234  # engine-level dropout key (shared dropout
                              # rate comes from the jobs' common value)

    def validate(self) -> None:
        if self.slots < 1 or self.rows_per_tenant < 1 \
                or self.grad_accum_steps < 1:
            raise ValueError(
                "slots, rows_per_tenant, and grad_accum_steps must be "
                ">= 1")
        if self.prefetch < 0 or self.flush_every < 1:
            raise ValueError("prefetch must be >= 0, flush_every >= 1")

    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


class TenantMux:
    """Per-tenant bounded input queues, pulled round-robin (slot order)
    into one combined step batch. Each tenant gets its OWN Prefetcher
    (producer thread + bounded queue of `depth` step batches), so a
    stalled tenant stream (a) never blocks the other k-1 producers and
    (b) never grows unbounded host memory — the step loop still has to
    wait for the straggler's rows (every slot feeds the same compiled
    step), but the wait is ATTRIBUTED: `wait_ms[name]` accumulates
    exactly the time `pull(name)` blocked, which is what the per-tenant
    host_wait attribution in step_stats renders (the fairness
    observable tests/test_multitenant.py pins with an injected slow
    stream)."""

    def __init__(self, depth: int = 2):
        self.depth = max(int(depth), 0)
        self._pf: Dict[str, Prefetcher] = {}
        self.wait_ms: Dict[str, float] = {}

    def add(self, name: str, source: Iterable) -> None:
        if name in self._pf:
            raise ValueError(f"tenant {name!r} already has a stream")
        # lookahead=0: the mux holds HOST batches only (device placement
        # happens when the combined step batch is fed), so the bound on
        # buffered batches per tenant is exactly `depth`
        self._pf[name] = Prefetcher(source, depth=self.depth,
                                    lookahead=0)
        self.wait_ms[name] = 0.0

    def remove(self, name: str) -> None:
        pf = self._pf.pop(name, None)
        if pf is not None:
            pf.close()
        # a departed tenant's residual wait is dropped WITH its stream:
        # the accumulators always describe the current resident set
        self.wait_ms.pop(name, None)

    def pull(self, name: str):
        """Next step batch for `name`; blocks on a stalled producer and
        charges the wait to that tenant alone."""
        t0 = time.perf_counter()
        try:
            batch = next(self._pf[name])
        except StopIteration:
            raise RuntimeError(
                f"tenant {name!r}'s data stream ended before its step "
                f"budget (streams must cycle epochs like "
                f"cli/common.micro_batches)") from None
        self.wait_ms[name] += (time.perf_counter() - t0) * 1000.0
        return batch

    def queue_depth(self, name: Optional[str] = None) -> int:
        if name is not None:
            pf = self._pf.get(name)
            return pf.queue_depth() if pf is not None else 0
        return sum(pf.queue_depth() for pf in self._pf.values())

    def take_waits(self) -> Dict[str, float]:
        """Drain the per-tenant wait accumulators (one flush interval)."""
        out, self.wait_ms = self.wait_ms, {n: 0.0 for n in self.wait_ms}
        return out

    def close(self) -> None:
        for pf in self._pf.values():
            pf.close()
        self._pf.clear()


class _Tenant:
    """One admitted (or pending) job's runtime state."""

    __slots__ = ("spec", "slot", "steps_done", "tokens", "last_loss",
                 "status", "save_path")

    def __init__(self, spec: JobSpec, out_dir: str):
        self.spec = spec
        self.slot = -1
        self.steps_done = 0
        self.tokens = 0           # cumulative valid tokens trained
        self.last_loss: Optional[float] = None
        self.status = "pending"   # pending|active|finished|cancelled
        self.save_path = spec.resolved_save_path(out_dir)


class MultiTenantEngine:
    """Drive with run() (admit -> step until every job finishes) or the
    finer-grained admit_pending()/step() for tests; close() drains the
    async writer and terminates the telemetry stream.

    family: "gpt2" | "gemma"; config: the model config; params: the
    frozen base tree (shared by every tenant, never copied);
    make_stream(job) -> iterator of per-tenant step batches
    ({input_ids/attention_mask/labels} of [rows_per_tenant *
    grad_accum_steps, seq_len]) cycling epochs forever.
    """

    def __init__(self, family: str, config, params, jobs: List[JobSpec],
                 make_stream: Callable[[JobSpec], Iterable],
                 cfg: Optional[EngineConfig] = None,
                 telemetry: Optional[Telemetry] = None):
        cfg = cfg or EngineConfig()
        cfg.validate()
        fam = get_family(family)
        validate_jobs(jobs)
        self.family = family
        self.config = config
        self.cfg = cfg
        self.params = params
        self.k = cfg.slots
        self._fam = fam
        self._dropout = jobs[0].dropout      # shared (validate_jobs)
        self._make_stream = make_stream
        self.tel = telemetry or Telemetry("", enabled=False)
        self.trace_counts: collections.Counter = collections.Counter()

        # the stacked bank: slot shapes come from the SHARED spec (rank/
        # targets validated equal); empty slots are all-zero (delta == 0)
        template = fam.init_lora(
            config, jobs[0].lora_spec(fam.lora_init), jax.random.PRNGKey(0))
        zero = jax.tree.map(jnp.zeros_like, template)
        self.bank = stack_adapters([zero] * self.k)
        self.mask = trainable_mask(self.bank)
        self._tc = TrainConfig(
            total_steps=1, lr=0.0, warmup_ratio=0.0,
            schedule=cfg.schedule, min_lr_ratio=cfg.min_lr_ratio,
            clip_grad_norm=cfg.clip_grad_norm,
            grad_accum_steps=cfg.grad_accum_steps,
            weight_decay=cfg.weight_decay,
            skip_nonfinite=cfg.skip_nonfinite)
        self.opt = init_multi_state(self.bank, self._tc.adam(), self.k,
                                    self.mask)

        # per-slot schedule/apply arrays: HOST data handed to the step
        # each call — tenant join/leave/budget changes mutate these,
        # never a compiled program
        self._lr = np.zeros(self.k, np.float32)
        self._total = np.ones(self.k, np.float32)
        self._warmup = np.zeros(self.k, np.float32)
        self._step_k = np.zeros(self.k, np.int32)
        self._active = np.zeros(self.k, bool)

        compute_dtype = cfg.compute_dtype()

        def loss_rows(tr, frozen, mb):
            # trace-time only: the compile-stability counter (the jit
            # runs this Python exactly when it traces)
            self.trace_counts["train_step"] += 1
            routed = assign_adapters(tr, mb["adapter_ids"])
            rng = mb["dropout_rng"][0] if "dropout_rng" in mb else None
            logits = fam.model.forward(
                config, frozen, mb["input_ids"],
                attention_mask=mb["attention_mask"], lora=routed,
                compute_dtype=compute_dtype, lora_dropout=self._dropout,
                dropout_rng=rng, lora_impl=cfg.lora_impl)
            return lm_cross_entropy_rows(logits, mb["labels"])

        self._step_fn = make_multi_train_step(loss_rows, self._tc,
                                              self.k, self.mask)

        def _admit_py(bank, opt, new, j):
            self.trace_counts["admit"] += 1
            bank2 = jax.tree.map(
                lambda b, n: b.at[j].set(jnp.asarray(n).astype(b.dtype)),
                bank, new)
            zero_slot = lambda x: (
                x if x.ndim == 0 or x.shape[0] != self.k
                else x.at[j].set(jnp.zeros_like(x[0])))
            opt2 = dict(opt)
            opt2["step"] = opt["step"].at[j].set(0)
            opt2["m"] = jax.tree.map(zero_slot, opt["m"])
            opt2["v"] = jax.tree.map(zero_slot, opt["v"])
            if "v_hat" in opt:
                opt2["v_hat"] = jax.tree.map(zero_slot, opt["v_hat"])
            return bank2, opt2

        self._admit_jit = jax.jit(_admit_py, donate_argnums=(0, 1))
        self._zero_adapter = jax.tree.map(np.asarray, zero)

        # tenants + slots
        self.tenants: Dict[str, _Tenant] = {
            j.name: _Tenant(j, cfg.out_dir) for j in jobs}
        self.pending: collections.deque = collections.deque(
            self.tenants[j.name] for j in jobs)
        self.slot_tenant: List[Optional[_Tenant]] = [None] * self.k
        self.mux = TenantMux(depth=cfg.prefetch)
        self._zero_batch = None
        self.global_step = 0
        self._buffered: List[tuple] = []   # (gstep, names, metrics)
        self._t_interval = time.perf_counter()
        self._ema: Optional[float] = None
        self._dropout_key = (jax.random.PRNGKey(cfg.dropout_seed)
                             if self._dropout > 0 else None)
        self.ckpt = async_ckpt.AsyncCheckpointer(
            enabled=cfg.async_save, event_sink=self.tel.emit)
        self._closed = False
        self._t_start = time.time()
        self.tel.emit("run_start", **run_manifest(
            {"engine": "multitenant", "family": family,
             "slots": self.k, "jobs": [j.name for j in jobs],
             "rows_per_tenant": cfg.rows_per_tenant,
             "grad_accum_steps": cfg.grad_accum_steps,
             "seq_len": cfg.seq_len, "dtype": cfg.dtype,
             "schedule": cfg.schedule, "lora_impl": cfg.lora_impl},
            None))

    # ------------------------------------------------------------ info ----
    def total_traces(self) -> int:
        return sum(self.trace_counts.values())

    @property
    def active(self) -> List[_Tenant]:
        return [t for t in self.slot_tenant if t is not None]

    def _has_work(self) -> bool:
        return bool(self.pending or self.active)

    # ------------------------------------------------------- admission ----
    def admit_pending(self) -> int:
        """Fill free slots from the pending queue; returns jobs admitted."""
        n = 0
        for j in range(self.k):
            if self.slot_tenant[j] is None and self.pending:
                self._admit(self.pending.popleft(), j)
                n += 1
        return n

    def _admit(self, tenant: _Tenant, j: int) -> None:
        spec = tenant.spec
        fresh = self._fam.init_lora(
            self.config, spec.lora_spec(self._fam.lora_init),
            jax.random.PRNGKey(spec.seed))
        self.bank, self.opt = self._admit_jit(self.bank, self.opt, fresh,
                                              jnp.int32(j))
        self._lr[j] = spec.lr
        self._total[j] = spec.steps
        self._warmup[j] = spec.warmup_ratio
        self._step_k[j] = 0
        self._active[j] = True
        tenant.slot = j
        tenant.status = "active"
        self.slot_tenant[j] = tenant
        self.mux.add(spec.name, self._make_stream(spec))
        self.tel.emit("tenant", name=spec.name, slot=j, phase="admit",
                      step=0, job_steps=spec.steps, tokens=0, loss=None,
                      path=None, tenant=spec.name)
        log.info(f"tenant {spec.name!r} -> slot {j} "
                 f"(lr={spec.lr:g}, {spec.steps} steps)")

    def _release_slot(self, tenant: _Tenant) -> None:
        """Zero the slot (hygiene: a stale id can only reach a zero
        delta), free it, and refill from the pending queue — all data,
        zero retraces (the same jitted admit writer serves the zeroing
        and the refill)."""
        j = tenant.slot
        self.bank, self.opt = self._admit_jit(self.bank, self.opt,
                                              self._zero_adapter,
                                              jnp.int32(j))
        self._active[j] = False
        self._lr[j] = 0.0
        self.slot_tenant[j] = None
        tenant.slot = -1
        self.mux.remove(tenant.spec.name)
        if self.pending:
            self._admit(self.pending.popleft(), j)

    def cancel(self, name: str) -> None:
        """Cancel a pending or active job (no save); its slot refills."""
        t = self.tenants[name]
        slot = t.slot
        if t.status == "pending":
            self.pending.remove(t)
        elif t.status == "active":
            self._flush_metrics()
            self._release_slot(t)
        else:
            return
        t.status = "cancelled"
        self.tel.emit("tenant", name=name, slot=slot, phase="cancel",
                      step=t.steps_done, job_steps=t.spec.steps,
                      tokens=t.tokens, loss=t.last_loss, path=None,
                      tenant=name)

    # ------------------------------------------------------------ step ----
    def _batch_template(self):
        if self._zero_batch is None:
            rows = self.cfg.rows_per_tenant * self.cfg.grad_accum_steps
            S = self.cfg.seq_len
            self._zero_batch = {
                "input_ids": np.zeros((rows, S), np.int32),
                "attention_mask": np.zeros((rows, S), np.float32),
                "labels": np.zeros((rows, S), np.int32)}
        return self._zero_batch

    def _assemble(self) -> dict:
        """Pull one step batch per active slot (idle slots contribute
        zero rows the masked update ignores) and interleave them so
        `reshape_for_accum` slices accum micro-batches each carrying
        every tenant's rows: row (a, slot, r) -> a*k*b + slot*b + r."""
        A = self.cfg.grad_accum_steps
        b = self.cfg.rows_per_tenant
        S = self.cfg.seq_len
        k = self.k
        per_slot = []
        for j in range(k):
            t = self.slot_tenant[j]
            if t is None:
                per_slot.append(self._batch_template())
            else:
                tb = self.mux.pull(t.spec.name)
                if isinstance(tb, tuple):   # (epoch, batch) generators
                    tb = tb[-1]
                per_slot.append(tb)
        batch = {}
        for key, dt in (("input_ids", np.int32),
                        ("attention_mask", np.float32),
                        ("labels", np.int32)):
            buf = np.empty((A * k * b, S), dt)
            for a in range(A):
                for j, tb in enumerate(per_slot):
                    buf[a * k * b + j * b:a * k * b + (j + 1) * b] = \
                        tb[key][a * b:(a + 1) * b]
            batch[key] = buf
        batch["adapter_ids"] = np.tile(
            np.repeat(np.arange(k, dtype=np.int32), b), A)
        if self._dropout_key is not None:
            batch["dropout_rng"] = jax.random.split(
                jax.random.fold_in(self._dropout_key, self.global_step),
                A * k * b)
        return batch

    def step(self) -> None:
        """One fused optimizer step over every resident tenant, then the
        bookkeeping: per-slot step counters, flush cadence, completions
        (save + refill) at the step boundary."""
        if not self.active:
            self.admit_pending()
            if not self.active:
                return
        batch = self._assemble()
        sched = {"step": jnp.asarray(self._step_k),
                 "total": jnp.asarray(self._total),
                 "lr": jnp.asarray(self._lr),
                 "warmup_ratio": jnp.asarray(self._warmup),
                 "active": jnp.asarray(self._active)}
        self.bank, self.opt, metrics = self._step_fn(
            self.bank, self.params, self.opt, batch, sched)
        names = tuple(t.spec.name if t is not None else None
                      for t in self.slot_tenant)
        self._buffered.append((self.global_step, names, metrics))
        self.global_step += 1
        done: List[_Tenant] = []
        for j, t in enumerate(self.slot_tenant):
            if t is None:
                continue
            self._step_k[j] += 1
            t.steps_done += 1
            spec = t.spec
            if t.steps_done >= spec.steps:
                done.append(t)
            elif spec.save_every and t.steps_done % spec.save_every == 0:
                self._save_tenant(t, final=False)
        if self.global_step % self.cfg.flush_every == 0:
            self._flush_metrics()
        for t in done:
            self._finish(t)

    def run(self) -> None:
        """Admit, step until every job is finished, final flush."""
        self.admit_pending()
        while self._has_work():
            self.step()
        self._flush_metrics()

    # ----------------------------------------------------------- saves ----
    def _save_tenant(self, tenant: _Tenant, final: bool) -> None:
        """One tenant's independent save through the shared async
        writer: blocking part = ONE batched bank snapshot; the slot
        slice, safetensors write (atomic + manifest), lineage record,
        and optional PEFT export run on the writer thread."""
        # off-cadence boundary flush (the run_training save discipline):
        # the tenant event below reports tokens/loss, which only advance
        # at a flush — without this, a save landing before the cadence
        # flush would stamp stale (or zero) progress on a current
        # checkpoint
        self._flush_metrics()
        spec = tenant.spec
        j = tenant.slot
        step = tenant.steps_done
        path = tenant.save_path
        if not final:
            root, ext = os.path.splitext(path)
            path = f"{root}_step{step}{ext}"
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        bank_h, snap_ms = async_ckpt.timed_snapshot(self.bank)
        lspec = spec.lora_spec(self._fam.lora_init)
        name = spec.name
        final_path = tenant.save_path

        def write():
            tree = unstack_adapter(bank_h, j)
            peft_io.save_adapter(path, tree, lspec,
                                 extra_metadata={"tenant": name,
                                                 "loop_step": str(step)})
            try:
                from mobilefinetuner_tpu.io.checkpoints import \
                    record_checkpoint
                record_checkpoint(final_path, step, [path],
                                  keep=max(spec.keep_ckpts, 0))
            except Exception as e:
                log.warning(f"tenant {name!r} lineage update failed: {e}")
            if final and spec.peft_export_dir:
                peft_io.export_peft(spec.peft_export_dir, tree, lspec,
                                    self.family)
            return [path]

        async_ckpt.submit(self.ckpt, step, write, final=final,
                          snapshot_ms=snap_ms)
        self.tel.emit("tenant", name=name, slot=j, phase="save",
                      step=step, job_steps=spec.steps,
                      tokens=tenant.tokens, loss=tenant.last_loss,
                      path=path, tenant=name)

    def _finish(self, tenant: _Tenant) -> None:
        self._save_tenant(tenant, final=True)  # flushes first
        tenant.status = "finished"
        self.tel.emit("tenant", name=tenant.spec.name, slot=tenant.slot,
                      phase="finish", step=tenant.steps_done,
                      job_steps=tenant.spec.steps, tokens=tenant.tokens,
                      loss=tenant.last_loss, path=tenant.save_path,
                      tenant=tenant.spec.name)
        log.info(f"tenant {tenant.spec.name!r} finished at step "
                 f"{tenant.steps_done} -> {tenant.save_path}")
        self._release_slot(tenant)

    # --------------------------------------------------------- metrics ----
    def _flush_metrics(self) -> None:
        """One device_get for everything buffered since the last flush
        (the zero-sync invariant): per-slot [k] metric vectors are
        attributed to the tenant resident in that slot AT THAT STEP
        (refills mid-interval keep their history straight), aggregates
        land as a schema-valid step_stats with the per-tenant `tenants`
        section, and the mux's per-tenant wait attribution rides along."""
        if not self._buffered:
            return
        # graftlint: disable=sync-hazard(the zero-sync contract: ONE device_get per metrics flush, DESIGN.md section 23)
        fetched = jax.device_get([m for _, _, m in self._buffered])
        dt_ms = ((time.perf_counter() - self._t_interval) * 1000.0
                 / len(self._buffered))
        waits = self.mux.take_waits()
        tenants_out: Dict[str, dict] = {}
        total_tokens = 0.0
        for (gstep, names, _), m in zip(self._buffered, fetched):
            for j, name in enumerate(names):
                if name is None:
                    continue
                t = self.tenants[name]
                toks = float(m["tokens"][j])
                t.tokens += int(toks)
                total_tokens += toks
                if m["active"][j]:
                    t.last_loss = float(m["loss"][j])
        last = fetched[-1]
        names = self._buffered[-1][1]
        act = [j for j in range(self.k) if names[j] is not None]
        for j in act:
            t = self.tenants[names[j]]
            tenants_out[names[j]] = {
                "slot": j, "step": t.steps_done,
                "loss": t.last_loss, "tokens": t.tokens,
                "wait_ms": round(waits.get(names[j], 0.0), 2)}
        def mean(key):
            vals = [float(last[key][j]) for j in act]
            return sum(vals) / len(vals) if vals else 0.0
        w = np.asarray(last["tokens"], np.float64)
        l = np.asarray(last["loss"], np.float64)
        wsum = float(sum(w[j] for j in act)) or 1.0
        loss = float(sum(l[j] * w[j] for j in act)) / wsum
        self._ema = loss if self._ema is None else \
            0.9 * self._ema + 0.1 * loss
        n_steps = len(self._buffered)
        step_time_s = max(dt_ms / 1000.0, 1e-9)
        self.tel.emit(
            "step_stats", step=self.global_step, loss=loss,
            ema=self._ema, lr=mean("lr"), grad_norm=mean("grad_norm"),
            step_time_ms=dt_ms,
            host_wait_ms=sum(waits.values()) / n_steps,
            slept_ms=None, tok_s=total_tokens / n_steps / step_time_s,
            mfu=None, param_norm=mean("param_norm"),
            update_ratio=mean("update_ratio"),
            nonfinite_count=int(sum(int(last["nonfinite_count"][j])
                                    for j in act)),
            skipped=int(sum(int(fm["skipped"][j]) for fm in fetched
                            for j in act)),
            hbm_mb=None, queue_depth=self.mux.queue_depth(),
            host_step_ms=None, tenants=tenants_out)
        self._buffered.clear()
        self._t_interval = time.perf_counter()

    # -------------------------------------------------------- lifecycle ----
    def close(self, exit_name: str = "ok") -> None:
        """Drain the async writer, stop the tenant producers, terminate
        the stream with run_end (exactly once — idempotent)."""
        if self._closed:
            return
        self._closed = True
        try:
            self._flush_metrics()
            self.ckpt.close(raise_errors=exit_name == "ok")
        finally:
            self.mux.close()
            self.tel.emit("run_end", steps=self.global_step,
                          wall_s=round(time.time() - self._t_start, 3),
                          exit=exit_name, goodput=None)
            self.tel.close()

    def __enter__(self) -> "MultiTenantEngine":
        return self

    def __exit__(self, exc_type, *exc) -> None:
        # the stream records HOW the run ended (the run_training
        # contract): an exception's run_end names its type, and writer
        # errors must not mask it
        self.close("ok" if exc_type is None else exc_type.__name__)
