"""Jitted train/eval step builders: value_and_grad + lax.scan gradient
accumulation + clip + LR schedule + Adam, one XLA program per optimizer step.

Re-design of the reference trainer loops (reference: optim/trainer.{h,cpp}
`LoRATrainer`, optim/gemma_trainer.{h,cpp} `GemmaLoRATrainer`, and the inline
loop in gpt2_lora_finetune/main.cpp:561-684): where the reference runs
per-micro-batch Python-level forward/backward with loss scaled by 1/accum
(main.cpp:569-583), we scan over the micro-batch axis INSIDE the compiled
step — micro-batches stream through one compiled block, gradients accumulate
in registers/HBM, and the optimizer update happens in the same program
(no host round-trips inside an optimizer step).

Generic over "what is trainable": LoRA training passes the LoRA tree as
`trainable` and the frozen base params as `frozen`; full fine-tuning passes
the model params as `trainable`. The loss_fn contract is
loss_fn(trainable, frozen, micro_batch) -> scalar loss.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.optim.adam import (AdamConfig, adam_update,
                                            clip_by_global_norm, init_state)
from mobilefinetuner_tpu.optim.schedule import lr_schedule


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    total_steps: int = 1000
    lr: float = 1e-4
    warmup_ratio: float = 0.03
    schedule: str = "cosine"  # cosine | linear | constant
    min_lr_ratio: float = 0.1
    clip_grad_norm: float = 1.0
    grad_accum_steps: int = 1
    weight_decay: float = 0.0
    coupled_weight_decay: bool = False
    amsgrad: bool = False
    # guarded update (--skip_nonfinite, DESIGN.md §20): when the step's
    # gradients carry any non-finite element (or the global grad norm is
    # non-finite), the Adam update degenerates to identity — params and
    # optimizer state pass through via a jnp.where tree-select inside the
    # SAME compiled program (donation and AOT shardings untouched, the
    # LR schedule still advances with the loop step), and a `skipped`
    # flag rides the buffered metrics with zero added host syncs.
    skip_nonfinite: bool = False

    def adam(self) -> AdamConfig:
        return AdamConfig(lr=self.lr, weight_decay=self.weight_decay,
                          coupled_weight_decay=self.coupled_weight_decay,
                          amsgrad=self.amsgrad)


def reshape_for_accum(batch: dict, accum: int) -> dict:
    """[accum*micro_b, ...] arrays -> [accum, micro_b, ...] for lax.scan.

    The step's accum and the data stream's accum are allowed to differ:
    the memory-admission degradation ladder (cli/common.run_training,
    DESIGN.md §21) rebuilds the step with DOUBLED grad_accum_steps at
    constant global batch — the same [rows, ...] step batch simply
    scans as twice as many half-size micro-batches, so batch shapes,
    shardings, and donation are untouched and only float reassociation
    moves (loss parity <=1e-5). The divisibility assert below is the
    ladder's gate: a rung that cannot split further is skipped."""
    def r(x):
        total = x.shape[0]
        assert total % accum == 0, (total, accum)
        return x.reshape(accum, total // accum, *x.shape[1:])
    return jax.tree.map(r, batch)


def make_train_step(loss_fn: Callable[[Any, Any, dict], tuple],
                    train_cfg: TrainConfig,
                    mask: Optional[Any] = None,
                    donate: bool = True,
                    in_shardings=None, out_shardings=None):
    """Build the jitted optimizer step.

    loss_fn(trainable, frozen, micro_batch) -> (sum_loss, weight): the SUM
    of per-token losses and the token count (or any weight). A third
    element, {"sum": {name: scalar}, "max": {name: scalar}}, carries the
    model's own device counters (models/afmoe.py: assignments to the held
    experts, their load imbalance): summed or maximised over the
    micro-batches, they ride the metrics (`counters_sum`, `counters_max`)
    and reach `step_stats` with the loss at the flush, at no sync of
    their own. Accumulation
    sums both across micro-batches and divides once at the end, so the
    update equals the gradient of total_loss/total_weight over the whole
    batch — exact even when micro-batches have unequal valid-token counts
    (masked labels), unlike mean-of-means accumulation. (The reference
    scales each micro loss by 1/accum, main.cpp:569-583, which has the
    mean-of-means bias; we keep the exact semantics.)

    Returns step_fn(trainable, frozen, opt_state, batch, step) ->
    (trainable, opt_state, metrics) where batch leaves are
    [accum*micro_b, ...] and step is the 0-based optimizer step index
    (drives the LR schedule as a traced value — no recompiles).
    metrics = {loss, grad_norm, lr} (scalars, pre-clip global norm as in
    main.cpp:490-516) plus the on-device train-health scalars
    {param_norm, update_ratio, nonfinite_count, skipped} (`skipped` is
    1 exactly when the skip_nonfinite guard turned this update into
    identity, else 0): ||w|| over the
    trainable leaves (pre-update — measured inside the optimizer kernel
    so the donated tree's lifetime is untouched), the step's relative
    update size ||Δw||/||w||, and the global count of non-finite
    gradient elements. All of them are device scalars that
    ride the step loop's buffered-metrics path (cli/common.run_training
    pulls the whole buffer in ONE device_get per flush), so health
    monitoring adds zero per-step host syncs — the telemetry
    zero-sync invariant (DESIGN.md §13).
    """
    accum = train_cfg.grad_accum_steps
    adam_cfg = train_cfg.adam()

    def step_fn(trainable, frozen, opt_state, batch, step):
        batch = dict(batch)
        # fault-injection seam (--inject grad_nan, cli/common.py): when
        # armed, every batch carries a [B] "grad_scale" row (1.0 on
        # clean steps, NaN in the poison window) that multiplies the
        # accumulated gradients INSIDE the compiled step — the honest
        # way to produce non-finite grads end to end. [B]-shaped so it
        # shards like every other batch leaf; absent on normal runs
        # (the key changes the compiled program, never per-step work).
        gscale = batch.pop("grad_scale", None)
        micro = reshape_for_accum(batch, accum)

        def sum_fn(tr, mb):
            s, w, *counters = loss_fn(tr, frozen, mb)
            return s, (w, counters[0] if counters else {})

        vg = jax.value_and_grad(sum_fn, has_aux=True)

        def body(carry, mb):
            g_acc, loss_acc, w_acc = carry
            (s, (w, counters)), g = vg(trainable, mb)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            return (g_acc, loss_acc + s,
                    w_acc + w.astype(jnp.float32)), counters

        g0 = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32),
                          trainable)
        (g_sum, loss_sum, w_sum), counters = jax.lax.scan(
            body, (g0, jnp.float32(0.0), jnp.float32(0.0)), micro)
        inv = 1.0 / jnp.maximum(w_sum, 1.0)
        grads = jax.tree.map(lambda g: g * inv, g_sum)
        if gscale is not None:
            s = gscale.reshape(-1)[0].astype(jnp.float32)
            grads = jax.tree.map(lambda g: g * s, grads)
        loss = loss_sum * inv
        # health: count non-finite grad elements BEFORE clipping (clip
        # propagates a NaN norm into every element, which would turn one
        # bad value into "all of them")
        nonfinite = sum(jnp.sum(~jnp.isfinite(g))
                        for g in jax.tree.leaves(grads))
        if train_cfg.clip_grad_norm and train_cfg.clip_grad_norm > 0:
            grads, norm = clip_by_global_norm(grads,
                                              train_cfg.clip_grad_norm)
        else:
            from mobilefinetuner_tpu.optim.adam import global_norm
            norm = global_norm(grads)
        lr = lr_schedule(step, train_cfg.total_steps, train_cfg.lr,
                         train_cfg.warmup_ratio, train_cfg.schedule,
                         train_cfg.min_lr_ratio)
        with jax.named_scope("optimizer"):
            # ||Δw|| and pre-update ||w|| come from INSIDE the update
            # (adam_update with_norms), where the delta already exists —
            # a post-hoc new-minus-old subtraction would keep the donated
            # pre-update tree alive past the in-place write and cost a
            # params-sized peak-HBM bump on full fine-tunes.
            trainable2, opt_state2, (upd_norm, w_norm) = adam_update(
                grads, opt_state, trainable, adam_cfg, lr, mask,
                with_norms=True)
        if train_cfg.skip_nonfinite:
            # guarded update: a scalar `bad` predicate selects, per leaf,
            # the PRE-update tree (params, Adam m/v AND Adam's own step
            # counter — a skipped step must not advance bias correction).
            # On clean steps jnp.where(False, old, new) IS `new`
            # bitwise, so the guard is numerically free — a guarded
            # clean run's loss trajectory is byte-identical to an
            # unguarded one (tests/test_recovery.py pins it). The
            # select happens inside the same compiled program: output
            # structure/shardings are unchanged, donation stays legal.
            bad = (nonfinite > 0) | ~jnp.isfinite(norm)
            keep = lambda old, new: jnp.where(bad, old, new)
            trainable2 = jax.tree.map(keep, trainable, trainable2)
            opt_state2 = jax.tree.map(keep, opt_state, opt_state2)
            skipped = bad.astype(jnp.int32)
        else:
            skipped = jnp.zeros((), jnp.int32)
        metrics = {"loss": loss, "grad_norm": norm, "lr": lr,
                   "param_norm": w_norm,
                   "update_ratio": upd_norm / jnp.maximum(w_norm, 1e-20),
                   "nonfinite_count": nonfinite.astype(jnp.int32),
                   "skipped": skipped}
        if counters:    # stacked over the micro-batches by the scan
            metrics["counters_sum"] = {
                k: v.sum() for k, v in counters.get("sum", {}).items()}
            metrics["counters_max"] = {
                k: v.max() for k, v in counters.get("max", {}).items()}
        return trainable2, opt_state2, metrics

    donate_argnums = (0, 2) if donate else ()
    return jax.jit(step_fn, donate_argnums=donate_argnums,
                   in_shardings=in_shardings, out_shardings=out_shardings)


def make_multi_train_step(loss_rows_fn: Callable[[Any, Any, dict], tuple],
                          train_cfg: TrainConfig, k: int,
                          mask: Optional[Any] = None,
                          donate: bool = True):
    """The multi-tenant optimizer step: k independent LoRA jobs through
    ONE compiled program (DESIGN.md §23, mobilefinetuner_tpu/multitenant/).

    loss_rows_fn(stacked_trainable, frozen, micro_batch) -> (row_nll_sums
    [R], row_token_counts [R]): per-ROW loss over a micro-batch whose
    every row carries its adapter id in micro_batch["adapter_ids"] [R]
    (the ids-routed `_multi_lora` forward — models/lora_apply.py — makes
    per-adapter grads fall out of the per-row gather's backward: slot j's
    gradient is the scatter-add of exactly its own rows' contributions).

    Per-tenant exactness (the k-vs-solo parity oracle): the scan
    accumulates UNNORMALIZED per-slot loss/token sums plus the grads of
    the total row-sum, then normalizes slot j's gradient by slot j's OWN
    token count, clips by slot j's own pre-clip norm, schedules slot j's
    own LR from its own step counter, and applies a per-slot Adam update
    with per-slot bias correction (optim/adam.multi_adam_update) — every
    per-slot quantity is the solo step's formula with the batch axis
    re-labelled, so adapter j's trajectory matches a solo run on the
    same data/seed to float-reassociation noise (<= 1e-5, pinned by
    tests/test_multitenant.py).

    step_fn(trainable, frozen, opt_state, batch, sched) ->
    (trainable, opt_state, metrics): `sched` carries the per-slot [k]
    DATA arrays {step, total, lr, warmup_ratio, active} — tenant
    join/leave/refill, per-job budgets, and per-job LR schedules never
    retrace. Inactive slots (active=False) contribute dummy rows whose
    grads are computed and discarded: params, Adam m/v, AND the slot's
    Adam step counter pass through untouched, so a refilled slot starts
    from a genuinely fresh optimizer state. metrics are per-slot [k]
    vectors (loss, grad_norm, lr, tokens, nonfinite_count, skipped,
    param_norm, update_ratio) riding the caller's buffered-metrics path
    (one device_get per flush, the zero-sync telemetry invariant).
    """
    from mobilefinetuner_tpu.optim.adam import (clip_by_slot_norm,
                                                multi_adam_update,
                                                slot_norms)
    from mobilefinetuner_tpu.optim.schedule import multi_lr_schedule
    accum = train_cfg.grad_accum_steps
    adam_cfg = train_cfg.adam()

    def step_fn(trainable, frozen, opt_state, batch, sched):
        micro = reshape_for_accum(dict(batch), accum)

        def sum_fn(tr, mb):
            s_rows, w_rows = loss_rows_fn(tr, frozen, mb)
            return s_rows.sum(), (s_rows, w_rows)

        vg = jax.value_and_grad(sum_fn, has_aux=True)

        def seg(rows, ids):
            return jnp.zeros((k,), jnp.float32).at[ids].add(
                rows.astype(jnp.float32))

        def body(carry, mb):
            g_acc, loss_k, w_k = carry
            (_, (s_rows, w_rows)), g = vg(trainable, mb)
            ids = mb["adapter_ids"]
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            return (g_acc, loss_k + seg(s_rows, ids),
                    w_k + seg(w_rows, ids)), None

        g0 = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32),
                          trainable)
        z = jnp.zeros((k,), jnp.float32)
        (g_sum, loss_sum_k, w_k), _ = jax.lax.scan(body, (g0, z, z), micro)
        inv = 1.0 / jnp.maximum(w_k, 1.0)                       # [k]
        bsel = lambda v, x: v.reshape((k,) + (1,) * (x.ndim - 1))
        grads = jax.tree.map(lambda g: g * bsel(inv, g), g_sum)
        loss_k = loss_sum_k * inv
        # per-slot non-finite census BEFORE clipping (a NaN norm would
        # smear one bad slot's poison over its whole tree — and per-slot
        # isolation is the point: tenant j's NaN must not gate tenant i)
        nonfinite_k = None
        for g in jax.tree.leaves(grads):
            s = jnp.sum(~jnp.isfinite(g), axis=tuple(range(1, g.ndim)))
            nonfinite_k = s if nonfinite_k is None else nonfinite_k + s
        if train_cfg.clip_grad_norm and train_cfg.clip_grad_norm > 0:
            grads, norm_k = clip_by_slot_norm(grads,
                                              train_cfg.clip_grad_norm)
        else:
            norm_k = slot_norms(grads)
        lr_k = multi_lr_schedule(sched["step"], sched["total"],
                                 sched["lr"], sched["warmup_ratio"],
                                 train_cfg.schedule,
                                 train_cfg.min_lr_ratio)
        active = jnp.asarray(sched["active"]).astype(bool)        # [k]
        apply_k = active
        if train_cfg.skip_nonfinite:
            bad = (nonfinite_k > 0) | ~jnp.isfinite(norm_k)
            apply_k = active & ~bad
            skipped = (active & bad).astype(jnp.int32)
        else:
            skipped = jnp.zeros((k,), jnp.int32)
        with jax.named_scope("optimizer"):
            trainable2, opt_state2, (upd_k, wn_k) = multi_adam_update(
                grads, opt_state, trainable, adam_cfg, lr_k, apply_k,
                mask, with_norms=True)
        metrics = {"loss": loss_k, "grad_norm": norm_k, "lr": lr_k,
                   "tokens": w_k,
                   "param_norm": wn_k,
                   "update_ratio": upd_k / jnp.maximum(wn_k, 1e-20),
                   "nonfinite_count": nonfinite_k.astype(jnp.int32),
                   "skipped": skipped,
                   "active": active.astype(jnp.int32)}
        return trainable2, opt_state2, metrics

    donate_argnums = (0, 2) if donate else ()
    return jax.jit(step_fn, donate_argnums=donate_argnums)


def make_eval_step(nll_fn: Callable[[Any, Any, dict], tuple]):
    """Jitted eval step: nll_fn(trainable, frozen, batch) ->
    (sum_nll, token_count). Token-weighted accumulation is the caller's job
    (eval_ppl.cpp:157-200 semantics)."""
    @jax.jit
    def eval_step(trainable, frozen, batch):
        return nll_fn(trainable, frozen, batch)
    return eval_step


def init_optimizer(trainable, train_cfg: TrainConfig,
                   mask: Optional[Any] = None) -> dict:
    return init_state(trainable, train_cfg.adam(), mask)


# The trainer's timing hook for the fleet-observability layer
# (DESIGN.md §14): the step loop records each completed optimizer step's
# wall seconds (deliberate idleness — governor sleep, input wait —
# excluded by the caller) and the straggler-attribution cadence gathers
# `median_ms()` across hosts via `parallel.distributed.allgather_scalars`.
# ONE implementation serves both it and the hang watchdog's deadline
# window, so it lives in core/telemetry (no jax dependency) and is
# re-exported here as the training-facing surface.
from mobilefinetuner_tpu.core.telemetry import StepClock  # noqa: E402,F401
