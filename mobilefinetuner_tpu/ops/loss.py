"""Language-model cross-entropy loss.

Semantics mirror the reference's `lm_cross_entropy`
(reference: operators/finetune_ops/core/lm_loss.cpp:19-103):
  - the HF label shift is performed INTERNALLY (logits[:, :-1] vs
    labels[:, 1:], lm_loss.cpp:27-32) — callers pass UNSHIFTED labels and
    must not shift again (SURVEY.md §2.12.4);
  - ignore_index = -100 positions contribute nothing and are excluded from
    the valid-token count;
  - "mean" reduction divides by the number of valid (non-ignored) tokens;
  - numerically stable logsumexp in fp32 regardless of logits dtype.

The backward is JAX autodiff of this forward — analytically identical to the
reference's fused `(softmax - onehot)/valid_count` (lm_loss.cpp:105+).

`chunked_lm_cross_entropy` fuses the lm_head projection with the loss over
sequence chunks so the full [B,S,V] logits tensor is never materialized —
needed for Gemma-3's 262k vocab (SURVEY.md §7 hard part (d)).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

IGNORE_INDEX = -100


def _shift(logits: jnp.ndarray, labels: jnp.ndarray):
    return logits[:, :-1, :], labels[:, 1:]


def _token_nll(logits: jnp.ndarray, labels: jnp.ndarray,
               ignore_index: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token NLL (fp32) and validity mask. No shift here."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, safe_labels[..., None], axis=-1).squeeze(-1)
    nll = jnp.where(valid, lse - gold, 0.0)
    return nll, valid


def lm_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                     ignore_index: int = IGNORE_INDEX,
                     reduction: str = "mean") -> jnp.ndarray:
    """Causal-LM loss over UNSHIFTED labels; shift happens inside.

    logits: [B, S, V] (any float dtype), labels: [B, S] int.
    Returns scalar for "mean"/"sum", [B, S-1] for "none".
    """
    with jax.named_scope("loss"):
        logits_s, labels_s = _shift(logits, labels)
        nll, valid = _token_nll(logits_s, labels_s, ignore_index)
        if reduction == "none":
            return nll
        total = nll.sum()
        if reduction == "sum":
            return total
        count = jnp.maximum(valid.sum(), 1)
        return total / count


def lm_cross_entropy_sum(
        logits: jnp.ndarray, labels: jnp.ndarray,
        ignore_index: int = IGNORE_INDEX) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum_nll, valid_token_count) — the accumulation-friendly form used by
    the train step for exact token-weighted gradient accumulation."""
    with jax.named_scope("loss"):
        logits_s, labels_s = _shift(logits, labels)
        nll, valid = _token_nll(logits_s, labels_s, ignore_index)
        return nll.sum(), valid.sum()


def lm_cross_entropy_rows(
        logits: jnp.ndarray, labels: jnp.ndarray,
        ignore_index: int = IGNORE_INDEX) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-ROW (sum_nll [B], valid_token_count [B]) — the multi-tenant
    train step's form (train/trainer.make_multi_train_step): each batch
    row belongs to one adapter job, so the step segment-sums these row
    vectors by adapter id and normalizes each tenant's gradient by its
    OWN token count (summing first and normalizing jointly would couple
    every tenant's update to the others' token counts). Summing the two
    vectors recovers lm_cross_entropy_sum exactly."""
    with jax.named_scope("loss"):
        logits_s, labels_s = _shift(logits, labels)
        nll, valid = _token_nll(logits_s, labels_s, ignore_index)
        return nll.sum(axis=-1), valid.sum(axis=-1)


def lm_cross_entropy_with_count(
        logits: jnp.ndarray, labels: jnp.ndarray,
        ignore_index: int = IGNORE_INDEX) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(mean_loss, valid_token_count) — eval_ppl needs token-weighted
    accumulation (reference: gpt2_lora_finetune/eval_ppl.cpp:157-200)."""
    with jax.named_scope("loss"):
        logits_s, labels_s = _shift(logits, labels)
        nll, valid = _token_nll(logits_s, labels_s, ignore_index)
        count = valid.sum()
        return nll.sum() / jnp.maximum(count, 1), count


def chunk_len(S: int, num_chunks: int) -> int:
    """Per-chunk length _shift_and_chunk produces for a [B, S, H] input —
    THE one copy of the pad arithmetic (the SP eligibility gate and the
    dryrun's phase guard both depend on it staying in lockstep)."""
    Sm1 = S - 1
    return (Sm1 + ((-Sm1) % num_chunks)) // num_chunks


def _shift_and_chunk(hidden, labels, ignore_index, num_chunks):
    """Shared shift/pad/chunk front end: [B,S,H] -> [num_chunks,B,chunk,H]
    (positions 0..S-2 predict labels 1..S-1; the pad tail is ignored)."""
    B, S, H = hidden.shape
    hidden_s = hidden[:, :-1, :]
    labels_s = labels[:, 1:]
    pad = num_chunks * chunk_len(S, num_chunks) - (S - 1)
    if pad:
        hidden_s = jnp.pad(hidden_s, ((0, 0), (0, pad), (0, 0)))
        labels_s = jnp.pad(labels_s, ((0, 0), (0, pad)),
                           constant_values=ignore_index)
    chunk = chunk_len(S, num_chunks)
    hs = hidden_s.reshape(B, num_chunks, chunk, H).swapaxes(0, 1)
    ls = labels_s.reshape(B, num_chunks, chunk).swapaxes(0, 1)
    return hs, ls


def _vp_chunked_nll_sum(hidden, lm_head_w, labels, ignore_index, num_chunks,
                        mesh, batch_axis, vocab_axis, seq_shard=False):
    """Vocab-parallel chunked CE under shard_map — the multi-device path.

    The fsdp-sharded [V, H] head table must NOT be all-gathered per step:
    without explicit structure GSPMD picks exactly that (gather the table,
    keep B fully sharded), which for Gemma's 262k vocab re-materializes
    the full table in HBM each step and defeats FSDP — and
    with_sharding_constraint on the logits alone is not enough (the
    partitioner's cost model still gathers the table at large mesh
    sizes). shard_map makes the Megatron-style algorithm structural:
    each device holds its [V/n, H] shard, computes its logits slice
    [B/data, chunk, V/n], and the softmax statistics reduce over the
    vocab axis with three tiny psums per chunk (max, sum-exp, gold
    logit). Per-device FLOPs equal the batch-sharded layout; the only
    resharding is a small hidden all-gather over the vocab axis.
    Gradients flow through dot/psum/take_along_axis (pmax is wrapped in
    stop_gradient — the lse value is invariant to the max shift, so the
    softmax gradient is exact). tests/test_multichip.py asserts the
    compiled HLO carries no full-table all-gather.

    seq_shard=True is the SEQUENCE-PARALLEL composition (round-5 verdict
    item 2): under ring attention the vocab axis ("fsdp") carries the
    sequence, so the incoming chunk dim arrives sharded over that same
    axis. Each scan step then all-gathers its [B/data, chunk/n, H] hidden
    slice over the axis (tiny — hidden bytes, the Megatron gather-at-head
    move) and proceeds exactly as above: the table stays [V/n, H]-sharded
    and the per-device logits block stays [B/data, chunk, V/n]. The
    gather's transpose is a reduce-scatter of dH back to each device's
    own sequence slice, so the backward keeps the sequence sharded too.
    """
    from jax.sharding import PartitionSpec as P

    if jnp.issubdtype(hidden.dtype, jnp.floating):
        lm_head_w = lm_head_w.astype(hidden.dtype)
    hs, ls = _shift_and_chunk(hidden, labels, ignore_index, num_chunks)

    def local(hs, ls, w):
        vloc = w.shape[0]
        start = jax.lax.axis_index(vocab_axis) * vloc

        def body(carry, xs):
            total, count = carry
            h, lab = xs
            if seq_shard:
                # reassemble the full chunk from the sequence shards; lab
                # enters unsharded on this axis (tiny int array)
                h = jax.lax.all_gather(h, vocab_axis, axis=1, tiled=True)
            logits = jax.lax.dot_general(
                h, w, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [B_loc, chunk, V/n]
            valid = lab != ignore_index
            # global max via all_gather (pmax has no differentiation rule
            # even under an outer stop_gradient — tracing is inside-out);
            # the gathered tensor is a tiny [n, B_loc, chunk]
            m = jax.lax.stop_gradient(
                jax.lax.all_gather(logits.max(-1), vocab_axis).max(0))
            se = jnp.sum(jnp.exp(logits - m[..., None]), -1)
            lse = jnp.log(jax.lax.psum(se, vocab_axis)) + m
            loc = lab - start
            in_shard = valid & (loc >= 0) & (loc < vloc)
            safe = jnp.clip(loc, 0, vloc - 1)
            gold_loc = jnp.take_along_axis(
                logits, safe[..., None], axis=-1)[..., 0]
            gold = jnp.where(in_shard, gold_loc, 0.0)
            gold = jax.lax.psum(gold, vocab_axis)
            nll = jnp.where(valid, lse - gold, 0.0)
            return (total + nll.sum(), count + valid.sum()), None

        (total, count), _ = jax.lax.scan(
            jax.checkpoint(body), (jnp.float32(0.0), jnp.int32(0)),
            (hs, ls))
        # batch is sharded over batch_axis only (vocab-axis members hold
        # identical replicas after the psums above)
        return (jax.lax.psum(total, batch_axis),
                jax.lax.psum(count, batch_axis))

    chunk_spec = vocab_axis if seq_shard else None
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, batch_axis, chunk_spec, None),
                  P(None, batch_axis, None), P(vocab_axis, None)),
        out_specs=(P(), P()), check_vma=False)(hs, ls, lm_head_w)


def vp_embed_lookup(table, ids, mesh, *, vocab_axis: str = "fsdp",
                    batch_axis: str = "data"):
    """Sequence-parallel vocab-parallel embedding LOOKUP: the Megatron
    front-end companion of _vp_chunked_nll_sum's head.

    Under --sequence_parallel one mesh axis carries BOTH the sequence
    shard of the activations and the vocab shard of the tied [V, H]
    table. Left to itself, GSPMD's cost model resolves `table[ids]` by
    ALL-GATHERING THE TABLE at large mesh sizes (observed at fsdp >= 16
    in the pod dryrun; at fsdp=4 it happens to pick the sharded plan) —
    re-materializing the 262k-row table per step, exactly the failure
    the vocab-parallel CE exists to prevent. shard_map makes the sharded
    plan structural: each device all-gathers the TINY int ids over the
    axis, looks the full sequence up against its OWN table shard
    (out-of-shard rows contribute zero), and the partial embeddings
    psum_scatter straight back to the sequence shard — one [B, S, H]
    reduce-scatter, the same bytes the SP activations already move, and
    the full table never exists. Differentiable end to end: the
    psum_scatter's transpose is an all-gather and the masked take's is a
    scatter-add into the local shard, so the trainable tied embed (full
    FT) gets exact vocab-sharded gradients.

    ids: [B, S] int, sequence-sharded over `vocab_axis` (batch over
    `batch_axis` when present); table: [V, H] V-sharded. V and S must
    divide by the axis size (the caller gates). Returns [B, S, H] in the
    table's dtype, sharded like the SP activations."""
    from jax.sharding import PartitionSpec as P
    ba = batch_axis if batch_axis in mesh.axis_names else None

    def local(w, ids_loc):
        vloc = w.shape[0]
        start = jax.lax.axis_index(vocab_axis) * vloc
        ids_full = jax.lax.all_gather(ids_loc, vocab_axis, axis=1,
                                      tiled=True)          # [B_loc, S]
        loc = ids_full - start
        in_shard = (loc >= 0) & (loc < vloc)
        safe = jnp.clip(loc, 0, vloc - 1)
        e = jnp.take(w, safe, axis=0)                      # [B_loc, S, H]
        e = jnp.where(in_shard[..., None], e, 0)
        return jax.lax.psum_scatter(e, vocab_axis, scatter_dimension=1,
                                    tiled=True)            # [B_loc, S/n, H]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(vocab_axis, None), P(ba, vocab_axis)),
        out_specs=P(ba, vocab_axis, None), check_vma=False)(table, ids)


def _use_fused_ce(use_fused_kernel, R, V, H, itemsize=2, lora_r=0,
                  lora_impl="naive") -> bool:
    """Resolve the fused-head-kernel dispatch. "auto" currently resolves
    to the XLA path on every shape: measured on v5e (r4), the Pallas
    fused head (ops/fused_ce.py) is ~6% SLOWER than XLA's consumer-fused
    matmul+logsumexp at Gemma-270M shapes and exactly at parity at
    Gemma-1B — XLA already keeps the chunk logits out of HBM well enough
    that the kernel's per-tile overhead has nothing to win back
    (DESIGN.md §5a). True forces the kernel (tests; future re-measure
    when the compiler or shapes change).

    lora_r > 0 is the head-ADAPTER case (DESIGN.md §17): under
    lora_impl="fused" the kernel engages whenever the epilogue variant
    is eligible (the adapter delta is the HBM traffic the base kernel
    never had to win back); "auto"/"naive" keep the XLA chunk path
    pending a TPU measurement."""
    from mobilefinetuner_tpu.ops.fused_ce import (fused_ce_eligible,
                                                  fused_ce_lora_eligible)
    eligible = (fused_ce_lora_eligible(R, V, H, lora_r, itemsize)
                if lora_r else fused_ce_eligible(R, V, H, itemsize))
    if use_fused_kernel == "auto":
        return bool(lora_r) and lora_impl == "fused" and eligible
    if not use_fused_kernel:
        return False
    if not eligible:
        # forcing must be loud: a silent XLA fallback would let a future
        # re-measure record XLA numbers as kernel numbers
        raise ValueError(
            f"use_fused_kernel=True but the fused CE kernel cannot run "
            f"R={R}, V={V}, H={H}, lora_r={lora_r} (alignment or VMEM "
            f"budget — fused_ce.pick_block_v); use 'auto' for dispatch")
    return True


@partial(jax.jit, static_argnames=("ignore_index", "num_chunks", "mesh",
                                   "batch_axis", "vocab_axis",
                                   "use_fused_kernel", "sequence_parallel",
                                   "lora_impl", "lora_dropout"))
def _chunked_nll_sum(hidden, lm_head_w, labels, ignore_index, num_chunks,
                     mesh=None, batch_axis="data", vocab_axis="fsdp",
                     use_fused_kernel="auto", sequence_parallel=False,
                     lora_head=None, lora_impl="naive",
                     lora_dropout=0.0, dropout_rng=None):
    if mesh is not None:
        V = lm_head_w.shape[0]
        B, S = hidden.shape[0], hidden.shape[1]
        n_vocab = mesh.shape.get(vocab_axis, 1)
        n_batch = mesh.shape.get(batch_axis, 1)
        # sequence-parallel composition: the chunk dim arrives sharded
        # over the vocab axis, so each scan chunk must split evenly
        # across it (see _vp_chunked_nll_sum seq_shard)
        chunk = chunk_len(S, num_chunks)
        sp_ok = (not sequence_parallel) or chunk % n_vocab == 0
        if n_vocab > 1 and V % n_vocab == 0 and B % n_batch == 0 and sp_ok:
            if use_fused_kernel is True:
                raise ValueError(
                    "use_fused_kernel=True is not available under the "
                    "vocab-parallel mesh path (shard_map CE)")
            if lora_head is not None:
                # a head adapter under the vocab-parallel CE would need
                # B column-sharded inside the shard_map — not built this
                # round; refusing beats silently dropping the delta
                raise ValueError(
                    "lora_head (lm_head adapter) is not supported under "
                    "the vocab-parallel CE path; run with mesh=None or "
                    "drop the lm_head target")
            return _vp_chunked_nll_sum(hidden, lm_head_w, labels,
                                       ignore_index, num_chunks, mesh,
                                       batch_axis, vocab_axis,
                                       seq_shard=sequence_parallel)
        if n_vocab > 1:
            # the caller asked for vocab-parallel but the shapes can't
            # shard — warn (once per trace: shapes are static) instead of
            # silently reintroducing the full-table all-gather/OOM this
            # path exists to prevent
            import warnings
            warnings.warn(
                f"vocab-parallel CE requested but V={V} % {vocab_axis}="
                f"{n_vocab} != 0 or B={B} % {batch_axis}={n_batch} != 0"
                + ("" if sp_ok else
                   f" or sequence-parallel chunk={chunk} % {n_vocab} != 0")
                + "; falling back to the single-program chunked CE (GSPMD "
                f"may all-gather the full [V, H] head table per step)",
                stacklevel=2)
    # Head matmul in the COMPUTE dtype with f32 accumulation: casting both
    # operands to f32 (the old form) forces the multi-pass f32 MXU
    # lowering on the [chunk, H] x [H, 262k] projection — the dominant
    # matmul of the small-Gemma configs. Under the bf16 compute policy the
    # hidden states arrive bf16; aligning the (frozen, tied) head weight
    # to them keeps the projection a single bf16 MXU pass, while
    # preferred_element_type=f32 in the dot and the f32 logsumexp in
    # _token_nll keep the reduction math exact. f32 callers (parity tests,
    # --dtype float32) are bit-for-bit unchanged.
    if jnp.issubdtype(hidden.dtype, jnp.floating):
        lm_head_w = lm_head_w.astype(hidden.dtype)
    hs, ls = _shift_and_chunk(hidden, labels, ignore_index, num_chunks)
    nc, B, chunk, H = hs.shape
    # Train-mode LoRA dropout on the head adapter's branch input (PEFT
    # semantics: the branch copy only, never the base logits). Masked
    # over the FULL hidden with the same fold_in(rng, 2000) site key as
    # the models' full-logits lm_head sites (gpt2/gemma3.forward), then
    # chunked alongside — bit-identical branch input to the unchunked
    # path, uncorrelated with every per-layer site mask.
    hbs = None
    if (lora_head is not None and lora_dropout > 0.0
            and dropout_rng is not None):
        from mobilefinetuner_tpu.ops.dropout import inverted_dropout
        dropped = inverted_dropout(
            hidden, lora_dropout, jax.random.fold_in(dropout_rng, 2000))
        hbs, _ = _shift_and_chunk(dropped, labels, ignore_index,
                                  num_chunks)
    lora_r = 0 if lora_head is None else int(lora_head["A"].shape[-1])

    # xs only grows the branch-hidden leaf when dropout is live — the
    # base graph (and every no-dropout caller's trace) is unchanged
    def unpack(xs):
        if hbs is None:
            h, lab = xs
            return h, h, lab
        return xs

    if _use_fused_ce(use_fused_kernel, B * chunk, lm_head_w.shape[0], H,
                     lm_head_w.dtype.itemsize, lora_r=lora_r,
                     lora_impl=lora_impl):
        # Pallas fused head (ops/fused_ce.py): the [B, chunk, V] logits
        # block stays in VMEM tiles instead of being written + twice-read
        # in HBM per chunk (and again in the checkpointed backward) —
        # with a head adapter, its delta folds into the same tile loop
        from mobilefinetuner_tpu.ops.fused_ce import fused_ce_nll_sum

        def body(carry, xs):
            total, count = carry
            h, hb, lab = unpack(xs)
            s, c = fused_ce_nll_sum(h, lm_head_w, lab, ignore_index,
                                    lora_head=lora_head,
                                    branch_hidden=hb)
            return (total + s, count + c), None
    else:
        def body(carry, xs):
            total, count = carry
            h, hb, lab = unpack(xs)
            logits = jax.lax.dot_general(
                h, lm_head_w, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # [B, chunk, V] f32
            if lora_head is not None:
                # chunk-local head-adapter delta (the contraction-order
                # rule: (h@A)@B, f32-accumulated; scale-folding shared
                # with the kernel path via head_bottleneck) — only a
                # [B, chunk, V] block ever exists, like the base logits
                from mobilefinetuner_tpu.ops.fused_ce import \
                    head_bottleneck
                xa, bt = head_bottleneck(hb.reshape(B * chunk, H),
                                         lora_head)
                logits = logits + jnp.einsum(
                    "rk,vk->rv", xa, bt,
                    preferred_element_type=jnp.float32) \
                    .reshape(B, chunk, -1)
            nll, valid = _token_nll(logits, lab, ignore_index)
            return (total + nll.sum(), count + valid.sum()), None

    (total, count), _ = jax.lax.scan(
        jax.checkpoint(body), (jnp.float32(0.0), jnp.int32(0)),
        (hs, ls) if hbs is None else (hs, hbs, ls))
    return total, count


def chunked_lm_cross_entropy(hidden: jnp.ndarray, lm_head_w: jnp.ndarray,
                             labels: jnp.ndarray,
                             ignore_index: int = IGNORE_INDEX,
                             num_chunks: int = 8, mesh=None,
                             batch_axis: str = "data",
                             vocab_axis: str = "fsdp",
                             use_fused_kernel="auto",
                             sequence_parallel: bool = False,
                             lora_head=None,
                             lora_impl: str = "naive",
                             lora_dropout: float = 0.0,
                             dropout_rng=None) -> jnp.ndarray:
    """Mean causal-LM loss computed without materializing [B,S,V] logits.

    hidden: [B, S, H] final hidden states; lm_head_w: [V, H] (HF layout);
    labels: [B, S] unshifted. The projection + logsumexp runs per sequence
    chunk under lax.scan with rematerialization, so peak memory holds one
    [B, S/num_chunks, V] block. Differentiable end-to-end.

    mesh: pass the ("data", "fsdp") device mesh when lm_head_w is
    FSDP-sharded to run the CE vocab-parallel (table stays sharded; see
    _chunked_nll_sum). In sequence-parallel mode (ring attention, the
    fsdp axis carrying S) ALSO pass sequence_parallel=True: the CE then
    gathers each hidden chunk over that axis before the vocab-parallel
    softmax, so the long-context configuration keeps the no-table-gather
    guarantee (round-5 verdict item 2).

    lora_head: optional lm_head adapter entry {A [H, r], B [r, V],
    scale}; its delta is applied chunk-locally (XLA) or folded into the
    fused kernel's tile loop (lora_impl="fused" when eligible) — the
    full [B, S, V] delta never materializes either way (DESIGN.md §17).
    lora_dropout/dropout_rng: train-mode inverted dropout on the head
    adapter's branch input (PEFT semantics, same fold_in(rng, 2000)
    site key as the models' full-logits lm_head sites) — pass the train
    CLI's --lora_dropout and per-micro-batch rng so the lm_head target
    regularizes like every per-layer site.
    """
    with jax.named_scope("loss"):
        total, count = _chunked_nll_sum(hidden, lm_head_w, labels,
                                        ignore_index, num_chunks, mesh,
                                        batch_axis, vocab_axis,
                                        use_fused_kernel,
                                        sequence_parallel, lora_head,
                                        lora_impl, lora_dropout,
                                        dropout_rng)
        return total / jnp.maximum(count, 1).astype(jnp.float32)


def chunked_lm_cross_entropy_sum(
        hidden: jnp.ndarray, lm_head_w: jnp.ndarray, labels: jnp.ndarray,
        ignore_index: int = IGNORE_INDEX, num_chunks: int = 8, mesh=None,
        batch_axis: str = "data", vocab_axis: str = "fsdp",
        use_fused_kernel="auto", sequence_parallel: bool = False,
        lora_head=None, lora_impl: str = "naive",
        lora_dropout: float = 0.0,
        dropout_rng=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum_nll, valid_token_count) form of the chunked loss — the
    accumulation-friendly contract the train step uses (trainer.py).
    mesh/sequence_parallel/lora_head/lora_dropout: see
    chunked_lm_cross_entropy."""
    with jax.named_scope("loss"):
        return _chunked_nll_sum(hidden, lm_head_w, labels, ignore_index,
                                num_chunks, mesh, batch_axis, vocab_axis,
                                use_fused_kernel, sequence_parallel,
                                lora_head, lora_impl, lora_dropout,
                                dropout_rng)


def perplexity_from_loss(loss) -> float:
    """ppl = exp(mean NLL) (reference: core/lm_loss.h:39-41)."""
    import math
    # graftlint: disable=sync-hazard(eval-end conversion: callers hand a host scalar or accept the one post-loop sync)
    return math.exp(float(loss))
