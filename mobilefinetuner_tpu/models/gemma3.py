"""Gemma-3 text decoder, pure-functional JAX.

Re-design of the reference's GemmaModel graph
(reference: operators/finetune_ops/graph/gemma_model.{h,cpp}), HF-Gemma3
aligned (SURVEY.md §2.5):
  - embeddings scaled by sqrt(hidden_size) (gemma_model.cpp:222-248);
  - GQA (num_attention_heads q-heads over num_key_value_heads kv-heads) —
    expressed as a broadcast einsum, not materialized repeat_kv_heads;
  - per-head q/k RMSNorm before RoPE;
  - dual RoPE theta: rope_theta (global layers) vs rope_local_base_freq
    (sliding-window layers) selected per layer_types[i]
    (gemma_model.cpp:579-625);
  - sliding-window mask (default 512) on local layers (gemma_model.h:26);
  - sandwich norms: input_ln -> attn -> post_attn_ln -> residual;
    pre_ffn_ln -> MLP(gelu_tanh(gate)*up -> down) -> post_ffn_ln ->
    residual (gemma_model.cpp:579-680);
  - RMSNorm with Gemma (1 + weight) semantics, fp32 accumulation
    (core/ops.cpp:1489);
  - query scaling by query_pre_attn_scalar^-0.5 (gemma_model.h:33);
  - lm_head tied to the embedding table (HF Gemma-3 text checkpoints).

Layers are stacked [L, ...] and run under lax.scan; per-layer global/local
behavior is selected with jnp.where over precomputed global+local RoPE
tables and masks (static shapes, no data-dependent control flow).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.core.config import Gemma3TextConfig
from mobilefinetuner_tpu.ops.attention import attention, causal_mask
from mobilefinetuner_tpu.ops.rope import apply_rope, rope_cos_sin


def rms_norm(x, w, eps, dtype=None):
    """Gemma RMSNorm: x/rms(x) * (1 + w), fp32 math
    (reference: core/ops.cpp:1489, scale at ops.cpp:1515)."""
    dtype = dtype or x.dtype
    xf = x.astype(jnp.float32)
    var = (xf * xf).mean(-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(dtype)


def gelu_tanh(x):
    return jax.nn.gelu(x, approximate=True)


def init_params(config: Gemma3TextConfig, key: jax.Array,
                dtype=jnp.float32) -> Dict[str, Any]:
    c = config
    L, H, D = c.num_hidden_layers, c.hidden_size, c.head_dim
    nq, nkv, I = c.num_attention_heads, c.num_key_value_heads, \
        c.intermediate_size
    ks = jax.random.split(key, 8)
    std = 0.02

    def n(k, shape):
        return (jax.random.normal(k, shape) * std).astype(dtype)

    z = lambda *s: jnp.zeros(s, dtype)
    return {
        "embed": n(ks[0], (c.vocab_size, H)),
        "blocks": {
            "input_ln": z(L, H),
            "attn": {
                "q_w": n(ks[1], (L, H, nq * D)),
                "k_w": n(ks[2], (L, H, nkv * D)),
                "v_w": n(ks[3], (L, H, nkv * D)),
                "o_w": n(ks[4], (L, nq * D, H)),
                "q_norm": z(L, D),
                "k_norm": z(L, D),
            },
            "post_attn_ln": z(L, H),
            "pre_ffn_ln": z(L, H),
            "mlp": {
                "gate_w": n(ks[5], (L, H, I)),
                "up_w": n(ks[6], (L, H, I)),
                "down_w": n(ks[7], (L, I, H)),
            },
            "post_ffn_ln": z(L, H),
        },
        "final_norm": z(H),
    }


from mobilefinetuner_tpu.models.lora_apply import maybe_lora


def _block(c: Gemma3TextConfig, bp, x, padding_mask, masks, ropes,
           is_global, lora_b, i, lora_dropout=0.0, dropout_rng=None,
           cp_mesh=None, cp_axis="fsdp", collect_kv: bool = False,
           lora_impl: str = "auto", mesh=None):
    """One Gemma-3 block; bp leaves are THIS layer's weights (sliced out of
    the [L, ...] stacks by the scan body); i (traced scalar) indexes the
    still-stacked LoRA leaves, RoPE tables, and masks. collect_kv: also
    return this layer's post-norm post-RoPE (k, v) head tensors
    [B, Hkv, S, D] (KV-cache prefill, models/generate.py)."""
    eps = c.rms_norm_eps
    B, S, H = x.shape
    nq, nkv, D = (c.num_attention_heads, c.num_key_value_heads, c.head_dim)
    rng = None if dropout_rng is None else jax.random.fold_in(dropout_rng, i)

    def lora(y, x_in, name, site):
        entry = None if lora_b is None else lora_b.get(name)
        return maybe_lora(y, x_in, entry, i, lora_dropout,
                          None if rng is None
                          else jax.random.fold_in(rng, site),
                          impl=lora_impl)

    a = bp["attn"]

    # --- attention, sandwich-normed (named scopes label the phase in
    # profiler traces and compiled-HLO op metadata, DESIGN.md §13)
    with jax.named_scope("attention"):
        h = rms_norm(x, bp["input_ln"], eps)
        q = lora(h @ a["q_w"], h, "q_proj", 0)
        k = lora(h @ a["k_w"], h, "k_proj", 1)
        v = lora(h @ a["v_w"], h, "v_proj", 2)
        q = q.reshape(B, S, nq, D).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, nkv, D).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, nkv, D).transpose(0, 2, 1, 3)
        q = rms_norm(q, a["q_norm"], eps)
        k = rms_norm(k, a["k_norm"], eps)
        cos = jnp.where(is_global[i], ropes["cos_g"], ropes["cos_l"])
        sin = jnp.where(is_global[i], ropes["sin_g"], ropes["sin_l"])
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        kv_out = (k, v) if collect_kv else None
        scale = c.query_pre_attn_scalar ** -0.5
        impl = c.attention_impl
        if impl == "auto":
            # resolved here (not inside attention()) because the flash
            # path needs the flag-based branch below instead of masks
            from mobilefinetuner_tpu.ops.attention import resolve_impl
            impl = resolve_impl(S, D)
        if cp_mesh is not None:
            # sequence-parallel: ring attention over the mesh axis; the
            # global/local choice is a traced bool under the layer scan,
            # so branch with lax.cond like the flash path
            from mobilefinetuner_tpu.parallel.ring_attention import \
                ring_attention
            ctx = jax.lax.cond(
                is_global[i],
                lambda ops: ring_attention(*ops, cp_mesh, axis=cp_axis,
                                           scale=scale, is_causal=True,
                                           padding_mask=padding_mask),
                lambda ops: ring_attention(*ops, cp_mesh, axis=cp_axis,
                                           scale=scale, is_causal=True,
                                           sliding_window=c.sliding_window,
                                           padding_mask=padding_mask),
                (q, k, v))
        elif impl == "flash":
            # The Pallas kernel takes causal/sliding-window as STATIC
            # config, not a mask matrix; under the layer scan the
            # global/local choice is a traced bool, so branch with
            # lax.cond (each branch compiles its own kernel variant).
            ctx = jax.lax.cond(
                is_global[i],
                lambda ops: attention(*ops, impl="flash", mesh=mesh,
                                      scale=scale, is_causal=True,
                                      padding_mask=padding_mask),
                lambda ops: attention(*ops, impl="flash", mesh=mesh,
                                      scale=scale, is_causal=True,
                                      sliding_window=c.sliding_window,
                                      padding_mask=padding_mask),
                (q, k, v))
        else:
            mask = jnp.where(is_global[i], masks["global"], masks["local"])
            ctx = attention(q, k, v, impl=impl, scale=scale,
                            is_causal=False, attn_mask=mask,
                            padding_mask=padding_mask)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, nq * D)
        attn_out = lora(ctx @ a["o_w"], ctx, "o_proj", 3)
        attn_out = rms_norm(attn_out, bp["post_attn_ln"], eps)
        x = x + attn_out

    # --- MLP, sandwich-normed
    with jax.named_scope("mlp"):
        h = rms_norm(x, bp["pre_ffn_ln"], eps)
        gate = lora(h @ bp["mlp"]["gate_w"], h, "gate_proj", 4)
        up = lora(h @ bp["mlp"]["up_w"], h, "up_proj", 5)
        act = gelu_tanh(gate) * up
        down = lora(act @ bp["mlp"]["down_w"], act, "down_proj", 6)
        down = rms_norm(down, bp["post_ffn_ln"], eps)
    if collect_kv:
        return x + down, kv_out
    return x + down


def hidden_states(config: Gemma3TextConfig, params, input_ids,
                  attention_mask=None, lora=None,
                  compute_dtype=jnp.float32, remat: bool = False,
                  lora_dropout: float = 0.0, dropout_rng=None,
                  offload=None, block_stream=None,
                  collect_layers: bool = False, collect_kv: bool = False,
                  cp_mesh=None, cp_axis: str = "fsdp",
                  scan_unroll: int = 1, lora_impl: str = "auto",
                  mesh=None):
    """mesh: the mesh a batch-parallel step is partitioned over, for the
    attention dispatcher (ops/attention.attention).
    offload: optional (plan, shardings) pair matching `params`; offloaded
    block weights stream host->HBM per layer inside the scan (forces remat
    of the block body) — see parallel/offload.py. block_stream: pre-resolved
    stream fn for callers that already ran resolve_offload (so the fetched
    embedding table is reused by the tied lm_head, not fetched twice).
    collect_layers: also return {"embed", "layers"} activations for the
    alignment harness (reference: train_lora_gemma.cpp:620-920 npy dumps,
    gemma_model.h:100-143 per-layer dump requests)."""
    from mobilefinetuner_tpu.parallel.offload import resolve_offload
    c = config
    B, S = input_ids.shape
    params = jax.tree.map(jnp.asarray, params)
    if offload is not None:
        params, block_stream = resolve_offload(params, offload)
    stream = block_stream
    with jax.named_scope("embed"):
        if (cp_mesh is not None and cp_axis in cp_mesh.axis_names
                and c.vocab_size % cp_mesh.shape[cp_axis] == 0
                and S % cp_mesh.shape[cp_axis] == 0):
            # sequence-parallel + V-sharded tied table: the structural
            # vocab-parallel lookup — GSPMD left alone all-gathers the
            # full table here at large mesh sizes (ops/loss.vp_embed_lookup)
            from mobilefinetuner_tpu.ops.loss import vp_embed_lookup
            x = vp_embed_lookup(params["embed"], input_ids, cp_mesh,
                                vocab_axis=cp_axis).astype(compute_dtype)
        else:
            x = params["embed"][input_ids].astype(compute_dtype)
        # sqrt(hidden) embedding scaling, in the embed dtype as HF does
        normalizer = jnp.asarray(c.hidden_size ** 0.5, compute_dtype)
        x = x * normalizer

    if attention_mask is not None:
        # mask-derived positions (HF convention) so left-padded batches get
        # the same RoPE phases as HF Gemma-3
        positions = jnp.clip(
            jnp.cumsum(attention_mask.astype(jnp.int32), axis=-1) - 1, 0)
    else:
        positions = jnp.arange(S)
    cos_g, sin_g = rope_cos_sin(positions, c.head_dim, c.rope_theta)
    cos_l, sin_l = rope_cos_sin(positions, c.head_dim,
                                c.rope_local_base_freq)
    ropes = {"cos_g": cos_g, "sin_g": sin_g, "cos_l": cos_l, "sin_l": sin_l}
    masks = {"global": causal_mask(S, S),
             "local": causal_mask(S, S, sliding_window=c.sliding_window)}
    is_global = jnp.asarray([c.is_global_layer(i)
                             for i in range(c.num_hidden_layers)])

    from mobilefinetuner_tpu.parallel.offload import layer_slicer
    slice_layer = layer_slicer(params["blocks"], stream, compute_dtype)
    lora_b = None if lora is None else lora.get("blocks")

    embed_out = x

    def body(x, i):
        r = _block(c, slice_layer(i), x, attention_mask, masks, ropes,
                   is_global, lora_b, i, lora_dropout, dropout_rng,
                   cp_mesh, cp_axis, collect_kv=collect_kv,
                   lora_impl=lora_impl, mesh=mesh)
        x2, kv = r if collect_kv else (r, None)
        return x2, (kv if collect_kv else (x2 if collect_layers else None))
    if remat or stream is not None:
        body = jax.checkpoint(body)
    # scan_unroll > 1 issues several layers' host->HBM fetches per loop
    # iteration on the streaming path — the host link is LATENCY-bound
    # (~2 GiB/s single stream vs ~8 concurrent), so overlapping fetches
    # raises effective bandwidth (bench offload-frontier rows)
    x, extras = jax.lax.scan(body, x, jnp.arange(c.num_hidden_layers),
                             unroll=scan_unroll)
    x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                 c.rms_norm_eps)
    if collect_kv:
        return x, extras  # ([L,B,Hkv,S,D] k, [L,B,Hkv,S,D] v)
    if collect_layers:
        return x, {"embed": embed_out, "layers": extras}
    return x


def forward(config: Gemma3TextConfig, params, input_ids,
            attention_mask=None, lora=None, compute_dtype=jnp.float32,
            remat: bool = False, lora_dropout: float = 0.0,
            dropout_rng=None, offload=None, cp_mesh=None,
            cp_axis: str = "fsdp", lora_impl: str = "auto",
            mesh=None) -> jnp.ndarray:
    """Logits [B, S, V]; lm_head tied to the embedding table. An
    "lm_head" adapter entry adds its delta at the logits projection
    (training paths should prefer the chunked CE's lora_head= instead —
    this materializes [B, S, V] by construction)."""
    from mobilefinetuner_tpu.parallel.offload import resolve_offload
    params, stream = resolve_offload(params, offload)
    x = hidden_states(config, params, input_ids, attention_mask, lora,
                      compute_dtype, remat, lora_dropout, dropout_rng,
                      block_stream=stream, cp_mesh=cp_mesh,
                      cp_axis=cp_axis, lora_impl=lora_impl, mesh=mesh)
    logits = x @ params["embed"].astype(compute_dtype).T
    lora_b = None if lora is None else lora.get("blocks")
    if lora_b is not None and "lm_head" in lora_b:
        rng = (None if dropout_rng is None
               else jax.random.fold_in(dropout_rng, 2000))
        logits = maybe_lora(logits, x, lora_b["lm_head"], None,
                            lora_dropout, rng, impl=lora_impl)
    return logits
