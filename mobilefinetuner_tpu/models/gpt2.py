"""GPT-2 family decoder, pure-functional JAX.

Re-design of the reference's GPT2Model graph
(reference: operators/finetune_ops/graph/gpt2_model.{h,cpp}): pre-LN blocks
with fused-QKV attention, gelu_new MLP, final LN, tied lm_head = x @ wte^T
(gpt2_model.cpp:421-440). Differences by design:
  - parameters are a pytree of stacked per-layer arrays ([L, ...]) and the
    block stack runs under `lax.scan` — one compiled block body instead of L
    unrolled copies (compile time, remat-friendly), idiomatic for XLA;
  - weights keep HF Conv1D [in, out] layout so `y = x @ W + b` loads GPT-2
    checkpoints without transposition (the reference needs a no-transpose
    flag for exactly this reason, gpt2_lora_finetune/main.cpp:292-296);
  - attention is fully differentiable on every path (the reference's default
    memory-efficient attention is forward-only, SURVEY.md §2.12.1 — a bug we
    deliberately do not replicate);
  - autodiff, fusion, and memory management come from JAX/XLA instead of the
    reference's L0-L3 hand-written engine.

LoRA enters functionally: `forward(..., lora=...)` takes an optional pytree
(see lora/lora.py) whose entries add scale·(x@A@B) to the matching linears.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.core.config import GPT2Config
from mobilefinetuner_tpu.ops.attention import attention


def layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * g + b
    return out.astype(x.dtype)


def gelu_new(x):
    """tanh-approx gelu, matches HF gelu_new (reference core/ops.cpp:1055)."""
    return jax.nn.gelu(x, approximate=True)


from mobilefinetuner_tpu.ops.dropout import inverted_dropout as _dropout


from mobilefinetuner_tpu.models.lora_apply import maybe_lora


def init_params(config: GPT2Config, key: jax.Array,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Random init (N(0, 0.02), zeros for biases/proj per GPT-2 paper)."""
    E, L, V, P = (config.n_embd, config.n_layer, config.vocab_size,
                  config.n_positions)
    ks = jax.random.split(key, 8)
    std = 0.02

    def n(k, shape):
        return (jax.random.normal(k, shape) * std).astype(dtype)

    z = lambda *shape: jnp.zeros(shape, dtype)
    o = lambda *shape: jnp.ones(shape, dtype)
    return {
        "wte": n(ks[0], (V, E)),
        "wpe": n(ks[1], (P, E)),
        "blocks": {
            "ln_1": {"g": o(L, E), "b": z(L, E)},
            "attn": {
                "qkv_w": n(ks[2], (L, E, 3 * E)), "qkv_b": z(L, 3 * E),
                "proj_w": n(ks[3], (L, E, E)), "proj_b": z(L, E),
            },
            "ln_2": {"g": o(L, E), "b": z(L, E)},
            "mlp": {
                "fc_w": n(ks[4], (L, E, 4 * E)), "fc_b": z(L, 4 * E),
                "proj_w": n(ks[5], (L, 4 * E, E)), "proj_b": z(L, E),
            },
        },
        "ln_f": {"g": o(E), "b": z(E)},
    }


def _dense(x, w, b):
    return x @ w + b


def _block(config: GPT2Config, bp, x, padding_mask, lora_b, layer_idx,
           lora_dropout=0.0, dropout_rng=None, cp_mesh=None,
           cp_axis="fsdp", collect_kv: bool = False,
           lora_impl: str = "auto", mesh=None, dense=_dense):
    """One pre-LN transformer block. bp leaves are THIS layer's weights
    (already sliced out of the [L, ...] stacks by the scan body); layer_idx
    (traced scalar) indexes the still-stacked LoRA leaves and salts
    dropout keys. cp_mesh: sequence-parallel mode — attention runs as
    ring attention over the mesh axis (parallel/ring_attention.py).
    mesh: the mesh a batch-parallel step is partitioned over, for the
    attention dispatcher (ops/attention.attention).
    collect_kv: also return this layer's (k, v) head tensors [B, H, S, D]
    (KV-cache prefill, models/generate.py).
    dense(x, w, b): the block's four products (x @ w + b by default; the
    layer gather over an FSDP mesh hands parallel/mesh.gathered_dense)."""
    eps = config.layer_norm_epsilon
    H, D = config.n_head, config.head_dim
    B, S, E = x.shape
    rng = (None if dropout_rng is None
           else jax.random.fold_in(dropout_rng, layer_idx))

    def lora(y, x_in, name, site):
        entry = None if lora_b is None else lora_b.get(name)
        return maybe_lora(y, x_in, entry, layer_idx, lora_dropout,
                          None if rng is None
                          else jax.random.fold_in(rng, site),
                          impl=lora_impl)

    # named scopes label the phase in profiler traces AND compiled-HLO
    # op metadata (asserted by tests/test_telemetry.py; DESIGN.md §13)
    with jax.named_scope("attention"):
        h = layer_norm(x, bp["ln_1"]["g"], bp["ln_1"]["b"], eps)
        qkv = dense(h, bp["attn"]["qkv_w"], bp["attn"]["qkv_b"])
        qkv = lora(qkv, h, "attn_qkv", 0)
        # split-QKV adapters hit only their column range of the fused
        # c_attn output (reference: lora_injector.h:169-191
        # col_offset/col_size)
        if lora_b is not None:
            from mobilefinetuner_tpu.lora.lora import GPT2_SPLIT_QKV_SLOTS
            for name, slot in GPT2_SPLIT_QKV_SLOTS.items():
                if name in lora_b:
                    sl = (Ellipsis, slice(slot * E, (slot + 1) * E))
                    qkv = qkv.at[sl].set(lora(qkv[sl], h, name, 4 + slot))
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(B, S, H, D).transpose(0, 2, 1, 3)
        kv_out = (to_heads(k), to_heads(v)) if collect_kv else None
        attn_rng = (None if rng is None or config.attn_pdrop <= 0.0
                    else jax.random.fold_in(rng, 9))
        if cp_mesh is not None:
            from mobilefinetuner_tpu.parallel.ring_attention import \
                ring_attention
            ctx = ring_attention(to_heads(q), to_heads(k), to_heads(v),
                                 cp_mesh, axis=cp_axis, is_causal=True,
                                 padding_mask=padding_mask)
        else:
            ctx = attention(to_heads(q), to_heads(k), to_heads(v),
                            impl=config.attention_impl, mesh=mesh,
                            is_causal=True, padding_mask=padding_mask,
                            attn_dropout=config.attn_pdrop,
                            attn_dropout_rng=attn_rng)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, E)
        proj = dense(ctx, bp["attn"]["proj_w"], bp["attn"]["proj_b"])
        proj = lora(proj, ctx, "attn_proj", 1)
        proj = _dropout(proj, config.resid_pdrop,
                        None if rng is None else jax.random.fold_in(rng, 7))
        x = x + proj

    with jax.named_scope("mlp"):
        h = layer_norm(x, bp["ln_2"]["g"], bp["ln_2"]["b"], eps)
        fc = dense(h, bp["mlp"]["fc_w"], bp["mlp"]["fc_b"])
        fc = lora(fc, h, "mlp_fc_in", 2)
        act = gelu_new(fc)
        out = dense(act, bp["mlp"]["proj_w"], bp["mlp"]["proj_b"])
        out = lora(out, act, "mlp_fc_out", 3)
        out = _dropout(out, config.resid_pdrop,
                       None if rng is None else jax.random.fold_in(rng, 8))
    if collect_kv:
        return x + out, kv_out
    return x + out


def hidden_states(config: GPT2Config, params, input_ids,
                  attention_mask=None, lora=None,
                  compute_dtype=jnp.float32, remat: bool = False,
                  lora_dropout: float = 0.0, dropout_rng=None,
                  offload=None, block_stream=None,
                  collect_layers: bool = False, collect_kv: bool = False,
                  cp_mesh=None, cp_axis: str = "fsdp",
                  lora_impl: str = "auto", mesh=None):
    """Final-LN hidden states [B, S, E] (pre lm_head).

    offload: optional (plan, shardings) pytree pair matching `params`
    (parallel/offload.py). Offloaded block weights are streamed host->HBM
    one layer at a time inside the scan; streaming forces remat of the
    block body so the backward re-fetches layers instead of keeping every
    layer's weights alive as residuals (which would defeat the budget).
    block_stream: pre-resolved stream fn from resolve_offload, for callers
    that already fetched the top-level leaves themselves (e.g. forward,
    which reuses the fetched wte for the tied lm_head).
    collect_layers: also return {"embed": [B,S,E], "layers": [L,B,S,E]}
    (post-embedding and post-block activations) for the alignment harness
    (reference: train_lora_gemma.cpp:620-920 npy dumps, GPT2_ALIGN_DUMP_DIR
    in gpt2_model.cpp:327-399).
    mesh: the mesh a batch-parallel step is partitioned over. Where its
    fsdp axis holds more than one device (and neither sequence
    parallelism nor streaming is on), each layer's weights are gathered
    whole inside the scan body and the block's activations stay on the
    batch axes (_layer_gather; DESIGN.md §31a).
    """
    from mobilefinetuner_tpu.parallel.offload import resolve_offload
    B, S = input_ids.shape
    params = jax.tree.map(jnp.asarray, params)
    if offload is not None:
        params, block_stream = resolve_offload(params, offload)
    stream = block_stream
    with jax.named_scope("embed"):
        if attention_mask is not None:
            # HF convention: position ids count only unmasked tokens, so
            # left-padded batches line up with HF GPT-2 exactly.
            positions = jnp.clip(
                jnp.cumsum(attention_mask.astype(jnp.int32), axis=-1) - 1,
                0)
            pos_emb = params["wpe"][positions]
        else:
            pos_emb = params["wpe"][:S][None, :, :]
        x = params["wte"][input_ids] + pos_emb
        x = x.astype(compute_dtype)
        x = _dropout(x, config.embd_pdrop,
                     None if dropout_rng is None
                     else jax.random.fold_in(dropout_rng, 1000))
    padding_mask = attention_mask
    from mobilefinetuner_tpu.parallel.mesh import gathers_layers, on_batch
    from mobilefinetuner_tpu.parallel.offload import layer_slicer
    if stream is None and cp_mesh is None and gathers_layers(mesh):
        slice_layer, dense = _layer_gather(config, params["blocks"], mesh,
                                           compute_dtype)
        pin = lambda t: on_batch(t, mesh)
    else:
        slice_layer = layer_slicer(params["blocks"], stream, compute_dtype)
        dense, pin = _dense, lambda t: t
    lora_b = None if lora is None else lora.get("blocks")

    embed_out = x

    def body(x, i):
        r = _block(config, slice_layer(i), pin(x), padding_mask, lora_b, i,
                   lora_dropout, dropout_rng, cp_mesh, cp_axis,
                   collect_kv=collect_kv, lora_impl=lora_impl, mesh=mesh,
                   dense=dense)
        x2, kv = r if collect_kv else (r, None)
        x2 = pin(x2)
        return x2, (kv if collect_kv else (x2 if collect_layers else None))
    if remat or stream is not None:
        body = jax.checkpoint(body)
    x, extras = jax.lax.scan(body, x, jnp.arange(config.n_layer))
    x = layer_norm(x, params["ln_f"]["g"].astype(compute_dtype),
                   params["ln_f"]["b"].astype(compute_dtype),
                   config.layer_norm_epsilon)
    if collect_kv:
        return x, extras  # ([L,B,H,S,D] k, [L,B,H,S,D] v)
    if collect_layers:
        return x, {"embed": embed_out, "layers": extras}
    return x


def _layer_gather(config: GPT2Config, blocks, mesh, compute_dtype):
    """(slice_layer, dense) of the layer gather over an FSDP mesh:
    slice_layer(i) is this layer's slice of the stacks as they are held
    (no whole-stack cast), the norms gathered whole (layer_norm applies
    them in float32 either way); the four products take their weight and
    bias shards to parallel/mesh.gathered_dense, which gathers them. Every
    gradient is summed across devices in float32."""
    from mobilefinetuner_tpu.parallel.mesh import (gathered_dense,
                                                   gathered_param)
    L = config.n_layer

    def slice_layer(i):
        bp = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
            blocks)
        for norm in ("ln_1", "ln_2"):
            bp[norm] = jax.tree.map(
                lambda t: gathered_param(t, mesh, compute_dtype, L),
                bp[norm])
        return bp

    def dense(x, w, b):
        return gathered_dense(x, w, b, mesh, compute_dtype, L)
    return slice_layer, dense


def forward(config: GPT2Config, params, input_ids, attention_mask=None,
            lora=None, compute_dtype=jnp.float32, remat: bool = False,
            lora_dropout: float = 0.0, dropout_rng=None,
            offload=None, cp_mesh=None, cp_axis: str = "fsdp",
            lora_impl: str = "auto", mesh=None) -> jnp.ndarray:
    """Logits [B, S, V]. Tied lm_head: x @ wte^T (gpt2_model.cpp:421-440).

    The reference caches wte^T when embeddings are frozen (SURVEY.md
    §2.12.5); under XLA the transpose is a free layout change, so no cache.
    An "lm_head" adapter entry (lora/lora.py UNSTACKED_TARGETS) adds its
    delta at the logits projection.
    """
    from mobilefinetuner_tpu.parallel.offload import resolve_offload
    params, stream = resolve_offload(params, offload)
    x = hidden_states(config, params, input_ids, attention_mask, lora,
                      compute_dtype, remat, lora_dropout, dropout_rng,
                      block_stream=stream, cp_mesh=cp_mesh,
                      cp_axis=cp_axis, lora_impl=lora_impl, mesh=mesh)
    logits = x @ params["wte"].astype(compute_dtype).T
    lora_b = None if lora is None else lora.get("blocks")
    if lora_b is not None and "lm_head" in lora_b:
        rng = (None if dropout_rng is None
               else jax.random.fold_in(dropout_rng, 2000))
        logits = maybe_lora(logits, x, lora_b["lm_head"], None,
                            lora_dropout, rng, impl=lora_impl)
    return logits


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
