"""AFMoE text decoder (Arcee Trinity, HF `model_type: afmoe`),
pure-functional JAX. The third family beside models/gpt2.py and
models/gemma3.py; what it shares with them it imports (rotary tables,
the attention dispatcher and its flash kernels, LoRA application).

The block, from the published config and the family's public modelling
code (DESIGN.md §30 lists what is taken from which):
  - embeddings scaled by sqrt(hidden_size) (`mup_enabled`);
  - GQA with per-head q/k RMSNorm; sliding layers (`layer_types`) rotate
    q and k (theta 10,000, whole head) and mask j <= i and j > i - window;
    full layers use NO positional encoding and mask j <= i;
  - an output gate: the heads' output times sigmoid(a @ gate_w), before
    o_proj;
  - sandwich norms, plain (not 1 + w) RMSNorm weights:
      h += norm(attn(norm(h)));  h += norm(mlp(norm(h)));
  - the first `num_dense_layers` layers have a SwiGLU MLP at
    `intermediate_size`; every later layer has one shared SwiGLU expert
    beside `num_experts` routed ones at `moe_intermediate_size`, of which
    this chip computes the `experts_held` (ops/moe.py: sigmoid router in
    float32, bias in the selection only, top-k, normalised, scaled);
  - final RMSNorm and an untied head.

Layout: the dense layers stacked under "dense" and applied one by one,
the expert layers stacked under "moe" and run under one lax.scan; the
window/full choice is a traced bool there, and the flash kernel takes its
window statically, so the two kinds are two branches of a lax.cond
(models/gemma3.py does the same). LoRA stacks run over ALL layers, dense
first. Matmul weights are used in `compute_dtype` — a bf16 checkpoint
stays bf16 from disk to the MXU; norms, router and expert bias stay
float32.

Device scopes: `embed`, `attention` (inside it `attn_window` /
`attn_full` around the attention call), `mlp` (inside it `moe_router`,
`moe_dispatch`, `moe_experts`, `moe_combine` from ops/moe.py and
`moe_shared` here).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.core.config import AfmoeConfig
from mobilefinetuner_tpu.models.lora_apply import maybe_lora
from mobilefinetuner_tpu.ops import moe
from mobilefinetuner_tpu.ops.attention import attention, resolve_impl
from mobilefinetuner_tpu.ops.rope import apply_rope, rope_cos_sin


def rms_norm(x, w, eps):
    """x / rms(x) * w, float32 math, x's dtype out."""
    xf = x.astype(jnp.float32)
    var = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def init_params(config: AfmoeConfig, key: jax.Array, dtype=jnp.float32,
                post_norm_scale: float = 1.0,
                expert_bias_std: float = 0.1) -> Dict[str, Any]:
    """Seeded weights: matrices N(0, 0.02) in `dtype`; norm weights
    1 + N(0, 0.02), router N(0, 0.02) and expert bias
    N(0, expert_bias_std) in float32 (a bias that moves the selection, so
    that selection and weights can be told apart; at 0.1 it also decides
    the load: the scores' top 4 of 256 lie in a tail where +0.2 makes an
    expert six times as likely). post_norm_scale multiplies the
    post-attention and post-MLP norm weights (a depth-scaled
    initialisation: each sublayer then enters the residual stream at that
    RMS instead of 1 — with random projections attention's output is
    nearly the same vector at every late position, and at RMS 1 the
    router would see mostly that)."""
    c = config
    H, D, I, Im = (c.hidden_size, c.head_dim, c.intermediate_size,
                   c.moe_intermediate_size)
    nq, nkv = c.num_attention_heads, c.num_key_value_heads
    nd, nm, n = c.num_dense_layers, c.num_moe_layers, c.experts_held[1]
    keys = iter(jax.random.split(key, 64))

    def mat(*shape):
        return (jax.random.normal(next(keys), shape) * 0.02).astype(dtype)

    def norm(*shape):
        return 1.0 + jax.random.normal(next(keys), shape) * 0.02

    def layers(L):
        return {
            "input_ln": norm(L, H),
            "attn": {"q_w": mat(L, H, nq * D), "k_w": mat(L, H, nkv * D),
                     "v_w": mat(L, H, nkv * D), "o_w": mat(L, nq * D, H),
                     "gate_w": mat(L, H, nq * D),
                     "q_norm": norm(L, D), "k_norm": norm(L, D)},
            "post_attn_ln": norm(L, H) * post_norm_scale,
            "pre_mlp_ln": norm(L, H),
            "post_mlp_ln": norm(L, H) * post_norm_scale,
        }

    def swiglu(*lead, width):
        return {"gate_w": mat(*lead, H, width), "up_w": mat(*lead, H, width),
                "down_w": mat(*lead, width, H)}

    return {
        "embed": mat(c.vocab_size, H),
        "dense": {**layers(nd), "mlp": swiglu(nd, width=I)},
        "moe": {**layers(nm),
                "router_w": jax.random.normal(
                    next(keys), (nm, H, c.num_experts)) * 0.02,
                "expert_bias": jax.random.normal(
                    next(keys), (nm, c.num_experts)) * expert_bias_std,
                "shared": swiglu(nm, width=Im * c.num_shared_experts),
                "experts": swiglu(nm, n, width=Im)},
        "final_norm": norm(H),
        "lm_head": mat(c.vocab_size, H),
    }


def _swiglu(x, p, dt):
    h = jax.nn.silu(x @ p["gate_w"].astype(dt)) * (x @ p["up_w"].astype(dt))
    return h @ p["down_w"].astype(dt)


def _output_gate(ctx, gate):
    return ctx * jax.nn.sigmoid(gate)


def _block(c: AfmoeConfig, bp, x, is_full, layer, padding_mask, rope,
           lora_b, dt, lora_dropout, rng, lora_impl, moe_layer=None):
    """One block. bp: THIS layer's weights — but for an expert layer
    under the scan bp["experts"] is the whole [L, n, ...] stack and
    `moe_layer` the traced index into it (ops/moe._gmm: no layer's
    experts are ever copied out); `is_full`: a Python bool (dense
    layers) or a traced one (under the scan); `layer` indexes the LoRA
    stacks. Returns (x, routing) with routing = (idx [T, k],
    counters) for an expert layer, None for a dense one."""
    eps = c.rms_norm_eps
    B, S, H = x.shape
    nq, nkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    a = bp["attn"]

    def lora(y, x_in, name, site):
        entry = None if lora_b is None else lora_b.get(name)
        return maybe_lora(y, x_in, entry, layer, lora_dropout,
                          None if rng is None
                          else jax.random.fold_in(rng, site),
                          impl=lora_impl)

    with jax.named_scope("attention"):
        h = rms_norm(x, bp["input_ln"], eps)
        q = lora(h @ a["q_w"].astype(dt), h, "q_proj", 0)
        k = lora(h @ a["k_w"].astype(dt), h, "k_proj", 1)
        v = lora(h @ a["v_w"].astype(dt), h, "v_proj", 2)
        gate = h @ a["gate_w"].astype(dt)
        q = q.reshape(B, S, nq, D).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, nkv, D).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, nkv, D).transpose(0, 2, 1, 3)
        q = rms_norm(q, a["q_norm"], eps)
        k = rms_norm(k, a["k_norm"], eps)
        impl = c.attention_impl
        if impl == "auto":
            impl = resolve_impl(S, D)

        def full(ops):
            with jax.named_scope("attn_full"):
                return attention(*ops, impl=impl, is_causal=True,
                                 padding_mask=padding_mask)

        def window(ops):
            with jax.named_scope("attn_window"):
                qr, kr, vr = ops
                return attention(apply_rope(qr, *rope), apply_rope(kr, *rope),
                                 vr, impl=impl, is_causal=True,
                                 sliding_window=c.sliding_window,
                                 padding_mask=padding_mask)

        if isinstance(is_full, bool):
            ctx = (full if is_full else window)((q, k, v))
        else:
            ctx = jax.lax.cond(is_full, full, window, (q, k, v))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, nq * D)
        ctx = _output_gate(ctx, gate)
        out = lora(ctx @ a["o_w"].astype(dt), ctx, "o_proj", 3)
        x = x + rms_norm(out, bp["post_attn_ln"], eps)

    with jax.named_scope("mlp"):
        if "experts" not in bp:
            m = rms_norm(x, bp["pre_mlp_ln"], eps)
            f = _swiglu(m, bp["mlp"], dt)
            return x + rms_norm(f, bp["post_mlp_ln"], eps), None
        # every op of an expert layer's MLP lies under exactly one moe_*
        # scope, so their device times add up to the layer's: the pre-norm
        # counts with the router that reads it, the shared sum, post-norm
        # and residual with the combine
        with jax.named_scope("moe_router"):
            mt = rms_norm(x, bp["pre_mlp_ln"], eps).reshape(B * S, H)
            idx, w, _ = moe.route(mt, bp["router_w"], bp["expert_bias"],
                                  c.num_experts_per_tok, c.route_norm,
                                  c.route_scale)
        with jax.named_scope("moe_experts"):
            experts = jax.tree.map(lambda t: t.astype(dt), bp["experts"])
        f, counters = moe.expert_layer(mt, idx, w, experts, c.experts_held,
                                       moe_layer)
        with jax.named_scope("moe_shared"):
            shared = _swiglu(mt, bp["shared"], dt)
        with jax.named_scope("moe_combine"):
            f = (f + shared).reshape(B, S, H)
            x = x + rms_norm(f, bp["post_mlp_ln"], eps)
        return x, (idx, counters)


def hidden_states(config: AfmoeConfig, params, input_ids,
                  attention_mask=None, lora=None,
                  compute_dtype=jnp.float32, remat: bool = False,
                  lora_dropout: float = 0.0, dropout_rng=None,
                  lora_impl: str = "auto", position_ids=None,
                  with_aux: bool = False):
    """Final-norm hidden states [B, S, H]. with_aux: also return
    {"idx": [expert layers, B*S, k] the router's choices (what the
    benchmark's comparison forces on the reference), "counters":
    {"sum": {"moe_assign_here", "moe_dropped"}, "max":
    {"moe_load_max_over_mean"}} over the expert layers — device scalars
    that ride the train step's metrics (train/trainer.py)}."""
    c, dt = config, compute_dtype
    B, S = input_ids.shape
    nd, nm = c.num_dense_layers, c.num_moe_layers
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(dt)
        x = x * jnp.asarray(c.hidden_size ** 0.5 if c.mup_enabled else 1.0,
                            dt)
    if position_ids is None:
        if attention_mask is not None:
            position_ids = jnp.clip(
                jnp.cumsum(attention_mask.astype(jnp.int32), axis=-1) - 1,
                0)
        else:
            position_ids = jnp.arange(S)
    rope = rope_cos_sin(position_ids, c.head_dim, c.rope_theta)
    lora_b = None if lora is None else lora.get("blocks")

    def run(bp, x, is_full, layer, moe_layer=None):
        rng = None if dropout_rng is None else jax.random.fold_in(
            dropout_rng, layer)
        return _block(c, bp, x, is_full, layer, attention_mask, rope,
                      lora_b, dt, lora_dropout, rng, lora_impl, moe_layer)

    def dense_layer(x, bp, i):
        return run(bp, x, c.is_global_layer(i), i)[0]

    if remat:
        dense_layer = jax.checkpoint(dense_layer, static_argnums=(2,))
    for i in range(nd):
        x = dense_layer(x, jax.tree.map(lambda t: t[i], params["dense"]), i)

    is_full = jnp.asarray([c.is_global_layer(nd + i) for i in range(nm)])

    experts = params["moe"]["experts"]
    rest = {k: v for k, v in params["moe"].items() if k != "experts"}

    def body(x, i):
        bp = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
            rest)
        return run({**bp, "experts": experts}, x, is_full[i], nd + i, i)

    if remat:
        body = jax.checkpoint(body)
    x, (idx, counters) = jax.lax.scan(body, x, jnp.arange(nm))
    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    if not with_aux:
        return x
    return x, {"idx": idx, "counters": {
        "sum": {"moe_assign_here": counters["assign_here"].sum(),
                "moe_dropped": counters["dropped"].sum()},
        "max": {"moe_load_max_over_mean":
                counters["load_max_over_mean"].max()}}}


def forward(config: AfmoeConfig, params, input_ids, attention_mask=None,
            lora=None, compute_dtype=jnp.float32, remat: bool = False,
            lora_dropout: float = 0.0, dropout_rng=None,
            lora_impl: str = "auto", position_ids=None,
            with_aux: bool = False):
    """Logits [B, S, V] over this chip's rows of the untied head (training
    goes through the chunked loss on `hidden_states` instead: this
    materialises [B, S, V])."""
    out = hidden_states(config, params, input_ids, attention_mask, lora,
                        compute_dtype, remat, lora_dropout, dropout_rng,
                        lora_impl, position_ids, with_aux)
    x, aux = out if with_aux else (out, None)
    logits = x @ params["lm_head"].astype(compute_dtype).T
    return (logits, aux) if with_aux else logits


def matmul_params_per_token(config: AfmoeConfig) -> int:
    """Matmul parameters one token is EXPECTED to meet on this chip:
    every attention projection with the gate, the dense MLPs, shared
    expert, router and head, and k routed experts times the share of the
    experts held here (uniform routing). The in-loop MFU estimate's N;
    the benchmark counts the measured assignments instead."""
    c = config
    H, D = c.hidden_size, c.head_dim
    attn = H * D * (3 * c.num_attention_heads + 2 * c.num_key_value_heads)
    expert = 3 * H * c.moe_intermediate_size
    moe = (expert * c.num_shared_experts + H * c.num_experts
           + expert * c.num_experts_per_tok * c.experts_held[1]
           / c.num_experts)
    return int(c.num_hidden_layers * attn
               + c.num_dense_layers * 3 * H * c.intermediate_size
               + c.num_moe_layers * moe + c.vocab_size * H)
