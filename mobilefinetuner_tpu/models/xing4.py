"""XingChen-AGI's Xing4.0 family (`model_type: xing4_0`; the benchmark's
configuration is Xing4.0-29B-A4B), pure-functional JAX: the fifth family
beside gpt2, gemma3, afmoe and ling_hybrid. DESIGN.md §33 lists every
equation with the config key or the paper it is taken from, and what is
assumed.

The residual stream is n = `hc_mult` rows a token, x [n, B, S, C]: the
embedding is copied into the n rows, every sublayer F (the mixer, then
the MLP or expert layer) reads ONE row's worth u = H_pre x through its
own hyper-connection coefficients and writes back x' = H_res x +
H_post^T F(RMSNorm(u)) (ops/hyper_connections.py: coefficients from the
normalised stream, H_res doubly stochastic by `hc_sinkhorn_iters`
Sinkhorn rounds, all float32), and after the last block the rows are
summed before the final RMSNorm and the untied head.

  mixer  DeepSeek-V3's latent attention with BOTH latents:
         c_q = RMSNorm(h W_qa), q = c_q W_qb (heads of nope + rope);
         [c_kv ; k_r] = h W_kva, [k_nope ; v] a head = RMSNorm(c_kv) W_kvb;
         rotary (rotate-half, yarn-blended frequencies: ops/rope.py) on
         q's rope dims and on k_r, which the heads share; causal softmax
         attention with q.k over nope + rope and v over v_head_dim at
         scale (nope + rope)^-1/2 · mscale² — the flash kernels at two
         head sizes, q and k zero-padded to the next kernel width
         (ops/attention._pad_qk); W_o. No q/k norm after the
         projections, no output gate. Keys and values are expanded from
         the latent, as in training; nothing is absorbed.
  MLP    SwiGLU at `intermediate_size` below `first_k_dense_replace`;
         above it `n_routed_experts` routed experts — sigmoid scores, a
         selection-only bias, top-k, normalised, scaled (ops/moe.route)
         — of which this chip computes the `experts_held`, plus one
         shared expert.
  MTP    one module (DeepSeek-V3 §2.2, depth 1): h'_i = W_eh
         [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(h_i)], h_i the main model's
         summed rows before its final norm; one more sparse block (its
         stream copied in and summed out like the model's); its own
         final norm; the SHARED head against t_{i+2}. `hidden_states(...,
         with_aux=True)` hands its normed hidden state back as the
         step's second loss (`aux["second"]`, cli/expert_lora.py);
         `mtp_loss_weight` 0 leaves the module out of the program.

Layout: `params["layers"]` a list of per-layer dicts run in a Python
loop, each block under jax.checkpoint with `remat` (a block's saved
input is the n-row stream); `params["mtp"]["block"]` one more such dict.
LoRA stacks (lora.XING4_TARGETS) run over the layers and then the MTP
module's block: position `num_hidden_layers` is the module's.

Device scopes: `embed`; `attention` ⊃ `mhc_coeff`, `mhc_pre`, `mla_proj`,
`attn_latent`, `mla_out`, `mhc_post`; `mlp` ⊃ `mhc_coeff`, `mhc_pre`, the
`moe_*` scopes of ops/moe.py and `moe_shared`, `mhc_post` (every op of a
mixer and of an expert MLP under exactly one); `mtp` around the whole
module, so that its block's scopes nest under it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.core.config import Xing4Config
from mobilefinetuner_tpu.models.afmoe import _swiglu, rms_norm
from mobilefinetuner_tpu.models.lora_apply import maybe_lora
from mobilefinetuner_tpu.ops import hyper_connections as hc
from mobilefinetuner_tpu.ops import moe
from mobilefinetuner_tpu.ops.attention import attention, resolve_impl
from mobilefinetuner_tpu.ops.rope import (apply_rope, rope_cos_sin,
                                          yarn_mscale)

F32 = jnp.float32


def softmax_scale(c: Xing4Config) -> float:
    """(nope + rope)^-1/2 · m², m = yarn_mscale(factor, mscale_all_dim)
    (1 without yarn)."""
    rs = c.rope_scaling
    m = 1.0 if rs is None else yarn_mscale(rs["factor"],
                                           rs.get("mscale_all_dim", 0))
    return c.qk_head_dim ** -0.5 * m * m


def init_params(config: Xing4Config, key: jax.Array, dtype=F32,
                expert_bias_std: float = 0.02, embed_std: float = 0.02,
                hc_res_logit: float = 8.0) -> Dict[str, Any]:
    """Seeded weights: matrices N(0, 0.02) in `dtype` (the embedding
    N(0, embed_std)); norm weights 1 + N(0, 0.02), the router
    N(0, 0.02) and the expert bias N(0, expert_bias_std) in float32;
    hyper-connections by ops/hyper_connections.init (phi in `dtype`; b
    and alpha float32, `hc_res_logit` on the diagonal of H~res's b, so
    that every sublayer starts near a plain residual block: reads the
    rows' mean, writes to all rows alike, keeps the rows apart)."""
    c = config
    H, n = c.hidden_size, c.num_attention_heads
    blocks = c.num_hidden_layers + c.num_nextn_predict_layers
    keys = iter(jax.random.split(key, 32 * blocks + 16))

    def mat(*shape, std=0.02):
        return (jax.random.normal(next(keys), shape) * std).astype(dtype)

    def norm(*shape):
        return 1.0 + jax.random.normal(next(keys), shape) * 0.02

    def swiglu(*lead, width):
        return {"gate_w": mat(*lead, H, width), "up_w": mat(*lead, H, width),
                "down_w": mat(*lead, width, H)}

    def block(dense: bool):
        rq, r = c.q_lora_rank, c.kv_lora_rank
        bp = {"hc_attn": hc.init(next(keys), c.hc_mult, H, dtype,
                                 res_logit=hc_res_logit),
              "hc_mlp": hc.init(next(keys), c.hc_mult, H, dtype,
                                res_logit=hc_res_logit),
              "input_ln": norm(H), "post_attn_ln": norm(H),
              "mla": {"q_a_w": mat(H, rq), "q_a_norm": norm(rq),
                      "q_b_w": mat(rq, n * c.qk_head_dim),
                      "kv_a_w": mat(H, r + c.qk_rope_head_dim),
                      "kv_a_norm": norm(r),
                      "kv_b_w": mat(r, n * (c.qk_nope_head_dim
                                            + c.v_head_dim)),
                      "o_w": mat(n * c.v_head_dim, H)}}
        if dense:
            bp["mlp"] = swiglu(width=c.intermediate_size)
        else:
            bp.update(
                router_w=jax.random.normal(
                    next(keys), (H, c.n_routed_experts)) * 0.02,
                expert_bias=jax.random.normal(
                    next(keys), (c.n_routed_experts,)) * expert_bias_std,
                shared=swiglu(width=c.moe_intermediate_size),
                experts=swiglu(c.experts_held[1],
                               width=c.moe_intermediate_size))
        return bp

    params = {"embed": mat(c.vocab_size, H, std=embed_std),
              "layers": [block(c.is_dense_layer(i))
                         for i in range(c.num_hidden_layers)],
              "final_norm": norm(H), "lm_head": mat(c.vocab_size, H)}
    if c.num_nextn_predict_layers:
        params["mtp"] = {"enorm": norm(H), "hnorm": norm(H),
                         "eh_proj": mat(2 * H, H), "block": block(False),
                         "norm": norm(H)}
    return params


def _heads(x, n):
    """[B, S, n*d] -> [B, n, S, d]."""
    B, S, _ = x.shape
    return x.reshape(B, S, n, -1).transpose(0, 2, 1, 3)


def _mla_mixer(c, p, h, lora, dt, rope, padding_mask):
    B, S, H = h.shape
    n, r = c.num_attention_heads, c.kv_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    eps = c.rms_norm_eps
    with jax.named_scope("mla_proj"):
        c_q = rms_norm(lora(h @ p["q_a_w"].astype(dt), h, "q_a_proj"),
                       p["q_a_norm"], eps)
        q = _heads(lora(c_q @ p["q_b_w"].astype(dt), c_q, "q_b_proj"), n)
        kv_a = lora(h @ p["kv_a_w"].astype(dt), h, "kv_a_proj")
        lat = rms_norm(kv_a[..., :r], p["kv_a_norm"], eps)
        kv = _heads(lora(lat @ p["kv_b_w"].astype(dt), lat, "kv_b_proj"),
                    n)                                   # [B, n, S, dn+dv]
        k_rope = apply_rope(kv_a[:, None, :, r:], *rope)  # [B, 1, S, dr]
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (B, n, S, dr))], axis=-1)
        v = kv[..., dn:]
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], *rope)], axis=-1)
    with jax.named_scope("attn_latent"):
        impl = c.attention_impl
        if impl == "auto":
            impl = resolve_impl(S, dn + dr)
        ctx = attention(q, k, v, impl=impl, is_causal=True,
                        padding_mask=padding_mask,
                        scale=softmax_scale(c))           # [B, n, S, dv]
    with jax.named_scope("mla_out"):
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, n * dv)
        return lora(ctx @ p["o_w"].astype(dt), ctx, "o_proj")


def _block(c: Xing4Config, at: int, bp, x, lora_b, padding_mask, rope, dt,
           lora_dropout, rng, lora_impl):
    """One block on the stream x [n, B, S, C]; `at` its position in the
    LoRA stacks. Returns (x, routing, row_err): routing = (idx [T, k],
    counters) for an expert block, None for a dense one; row_err the
    largest |row sum − 1| of its two H_res."""
    eps = c.rms_norm_eps
    _, B, S, H = x.shape
    sites = iter(range(16))
    mix = functools.partial(
        hc.coefficients, iters=c.hc_sinkhorn_iters, eps=c.hc_eps,
        clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max), norm_eps=eps)

    def lora(y, x_in, name):
        site = next(sites)
        entry = None if lora_b is None else lora_b.get(name)
        return maybe_lora(y, x_in, entry, at, lora_dropout,
                          None if rng is None
                          else jax.random.fold_in(rng, site),
                          impl=lora_impl)

    with jax.named_scope("attention"):
        pre, post, res, err_a = mix(x, bp["hc_attn"])
        u = hc.pre_mix(x, pre)
        with jax.named_scope("mla_proj"):
            h = rms_norm(u, bp["input_ln"], eps)
        out = _mla_mixer(c, bp["mla"], h, lora, dt, rope, padding_mask)
        x = hc.post_mix(x, out, post, res)

    with jax.named_scope("mlp"):
        pre, post, res, err_m = mix(x, bp["hc_mlp"])
        err = jnp.maximum(err_a, err_m)
        u = hc.pre_mix(x, pre)
        if "experts" not in bp:
            m = rms_norm(u, bp["post_attn_ln"], eps)
            return hc.post_mix(x, _swiglu(m, bp["mlp"], dt), post, res), \
                None, err
        with jax.named_scope("moe_router"):
            mt = rms_norm(u, bp["post_attn_ln"], eps).reshape(B * S, H)
        idx, w, _ = moe.route(
            mt, bp["router_w"], bp["expert_bias"], c.num_experts_per_tok,
            c.norm_topk_prob, c.routed_scaling_factor, c.n_group,
            c.topk_group)
        with jax.named_scope("moe_experts"):
            experts = jax.tree.map(lambda t: t.astype(dt), bp["experts"])
        f, counters = moe.expert_layer(mt, idx, w, experts, c.experts_held)
        with jax.named_scope("moe_shared"):
            shared = _swiglu(mt, bp["shared"], dt)
        with jax.named_scope("moe_combine"):
            y = (f + shared).reshape(B, S, H)
        return hc.post_mix(x, y, post, res), (idx, counters), err


def _copy_in(c, h):
    """[B, S, C] -> the stream [n, B, S, C]: every row the same."""
    return jnp.broadcast_to(h[None], (c.hc_mult, *h.shape))


def _sum_out(x):
    """The stream's rows summed (float32), in its dtype."""
    return x.astype(F32).sum(0).astype(x.dtype)


def hidden_states(config: Xing4Config, params, input_ids,
                  attention_mask=None, lora=None, compute_dtype=F32,
                  remat: bool = False, lora_dropout: float = 0.0,
                  dropout_rng=None, lora_impl: str = "auto",
                  position_ids=None, with_aux: bool = False):
    """Final-norm hidden states [B, S, H] of the main model. with_aux:
    also {"idx": [expert layers, B*S, k] the router's choices,
    "counters": {"sum": {"moe_assign_here", "moe_dropped"}, "max":
    {"moe_load_max_over_mean", "hc_res_row_err"}} — device scalars that
    ride the train step's metrics — and, where the step runs the
    multi-token-prediction module (`config.has_mtp`), "idx_mtp" [B*S, k]
    and "second": {"hidden": the module's own final-norm hidden states
    [B, S, H] (position i has read tokens 0..i+1), "shift": 1 (its
    labels are the main model's moved one further: position i predicts
    t_{i+2}), "weight": mtp_loss_weight, "name": "mtp"} for the
    trainer's second loss (cli/expert_lora.py). The counters then
    include the module's block."""
    c, dt = config, compute_dtype
    B, S = input_ids.shape
    with jax.named_scope("embed"):
        x = _copy_in(c, params["embed"][input_ids].astype(dt))
    if position_ids is None:
        if attention_mask is not None:
            position_ids = jnp.clip(
                jnp.cumsum(attention_mask.astype(jnp.int32), axis=-1) - 1,
                0)
        else:
            position_ids = jnp.arange(S)
    rope = rope_cos_sin(position_ids, c.qk_rope_head_dim, c.rope_theta,
                        c.rope_scaling)
    lora_b = None if lora is None else lora.get("blocks")

    def run(at, bp, x):
        rng = None if dropout_rng is None else jax.random.fold_in(
            dropout_rng, at)
        fn = functools.partial(_block, c, at, padding_mask=attention_mask,
                               rope=rope, dt=dt, lora_dropout=lora_dropout,
                               rng=rng, lora_impl=lora_impl)
        return (jax.checkpoint(fn) if remat else fn)(bp, x, lora_b)

    routings, errs = [], []
    for i, bp in enumerate(params["layers"]):
        x, routing, err = run(i, bp, x)
        errs.append(err)
        if routing is not None:
            routings.append(routing)
    h = _sum_out(x)
    out = rms_norm(h, params["final_norm"], c.rms_norm_eps)
    if not with_aux:
        return out
    aux = {"idx": jnp.stack([r[0] for r in routings])}
    if c.has_mtp:
        with jax.named_scope("mtp"):
            mp = params["mtp"]
            nxt = jnp.concatenate([input_ids[:, 1:], input_ids[:, -1:]], 1)
            with jax.named_scope("embed"):
                e = params["embed"][nxt].astype(dt)
            merged = jnp.concatenate(
                [rms_norm(e, mp["enorm"], c.rms_norm_eps),
                 rms_norm(h, mp["hnorm"], c.rms_norm_eps)], axis=-1)
            xm, routing, err = run(
                c.num_hidden_layers, mp["block"],
                _copy_in(c, merged @ mp["eh_proj"].astype(dt)))
            errs.append(err)
            routings.append(routing)
            aux["idx_mtp"] = routing[0]
            aux["second"] = {
                "hidden": rms_norm(_sum_out(xm), mp["norm"],
                                   c.rms_norm_eps),
                "shift": 1, "weight": c.mtp_loss_weight, "name": "mtp"}
    stack = lambda name: jnp.stack([r[1][name] for r in routings])
    aux["counters"] = {
        "sum": {"moe_assign_here": stack("assign_here").sum(),
                "moe_dropped": stack("dropped").sum()},
        "max": {"moe_load_max_over_mean": stack("load_max_over_mean").max(),
                "hc_res_row_err": jnp.stack(errs).max()}}
    return out, aux


def forward(config: Xing4Config, params, input_ids, attention_mask=None,
            lora=None, compute_dtype=F32, remat: bool = False,
            lora_dropout: float = 0.0, dropout_rng=None,
            lora_impl: str = "auto", position_ids=None,
            with_aux: bool = False):
    """Logits [B, S, V] of the main model over this chip's rows of the
    untied head (training goes through the chunked loss on
    `hidden_states` instead); with_aux: also aux["second"]["hidden"]'s
    logits under aux["logits_mtp"], where the module runs."""
    out = hidden_states(config, params, input_ids, attention_mask, lora,
                        compute_dtype, remat, lora_dropout, dropout_rng,
                        lora_impl, position_ids, with_aux)
    x, aux = out if with_aux else (out, None)
    head = params["lm_head"].astype(compute_dtype).T
    if aux is not None and "second" in aux:
        aux["logits_mtp"] = aux["second"]["hidden"] @ head
    return (x @ head, aux) if with_aux else x @ head


def mixer_params(config: Xing4Config) -> int:
    """Matmul parameters of one mixer (its two norms are not matmuls)."""
    c = config
    H, n = c.hidden_size, c.num_attention_heads
    return (H * c.q_lora_rank + c.q_lora_rank * n * c.qk_head_dim
            + H * (c.kv_lora_rank + c.qk_rope_head_dim)
            + c.kv_lora_rank * n * (c.qk_nope_head_dim + c.v_head_dim)
            + n * c.v_head_dim * H)


def matmul_params_per_token(config: Xing4Config) -> int:
    """Matmul parameters one token is EXPECTED to meet on this chip
    (uniform routing over the held experts; the head twice and the
    module's merge and block where the step runs it): the in-loop MFU
    estimate's N; the benchmark counts the measured assignments
    instead."""
    c = config
    H = c.hidden_size
    phi = 2 * c.hc_mult * H * (2 * c.hc_mult + c.hc_mult ** 2)
    expert = 3 * H * c.moe_intermediate_size
    sparse = (mixer_params(c) + phi + expert + H * c.n_routed_experts
              + expert * c.num_experts_per_tok * c.experts_held[1]
              / c.n_routed_experts)
    dense = mixer_params(c) + phi + 3 * H * c.intermediate_size
    mtp = (2 * H * H + sparse + c.vocab_size * H) if c.has_mtp else 0
    return int(c.first_k_dense_replace * dense + c.num_moe_layers * sparse
               + c.vocab_size * H + mtp)
