"""The language model of inclusionAI's Ling-3.0-flash family (Ling-3.0-
flash-VL without its vision tower; the text sibling's `model_type` is
`bailing_hybrid`), pure-functional JAX: the fourth family beside gpt2,
gemma3 and afmoe. DESIGN.md §32 lists every equation with the config key
or the public description it is taken from, and what is assumed.

Pre-norm residual blocks, x += mixer(norm(x)); x += mlp(norm(x)), RMSNorm
with plain weights in float32, an unscaled embedding, a final norm and an
untied head. Two kinds of mixer with DIFFERENT weights in one stack
(`LingHybridConfig.layer_kind`: the last layer of every
`layer_group_size` is latent attention, the others the delta rule):

  kda  q, k, v = SiLU(conv4(h W)) per channel over time; per head
       q^ = q / |q| d^-1/2, k^ = k / |k|; log-decay per channel
       g = lb sigmoid(exp(A_log) (h W_f + dt_bias)) in (lb, 0), write
       strength beta = sigmoid(h W_b); the gated delta rule (ops/kda.py,
       chunked, float32 state); RMSNorm over each head's output, times
       sigmoid(h W_g) a head, W_o. No rotary.
  mla  q = h W_q (heads of nope + rope); [c ; k_r] = h W_dkv,
       c = RMSNorm(c); [k_nope ; v] a head = c W_ukv; k = [k_nope ; k_r]
       (k_r shared by the heads); per-head RMSNorm of q and k, rotary on
       their last `rotary_dim` dims; causal softmax attention with q.k
       over nope + rope and v over v_head_dim — the flash kernels at two
       head sizes, q and k zero-padded to the next kernel width
       (ops/attention._pad_qk); times sigmoid(h W_g) a head, W_o. Keys
       and values are expanded from the latent, as in training; nothing
       is absorbed.

MLP: SwiGLU at `intermediate_size` below `first_k_dense_replace`, above
it `num_experts` routed experts — sigmoid scores, a selection-only bias,
`topk_group` of `n_group` groups kept by the sum of their two best, top-k
among those, normalised, scaled (ops/moe.route) — of which this chip
computes the `experts_held`, plus one shared expert.

Layout: `params["layers"]` is a list, one dict a layer (the two mixers
share no shape, so nothing is stacked and nothing is sliced out of a
stack inside the step); the layers run in a Python loop, each under
jax.checkpoint with `remat`. LoRA stacks are per layer KIND
(lora.LING_TARGETS): entry `kda_q_proj` holds [number of kda layers, ...]
and is indexed by the layer's position among its kind.

Device scopes: `embed`; `attention` ⊃ `kda_proj_conv`, `kda_gate`,
`kda_scan`, `kda_out` or `mla_proj`, `attn_latent`, `mla_out`; `mlp` ⊃
the `moe_*` scopes of ops/moe.py and `moe_shared` (every op of a mixer
and of an expert MLP under exactly one).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.core.config import LingHybridConfig
from mobilefinetuner_tpu.models.afmoe import _swiglu, rms_norm
from mobilefinetuner_tpu.models.lora_apply import maybe_lora
from mobilefinetuner_tpu.ops import kda, moe
from mobilefinetuner_tpu.ops.attention import attention, resolve_impl
from mobilefinetuner_tpu.ops.rope import apply_rope, rope_cos_sin

F32 = jnp.float32


def kind_index(config: LingHybridConfig) -> list:
    """Layer i's position among the layers of its own kind (the index
    into that kind's LoRA stacks)."""
    seen: Dict[str, int] = {}
    out = []
    for kind in config.layer_kinds:
        out.append(seen.get(kind, 0))
        seen[kind] = out[-1] + 1
    return out


def init_params(config: LingHybridConfig, key: jax.Array, dtype=F32,
                expert_bias_std: float = 0.02,
                embed_std: float = 0.02) -> Dict[str, Any]:
    """Seeded weights: matrices N(0, 0.02) in `dtype` (the embedding
    N(0, embed_std): at 0.02 a token's identity is a thirtieth of the
    first mixer's output in the residual stream, and the biased
    activations' common vector — SiLU has a positive mean — then steers
    every token's router to the same few experts); norm weights
    1 + N(0, 0.02), the router N(0, 0.02), the expert bias N(0,
    expert_bias_std), A_log = log U(1, 16) and dt_bias N(0, 1) in
    float32 (the gate's slope and offset then spread the decays over the
    whole of (lb, 0): fast-forgetting and long-memory channels both
    occur); the conv taps N(0, 0.5) (a sum of four inputs at unit
    scale)."""
    c = config
    H, n, d = c.hidden_size, c.num_attention_heads, c.head_dim
    keys = iter(jax.random.split(key, 40 * c.num_hidden_layers + 8))

    def mat(*shape, std=0.02):
        return (jax.random.normal(next(keys), shape) * std).astype(dtype)

    def norm(*shape):
        return 1.0 + jax.random.normal(next(keys), shape) * 0.02

    def swiglu(*lead, width):
        return {"gate_w": mat(*lead, H, width), "up_w": mat(*lead, H, width),
                "down_w": mat(*lead, width, H)}

    def kda_mixer():
        K = c.short_conv_kernel_size
        return {"q_w": mat(H, n * d), "k_w": mat(H, n * d),
                "v_w": mat(H, n * d), "o_w": mat(n * d, H),
                "q_conv": mat(K, n * d, std=0.5),
                "k_conv": mat(K, n * d, std=0.5),
                "v_conv": mat(K, n * d, std=0.5),
                "f_w": mat(H, n * d), "b_w": mat(H, n), "g_w": mat(H, n),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (n,), F32, 1.0, 16.0)),
                "dt_bias": jax.random.normal(next(keys), (n * d,), F32),
                "o_norm": norm(d)}

    def mla_mixer():
        r, dq = c.kv_lora_rank, c.qk_head_dim
        return {"q_w": mat(H, n * dq),
                "kv_a_w": mat(H, r + c.qk_rope_head_dim),
                "kv_a_norm": norm(r),
                "kv_b_w": mat(r, n * (c.qk_nope_head_dim + c.v_head_dim)),
                "q_norm": norm(dq), "k_norm": norm(dq),
                "g_w": mat(H, n), "o_w": mat(n * c.v_head_dim, H)}

    layers = []
    for i in range(c.num_hidden_layers):
        kind = c.layer_kind(i)
        lp = {"input_ln": norm(H), "post_attn_ln": norm(H),
              kind: kda_mixer() if kind == "kda" else mla_mixer()}
        if c.is_dense_layer(i):
            lp["mlp"] = swiglu(width=c.intermediate_size)
        else:
            lp.update(
                router_w=jax.random.normal(
                    next(keys), (H, c.num_experts)) * 0.02,
                expert_bias=jax.random.normal(
                    next(keys), (c.num_experts,)) * expert_bias_std,
                shared=swiglu(width=c.moe_shared_expert_intermediate_size),
                experts=swiglu(c.experts_held[1],
                               width=c.moe_intermediate_size))
        layers.append(lp)
    return {"embed": mat(c.vocab_size, H, std=embed_std), "layers": layers,
            "final_norm": norm(H), "lm_head": mat(c.vocab_size, H)}


def _heads(x, n):
    """[B, S, n*d] -> [B, n, S, d]."""
    B, S, _ = x.shape
    return x.reshape(B, S, n, -1).transpose(0, 2, 1, 3)


def _l2(x):
    xf = x.astype(F32)
    return xf * jax.lax.rsqrt((xf * xf).sum(-1, keepdims=True) + 1e-12)


def resolve_kda_impl(S: int, chunk: int, head_dim: int) -> str:
    """The delta rule's 'auto', read from shapes (ops/kda.resolve_impl):
    `kernel` (the Pallas kernels) where the length is whole chunks and
    the head whole 128-lane tiles, `chunked` (the same algebra as XLA
    scans) where the length is whole chunks and the head is not,
    `recurrent` (the token-by-token scan) otherwise (odd test lengths).
    The kernels are not wrapped in shard_map, so GSPMD could not
    partition them; the family's entry point (cli/expert_lora.main)
    refuses a mesh of more than one device before anything is resolved."""
    return kda.resolve_impl(S, chunk, head_dim, head_dim)


def _kda_mixer(c, p, h, lora, dt):
    B, S, H = h.shape
    n, d = c.num_attention_heads, c.head_dim
    with jax.named_scope("kda_proj_conv"):
        def proj(name, w, conv):
            y = lora(h @ p[w].astype(dt), h, f"kda_{name}_proj")
            return _heads(jax.nn.silu(kda.causal_conv(y, p[conv])), n)
        q = (_l2(proj("q", "q_w", "q_conv")) * d ** -0.5).astype(dt)
        k = _l2(proj("k", "k_w", "k_conv")).astype(dt)
        v = proj("v", "v_w", "v_conv")
    with jax.named_scope("kda_gate"):
        # float32 from the product's accumulator on: the gate is summed
        # over a chunk, and bf16 loses the decays' differences
        f = jnp.dot(h, p["f_w"].astype(dt), preferred_element_type=F32)
        slope = jnp.repeat(jnp.exp(p["A_log"].astype(F32)), d)
        g = _heads(c.kda_lower_bound * jax.nn.sigmoid(
            slope * (f + p["dt_bias"].astype(F32))), n)
        beta = jax.nn.sigmoid(jnp.dot(
            h, p["b_w"].astype(dt), preferred_element_type=F32)
        ).transpose(0, 2, 1)                              # [B, n, S]
    with jax.named_scope("kda_scan"):
        if resolve_kda_impl(S, c.kda_chunk, d) != "recurrent":
            o = kda.kda_chunked(q, k, v, g, beta, chunk=c.kda_chunk)
        else:
            o = kda.kda_recurrent(q, k, v, g, beta)[0].astype(dt)
    with jax.named_scope("kda_out"):
        gate = jax.nn.sigmoid(h @ p["g_w"].astype(dt))     # [B, S, n]
        o = rms_norm(o, p["o_norm"], c.rms_norm_eps) \
            * gate.transpose(0, 2, 1)[..., None]
        o = o.transpose(0, 2, 1, 3).reshape(B, S, n * d)
        return lora(o @ p["o_w"].astype(dt), o, "kda_o_proj")


def _mla_mixer(c, p, h, lora, dt, rope, padding_mask):
    B, S, H = h.shape
    n, r = c.num_attention_heads, c.kv_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    eps = c.rms_norm_eps
    with jax.named_scope("mla_proj"):
        q = _heads(lora(h @ p["q_w"].astype(dt), h, "mla_q_proj"), n)
        kv_a = lora(h @ p["kv_a_w"].astype(dt), h, "mla_kv_a_proj")
        lat = rms_norm(kv_a[..., :r], p["kv_a_norm"], eps)
        kv = _heads(lora(lat @ p["kv_b_w"].astype(dt), lat,
                         "mla_kv_b_proj"), n)              # [B, n, S, dn+dv]
        k_rope = jnp.broadcast_to(kv_a[:, None, :, r:], (B, n, S, dr))
        k = jnp.concatenate([kv[..., :dn], k_rope], axis=-1)
        v = kv[..., dn:]
        q = rms_norm(q, p["q_norm"], eps)
        k = rms_norm(k, p["k_norm"], eps)
        rot = lambda x: jnp.concatenate(
            [x[..., :dn], apply_rope(x[..., dn:], *rope)], axis=-1)
        q, k = rot(q), rot(k)
    with jax.named_scope("attn_latent"):
        impl = c.attention_impl
        if impl == "auto":
            impl = resolve_impl(S, dn + dr)
        ctx = attention(q, k, v, impl=impl, is_causal=True,
                        padding_mask=padding_mask,
                        scale=(dn + dr) ** -0.5)           # [B, n, S, dv]
    with jax.named_scope("mla_out"):
        gate = jax.nn.sigmoid(h @ p["g_w"].astype(dt))
        ctx = ctx * gate.transpose(0, 2, 1)[..., None]
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, n * dv)
        return lora(ctx @ p["o_w"].astype(dt), ctx, "mla_o_proj")


def _layer(c: LingHybridConfig, i: int, lp, x, lora_b, padding_mask, rope,
           dt, lora_dropout, rng, lora_impl):
    """Layer i on x [B, S, H]. Returns (x, routing): routing = (idx
    [T, k], counters) for an expert layer, None for a dense one."""
    eps = c.rms_norm_eps
    B, S, H = x.shape
    kind, at = c.layer_kind(i), kind_index(c)[i]
    sites = iter(range(16))

    def lora(y, x_in, name):
        site = next(sites)
        entry = None if lora_b is None else lora_b.get(name)
        return maybe_lora(y, x_in, entry, at, lora_dropout,
                          None if rng is None
                          else jax.random.fold_in(rng, site),
                          impl=lora_impl)

    with jax.named_scope("attention"):
        with jax.named_scope("kda_proj_conv" if kind == "kda"
                             else "mla_proj"):
            h = rms_norm(x, lp["input_ln"], eps)
        if kind == "kda":
            out = _kda_mixer(c, lp["kda"], h, lora, dt)
        else:
            out = _mla_mixer(c, lp["mla"], h, lora, dt, rope, padding_mask)
        with jax.named_scope("kda_out" if kind == "kda" else "mla_out"):
            x = x + out

    with jax.named_scope("mlp"):
        if "experts" not in lp:
            m = rms_norm(x, lp["post_attn_ln"], eps)
            return x + _swiglu(m, lp["mlp"], dt), None
        with jax.named_scope("moe_router"):
            mt = rms_norm(x, lp["post_attn_ln"], eps).reshape(B * S, H)
            idx, w, kept = moe.route(
                mt, lp["router_w"], lp["expert_bias"],
                c.num_experts_per_tok, c.norm_topk_prob,
                c.routed_scaling_factor, c.n_group, c.topk_group)
            first, count = c.experts_held
            per = c.num_experts // c.n_group
            mine = jnp.zeros((c.n_group,), bool).at[
                first // per:-(-(first + count) // per)].set(True)
            # tokens whose kept groups include one held here (all of them
            # where the router has one group)
            here = (kept & mine).any(-1).sum() if kept is not None \
                else jnp.asarray(B * S)
        with jax.named_scope("moe_experts"):
            experts = jax.tree.map(lambda t: t.astype(dt), lp["experts"])
        f, counters = moe.expert_layer(mt, idx, w, experts, c.experts_held)
        with jax.named_scope("moe_shared"):
            shared = _swiglu(mt, lp["shared"], dt)
        with jax.named_scope("moe_combine"):
            x = x + (f + shared).reshape(B, S, H)
        return x, (idx, dict(counters, groups_here=here.astype(F32)))


def hidden_states(config: LingHybridConfig, params, input_ids,
                  attention_mask=None, lora=None, compute_dtype=F32,
                  remat: bool = False, lora_dropout: float = 0.0,
                  dropout_rng=None, lora_impl: str = "auto",
                  position_ids=None, with_aux: bool = False):
    """Final-norm hidden states [B, S, H]. with_aux: also {"idx":
    [expert layers, B*S, k] the router's choices, "counters": {"sum":
    {"moe_assign_here", "moe_dropped", "moe_groups_here": the (token,
    expert layer) pairs whose kept groups include one this chip holds —
    over B*S*expert layers it is the share a reader reports}, "max":
    {"moe_load_max_over_mean"}} — device scalars that ride the train
    step's metrics."""
    c, dt = config, compute_dtype
    B, S = input_ids.shape
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(dt)
    if position_ids is None:
        if attention_mask is not None:
            position_ids = jnp.clip(
                jnp.cumsum(attention_mask.astype(jnp.int32), axis=-1) - 1,
                0)
        else:
            position_ids = jnp.arange(S)
    rope = rope_cos_sin(position_ids, c.rotary_dim, c.rope_theta)
    lora_b = None if lora is None else lora.get("blocks")
    routings = []
    for i, lp in enumerate(params["layers"]):
        rng = None if dropout_rng is None else jax.random.fold_in(
            dropout_rng, i)
        fn = functools.partial(_layer, c, i, padding_mask=attention_mask,
                               rope=rope, dt=dt, lora_dropout=lora_dropout,
                               rng=rng, lora_impl=lora_impl)
        if remat:
            # keep the delta rule's output and block states (0.2 GB a
            # layer at 16k): the backward then recomputes projections,
            # conv and gates, but not the forward scan
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies.save_only_these_names(
                    *kda.SAVE_NAMES))
        x, routing = fn(lp, x, lora_b)
        if routing is not None:
            routings.append(routing)
    x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    if not with_aux:
        return x
    stack = lambda name: jnp.stack([r[1][name] for r in routings])
    return x, {"idx": jnp.stack([r[0] for r in routings]), "counters": {
        "sum": {"moe_assign_here": stack("assign_here").sum(),
                "moe_dropped": stack("dropped").sum(),
                "moe_groups_here": stack("groups_here").sum()},
        "max": {"moe_load_max_over_mean":
                stack("load_max_over_mean").max()}}}


def forward(config: LingHybridConfig, params, input_ids, attention_mask=None,
            lora=None, compute_dtype=F32, remat: bool = False,
            lora_dropout: float = 0.0, dropout_rng=None,
            lora_impl: str = "auto", position_ids=None,
            with_aux: bool = False):
    """Logits [B, S, V] over this chip's rows of the untied head (training
    goes through the chunked loss on `hidden_states` instead)."""
    out = hidden_states(config, params, input_ids, attention_mask, lora,
                        compute_dtype, remat, lora_dropout, dropout_rng,
                        lora_impl, position_ids, with_aux)
    x, aux = out if with_aux else (out, None)
    logits = x @ params["lm_head"].astype(compute_dtype).T
    return (logits, aux) if with_aux else logits


def mixer_params(config: LingHybridConfig) -> Dict[str, int]:
    """Matmul parameters of one mixer of each kind (the conv's taps, the
    norms, A_log and dt_bias are not matmuls)."""
    c = config
    H, n, d = c.hidden_size, c.num_attention_heads, c.head_dim
    return {"kda": 5 * H * n * d + 2 * H * n,         # q k v o f; b, g
            "mla": H * n * c.qk_head_dim
            + H * (c.kv_lora_rank + c.qk_rope_head_dim)
            + c.kv_lora_rank * n * (c.qk_nope_head_dim + c.v_head_dim)
            + n * c.v_head_dim * H + H * n}


def matmul_params_per_token(config: LingHybridConfig) -> int:
    """Matmul parameters one token is EXPECTED to meet on this chip
    (uniform routing over the held experts): the in-loop MFU estimate's
    N; the benchmark counts the measured assignments instead."""
    c = config
    H = c.hidden_size
    mix = mixer_params(c)
    expert = 3 * H * c.moe_intermediate_size
    sparse = (3 * H * c.moe_shared_expert_intermediate_size
              + H * c.num_experts
              + expert * c.num_experts_per_tok * c.experts_held[1]
              / c.num_experts)
    return int(sum(mix[k] for k in c.layer_kinds)
               + c.first_k_dense_replace * 3 * H * c.intermediate_size
               + c.num_moe_layers * sparse + c.vocab_size * H)
