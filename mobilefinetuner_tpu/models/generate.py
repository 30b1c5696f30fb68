"""Autoregressive generation with a KV cache, for GPT-2 and Gemma-3.

The reference framework is training/eval-only: its only KV-cache and
sampling code sits in the excluded legacy tree (reference:
legacy/transformer/kv_cache.cpp + autoregressive_ops, catalogued "orphan"
in SURVEY.md §2.10). This module supplies that missing capability
TPU-natively:

  * prefill = ONE full-sequence forward (the models' scan path, MXU-sized
    matmuls) that also returns every layer's K/V (`collect_kv=True`);
  * decode = a `lax.scan` over token steps; each step runs all layers via
    an inner scan over the stacked [L, ...] weights, updating the cache
    with `dynamic_update_slice` — static shapes throughout, one compiled
    program for the whole generation;
  * prompts are LEFT-padded to a common length so every cache write lands
    at the same column; positions/RoPE phases are mask-derived per sample,
    matching the models' HF-aligned padded-batch semantics.

LoRA: merge adapters into the base weights first (lora.merge_gpt2 /
merge_gemma3) — generation reads plain params.

Sampling: greedy, temperature, top-k, nucleus (top-p), composable; eos
stops a row (further slots fill with pad_id) and `lax.while_loop`-free
full-length scan keeps shapes static.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.core.config import GPT2Config, Gemma3TextConfig
from mobilefinetuner_tpu.lora.lora import GPT2_SPLIT_QKV_SLOTS
from mobilefinetuner_tpu.models import gemma3, gpt2
from mobilefinetuner_tpu.models.family import GEMMA, GPT2, Family
from mobilefinetuner_tpu.models.lora_apply import maybe_lora
from mobilefinetuner_tpu.ops.rope import apply_rope, rope_cos_sin

NEG_INF = -1e30


def _head_lora(logits, h, lora_b, impl):
    """Apply an optional "lm_head" adapter entry at a logits projection
    site (decode/prefill shapes are one token per row — the cost model
    keeps these on the rank-r XLA order)."""
    if lora_b is None or "lm_head" not in lora_b:
        return logits
    return maybe_lora(logits, h, lora_b["lm_head"], None, impl=impl)


class AdapterSites:
    """The routed adapters of one trace at the block's matmul sites:
    `sites(y, x, name, i)` is the product y = x @ W plus adapter `name`
    of layer i (y itself where the tree has no such entry), and
    `name in sites` says whether it has one."""

    def __init__(self, lora, impl: str):
        self.blocks = None if lora is None else lora.get("blocks")
        self.impl = impl

    def __contains__(self, name) -> bool:
        return self.blocks is not None and name in self.blocks

    def __call__(self, y, x_in, name, i):
        entry = None if self.blocks is None else self.blocks.get(name)
        return maybe_lora(y, x_in, entry, i, impl=self.impl)


def _cast_floats(tree, dtype):
    return jax.tree.map(
        lambda t: t.astype(dtype)
        if jnp.issubdtype(t.dtype, jnp.floating) else t, tree)


@dataclasses.dataclass(frozen=True)
class SampleConfig:
    max_new_tokens: int = 32
    temperature: float = 1.0     # <= 0 or greedy=True -> argmax
    top_k: int = 0               # 0 = off
    top_p: float = 1.0           # 1.0 = off
    greedy: bool = False
    eos_id: Optional[int] = None
    pad_id: int = 0


def _filter_logits(logits, cfg: SampleConfig):
    """Apply top-k then top-p filtering (HF order) to [B, V] logits."""
    V = logits.shape[-1]
    if cfg.top_k and cfg.top_k < V:
        kth = jnp.sort(logits, axis=-1)[:, V - cfg.top_k][:, None]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if cfg.top_p < 1.0:
        sort_idx = jnp.argsort(-logits, axis=-1)
        sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose cumulative mass (exclusive) is < top_p; the
        # first token is always kept
        keep_sorted = (cum - probs) < cfg.top_p
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(logits.shape[0])[:, None], sort_idx].set(keep_sorted)
        logits = jnp.where(keep, logits, NEG_INF)
    return logits


def _sample(logits, key, cfg: SampleConfig):
    """[B, V] logits -> [B] token ids."""
    if cfg.greedy or cfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _filter_logits(logits / cfg.temperature, cfg)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def sample_per_row(logits, temperature, top_k, top_p, keys):
    """Per-row sampling for the serve engine's slot batch: [S, V]
    logits with PER-ROW temperature [S] f32 / top_k [S] i32 / top_p
    [S] f32 and per-row PRNG keys [S, 2] uint32 (raw legacy layout,
    already fold_in'd with the token's absolute position by the
    caller). Generalizes _filter_logits' scalar top-k/top-p to vector
    parameters so one compiled step serves mixed greedy/sampled slots:
    temperature <= 0 rows take the bit-exact greedy argmax (idle slots
    and greedy requests), top_k <= 0 / top_p >= 1 disable each filter
    per row."""
    S, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    # per-row top-k: kth-value threshold (the row-gathered analog of
    # _filter_logits' scalar sort-index)
    k_eff = jnp.where((top_k > 0) & (top_k < V), top_k, V)
    kth = jnp.sort(scaled, axis=-1)[jnp.arange(S), V - k_eff][:, None]
    scaled = jnp.where(scaled < kth, NEG_INF, scaled)
    # per-row top-p: exclusive-cumulative-mass keep mask scattered back
    # through the descending sort (HF order, as _filter_logits)
    sort_idx = jnp.argsort(-scaled, axis=-1)
    sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < jnp.minimum(top_p, 1.0)[:, None]
    keep = jnp.zeros_like(keep_sorted).at[
        jnp.arange(S)[:, None], sort_idx].set(keep_sorted)
    scaled = jnp.where(keep, scaled, NEG_INF)
    sampled = jax.vmap(
        lambda key, row: jax.random.categorical(key, row))(keys, scaled)
    return jnp.where(temperature <= 0.0, greedy,
                     sampled.astype(jnp.int32))


def _advance(tok_raw, done, cfg: SampleConfig):
    """eos bookkeeping: emit pad for finished rows, mark rows that just
    emitted eos as finished AFTER emitting it."""
    tok = jnp.where(done, jnp.int32(cfg.pad_id), tok_raw)
    if cfg.eos_id is not None:
        done = done | (tok_raw == cfg.eos_id)
    return tok, done


def _col_positions(attention_mask, P, T):
    """Per-sample position ids of every cache column [B, T]: prompt columns
    use mask-derived positions (HF convention), generated column P+j has
    position n_real + j."""
    n_real = attention_mask.sum(-1).astype(jnp.int32)            # [B]
    prompt_pos = jnp.clip(
        jnp.cumsum(attention_mask.astype(jnp.int32), axis=-1) - 1, 0)
    gen_pos = n_real[:, None] + jnp.arange(T - P, dtype=jnp.int32)[None, :]
    return jnp.concatenate([prompt_pos, gen_pos], axis=-1)


def _col_valid(attention_mask, P, T, t):
    """[B, T] bool: which cache columns are attendable at decode step t
    (prompt columns per the mask; generated columns 0..t)."""
    cols = jnp.arange(T)
    gen_ok = cols[None, :] <= P + t
    prompt = jnp.pad(attention_mask.astype(bool),
                     ((0, 0), (0, T - P)), constant_values=True)
    return prompt & gen_ok


def _decode_all(decode_step, logits0, kc, vc, rng, cfg: SampleConfig):
    """Sample token 0 from the prefill's logits, then scan N-1 decode
    steps: step t consumes token t and samples token t+1, so the final
    token comes out of the carry — no trailing all-layers forward whose
    sample would be discarded. Returns [B, N] ids."""
    N = cfg.max_new_tokens
    all_keys = jax.random.split(rng, N + 1)
    tok0_raw = _sample(logits0.astype(jnp.float32), all_keys[N], cfg)
    tok0, done0 = _advance(
        tok0_raw, jnp.zeros((logits0.shape[0],), bool), cfg)
    (tok_last, _, _, _), toks = jax.lax.scan(
        decode_step, (tok0, done0, kc, vc),
        (jnp.arange(N - 1, dtype=jnp.int32), all_keys[:N - 1]))
    return jnp.concatenate([toks, tok_last[None]], axis=0).T


# ----------------------------------------------------------- GPT-2 ----------

def gpt2_generate(config: GPT2Config, params, input_ids, attention_mask,
                  cfg: SampleConfig, rng: Optional[jax.Array] = None,
                  compute_dtype=jnp.float32, lora=None,
                  lora_impl: str = "auto"):
    """Generate [B, max_new_tokens] ids from LEFT-padded prompts [B, P].

    One jittable program: full-forward prefill (collect_kv) + scanned
    single-token decode over a [L, B, H, P+N, D] cache.

    lora: optional adapter pytree (lora/lora.py) applied DYNAMICALLY —
    prefill through the training forward's LoRA path, decode via
    per-layer maybe_lora at every adapter site. Serving many adapters
    without materializing merged weight copies; merge_gpt2 + lora=None
    remains the (slightly faster) single-adapter path.
    """
    B, P = input_ids.shape
    N = cfg.max_new_tokens
    T = P + N
    if T > config.n_positions:
        # learned absolute positions: an out-of-range wpe gather would
        # silently clamp to the last row and quietly degrade sampling.
        # Validated BEFORE the N<=0 early-out so an over-long prompt
        # errors regardless of how many tokens were requested.
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({N}) = {T} exceeds "
            f"n_positions={config.n_positions}")
    if N <= 0:
        # honor max_new_tokens=0 instead of silently emitting the prefill
        # sample (the decode scan below always appends the carried token)
        return jnp.zeros((B, 0), jnp.int32)
    E, H, D = config.n_embd, config.n_head, config.head_dim
    L = config.n_layer
    rng = jax.random.PRNGKey(0) if rng is None else rng
    params = jax.tree.map(jnp.asarray, params)

    x, (pk, pv) = gpt2.hidden_states(
        config, params, input_ids, attention_mask, lora=lora,
        compute_dtype=compute_dtype, collect_kv=True,
        lora_impl=lora_impl)
    lora_b = None if lora is None else lora.get("blocks")
    logits0 = x[:, -1] @ params["wte"].astype(compute_dtype).T  # [B, V]
    logits0 = _head_lora(logits0, x[:, -1], lora_b, lora_impl)

    pad_kv = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, 0), (0, N), (0, 0)))
    kc, vc = pad_kv(pk), pad_kv(pv)                  # [L, B, H, T, D]

    n_real = attention_mask.sum(-1).astype(jnp.int32)
    eps = config.layer_norm_epsilon
    wb = _cast_floats(params["blocks"], compute_dtype)
    apply_lora = AdapterSites(lora, lora_impl)

    def decode_step(carry, step_rng_t):
        tok, done, kc, vc = carry
        t, key = step_rng_t
        pos = n_real + t                                        # [B]
        x = params["wte"][tok].astype(compute_dtype) \
            + params["wpe"][pos].astype(compute_dtype)          # [B, E]
        valid = _col_valid(attention_mask, P, T, t)             # [B, T]

        def layer(inner, inp):
            # The [L, B, H, T, D] caches ride the inner CARRY and are
            # updated with one [1,B,H,1,D] dynamic-update-slice per layer.
            # The previous structure scanned them as xs and restacked them
            # as ys, which materialized a full-cache copy per decode step
            # (~460 us/step for GPT-2s B=8, measured — the single largest
            # decode cost). As carry leaves, the updates alias in place.
            x, kc, vc = inner
            bp, i = inp
            h = gpt2.layer_norm(x, bp["ln_1"]["g"], bp["ln_1"]["b"], eps)
            qkv = h @ bp["attn"]["qkv_w"] + bp["attn"]["qkv_b"]
            qkv = apply_lora(qkv, h, "attn_qkv", i)
            # split-QKV adapters hit their column range of the fused
            # c_attn output (models/gpt2.py _block, same site salts)
            for name, slot in GPT2_SPLIT_QKV_SLOTS.items():
                if name in apply_lora:
                    sl = (Ellipsis, slice(slot * E, (slot + 1) * E))
                    qkv = qkv.at[sl].set(apply_lora(qkv[sl], h, name, i))
            q, k, v = jnp.split(qkv, 3, axis=-1)
            hd = lambda z: z.reshape(B, H, D)
            q, k, v = hd(q), hd(k), hd(v)
            kc = jax.lax.dynamic_update_slice(
                kc, k[None, :, :, None, :].astype(kc.dtype),
                (i, 0, 0, P + t, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v[None, :, :, None, :].astype(vc.dtype),
                (i, 0, 0, P + t, 0))
            kc_l = jax.lax.dynamic_index_in_dim(kc, i, 0, keepdims=False)
            vc_l = jax.lax.dynamic_index_in_dim(vc, i, 0, keepdims=False)
            # keep the cache operands in their storage dtype and accumulate
            # in f32 (preferred_element_type): an explicit .astype(f32) on
            # the [B,H,T,D] cache slices materializes ~9 MB of converts
            # per layer per token — measured decode cost, not a numerics
            # win (softmax statistics stay f32 either way). (Tried and
            # rejected: broadcasting q to 8 query rows to force the MXU —
            # the extra consumer broke the cache DUS aliasing and brought
            # full-cache copies back, 1.35 -> 1.62 ms/token.)
            s = jnp.einsum("bhd,bhtd->bht", q, kc_l,
                           preferred_element_type=jnp.float32) / (D ** 0.5)
            s = jnp.where(valid[:, None, :], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bht,bhtd->bhd", p.astype(vc_l.dtype), vc_l,
                             preferred_element_type=jnp.float32)
            ctx = ctx.reshape(B, E).astype(compute_dtype)
            proj = ctx @ bp["attn"]["proj_w"] + bp["attn"]["proj_b"]
            proj = apply_lora(proj, ctx, "attn_proj", i)
            x = x + proj
            h2 = gpt2.layer_norm(x, bp["ln_2"]["g"], bp["ln_2"]["b"], eps)
            fc = h2 @ bp["mlp"]["fc_w"] + bp["mlp"]["fc_b"]
            fc = gpt2.gelu_new(apply_lora(fc, h2, "mlp_fc_in", i))
            out = fc @ bp["mlp"]["proj_w"] + bp["mlp"]["proj_b"]
            out = apply_lora(out, fc, "mlp_fc_out", i)
            return (x + out, kc, vc), None

        (x, kc, vc), _ = jax.lax.scan(
            layer, (x, kc, vc), (wb, jnp.arange(L, dtype=jnp.int32)))
        x = gpt2.layer_norm(x, params["ln_f"]["g"].astype(compute_dtype),
                            params["ln_f"]["b"].astype(compute_dtype), eps)
        logits = x @ params["wte"].astype(compute_dtype).T
        logits = _head_lora(logits, x, lora_b, lora_impl)
        nxt_raw = _sample(logits.astype(jnp.float32), key, cfg)
        nxt, done = _advance(nxt_raw, done, cfg)
        return (nxt, done, kc, vc), tok

    return _decode_all(decode_step, logits0, kc, vc, rng, cfg)


# ---------------------------------------------------------- Gemma-3 ---------

def _gemma_chunked_prefill(c, params, wb, input_ids, attention_mask,
                           apply_lora, T, compute_dtype, W, apply_rope_fn):
    """Windowed prefill for LONG prompts: process the prompt in W-token
    windows, each window's attention reading the K/V cache of everything
    before it plus itself — peak score memory is O(W·P) instead of the
    whole-forward's O(P^2) blocks, and windows compile per static prefix
    length (the window loop is a Python loop over static offsets).
    Returns (last_hidden [B, E], kc, vc [L, B, Hkv, T, D]).

    The math per window is the training block's (sandwich norms, GQA,
    q/k RMSNorm, dual-theta RoPE, sliding window over POSITION ids)
    vectorized the decode way: scores against the cache with explicit
    validity masks, so left padding and window boundaries cannot shift
    phases. Gemma-only: GPT-2's 1024 learned positions make long prompts
    impossible before memory does.

    This is deliberately the THIRD spelling of the Gemma block (after
    gemma3._block and decode_step's layer) rather than a shared
    windowed-layer function: the decode copy's buffer structure is
    perf-fragile (an extra consumer of the cache broke its in-place DUS
    aliasing once already — DESIGN.md §10), and each copy is pinned by
    an exact-parity CI oracle (training ≡ HF, decode ≡ no-cache rollout,
    chunked ≡ whole-prompt), so a site change that misses one copy fails
    tests instead of shipping."""
    B, P = input_ids.shape
    nq, nkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    G = nq // nkv
    eps = c.rms_norm_eps
    scale = c.query_pre_attn_scalar ** -0.5
    L = c.num_hidden_layers
    is_global = jnp.asarray([c.is_global_layer(i) for i in range(L)])
    normalizer = jnp.asarray(c.hidden_size ** 0.5, compute_dtype)
    col_pos = _col_positions(attention_mask, P, T)              # [B, T]
    prompt_ok = attention_mask.astype(bool)                     # [B, P]

    kc = jnp.zeros((L, B, nkv, T, D), compute_dtype)
    vc = jnp.zeros((L, B, nkv, T, D), compute_dtype)

    x_last = None
    for w0 in range(0, P, W):
        ids_w = input_ids[:, w0:w0 + W]
        pos_w = col_pos[:, w0:w0 + W]                           # [B, W]
        x = params["embed"][ids_w].astype(compute_dtype) * normalizer
        cos_g, sin_g = rope_cos_sin(pos_w, D, c.rope_theta)
        cos_l, sin_l = rope_cos_sin(pos_w, D, c.rope_local_base_freq)
        hi = w0 + W                          # static prefix length
        # [B, W, hi]: prompt-mask valid AND causal vs the global column
        cols = jnp.arange(hi)
        causal = cols[None, None, :] <= (w0 + jnp.arange(W))[None, :, None]
        valid = prompt_ok[:, None, :hi] & causal
        win = (pos_w[:, :, None] - col_pos[:, None, :hi]) < c.sliding_window

        def layer(inner, inp):
            x, kc, vc = inner
            bp, glob, i = inp
            a = bp["attn"]
            h = gemma3.rms_norm(x, bp["input_ln"], eps)
            q = apply_lora(h @ a["q_w"], h, "q_proj", i) \
                .reshape(B, W, nq, D)
            k = apply_lora(h @ a["k_w"], h, "k_proj", i) \
                .reshape(B, W, nkv, D)
            v = apply_lora(h @ a["v_w"], h, "v_proj", i) \
                .reshape(B, W, nkv, D)
            q = gemma3.rms_norm(q, a["q_norm"], eps)
            k = gemma3.rms_norm(k, a["k_norm"], eps)
            cos = jnp.where(glob, cos_g, cos_l)
            sin = jnp.where(glob, sin_g, sin_l)
            # apply_rope expects [B, H, S, D]; v joins the cache layout
            q = apply_rope_fn(q.transpose(0, 2, 1, 3), cos, sin)
            k = apply_rope_fn(k.transpose(0, 2, 1, 3), cos, sin)
            v = v.transpose(0, 2, 1, 3)              # [B, nkv, W, D]
            kc = jax.lax.dynamic_update_slice(
                kc, k[None].astype(kc.dtype), (i, 0, 0, w0, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v[None].astype(vc.dtype), (i, 0, 0, w0, 0))
            kc_l = jax.lax.dynamic_index_in_dim(kc, i, 0, keepdims=False)
            vc_l = jax.lax.dynamic_index_in_dim(vc, i, 0, keepdims=False)
            k_pre = kc_l[:, :, :hi]          # static slice: grown prefix
            v_pre = vc_l[:, :, :hi]
            qg = q.reshape(B, nkv, G, W, D)
            s = jnp.einsum("bkgwd,bktd->bkgwt", qg, k_pre,
                           preferred_element_type=jnp.float32) * scale
            ok = jnp.where(glob, valid, valid & win)            # [B,W,hi]
            s = jnp.where(ok[:, None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bkgwt,bktd->bkgwd", p.astype(v_pre.dtype),
                             v_pre, preferred_element_type=jnp.float32)
            ctx = ctx.reshape(B, nq, W, D).transpose(0, 2, 1, 3) \
                .reshape(B, W, nq * D).astype(compute_dtype)
            attn_out = apply_lora(ctx @ a["o_w"], ctx, "o_proj", i)
            attn_out = gemma3.rms_norm(attn_out, bp["post_attn_ln"], eps)
            x = x + attn_out
            h2 = gemma3.rms_norm(x, bp["pre_ffn_ln"], eps)
            act = gemma3.gelu_tanh(
                apply_lora(h2 @ bp["mlp"]["gate_w"], h2, "gate_proj", i)) \
                * apply_lora(h2 @ bp["mlp"]["up_w"], h2, "up_proj", i)
            down = apply_lora(act @ bp["mlp"]["down_w"], act,
                              "down_proj", i)
            down = gemma3.rms_norm(down, bp["post_ffn_ln"], eps)
            return (x + down, kc, vc), None

        (x, kc, vc), _ = jax.lax.scan(
            layer, (x, kc, vc),
            (wb, is_global, jnp.arange(L, dtype=jnp.int32)))
        x_last = x
    x_last = gemma3.rms_norm(
        x_last, params["final_norm"].astype(compute_dtype), eps)
    return x_last[:, -1], kc, vc


def gemma3_generate(config: Gemma3TextConfig, params, input_ids,
                    attention_mask, cfg: SampleConfig,
                    rng: Optional[jax.Array] = None,
                    compute_dtype=jnp.float32, lora=None,
                    prefill_chunk: Optional[int] = None,
                    lora_impl: str = "auto"):
    """Gemma-3 generation: GQA cache [L, B, Hkv, T, D], per-layer
    global/local RoPE + sliding-window validity over POSITION ids.
    lora: optional adapter pytree applied dynamically (see
    gpt2_generate). prefill_chunk: process prompts longer than this in
    W-sized windows against the growing cache (_gemma_chunked_prefill)
    instead of one whole-prompt forward — bounds prefill score memory
    for long prompts."""
    c = config
    B, P = input_ids.shape
    N = cfg.max_new_tokens
    if N <= 0:
        # honor max_new_tokens=0 (see gpt2_generate)
        return jnp.zeros((B, 0), jnp.int32)
    nq, nkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    G = nq // nkv
    rng = jax.random.PRNGKey(0) if rng is None else rng
    params = jax.tree.map(jnp.asarray, params)
    lora_b = None if lora is None else lora.get("blocks")

    if prefill_chunk is not None and prefill_chunk < 1:
        raise ValueError(
            f"prefill_chunk must be >= 1, got {prefill_chunk}")
    chunked = prefill_chunk is not None and P > prefill_chunk
    if chunked:
        # pad the prompt on the LEFT to a window multiple (extra pads are
        # masked out; positions are mask-derived, so phases don't move)
        W = int(prefill_chunk)
        pad_n = (-P) % W
        if pad_n:
            input_ids = jnp.pad(input_ids, ((0, 0), (pad_n, 0)),
                                constant_values=cfg.pad_id)
            attention_mask = jnp.pad(attention_mask, ((0, 0), (pad_n, 0)))
            P += pad_n
    T = P + N

    wb_pre = _cast_floats(params["blocks"], compute_dtype)
    apply_lora = AdapterSites(lora, lora_impl)

    if chunked:
        x_last, kc, vc = _gemma_chunked_prefill(
            c, params, wb_pre, input_ids, attention_mask, apply_lora, T,
            compute_dtype, W, apply_rope)
        logits0 = x_last @ params["embed"].astype(compute_dtype).T
        logits0 = _head_lora(logits0, x_last, lora_b, lora_impl)
    else:
        x, (pk, pv) = gemma3.hidden_states(
            c, params, input_ids, attention_mask, lora=lora,
            compute_dtype=compute_dtype, collect_kv=True,
            lora_impl=lora_impl)
        logits0 = x[:, -1] @ params["embed"].astype(compute_dtype).T
        logits0 = _head_lora(logits0, x[:, -1], lora_b, lora_impl)
        pad_kv = lambda t: jnp.pad(
            t, ((0, 0), (0, 0), (0, 0), (0, N), (0, 0)))
        kc, vc = pad_kv(pk), pad_kv(pv)

    n_real = attention_mask.sum(-1).astype(jnp.int32)
    col_pos = _col_positions(attention_mask, P, T)              # [B, T]
    is_global = jnp.asarray([c.is_global_layer(i)
                             for i in range(c.num_hidden_layers)])
    eps = c.rms_norm_eps
    scale = c.query_pre_attn_scalar ** -0.5
    wb = wb_pre
    normalizer = jnp.asarray(c.hidden_size ** 0.5, compute_dtype)

    def decode_step(carry, step_rng_t):
        tok, done, kc, vc = carry
        t, key = step_rng_t
        pos = n_real + t                                        # [B]
        x = params["embed"][tok].astype(compute_dtype) * normalizer
        cos_g, sin_g = rope_cos_sin(pos[:, None], D, c.rope_theta)
        cos_l, sin_l = rope_cos_sin(pos[:, None], D, c.rope_local_base_freq)
        valid = _col_valid(attention_mask, P, T, t)             # [B, T]
        # sliding-window validity uses POSITION ids (mask-derived), same
        # phases as the padded-batch training forward
        win_ok = (pos[:, None] - col_pos) < c.sliding_window    # [B, T]

        def layer(inner, inp):
            # caches ride the inner CARRY (one [1,B,Hkv,1,D] DUS per
            # layer); scanning them as xs/ys restacked the full cache
            # every decode step — see the GPT-2 decode note above
            x, kc, vc = inner
            bp, glob, i = inp
            a = bp["attn"]
            h = gemma3.rms_norm(x, bp["input_ln"], eps)
            q = apply_lora(h @ a["q_w"], h, "q_proj", i).reshape(B, nq, D)
            k = apply_lora(h @ a["k_w"], h, "k_proj", i).reshape(B, nkv, D)
            v = apply_lora(h @ a["v_w"], h, "v_proj", i).reshape(B, nkv, D)
            q = gemma3.rms_norm(q, a["q_norm"], eps)
            k = gemma3.rms_norm(k, a["k_norm"], eps)
            cos = jnp.where(glob, cos_g, cos_l)
            sin = jnp.where(glob, sin_g, sin_l)
            # apply_rope expects [..., S, D]; insert S=1
            q = apply_rope(q[:, :, None, :], cos, sin)[:, :, 0]
            k = apply_rope(k[:, :, None, :], cos, sin)[:, :, 0]
            kc = jax.lax.dynamic_update_slice(
                kc, k[None, :, :, None, :].astype(kc.dtype),
                (i, 0, 0, P + t, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v[None, :, :, None, :].astype(vc.dtype),
                (i, 0, 0, P + t, 0))
            kc_l = jax.lax.dynamic_index_in_dim(kc, i, 0, keepdims=False)
            vc_l = jax.lax.dynamic_index_in_dim(vc, i, 0, keepdims=False)
            qg = q.reshape(B, nkv, G, D)
            # storage-dtype operands + f32 accumulation (see GPT-2 note)
            s = jnp.einsum("bkgd,bktd->bkgt", qg, kc_l,
                           preferred_element_type=jnp.float32) * scale
            ok = jnp.where(glob, valid, valid & win_ok)         # [B, T]
            s = jnp.where(ok[:, None, None, :], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bkgt,bktd->bkgd", p.astype(vc_l.dtype), vc_l,
                             preferred_element_type=jnp.float32)
            ctx = ctx.reshape(B, nq * D).astype(compute_dtype)
            attn_out = apply_lora(ctx @ a["o_w"], ctx, "o_proj", i)
            attn_out = gemma3.rms_norm(attn_out, bp["post_attn_ln"], eps)
            x = x + attn_out
            h2 = gemma3.rms_norm(x, bp["pre_ffn_ln"], eps)
            act = gemma3.gelu_tanh(
                apply_lora(h2 @ bp["mlp"]["gate_w"], h2, "gate_proj", i)) \
                * apply_lora(h2 @ bp["mlp"]["up_w"], h2, "up_proj", i)
            down = apply_lora(act @ bp["mlp"]["down_w"], act,
                              "down_proj", i)
            down = gemma3.rms_norm(down, bp["post_ffn_ln"], eps)
            return (x + down, kc, vc), None

        (x, kc, vc), _ = jax.lax.scan(
            layer, (x, kc, vc),
            (wb, is_global,
             jnp.arange(c.num_hidden_layers, dtype=jnp.int32)))
        x = gemma3.rms_norm(x, params["final_norm"].astype(compute_dtype),
                            eps)
        logits = x @ params["embed"].astype(compute_dtype).T
        logits = _head_lora(logits, x, lora_b, lora_impl)
        nxt_raw = _sample(logits.astype(jnp.float32), key, cfg)
        nxt, done = _advance(nxt_raw, done, cfg)
        return (nxt, done, kc, vc), tok

    return _decode_all(decode_step, logits0, kc, vc, rng, cfg)


# the contiguous-cache program of each family, by Family.name
GENERATE = {GPT2.name: gpt2_generate, GEMMA.name: gemma3_generate}


# ------------------------------------------------- serving entry points -----
#
# The serving subsystem (serve/engine.py, DESIGN.md §16) decomposes the
# one-shot generate() programs above into trace-stable pieces it can
# drive per request / per step. Each exists ONCE, a skeleton over the
# family record (models/family.py), which supplies the embedding, q/k/v,
# the per-layer mask, the block's second half and the final norm:
#
#   prefill              one full-sequence forward for ONE admitted
#                        request (right-padded to the engine's static
#                        prompt length), returning the next-token logits
#                        at the last REAL position plus every layer's
#                        K/V for the host to scatter into pool blocks;
#   prefill_chunk        one fixed-width slice of a long prompt against
#                        the pages earlier slices already wrote;
#   decode_step_paged    one token step for ALL slots against the shared
#                        block pool (its layout is serve/paged_kv.py's
#                        contract; these programs only go through that
#                        module's writers and readers): write the fed
#                        token's K/V at (tbl[s, pos//bT], pos%bT), read
#                        each slot's pages through its block table
#                        (ops/decode_attention.paged_attention — the
#                        Pallas paged kernel is the TPU fast path), and
#                        return the next-token logits.
#
# Serve sequences start at position 0 with no padding inside (the engine
# right-pads only the prompt TAIL), so validity is simply col <= pos —
# none of the left-padded mask algebra above applies. The contiguous
# generate() programs above do NOT share this layer math: they are the
# oracle the paged programs are tested against (tests/test_serve.py).


def _cast_blocks(params, compute_dtype):
    """(params as arrays, the block weights in the compute dtype)."""
    params = jax.tree.map(jnp.asarray, params)
    with jax.named_scope("decode_weight_cast"):
        return params, _cast_floats(params["blocks"], compute_dtype)


def _tied_head(fam: Family, params, x, sites: AdapterSites, compute_dtype):
    """f32 logits of the (final-normed) rows x through the tied head."""
    logits = x @ params[fam.head_key].astype(compute_dtype).T
    logits = _head_lora(logits, x, sites.blocks, sites.impl)
    return logits.astype(jnp.float32)


def prefill(fam: Family, config, params, input_ids, attention_mask,
            compute_dtype=jnp.float32, lora=None,
            lora_impl: str = "auto", shardings=None):
    """Prefill for serving: [B, P] right-padded prompts -> (next-token
    logits [B, V] f32 at each row's last real position, (k, v) per-layer
    caches [L, B, KV, P, D]). shardings: a serve/sharding.ServeSharding
    under the (dp, tp) serve mesh — the prefill matmuls TP-partition by
    propagation from the column/row-sharded weight placement; the only
    explicit pin is the collected caches' KV-head axis, so the engine's
    prompt-page scatter receives pool-aligned K/V."""
    params = jax.tree.map(jnp.asarray, params)
    x, (pk, pv) = fam.model.hidden_states(
        config, params, input_ids, attention_mask, lora=lora,
        compute_dtype=compute_dtype, collect_kv=True,
        lora_impl=lora_impl)
    if shardings is not None:
        pk = shardings.prefill_cache(pk)
        pv = shardings.prefill_cache(pv)
    n_real = attention_mask.sum(-1).astype(jnp.int32)
    last = x[jnp.arange(x.shape[0]), n_real - 1]          # [B, E]
    return _tied_head(fam, params, last, AdapterSites(lora, lora_impl),
                      compute_dtype), (pk, pv)


def prefill_chunk(fam: Family, config, params, pool_k, pool_v, ids, start,
                  n_tok, tbl, lora=None, compute_dtype=jnp.float32,
                  lora_impl: str = "auto", shardings=None):
    """One fixed-width prefill CHUNK against the block pool (round 21):
    W prompt tokens starting at absolute position `start`, attending
    the pages earlier chunks (or the prefix cache) already wrote.

    ids [1, W] the chunk's tokens (pad-padded past n_tok); start 0-d
    i32 (block_T-aligned chunk origin); n_tok 0-d i32 real tokens in
    the chunk (1..W); tbl [1, M] the request's block table (garbage
    regions -> trash block 0). Returns (logits [1, V] f32 at the
    chunk's last real row, pool_k, pool_v) with the chunk's K/V
    scattered in at (tbl[0, (start+w)//bT], (start+w)%bT) — padded
    rows land in the trash page.

    W is one of the engine's STATIC chunk buckets, and start/n_tok ride
    as 0-d device scalars, so the whole bucket set costs one trace per
    width — never one per prompt length. Row w's causal span is the
    union of the already-written prefix (pool columns < start) and the
    chunk's own rows j <= w, so attention splits into a read-only page
    gather plus a dense within-chunk part under ONE joint softmax —
    token-identical to one-shot prefill; a windowed layer's mask
    (Family.layer_ok) applies to both parts. The pools are NOT layer-
    scan carries: the scan threads only the hidden state, stacks each
    layer's chunk K/V as scan outputs, and a single post-scan scatter
    lands all L layers' rows at once. That keeps the chunk program's
    cost proportional to the chunk width, not the pool size (pool-
    sized carries made every dispatch pay pool-copy traffic on
    backends without donation). XLA partitions both attention parts
    under `shardings` like any dense op (a chunk-shaped Pallas kernel
    is future work, gated behind the same benched decision)."""
    from mobilefinetuner_tpu.ops.decode_attention import NEG_INF
    from mobilefinetuner_tpu.serve.paged_kv import (TRASH_BLOCK,
                                                    gather_pages,
                                                    write_chunk_rows)
    W = ids.shape[1]
    M = tbl.shape[1]
    L, bT = pool_k.shape[1:3]
    d = fam.dims(config)
    KV, G = d.KV, d.nq // d.KV
    params, wb = _cast_blocks(params, compute_dtype)
    sites = AdapterSites(lora, lora_impl)
    shd = shardings

    rows = jnp.arange(W, dtype=jnp.int32)
    pos = start + rows                                        # [W]
    real = rows < n_tok
    # padded rows clip a learned-position lookup (their K/V goes to
    # trash and their logits row is never read)
    x = fam.embed(config, params, ids[0],
                  pos if d.cap is None else jnp.minimum(pos, d.cap - 1),
                  compute_dtype)
    if shd is not None:
        x = shd.slots(x)
    ctx = fam.pos_ctx(config, pos)
    cols = jnp.arange(M * bT, dtype=jnp.int32)
    # every chunk row shares the prefix span (pool columns < start);
    # columns >= start are this chunk's own rows, attended densely
    pre_ok = cols[None, :] < start                            # [1, M*bT]
    causal = rows[:, None] >= rows[None, :]                   # [W, W]
    win_pre = win_in = None
    if d.window is not None:
        win_pre = (pos[:, None] - cols[None, :]) < d.window
        win_in = (rows[:, None] - rows[None, :]) < d.window
    blk = jnp.where(real, tbl[0, pos // bT],
                    jnp.int32(TRASH_BLOCK))                   # [W]
    off = pos % bT

    def layer(x, inp):
        bp, layer_x, i = inp
        with jax.named_scope("decode_proj_mlp"):
            q, k, v = fam.qkv(config, x, bp, i, layer_x, ctx, sites, shd)
            # pool-dtype roundtrip: within-chunk attention must read the
            # same values the pages will hold, or chunked-vs-one-shot
            # token parity drifts at low pool precision
            kq = k.astype(pool_k.dtype)
            vq = v.astype(pool_v.dtype)
        with jax.named_scope("decode_paged_attn"):
            # joint softmax over [prefix pages | chunk rows]: the pools
            # are closed over READ-ONLY here (gather, never scatter), so
            # they are not scan carries — dtype discipline mirrors
            # ops.decode_attention.paged_attention
            ok1 = fam.layer_ok(layer_x, pre_ok, win_pre)
            ok2 = fam.layer_ok(layer_x, causal, win_in)       # [W, W]
            kc = gather_pages(pool_k, tbl[0], i, KV)  # [M, bT, KV, D]
            vc = gather_pages(pool_v, tbl[0], i, KV)
            s1 = jnp.einsum("wkgd,mtkd->wkgmt", q, kc,
                            preferred_element_type=jnp.float32) * d.scale
            s1 = jnp.where(ok1[:, None, None, :],
                           s1.reshape(W, KV, G, M * bT), NEG_INF)
            s2 = jnp.einsum("wkgd,jkd->wkgj", q, kq,
                            preferred_element_type=jnp.float32) * d.scale
            s2 = jnp.where(ok2[:, None, None, :], s2, NEG_INF)
            p = jax.nn.softmax(jnp.concatenate([s1, s2], -1), axis=-1)
            attn = jnp.einsum("wkgmt,mtkd->wkgd",
                              p[..., :M * bT].reshape(W, KV, G, M, bT)
                              .astype(vc.dtype), vc,
                              preferred_element_type=jnp.float32) \
                + jnp.einsum("wkgj,jkd->wkgd",
                             p[..., M * bT:].astype(vq.dtype), vq,
                             preferred_element_type=jnp.float32)
            if shd is not None:
                attn = shd.heads4(attn)
        with jax.named_scope("decode_proj_mlp"):
            x = fam.post(config, x,
                         attn.reshape(W, d.nq * d.D).astype(compute_dtype),
                         bp, i, sites, shd)
        return x, (kq, vq)

    x, (ks, vs) = jax.lax.scan(
        layer, x, (wb, fam.layer_xs(config),
                   jnp.arange(L, dtype=jnp.int32)))
    # one write for all L layers' chunk rows (padded rows -> trash)
    with jax.named_scope("decode_kv_write"):
        pool_k = write_chunk_rows(pool_k, blk, off, ks)
        pool_v = write_chunk_rows(pool_v, blk, off, vs)
    with jax.named_scope("decode_head"):
        x = fam.final_norm(config, params, x, compute_dtype)
        last = jax.lax.dynamic_index_in_dim(x, n_tok - 1, 0,
                                            keepdims=True)    # [1, E]
        logits = _tied_head(fam, params, last, sites, compute_dtype)
    return logits, pool_k, pool_v


def _paged_attend(fam: Family, d, attn_impl: str, pool_k, M: int, ok,
                  win_ok, shardings):
    """The decode step's attend(q, pool_k, pool_v, tbl, layer, layer_x)
    -> ctx [S, KV, G, D] f32: the Pallas paged kernel (under shard_map
    on a serve mesh) or the gather oracle, by
    ops/decode_attention.resolve_paged_impl. Called ONCE a step, before
    the layer scan: for the kernel it makes the step's plan here — the
    mask cut into the kernel's chunks and each slot's live page range,
    the same for every layer, or one of two where the family has
    windowed layers (then the plan holds both and a layer picks, as it
    picks its mask) — so the scan's body holds the kernel and nothing
    that prepares its operands. The oracle takes the layer's boolean
    mask and knows no range."""
    from mobilefinetuner_tpu.ops.decode_attention import (
        paged_attention, paged_decode_attention, paged_plan, pick_pages,
        resolve_paged_impl, sharded_paged_attend)
    KV, G, bT = d.KV, d.nq // d.KV, pool_k.shape[2]
    itemsize = pool_k.dtype.itemsize
    tp = 1 if shardings is None else shardings.tp
    if resolve_paged_impl(attn_impl, KV, G, bT, d.D, itemsize,
                          tp=tp) != "pallas":
        return lambda q, pk, pv, tbl, i, layer_x: paged_attention(
            q, pk, pv, tbl, i, fam.layer_ok(layer_x, ok, win_ok), d.scale)
    kernel = paged_decode_attention if shardings is None \
        else sharded_paged_attend(shardings)
    with jax.named_scope("decode_paged_attn"):
        # (the barrier: left alone, XLA sinks the cheap making of the
        # plan's mask back into the scan's body, once a layer again)
        plan = jax.lax.optimization_barrier(paged_plan(
            jnp.stack([ok] if win_ok is None else [ok, ok & win_ok]),
            bT, pick_pages(KV, G, bT, d.D, itemsize, M, tp)))

    def attend(q, pk, pv, tbl, i, layer_x):
        # which of the plan's masks layer_ok would hand this layer: the
        # record's own answer, asked with one-element masks
        kind = 0 if win_ok is None else jnp.where(
            fam.layer_ok(layer_x, jnp.bool_(True), jnp.bool_(False)), 0, 1)
        return kernel(q, pk, pv, tbl, i, plan.of_kind(kind), d.scale)

    return attend


def decode_step_paged(fam: Family, config, params, pool_k, pool_v, tok,
                      pos, tbl, lora=None, compute_dtype=jnp.float32,
                      attn_impl: str = "auto", lora_impl: str = "auto",
                      shardings=None):
    """One continuous-batching decode step over a block-paged KV pool.

    pool_k/pool_v the block pools (serve/paged_kv.py's layout; KV heads
    to a page row); tok [S] the token each slot feeds; pos [S] its cache
    position (= tokens already cached); tbl [S, M] per-slot block tables
    (idle slots -> trash block 0). Returns (logits [S, V] f32, pool_k,
    pool_v) with the fed tokens' K/V written in at
    (tbl[s, pos//bT], pos%bT).

    attn_impl: "xla" = gather-based paged_attention (every backend),
    "pallas" = the scalar-prefetch paged kernel, "auto" = pallas on TPU
    when eligible. Both are parity-pinned to each other and to the
    contiguous generate() oracle.

    shardings: a serve/sharding.ServeSharding — the layer math is
    unchanged; the head/hidden axes get with_sharding_constraint pins
    (GSPMD inserts the collectives; check_compiled_contracts pins the
    census), the Pallas gate charges per-shard head counts, and the
    kernel path routes through sharded_paged_attend's shard_map. The
    GQA head placement follows shard_heads: the pool's KV axis shards
    when KV % tp == 0, otherwise the query-group axis does (pools
    replicated) — either way constraints pin the 4D [S, KV, G, D]
    layout."""
    from mobilefinetuner_tpu.serve.paged_kv import write_token_rows
    S, M = tbl.shape
    L, bT = pool_k.shape[1:3]
    d = fam.dims(config)
    params, wb = _cast_blocks(params, compute_dtype)
    sites = AdapterSites(lora, lora_impl)
    shd = shardings

    x = fam.embed(config, params, tok, pos, compute_dtype)    # [S, E]
    if shd is not None:
        x = shd.slots(x)
    ctx = fam.pos_ctx(config, pos)
    cols = jnp.arange(M * bT, dtype=jnp.int32)
    ok = cols[None, :] <= pos[:, None]                        # [S, M*bT]
    win_ok = None if d.window is None else \
        (pos[:, None] - cols[None, :]) < d.window
    blk = tbl[jnp.arange(S), pos // bT]                       # [S]
    off = pos % bT
    attend = _paged_attend(fam, d, attn_impl, pool_k, M, ok, win_ok, shd)

    def layer(inner, inp):
        x, pk, pv = inner
        bp, layer_x, i = inp
        with jax.named_scope("decode_proj_mlp"):
            q, k, v = fam.qkv(config, x, bp, i, layer_x, ctx, sites, shd)
        # write the fed token's K/V row into its slot's current page;
        # idle slots land in the reserved trash block (never attended)
        with jax.named_scope("decode_kv_write"):
            pk = write_token_rows(pk, blk, i, off, k)
            pv = write_token_rows(pv, blk, i, off, v)
        with jax.named_scope("decode_paged_attn"):
            attn = attend(q, pk, pv, tbl, i, layer_x)
            if shd is not None:
                attn = shd.heads4(attn)
        with jax.named_scope("decode_proj_mlp"):
            x = fam.post(config, x,
                         attn.reshape(S, d.nq * d.D).astype(compute_dtype),
                         bp, i, sites, shd)
        return (x, pk, pv), None

    (x, pool_k, pool_v), _ = jax.lax.scan(
        layer, (x, pool_k, pool_v),
        (wb, fam.layer_xs(config), jnp.arange(L, dtype=jnp.int32)))
    with jax.named_scope("decode_head"):
        x = fam.final_norm(config, params, x, compute_dtype)
        logits = _tied_head(fam, params, x, sites, compute_dtype)
    return logits, pool_k, pool_v


# the names callers outside this package import (benchmark/harness,
# chip_smoke.py, tools/, tests/): the single programs, bound to a family
gpt2_prefill = partial(prefill, GPT2)
gpt2_prefill_chunk = partial(prefill_chunk, GPT2)
gpt2_decode_step_paged = partial(decode_step_paged, GPT2)
gemma3_prefill = partial(prefill, GEMMA)
gemma3_prefill_chunk = partial(prefill_chunk, GEMMA)
gemma3_decode_step_paged = partial(decode_step_paged, GEMMA)


def left_pad(seqs, pad_id: int):
    """[[ids...], ...] -> (input_ids [B, P], attention_mask [B, P]) with
    LEFT padding (generation convention; cache writes share one column)."""
    import numpy as np
    P = max(len(s) for s in seqs)
    B = len(seqs)
    ids = np.full((B, P), pad_id, np.int32)
    mask = np.zeros((B, P), np.int32)
    for i, s in enumerate(seqs):
        if len(s):
            ids[i, P - len(s):] = s
            mask[i, P - len(s):] = 1
    return ids, mask
