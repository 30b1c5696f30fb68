"""Microsoft's Phi-4-mini-flash-reasoning (`model_type: phi4flash`), the
SambaY decoder-hybrid-decoder, pure-functional JAX: the sixth family on
the LoRA trainer. DESIGN.md §34 lists every equation with the config key
or the paper it is taken from, and what is assumed.

Pre-LayerNorm residual blocks, x += mixer(LN1(x)); x += MLP(LN2(x)),
with MLP(h) = (v . SiLU(g)) W_fc2 for [g | v] = h W_fc1; a final
LayerNorm and the head tied to the embedding. No positional encoding:
the Mamba layers carry position. Layer l of L (Phi4FlashConfig.
layer_kind):

  mamba  l even, l <= L/2 (Mamba, arXiv:2312.00752):
         [u | z] = h W_in; u = SiLU(conv4(u) + b_conv);
         [delta | B | C] = u W_x; Delta = softplus(delta W_dt + b_dt);
         y = the selective scan of u under Delta, A = -exp(A_log), B, C,
         plus D . u (ops/ssm.py, float32 state); out = (y . SiLU(z)) W_out.
         Layer L/2 also hands y on as the MEMORY m (before the z gate).
  swa    l odd, l < L/2: differential attention (arXiv:2410.05258) over a
         causal window of `sliding_window` positions.
  full   l = L/2 + 1: differential attention over the whole causal
         prefix; its keys and values are kept for the cross-decoder.
  gmu    l even, l > L/2 + 1 (gated memory unit, SambaY):
         out = (m . SiLU(h W_1)) W_2.
  cross  l odd, l > L/2 + 1: differential attention of its own queries
         over layer L/2 + 1's keys and values (no key or value
         projection of its own).

Differential attention: the query heads form pairs (2i, 2i + 1) ->
(q^1_i, q^2_i), the key heads likewise (k^1_j, k^2_j), and value heads
2j, 2j + 1 are joined into one value of twice the head width — GQA
with group nq / nkv; a^s = softmax(q^s k^sT / sqrt(d) + mask) v for
s = 1, 2 (two calls of ops/attention.attention, so the flash kernels at
d = 64 against a value of 128); lambda = exp(lq1 . lk1) - exp(lq2 . lk2)
+ lambda_init(l), lambda_init(l) = 0.8 - 0.6 exp(-0.3 l); out =
(1 - lambda_init) RMSNorm(a^1 - lambda a^2) per pair (eps DIFF_NORM_EPS,
a learned weight), pairs concatenated, W_o.

Layout: the self-decoder's (mamba, swa) pairs are stacked under
`params["self"]` and run under one lax.scan; the middle pair (L/2,
L/2 + 1) under `params["mid"]`, unrolled; the cross-decoder's (gmu,
cross) pairs stacked under `params["cross"]` under a second lax.scan,
which reads the memory and the kept keys and values as constants (their
gradients sum over every layer that reads them). Each PAIR under
jax.checkpoint with `remat` (a pair's input is what the forward keeps;
the backward recomputes the pair once, as it would each layer), the
Mamba mixers and the MLPs in pieces of `mixer_chunk` tokens inside it
(`by_pieces`). LoRA stacks (lora.PHI4FLASH_TARGETS) run
over the layers of the target's kind in layer order: `in_proj` /
`out_proj` over the 9 Mamba layers (the memory's last), `gmu_in_proj` /
`gmu_out_proj` over the GMUs, `k_proj` / `v_proj` over the attention
layers with keys of their own (the full layer's last), `q_proj` /
`o_proj` over every attention layer.

Device scopes: `embed`; `ssm_proj` (LN1, the in, x, dt and out
projections, the gate by z, the residual add), `ssm_conv`, `ssm_scan`;
`gmu` (the whole unit with its LN1 and residual); `diff_attn_proj` (LN1,
q/k/v/o, the head split and merge, the residual) and `diff_attn` (both
maps, the subtraction and the head norm); `mlp` (LN2 and the MLP); the
loss's own `loss`.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from mobilefinetuner_tpu.core.config import Phi4FlashConfig
from mobilefinetuner_tpu.models.afmoe import rms_norm
from mobilefinetuner_tpu.models.lora_apply import maybe_lora
from mobilefinetuner_tpu.ops import ssm
from mobilefinetuner_tpu.ops.attention import attention, resolve_impl

F32 = jnp.float32
DIFF_NORM_EPS = 1e-5


def lambda_init(layer):
    """Differential attention's lambda_init at layer index `layer` (a
    Python int or a traced scalar)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))


def stack_layer_ids(config: Phi4FlashConfig) -> Dict[str, list]:
    """{target: the layer index of each position of its stack}."""
    kinds = config.layer_kinds
    of = lambda *ks: [i for i, k in enumerate(kinds) if k in ks]
    attn, own = of("swa", "full", "cross"), of("swa", "full")
    return {"q_proj": attn, "o_proj": attn, "k_proj": own, "v_proj": own,
            "in_proj": of("mamba"), "out_proj": of("mamba"),
            "gmu_in_proj": of("gmu"), "gmu_out_proj": of("gmu")}


def stack_sizes(config: Phi4FlashConfig) -> Dict[str, int]:
    """Layers a LoRA target's stack runs over."""
    return {t: len(ids) for t, ids in stack_layer_ids(config).items()}


def init_params(config: Phi4FlashConfig, key: jax.Array, dtype=F32,
                lambda_std: float = 0.1) -> Dict[str, Any]:
    """Seeded weights: matrices N(0, 0.02) in `dtype`, LayerNorm weights
    1 + N(0, 0.02) and biases N(0, 0.02), the head norms' weights
    1 + N(0, 0.02), the lambda vectors N(0, lambda_std), all float32;
    Mamba's own initialisation for the scan's parameters (float32): A_log
    = log(1 ... N) on every channel, b_dt = softplus^-1(Delta_0) with
    Delta_0 log-uniform in [1e-3, 1e-1], D = 1, W_dt U(+-r^-1/2), the
    conv's taps and bias U(+-1/2) (PyTorch's default at a fan-in of 4).
    With them the random model's scan decays as a trained one's does."""
    c = config
    H, I, di, N = c.hidden_size, c.intermediate_size, c.ssm_inner, \
        c.mamba_d_state
    r, K, hd = c.mamba_dt_rank, c.mamba_d_conv, c.head_dim
    nq, nkv = c.num_attention_heads, c.num_key_value_heads
    keys = iter(jax.random.split(key, 128))

    def mat(*shape, std=0.02):
        return (jax.random.normal(next(keys), shape) * std).astype(dtype)

    def f32(*shape, std):
        return jax.random.normal(next(keys), shape) * std

    def unif(*shape, bound):
        return jax.random.uniform(next(keys), shape, F32, -bound, bound)

    def block(n, mixer):
        return {"ln1_w": 1.0 + f32(n, H, std=0.02), "ln1_b": f32(n, H,
                                                                 std=0.02),
                "ln2_w": 1.0 + f32(n, H, std=0.02), "ln2_b": f32(n, H,
                                                                 std=0.02),
                "mlp": {"fc1_w": mat(n, H, 2 * I), "fc2_w": mat(n, I, H)},
                "mixer": mixer}

    def mamba(n):
        dt0 = jnp.exp(jax.random.uniform(next(keys), (n, di), F32,
                                         math.log(1e-3), math.log(1e-1)))
        return block(n, {
            "in_w": mat(n, H, 2 * di), "conv_w": unif(n, K, di, bound=0.5),
            "conv_b": unif(n, di, bound=0.5), "x_w": mat(n, di, r + 2 * N),
            "dt_w": unif(n, r, di, bound=r ** -0.5),
            "dt_b": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1,
                                                         dtype=F32)),
                                      (n, di, N)),
            "D": jnp.ones((n, di), F32), "out_w": mat(n, di, H)})

    def attn(n, own_kv: bool):
        m = {"q_w": mat(n, H, nq * hd), "o_w": mat(n, nq * hd, H),
             **{name: f32(n, hd, std=lambda_std)
                for name in ("lq1", "lk1", "lq2", "lk2")},
             "subln": 1.0 + f32(n, 2 * hd, std=0.02)}
        if own_kv:
            m.update(k_w=mat(n, H, nkv * hd), v_w=mat(n, H, nkv * hd))
        return block(n, m)

    def gmu(n):
        return block(n, {"in_w": mat(n, H, di), "out_w": mat(n, di, H)})

    P = c.num_pairs
    one = lambda t: jax.tree.map(lambda a: a[0], t)
    return {"embed": mat(c.vocab_size, H),
            "self": {"mamba": mamba(P), "attn": attn(P, True)},
            "mid": {"mamba": one(mamba(1)), "attn": one(attn(1, True))},
            "cross": {"gmu": gmu(P - 1), "attn": attn(P - 1, False)},
            "final_ln_w": 1.0 + f32(H, std=0.02),
            "final_ln_b": f32(H, std=0.02)}


def layer_norm(x, w, b, eps):
    """LayerNorm over the last axis, float32 math, x's dtype out."""
    xf = x.astype(F32)
    mu = xf.mean(-1, keepdims=True)
    var = jnp.square(xf - mu).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32)
            + b.astype(F32)).astype(x.dtype)


def _pairs(x, n):
    """[B, S, n*d] -> the two halves of each head pair (2i, 2i + 1), each
    [B, n/2, S, d]."""
    B, S, _ = x.shape
    x = x.reshape(B, S, n // 2, 2, -1).transpose(3, 0, 2, 1, 4)
    return x[0], x[1]


def by_pieces(fn, carry, x, T: int):
    """fn(carry, x_piece, i) -> (carry, y_piece) over the sequence axis
    of x [B, S, ...] in pieces of T tokens: a jax.checkpoint'ed lax.scan
    over the whole pieces, then the remainder; the pieces' y (a tree)
    concatenated. Under the pair's remat the backward then holds one
    piece's intermediates at a time (and recomputes them), not the whole
    sequence's. T <= 0 or S <= T: one piece."""
    B, S = x.shape[:2]
    n = S // T if T > 0 else 0
    if n <= 1:
        return fn(carry, x, 0)
    whole = jnp.swapaxes(x[:, :n * T].reshape(B, n, T, *x.shape[2:]), 0, 1)
    carry, ys = jax.lax.scan(jax.checkpoint(lambda c, a: fn(c, *a)), carry,
                             (whole, jnp.arange(n)))
    ys = jax.tree.map(lambda y: jnp.swapaxes(y, 0, 1).reshape(
        B, n * T, *y.shape[3:]), ys)
    if n * T == S:
        return carry, ys
    carry, rest = fn(carry, x[:, n * T:], n)
    return carry, jax.tree.map(lambda a, b: jnp.concatenate([a, b], 1), ys,
                               rest)


def _mamba(c, p, h, lora_at, dt):
    """(out [B, S, H], y [B, S, d_in] in dt: the memory, |h| max), in
    pieces of `mixer_chunk` tokens: the conv's last K - 1 inputs and the
    scan's state carried from piece to piece. `lora_at(i)` is the lora
    function of piece i."""
    B = h.shape[0]
    di, N, r, K = c.ssm_inner, c.mamba_d_state, c.mamba_dt_rank, \
        c.mamba_d_conv

    def piece(carry, hc, i):
        tail, state, amax = carry
        lora = lora_at(i)
        with jax.named_scope("ssm_proj"):
            xz = lora(hc @ p["in_w"].astype(dt), hc, "in_proj")
            u, z = xz[..., :di], xz[..., di:]
        with jax.named_scope("ssm_conv"):
            ext = jnp.concatenate([tail, u], axis=1)
            u = ssm.conv_silu(ext, p["conv_w"], p["conv_b"])[:, K - 1:]
            tail = ext[:, ext.shape[1] - (K - 1):]
        with jax.named_scope("ssm_proj"):
            xdbl = jnp.dot(u, p["x_w"].astype(dt),
                           preferred_element_type=F32)
            delta = jax.nn.softplus(
                jnp.dot(xdbl[..., :r].astype(dt), p["dt_w"].astype(dt),
                        preferred_element_type=F32)
                + p["dt_b"].astype(F32))
            Bm, Cm = xdbl[..., r:r + N], xdbl[..., r + N:]
        with jax.named_scope("ssm_scan"):
            y, state, a = ssm.selective_scan(u, delta, p["A_log"], Bm, Cm,
                                             p["D"], chunk=c.ssm_chunk,
                                             h0=state)
            y = y.astype(dt)
        with jax.named_scope("ssm_proj"):
            g = y * jax.nn.silu(z)
            out = lora(g @ p["out_w"].astype(dt), g, "out_proj")
        return (tail, state, jnp.maximum(amax, a)), (out, y)

    carry = (jnp.zeros((B, K - 1, di), dt), jnp.zeros((B, N, di), F32),
             jnp.zeros((), F32))
    (_, _, absmax), (out, y) = by_pieces(piece, carry, h, c.mixer_chunk)
    return out, y, absmax


def _diff_attn(c, p, h, lora, dt, layer, kv, window, padding_mask):
    """(out [B, S, H], the keys and values it read: (k1, k2, v))."""
    B, S, _ = h.shape
    nq, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope("diff_attn_proj"):
        q1, q2 = _pairs(lora(h @ p["q_w"].astype(dt), h, "q_proj"), nq)
        if kv is None:
            k1, k2 = _pairs(lora(h @ p["k_w"].astype(dt), h, "k_proj"), nkv)
            v = lora(h @ p["v_w"].astype(dt), h, "v_proj").reshape(
                B, S, nkv // 2, 2 * hd).transpose(0, 2, 1, 3)
            kv = (k1, k2, v)
        k1, k2, v = kv
    with jax.named_scope("diff_attn"):
        impl = c.attention_impl
        if impl == "auto":
            impl = resolve_impl(S, hd)
        a1, a2 = (attention(qs, ks, v, impl=impl, is_causal=True,
                            sliding_window=window,
                            padding_mask=padding_mask, scale=hd ** -0.5)
                  for qs, ks in ((q1, k1), (q2, k2)))
        lam0 = lambda_init(layer)
        dot = lambda a, b: jnp.dot(a.astype(F32), b.astype(F32),
                                   preferred_element_type=F32)
        lam = (jnp.exp(dot(p["lq1"], p["lk1"]))
               - jnp.exp(dot(p["lq2"], p["lk2"])) + lam0)
        o = a1.astype(F32) - lam * a2.astype(F32)
        o = rms_norm(o, p["subln"], DIFF_NORM_EPS) * (1.0 - lam0)
        o = o.astype(dt)
    with jax.named_scope("diff_attn_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(B, S, nq * hd)
        return lora(o @ p["o_w"].astype(dt), o, "o_proj"), kv


def _gmu(p, h, m, lora, dt):
    g = jax.nn.silu(lora(h @ p["in_w"].astype(dt), h, "gmu_in_proj"))
    y = m * g
    return lora(y @ p["out_w"].astype(dt), y, "gmu_out_proj")


def _mlp(c, lp, x, dt):
    """x + MLP(LN2(x)), in pieces of `mixer_chunk` tokens."""
    I = c.intermediate_size

    def piece(_, xc, i):
        h = layer_norm(xc, lp["ln2_w"], lp["ln2_b"], c.layer_norm_eps)
        gv = h @ lp["mlp"]["fc1_w"].astype(dt)
        g, v = gv[..., :I], gv[..., I:]
        return None, xc + (v * jax.nn.silu(g)) @ lp["mlp"]["fc2_w"].astype(dt)

    with jax.named_scope("mlp"):
        return by_pieces(piece, None, x, c.mixer_chunk)[1]


def _lora_fn(lora_b, at: Dict[str, Any], lora_dropout, rng, lora_impl):
    """lora(y, x, target): maybe_lora with the target's stack read at
    position at[target] (a Python or traced int)."""
    sites = iter(range(16))

    def lora(y, x_in, name):
        site = next(sites)
        entry = None if lora_b is None else lora_b.get(name)
        return maybe_lora(y, x_in, entry, at.get(name), lora_dropout,
                          None if rng is None
                          else jax.random.fold_in(rng, site),
                          impl=lora_impl)
    return lora


def _lora_pieces(lora_b, at, lora_dropout, rng, lora_impl):
    """i -> the lora function of sequence piece i (its own dropout
    masks)."""
    return lambda i: _lora_fn(lora_b, at, lora_dropout,
                              None if rng is None
                              else jax.random.fold_in(rng, 1000 + i),
                              lora_impl)


def hidden_states(config: Phi4FlashConfig, params, input_ids,
                  attention_mask=None, lora=None, compute_dtype=F32,
                  remat: bool = False, lora_dropout: float = 0.0,
                  dropout_rng=None, lora_impl: str = "auto",
                  position_ids=None, with_aux: bool = False):
    """Final-LayerNorm hidden states [B, S, H]. with_aux: also {"counters":
    {"max": {"ssm_state_absmax": the largest |h| at a chunk boundary of
    any Mamba layer's scan}}} — a device scalar that rides the train
    step's metrics. `position_ids` is accepted for the family API and
    unused: nothing here reads positions."""
    c, dt = config, compute_dtype
    eps = c.layer_norm_eps
    P, mid = c.num_pairs, c.num_hidden_layers // 2
    lora_b = None if lora is None else lora.get("blocks")
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(dt)

    def rng_of(layer):
        return None if dropout_rng is None else jax.random.fold_in(
            dropout_rng, layer)

    def ckpt(fn):
        return jax.checkpoint(fn) if remat else fn

    def mamba_layer(x, lp, layer, at):
        lo = _lora_pieces(lora_b, {"in_proj": at, "out_proj": at},
                          lora_dropout, rng_of(layer), lora_impl)
        with jax.named_scope("ssm_proj"):
            h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], eps)
        out, y, absmax = _mamba(c, lp["mixer"], h, lo, dt)
        with jax.named_scope("ssm_proj"):
            x = x + out
        return _mlp(c, lp, x, dt), y, absmax

    def attn_layer(x, lp, layer, q_at, kv_at, kv, window):
        lo = _lora_fn(lora_b, {"q_proj": q_at, "o_proj": q_at,
                               "k_proj": kv_at, "v_proj": kv_at},
                      lora_dropout, rng_of(layer), lora_impl)
        with jax.named_scope("diff_attn_proj"):
            h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], eps)
        out, kv = _diff_attn(c, lp["mixer"], h, lo, dt, layer, kv, window,
                             attention_mask)
        with jax.named_scope("diff_attn_proj"):
            x = x + out
        return _mlp(c, lp, x, dt), kv

    def gmu_layer(x, lp, layer, at, m):
        lo = _lora_fn(lora_b, {"gmu_in_proj": at, "gmu_out_proj": at},
                      lora_dropout, rng_of(layer), lora_impl)
        with jax.named_scope("gmu"):
            h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], eps)
            x = x + _gmu(lp["mixer"], h, m, lo, dt)
        return _mlp(c, lp, x, dt)

    # ---- self-decoder: (Mamba, window attention) x P -------------------
    def self_pair(x, mp, ap, i):
        x, _, absmax = mamba_layer(x, mp, 2 * i, i)
        return attn_layer(x, ap, 2 * i + 1, i, i, None,
                          c.sliding_window)[0], absmax

    x, absmax_self = jax.lax.scan(
        lambda x, xs: ckpt(self_pair)(x, *xs), x,
        (params["self"]["mamba"], params["self"]["attn"], jnp.arange(P)))

    # ---- the middle pair: the memory and the shared keys and values ----
    def mid_pair(x, mp, ap):
        x, m, absmax = mamba_layer(x, mp, mid, P)
        x, kv = attn_layer(x, ap, mid + 1, P, P, None, None)
        return x, m, kv, absmax

    x, m, kv, absmax_mid = ckpt(mid_pair)(x, params["mid"]["mamba"],
                                          params["mid"]["attn"])

    # ---- cross-decoder: (GMU, cross-attention) x (P - 1) ---------------
    def cross_pair(x, gp, ap, i, m, kv):
        x = gmu_layer(x, gp, mid + 2 + 2 * i, i, m)
        return attn_layer(x, ap, mid + 3 + 2 * i, P + 1 + i, None, kv,
                          None)[0]

    x, _ = jax.lax.scan(
        lambda x, xs: (ckpt(cross_pair)(x, *xs, m, kv), None), x,
        (params["cross"]["gmu"], params["cross"]["attn"], jnp.arange(P - 1)))
    x = layer_norm(x, params["final_ln_w"], params["final_ln_b"], eps)
    if not with_aux:
        return x
    absmax = jnp.maximum(absmax_self.max(), absmax_mid)
    return x, {"counters": {"max": {"ssm_state_absmax": absmax}}}


def forward(config: Phi4FlashConfig, params, input_ids, attention_mask=None,
            lora=None, compute_dtype=F32, remat: bool = False,
            lora_dropout: float = 0.0, dropout_rng=None,
            lora_impl: str = "auto", position_ids=None,
            with_aux: bool = False):
    """Logits [B, S, V] against the tied head (training goes through the
    chunked loss on `hidden_states` instead)."""
    out = hidden_states(config, params, input_ids, attention_mask, lora,
                        compute_dtype, remat, lora_dropout, dropout_rng,
                        lora_impl, position_ids, with_aux)
    x, aux = out if with_aux else (out, None)
    logits = x @ params["embed"].astype(compute_dtype).T
    return (logits, aux) if with_aux else logits


def mixer_params(config: Phi4FlashConfig) -> Dict[str, int]:
    """Matmul parameters of one mixer of each kind (conv taps, norms,
    A_log, D, the lambdas are not matmuls)."""
    c = config
    H, di, N, r = c.hidden_size, c.ssm_inner, c.mamba_d_state, \
        c.mamba_dt_rank
    q = H * c.num_attention_heads * c.head_dim
    kv = 2 * H * c.num_key_value_heads * c.head_dim
    return {"mamba": H * 2 * di + di * (r + 2 * N) + r * di + di * H,
            "swa": 2 * q + kv, "full": 2 * q + kv, "cross": 2 * q,
            "gmu": 2 * H * di}


def matmul_params_per_token(config: Phi4FlashConfig) -> int:
    """Matmul parameters one token meets: every mixer, every MLP and the
    tied head (the in-loop MFU estimate's N)."""
    c = config
    mix = mixer_params(c)
    return int(sum(mix[k] for k in c.layer_kinds)
               + c.num_hidden_layers * 3 * c.hidden_size
               * c.intermediate_size + c.vocab_size * c.hidden_size)
