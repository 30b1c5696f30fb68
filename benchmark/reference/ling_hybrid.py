"""Plain reference of the Ling-3.0-flash family's language model (Ling-3.0-
flash-VL without the vision tower): forward, loss and gradients in
straightforward jax.numpy, float32, matmul precision "highest". No
kernels, no chunked scan, no sort or grouped product, nothing imported
from the program.

The equations (T tokens, H hidden, n heads; RMSNorm(x; w) = x /
sqrt(mean(x^2) + eps) * w; SwiGLU(x) = (silu(x Wg) * (x Wu)) Wd):
  h = E[ids]
  layer l is `mla` where (l + 1) mod layer_group_size = 0, else `kda`:
    a = RMSNorm(h; w_in)
    kda (d = head_dim):
      q, k, v = silu(conv(a W_{q,k,v}))    conv: y_t = sum_{j<K} c_j x_{t-K+1+j}
        per channel, zeros before the start
      per head: q^ = q / |q| * d^-1/2, k^ = k / |k|
      g = lb * sigmoid(exp(A_log[head]) * (a W_f + dt_bias))    [T, n, d]
      beta = sigmoid(a W_b)                                     [T, n]
      S_0 = 0 in R^{d x d};  S~ = diag(exp g_t) S_{t-1};
      S_t = S~ + beta_t k^_t (v_t - S~^T k^_t)^T;  o_t = S_t^T q^_t
      out = (RMSNorm_d(o; w_o) * sigmoid(a W_g)[head]) W_o
    mla (dn nope, dr rope, dv value dims, r latent):
      q = a W_q [T, n, dn + dr];  [c ; k_r] = a W_dkv;  c = RMSNorm(c; w_c)
      [k_nope ; v] = c W_ukv [T, n, dn + dv];  k = [k_nope ; k_r] (k_r the
      same for every head);  q, k = RMSNorm over dn + dr (w_q, w_k);
      rotary (theta, rotate-half) on the last dr dims of q and k;
      o = softmax(q k^T / sqrt(dn + dr) + causal) v
      out = (o * sigmoid(a W_g)[head]) W_o
    h = h + out;  m = RMSNorm(h; w_post)
    l < first_k_dense_replace:  f = SwiGLU(m) at intermediate_size
    else: s = sigmoid(m W_r) over all E; b = s + bias; group score = sum
      of a group's two largest b; the topk_group best of n_group groups
      stay; sel = top-k of b among their experts (ties to the lower
      index); w = s[sel] / (sum + 1e-20) * routed_scaling_factor;
      f = Shared(m) + sum over e in sel AND held of w_e Expert_e(m)
    h = h + f
  logits = RMSNorm(h; w_f) W_head^T;  loss = mean next-token CE.
LoRA on a projection: y = x W + (alpha / r) (x A) B.

Departures from the published model, each on purpose: only the experts
`held` enter the routed sum and the vocabulary is the rows held (the
chip's share); no vision tower, no multi-token prediction head; a
non-zero SwiGLU limit is refused (config.json does not give the clamp);
`forced_idx` replaces the reference's own top-k by given choices. What
config.json leaves to the family's modelling code is listed in the
configuration file's `assumed`.

Memory, so that the published widths fit one chip at 16,384 tokens: a
layer's weights are cast to float32 one layer at a time and the routed
experts one expert at a time; the delta rule runs token by token in
segments of 128 steps (a gradient keeps the state at segment starts
only); latent attention runs a head and a block of queries at a time;
both under jax.checkpoint where a gradient is taken.

The parameter tree is the program's layout, which is only a layout:
  embed, lm_head [V, H], final_norm [H], layers: a list of
  {input_ln, post_attn_ln, kda{q_w, k_w, v_w, f_w [H, n d], b_w, g_w
  [H, n], o_w [n d, H], q_conv, k_conv, v_conv [K, n d], A_log [n],
  dt_bias [n d], o_norm [d]} | mla{q_w, kv_a_w, kv_a_norm, kv_b_w, q_norm,
  k_norm, g_w, o_w}, mlp{gate_w, up_w, down_w} | router_w [H, E],
  expert_bias [E], shared{...}, experts{gate_w, up_w [n_held, H, I],
  down_w [n_held, I, H]}}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
SEGMENT = 128       # delta-rule steps between kept states
Q_BLOCK = 2048      # query rows of latent attention at a time
# What tools/lower_precision_ling.py lowers, one at a time, to show that
# the comparison refuses it: the dtype the delta rule's state is carried
# in, the log-decay is kept in, the router computes in. float32 always,
# but in that tool.
LOWER = {"state": F32, "gate": F32, "router": F32}


def lowered(x, what: str):
    """x rounded to LOWER[what]'s exponent and mantissa (float32: as it
    is). `reduce_precision`, not a cast there and back: the compiler is
    allowed to drop such a pair (`xla_allow_excess_precision`), and on
    the chip it does."""
    if LOWER[what] == F32:
        return x
    fi = jnp.finfo(LOWER[what])
    return jax.lax.reduce_precision(x, fi.nexp, fi.nmant)


def highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def held_of(cfg: dict) -> tuple:
    h = cfg.get("experts_held")
    return (0, cfg["num_experts"]) if h is None else (int(h[0]), int(h[1]))


def layer_kind(cfg: dict, l: int) -> str:
    return "mla" if (l + 1) % cfg["layer_group_size"] == 0 else "kda"


def kind_position(cfg: dict, l: int) -> int:
    """Layer l's position among the layers of its kind (LoRA stacks run
    over a kind's layers)."""
    return sum(layer_kind(cfg, j) == layer_kind(cfg, l) for j in range(l))


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def rotate(x, positions, theta):
    """x [T, heads, D], rotate-half pairing (dim i with i + D/2)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = positions.astype(F32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def lora_delta(x, entry):
    if entry is None:
        return 0.0
    return (x @ entry["A"].astype(F32)) @ entry["B"].astype(F32) \
        * entry["scale"].astype(F32)


@highest
def swiglu(x, p):
    return (jax.nn.silu(x @ p["gate_w"]) * (x @ p["up_w"])) @ p["down_w"]


def short_conv(x, taps):
    """x [T, C], taps [K, C]: y_t = sum_j taps[j] x[t - (K-1) + j] as K
    shifted copies, zeros before the start."""
    K = taps.shape[0]
    T = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + T] * taps[j] for j in range(K))


@highest
def delta_rule(q, k, v, g, beta):
    """q, k, g [T, n, d], v [T, n, dv], beta [T, n] -> o [T, n, dv]: the
    recurrence, one token a step; segments of SEGMENT steps are
    recomputed in a backward pass."""
    T, n, d = q.shape
    seg = SEGMENT if T % SEGMENT == 0 else 1
    cut = lambda x: x.reshape(T // seg, seg, *x.shape[1:])

    carried = lambda s: lowered(s, "state")

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None] * s                       # [n, d, dv]
        pred = jnp.einsum("ndv,nd->nv", s, kt)
        s = carried(s + jnp.einsum("nd,nv->ndv", bt[:, None] * kt,
                                   vt - pred))
        return s, jnp.einsum("ndv,nd->nv", s, qt)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(step, s, xs)

    _, o = jax.lax.scan(segment, jnp.zeros((n, d, v.shape[-1]), F32),
                        tuple(map(cut, (q, k, v, g, beta))))
    return o.reshape(T, n, v.shape[-1])


@highest
def causal_attention(q, k, v, scale):
    """q, k [T, n, dq], v [T, n, dv] -> [T, n, dv]; a head and a block of
    queries at a time."""
    T, n, _ = q.shape
    blk = Q_BLOCK if T % Q_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def one(args):
        head, b = args
        rows = jax.lax.dynamic_slice_in_dim(q[:, head], b * blk, blk)
        i = (b * blk + jnp.arange(blk))[:, None]
        s = (rows @ k[:, head].T) * scale
        p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
        return p @ v[:, head]

    heads, blocks = jnp.meshgrid(jnp.arange(n), jnp.arange(T // blk),
                                 indexing="ij")
    o = jax.lax.map(one, (heads.ravel(), blocks.ravel()))
    return o.reshape(n, T, -1).transpose(1, 0, 2)


def kda_mixer(cfg, p, a, lo):
    T = a.shape[0]
    n, d = cfg["num_attention_heads"], cfg["head_dim"]

    def proj(w, conv, target):
        y = a @ p[w] + lora_delta(a, lo(target))
        return jax.nn.silu(short_conv(y, p[conv])).reshape(T, n, d)

    unit = lambda x: x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True)
                                       + 1e-12)
    q = unit(proj("q_w", "q_conv", "kda_q_proj")) * d ** -0.5
    k = unit(proj("k_w", "k_conv", "kda_k_proj"))
    v = proj("v_w", "v_conv", "kda_v_proj")
    slope = jnp.exp(p["A_log"])[None, :, None]
    g = lowered(cfg["kda_lower_bound"] * jax.nn.sigmoid(
        slope * (a @ p["f_w"] + p["dt_bias"]).reshape(T, n, d)), "gate")
    beta = jax.nn.sigmoid(a @ p["b_w"])
    o = delta_rule(q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(a @ p["g_w"])[..., None]
    o = o.reshape(T, n * d)
    return o @ p["o_w"] + lora_delta(o, lo("kda_o_proj"))


def mla_mixer(cfg, p, a, lo, positions):
    T = a.shape[0]
    n, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    q = (a @ p["q_w"] + lora_delta(a, lo("mla_q_proj"))) \
        .reshape(T, n, dn + dr)
    kv_a = a @ p["kv_a_w"] + lora_delta(a, lo("mla_kv_a_proj"))
    c = rms_norm(kv_a[:, :r], p["kv_a_norm"], eps)
    kv = (c @ p["kv_b_w"] + lora_delta(c, lo("mla_kv_b_proj"))) \
        .reshape(T, n, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kv_a[:, None, r:], (T, n, dr))], -1)
    v = kv[..., dn:]
    q = rms_norm(q, p["q_norm"], eps)
    k = rms_norm(k, p["k_norm"], eps)
    theta = cfg["rope_theta"]
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], positions,
                                             theta)], -1)
    k = jnp.concatenate([k[..., :dn], rotate(k[..., dn:], positions,
                                             theta)], -1)
    o = causal_attention(q, k, v, (dn + dr) ** -0.5)
    o = (o * jax.nn.sigmoid(a @ p["g_w"])[..., None]).reshape(T, n * dv)
    return o @ p["o_w"] + lora_delta(o, lo("mla_o_proj"))


@highest
def router(cfg, m, lp, forced_idx=None):
    """(idx [T, k], w [T, k], s [T, E], the scores the selection is made
    on [T, E]: biased, -inf outside the kept groups; the groups' scores
    [T, n_group])."""
    E, ng, kg = cfg["num_experts"], cfg["n_group"], cfg["topk_group"]
    T = m.shape[0]
    low = LOWER["router"]
    s = lowered(jax.nn.sigmoid(lowered(
        (m.astype(low) @ lp["router_w"].astype(low)).astype(F32),
        "router")), "router")
    biased = s + lp["expert_bias"]
    score = biased[:, :1]
    if ng > 1:
        per = biased.reshape(T, ng, E // ng)
        score = -jnp.sort(-per, axis=-1)[..., :2].sum(-1)        # [T, ng]
        order = jnp.argsort(-score, axis=-1, stable=True)[:, :kg]
        keep = (order[:, :, None] == jnp.arange(ng)[None, None, :]).any(1)
        biased = jnp.where(jnp.repeat(keep, E // ng, axis=1), biased,
                           -jnp.inf)
    if forced_idx is None:
        idx = jnp.argsort(-biased, axis=-1, stable=True)[
            :, :cfg["num_experts_per_tok"]]
    else:
        idx = forced_idx
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"], s, biased, score


@highest
def routed_sum(cfg, m, idx, w, experts):
    """sum over held experts e of [e in sel] w_e Expert_e(m): every held
    expert runs on every token, masked by its routing weight."""
    first, n = held_of(cfg)

    @jax.checkpoint
    def one(m, we, p):      # this expert's weights to float32, here
        return we[:, None] * swiglu(m, to_f32(p))

    def step(f, xs):
        e, p = xs
        we = jnp.where(idx == first + e, w, 0.0).sum(-1)
        return f + one(m, we, p), None

    f, _ = jax.lax.scan(step, jnp.zeros_like(m), (jnp.arange(n), experts))
    return f


@highest
def block(cfg, lp, h, positions, l: int, lora_l=None, forced_idx=None):
    """Layer l on [T, H] float32. lp: its weights (float32, but the
    routed experts); lora_l: {target: {"A", "B", "scale"}} or None.
    Returns (h, routing): {"idx", "s", "biased", "groups"} for an expert
    layer."""
    eps = cfg["rms_norm_eps"]
    lo = (lambda n: None) if lora_l is None else lora_l.get
    a = rms_norm(h, lp["input_ln"], eps)
    if "kda" in lp:
        h = h + kda_mixer(cfg, lp["kda"], a, lo)
    else:
        h = h + mla_mixer(cfg, lp["mla"], a, lo, positions)
    m = rms_norm(h, lp["post_attn_ln"], eps)
    routing = None
    if "experts" in lp:
        idx, w, s, biased, groups = router(cfg, m, lp, forced_idx)
        f = swiglu(m, lp["shared"]) + routed_sum(cfg, m, idx, w,
                                                 lp["experts"])
        routing = {"idx": idx, "s": s, "biased": biased, "groups": groups}
    else:
        f = swiglu(m, lp["mlp"])
    return h + f, routing


def to_f32(tree):
    return jax.tree.map(lambda t: t.astype(F32), tree)


def layer_f32(lp):
    return {k: (v if k == "experts" else to_f32(v)) for k, v in lp.items()}


def layer(params, l: int):
    return layer_f32(jax.tree.map(jnp.asarray, params["layers"][l]))


def lora_layer(cfg, lora, l: int):
    """Layer l's adapters: the entries of its kind, at its position among
    that kind's layers."""
    if lora is None:
        return None
    kind, at = layer_kind(cfg, l), kind_position(cfg, l)
    return {t: {"A": e["A"][at], "B": e["B"][at], "scale": e["scale"]}
            for t, e in lora["blocks"].items() if t.startswith(kind)}


def embed(cfg, params, ids):
    return jnp.asarray(params["embed"])[ids].astype(F32)


@highest
def head(cfg, params, h):
    x = rms_norm(h, jnp.asarray(params["final_norm"]).astype(F32),
                 cfg["rms_norm_eps"])
    return x @ jnp.asarray(params["lm_head"]).astype(F32).T


@functools.partial(jax.jit, static_argnums=(0, 4))
def _block_jit(cfg_items, lp, h, positions, l, lora_l, forced_idx):
    return block(dict(cfg_items), lp, h, positions, l, lora_l, forced_idx)


def freeze(cfg: dict) -> tuple:
    """The configuration keys the equations read, hashable."""
    keys = ("rms_norm_eps", "num_attention_heads", "head_dim",
            "layer_group_size", "kda_lower_bound", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "num_experts", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "routed_scaling_factor",
            "hidden_size")
    return tuple((k, cfg[k]) for k in keys) + (
        ("experts_held", held_of(cfg)),)


def check(cfg: dict) -> None:
    n = cfg["num_hidden_layers"]
    for name in ("expert_swiglu_limit_list",
                 "share_expert_swiglu_limit_list"):
        if any((cfg.get(name) or [])[:n]):
            raise ValueError(f"{name}: a non-zero SwiGLU limit among the "
                             f"kept layers; the clamp is not published")


def run_layers(cfg, params, hs, layers, lora=None, forced_idx=None,
               positions=None):
    """Apply the given layers to each [T, H] of the list `hs`, a layer's
    float32 weights made once for all of them. Returns (hs, [per
    sequence: the routing of each expert layer among them, in order]).
    forced_idx: per sequence, [expert layers of the MODEL, T, k] (indexed
    by l - first_k_dense_replace), or None."""
    check(cfg)
    nd = cfg["first_k_dense_replace"]
    hs, routings = list(hs), [[] for _ in hs]
    for l in layers:
        lp = layer(params, l)
        for n, h in enumerate(hs):
            pos = jnp.arange(h.shape[0]) if positions is None else positions
            forced = None if forced_idx is None or l < nd \
                else jnp.asarray(forced_idx[n][l - nd])
            hs[n], r = _block_jit(freeze(cfg), lp, h, pos, l,
                                  lora_layer(cfg, lora, l), forced)
            if r is not None:
                routings[n].append(r)
    return hs, routings


def forward(cfg, params, ids, lora=None, forced_idx=None, positions=None):
    """ids [T] -> (logits [T, V], routings of the expert layers)."""
    hs, routings = run_layers(
        cfg, params, [embed(cfg, params, ids)],
        range(cfg["num_hidden_layers"]), lora,
        None if forced_idx is None else [forced_idx], positions)
    return head(cfg, params, hs[0]), routings[0]


def tail_grad(cfg, params, h, ids, lora, forced_idx, K: int):
    """Gradient of the loss to the adapters of the LAST K layers, given
    the hidden state `h` [T, H] that enters them (the layers below run
    without autodiff). Returns {target: {"A", "B"}} with the stacks cut
    to the positions of those layers among their kind."""
    check(cfg)
    L, nd = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    tail = list(range(L - K, L))
    weights = [jax.tree.map(jnp.asarray, params["layers"][l]) for l in tail]
    lora_t = [lora_layer(cfg, lora, l) for l in tail]
    pos = jnp.arange(h.shape[0])
    top = {k: jnp.asarray(params[k]) for k in ("final_norm", "lm_head")}

    def tail_loss(lt, weights, top, h):
        for j, l in enumerate(tail):
            forced = None if l < nd else jnp.asarray(forced_idx[l - nd])

            @jax.checkpoint
            def one(h, w, lj, l=l, forced=forced):
                return block(cfg, layer_f32(w), h, pos, l, lj, forced)[0]

            h = one(h, weights[j], lt[j])
        return loss(head(cfg, top, h), ids)

    g = jax.jit(jax.grad(tail_loss))(lora_t, weights, top, h)
    out: dict = {}
    for gj in g:            # layer order: a kind's positions ascend
        for t, e in gj.items():
            for leaf in ("A", "B"):
                out.setdefault(t, {}).setdefault(leaf, []).append(e[leaf])
    return {t: {leaf: jnp.stack(v) for leaf, v in e.items()}
            for t, e in out.items()}


def loss(logits, ids):
    """Mean next-token cross-entropy; logits [..., T, V], ids [..., T]."""
    lp = jax.nn.log_softmax(logits[..., :-1, :].astype(F32), axis=-1)
    nll = -jnp.take_along_axis(lp, ids[..., 1:, None], axis=-1)
    return nll.mean()
