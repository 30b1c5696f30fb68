"""Plain reference of the AFMoE decoder (Arcee Trinity, `model_type:
afmoe`): forward, loss and gradients in straightforward jax.numpy,
float32, matmul precision "highest". No kernels, no sort, no grouped
product, no scan over layers, nothing imported from the program.

The equations (T tokens, H hidden, RMSNorm(x; w) = x / sqrt(mean(x^2) +
eps) * w):
  h = E[ids] * sqrt(H)                                   (mup_enabled)
  per layer l of kind layer_types[l]:
    a = RMSNorm(h; w_in); q = a Wq [T, nq, D]; k = a Wk, v = a Wv
    [T, nkv, D]; g = a Wg [T, nq D]; q, k = RMSNorm over D (w_q, w_k)
    sliding_attention: rotary on q, k (theta, whole head, rotate-half),
      mask j <= i and j > i - window; full_attention: NO positional
      encoding, mask j <= i
    o = softmax(q k^T / sqrt(D) + mask) v   (a KV head serves nq/nkv
      query heads);  o = o * sigmoid(g);  h = h + RMSNorm(o Wo; w_post_attn)
    m = RMSNorm(h; w_pre_mlp)
    dense layer (l < num_dense_layers):
      f = (silu(m W_gate) * (m W_up)) W_down
    expert layer: s = sigmoid(m W_r) over all E experts;
      sel = top-k(s + b)  (b enters the selection only; ties to the lower
      index); w = s[sel]; w = w / (sum w + 1e-20) (route_norm);
      w = route_scale * w;
      f = Shared(m) + sum over e in sel AND held of w_e Expert_e(m)
    h = h + RMSNorm(f; w_post_mlp)
  logits = RMSNorm(h; w_f) W_head^T;  loss = mean next-token CE.
LoRA on a projection: y = x W + (alpha / r) (x A) B.

Departures from the published model, each on purpose:
  - only the experts `held` here enter the routed sum (the chip's share of
    an expert-parallel deployment; with held = all it is the model);
  - the vocabulary is the rows the parameter tree holds (a slice is a
    smaller vocabulary);
  - no auxiliary load-balancing loss (the router is frozen in LoRA
    fine-tuning; `load_balance_coeff` belongs to pre-training);
  - `forced_idx` replaces the reference's own top-k by given choices (the
    benchmark compares logits under the PROGRAM's routing, and the two
    routings separately: bf16 activations flip near-ties).
Taken from the family's public modelling code, not from config.json
(the configuration file lists them under `assumed`): rotary on sliding
layers only, the gate's place and width, the four norms' places, plain
norm weights, the sqrt(H) multiplier, bias in the selection only, 1e-20.

Memory: one layer's weights are cast to float32 at a time (`layer`) and
its routed experts one expert at a time, a block is one jitted call,
attention runs a query head at a time and the routed sum an expert at a
time (both under jax.checkpoint where a gradient is taken), so the
published widths fit one chip.

The parameter tree is the program's layout, which is only a layout:
  embed [V, H], lm_head [V, H], final_norm [H],
  dense / moe: per-layer stacks {input_ln, attn{q_w, k_w, v_w, o_w,
  gate_w [in, out], q_norm, k_norm}, post_attn_ln, pre_mlp_ln,
  post_mlp_ln} + dense: mlp{gate_w, up_w, down_w};  moe: router_w [H, E],
  expert_bias [E], shared{...}, experts{gate_w, up_w [n, H, I],
  down_w [n, I, H]} for the n experts held[0] .. held[0] + n - 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def highest(fn):
    """Trace `fn` under matmul precision "highest" (on a TPU a float32
    product otherwise runs in bfloat16 passes)."""
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def held_of(cfg: dict) -> tuple:
    h = cfg.get("experts_held")
    return (0, cfg["num_experts"]) if h is None else (int(h[0]), int(h[1]))


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


@highest
def attention(q, k, v, window):
    """q [T, nq, D], k, v [T, nkv, D] -> [T, nq, D]; one query head at a
    time (a [T, T] score matrix each), recomputed in a backward pass."""
    T, nq, D = q.shape
    G = nq // k.shape[1]
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = j <= i
    if window is not None:
        mask &= j > i - window

    @jax.checkpoint
    def one_head(hq):
        s = (q[:, hq] @ k[:, hq // G].T) / jnp.sqrt(F32(D))
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return p @ v[:, hq // G]

    return jax.lax.map(one_head, jnp.arange(nq)).transpose(1, 0, 2)


@highest
def router(cfg, m, lp, forced_idx=None):
    """(idx [T, k], w [T, k], s [T, E], biased scores [T, E])."""
    s = jax.nn.sigmoid(m @ lp["router_w"])
    biased = s + lp["expert_bias"]
    if forced_idx is None:
        # top-k, ties to the lower index: a stable descending sort
        idx = jnp.argsort(-biased, axis=-1, stable=True)[
            :, :cfg["num_experts_per_tok"]]
    else:
        idx = forced_idx
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg["route_scale"], s, biased


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
def block(cfg, lp, h, positions, kind, lora_l=None, forced_idx=None):
    """One layer on [T, H] float32. lp: this layer's weights (float32);
    lora_l: {target: {"A" [in, r], "B" [r, out], "scale"}} or None.
    Returns (h, routing) with routing = {"idx", "s", "biased"} for an
    expert layer and None for a dense one."""
    eps = cfg["rms_norm_eps"]
    T, H = h.shape
    nq, nkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    lo = (lambda n: None) if lora_l is None else lora_l.get
    at = lp["attn"]
    a = rms_norm(h, lp["input_ln"], eps)
    q = (a @ at["q_w"] + lora_delta(a, lo("q_proj"))).reshape(T, nq, D)
    k = (a @ at["k_w"] + lora_delta(a, lo("k_proj"))).reshape(T, nkv, D)
    v = (a @ at["v_w"] + lora_delta(a, lo("v_proj"))).reshape(T, nkv, D)
    g = a @ at["gate_w"]
    q = rms_norm(q, at["q_norm"], eps)
    k = rms_norm(k, at["k_norm"], eps)
    if kind == "sliding_attention":
        q = rotate(q, positions, cfg["rope_theta"])
        k = rotate(k, positions, cfg["rope_theta"])
        o = attention(q, k, v, cfg["sliding_window"])
    else:
        o = attention(q, k, v, None)
    o = o.reshape(T, nq * D) * jax.nn.sigmoid(g)
    o = o @ at["o_w"] + lora_delta(o, lo("o_proj"))
    h = h + rms_norm(o, lp["post_attn_ln"], eps)
    m = rms_norm(h, lp["pre_mlp_ln"], eps)
    routing = None
    if "experts" in lp:
        idx, w, s, biased = router(cfg, m, lp, forced_idx)
        f = swiglu(m, lp["shared"]) + routed_sum(cfg, m, idx, w,
                                                 lp["experts"])
        routing = {"idx": idx, "s": s, "biased": biased}
    else:
        f = swiglu(m, lp["mlp"])
    return h + rms_norm(f, lp["post_mlp_ln"], eps), routing


def to_f32(tree):
    return jax.tree.map(lambda t: t.astype(F32), tree)


def layer_f32(lp):
    """A layer's weights in float32 — but the routed experts, which stay
    as stored and are cast one expert at a time where they are used
    (`routed_sum`): 32 experts of the published width are 3.6 GB in
    float32."""
    return {k: (v if k == "experts" else to_f32(v)) for k, v in lp.items()}


def layer(cfg, params, l: int):
    """(this layer's weights, float32 as `layer_f32` makes them; its
    kind)."""
    nd = cfg["num_dense_layers"]
    stack, i = (params["dense"], l) if l < nd else (params["moe"], l - nd)
    return (layer_f32(jax.tree.map(lambda t: jnp.asarray(t[i]), stack)),
            cfg["layer_types"][l])


def lora_layer(lora, l: int):
    if lora is None:
        return None
    return {t: {"A": e["A"][l], "B": e["B"][l], "scale": e["scale"]}
            for t, e in lora["blocks"].items()}


def embed(cfg, params, ids):
    """ids [T] -> [T, H]."""
    scale = cfg["hidden_size"] ** 0.5 if cfg.get("mup_enabled", True) \
        else 1.0
    return jnp.asarray(params["embed"])[ids].astype(F32) * F32(scale)


@highest
def head(cfg, params, h):
    x = rms_norm(h, jnp.asarray(params["final_norm"]).astype(F32),
                 cfg["rms_norm_eps"])
    return x @ jnp.asarray(params["lm_head"]).astype(F32).T


@functools.partial(jax.jit, static_argnums=(0, 4))
def _block_jit(cfg_items, lp, h, positions, kind, lora_l, forced_idx):
    return block(dict(cfg_items), lp, h, positions, kind, lora_l,
                 forced_idx)


def freeze(cfg: dict) -> tuple:
    """The configuration keys the equations read, hashable (a static
    argument of the jitted block)."""
    keys = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "sliding_window", "num_experts",
            "num_experts_per_tok", "route_norm", "route_scale",
            "hidden_size")
    return tuple((k, cfg[k]) for k in keys) + (
        ("experts_held", held_of(cfg)),)


def run_layers(cfg, params, hs, layers, lora=None, forced_idx=None,
               positions=None):
    """Apply the given layers to each [T, H] of the list `hs`, a layer's
    float32 weights made once for all of them. Returns (hs, [per
    sequence: the routing of each expert layer among them, in order]).
    forced_idx: per sequence, [expert layers of the MODEL, T, k] (indexed
    by l - num_dense_layers), or None."""
    nd = cfg["num_dense_layers"]
    hs, routings = list(hs), [[] for _ in hs]
    for l in layers:
        lp, kind = layer(cfg, params, l)
        for n, h in enumerate(hs):
            pos = jnp.arange(h.shape[0]) if positions is None else positions
            forced = None if forced_idx is None or l < nd \
                else jnp.asarray(forced_idx[n][l - nd])
            hs[n], r = _block_jit(freeze(cfg), lp, h, pos, kind,
                                  lora_layer(lora, l), forced)
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
    without autodiff): the exact gradient of those layers' slices. The
    K layers' weights stay in their stored dtype and are cast to float32
    inside each checkpointed layer (`layer_f32`), so K float32 copies
    never coexist with the residuals. Returns {target: {"A": [K, ...], "B": [K, ...]}}.
    """
    L, nd = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    tail = list(range(L - K, L))
    stacks = [(params["dense"], l) if l < nd else (params["moe"], l - nd)
              for l in tail]
    weights = [jax.tree.map(lambda t: jnp.asarray(t[i]), st)
               for st, i in stacks]
    lora_t = {t: {"A": e["A"][L - K:], "B": e["B"][L - K:],
                  "scale": e["scale"]} for t, e in lora["blocks"].items()}
    pos = jnp.arange(h.shape[0])
    top = {k: jnp.asarray(params[k]) for k in ("final_norm", "lm_head")}

    def tail_loss(lt, weights, top, h):
        for j, l in enumerate(tail):
            forced = None if l < nd else jnp.asarray(forced_idx[l - nd])

            @jax.checkpoint
            def one(h, w, lj, l=l, forced=forced):
                return block(cfg, layer_f32(w), h, pos,
                             cfg["layer_types"][l], lj, forced)[0]

            h = one(h, weights[j], {t: {"A": e["A"][j], "B": e["B"][j],
                                        "scale": e["scale"]}
                                    for t, e in lt.items()})
        return loss(head(cfg, top, h), ids)

    g = jax.jit(jax.grad(tail_loss))(lora_t, weights, top, h)
    return {t: {"A": e["A"], "B": e["B"]} for t, e in g.items()}


def loss(logits, ids):
    """Mean next-token cross-entropy; logits [..., T, V], ids [..., T]."""
    lp = jax.nn.log_softmax(logits[..., :-1, :].astype(F32), axis=-1)
    nll = -jnp.take_along_axis(lp, ids[..., 1:, None], axis=-1)
    return nll.mean()
