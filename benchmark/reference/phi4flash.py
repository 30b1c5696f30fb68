"""Plain reference of Phi-4-mini-flash-reasoning (SambaY with differential
attention): forward, loss and gradients in straightforward jax.numpy,
float32, matmul precision "highest". No kernels, no chunked scan,
nothing imported from the program.

The equations (T tokens, H hidden, LN(x; w, b) = (x - mean) /
sqrt(var + eps) * w + b at eps = layer_norm_eps; RMSNorm(x; w) = x /
sqrt(mean(x^2) + 1e-5) * w; L layers, mid = L / 2):
  h = E[ids]
  layer l:  a = LN1(h);  h = h + mixer_l(a);  h = h + MLP(LN2(h)),
            MLP(x) = (v * silu(g)) W_fc2 for [g | v] = x W_fc1
  mamba (l even, l <= mid; d_in = expand H, N state, r = ceil(H / 16)):
    [u | z] = a W_in;  u = silu(conv(u) + b_conv)   conv: y_t = sum_{j<K}
      w_j u_{t-K+1+j} per channel, zeros before the start
    [delta | B | C] = u W_x;  Delta = softplus(delta W_dt + b_dt)
    A = -exp(A_log) [d_in, N];  h_0 = 0;
    h_t = exp(Delta_t A) h_{t-1} + (Delta_t u_t) B_t^T   (one token a step)
    y_t = h_t C_t + D u_t;  out = (y * silu(z)) W_out
    layer mid keeps m = y (before the z gate)
  attention (swa: l odd < mid, window W = sliding_window; full: l =
    mid + 1; cross: l odd > mid + 1, keys and values of layer mid + 1):
    q = a W_q as heads of d; pair i = heads (2i, 2i + 1) -> q1_i, q2_i;
    k = a W_k likewise -> k1_j, k2_j; v = a W_v with heads 2j, 2j + 1
    joined -> v_j of 2 d; query pair i reads key pair j = i / (nq / nkv);
    A^s = softmax(q^s k^sT / sqrt(d) + mask) v, mask causal (and j > i -
    W in a window layer);
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 l);
    o_i = (1 - lambda_init) RMSNorm(A^1_i - lambda A^2_i; subln);
    out = [o_0 ... ] W_o
  gmu (l even > mid + 1):  out = (m * silu(a W_1)) W_2
  logits = LN(h; final) E^T (tied);  loss = mean next-token CE.
LoRA on a projection: y = x W + (alpha / r) (x A) B.

Departures from the published model: none by choice; what config.json
leaves to the family's modelling code (the Mamba sizes, where the memory
is tapped, the pairing of half-heads, the window's boundary, the biases,
differential attention in every attention layer) is listed in the
configuration file's `assumed`.

Memory, so that the whole model fits one chip beside nothing else at
16,384 tokens: a layer's weights are cast to float32 one layer at a
time; the scan runs token by token in segments of SEGMENT steps (a
gradient keeps the state at segment starts only); attention runs a pair
of heads and a block of Q_BLOCK queries at a time; all under
jax.checkpoint where a gradient is taken.

The parameter tree is the program's layout, which is only a layout:
  embed [V, H], final_ln_w, final_ln_b [H], self{mamba, attn}: stacks
  over the self-decoder's pairs, mid{mamba, attn}: one layer each,
  cross{gmu, attn}: stacks over the cross-decoder's pairs; a layer is
  {ln1_w, ln1_b, ln2_w, ln2_b, mlp{fc1_w [H, 2I], fc2_w [I, H]},
  mixer{...}} with mixer mamba {in_w, conv_w [K, d_in], conv_b, x_w,
  dt_w, dt_b, A_log [d_in, N], D, out_w}, attention {q_w, k_w, v_w (not
  in a cross layer), o_w, lq1, lk1, lq2, lk2 [d], subln [2 d]}, gmu
  {in_w, out_w}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
SEGMENT = 128       # scan steps between kept states
Q_BLOCK = 2048      # query rows of attention at a time
LOSS_BLOCK = 1024   # rows of the head's logits at a time
DIFF_EPS = 1e-5
# What tools/lower_precision_phi4flash.py lowers to show that the
# comparison refuses it: the dtype the scan's state and its decay are
# carried in. float32 always, but in that tool.
LOWER = {"state": F32}
# The fault that tool plants to show that the gradients' comparison
# refuses it: the cross-decoder reading the memory and the shared keys
# and values with no gradient flowing back through them. Off always, but
# in that tool.
DETACH = {"shared": False}


def lowered(x, what: str):
    """x rounded to LOWER[what]'s exponent and mantissa (float32: as it
    is). `reduce_precision`, not a cast there and back: the compiler may
    drop such a pair."""
    if LOWER[what] == F32:
        return x
    fi = jnp.finfo(LOWER[what])
    return jax.lax.reduce_precision(x, fi.nexp, fi.nmant)


def shared(x):
    """What the cross-decoder reads of the middle pair (DETACH)."""
    return jax.lax.stop_gradient(x) if DETACH["shared"] else x


def highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def dims(cfg: dict) -> dict:
    """The sizes the equations read; the Mamba sizes default to the
    convention the configuration file's `assumed` states."""
    H = cfg["hidden_size"]
    return {"H": H, "di": (cfg.get("mamba_expand") or 2) * H,
            "N": cfg.get("mamba_d_state") or 16,
            "r": cfg.get("mamba_dt_rank") or -(-H // 16),
            "d": H // cfg["num_attention_heads"],
            "nq": cfg["num_attention_heads"],
            "nkv": cfg["num_key_value_heads"]}


def layer_kind(cfg: dict, l: int) -> str:
    mid = cfg["num_hidden_layers"] // 2
    if l <= mid:
        return "mamba" if l % 2 == 0 else "swa"
    if l == mid + 1:
        return "full"
    return "gmu" if l % 2 == 0 else "cross"


def role(cfg: dict, l: int) -> str:
    """Layer l's kind, `memory` for the Mamba layer whose readout is kept
    (what a block is traced for: the layer's index itself only sets
    lambda_init, and is traced)."""
    kind = layer_kind(cfg, l)
    return "memory" if kind == "mamba" \
        and l == cfg["num_hidden_layers"] // 2 else kind


def slot(cfg: dict, l: int):
    """(group, kind key, stack position or None) of layer l in the tree."""
    mid = cfg["num_hidden_layers"] // 2
    kind = layer_kind(cfg, l)
    key = {"mamba": "mamba", "gmu": "gmu"}.get(kind, "attn")
    if l < mid:
        return "self", key, l // 2
    if l <= mid + 1:
        return "mid", key, None
    return "cross", key, (l - mid - 2) // 2


TARGET_KINDS = {"q_proj": ("swa", "full", "cross"),
                "o_proj": ("swa", "full", "cross"),
                "k_proj": ("swa", "full"), "v_proj": ("swa", "full"),
                "in_proj": ("mamba",), "out_proj": ("mamba",),
                "gmu_in_proj": ("gmu",), "gmu_out_proj": ("gmu",)}


def stack_position(cfg: dict, target: str, l: int):
    """Layer l's position in target's LoRA stack, or None."""
    kinds = TARGET_KINDS[target]
    if layer_kind(cfg, l) not in kinds:
        return None
    return sum(layer_kind(cfg, j) in kinds for j in range(l))


def layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def lora_delta(x, entry):
    if entry is None:
        return 0.0
    return (x @ entry["A"].astype(F32)) @ entry["B"].astype(F32) \
        * entry["scale"].astype(F32)


def short_conv(x, taps):
    """x [T, C], taps [K, C]: y_t = sum_j taps[j] x[t - (K-1) + j]."""
    K, T = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + T] * taps[j] for j in range(K))


@highest
def scan(u, delta, A_log, Bm, Cm, D):
    """u, delta [T, d_in], B, C [T, N] -> y [T, d_in]: the recurrence one
    token a step, segments of SEGMENT steps recomputed in a backward."""
    T, di = u.shape
    A = -jnp.exp(A_log)                                   # [d_in, N]
    seg = SEGMENT if T % SEGMENT == 0 else 1

    def step(h, x):
        ut, dt, bt, ct = x
        h = lowered(lowered(jnp.exp(dt[:, None] * A), "state") * h
                    + (dt * ut)[:, None] * bt[None, :], "state")
        return h, h @ ct

    @jax.checkpoint
    def segment(h, xs):
        return jax.lax.scan(step, h, xs)

    cut = lambda x: x.reshape(T // seg, seg, *x.shape[1:])
    _, y = jax.lax.scan(segment, jnp.zeros((di, A.shape[1]), F32),
                        tuple(map(cut, (u, delta, Bm, Cm))))
    return y.reshape(T, di) + D * u


@highest
def attend(q, k, v, scale, window=None):
    """q [T, n, dq], k [T, m, dq], v [T, m, dv] with m | n -> [T, n, dv]:
    explicit causal softmax (and j > i - window), query head h reading key
    head h // (n / m); a head and a block of queries at a time."""
    T, n, _ = q.shape
    g = n // k.shape[1]
    blk = Q_BLOCK if T % Q_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def one(args):
        head, b = args
        rows = jax.lax.dynamic_slice_in_dim(q[:, head], b * blk, blk)
        i = (b * blk + jnp.arange(blk))[:, None]
        s = (rows @ k[:, head // g].T) * scale
        ok = j <= i
        if window is not None:
            ok &= j > i - window
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return p @ v[:, head // g]

    heads, blocks = jnp.meshgrid(jnp.arange(n), jnp.arange(T // blk),
                                 indexing="ij")
    o = jax.lax.map(one, (heads.ravel(), blocks.ravel()))
    return o.reshape(n, T, -1).transpose(1, 0, 2)


def mamba_mixer(cfg, p, a, lo):
    dm = dims(cfg)
    di, N, r = dm["di"], dm["N"], dm["r"]
    xz = a @ p["in_w"] + lora_delta(a, lo("in_proj"))
    u, z = xz[:, :di], xz[:, di:]
    u = jax.nn.silu(short_conv(u, p["conv_w"]) + p["conv_b"])
    xd = u @ p["x_w"]
    delta = jax.nn.softplus(xd[:, :r] @ p["dt_w"] + p["dt_b"])
    y = scan(u, delta, p["A_log"], xd[:, r:r + N], xd[:, r + N:], p["D"])
    g = y * jax.nn.silu(z)
    return g @ p["out_w"] + lora_delta(g, lo("out_proj")), y


def diff_attention(cfg, p, a, lo, l, kind, kv=None):
    """(out [T, H], (k1, k2, v)) of layer l (a traced index) of `kind`."""
    dm = dims(cfg)
    T, d, nq, nkv = a.shape[0], dm["d"], dm["nq"], dm["nkv"]
    q = (a @ p["q_w"] + lora_delta(a, lo("q_proj"))).reshape(T, nq // 2,
                                                              2, d)
    if kv is None:
        k = (a @ p["k_w"] + lora_delta(a, lo("k_proj"))).reshape(
            T, nkv // 2, 2, d)
        v = (a @ p["v_w"] + lora_delta(a, lo("v_proj"))).reshape(
            T, nkv // 2, 2 * d)
        kv = (k[:, :, 0], k[:, :, 1], v)
    k1, k2, v = kv
    window = cfg["sliding_window"] if kind == "swa" else None
    a1 = attend(q[:, :, 0], k1, v, d ** -0.5, window)
    a2 = attend(q[:, :, 1], k2, v, d ** -0.5, window)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * l)
    lam = jnp.exp(p["lq1"] @ p["lk1"]) - jnp.exp(p["lq2"] @ p["lk2"]) + lam0
    o = rms_norm(a1 - lam * a2, p["subln"], DIFF_EPS) * (1.0 - lam0)
    o = o.reshape(T, nq * d)
    return o @ p["o_w"] + lora_delta(o, lo("o_proj")), kv


@highest
def block(cfg, lp, h, l, lora_l, state, kind: str):
    """Layer l (its index, may be traced) of `kind` (role()) on [T, H]
    float32; state {"m", "kv"} (what the middle pair hands the
    cross-decoder). Returns (h, state)."""
    eps = cfg["layer_norm_eps"]
    lo = (lambda n: None) if lora_l is None else lora_l.get
    a = layer_norm(h, lp["ln1_w"], lp["ln1_b"], eps)
    if kind in ("mamba", "memory"):
        out, y = mamba_mixer(cfg, lp["mixer"], a, lo)
        if kind == "memory":
            state = {**state, "m": y}
    elif kind == "gmu":
        g = jax.nn.silu(a @ lp["mixer"]["in_w"]
                        + lora_delta(a, lo("gmu_in_proj")))
        y = shared(state["m"]) * g
        out = y @ lp["mixer"]["out_w"] + lora_delta(y, lo("gmu_out_proj"))
    else:
        out, kv = diff_attention(cfg, lp["mixer"], a, lo, l, kind,
                                 shared(state["kv"]) if kind == "cross"
                                 else None)
        if kind == "full":
            state = {**state, "kv": kv}
    h = h + out
    x = layer_norm(h, lp["ln2_w"], lp["ln2_b"], eps)
    gv = x @ lp["mlp"]["fc1_w"]
    I = cfg["intermediate_size"]
    return h + (gv[:, I:] * jax.nn.silu(gv[:, :I])) @ lp["mlp"]["fc2_w"], \
        state


def to_f32(tree):
    return jax.tree.map(lambda t: jnp.asarray(t).astype(F32), tree)


def layer_weights(cfg, params, l: int):
    """Layer l's weights as held (bf16 on the chip), not yet float32."""
    group, key, at = slot(cfg, l)
    lp = params[group][key]
    return lp if at is None else jax.tree.map(lambda t: t[at], lp)


def lora_layer(cfg, lora, l: int):
    """Layer l's adapters, at its position in each target's stack."""
    if lora is None:
        return None
    out = {}
    for t, e in lora["blocks"].items():
        at = stack_position(cfg, t, l)
        if at is not None:
            out[t] = {"A": e["A"][at], "B": e["B"][at], "scale": e["scale"]}
    return out


def embed(cfg, params, ids):
    return jnp.asarray(params["embed"])[ids].astype(F32)


@highest
def head(cfg, params, h):
    x = layer_norm(h, jnp.asarray(params["final_ln_w"]).astype(F32),
                   jnp.asarray(params["final_ln_b"]).astype(F32),
                   cfg["layer_norm_eps"])
    return x @ jnp.asarray(params["embed"]).astype(F32).T


@highest
def head_loss(cfg, params, h, ids):
    """loss(head(h), ids) with the logits made LOSS_BLOCK rows at a time
    (a whole [T, V] of float32 logits is 13 GB at 16,384 x 200,064)."""
    T = h.shape[0]
    if T - 1 <= LOSS_BLOCK:
        return loss(head(cfg, params, h), ids)
    n = -(-(T - 1) // LOSS_BLOCK)
    pad = n * LOSS_BLOCK - (T - 1)
    rows = jnp.pad(h[:-1], ((0, pad), (0, 0)))
    nxt = jnp.pad(ids[1:], (0, pad))
    valid = jnp.arange(n * LOSS_BLOCK) < T - 1
    cut = lambda x: x.reshape(n, LOSS_BLOCK, *x.shape[1:])

    @jax.checkpoint
    def one(args):
        hb, tb, vb = args
        lp = jax.nn.log_softmax(head(cfg, params, hb), axis=-1)
        nll = -jnp.take_along_axis(lp, tb[:, None], axis=-1)[:, 0]
        return jnp.where(vb, nll, 0.0).sum()

    return jax.lax.map(one, (cut(rows), cut(nxt), cut(valid))).sum() \
        / (T - 1)


def freeze(cfg: dict) -> tuple:
    """The configuration keys the equations read, hashable."""
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "sliding_window",
            "layer_norm_eps", "mamba_d_state", "mamba_expand",
            "mamba_dt_rank")
    return tuple((k, cfg[k]) for k in keys if cfg.get(k) is not None)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _block_jit(cfg_items, kind, lp, h, l, lora_l, state):
    return block(dict(cfg_items), to_f32(lp), h, l, lora_l, state, kind)


def run_layers(cfg, params, h, layers, lora=None, state=None):
    """Apply the given layers to h [T, H]; returns (h, state). One
    compiled block a kind of layer (and shape of state), the layer's
    index handed in as a number."""
    state = dict(state or {})
    for l in layers:
        h, state = _block_jit(freeze(cfg), role(cfg, l),
                              layer_weights(cfg, params, l), h,
                              jnp.float32(l), lora_layer(cfg, lora, l),
                              state)
    return h, state


def forward(cfg, params, ids, lora=None):
    """ids [T] -> logits [T, V]."""
    h, _ = run_layers(cfg, params, embed(cfg, params, ids),
                      range(cfg["num_hidden_layers"]), lora)
    return head(cfg, params, h)


def tail_grad(cfg, params, h, state, ids, lora, first: int):
    """Gradient of the loss to the adapters of layers first ... L - 1,
    given the hidden state `h` [T, H] that enters layer `first` and the
    middle pair's `state` where `first` lies above it (the layers below
    run without autodiff). Returns {target: {"A", "B"}} over the stack
    positions of those layers."""
    L = cfg["num_hidden_layers"]
    tail = list(range(first, L))
    weights = [layer_weights(cfg, params, l) for l in tail]
    lora_t = [lora_layer(cfg, lora, l) for l in tail]
    top = {k: jnp.asarray(params[k])
           for k in ("final_ln_w", "final_ln_b", "embed")}

    def tail_loss(lt, weights, top, h, state):
        for j, l in enumerate(tail):
            @jax.checkpoint
            def one(h, state, w, lj, l=l):
                return block(cfg, to_f32(w), h, l, lj, state, role(cfg, l))

            h, state = one(h, state, weights[j], lt[j])
        return head_loss(cfg, top, h, ids)

    g = jax.jit(jax.grad(tail_loss))(lora_t, weights, top, h, dict(state))
    out: dict = {}
    for gj in g:            # layer order: a target's positions ascend
        for t, e in (gj or {}).items():
            for leaf in ("A", "B"):
                out.setdefault(t, {}).setdefault(leaf, []).append(e[leaf])
    return {t: {leaf: jnp.stack(v) for leaf, v in e.items()}
            for t, e in out.items()}


def adam_first_update(g, lr: float, eps: float = 1e-8):
    """The change of a leaf at Adam's first step from its gradient g
    (numpy, float32): the bias-corrected moments are g and g^2, so the
    step is -lr g / (|g| + eps). A clip of the whole gradient by one
    factor moves it only where |g| is near eps."""
    import numpy as np
    g = np.asarray(g, np.float32)
    return -np.float32(lr) * g / (np.abs(g) + np.float32(eps))


def loss(logits, ids):
    """Mean next-token cross-entropy; logits [..., T, V], ids [..., T]."""
    lp = jax.nn.log_softmax(logits[..., :-1, :].astype(F32), axis=-1)
    nll = -jnp.take_along_axis(lp, ids[..., 1:, None], axis=-1)
    return nll.mean()
