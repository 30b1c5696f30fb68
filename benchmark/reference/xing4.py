"""Plain reference of XingChen-AGI's Xing4.0 family (`model_type:
xing4_0`; Xing4.0-29B-A4B): forward, both losses and gradients in
straightforward jax.numpy, float32, matmul precision "highest". No
kernels, no sort or grouped product, a Python loop over layers and over
the Sinkhorn rounds, nothing imported from the program.

The equations (T tokens, C hidden, n = hc_mult stream rows, N heads;
RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w; SwiGLU(x) = (silu(x Wg) *
(x Wu)) Wd):
  x_0[j] = E[ids] for every row j = 0..n-1              x in R^{T x n x C}
  every block has two sublayers F (the mixer, then the MLP), each with
  its own phi [n C, 2n + n^2], b [2n + n^2], alpha [3]:
    x^ = vec(x) / sqrt(mean(vec(x)^2) + rms_norm_eps)   over all n C values
    [H~pre | H~post | H~res] = [a0 ..| a1 ..| a2 ..] * (x^ phi) + b
    H_pre = sigmoid(H~pre) [n];  H_post = 2 sigmoid(H~post) [n]
    H_res = Sinkhorn(clip(H~res, clamp_min, clamp_max)) [n, n]:
      M = exp(.); `hc_sinkhorn_iters` times: M = M / (rowsum + hc_eps),
      then M = M / (colsum + hc_eps)
    u = sum_j H_pre[j] x[j];  y = F(RMSNorm(u; w));
    x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y
  mixer F (dn nope, dr rope, dv value dims, rq / r the two latents):
    c_q = RMSNorm(a W_qa; w_cq);  q = c_q W_qb  [T, N, dn + dr]
    [c ; k_r] = a W_kva;  [k_nope ; v] = RMSNorm(c; w_c) W_kvb [T, N, dn + dv]
    rotary (rotate-half) on q's last dr dims and on k_r (one for all
    heads), inverse frequencies yarn-blended: f_i = theta^(-2i/dr); with
    the correction range low = floor(at(beta_fast)), high =
    ceil(at(beta_slow)), at(r) = dr ln(L0 / (2 pi r)) / (2 ln theta),
    ramp_i = clip((i - low) / (high - low), 0, 1),
    f'_i = f_i / factor * ramp_i + f_i (1 - ramp_i);
    cos and sin times mscale(factor, mscale) / mscale(factor,
    mscale_all_dim), mscale(s, m) = 0.1 m ln s + 1;
    o = softmax(q k^T (dn + dr)^-1/2 mscale(factor, mscale_all_dim)^2
                + causal) v;  out = o W_o
  MLP F: block l < first_k_dense_replace: SwiGLU at intermediate_size;
    else s = sigmoid(m W_r) over all E; sel = top-k of s + bias (ties to
    the lower index); w = s[sel] / (sum + 1e-20) * routed_scaling_factor;
    f = Shared(m) + sum over e in sel AND held of w_e Expert_e(m)
  h = sum_j x_L[j];  logits = RMSNorm(h; w_f) W_head^T
  multi-token prediction (one module, DeepSeek-V3 section 2.2):
    h'_i = [RMSNorm(E[t_{i+1}]; w_e) ; RMSNorm(h_i; w_h)] W_eh, one more
    sparse block on the stream copied from h', rows summed, its own
    final RMSNorm, the SAME head; its logits at i are scored against
    t_{i+2}
  loss = mean_i CE(logits_i, t_{i+1}) + lambda mean_i CE(logits'_i, t_{i+2}).
LoRA on a projection: y = x W + (alpha / r) (x A) B; stacks run over the
layers and then the module's block.

Departures from the published model, each on purpose: only the experts
`held` enter the routed sum and the vocabulary is the rows held (the
chip's share); the module's last position reads t_{T-1} again in place
of a token past the end (its loss term does not exist); `forced_idx`
replaces the reference's own top-k by given choices. What config.json
leaves to the family's modelling code is listed in the configuration
file's `assumed`.

Memory, so that the published widths fit one chip at 8,192 tokens: a
block's weights are cast to float32 one block at a time and the routed
experts one expert at a time; attention runs a head and a block of
queries at a time; both under jax.checkpoint where a gradient is taken.

The parameter tree is the program's layout, which is only a layout:
  embed, lm_head [V, C], final_norm [C], layers: a list of blocks
  {hc_attn, hc_mlp {phi, b, alpha}, input_ln, post_attn_ln, mla{q_a_w,
  q_a_norm, q_b_w, kv_a_w, kv_a_norm, kv_b_w, o_w}, mlp{gate_w, up_w,
  down_w} | router_w [C, E], expert_bias [E], shared{...},
  experts{gate_w, up_w [n_held, C, I], down_w [n_held, I, C]}},
  mtp {enorm, hnorm, eh_proj [2C, C], block, norm}.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 2048      # query rows of attention at a time
# What tools/lower_precision_xing4.py (and tests/test_xing4.py) lowers or
# breaks, one at a time, to show that the comparison refuses it: the dtype
# the Sinkhorn rounds and the router compute in; rounds added to
# hc_sinkhorn_iters; the softmax scale without mscale^2; the loss without
# the module's term. float32, 0 and False always, but in those two.
LOWER = {"sinkhorn": F32, "router": F32}
FAULT = {"sinkhorn_iters": 0, "no_mscale": False, "drop_mtp": False}


def lowered(x, what: str):
    """x rounded to LOWER[what]'s exponent and mantissa (float32: as it
    is). `reduce_precision`, not a cast there and back: the compiler is
    allowed to drop such a pair, and on the chip it does."""
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
    return (0, cfg["n_routed_experts"]) if h is None \
        else (int(h[0]), int(h[1]))


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def inv_freq(cfg: dict):
    """[dr / 2] inverse frequencies of the rotary, yarn-blended."""
    d, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    f = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    rs = cfg.get("rope_scaling")
    if not rs:
        return f
    L0 = rs["original_max_position_embeddings"]
    at = lambda r: d * math.log(L0 / (r * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(at(rs["beta_fast"])), 0)
    high = min(math.ceil(at(rs["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return f / rs["factor"] * ramp + f * (1.0 - ramp)


def softmax_scale(cfg: dict) -> float:
    rs = cfg.get("rope_scaling")
    m = 1.0 if not rs or FAULT["no_mscale"] \
        else mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def rotate(cfg, x, positions):
    """x [T, heads, D], rotate-half pairing (dim i with i + D/2)."""
    D = x.shape[-1]
    rs = cfg.get("rope_scaling")
    m = 1.0 if not rs else mscale(rs["factor"], rs["mscale"]) \
        / mscale(rs["factor"], rs["mscale_all_dim"])
    ang = positions.astype(F32)[:, None] * inv_freq(cfg)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :] * m
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :] * m
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


def sinkhorn(cfg: dict, logits):
    """[..., n, n] -> [..., n, n]: rows (last axis summed) then columns,
    hc_sinkhorn_iters times, a Python loop."""
    m = lowered(jnp.exp(jnp.clip(logits, cfg["mhc_h_res_clamp_min"],
                                 cfg["mhc_h_res_clamp_max"])), "sinkhorn")
    eps = cfg["hc_eps"]
    for _ in range(cfg["hc_sinkhorn_iters"] + FAULT["sinkhorn_iters"]):
        m = lowered(m / (m.sum(-1, keepdims=True) + eps), "sinkhorn")
        m = lowered(m / (m.sum(-2, keepdims=True) + eps), "sinkhorn")
    return m


@highest
def hyper(cfg: dict, hcp, x):
    """x [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    T, n, C = x.shape
    v = x.reshape(T, n * C)
    v = v * jax.lax.rsqrt((v * v).mean(-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    a = hcp["alpha"]
    alpha = jnp.concatenate([jnp.full((n,), a[0]), jnp.full((n,), a[1]),
                             jnp.full((n * n,), a[2])])
    h = alpha * (v @ hcp["phi"]) + hcp["b"]
    return (jax.nn.sigmoid(h[:, :n]), 2.0 * jax.nn.sigmoid(h[:, n:2 * n]),
            sinkhorn(cfg, h[:, 2 * n:].reshape(T, n, n)))


def sublayer(cfg, hcp, x, fn):
    """x' = H_res x + H_post^T fn(H_pre x); also H_res."""
    pre, post, res = hyper(cfg, hcp, x)
    u = (pre[..., None] * x).sum(1)
    y = fn(u)
    return jnp.einsum("tij,tjc->tic", res, x,
                      precision="highest") + post[..., None] * y[:, None], \
        res


@highest
def causal_attention(q, k, v, scale):
    """q, k [T, N, dq], v [T, N, dv] -> [T, N, dv]: a plain softmax over
    the masked score matrix, a head and a block of queries at a time."""
    T, N, _ = q.shape
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

    heads, blocks = jnp.meshgrid(jnp.arange(N), jnp.arange(T // blk),
                                 indexing="ij")
    o = jax.lax.map(one, (heads.ravel(), blocks.ravel()))
    return o.reshape(N, T, -1).transpose(1, 0, 2)


def mla_mixer(cfg, p, a, lo, positions):
    T = a.shape[0]
    N, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    c_q = rms_norm(a @ p["q_a_w"] + lora_delta(a, lo("q_a_proj")),
                   p["q_a_norm"], eps)
    q = (c_q @ p["q_b_w"] + lora_delta(c_q, lo("q_b_proj"))) \
        .reshape(T, N, dn + dr)
    kv_a = a @ p["kv_a_w"] + lora_delta(a, lo("kv_a_proj"))
    c = rms_norm(kv_a[:, :r], p["kv_a_norm"], eps)
    kv = (c @ p["kv_b_w"] + lora_delta(c, lo("kv_b_proj"))) \
        .reshape(T, N, dn + dv)
    k_r = rotate(cfg, kv_a[:, None, r:], positions)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (T, N, dr))],
                        -1)
    q = jnp.concatenate([q[..., :dn], rotate(cfg, q[..., dn:], positions)],
                        -1)
    o = causal_attention(q, k, kv[..., dn:], softmax_scale(cfg))
    o = o.reshape(T, N * dv)
    return o @ p["o_w"] + lora_delta(o, lo("o_proj"))


@highest
def router(cfg, m, lp, forced_idx=None):
    """(idx [T, k], w [T, k], the biased scores the selection is made on
    [T, E])."""
    if cfg.get("n_group", 1) != 1:
        raise ValueError("the reference routes over one group")
    low = LOWER["router"]
    s = lowered(jax.nn.sigmoid(lowered(
        (m.astype(low) @ lp["router_w"].astype(low)).astype(F32),
        "router")), "router")
    biased = s + lp["expert_bias"]
    if forced_idx is None:
        idx = jnp.argsort(-biased, axis=-1, stable=True)[
            :, :cfg["num_experts_per_tok"]]
    else:
        idx = forced_idx
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"], biased


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
def block(cfg, lp, x, positions, lora_l=None, forced_idx=None):
    """One block on the stream [T, n, C] float32. lp: its weights
    (float32, but the routed experts); lora_l: {target: {"A", "B",
    "scale"}} or None. Returns (x, info): {"res": the two H_res
    [2, T, n, n]} and, for an expert block, "idx" and "biased"."""
    eps = cfg["rms_norm_eps"]
    lo = (lambda n: None) if lora_l is None else lora_l.get
    x, res_a = sublayer(
        cfg, lp["hc_attn"], x, lambda u: mla_mixer(
            cfg, lp["mla"], rms_norm(u, lp["input_ln"], eps), lo,
            positions))
    info = {}

    def mlp(u):
        m = rms_norm(u, lp["post_attn_ln"], eps)
        if "experts" not in lp:
            return swiglu(m, lp["mlp"])
        idx, w, biased = router(cfg, m, lp, forced_idx)
        info.update(idx=idx, biased=biased)
        return swiglu(m, lp["shared"]) + routed_sum(cfg, m, idx, w,
                                                    lp["experts"])

    x, res_m = sublayer(cfg, lp["hc_mlp"], x, mlp)
    info["res"] = jnp.stack([res_a, res_m])
    return x, info


def to_f32(tree):
    return jax.tree.map(lambda t: t.astype(F32), tree)


def block_f32(lp):
    return {k: (v if k == "experts" else to_f32(v)) for k, v in lp.items()}


def block_weights(params, at: int):
    """Block `at`'s weights: a layer, or (at = number of layers) the
    multi-token-prediction module's block."""
    L = len(params["layers"])
    lp = params["layers"][at] if at < L else params["mtp"]["block"]
    return block_f32(jax.tree.map(jnp.asarray, lp))


def lora_block(lora, at: int):
    if lora is None:
        return None
    return {t: {"A": e["A"][at], "B": e["B"][at], "scale": e["scale"]}
            for t, e in lora["blocks"].items()}


def embed(cfg, params, ids):
    return jnp.asarray(params["embed"])[ids].astype(F32)


def copy_in(cfg, h):
    """[T, C] -> the stream [T, n, C]."""
    return jnp.broadcast_to(h[:, None], (h.shape[0], cfg["hc_mult"],
                                         h.shape[1]))


@highest
def head(cfg, params, h, norm="final_norm"):
    """h [T, C] (the stream's rows summed) -> logits [T, V]."""
    w = params["mtp"]["norm"] if norm == "mtp" else params[norm]
    x = rms_norm(h, jnp.asarray(w).astype(F32), cfg["rms_norm_eps"])
    return x @ jnp.asarray(params["lm_head"]).astype(F32).T


@highest
def mtp_merge(cfg, mp, e_next, h):
    """[RMSNorm(E[t_{i+1}]) ; RMSNorm(h_i)] W_eh -> [T, C]; mp: the
    module's enorm, hnorm, eh_proj in float32."""
    eps = cfg["rms_norm_eps"]
    return jnp.concatenate([rms_norm(e_next, mp["enorm"], eps),
                            rms_norm(h, mp["hnorm"], eps)], -1) \
        @ mp["eh_proj"]


def next_ids(ids):
    """t_{i+1} at position i; the last position reads t_{T-1} again."""
    return jnp.concatenate([ids[1:], ids[-1:]])


@functools.partial(jax.jit, static_argnums=(0,))
def _block_jit(cfg_items, lp, x, positions, lora_l, forced_idx):
    return block(thaw(cfg_items), lp, x, positions, lora_l, forced_idx)


def freeze(cfg: dict) -> tuple:
    """The configuration keys the equations read, hashable."""
    keys = ("rms_norm_eps", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "n_routed_experts",
            "num_experts_per_tok", "n_group", "norm_topk_prob",
            "routed_scaling_factor", "hidden_size", "hc_mult",
            "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max")
    rs = cfg.get("rope_scaling")
    return tuple((k, cfg[k]) for k in keys) + (
        ("experts_held", held_of(cfg)),
        ("rope_scaling", None if not rs else tuple(sorted(rs.items()))))


def thaw(items) -> dict:
    cfg = dict(items)
    if cfg["rope_scaling"] is not None:
        cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


def run_blocks(cfg, params, xs, blocks, lora=None, forced_idx=None):
    """Apply the given blocks (layer numbers; the number of layers names
    the module's block) to each stream [T, n, C] of the list `xs`, a
    block's float32 weights made once for all of them. Returns (xs, [per
    sequence: the info of each block, in order]). forced_idx: per
    sequence, {block number: [T, k]}, or None."""
    xs, infos = list(xs), [[] for _ in xs]
    for at in blocks:
        lp = block_weights(params, at)
        for n, x in enumerate(xs):
            forced = None if forced_idx is None \
                else forced_idx[n].get(at)
            xs[n], info = _block_jit(
                freeze(cfg), lp, x, jnp.arange(x.shape[0]),
                lora_block(lora, at),
                None if forced is None else jnp.asarray(forced))
            infos[n].append(info)
    return xs, infos


def forced_of(cfg, idx, idx_mtp=None) -> dict:
    """{block number: [T, k]} from the program's stacked choices (expert
    layers in order; the module's block last)."""
    nd, L = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    out = {nd + j: idx[j] for j in range(len(idx))}
    if idx_mtp is not None:
        out[L] = idx_mtp
    return out


def forward(cfg, params, ids, lora=None, forced_idx=None, mtp=True):
    """ids [T] -> (logits [T, V], the module's logits [T, V] or None,
    the info of every block run, in order)."""
    L = cfg["num_hidden_layers"]
    xs, infos = run_blocks(cfg, params,
                           [copy_in(cfg, embed(cfg, params, ids))],
                           range(L), lora,
                           None if forced_idx is None else [forced_idx])
    h = xs[0].sum(1)
    logits = head(cfg, params, h)
    if not (mtp and cfg.get("num_nextn_predict_layers") and "mtp" in params):
        return logits, None, infos[0]
    mp = to_f32({k: jnp.asarray(params["mtp"][k])
                 for k in ("enorm", "hnorm", "eh_proj")})
    hm = mtp_merge(cfg, mp, embed(cfg, params, next_ids(ids)), h)
    xm, info = run_blocks(cfg, params, [copy_in(cfg, hm)], [L], lora,
                          None if forced_idx is None else [forced_idx])
    return logits, head(cfg, params, xm[0].sum(1), "mtp"), \
        infos[0] + info[0]


def loss(logits, ids, shift: int = 1):
    """Mean cross-entropy of position i against ids[i + shift]; logits
    [T, V], ids [T]."""
    lp = jax.nn.log_softmax(logits[:-shift].astype(F32), axis=-1)
    return -jnp.take_along_axis(lp, ids[shift:, None], axis=-1).mean()


def total_loss(cfg, logits, logits_mtp, ids, weight: float):
    """(loss, main, module's): L_main + weight L_mtp."""
    main = loss(logits, ids)
    if logits_mtp is None or FAULT["drop_mtp"]:
        return main, main, jnp.zeros(())
    second = loss(logits_mtp, ids, 2)
    return main + weight * second, main, second


def tail_grad(cfg, params, x, ids, lora, forced_idx, K: int,
              weight: float):
    """Gradient of L_main + weight L_mtp to the adapters of the LAST K
    layers and of the module's block, given the stream `x` [T, n, C]
    that enters them (the layers below run without autodiff). Returns
    {target: {"A", "B"}} with the stacks cut to those K + 1 positions."""
    L = cfg["num_hidden_layers"]
    tail = list(range(L - K, L + 1))
    weights = [jax.tree.map(
        jnp.asarray, params["layers"][at] if at < L
        else params["mtp"]["block"]) for at in tail]
    lora_t = [lora_block(lora, at) for at in tail]
    pos = jnp.arange(x.shape[0])
    top = {"final_norm": jnp.asarray(params["final_norm"]),
           "lm_head": jnp.asarray(params["lm_head"]),
           "embed": jnp.asarray(params["embed"]),
           "mtp": {k: jnp.asarray(params["mtp"][k])
                   for k in ("enorm", "hnorm", "eh_proj", "norm")}}
    forced = [None if forced_idx.get(at) is None
              else jnp.asarray(forced_idx[at]) for at in tail]

    def tail_loss(lt, weights, top, x):
        def run(j, x):
            @jax.checkpoint
            def one(x, w, lj):
                return block(cfg, block_f32(w), x, pos, lj, forced[j])[0]
            return one(x, weights[j], lt[j])

        for j in range(K):
            x = run(j, x)
        h = x.sum(1)
        mp = to_f32({k: top["mtp"][k] for k in ("enorm", "hnorm",
                                                "eh_proj")})
        hm = mtp_merge(cfg, mp, embed(cfg, top, next_ids(ids)), h)
        xm = run(K, copy_in(cfg, hm))
        return total_loss(cfg, head(cfg, top, h),
                          head(cfg, top, xm.sum(1), "mtp"), ids, weight)[0]

    g = jax.jit(jax.grad(tail_loss))(lora_t, weights, top, x)
    return {t: {leaf: jnp.stack([gj[t][leaf] for gj in g])
                for leaf in ("A", "B")} for t in g[0]}
