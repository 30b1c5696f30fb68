"""GPT-2 in plain jax.numpy — the benchmark's reference.

Forward pass, next-token loss and LoRA application as the GPT-2 paper
and the HF `GPT2LMHeadModel` define them: pre-LN blocks, learned
position table, fused c_attn, causal softmax attention, gelu_new (the
tanh approximation), tied output head; LoRA adds scale * (x A) B to the
projection it targets (Hu et al. 2021), scale = alpha / rank.

Float32 throughout under `jax.default_matmul_precision("highest")` (on
a TPU a float32 matmul otherwise runs as bf16 passes). No kernel, no
cache, no scan, no remat, no batching tricks: one jitted block applied
layer after layer in a Python loop. It imports nothing from the program
under test. Weights arrive as plain arrays in the stacked layout the
fixtures are drawn in ({"wte","wpe","blocks":{...[L, ...]},"ln_f"});
that is data, not code. Departures from the published model: dropout is
off (evaluation mode), as in every comparison the benchmark makes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
IGNORE = -100


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _lora(y, x, entry):
    """entry: None or {"A": [in, r], "B": [r, out], "scale": scalar}."""
    if entry is None:
        return y
    return y + entry["scale"] * ((x @ entry["A"]) @ entry["B"])


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def block(x, bp, lora_l, *, n_head: int, eps: float):
    """One pre-LN transformer block on x [B, S, E]; bp and lora_l hold
    THIS layer's arrays."""
    B, S, E = x.shape
    D = E // n_head
    lora_l = lora_l or {}
    h = _ln(x, bp["ln_1"]["g"], bp["ln_1"]["b"], eps)
    qkv = _lora(h @ bp["attn"]["qkv_w"] + bp["attn"]["qkv_b"], h,
                lora_l.get("attn_qkv"))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    heads = lambda t: t.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
    q, k, v = heads(q), heads(k), heads(v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(F32(D))
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, E)
    x = x + _lora(ctx @ bp["attn"]["proj_w"] + bp["attn"]["proj_b"], ctx,
                  lora_l.get("attn_proj"))
    h = _ln(x, bp["ln_2"]["g"], bp["ln_2"]["b"], eps)
    fc = _lora(h @ bp["mlp"]["fc_w"] + bp["mlp"]["fc_b"], h,
               lora_l.get("mlp_fc_in"))
    act = _gelu_new(fc)
    return x + _lora(act @ bp["mlp"]["proj_w"] + bp["mlp"]["proj_b"], act,
                     lora_l.get("mlp_fc_out"))


def _layer(tree, l):
    return jax.tree.map(lambda a: a[l], tree)


def embed(params, ids):
    """Token + learned position embeddings, [B, S, E] float32."""
    ids = jnp.asarray(ids)
    return (params["wte"][ids]
            + params["wpe"][jnp.arange(ids.shape[1])]).astype(F32)


def run_blocks(cfg: dict, blocks, x, lora_blocks=None, layers=None):
    """Apply the blocks `layers` (default: all) of the stacked trees to
    x, one after another. lora_blocks: None or {target: {"A": [L, in,
    r], "B": [L, r, out], "scale"}} stacked like `blocks`."""
    n = jax.tree.leaves(blocks)[0].shape[0]
    with jax.default_matmul_precision("highest"):
        for l in (range(n) if layers is None else layers):
            lora_l = None
            if lora_blocks is not None:
                lora_l = {t: {"A": e["A"][l], "B": e["B"][l],
                              "scale": jnp.asarray(e["scale"], F32)}
                          for t, e in lora_blocks.items()}
            x = block(x, _layer(blocks, l), lora_l, n_head=cfg["n_head"],
                      eps=float(cfg["layer_norm_epsilon"]))
    return x


def head(cfg: dict, params, x):
    """Final LayerNorm and the tied output head: logits [B, S, V]."""
    with jax.default_matmul_precision("highest"):
        x = _ln(x, params["ln_f"]["g"], params["ln_f"]["b"],
                float(cfg["layer_norm_epsilon"]))
        return x @ params["wte"].T


def forward(cfg: dict, params, ids, lora=None):
    """Logits [B, S, V] in float32. cfg: the configuration FILE's keys
    (n_head, layer_norm_epsilon). lora: None or {"blocks": {...}}."""
    with jax.default_matmul_precision("highest"):
        x = run_blocks(cfg, params["blocks"], embed(params, ids),
                       None if lora is None else lora["blocks"])
        return head(cfg, params, x)


def loss(logits, labels):
    """Mean next-token negative log-likelihood: position t predicts
    labels[t + 1]; labels equal to -100 are ignored."""
    lg, lb = logits[:, :-1], labels[:, 1:]
    logp = jax.nn.log_softmax(lg.astype(F32), axis=-1)
    valid = lb != IGNORE
    nll = -jnp.take_along_axis(
        logp, jnp.where(valid, lb, 0)[..., None], axis=-1)[..., 0]
    return (nll * valid).sum() / valid.sum()


def loss_of(cfg: dict, params, ids, labels, lora=None):
    return loss(forward(cfg, params, ids, lora), labels)
