"""What a served model family is: one record.

Everything that differs between GPT-2 and Gemma-3 outside their own
model modules is a field of `Family`, and every other module asks the
record instead of comparing a name: the serve programs
(models/generate.py: one prefill, one prefill chunk, one paged decode
step, each a skeleton over the record's block functions), the serve
engine and its weight placement (serve/), the multi-tenant trainer
(multitenant/) and the eval / generate CLIs (cli/). Serving another
architecture is writing another record (tests/test_family.py serves one
made in the test).

The decode-time block is five plain functions over ONE layer's weights
`bp` and R rows (R = slots in a decode step, the chunk width in a
prefill chunk); `sites(y, x, name, i)` adds the routed adapter `name`
of layer i to a product y = x @ W (generate.AdapterSites), `shd` is a
serve/sharding.ServeSharding or None:

  embed(config, params, tok, pos, dtype)            -> x [R, E]
  pos_ctx(config, pos)                              -> what qkv needs of
      the rows' positions (Gemma's rotary tables; None for GPT-2)
  qkv(config, x, bp, i, layer_x, ctx, sites, shd)   -> q [R, KV, G, D],
      k [R, KV, D], v [R, KV, D] (its own pre-norm inside)
  layer_ok(layer_x, ok, win_ok)                     -> this layer's mask
      from the causal one and the window's (None where `window` is)
  post(config, x, attn, bp, i, sites, shd)          -> x after the output
      projection, the MLP and both residuals (attn [R, nq*D])
  final_norm(config, params, x, dtype)              -> x before the tied
      head, params[head_key]

`layer_xs(config)` is the static per-layer data scanned beside the
weights and handed to qkv / layer_ok as `layer_x` (Gemma's is_global).

Arrows point one way: this module imports the model modules, lora/,
io/checkpoints and the tokenizers; serve/, multitenant/ and cli/ import
it; it imports none of them.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Any, Callable, NamedTuple, Optional

import jax.numpy as jnp

from mobilefinetuner_tpu.core.config import GPT2Config, Gemma3TextConfig
from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.data.tokenizer_bpe import GPT2BPETokenizer
from mobilefinetuner_tpu.data.tokenizer_gemma import GemmaTokenizer
from mobilefinetuner_tpu.io.checkpoints import load_gemma3, load_gpt2
from mobilefinetuner_tpu.lora import lora as lora_mod
from mobilefinetuner_tpu.lora import peft_io
from mobilefinetuner_tpu.models import gemma3, gpt2
from mobilefinetuner_tpu.ops.rope import apply_rope, rope_cos_sin

log = get_logger()


class Dims(NamedTuple):
    """A family's shape facts, read from one config."""
    L: int                   # layers
    nq: int                  # query heads
    KV: int                  # KV heads (the page pools' head axis)
    D: int                   # head dim
    scale: float             # softmax scale
    cap: Optional[int]       # hard position cap (learned positions)
    window: Optional[int]    # sliding window of the local layers


@dataclasses.dataclass(frozen=True, repr=False)
class Family:
    name: str
    config_type: type
    model: Any               # its module: init_params / hidden_states /
                             # forward, one keyword signature
    head_key: str            # tied lm_head weight: params[head_key] [V, E]
    dims: Callable[[Any], Dims]
    max_len: Callable[[Any], int]        # trained context length
    # the decode-time block (module docstring)
    layer_xs: Callable
    embed: Callable
    pos_ctx: Callable
    qkv: Callable
    layer_ok: Callable
    post: Callable
    final_norm: Callable
    # serve placement under tp (serve/sharding.py): block leaves sharded
    # on the output-feature (last) axis, those that join them only when
    # the pools' KV axis shards, and those sharded on the input-feature
    # (second-to-last) axis. Biases ride their matmul's output axis;
    # everything unlisted (embeds, norms, row-parallel biases)
    # replicates. GPT-2's fused qkv_w [E, 3E] shards the packed 3E axis:
    # a tp boundary can cross the Q/K/V section edges — semantically
    # fine under GSPMD (the jnp.split resharding is part of the pinned
    # census), and head-aligned within each section because E % tp == 0.
    col_leaves: frozenset
    kv_col_leaves: frozenset
    row_leaves: frozenset
    # adapters and I/O
    init_lora: Callable                  # (config, LoRASpec, key) -> tree
    lora_init: str                       # LoRASpec.init where a job names none
    merge_fn: Callable                   # (params, lora) -> merged params
    load: Callable[[str], tuple]         # model dir -> (config, params)
    load_tokenizer: Callable[[str], Any]
    encode_plain: Callable               # tok -> encoder adding no BOS
    # eval scores full [B, S, V] logits (a 50k vocabulary); otherwise the
    # chunked CE over the tied head (Gemma's 262k would not fit)
    full_logits_eval: bool

    def __repr__(self) -> str:
        return f"Family({self.name!r})"


# ----------------------------------------------------------- GPT-2 ----------

def _gpt2_dims(c: GPT2Config) -> Dims:
    return Dims(c.n_layer, c.n_head, c.n_head, c.head_dim,
                c.head_dim ** -0.5, c.n_positions, None)


def _gpt2_embed(c, params, tok, pos, dtype):
    return params["wte"][tok].astype(dtype) \
        + params["wpe"][pos].astype(dtype)


def _gpt2_qkv(c, x, bp, i, layer_x, ctx, sites, shd):
    R, E = x.shape
    H = c.n_head
    h = gpt2.layer_norm(x, bp["ln_1"]["g"], bp["ln_1"]["b"],
                        c.layer_norm_epsilon)
    qkv = h @ bp["attn"]["qkv_w"] + bp["attn"]["qkv_b"]
    qkv = sites(qkv, h, "attn_qkv", i)
    # split-QKV adapters hit their column range of the fused c_attn
    # output (models/gpt2.py _block, same site salts)
    for name, slot in lora_mod.GPT2_SPLIT_QKV_SLOTS.items():
        if name in sites:
            sl = (Ellipsis, slice(slot * E, (slot + 1) * E))
            qkv = qkv.at[sl].set(sites(qkv[sl], h, name, i))
    q, k, v = (z.reshape(R, H, E // H)
               for z in jnp.split(qkv, 3, axis=-1))
    if shd is not None:
        q, k, v = shd.kv_rows(q), shd.kv_rows(k), shd.kv_rows(v)
    return q[:, :, None, :], k, v


def _gpt2_post(c, x, attn, bp, i, sites, shd):
    proj = attn @ bp["attn"]["proj_w"] + bp["attn"]["proj_b"]
    proj = sites(proj, attn, "attn_proj", i)
    x = x + proj
    h2 = gpt2.layer_norm(x, bp["ln_2"]["g"], bp["ln_2"]["b"],
                         c.layer_norm_epsilon)
    fc = h2 @ bp["mlp"]["fc_w"] + bp["mlp"]["fc_b"]
    if shd is not None:
        fc = shd.hidden(fc)
    fc = gpt2.gelu_new(sites(fc, h2, "mlp_fc_in", i))
    out = fc @ bp["mlp"]["proj_w"] + bp["mlp"]["proj_b"]
    return x + sites(out, fc, "mlp_fc_out", i)


def _gpt2_final_norm(c, params, x, dtype):
    return gpt2.layer_norm(x, params["ln_f"]["g"].astype(dtype),
                           params["ln_f"]["b"].astype(dtype),
                           c.layer_norm_epsilon)


GPT2 = Family(
    name="gpt2", config_type=GPT2Config, model=gpt2, head_key="wte",
    dims=_gpt2_dims, max_len=lambda c: c.n_positions,
    layer_xs=lambda c: None, embed=_gpt2_embed,
    pos_ctx=lambda c, pos: None, qkv=_gpt2_qkv,
    layer_ok=lambda layer_x, ok, win_ok: ok, post=_gpt2_post,
    final_norm=_gpt2_final_norm,
    col_leaves=frozenset({"qkv_w", "qkv_b", "fc_w", "fc_b"}),
    kv_col_leaves=frozenset(), row_leaves=frozenset({"proj_w"}),
    init_lora=lora_mod.init_lora_gpt2, lora_init="gpt2",
    merge_fn=lora_mod.merge_gpt2, load=load_gpt2,
    load_tokenizer=GPT2BPETokenizer.from_pretrained,
    encode_plain=lambda tok: tok.encode, full_logits_eval=True)


# ---------------------------------------------------------- Gemma-3 ---------

def _gemma_dims(c: Gemma3TextConfig) -> Dims:
    return Dims(c.num_hidden_layers, c.num_attention_heads,
                c.num_key_value_heads, c.head_dim,
                c.query_pre_attn_scalar ** -0.5, None, c.sliding_window)


def _gemma_layer_xs(c):
    return jnp.asarray([c.is_global_layer(i)
                        for i in range(c.num_hidden_layers)])


def _gemma_embed(c, params, tok, pos, dtype):
    return params["embed"][tok].astype(dtype) \
        * jnp.asarray(c.hidden_size ** 0.5, dtype)


def _gemma_pos_ctx(c, pos):
    """The rows' rotary tables at both thetas: (global, local)."""
    return (rope_cos_sin(pos[:, None], c.head_dim, c.rope_theta),
            rope_cos_sin(pos[:, None], c.head_dim, c.rope_local_base_freq))


def _gemma_qkv(c, x, bp, i, glob, ctx, sites, shd):
    R = x.shape[0]
    nq, KV, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    eps = c.rms_norm_eps
    (cos_g, sin_g), (cos_l, sin_l) = ctx
    a = bp["attn"]
    h = gemma3.rms_norm(x, bp["input_ln"], eps)
    q = sites(h @ a["q_w"], h, "q_proj", i).reshape(R, nq, D)
    k = sites(h @ a["k_w"], h, "k_proj", i).reshape(R, KV, D)
    v = sites(h @ a["v_w"], h, "v_proj", i).reshape(R, KV, D)
    q = gemma3.rms_norm(q, a["q_norm"], eps)
    k = gemma3.rms_norm(k, a["k_norm"], eps)
    cos = jnp.where(glob, cos_g, cos_l)
    sin = jnp.where(glob, sin_g, sin_l)
    # apply_rope expects [..., S, D]; insert S=1
    q = apply_rope(q[:, :, None, :], cos, sin)[:, :, 0]
    k = apply_rope(k[:, :, None, :], cos, sin)[:, :, 0]
    q = q.reshape(R, KV, nq // KV, D)
    if shd is not None:
        k, v = shd.kv_rows(k), shd.kv_rows(v)
        q = shd.heads4(q)
    return q, k, v


def _gemma_layer_ok(glob, ok, win_ok):
    """Global layers attend the causal span, local ones its window
    (over absolute positions: serve sequences are unpadded, so the
    column index IS the position)."""
    return jnp.where(glob, ok, ok & win_ok)


def _gemma_post(c, x, attn, bp, i, sites, shd):
    eps = c.rms_norm_eps
    attn_out = sites(attn @ bp["attn"]["o_w"], attn, "o_proj", i)
    attn_out = gemma3.rms_norm(attn_out, bp["post_attn_ln"], eps)
    x = x + attn_out
    h2 = gemma3.rms_norm(x, bp["pre_ffn_ln"], eps)
    act = gemma3.gelu_tanh(
        sites(h2 @ bp["mlp"]["gate_w"], h2, "gate_proj", i)) \
        * sites(h2 @ bp["mlp"]["up_w"], h2, "up_proj", i)
    if shd is not None:
        act = shd.hidden(act)
    down = sites(act @ bp["mlp"]["down_w"], act, "down_proj", i)
    down = gemma3.rms_norm(down, bp["post_ffn_ln"], eps)
    return x + down


def _gemma_final_norm(c, params, x, dtype):
    return gemma3.rms_norm(x, params["final_norm"].astype(dtype),
                           c.rms_norm_eps)


GEMMA = Family(
    name="gemma", config_type=Gemma3TextConfig, model=gemma3,
    head_key="embed", dims=_gemma_dims,
    max_len=lambda c: c.max_position_embeddings,
    layer_xs=_gemma_layer_xs, embed=_gemma_embed, pos_ctx=_gemma_pos_ctx,
    qkv=_gemma_qkv, layer_ok=_gemma_layer_ok, post=_gemma_post,
    final_norm=_gemma_final_norm,
    col_leaves=frozenset({"q_w", "gate_w", "up_w"}),
    kv_col_leaves=frozenset({"k_w", "v_w"}),
    row_leaves=frozenset({"o_w", "down_w"}),
    init_lora=lora_mod.init_lora_gemma3, lora_init="peft",
    merge_fn=lora_mod.merge_gemma3, load=load_gemma3,
    load_tokenizer=GemmaTokenizer.from_pretrained,
    # the letter-id lookup and the eval stream must not see the auto-BOS
    encode_plain=lambda tok: functools.partial(tok.encode, add_bos=False),
    full_logits_eval=False)


# ----------------------------------------------------------- lookup ---------

FAMILIES = {f.name: f for f in (GPT2, GEMMA)}


def get_family(name: str) -> Family:
    try:
        return FAMILIES[name]
    except KeyError:
        raise ValueError(f"unknown family {name!r}") from None


def family_of(config) -> Family:
    """The record whose config type `config` is."""
    for fam in FAMILIES.values():
        if isinstance(config, fam.config_type):
            return fam
    raise ValueError(f"no family for config {type(config).__name__}")


def detect_family(model_dir: str) -> str:
    """gpt2 vs gemma from config.json (model_type or nested text_config)."""
    with open(os.path.join(model_dir, "config.json")) as f:
        raw = json.load(f)
    mt = str(raw.get("model_type", "")).lower()
    if "gemma" in mt or "text_config" in raw:
        return GEMMA.name
    return GPT2.name


# ------------------------------------------------ a loaded checkpoint -------

@dataclasses.dataclass
class FamilyBundle:
    """The record plus what was loaded from --pretrained_dir."""
    family: Family
    config: Any
    params: Any              # host numpy tree (device_put is the caller's
                             # decision — see eval_ppl's commit-once note)
    tok: Any

    @property
    def max_len(self) -> int:
        return self.family.max_len(self.config)


def load_family(pretrained_dir: str, family: str = "auto") -> FamilyBundle:
    if family == "auto":
        try:
            family = detect_family(pretrained_dir)
        except OSError:
            raise SystemExit(
                f"no readable config.json under {pretrained_dir}")
    log.info(f"model family: {family}")
    fam = get_family(family)
    config, params = fam.load(pretrained_dir)
    return FamilyBundle(fam, config, params,
                        fam.load_tokenizer(pretrained_dir))


def apply_adapter(bundle: FamilyBundle, lora_path: str,
                  lora_merge: bool) -> Optional[Any]:
    """Load an adapter; merged -> fold into bundle.params and return None,
    dynamic -> return the LoRA tree for the model's lora= argument."""
    if not lora_path:
        return None
    lora, spec = peft_io.load_adapter(lora_path)
    log.info(f"adapter: r={spec.rank} alpha={spec.alpha} "
             f"targets={spec.targets} "
             f"({'merged' if lora_merge else 'dynamic'})")
    if lora_merge:
        bundle.params = bundle.family.merge_fn(bundle.params, lora)
        return None
    return lora
