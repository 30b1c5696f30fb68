"""LoRA: spec, initialization, merge/unmerge, trainable-subset partitioning.

TPU-native re-design of the reference's LoRA machinery
(reference: graph/lora_injector.{h,cpp} for GPT-2,
graph/gemma_lora_injector.{h,cpp} for Gemma, nn/lora_linear.{h,cpp}).
The reference wraps each linear in a LoRALinear module holding pointers to
the frozen base weight; here LoRA is a *separate pytree* of stacked per-layer
A/B factors that the model forward adds functionally
(y = x@W + scale·(x@A@B)), so:
  - base params stay frozen by construction (grads are taken w.r.t. the LoRA
    tree only via jax.grad argnums),
  - FSDP can shard base params independently of the tiny trainable tree
    (SURVEY.md §7 hard part (c)),
  - merge/unmerge is a pure pytree->pytree function.

Entry layout per target: {"A": [L, in, r], "B": [L, r, out], "scale": ()}
with scale = alpha/rank (lora_injector.h:29-71). "scale" leaves are
non-trainable: forward stop-gradients them and trainable_mask() excludes
them from optimizer updates.

Init parity (SURVEY.md §2.5):
  - gpt2 style: A ~ N(0, 1/sqrt(r)), B = 0 (lora_injector.cpp:18-42) — but
    seeded jax.random instead of the reference's std::random_device
    (SURVEY.md §2.12.6: the reference is non-reproducible; we are).
  - peft style (Gemma, gemma_lora_injector.cpp:31): kaiming_uniform(a=√5)
    on A = U(-1/sqrt(in), 1/sqrt(in)) scaled by gain for fan_in; B = 0.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# GPT-2 target name -> (in_dim_fn, out_dim_fn) over config
GPT2_TARGETS = {
    "attn_qkv": lambda c: (c.n_embd, 3 * c.n_embd),
    # split-QKV: separate adapters on the q/k/v column ranges of the fused
    # c_attn projection (reference: lora_injector.h:169-191 Hook
    # col_offset/col_size) — finer-grained than the fused default
    "attn_q": lambda c: (c.n_embd, c.n_embd),
    "attn_k": lambda c: (c.n_embd, c.n_embd),
    "attn_v": lambda c: (c.n_embd, c.n_embd),
    "attn_proj": lambda c: (c.n_embd, c.n_embd),
    "mlp_fc_in": lambda c: (c.n_embd, 4 * c.n_embd),
    "mlp_fc_out": lambda c: (4 * c.n_embd, c.n_embd),
    # head adapter on the tied lm_head (logits = x @ wte^T): a SINGLE
    # unstacked site — A [E, r], B [r, V] — applied at the logits
    # projection. Opt-in (never part of the defaults/presets): its delta
    # rides the chunked-CE/fused-CE epilogue so [B, S, V] never
    # materializes in training (DESIGN.md §17); merge is refused (the
    # table is tied — folding ΔW in would change the input lookup too).
    "lm_head": lambda c: (c.n_embd, c.vocab_size),
}
# column slot of each split target within the fused [E, 3E] c_attn weight
GPT2_SPLIT_QKV_SLOTS = {"attn_q": 0, "attn_k": 1, "attn_v": 2}
# Default PEFT-aligned GPT-2 topology: fused c_attn + c_proj
# (reference: gpt2_lora_finetune/main.cpp:381-390).
GPT2_DEFAULT_TARGETS = ["attn_qkv", "attn_proj"]

GEMMA_TARGETS = {
    "q_proj": lambda c: (c.hidden_size, c.num_attention_heads * c.head_dim),
    "k_proj": lambda c: (c.hidden_size, c.num_key_value_heads * c.head_dim),
    "v_proj": lambda c: (c.hidden_size, c.num_key_value_heads * c.head_dim),
    "o_proj": lambda c: (c.num_attention_heads * c.head_dim, c.hidden_size),
    "gate_proj": lambda c: (c.hidden_size, c.intermediate_size),
    "up_proj": lambda c: (c.hidden_size, c.intermediate_size),
    "down_proj": lambda c: (c.intermediate_size, c.hidden_size),
    "lm_head": lambda c: (c.hidden_size, c.vocab_size),  # tied embed head
}
# AFMoE (Arcee Trinity): the attention projections of every layer, dense
# and expert alike — the usual set for expert models. The output gate,
# the router, the shared and the routed experts stay frozen.
AFMOE_TARGETS = {t: GEMMA_TARGETS[t]
                 for t in ("q_proj", "k_proj", "v_proj", "o_proj")}
# The Ling-3.0-flash hybrid (models/ling_hybrid.py): two kinds of mixer
# with different weights, so a target names its kind and its stack runs
# over the layers OF THAT KIND only (`kda_*` [number of kda layers, ...],
# `mla_*` [number of mla layers, ...]). The projections that carry
# attention's content; gates, conv taps, norms, router and experts stay
# frozen.
LING_TARGETS = {
    "kda_q_proj": lambda c: (c.hidden_size,
                             c.num_attention_heads * c.head_dim),
    "kda_k_proj": lambda c: (c.hidden_size,
                             c.num_attention_heads * c.head_dim),
    "kda_v_proj": lambda c: (c.hidden_size,
                             c.num_attention_heads * c.head_dim),
    "kda_o_proj": lambda c: (c.num_attention_heads * c.head_dim,
                             c.hidden_size),
    "mla_q_proj": lambda c: (c.hidden_size,
                             c.num_attention_heads * c.qk_head_dim),
    "mla_kv_a_proj": lambda c: (c.hidden_size,
                                c.kv_lora_rank + c.qk_rope_head_dim),
    "mla_kv_b_proj": lambda c: (
        c.kv_lora_rank,
        c.num_attention_heads * (c.qk_nope_head_dim + c.v_head_dim)),
    "mla_o_proj": lambda c: (c.num_attention_heads * c.v_head_dim,
                             c.hidden_size),
}
# Xing4.0 (models/xing4.py): the five projections of latent attention,
# both latents' down- and up-projections and the output, in every block
# — stacks over the layers and then the multi-token-prediction module's
# block (position num_hidden_layers). Hyper-connection coefficients,
# norms, router, experts, the module's merge, embedding and head stay
# frozen.
XING4_TARGETS = {
    "q_a_proj": lambda c: (c.hidden_size, c.q_lora_rank),
    "q_b_proj": lambda c: (c.q_lora_rank,
                           c.num_attention_heads * c.qk_head_dim),
    "kv_a_proj": lambda c: (c.hidden_size,
                            c.kv_lora_rank + c.qk_rope_head_dim),
    "kv_b_proj": lambda c: (
        c.kv_lora_rank,
        c.num_attention_heads * (c.qk_nope_head_dim + c.v_head_dim)),
    "o_proj": lambda c: (c.num_attention_heads * c.v_head_dim,
                         c.hidden_size),
}
# Phi-4-mini-flash (models/phi4flash.py): a target's stack runs over the
# layers of its kind in layer order (models/phi4flash.stack_layer_ids):
# q/o over every attention layer, k/v over those with keys of their own,
# in/out over the Mamba layers, gmu_in/gmu_out over the gated memory
# units. The conv, x/dt projections, A_log, D, lambdas, norms, MLPs,
# embedding and head stay frozen.
PHI4FLASH_TARGETS = {
    "q_proj": lambda c: (c.hidden_size,
                         c.num_attention_heads * c.head_dim),
    "k_proj": lambda c: (c.hidden_size,
                         c.num_key_value_heads * c.head_dim),
    "v_proj": lambda c: (c.hidden_size,
                         c.num_key_value_heads * c.head_dim),
    "o_proj": lambda c: (c.num_attention_heads * c.head_dim,
                         c.hidden_size),
    "in_proj": lambda c: (c.hidden_size, 2 * c.ssm_inner),
    "out_proj": lambda c: (c.ssm_inner, c.hidden_size),
    "gmu_in_proj": lambda c: (c.hidden_size, c.ssm_inner),
    "gmu_out_proj": lambda c: (c.ssm_inner, c.hidden_size),
}
# targets with ONE site instead of a per-layer stack: A [in, r],
# B [r, out] (no leading L axis; maybe_lora's ndim checks skip the
# layer_idx slice for them)
UNSTACKED_TARGETS = frozenset({"lm_head"})
# Target presets (reference: gemma_lora_injector.h:9-34). lm_head is
# opt-in only — "full" keeps the reference's per-layer target set.
GEMMA_PRESETS = {
    "full": [t for t in GEMMA_TARGETS if t not in UNSTACKED_TARGETS],
    "attn": ["q_proj", "k_proj", "v_proj", "o_proj"],
    "light": ["q_proj", "v_proj"],
}


@dataclasses.dataclass
class LoRASpec:
    rank: int = 8
    alpha: float = 16.0
    dropout: float = 0.0
    targets: Optional[List[str]] = None
    init: str = "gpt2"  # "gpt2" | "peft"

    @property
    def scale(self) -> float:
        return self.alpha / self.rank

    def to_metadata(self) -> Dict[str, str]:
        return {"lora_rank": str(self.rank), "lora_alpha": str(self.alpha),
                "lora_dropout": str(self.dropout),
                "lora_targets": ",".join(self.targets or []),
                "lora_init": self.init}

    @classmethod
    def from_metadata(cls, md: Dict[str, str]) -> "LoRASpec":
        return cls(rank=int(md["lora_rank"]),
                   alpha=float(md["lora_alpha"]),
                   dropout=float(md.get("lora_dropout", 0.0)),
                   targets=[t for t in md.get("lora_targets", "").split(",")
                            if t],
                   init=md.get("lora_init", "gpt2"))


def _init_A(key, shape, style: str, dtype):
    """shape = [L, in, r]."""
    _, fan_in, r = shape
    if style == "peft":
        # torch kaiming_uniform_(a=sqrt(5)) on a [r, in] matrix:
        # bound = sqrt(3) * (1/sqrt(5+1) gain...) — torch computes
        # gain = sqrt(2/(1+a^2)) = sqrt(1/3), std = gain/sqrt(fan_in),
        # bound = sqrt(3)*std = 1/sqrt(fan_in).
        bound = 1.0 / math.sqrt(fan_in)
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    # reference GPT-2 init: N(0, 1/sqrt(r)) (lora_injector.cpp:18-42)
    return (jax.random.normal(key, shape) / math.sqrt(r)).astype(dtype)


def init_lora(target_dims: Dict[str, Tuple[int, int]], n_layers: int,
              spec: LoRASpec, key: jax.Array, dtype=jnp.float32) -> dict:
    """Build the stacked LoRA pytree for the given targets."""
    tree = {}
    keys = jax.random.split(key, max(len(target_dims), 1))
    for k, name in zip(keys, sorted(target_dims)):
        fan_in, fan_out = target_dims[name]
        if name in UNSTACKED_TARGETS:  # single site, no layer stack
            tree[name] = {
                "A": _init_A(k, (1, fan_in, spec.rank), spec.init,
                             dtype)[0],
                "B": jnp.zeros((spec.rank, fan_out), dtype),
                "scale": jnp.asarray(spec.scale, dtype),
            }
            continue
        tree[name] = {
            "A": _init_A(k, (n_layers, fan_in, spec.rank), spec.init, dtype),
            "B": jnp.zeros((n_layers, spec.rank, fan_out), dtype),
            "scale": jnp.asarray(spec.scale, dtype),
        }
    return {"blocks": tree}


def init_lora_gpt2(config, spec: LoRASpec, key: jax.Array,
                   dtype=jnp.float32) -> dict:
    targets = spec.targets or GPT2_DEFAULT_TARGETS
    dims = {t: GPT2_TARGETS[t](config) for t in targets}
    return init_lora(dims, config.n_layer, spec, key, dtype)


def init_lora_gemma3(config, spec: LoRASpec, key: jax.Array,
                     dtype=jnp.float32) -> dict:
    targets = spec.targets or GEMMA_PRESETS["full"]
    if isinstance(targets, str):
        targets = GEMMA_PRESETS[targets]
    dims = {t: GEMMA_TARGETS[t](config) for t in targets}
    return init_lora(dims, config.num_hidden_layers, spec, key, dtype)


def init_lora_afmoe(config, spec: LoRASpec, key: jax.Array,
                    dtype=jnp.float32) -> dict:
    """Stacks over ALL layers, the dense ones first (models/afmoe.py)."""
    dims = {t: AFMOE_TARGETS[t](config)
            for t in spec.targets or list(AFMOE_TARGETS)}
    return init_lora(dims, config.num_hidden_layers, spec, key, dtype)


def init_lora_ling(config, spec: LoRASpec, key: jax.Array,
                   dtype=jnp.float32) -> dict:
    """One stack a target, over the layers of the target's kind (the
    prefix of its name) in layer order."""
    kinds = config.layer_kinds
    tree = {}
    for name in sorted(spec.targets or list(LING_TARGETS)):
        n = kinds.count(name.split("_")[0])
        if n:
            tree.update(init_lora(
                {name: LING_TARGETS[name](config)}, n, spec,
                jax.random.fold_in(key, sorted(LING_TARGETS).index(name)),
                dtype)["blocks"])
    return {"blocks": tree}


def init_lora_xing4(config, spec: LoRASpec, key: jax.Array,
                    dtype=jnp.float32) -> dict:
    """Stacks over the layers, then the multi-token-prediction module's
    block where the model has one (models/xing4.py)."""
    dims = {t: XING4_TARGETS[t](config)
            for t in spec.targets or list(XING4_TARGETS)}
    return init_lora(
        dims, config.num_hidden_layers + config.num_nextn_predict_layers,
        spec, key, dtype)


def init_lora_phi4flash(config, spec: LoRASpec, key: jax.Array,
                        dtype=jnp.float32) -> dict:
    """One stack a target, over the layers of the target's kind
    (models/phi4flash.stack_sizes)."""
    from mobilefinetuner_tpu.models.phi4flash import stack_sizes
    n = stack_sizes(config)
    tree = {}
    for name in sorted(spec.targets or list(PHI4FLASH_TARGETS)):
        tree.update(init_lora(
            {name: PHI4FLASH_TARGETS[name](config)}, n[name], spec,
            jax.random.fold_in(key, sorted(PHI4FLASH_TARGETS).index(name)),
            dtype)["blocks"])
    return {"blocks": tree}


def stack_adapters(loras) -> dict:
    """Stack N same-shaped adapter trees along a new leading ADAPTER axis
    (multi-adapter batched serving, models/lora_apply.py). All adapters
    must share rank and target set; scale stacks to [N] so per-adapter
    alpha/r survives."""
    if not loras:
        raise ValueError("stack_adapters needs at least one adapter")
    ref = jax.tree.structure(loras[0])
    ref_flat = jax.tree_util.tree_flatten_with_path(loras[0])[0]
    for i, t in enumerate(loras[1:], 1):
        if jax.tree.structure(t) != ref:
            names = sorted(t.get("blocks", {})) if isinstance(t, dict) \
                else []
            ref_names = sorted(loras[0].get("blocks", {}))
            raise ValueError(
                f"adapter {i} has different targets/structure than "
                f"adapter 0: targets {names} vs {ref_names} "
                f"(multi-adapter serving needs identical rank + target "
                f"sets)")
        flat = jax.tree_util.tree_flatten_with_path(t)[0]
        for (path, x0), (_, xi) in zip(ref_flat, flat):
            if x0.shape != xi.shape:
                # keystr spelling varies across jax versions; build the
                # path by hand for a stable message
                leaf = "".join(str(p) for p in path)
                raise ValueError(
                    f"adapter {i} leaf {leaf} has shape "
                    f"{tuple(xi.shape)} but adapter 0 has "
                    f"{tuple(x0.shape)} (rank/dim mismatch — stacked "
                    f"serving needs identical shapes)")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *loras)


def unstack_adapter(stacked: dict, index: int) -> dict:
    """Inverse of one stack_adapters slot: slice adapter `index` out of a
    stacked [k, ...] bank back to the solo tree layout (A [L, in, r],
    B [L, r, out], scalar scale). The multi-tenant engine's save path
    uses this so a bank-trained adapter round-trips through peft_io
    BYTE-IDENTICAL to a solo-trained one (tests/test_multitenant.py pins
    the file bytes) — the serve/eval/PEFT consumers never learn the
    adapter was trained in a bank. Routing `ids` leaves (assign_adapters)
    are dropped: they are batch data, not adapter state."""
    first = next(iter(stacked["blocks"].values()))
    # .shape on the leaf directly: this runs on the async writer thread
    # over HOST snapshots, and a jnp.asarray just to read a dimension
    # would copy the whole stacked bank to the device
    n = int(first["A"].shape[0])
    if not (0 <= index < n):
        raise ValueError(
            f"adapter index {index} out of range for a stacked bank of "
            f"{n} adapter(s) (valid: 0..{n - 1})")
    out = dict(stacked)
    out["blocks"] = {
        name: {leaf: v[index] for leaf, v in entry.items()
               if leaf != "ids"}
        for name, entry in stacked["blocks"].items()}
    return out


def assign_adapters(stacked: dict, adapter_ids) -> dict:
    """Route batch rows to adapters: insert the per-row index array into
    every site entry of a stack_adapters tree. SERVING/EVAL only: the
    returned tree drops into the models' `lora=` argument for forwards
    and generation, but it is not a trainable tree (the int32 "ids" leaf
    cannot be differentiated, and routing indices are not parameters —
    trainable_mask excludes them).

    Concrete ids are validated against the stacked bank size here — a
    jnp gather CLAMPS out-of-range indices, so an id typo would silently
    serve every overflowing row from the LAST adapter in the bank (the
    worst possible failure for multi-tenant routing: tenant A quietly
    gets tenant Z's weights). Traced ids (the serve engine routes inside
    its compiled step) skip the check; the engine's bank resolution is
    the validator there."""
    ids = jnp.asarray(adapter_ids, jnp.int32)
    first = next(iter(stacked["blocks"].values()))
    n = int(first["A"].shape[0])
    if not isinstance(ids, jax.core.Tracer):
        concrete = np.asarray(ids)
        bad = concrete[(concrete < 0) | (concrete >= n)]
        if bad.size:
            raise ValueError(
                f"adapter id(s) {sorted(set(int(b) for b in bad))} out "
                f"of range for a stacked bank of {n} adapter(s) "
                f"(valid: 0..{n - 1})")
    out = dict(stacked)
    out["blocks"] = {name: dict(entry, ids=ids)
                     for name, entry in stacked["blocks"].items()}
    return out


def trainable_mask(lora_tree) -> dict:
    """Pytree of bools: True for trainable leaves (A/B), False for scale
    and for multi-adapter routing ids. Feed to the optimizer so those are
    never updated/decayed."""
    # tree_util spelling: jax.tree.map_with_path only exists on newer jax
    return jax.tree_util.tree_map_with_path(
        lambda path, _: not (path and getattr(path[-1], "key", None)
                             in ("scale", "ids")),
        lora_tree)


def num_trainable(lora_tree) -> int:
    mask = trainable_mask(lora_tree)
    return sum(int(x.size) for x, m in
               zip(jax.tree.leaves(lora_tree), jax.tree.leaves(mask)) if m)


def _delta_w(entry) -> jnp.ndarray:
    """[L, in, out] = scale * A @ B per layer."""
    return entry["scale"] * jnp.einsum("lir,lro->lio", entry["A"],
                                       entry["B"])


# name of the base-weight leaf each target modifies, per model family;
# an optional third element is the column slot within the fused weight
# (split-QKV, lora_injector.h:169-191)
_GPT2_BASE = {"attn_qkv": ("attn", "qkv_w"), "attn_proj": ("attn", "proj_w"),
              "mlp_fc_in": ("mlp", "fc_w"), "mlp_fc_out": ("mlp", "proj_w"),
              **{name: ("attn", "qkv_w", slot)
                 for name, slot in GPT2_SPLIT_QKV_SLOTS.items()}}
_GEMMA_BASE = {"q_proj": ("attn", "q_w"), "k_proj": ("attn", "k_w"),
               "v_proj": ("attn", "v_w"), "o_proj": ("attn", "o_w"),
               "gate_proj": ("mlp", "gate_w"), "up_proj": ("mlp", "up_w"),
               "down_proj": ("mlp", "down_w")}


def _merge(params, lora_tree, base_map, sign: float):
    """params + sign * ΔW on every LoRA'd base weight (functional).
    Split targets add their ΔW into the matching column range of the
    fused weight."""
    params = jax.tree.map(jnp.asarray, params)
    blocks = dict(params["blocks"])
    groups = {g: dict(blocks[g]) for g in {v[0] for v in base_map.values()}}
    for name, entry in lora_tree["blocks"].items():
        if name not in base_map:
            raise ValueError(
                f"target {name!r} cannot be merged into the base "
                f"weights (the lm_head is TIED to the embedding table — "
                f"folding its ΔW in would change the input lookup too); "
                f"serve it dynamically via the lora= argument")
        spec = base_map[name]
        group, leaf = spec[0], spec[1]
        w = groups[group][leaf]
        delta = sign * _delta_w(entry).astype(w.dtype)
        if len(spec) == 3:
            out = delta.shape[-1]
            col0 = spec[2] * out
            w = w.at[:, :, col0:col0 + out].add(delta)
        else:
            w = w + delta
        groups[group][leaf] = w
    blocks.update(groups)
    out = dict(params)
    out["blocks"] = blocks
    return out


def merge_gpt2(params, lora_tree):
    """Fold ΔW into base weights (reference: lora_linear.cpp:109-176
    merge; used by eval with --merge)."""
    return _merge(params, lora_tree, _GPT2_BASE, +1.0)


def unmerge_gpt2(params, lora_tree):
    return _merge(params, lora_tree, _GPT2_BASE, -1.0)


def merge_gemma3(params, lora_tree):
    return _merge(params, lora_tree, _GEMMA_BASE, +1.0)


def unmerge_gemma3(params, lora_tree):
    return _merge(params, lora_tree, _GEMMA_BASE, -1.0)
