"""HF checkpoint <-> framework-pytree conversion for GPT-2 and Gemma-3.

Reference: operators/finetune_ops/graph/safetensors_loader.cpp
(`GPT2KeyMapper` mapping HF `h.i.attn.c_attn.*` -> internal keys;
`GemmaKeyMapper` mapping `model.layers.i.*`). Our internal layout stacks
per-layer tensors into [L, ...] arrays (models/gpt2.py, models/gemma3.py),
so "mapping" here is gather+stack rather than per-key rename.

GPT-2 Conv1D subtlety (SURVEY.md §7.3): HF GPT-2 linear weights are stored
[in, out] (Conv1D) and our models compute y = x @ W, so NO transpose is
applied — the same reason the reference CLI disables its loader transpose
(gpt2_lora_finetune/main.cpp:292-296). Gemma weights are true nn.Linear
[out, in]; we transpose those to [in, out] at load.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob as _glob
import json
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from mobilefinetuner_tpu.core.config import (AfmoeConfig, GPT2Config,
                                             LingHybridConfig,
                                             Gemma3TextConfig,
                                             Phi4FlashConfig, Xing4Config)
from mobilefinetuner_tpu.io.safetensors_io import (CheckpointIntegrityError,
                                                   SafeTensorsReader,
                                                   atomic_publish,
                                                   manifest_path,
                                                   save_safetensors,
                                                   verify_report)


def load_hf_state_dict(model_dir: str,
                       promote_to_f32: bool = True) -> Dict[str, np.ndarray]:
    """Load an HF checkpoint dir's full state dict — single-file or sharded
    (model.safetensors.index.json) layouts."""
    index = os.path.join(model_dir, "model.safetensors.index.json")
    if os.path.exists(index):
        import json
        with open(index) as f:
            weight_map = json.load(f)["weight_map"]
        out = {}
        for shard in sorted(set(weight_map.values())):
            reader = SafeTensorsReader(os.path.join(model_dir, shard))
            out.update(reader.load_all(promote_to_f32))
        return out
    return SafeTensorsReader(
        _find_weights_file(model_dir)).load_all(promote_to_f32)


def _find_weights_file(model_dir: str) -> str:
    for name in ("model.safetensors", "pytorch_model.safetensors"):
        p = os.path.join(model_dir, name)
        if os.path.exists(p):
            return p
    cands = [f for f in os.listdir(model_dir) if f.endswith(".safetensors")]
    if len(cands) == 1:
        return os.path.join(model_dir, cands[0])
    if cands:
        raise FileNotFoundError(
            f"multiple safetensors shards in {model_dir} but no "
            "model.safetensors.index.json")
    raise FileNotFoundError(f"no safetensors weights in {model_dir}")


def _strip_prefix(tensors: Dict[str, np.ndarray],
                  prefixes=("transformer.", "model.")) -> Dict[str, np.ndarray]:
    out = {}
    for k, v in tensors.items():
        for p in prefixes:
            if k.startswith(p):
                k = k[len(p):]
                break
        out[k] = v
    return out


# ----------------------------- GPT-2 ---------------------------------------

def gpt2_params_from_hf(tensors: Dict[str, np.ndarray],
                        config: GPT2Config) -> dict:
    """HF GPT2LMHeadModel state-dict -> stacked pytree (float32 numpy)."""
    t = _strip_prefix(tensors)
    L = config.n_layer

    def stack(fmt):
        return np.stack([t[fmt.format(i)] for i in range(L)])

    return {
        "wte": t["wte.weight"],
        "wpe": t["wpe.weight"],
        "blocks": {
            "ln_1": {"g": stack("h.{}.ln_1.weight"),
                     "b": stack("h.{}.ln_1.bias")},
            "attn": {
                "qkv_w": stack("h.{}.attn.c_attn.weight"),
                "qkv_b": stack("h.{}.attn.c_attn.bias"),
                "proj_w": stack("h.{}.attn.c_proj.weight"),
                "proj_b": stack("h.{}.attn.c_proj.bias"),
            },
            "ln_2": {"g": stack("h.{}.ln_2.weight"),
                     "b": stack("h.{}.ln_2.bias")},
            "mlp": {
                "fc_w": stack("h.{}.mlp.c_fc.weight"),
                "fc_b": stack("h.{}.mlp.c_fc.bias"),
                "proj_w": stack("h.{}.mlp.c_proj.weight"),
                "proj_b": stack("h.{}.mlp.c_proj.bias"),
            },
        },
        "ln_f": {"g": t["ln_f.weight"], "b": t["ln_f.bias"]},
    }


def gpt2_params_to_hf(params, prefix: str = "") -> Dict[str, np.ndarray]:
    """Stacked pytree -> HF GPT2LMHeadModel key scheme (for full-FT save,
    reference: gpt2_full_finetune/main.cpp:156-237)."""
    p = {k: np.asarray(v) for k, v in (
        ("wte.weight", params["wte"]), ("wpe.weight", params["wpe"]),
        ("ln_f.weight", params["ln_f"]["g"]),
        ("ln_f.bias", params["ln_f"]["b"]))}
    b = params["blocks"]
    L = np.asarray(b["ln_1"]["g"]).shape[0]
    names = [
        ("h.{}.ln_1.weight", b["ln_1"]["g"]),
        ("h.{}.ln_1.bias", b["ln_1"]["b"]),
        ("h.{}.attn.c_attn.weight", b["attn"]["qkv_w"]),
        ("h.{}.attn.c_attn.bias", b["attn"]["qkv_b"]),
        ("h.{}.attn.c_proj.weight", b["attn"]["proj_w"]),
        ("h.{}.attn.c_proj.bias", b["attn"]["proj_b"]),
        ("h.{}.ln_2.weight", b["ln_2"]["g"]),
        ("h.{}.ln_2.bias", b["ln_2"]["b"]),
        ("h.{}.mlp.c_fc.weight", b["mlp"]["fc_w"]),
        ("h.{}.mlp.c_fc.bias", b["mlp"]["fc_b"]),
        ("h.{}.mlp.c_proj.weight", b["mlp"]["proj_w"]),
        ("h.{}.mlp.c_proj.bias", b["mlp"]["proj_b"]),
    ]
    for fmt, arr in names:
        arr = np.asarray(arr)
        for i in range(L):
            p[fmt.format(i)] = arr[i]
    if prefix:
        p = {prefix + k: v for k, v in p.items()}
    return p


def load_gpt2(model_dir: str, config: Optional[GPT2Config] = None):
    """(config, params) from an HF GPT-2 checkpoint directory."""
    if config is None:
        config = GPT2Config.from_pretrained(model_dir)
    tensors = load_hf_state_dict(model_dir)
    return config, gpt2_params_from_hf(tensors, config)


def save_gpt2(path: str, params, metadata: Optional[dict] = None):
    save_safetensors(path, gpt2_params_to_hf(jax_to_numpy(params)),
                     metadata=metadata or {"format": "pt"})


# ----------------------------- Gemma-3 -------------------------------------

def gemma3_params_from_hf(tensors: Dict[str, np.ndarray],
                          config: Gemma3TextConfig) -> dict:
    """HF Gemma3ForCausalLM (text) state-dict -> stacked pytree.

    HF keys: model.embed_tokens.weight, model.layers.{i}.self_attn.{q,k,v,o}_proj.weight,
    ...input_layernorm, post_attention_layernorm, pre_feedforward_layernorm,
    post_feedforward_layernorm, self_attn.{q,k}_norm, mlp.{gate,up,down}_proj,
    model.norm.weight. Linear weights are [out, in] -> transposed to [in, out].
    """
    t = {}
    for k, v in tensors.items():
        if k.startswith("model."):
            k = k[len("model."):]
        t[k] = v
    L = config.num_hidden_layers

    def stack_w(fmt):  # linear weight: transpose [out,in] -> [in,out]
        return np.stack([t[fmt.format(i)].T for i in range(L)])

    def stack(fmt):
        return np.stack([t[fmt.format(i)] for i in range(L)])

    a = "layers.{}.self_attn."
    m = "layers.{}.mlp."
    return {
        "embed": t["embed_tokens.weight"],
        "blocks": {
            "input_ln": stack("layers.{}.input_layernorm.weight"),
            "attn": {
                "q_w": stack_w(a + "q_proj.weight"),
                "k_w": stack_w(a + "k_proj.weight"),
                "v_w": stack_w(a + "v_proj.weight"),
                "o_w": stack_w(a + "o_proj.weight"),
                "q_norm": stack(a + "q_norm.weight"),
                "k_norm": stack(a + "k_norm.weight"),
            },
            "post_attn_ln": stack("layers.{}.post_attention_layernorm.weight"),
            "pre_ffn_ln": stack("layers.{}.pre_feedforward_layernorm.weight"),
            "mlp": {
                "gate_w": stack_w(m + "gate_proj.weight"),
                "up_w": stack_w(m + "up_proj.weight"),
                "down_w": stack_w(m + "down_proj.weight"),
            },
            "post_ffn_ln": stack("layers.{}.post_feedforward_layernorm.weight"),
        },
        "final_norm": t["norm.weight"],
    }


def gemma3_params_to_hf(params) -> Dict[str, np.ndarray]:
    """Stacked pytree -> HF Gemma3 text key scheme (inverse of
    gemma3_params_from_hf; linear weights back to [out, in]). Used by the
    full-size synthetic-checkpoint pipeline and Gemma full-state saves."""
    p = {"model.embed_tokens.weight": np.asarray(params["embed"])}
    b = params["blocks"]
    L = np.asarray(b["input_ln"]).shape[0]
    a, m = "model.layers.{}.self_attn.", "model.layers.{}.mlp."
    per_layer = [
        ("model.layers.{}.input_layernorm.weight", b["input_ln"], False),
        (a + "q_proj.weight", b["attn"]["q_w"], True),
        (a + "k_proj.weight", b["attn"]["k_w"], True),
        (a + "v_proj.weight", b["attn"]["v_w"], True),
        (a + "o_proj.weight", b["attn"]["o_w"], True),
        (a + "q_norm.weight", b["attn"]["q_norm"], False),
        (a + "k_norm.weight", b["attn"]["k_norm"], False),
        ("model.layers.{}.post_attention_layernorm.weight",
         b["post_attn_ln"], False),
        ("model.layers.{}.pre_feedforward_layernorm.weight",
         b["pre_ffn_ln"], False),
        (m + "gate_proj.weight", b["mlp"]["gate_w"], True),
        (m + "up_proj.weight", b["mlp"]["up_w"], True),
        (m + "down_proj.weight", b["mlp"]["down_w"], True),
        ("model.layers.{}.post_feedforward_layernorm.weight",
         b["post_ffn_ln"], False),
    ]
    for fmt, arr, is_linear in per_layer:
        arr = np.asarray(arr)
        for i in range(L):
            p[fmt.format(i)] = arr[i].T if is_linear else arr[i]
    p["model.norm.weight"] = np.asarray(params["final_norm"])
    return p


def load_gemma3(model_dir: str, config: Optional[Gemma3TextConfig] = None):
    if config is None:
        config = Gemma3TextConfig.from_pretrained(model_dir)
    tensors = load_hf_state_dict(model_dir)
    return config, gemma3_params_from_hf(tensors, config)


def save_gemma3(path: str, params, metadata: Optional[dict] = None):
    """Full-model Gemma-3 save in the HF key scheme (save_gpt2 analog —
    the Gemma full-FT CLI's checkpoint; loads back via load_gemma3 or HF
    transformers)."""
    save_safetensors(path, gemma3_params_to_hf(jax_to_numpy(params)),
                     metadata=metadata or {"format": "pt"})


# ----------------------------- AFMoE ---------------------------------------
#
# HF `afmoe` key scheme (the family's public modelling code):
#   model.embed_tokens.weight, lm_head.weight (untied), model.norm.weight,
#   model.layers.{i}.self_attn.{q,k,v,o,gate}_proj.weight, .q_norm.weight,
#   .k_norm.weight, .input_layernorm / post_attention_layernorm /
#   pre_mlp_layernorm / post_mlp_layernorm .weight; dense layers
#   .mlp.{gate,up,down}_proj.weight; expert layers .mlp.router.gate.weight
#   [E, H], .mlp.expert_bias [E], .mlp.shared_experts.{gate,up,down}_proj,
#   .mlp.experts.{e}.{gate,up,down}_proj. Linear weights are [out, in].

_AFMOE_LAYER = "model.layers.{}."
_AFMOE_NORMS = (("input_ln", "input_layernorm"),
                ("post_attn_ln", "post_attention_layernorm"),
                ("pre_mlp_ln", "pre_mlp_layernorm"),
                ("post_mlp_ln", "post_mlp_layernorm"))
_AFMOE_ATTN = (("q_w", "q_proj"), ("k_w", "k_proj"), ("v_w", "v_proj"),
               ("o_w", "o_proj"), ("gate_w", "gate_proj"))
_SWIGLU = (("gate_w", "gate_proj"), ("up_w", "up_proj"),
           ("down_w", "down_proj"))


def _tensor_readers(model_dir: str) -> Dict[str, SafeTensorsReader]:
    """key -> the reader of the file that holds it (single-file or
    sharded layout), nothing loaded yet."""
    index = os.path.join(model_dir, "model.safetensors.index.json")
    if os.path.exists(index):
        with open(index) as f:
            weight_map = json.load(f)["weight_map"]
        readers = {sh: SafeTensorsReader(os.path.join(model_dir, sh))
                   for sh in sorted(set(weight_map.values()))}
        return {k: readers[sh] for k, sh in weight_map.items()}
    r = SafeTensorsReader(_find_weights_file(model_dir))
    return {k: r for k in r.keys()}


def load_afmoe(model_dir: str, experts_held: Optional[tuple] = None,
               config: Optional[AfmoeConfig] = None):
    """(config, params in models/afmoe.py's layout) from an HF-layout
    directory. Of each expert layer only the `experts_held = (first,
    count)` experts are READ (default: all); the absent ones' bytes are
    never touched. A BF16 tensor stays bf16 (`ml_dtypes.bfloat16`), so a
    frozen base published in bf16 is resident at 2 bytes a parameter."""
    if config is None:
        config = AfmoeConfig.from_pretrained(model_dir)
    if experts_held is not None:
        config = dataclasses.replace(config,
                                     experts_held=tuple(experts_held))
    c = config
    readers = _tensor_readers(model_dir)

    def get(key, linear=False):
        a = readers[key].load(key, keep_bf16=True)
        return a.T if linear else a      # [out, in] -> [in, out]

    def stack(keys, linear=False):
        return np.stack([get(k, linear) for k in keys])

    def layers(ids):
        out = {name: stack([_AFMOE_LAYER.format(i) + hf + ".weight"
                            for i in ids]) for name, hf in _AFMOE_NORMS}
        a = _AFMOE_LAYER + "self_attn.{}.weight"
        out["attn"] = {name: stack([a.format(i, hf) for i in ids], True)
                       for name, hf in _AFMOE_ATTN}
        out["attn"].update({n: stack([a.format(i, n) for i in ids])
                            for n in ("q_norm", "k_norm")})
        return out

    def swiglu(fmt, ids):
        return {name: stack([fmt.format(i, hf) for i in ids], True)
                for name, hf in _SWIGLU}

    nd = c.num_dense_layers
    dense_ids, moe_ids = range(nd), range(nd, c.num_hidden_layers)
    first, n = c.experts_held
    mlp = _AFMOE_LAYER + "mlp."
    experts = {}
    for name, hf in _SWIGLU:
        rows = [np.stack([get(f"{mlp.format(i)}experts.{e}.{hf}.weight",
                              True) for e in range(first, first + n)])
                for i in moe_ids]
        experts[name] = np.stack(rows)
    params = {
        "embed": get("model.embed_tokens.weight"),
        "dense": {**layers(dense_ids),
                  "mlp": swiglu(mlp + "{}.weight", dense_ids)},
        "moe": {**layers(moe_ids),
                "router_w": stack([mlp.format(i) + "router.gate.weight"
                                   for i in moe_ids], True),
                "expert_bias": stack([mlp.format(i) + "expert_bias"
                                      for i in moe_ids]),
                "shared": swiglu(mlp + "shared_experts.{}.weight",
                                 moe_ids),
                "experts": experts},
        "final_norm": get("model.norm.weight"),
        "lm_head": get("lm_head.weight"),
    }
    return config, params


def afmoe_params_to_hf(params, first_expert: int = 0) -> Dict[str, np.ndarray]:
    """models/afmoe.py's tree -> the HF key scheme above (the inverse of
    load_afmoe; linear weights back to [out, in]). The held experts are
    written under their own numbers, `first_expert` onwards."""
    p = {"model.embed_tokens.weight": np.asarray(params["embed"]),
         "lm_head.weight": np.asarray(params["lm_head"]),
         "model.norm.weight": np.asarray(params["final_norm"])}
    nd = np.asarray(params["dense"]["input_ln"]).shape[0]

    def put_layers(stack, base):
        for j in range(np.asarray(stack["input_ln"]).shape[0]):
            pre = _AFMOE_LAYER.format(base + j)
            for name, hf in _AFMOE_NORMS:
                p[pre + hf + ".weight"] = np.asarray(stack[name][j])
            for name, hf in _AFMOE_ATTN:
                p[f"{pre}self_attn.{hf}.weight"] = \
                    np.asarray(stack["attn"][name][j]).T
            for nme in ("q_norm", "k_norm"):
                p[f"{pre}self_attn.{nme}.weight"] = \
                    np.asarray(stack["attn"][nme][j])
            for name, hf in _SWIGLU:
                if "mlp" in stack:
                    p[f"{pre}mlp.{hf}.weight"] = \
                        np.asarray(stack["mlp"][name][j]).T
                    continue
                p[f"{pre}mlp.shared_experts.{hf}.weight"] = \
                    np.asarray(stack["shared"][name][j]).T
                ex = np.asarray(stack["experts"][name][j])
                for e in range(ex.shape[0]):
                    p[f"{pre}mlp.experts.{first_expert + e}.{hf}.weight"] \
                        = ex[e].T
            if "mlp" not in stack:
                p[pre + "mlp.router.gate.weight"] = \
                    np.asarray(stack["router_w"][j]).T
                p[pre + "mlp.expert_bias"] = \
                    np.asarray(stack["expert_bias"][j])

    put_layers(params["dense"], 0)
    put_layers(params["moe"], nd)
    return p


# ----------------------------- Ling hybrid ----------------------------------
#
# Key scheme of models/ling_hybrid.py's checkpoints — ASSUMED (the
# family's modelling code is not in this installation; the names follow
# its Ling-2.0 predecessor where a weight has one there, FLA's KDA layer
# and DeepSeek-V2's MLA otherwise; DESIGN.md §32):
#   model.word_embeddings.weight, lm_head.weight, model.norm.weight,
#   model.layers.{i}.input_layernorm / post_attention_layernorm .weight,
#   kda layers  .attention.{q,k,v,o,f,b,g}_proj.weight,
#               .attention.{q,k,v}_conv1d.weight [C, K], .attention.A_log,
#               .attention.dt_bias, .attention.o_norm.weight
#   mla layers  .attention.{q_proj, kv_a_proj_with_mqa, kv_b_proj, g_proj,
#               o_proj}.weight, .attention.kv_a_layernorm.weight,
#               .attention.{q,k}_norm.weight
#   dense MLP   .mlp.{gate,up,down}_proj.weight
#   expert MLP  .mlp.gate.weight [E, H], .mlp.gate.expert_bias [E],
#               .mlp.shared_experts.{gate,up,down}_proj.weight,
#               .mlp.experts.{e}.{gate,up,down}_proj.weight
# Linear weights are [out, in].

_LING_LAYER = "model.layers.{}."
_LING_KDA_LINEAR = tuple((f"{t}_w", f"{t}_proj") for t in "qkvofbg")
_LING_KDA_PLAIN = (("A_log", "A_log"), ("dt_bias", "dt_bias"),
                   ("o_norm", "o_norm.weight"))
_LING_MLA_LINEAR = (("q_w", "q_proj"), ("kv_a_w", "kv_a_proj_with_mqa"),
                    ("kv_b_w", "kv_b_proj"), ("g_w", "g_proj"),
                    ("o_w", "o_proj"))
_LING_MLA_PLAIN = (("kv_a_norm", "kv_a_layernorm.weight"),
                   ("q_norm", "q_norm.weight"), ("k_norm", "k_norm.weight"))


def load_ling_hybrid(model_dir: str,
                     config: Optional[LingHybridConfig] = None):
    """(config, params in models/ling_hybrid.py's layout) from a
    directory in the key scheme above. Of each expert layer only the
    `config.experts_held` experts are read; a BF16 tensor stays bf16, so
    a frozen base published in bf16 is resident at 2 bytes a parameter."""
    if config is None:
        config = LingHybridConfig.from_pretrained(model_dir)
    c = config
    readers = _tensor_readers(model_dir)

    def get(key, linear=False):
        a = readers[key].load(key, keep_bf16=True)
        return a.T if linear else a      # [out, in] -> [in, out]

    def swiglu(pre):
        return {name: get(f"{pre}{hf}.weight", True) for name, hf in _SWIGLU}

    first, n = c.experts_held
    layers = []
    for i in range(c.num_hidden_layers):
        pre = _LING_LAYER.format(i)
        at = pre + "attention."
        lp = {"input_ln": get(pre + "input_layernorm.weight"),
              "post_attn_ln": get(pre + "post_attention_layernorm.weight")}
        if c.layer_kind(i) == "kda":
            mix = {name: get(f"{at}{hf}.weight", True)
                   for name, hf in _LING_KDA_LINEAR}
            mix.update({name: get(at + hf) for name, hf in _LING_KDA_PLAIN})
            mix.update({f"{t}_conv": get(f"{at}{t}_conv1d.weight").T
                        for t in "qkv"})
            lp["kda"] = mix
        else:
            mix = {name: get(f"{at}{hf}.weight", True)
                   for name, hf in _LING_MLA_LINEAR}
            mix.update({name: get(at + hf) for name, hf in _LING_MLA_PLAIN})
            lp["mla"] = mix
        mlp = pre + "mlp."
        if c.is_dense_layer(i):
            lp["mlp"] = swiglu(mlp)
        else:
            lp["router_w"] = get(mlp + "gate.weight", True)
            lp["expert_bias"] = get(mlp + "gate.expert_bias")
            lp["shared"] = swiglu(mlp + "shared_experts.")
            lp["experts"] = {
                name: np.stack([get(f"{mlp}experts.{e}.{hf}.weight", True)
                                for e in range(first, first + n)])
                for name, hf in _SWIGLU}
        layers.append(lp)
    return config, {"embed": get("model.word_embeddings.weight"),
                    "layers": layers,
                    "final_norm": get("model.norm.weight"),
                    "lm_head": get("lm_head.weight")}


def ling_hybrid_params_to_hf(params,
                             first_expert: int = 0) -> Dict[str, np.ndarray]:
    """models/ling_hybrid.py's tree -> the key scheme above (the inverse
    of load_ling_hybrid); the held experts under their own numbers."""
    p = {"model.word_embeddings.weight": np.asarray(params["embed"]),
         "lm_head.weight": np.asarray(params["lm_head"]),
         "model.norm.weight": np.asarray(params["final_norm"])}
    for i, lp in enumerate(params["layers"]):
        pre = _LING_LAYER.format(i)
        at = pre + "attention."
        p[pre + "input_layernorm.weight"] = np.asarray(lp["input_ln"])
        p[pre + "post_attention_layernorm.weight"] = \
            np.asarray(lp["post_attn_ln"])
        if "kda" in lp:
            mix, linear, plain = lp["kda"], _LING_KDA_LINEAR, _LING_KDA_PLAIN
            for t in "qkv":
                p[f"{at}{t}_conv1d.weight"] = np.asarray(mix[f"{t}_conv"]).T
        else:
            mix, linear, plain = lp["mla"], _LING_MLA_LINEAR, _LING_MLA_PLAIN
        for name, hf in linear:
            p[f"{at}{hf}.weight"] = np.asarray(mix[name]).T
        for name, hf in plain:
            p[at + hf] = np.asarray(mix[name])
        mlp = pre + "mlp."
        for name, hf in _SWIGLU:
            if "mlp" in lp:
                p[f"{mlp}{hf}.weight"] = np.asarray(lp["mlp"][name]).T
                continue
            p[f"{mlp}shared_experts.{hf}.weight"] = \
                np.asarray(lp["shared"][name]).T
            ex = np.asarray(lp["experts"][name])
            for e in range(ex.shape[0]):
                p[f"{mlp}experts.{first_expert + e}.{hf}.weight"] = ex[e].T
        if "mlp" not in lp:
            p[mlp + "gate.weight"] = np.asarray(lp["router_w"]).T
            p[mlp + "gate.expert_bias"] = np.asarray(lp["expert_bias"])
    return p


# --------------------------- Phi-4-mini-flash ------------------------------
#
# Key scheme of models/phi4flash.py's checkpoints — ASSUMED (the
# family's modelling code is not in this installation; Mamba's module
# names for the scan's weights, `attn` for every layer's mixer as in
# Samba, DESIGN.md §34):
#   model.embed_tokens.weight (the head is tied: no lm_head),
#   model.final_layernorm.{weight,bias},
#   model.layers.{i}.input_layernorm / post_attention_layernorm
#     .{weight,bias}, .mlp.fc1.weight [2I, H], .mlp.fc2.weight [H, I],
#   mamba  .attn.{in_proj, x_proj, dt_proj, out_proj}.weight,
#          .attn.dt_proj.bias, .attn.conv1d.weight [d_in, 1, K],
#          .attn.conv1d.bias, .attn.A_log [d_in, N], .attn.D [d_in]
#   attention  .attn.{q_proj, k_proj, v_proj, o_proj}.weight (no k, v in
#          a cross layer), .attn.lambda_{q1,k1,q2,k2} [d],
#          .attn.subln.weight [2 d]
#   gmu    .attn.in_proj.weight [d_in, H], .attn.out_proj.weight
# Linear weights are [out, in].

_PHI4_LINEAR = {"mamba": (("in_w", "in_proj"), ("x_w", "x_proj"),
                          ("dt_w", "dt_proj"), ("out_w", "out_proj")),
                "attn": (("q_w", "q_proj"), ("k_w", "k_proj"),
                         ("v_w", "v_proj"), ("o_w", "o_proj")),
                "gmu": (("in_w", "in_proj"), ("out_w", "out_proj"))}
_PHI4_PLAIN = {"mamba": (("dt_b", "dt_proj.bias"), ("conv_b", "conv1d.bias"),
                         ("A_log", "A_log"), ("D", "D")),
               "attn": (("lq1", "lambda_q1"), ("lk1", "lambda_k1"),
                        ("lq2", "lambda_q2"), ("lk2", "lambda_k2"),
                        ("subln", "subln.weight")),
               "gmu": ()}
_PHI4_NORMS = (("ln1", "input_layernorm"), ("ln2", "post_attention_layernorm"))


def _phi4_slots(c: Phi4FlashConfig):
    """(layer index, group, kind, stack position or None) of every layer:
    where it lives in models/phi4flash.py's tree."""
    P, mid = c.num_pairs, c.num_hidden_layers // 2
    for i in range(P):
        yield 2 * i, "self", "mamba", i
        yield 2 * i + 1, "self", "attn", i
    yield mid, "mid", "mamba", None
    yield mid + 1, "mid", "attn", None
    for i in range(P - 1):
        yield mid + 2 + 2 * i, "cross", "gmu", i
        yield mid + 3 + 2 * i, "cross", "attn", i


def load_phi4flash(model_dir: str, config: Optional[Phi4FlashConfig] = None):
    """(config, params in models/phi4flash.py's layout) from a directory
    in the key scheme above; a BF16 tensor stays bf16, so a frozen base
    published in bf16 is resident at 2 bytes a parameter."""
    if config is None:
        config = Phi4FlashConfig.from_pretrained(model_dir)
    c = config
    readers = _tensor_readers(model_dir)

    def get(key, linear=False):
        a = readers[key].load(key, keep_bf16=True)
        return a.T if linear else a      # [out, in] -> [in, out]

    def layer(i, kind):
        pre = _LING_LAYER.format(i)
        at = pre + "attn."
        lp = {}
        for name, hf in _PHI4_NORMS:
            lp[name + "_w"] = get(f"{pre}{hf}.weight")
            lp[name + "_b"] = get(f"{pre}{hf}.bias")
        lp["mlp"] = {"fc1_w": get(pre + "mlp.fc1.weight", True),
                     "fc2_w": get(pre + "mlp.fc2.weight", True)}
        mix = {name: get(f"{at}{hf}.weight", True)
               for name, hf in _PHI4_LINEAR[kind]
               if f"{at}{hf}.weight" in readers}
        mix.update({name: get(at + hf) for name, hf in _PHI4_PLAIN[kind]})
        if kind == "mamba":
            w = get(at + "conv1d.weight")                 # [d_in, 1, K]
            mix["conv_w"] = w.reshape(w.shape[0], w.shape[-1]).T
        lp["mixer"] = mix
        return lp

    groups: Dict[str, Dict[str, list]] = {}
    for i, group, kind, _ in _phi4_slots(c):
        groups.setdefault(group, {}).setdefault(kind, []).append(
            layer(i, kind))
    stack = lambda layers: {k: (stack(
        [l[k] for l in layers]) if isinstance(layers[0][k], dict)
        else np.stack([l[k] for l in layers])) for k in layers[0]}
    params = {g: {k: (v[0] if g == "mid" else stack(v))
                  for k, v in kinds.items()} for g, kinds in groups.items()}
    params.update(embed=get("model.embed_tokens.weight"),
                  final_ln_w=get("model.final_layernorm.weight"),
                  final_ln_b=get("model.final_layernorm.bias"))
    return config, params


def phi4flash_params_to_hf(params, config: Phi4FlashConfig
                           ) -> Dict[str, np.ndarray]:
    """models/phi4flash.py's tree -> the key scheme above (the inverse of
    load_phi4flash)."""
    p = {"model.embed_tokens.weight": np.asarray(params["embed"]),
         "model.final_layernorm.weight": np.asarray(params["final_ln_w"]),
         "model.final_layernorm.bias": np.asarray(params["final_ln_b"])}
    for i, group, kind, at in _phi4_slots(config):
        lp = params[group][kind]
        if at is not None:
            lp = {k: ({kk: vv[at] for kk, vv in v.items()}
                      if isinstance(v, dict) else v[at])
                  for k, v in lp.items()}
        pre = _LING_LAYER.format(i)
        for name, hf in _PHI4_NORMS:
            p[f"{pre}{hf}.weight"] = np.asarray(lp[name + "_w"])
            p[f"{pre}{hf}.bias"] = np.asarray(lp[name + "_b"])
        p[pre + "mlp.fc1.weight"] = np.asarray(lp["mlp"]["fc1_w"]).T
        p[pre + "mlp.fc2.weight"] = np.asarray(lp["mlp"]["fc2_w"]).T
        mix = lp["mixer"]
        for name, hf in _PHI4_LINEAR[kind]:
            if name in mix:
                p[f"{pre}attn.{hf}.weight"] = np.asarray(mix[name]).T
        for name, hf in _PHI4_PLAIN[kind]:
            p[f"{pre}attn.{hf}"] = np.asarray(mix[name])
        if kind == "mamba":
            w = np.asarray(mix["conv_w"]).T               # [d_in, K]
            p[f"{pre}attn.conv1d.weight"] = w.reshape(w.shape[0], 1, -1)
    return p


# ------------------------------- Xing4.0 ------------------------------------
#
# Key scheme of models/xing4.py's checkpoints — ASSUMED (the family's
# modelling code is not in this installation; DeepSeek-V3's names where a
# weight has one there, DESIGN.md §33):
#   model.embed_tokens.weight, lm_head.weight, model.norm.weight,
#   model.layers.{i}.input_layernorm / post_attention_layernorm .weight,
#   .self_attn.{q_a_proj, q_b_proj, kv_a_proj_with_mqa, kv_b_proj,
#               o_proj}.weight, .self_attn.{q_a,kv_a}_layernorm.weight,
#   .attn_hc / .mlp_hc .{phi.weight [2n + n², n·C], base [2n + n²],
#               scale [3]}  (the two sublayers' hyper-connections)
#   dense MLP   .mlp.{gate,up,down}_proj.weight
#   expert MLP  .mlp.gate.weight [E, H],
#               .mlp.gate.e_score_correction_bias [E],
#               .mlp.shared_experts.{gate,up,down}_proj.weight,
#               .mlp.experts.{e}.{gate,up,down}_proj.weight
#   the multi-token-prediction module as layer L = num_hidden_layers:
#   a sparse block's keys and .enorm.weight, .hnorm.weight,
#   .eh_proj.weight [C, 2C], .shared_head.norm.weight (the head itself is
#   lm_head, shared).
# Linear weights are [out, in].

_XING4_MLA_LINEAR = (("q_a_w", "q_a_proj"), ("q_b_w", "q_b_proj"),
                     ("kv_a_w", "kv_a_proj_with_mqa"),
                     ("kv_b_w", "kv_b_proj"), ("o_w", "o_proj"))
_XING4_MLA_PLAIN = (("q_a_norm", "q_a_layernorm.weight"),
                    ("kv_a_norm", "kv_a_layernorm.weight"))
_XING4_HC = (("hc_attn", "attn_hc."), ("hc_mlp", "mlp_hc."))
_XING4_MTP = (("enorm", "enorm.weight"), ("hnorm", "hnorm.weight"),
              ("norm", "shared_head.norm.weight"))


def load_xing4(model_dir: str, config: Optional[Xing4Config] = None):
    """(config, params in models/xing4.py's layout) from a directory in
    the key scheme above. Of each expert layer only the
    `config.experts_held` experts are read; a BF16 tensor stays bf16, so
    a frozen base published in bf16 is resident at 2 bytes a parameter."""
    if config is None:
        config = Xing4Config.from_pretrained(model_dir)
    c = config
    readers = _tensor_readers(model_dir)

    def get(key, linear=False):
        a = readers[key].load(key, keep_bf16=True)
        return a.T if linear else a      # [out, in] -> [in, out]

    def swiglu(pre):
        return {name: get(f"{pre}{hf}.weight", True) for name, hf in _SWIGLU}

    first, n = c.experts_held

    def block(i: int, dense: bool):
        pre = _LING_LAYER.format(i)
        at = pre + "self_attn."
        bp = {"input_ln": get(pre + "input_layernorm.weight"),
              "post_attn_ln": get(pre + "post_attention_layernorm.weight"),
              "mla": {**{name: get(f"{at}{hf}.weight", True)
                         for name, hf in _XING4_MLA_LINEAR},
                      **{name: get(at + hf)
                         for name, hf in _XING4_MLA_PLAIN}}}
        for name, hf in _XING4_HC:
            bp[name] = {"phi": get(pre + hf + "phi.weight", True),
                        "b": get(pre + hf + "base"),
                        "alpha": get(pre + hf + "scale")}
        mlp = pre + "mlp."
        if dense:
            bp["mlp"] = swiglu(mlp)
        else:
            bp["router_w"] = get(mlp + "gate.weight", True)
            bp["expert_bias"] = get(mlp + "gate.e_score_correction_bias")
            bp["shared"] = swiglu(mlp + "shared_experts.")
            bp["experts"] = {
                name: np.stack([get(f"{mlp}experts.{e}.{hf}.weight", True)
                                for e in range(first, first + n)])
                for name, hf in _SWIGLU}
        return bp

    L = c.num_hidden_layers
    params = {"embed": get("model.embed_tokens.weight"),
              "layers": [block(i, c.is_dense_layer(i)) for i in range(L)],
              "final_norm": get("model.norm.weight"),
              "lm_head": get("lm_head.weight")}
    if c.num_nextn_predict_layers:
        pre = _LING_LAYER.format(L)
        params["mtp"] = {
            **{name: get(pre + hf) for name, hf in _XING4_MTP},
            "eh_proj": get(pre + "eh_proj.weight", True),
            "block": block(L, False)}
    return config, params


def xing4_params_to_hf(params, first_expert: int = 0) -> Dict[str, np.ndarray]:
    """models/xing4.py's tree -> the key scheme above (the inverse of
    load_xing4); the held experts under their own numbers."""
    p = {"model.embed_tokens.weight": np.asarray(params["embed"]),
         "lm_head.weight": np.asarray(params["lm_head"]),
         "model.norm.weight": np.asarray(params["final_norm"])}

    def block(i: int, bp):
        pre = _LING_LAYER.format(i)
        at = pre + "self_attn."
        p[pre + "input_layernorm.weight"] = np.asarray(bp["input_ln"])
        p[pre + "post_attention_layernorm.weight"] = \
            np.asarray(bp["post_attn_ln"])
        for name, hf in _XING4_MLA_LINEAR:
            p[f"{at}{hf}.weight"] = np.asarray(bp["mla"][name]).T
        for name, hf in _XING4_MLA_PLAIN:
            p[at + hf] = np.asarray(bp["mla"][name])
        for name, hf in _XING4_HC:
            p[pre + hf + "phi.weight"] = np.asarray(bp[name]["phi"]).T
            p[pre + hf + "base"] = np.asarray(bp[name]["b"])
            p[pre + hf + "scale"] = np.asarray(bp[name]["alpha"])
        mlp = pre + "mlp."
        for name, hf in _SWIGLU:
            if "mlp" in bp:
                p[f"{mlp}{hf}.weight"] = np.asarray(bp["mlp"][name]).T
                continue
            p[f"{mlp}shared_experts.{hf}.weight"] = \
                np.asarray(bp["shared"][name]).T
            ex = np.asarray(bp["experts"][name])
            for e in range(ex.shape[0]):
                p[f"{mlp}experts.{first_expert + e}.{hf}.weight"] = ex[e].T
        if "mlp" not in bp:
            p[mlp + "gate.weight"] = np.asarray(bp["router_w"]).T
            p[mlp + "gate.e_score_correction_bias"] = \
                np.asarray(bp["expert_bias"])

    for i, bp in enumerate(params["layers"]):
        block(i, bp)
    if "mtp" in params:
        L = len(params["layers"])
        pre = _LING_LAYER.format(L)
        for name, hf in _XING4_MTP:
            p[pre + hf] = np.asarray(params["mtp"][name])
        p[pre + "eh_proj.weight"] = np.asarray(params["mtp"]["eh_proj"]).T
        block(L, params["mtp"]["block"])
    return p


# --------------------------- checkpoint lineage ------------------------------
#
# Step-tagged last-known-good checkpoints with GC and verify-on-load
# fallback (DESIGN.md §20). Every train CLI's write hook records each
# completed save into `<final_path>.lineage.json` (atomic publish),
# newest-first: [{"step": S, "files": [basenames...]}, ...] where
# files[0] is the loadable checkpoint and the rest are sidecars (.opt).
# `--keep_ckpts K` prunes the list to the K newest step-tagged entries
# BEFORE unlinking the pruned files — a SIGKILL between the two leaves
# orphan files (harmless), never a lineage that names deleted
# checkpoints as retained. Load paths (`--resume_from`, in-process
# rollback, serve hot-swap) walk the lineage through
# `resolve_checkpoint`, verifying each candidate's manifest and falling
# back down the chain on mismatch instead of crashing on — or silently
# loading — the newest file.

def lineage_path(final_path: str) -> str:
    return final_path + ".lineage.json"


def _load_lineage(final_path: str) -> List[dict]:
    try:
        with open(lineage_path(final_path), "r", encoding="utf-8") as f:
            entries = json.load(f)["entries"]
        return [e for e in entries
                if isinstance(e.get("step"), int) and e.get("files")]
    except (OSError, ValueError, KeyError, TypeError):
        return []


def lineage_entries(final_path: str) -> List[dict]:
    """Newest-first [{step, files: [abs paths]}] from the lineage json
    next to `final_path`; [] when absent/unreadable. Paths are made
    absolute against the checkpoint directory (the lineage stores
    basenames so a checkpoint directory can be moved wholesale)."""
    d = os.path.dirname(os.path.abspath(final_path))
    out = []
    for e in sorted(_load_lineage(final_path),
                    key=lambda e: e["step"], reverse=True):
        out.append({"step": e["step"],
                    "files": [os.path.join(d, os.path.basename(f))
                              for f in e["files"]]})
    return out


def record_checkpoint(final_path: str, step: int, files: List[str],
                      keep: int = 0) -> List[str]:
    """Record one completed save into the lineage and GC old entries.
    `files`: the paths this save wrote (files[0] = the loadable
    checkpoint). `keep` > 0 retains only the `keep` newest STEP-TAGGED
    entries (an entry whose checkpoint is `final_path` itself — the
    run's final artifact — is never pruned); 0 keeps everything.
    Returns the pruned files it unlinked. Kill-safe ordering: the
    pruned lineage publishes atomically FIRST, then files are unlinked
    — dying between the two leaves orphans, not a lineage pointing at
    deleted checkpoints (tests/test_recovery.py pins this)."""
    d = os.path.dirname(os.path.abspath(final_path))
    final_base = os.path.basename(final_path)
    bases = [os.path.basename(f) for f in files]
    entries = [e for e in _load_lineage(final_path)
               if e["step"] != step and e["files"][0] != bases[0]]
    entries.append({"step": int(step), "files": bases})
    entries.sort(key=lambda e: e["step"], reverse=True)
    pruned: List[dict] = []
    if keep and keep > 0:
        kept, tagged = [], 0
        for e in entries:
            if e["files"][0] == final_base:
                kept.append(e)  # the final artifact is never GC'd
            elif tagged < keep:
                kept.append(e)
                tagged += 1
            else:
                pruned.append(e)
        entries = kept
    with atomic_publish(lineage_path(final_path)) as tmp:
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"version": 1, "entries": entries}, f,
                      separators=(",", ":"))
    removed = []
    keep_set = {b for e in entries for b in e["files"]}
    for e in pruned:
        for b in e["files"]:
            if b in keep_set:
                continue  # shared file (should not happen; be safe)
            p = os.path.join(d, b)
            for victim in (p, manifest_path(p)):
                with contextlib.suppress(OSError):
                    os.unlink(victim)
                removed.append(victim)
    return removed


def lineage_base_for(path: str) -> Optional[str]:
    """The FINAL-artifact path whose lineage json lists `path` as a
    checkpoint — found by scanning `*.lineage.json` next to it. A
    step-tagged file (`a_step6.safetensors`) carries no lineage of its
    own; its chain lives at `a.safetensors.lineage.json`."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    base = os.path.basename(path)
    if os.path.exists(lineage_path(path)):
        return path
    for lp in _glob.glob(os.path.join(_glob.escape(d), "*.lineage.json")):
        final = lp[: -len(".lineage.json")]
        for e in _load_lineage(final):
            if e["files"] and os.path.basename(e["files"][0]) == base:
                return final
    return None


def lineage_step_for(path: str) -> Optional[int]:
    """The LOOP step a checkpoint file was saved at, looked up from the
    lineage that lists it (lineage_base_for). Needed because a
    `--skip_nonfinite` run's Adam step counter lags the loop step by
    the skipped updates — the .opt sidecar's `step` tensor is the wrong
    resume point then (the sidecar's `loop_step` metadata is the
    primary source; this is the fallback for sidecars written before
    it existed)."""
    base = lineage_base_for(path)
    if base is None:
        return None
    name = os.path.basename(path)
    for e in lineage_entries(base):
        if os.path.basename(e["files"][0]) == name:
            return e["step"]
    return None


def _verify_entry(files: List[str]) -> Tuple[str, Optional[str]]:
    """Aggregate verify_report over an entry's file set: 'corrupt'
    dominates, then 'unverified', else 'ok'. A missing SIDECAR is
    corruption of the entry (the checkpoint alone cannot resume the
    optimizer); reasons are prefixed with the offending basename."""
    worst, why = "ok", None
    for f in files:
        status, reason = verify_report(f)
        tagged = f"{os.path.basename(f)}:{reason}" if reason else None
        if status == "corrupt":
            return "corrupt", tagged
        if status == "unverified" and worst == "ok":
            worst, why = "unverified", tagged
    return worst, why


def resolve_checkpoint(path: Optional[str], verify: bool = True,
                       lineage_base: Optional[str] = None,
                       max_step: Optional[int] = None):
    """Resolve the checkpoint a load should actually use, walking the
    integrity lineage: returns (resolved_path, step_or_None, events)
    where events is a list of `ckpt_verify` telemetry payloads
    ({path, ok, reason, step, action}) in visit order.

    Candidates: the explicit `path` first (when given), then the
    lineage entries next to `lineage_base` (default: `path`) newest-
    first, skipping entries newer than `max_step` (the rollback caller
    must not "resume" into the future). The first candidate whose
    manifest fully verifies wins; if NONE verifies, the newest
    'unverified' candidate (parseable file, no manifest — a
    pre-manifest checkpoint) is accepted with ok=false so legacy
    resumes keep working; if nothing is loadable at all, an explicit
    `path` raises CheckpointIntegrityError and a lineage-only walk
    (rollback) returns (None, None, events). verify=False short-
    circuits to the explicit path unchanged (--verify_ckpt 0)."""
    if not verify:
        # trust-the-newest mode (--verify_ckpt 0): no checksum walk,
        # but a lineage-only call (rollback's path=None) must still
        # resolve the newest EXISTING entry — "don't verify" must not
        # mean "can't roll back"
        if path:
            return path, lineage_step_for(path), []
        for e in (lineage_entries(lineage_base) if lineage_base else []):
            if max_step is not None and e["step"] > max_step:
                continue
            if os.path.exists(e["files"][0]):
                return e["files"][0], e["step"], []
        return None, None, []
    base = lineage_base or (lineage_base_for(path) if path else None)
    candidates: List[Tuple[str, Optional[int], List[str]]] = []
    seen = set()
    if path:
        files = [path] + ([path + ".opt"]
                          if os.path.exists(path + ".opt") else [])
        candidates.append((path, lineage_step_for(path), files))
        seen.add(os.path.abspath(path))
    if base:
        for e in lineage_entries(base):
            main = e["files"][0]
            if os.path.abspath(main) in seen:
                continue
            if max_step is not None and e["step"] > max_step:
                continue
            seen.add(os.path.abspath(main))
            candidates.append((main, e["step"], e["files"]))
    events: List[dict] = []
    fallback: Optional[Tuple[str, Optional[int]]] = None
    for main, step, files in candidates:
        status, reason = _verify_entry(files)
        ok = status == "ok"
        events.append({"path": main, "ok": ok, "reason": reason,
                       "step": step,
                       "action": "load" if ok else "reject"})
        if ok:
            return main, step, events
        if status == "unverified" and fallback is None:
            fallback = (main, step)
    if fallback is not None:
        main, step = fallback
        events.append({"path": main, "ok": False,
                       "reason": "loaded_unverified", "step": step,
                       "action": "load"})
        return main, step, events
    if path:
        raise CheckpointIntegrityError(
            f"{path}: no loadable checkpoint in its lineage "
            f"({len(candidates)} candidate(s) rejected: "
            f"{[e['reason'] for e in events]})")
    return None, None, events


def jax_to_numpy(tree):
    """Device pytree -> host numpy, BATCHED: all device->host transfers
    are issued async first, then awaited once (io/async_ckpt.snapshot) —
    a per-leaf np.asarray loop would serialize one blocking D2H per
    tensor, which was the dominant save stall on large trees."""
    from mobilefinetuner_tpu.io.async_ckpt import snapshot
    return snapshot(tree)
