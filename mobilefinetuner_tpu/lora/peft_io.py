"""Adapter save/load: native format (fast resume) + PEFT-compatible export.

Reference: graph/lora_saver.{h,cpp} — PEFT-compatible safetensors of adapter
weights with rank/alpha/dropout metadata in the safetensors header, plus
`load_safetensors -> attach_from_state` for resume. We mirror both:

  - native format: keys `blocks.{target}.{A|B}` holding the stacked
    [L, ...] arrays, spec in the header metadata — exact, single-blob resume;
  - PEFT export/import: per-layer `base_model.model.<hf_module_path>.
    lora_A.weight` ([r, in], torch layout) + `adapter_config.json`, loadable
    by HF PEFT on the matching base model.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from mobilefinetuner_tpu.io.safetensors_io import (SafeTensorsReader,
                                                   save_safetensors)
from mobilefinetuner_tpu.lora.lora import LoRASpec

# target name -> HF module path fragment (PEFT keys prepend
# "base_model.model." and append ".lora_A.weight"/".lora_B.weight")
GPT2_PEFT_MODULES = {
    "attn_qkv": "transformer.h.{}.attn.c_attn",
    "attn_proj": "transformer.h.{}.attn.c_proj",
    "mlp_fc_in": "transformer.h.{}.mlp.c_fc",
    "mlp_fc_out": "transformer.h.{}.mlp.c_proj",
}
GEMMA_PEFT_MODULES = {
    t: "model.layers.{}.self_attn." + t for t in
    ("q_proj", "k_proj", "v_proj", "o_proj")
}
GEMMA_PEFT_MODULES.update({
    t: "model.layers.{}.mlp." + t for t in
    ("gate_proj", "up_proj", "down_proj")
})
# AFMoE (HF `afmoe`): the same attention module names as Gemma's
AFMOE_PEFT_MODULES = {t: GEMMA_PEFT_MODULES[t] for t in
                      ("q_proj", "k_proj", "v_proj", "o_proj")}
# The Ling hybrid (io/checkpoints.py's key scheme): a target's stack runs
# over the layers of its kind, so export/import take `layer_ids`
# {target: [layer index of each stack position]}
LING_PEFT_MODULES = {
    **{f"kda_{t}": "model.layers.{}.attention." + t
       for t in ("q_proj", "k_proj", "v_proj", "o_proj")},
    "mla_q_proj": "model.layers.{}.attention.q_proj",
    "mla_kv_a_proj": "model.layers.{}.attention.kv_a_proj_with_mqa",
    "mla_kv_b_proj": "model.layers.{}.attention.kv_b_proj",
    "mla_o_proj": "model.layers.{}.attention.o_proj",
}
# Xing4.0 (io/checkpoints.py's key scheme, DeepSeek-V3's module names):
# the stack's last position is the multi-token-prediction module's
# block, which the checkpoint numbers as layer num_hidden_layers
XING4_PEFT_MODULES = {
    "q_a_proj": "model.layers.{}.self_attn.q_a_proj",
    "q_b_proj": "model.layers.{}.self_attn.q_b_proj",
    "kv_a_proj": "model.layers.{}.self_attn.kv_a_proj_with_mqa",
    "kv_b_proj": "model.layers.{}.self_attn.kv_b_proj",
    "o_proj": "model.layers.{}.self_attn.o_proj",
}
# Phi-4-mini-flash (io/checkpoints.py's key scheme: every mixer under
# `attn`): stacks run over a kind's layers, export/import take
# `layer_ids` (models/phi4flash.stack_layer_ids)
PHI4FLASH_PEFT_MODULES = {
    **{t: "model.layers.{}.attn." + t
       for t in ("q_proj", "k_proj", "v_proj", "o_proj", "in_proj",
                 "out_proj")},
    "gmu_in_proj": "model.layers.{}.attn.in_proj",
    "gmu_out_proj": "model.layers.{}.attn.out_proj",
}
PEFT_MODULES = {"gpt2": GPT2_PEFT_MODULES, "gemma": GEMMA_PEFT_MODULES,
                "afmoe": AFMOE_PEFT_MODULES,
                "ling_hybrid": LING_PEFT_MODULES,
                "xing4_0": XING4_PEFT_MODULES,
                "phi4flash": PHI4FLASH_PEFT_MODULES}
# For adapter_config.json target_modules. PEFT suffix-matches these against
# full module paths, so they must be path-qualified: a bare "c_proj" would
# match BOTH attn.c_proj and mlp.c_proj and make PEFT instantiate phantom
# adapters the safetensors has no weights for.
PEFT_TARGET_MODULES = {
    "attn_qkv": "attn.c_attn", "attn_proj": "attn.c_proj",
    "mlp_fc_in": "mlp.c_fc", "mlp_fc_out": "mlp.c_proj",
}
# adapter_config.json per family: (fan_in_fan_out — GPT-2's Conv1D stores
# [in, out] — and the target_modules naming; None = the target names are
# already HF's module names)
LING_TARGET_MODULES = {t: m.rsplit(".", 1)[1]
                       for t, m in LING_PEFT_MODULES.items()}
XING4_TARGET_MODULES = {t: m.rsplit(".", 1)[1]
                        for t, m in XING4_PEFT_MODULES.items()}
PHI4FLASH_TARGET_MODULES = {t: m.rsplit(".", 1)[1]
                            for t, m in PHI4FLASH_PEFT_MODULES.items()}
PEFT_CONFIG = {"gpt2": (True, PEFT_TARGET_MODULES), "gemma": (False, None),
               "afmoe": (False, None),
               "ling_hybrid": (False, LING_TARGET_MODULES),
               "xing4_0": (False, XING4_TARGET_MODULES),
               "phi4flash": (False, PHI4FLASH_TARGET_MODULES)}


# ----------------------------- native format --------------------------------

def save_adapter(path: str, lora_tree, spec: LoRASpec,
                 extra_metadata: Optional[Dict[str, str]] = None):
    """Native adapter safetensors: stacked arrays + spec metadata.
    Atomically published via save_safetensors (tmp + fsync + rename) —
    a crash mid-save leaves the previous adapter intact."""
    tensors = {}
    for name, entry in lora_tree["blocks"].items():
        tensors[f"blocks.{name}.A"] = np.asarray(entry["A"],
                                                 dtype=np.float32)
        tensors[f"blocks.{name}.B"] = np.asarray(entry["B"],
                                                 dtype=np.float32)
    md = spec.to_metadata()
    md["format"] = "mobilefinetuner_tpu.lora.v1"
    if extra_metadata:
        md.update(extra_metadata)
    save_safetensors(path, tensors, metadata=md)


def load_adapter(path: str) -> Tuple[dict, LoRASpec]:
    """Load a native adapter -> (lora_tree, spec). Resume analog of the
    reference's attach_from_state (lora_saver.h:16-46)."""
    reader = SafeTensorsReader(path)
    spec = LoRASpec.from_metadata(reader.metadata)
    blocks: dict = {}
    for key in reader.keys():
        assert key.startswith("blocks."), key
        _, name, leaf = key.split(".")
        blocks.setdefault(name, {})[leaf] = jnp.asarray(reader.load(key))
    for name in blocks:
        blocks[name]["scale"] = jnp.asarray(spec.scale, jnp.float32)
    spec.targets = sorted(blocks)
    return {"blocks": blocks}, spec


# ----------------------------- PEFT export ----------------------------------

def export_peft(out_dir: str, lora_tree, spec: LoRASpec, family: str,
                base_model_name: str = "", layer_ids=None):
    """Write adapter_model.safetensors + adapter_config.json loadable by HF
    PEFT. A/B are stored in torch nn.Linear layout: lora_A.weight [r, in],
    lora_B.weight [out, r] (our stacked layout is A [L, in, r], B [L, r, out]
    → transpose per layer). layer_ids: {target: the model's layer index
    of each stack position} where a stack does not run over all layers
    (default: position i is layer i)."""
    modules = PEFT_MODULES[family]
    unsupported = sorted(set(lora_tree["blocks"]) - set(modules))
    if unsupported:
        raise ValueError(
            f"targets {unsupported} have no PEFT representation (HF PEFT "
            f"cannot express column-sliced adapters on the fused c_attn; "
            f"reference split-QKV uses its own key scheme too, "
            f"lora_saver.cpp make_peft_key) — use the native adapter "
            f"format for split-QKV runs")
    os.makedirs(out_dir, exist_ok=True)
    tensors = {}
    for name, entry in lora_tree["blocks"].items():
        A = np.asarray(entry["A"], dtype=np.float32)
        B = np.asarray(entry["B"], dtype=np.float32)
        L = A.shape[0]
        ids = (layer_ids or {}).get(name, range(L))
        for i in range(L):
            mod = "base_model.model." + modules[name].format(ids[i])
            tensors[mod + ".lora_A.weight"] = A[i].T.copy()
            tensors[mod + ".lora_B.weight"] = B[i].T.copy()
    save_safetensors(os.path.join(out_dir, "adapter_model.safetensors"),
                     tensors, metadata={"format": "pt"})
    fan_in_fan_out, names = PEFT_CONFIG[family]
    target_modules = sorted({names[t] if names else t
                             for t in lora_tree["blocks"]})
    cfg = {
        "peft_type": "LORA", "task_type": "CAUSAL_LM",
        "base_model_name_or_path": base_model_name,
        "r": spec.rank, "lora_alpha": spec.alpha,
        "lora_dropout": spec.dropout, "bias": "none",
        "fan_in_fan_out": fan_in_fan_out,
        "target_modules": target_modules,
        "inference_mode": False,
    }
    from mobilefinetuner_tpu.io.safetensors_io import atomic_publish
    cfg_path = os.path.join(out_dir, "adapter_config.json")
    with atomic_publish(cfg_path) as tmp:  # crash-safe like the tensors
        with open(tmp, "w") as f:
            json.dump(cfg, f, indent=2)


def import_peft(adapter_dir: str, family: str,
                layer_ids=None) -> Tuple[dict, LoRASpec]:
    """Load an HF-PEFT adapter dir into our stacked lora_tree (layer_ids
    as in export_peft: which layers a target's stack holds)."""
    with open(os.path.join(adapter_dir, "adapter_config.json")) as f:
        cfg = json.load(f)
    spec = LoRASpec(rank=cfg["r"], alpha=cfg["lora_alpha"],
                    dropout=cfg.get("lora_dropout", 0.0), init="peft")
    path = os.path.join(adapter_dir, "adapter_model.safetensors")
    raw = SafeTensorsReader(path).load_all(promote_to_f32=True)
    modules = PEFT_MODULES[family]
    if layer_ids is None and len(set(modules.values())) < len(modules):
        raise ValueError(
            f"family {family!r} names two targets by one module path (its "
            f"layers are of several kinds): pass layer_ids, which layers "
            f"each target's stack holds")
    blocks: dict = {}
    for name, fmt in modules.items():
        per_layer_A, per_layer_B = [], []
        ids = (layer_ids or {}).get(name)
        i = 0
        while ids is None or i < len(ids):
            mod = "base_model.model." + fmt.format(i if ids is None
                                                   else ids[i])
            ka, kb = mod + ".lora_A.weight", mod + ".lora_B.weight"
            if ka not in raw:
                break
            per_layer_A.append(raw[ka].T)
            per_layer_B.append(raw[kb].T)
            i += 1
        if per_layer_A:
            blocks[name] = {
                "A": jnp.asarray(np.stack(per_layer_A)),
                "B": jnp.asarray(np.stack(per_layer_B)),
                "scale": jnp.asarray(spec.scale, jnp.float32),
            }
    spec.targets = sorted(blocks)
    return {"blocks": blocks}, spec
