"""Tiny on-disk HF-format checkpoint + data fixtures for CLI tests.

Builds what the CLIs expect to find in a real model dir: config.json,
model.safetensors with HF key schemes, tokenizer files — all tiny enough
for CPU test runs (the analog of the reference's committed small fixtures,
SURVEY.md §4.2)."""

from __future__ import annotations

import contextlib
import json
import os

import jax
import numpy as np

from mobilefinetuner_tpu.core.config import (AfmoeConfig, GPT2Config,
                                              Gemma3TextConfig,
                                              LingHybridConfig,
                                              Phi4FlashConfig, Xing4Config)
from mobilefinetuner_tpu.io.checkpoints import (afmoe_params_to_hf,
                                                gpt2_params_to_hf,
                                                ling_hybrid_params_to_hf,
                                                phi4flash_params_to_hf,
                                                xing4_params_to_hf)
from mobilefinetuner_tpu.io.safetensors_io import save_safetensors
from mobilefinetuner_tpu.models import (afmoe, gemma3, gpt2, ling_hybrid,
                                        phi4flash, xing4)

@contextlib.contextmanager
def backend_compiles():
    """The backend compilations of this process while the block runs, as
    a list that grows (jax.monitoring, the way the benchmark's
    `harness/serve_engine.CompileCounter` counts them): a second
    lowering of a program whose trace is cached shows here and nowhere
    in `trace_counts`."""
    seen = []

    def on(event, *_a, **_k):
        if event.endswith("backend_compile_duration"):
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


WIKI_LINES = [
    " = Heading = ",
    " The quick brown fox jumps over the lazy dog . ",
    " In 1984 , George Orwell wrote about surveillance states . ",
    " Prices rose 3.5 % to $ 1,234.56 yesterday . ",
    " Tokenization matters for language models . ",
    " A small corpus still produces many chunks when repeated . ",
] * 30


def write_wikitext_dir(d: str) -> str:
    os.makedirs(d, exist_ok=True)
    for split, frac in (("train", 1.0), ("valid", 0.3), ("test", 0.3)):
        n = int(len(WIKI_LINES) * frac)
        with open(os.path.join(d, f"wiki.{split}.tokens"), "w") as f:
            f.write("\n".join(WIKI_LINES[:n]) + "\n")
    return d


def train_tiny_gpt2_tokenizer(d: str):
    """Train a tiny byte-level BPE with the HF tokenizers lib and save
    vocab.json/merges.txt (the files GPT2BPETokenizer.from_pretrained
    reads)."""
    from tokenizers import Tokenizer, models, pre_tokenizers, trainers
    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    trainer = trainers.BpeTrainer(
        vocab_size=600, special_tokens=["<|endoftext|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False)
    tok.train_from_iterator(WIKI_LINES, trainer)
    tok.model.save(d)
    with open(os.path.join(d, "vocab.json")) as f:
        return len(json.load(f))


def write_tiny_gpt2_dir(d: str, seed: int = 0,
                        **config_overrides) -> GPT2Config:
    """HF-format GPT-2 checkpoint dir: config.json + model.safetensors
    (HF GPT2LMHeadModel keys, Conv1D [in, out] layout) + tokenizer files.
    config_overrides replace GPT2Config.tiny fields — the elastic-resume
    mesh tests use n_embd=128 so the stacked per-layer leaves exceed the
    FSDP min_size and actually re-shard across mesh shapes."""
    import dataclasses
    os.makedirs(d, exist_ok=True)
    vocab_size = train_tiny_gpt2_tokenizer(d)
    config = GPT2Config.tiny(vocab_size=vocab_size)
    if config_overrides:
        config = dataclasses.replace(config, **config_overrides)
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump({"model_type": "gpt2", "vocab_size": config.vocab_size,
                   "n_positions": config.n_positions,
                   "n_embd": config.n_embd, "n_layer": config.n_layer,
                   "n_head": config.n_head,
                   "layer_norm_epsilon": config.layer_norm_epsilon,
                   "activation_function": "gelu_new"}, f)
    params = gpt2.init_params(config, jax.random.PRNGKey(seed))
    tensors = gpt2_params_to_hf(jax.tree.map(np.asarray, params))
    save_safetensors(os.path.join(d, "model.safetensors"), tensors,
                     metadata={"format": "pt"})
    return config


from mobilefinetuner_tpu.io.checkpoints import \
    gemma3_params_to_hf  # production inverse mapper (io/checkpoints.py)


def train_tiny_gemma_tokenizer(path: str):
    from tokenizers import Tokenizer, models, normalizers, trainers
    byte_tokens = [f"<0x{b:02X}>" for b in range(256)]
    tok = Tokenizer(models.BPE(unk_token="<unk>", byte_fallback=True))
    tok.normalizer = normalizers.Replace(" ", "▁")
    trainer = trainers.BpeTrainer(
        vocab_size=700,
        special_tokens=["<pad>", "<eos>", "<bos>", "<unk>"] + byte_tokens,
        show_progress=False)
    tok.train_from_iterator(WIKI_LINES, trainer)
    tok.save(path)
    return tok.get_vocab_size()


def write_tiny_gemma3_dir(d: str, seed: int = 0) -> Gemma3TextConfig:
    os.makedirs(d, exist_ok=True)
    vocab_size = train_tiny_gemma_tokenizer(os.path.join(d,
                                                         "tokenizer.json"))
    config = Gemma3TextConfig.tiny(vocab_size=vocab_size)
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump({"model_type": "gemma3_text",
                   "vocab_size": config.vocab_size,
                   "hidden_size": config.hidden_size,
                   "intermediate_size": config.intermediate_size,
                   "num_hidden_layers": config.num_hidden_layers,
                   "num_attention_heads": config.num_attention_heads,
                   "num_key_value_heads": config.num_key_value_heads,
                   "head_dim": config.head_dim,
                   "max_position_embeddings":
                       config.max_position_embeddings,
                   "rms_norm_eps": config.rms_norm_eps,
                   "rope_theta": config.rope_theta,
                   "rope_local_base_freq": config.rope_local_base_freq,
                   "sliding_window": config.sliding_window,
                   "query_pre_attn_scalar": config.query_pre_attn_scalar,
                   "sliding_window_pattern":
                       config.sliding_window_pattern}, f)
    params = gemma3.init_params(config, jax.random.PRNGKey(seed))
    tensors = gemma3_params_to_hf(jax.tree.map(np.asarray, params))
    save_safetensors(os.path.join(d, "model.safetensors"), tensors,
                     metadata={"format": "pt"})
    return config


MMLU_ROWS = [
    ("What is 2 + 2 ?", "3", "4", "5", "6", "B"),
    ("The sky is usually what color ?", "green", "red", "blue", "yellow",
     "C"),
    ("Which animal barks ?", "dog", "cat", "fish", "bird", "A"),
    ("How many days in a week ?", "five", "six", "eight", "seven", "D"),
]


def write_tiny_afmoe_dir(d: str, seed: int = 0, dtype=None):
    """HF-layout AFMoE checkpoint dir (config.json with the published key
    names, model.safetensors in the afmoe key scheme, the tiny GPT-2
    byte-level tokenizer). dtype: the matrices' storage dtype (e.g.
    jnp.bfloat16: stored BF16). Returns (config, params as written)."""
    import dataclasses
    import jax.numpy as jnp
    os.makedirs(d, exist_ok=True)
    config = AfmoeConfig.tiny(vocab_size=train_tiny_gpt2_tokenizer(d))
    with open(os.path.join(d, "config.json"), "w") as f:
        raw = dataclasses.asdict(config)
        del raw["experts_held"], raw["attention_impl"]
        json.dump({"model_type": "afmoe", **raw}, f)
    params = afmoe.init_params(config, jax.random.PRNGKey(seed),
                               dtype or jnp.float32)
    save_safetensors(os.path.join(d, "model.safetensors"),
                     afmoe_params_to_hf(jax.tree.map(np.asarray, params)),
                     metadata={"format": "pt"})
    return config, params


def write_tiny_ling_dir(d: str, seed: int = 0, dtype=None):
    """As write_tiny_afmoe_dir, for the Ling hybrid family
    (io/checkpoints.load_ling_hybrid's key scheme)."""
    import dataclasses
    import jax.numpy as jnp
    os.makedirs(d, exist_ok=True)
    config = LingHybridConfig.tiny(vocab_size=train_tiny_gpt2_tokenizer(d))
    with open(os.path.join(d, "config.json"), "w") as f:
        raw = dataclasses.asdict(config)
        del raw["experts_held"], raw["attention_impl"]
        json.dump({"model_type": "ling_hybrid", **raw}, f)
    params = ling_hybrid.init_params(config, jax.random.PRNGKey(seed),
                                     dtype or jnp.float32)
    save_safetensors(os.path.join(d, "model.safetensors"),
                     ling_hybrid_params_to_hf(jax.tree.map(np.asarray,
                                                           params)),
                     metadata={"format": "pt"})
    return config, params


def write_tiny_xing4_dir(d: str, seed: int = 0, dtype=None):
    """As write_tiny_afmoe_dir, for the Xing4.0 family
    (io/checkpoints.load_xing4's key scheme)."""
    import dataclasses
    import jax.numpy as jnp
    os.makedirs(d, exist_ok=True)
    config = Xing4Config.tiny(vocab_size=train_tiny_gpt2_tokenizer(d))
    with open(os.path.join(d, "config.json"), "w") as f:
        raw = dataclasses.asdict(config)
        for k in ("experts_held", "attention_impl", "mtp_loss_weight"):
            del raw[k]
        json.dump({"model_type": "xing4_0", **raw}, f)
    params = xing4.init_params(config, jax.random.PRNGKey(seed),
                               dtype or jnp.float32)
    save_safetensors(os.path.join(d, "model.safetensors"),
                     xing4_params_to_hf(jax.tree.map(np.asarray, params)),
                     metadata={"format": "pt"})
    return config, params


def write_tiny_phi4flash_dir(d: str, seed: int = 0, dtype=None):
    """As write_tiny_afmoe_dir, for the Phi-4-mini-flash family
    (io/checkpoints.load_phi4flash's key scheme)."""
    import dataclasses
    import jax.numpy as jnp
    os.makedirs(d, exist_ok=True)
    config = Phi4FlashConfig.tiny(vocab_size=train_tiny_gpt2_tokenizer(d))
    with open(os.path.join(d, "config.json"), "w") as f:
        raw = dataclasses.asdict(config)
        del raw["attention_impl"]
        json.dump({"model_type": "phi4flash", **raw}, f)
    params = phi4flash.init_params(config, jax.random.PRNGKey(seed),
                                   dtype or jnp.float32)
    save_safetensors(os.path.join(d, "model.safetensors"),
                     phi4flash_params_to_hf(jax.tree.map(np.asarray, params),
                                            config),
                     metadata={"format": "pt"})
    return config, params


def write_tiny_mmlu_dir(d: str, split: str = "test") -> str:
    sd = os.path.join(d, split)
    os.makedirs(sd, exist_ok=True)
    for subject in ("toy_math", "toy_facts"):
        with open(os.path.join(sd, f"{subject}_{split}.csv"), "w") as f:
            for q, a, b, c, dd, ans in MMLU_ROWS:
                f.write(f'"{q}",{a},{b},{c},{dd},{ans}\n')
    return d
