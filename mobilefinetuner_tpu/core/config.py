"""Model configurations, parsed from HF `config.json`.

TPU-native analog of the reference's hand-rolled JSON field extraction
(reference: operators/finetune_ops/graph/gpt2_model.h:50-66 `GPT2Config`,
graph/gemma_model.h:17-43 `GemmaTextConfig`, both with `from_pretrained(dir)`).
We parse with the stdlib json module instead of hand-rolled string scanning,
but keep the same field surface + defaults so the same HF checkpoint dirs work.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional


def _load_config_json(model_dir: str) -> dict:
    path = os.path.join(model_dir, "config.json")
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass
class GPT2Config:
    """GPT-2 family config (reference: graph/gpt2_model.h:50-66).

    Field names follow HF `config.json` for GPT-2 (n_embd/n_head/n_layer/...).
    """

    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    # "gelu_new" = tanh approximation; the reference's gelu matches HF
    # gelu_new / gelu_pytorch_tanh (reference: core/ops.cpp:1055-1062).
    activation_function: str = "gelu_new"
    embd_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    attn_pdrop: float = 0.0
    tie_word_embeddings: bool = True
    # Attention impl: "auto" (flash from S >= 512 at D <= 128, S >= 2048
    # at D = 256; measured e2e on v5e — ops/attention.resolve_impl),
    # "flash" (Pallas kernel), "xla" (jnp reference).
    attention_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @classmethod
    def from_pretrained(cls, model_dir: str) -> "GPT2Config":
        raw = _load_config_json(model_dir)
        return cls(
            vocab_size=raw.get("vocab_size", 50257),
            n_positions=raw.get("n_positions", raw.get("n_ctx", 1024)),
            n_embd=raw.get("n_embd", 768),
            n_layer=raw.get("n_layer", 12),
            n_head=raw.get("n_head", 12),
            layer_norm_epsilon=raw.get("layer_norm_epsilon", 1e-5),
            activation_function=raw.get("activation_function", "gelu_new"),
            embd_pdrop=raw.get("embd_pdrop", 0.0),
            resid_pdrop=raw.get("resid_pdrop", 0.0),
            attn_pdrop=raw.get("attn_pdrop", 0.0),
            tie_word_embeddings=raw.get("tie_word_embeddings", True),
        )

    @classmethod
    def gpt2_small(cls) -> "GPT2Config":
        return cls()

    @classmethod
    def gpt2_medium(cls) -> "GPT2Config":
        return cls(n_embd=1024, n_layer=24, n_head=16)

    @classmethod
    def gpt2_large(cls) -> "GPT2Config":
        return cls(n_embd=1280, n_layer=36, n_head=20)

    @classmethod
    def gpt2_xl(cls) -> "GPT2Config":
        return cls(n_embd=1600, n_layer=48, n_head=25)

    @classmethod
    def tiny(cls, vocab_size: int = 257) -> "GPT2Config":
        """A tiny config for tests (fast CPU forward/backward)."""
        return cls(vocab_size=vocab_size, n_positions=64, n_embd=32,
                   n_layer=2, n_head=2)


@dataclasses.dataclass
class Gemma3TextConfig:
    """Gemma-3 text-decoder config (reference: graph/gemma_model.h:17-43).

    Defaults are the Gemma-3-270M text config. Key Gemma-3 specifics mirrored
    from the reference model graph (graph/gemma_model.cpp):
    - embeddings scaled by sqrt(hidden_size) (gemma_model.cpp:222-248)
    - GQA with num_key_value_heads < num_attention_heads
    - per-head q/k RMSNorm
    - dual RoPE theta: `rope_theta` (global layers) vs `rope_local_base_freq`
      (sliding-window layers), chosen per `layer_types[i]`
      (gemma_model.cpp:579-625)
    - 512-token sliding-window mask on local layers (gemma_model.h:26)
    - sandwich norms + (1+weight) RMSNorm semantics (core/ops.cpp:1489)
    - untied behavior: lm_head weight is tied to embeddings in HF Gemma-3.
    """

    vocab_size: int = 262144
    hidden_size: int = 640
    intermediate_size: int = 2048
    num_hidden_layers: int = 18
    num_attention_heads: int = 4
    num_key_value_heads: int = 1
    head_dim: int = 256
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_local_base_freq: float = 10000.0
    sliding_window: int = 512
    # Per-layer attention type: "full_attention" | "sliding_attention".
    # Gemma-3 default pattern: 5 local : 1 global.
    layer_types: Optional[List[str]] = None
    query_pre_attn_scalar: float = 256.0
    hidden_activation: str = "gelu_pytorch_tanh"
    tie_word_embeddings: bool = True
    sliding_window_pattern: int = 6
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.layer_types is None:
            p = self.sliding_window_pattern
            self.layer_types = [
                "full_attention" if (i + 1) % p == 0 else "sliding_attention"
                for i in range(self.num_hidden_layers)
            ]

    def is_global_layer(self, i: int) -> bool:
        return self.layer_types[i] == "full_attention"

    @classmethod
    def from_pretrained(cls, model_dir: str) -> "Gemma3TextConfig":
        raw = _load_config_json(model_dir)
        # Multimodal Gemma-3 checkpoints nest the text config.
        if "text_config" in raw:
            raw = raw["text_config"]
        kw = {}
        for f in dataclasses.fields(cls):
            if f.name in raw:
                kw[f.name] = raw[f.name]
        return cls(**kw)

    @classmethod
    def gemma3_270m(cls) -> "Gemma3TextConfig":
        return cls()

    @classmethod
    def gemma3_1b(cls) -> "Gemma3TextConfig":
        return cls(hidden_size=1152, intermediate_size=6912,
                   num_hidden_layers=26, num_attention_heads=4,
                   num_key_value_heads=1, head_dim=256)

    @classmethod
    def tiny(cls, vocab_size: int = 300) -> "Gemma3TextConfig":
        """Tiny config for tests; keeps GQA + local/global interleave."""
        return cls(vocab_size=vocab_size, hidden_size=32,
                   intermediate_size=64, num_hidden_layers=4,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                   max_position_embeddings=128, sliding_window=16,
                   query_pre_attn_scalar=8.0, sliding_window_pattern=3)


@dataclasses.dataclass
class AfmoeConfig:
    """AFMoE text decoder (Arcee Trinity; HF `model_type: afmoe`) — the
    published `config.json` keys, plus what this chip holds of each
    expert layer.

    `num_experts` is the router's width (every expert of the model);
    `experts_held = (first, count)` says which of them live here
    (models/afmoe.py computes their part only, ops/moe.py). Defaults to
    all. `vocab_size` is the rows of the embedding and of the untied head
    that live here: a sliced vocabulary is a smaller vocabulary.
    Defaults are Trinity-Large-Preview's widths at a depth of one period.
    """

    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 5
    num_dense_layers: int = 1
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.448
    sliding_window: int = 4096
    global_attn_every_n_layers: int = 4
    layer_types: Optional[List[str]] = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 262144
    attention_impl: str = "auto"
    experts_held: Optional[tuple] = None

    def __post_init__(self):
        if self.layer_types is None:
            p = self.global_attn_every_n_layers
            self.layer_types = [
                "full_attention" if (i + 1) % p == 0 else "sliding_attention"
                for i in range(self.num_hidden_layers)]
        self.layer_types = list(self.layer_types)[:self.num_hidden_layers]
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(int(x) for x in self.experts_held)
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f"experts_held={self.experts_held} is not a "
                             f"range of the {self.num_experts} experts")
        if self.score_func != "sigmoid" or self.tie_word_embeddings \
                or self.num_shared_experts != 1:
            raise ValueError(
                "AfmoeConfig: only score_func=sigmoid, an untied head and "
                "one shared expert are implemented (the published Trinity "
                "configurations)")

    def is_global_layer(self, i: int) -> bool:
        return self.layer_types[i] == "full_attention"

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def with_expert_parallel(self, n: int, rank: int) -> "AfmoeConfig":
        """This chip as rank `rank` of `n` that share each expert layer:
        experts [rank·E/n, (rank+1)·E/n)."""
        if n < 1 or self.num_experts % n or not 0 <= rank < n:
            raise ValueError(f"expert_parallel={n} rank={rank} does not "
                             f"divide {self.num_experts} experts")
        per = self.num_experts // n
        return dataclasses.replace(self, experts_held=(rank * per, per))

    @classmethod
    def from_pretrained(cls, model_dir: str) -> "AfmoeConfig":
        raw = _load_config_json(model_dir)
        return cls(**{f.name: raw[f.name] for f in dataclasses.fields(cls)
                      if f.name in raw})

    @classmethod
    def tiny(cls, vocab_size: int = 300) -> "AfmoeConfig":
        """Tiny config for tests: one dense layer, then one whole period
        (s, s, f, s) of expert layers, window < S, GQA, 8 experts top-2."""
        return cls(vocab_size=vocab_size, hidden_size=64,
                   intermediate_size=128, moe_intermediate_size=32,
                   num_hidden_layers=5, num_dense_layers=1,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=16, num_experts=8, num_experts_per_tok=2,
                   sliding_window=8, max_position_embeddings=128)


@dataclasses.dataclass
class LingHybridConfig:
    """The language model of inclusionAI's Ling-3.0-flash family (the text
    sibling's `model_type` is `bailing_hybrid`): delta-rule linear
    attention (KDA) in `layer_group_size - 1` layers of every
    `layer_group_size`, latent attention (MLA) in the last of each group,
    a SwiGLU MLP below `first_k_dense_replace` and sparse experts routed
    by groups above — the published `config.json` keys (DESIGN.md §32
    says which equation reads which), plus what this chip holds of each
    expert layer, as AfmoeConfig does.

    `num_experts` is the router's width; `experts_held = (first, count)`
    the experts that live here; `vocab_size` the rows held. Defaults are
    Ling-3.0-flash-VL's widths at one dense layer plus one period."""

    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 7
    first_k_dense_replace: int = 1
    layer_group_size: int = 6
    num_attention_heads: int = 32
    head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_safe_gate: bool = True
    no_kda_lora: bool = True
    gated_attention_proj_granularity_type: str = "head_wise"
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rotary_dim: int = 64
    rope_theta: float = 6000000.0
    use_qk_norm: bool = True
    num_experts: int = 512
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    score_function: str = "sigmoid"
    moe_router_enable_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    expert_swiglu_limit_list: Optional[List[float]] = None
    share_expert_swiglu_limit_list: Optional[List[float]] = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 131072
    attention_impl: str = "auto"
    kda_chunk: int = 64
    experts_held: Optional[tuple] = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(int(x) for x in self.experts_held)
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f"experts_held={self.experts_held} is not a "
                             f"range of the {self.num_experts} experts")
        limits = [x for name in ("expert_swiglu_limit_list",
                                 "share_expert_swiglu_limit_list")
                  for x in (getattr(self, name) or [])
                  [:self.num_hidden_layers]]
        if any(limits):
            raise ValueError(
                "LingHybridConfig: a non-zero SwiGLU limit among the kept "
                f"layers ({limits}): config.json does not give the "
                "clamp's formula, and guessing one under the model's name "
                "is worse than refusing (the published limits are 0 for "
                "layers 0-33)")
        if (self.score_function != "sigmoid" or self.tie_word_embeddings
                or self.num_shared_experts != 1
                or self.q_lora_rank is not None
                or not self.kda_safe_gate or not self.no_kda_lora
                or not self.use_qk_norm
                or not self.moe_router_enable_expert_bias
                or self.gated_attention_proj_granularity_type
                != "head_wise"
                or self.rotary_dim != self.qk_rope_head_dim
                or self.num_experts % self.n_group):
            raise ValueError(
                "LingHybridConfig: only the published Ling-3.0-flash form "
                "is implemented (sigmoid scores with a selection bias, an "
                "untied head, one shared expert, no query latent, the "
                "safe gate at full rank, head-wise output gates, q/k "
                "norm, rotary over the rope dims, groups that divide the "
                "experts)")

    def layer_kind(self, i: int) -> str:
        return "mla" if (i + 1) % self.layer_group_size == 0 else "kda"

    @property
    def layer_kinds(self) -> List[str]:
        return [self.layer_kind(i) for i in range(self.num_hidden_layers)]

    def is_dense_layer(self, i: int) -> bool:
        return i < self.first_k_dense_replace

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def with_expert_parallel(self, n: int, rank: int) -> "LingHybridConfig":
        """This chip as rank `rank` of `n` that share each expert layer:
        experts [rank·E/n, (rank+1)·E/n) — with n = n_group a rank's
        experts are one routing group."""
        if n < 1 or self.num_experts % n or not 0 <= rank < n:
            raise ValueError(f"expert_parallel={n} rank={rank} does not "
                             f"divide {self.num_experts} experts")
        per = self.num_experts // n
        return dataclasses.replace(self, experts_held=(rank * per, per))

    @classmethod
    def from_pretrained(cls, model_dir: str) -> "LingHybridConfig":
        raw = _load_config_json(model_dir)
        return cls(**{f.name: raw[f.name] for f in dataclasses.fields(cls)
                      if f.name in raw})

    @classmethod
    def tiny(cls, vocab_size: int = 300) -> "LingHybridConfig":
        """Tiny config for tests: one dense layer, then one whole period
        (k k k k m k on the cut's own indices), 16 experts in 4 groups of
        which 2 are kept, top-2; MLA at 24 + 8 rotary / v 16, so q.k and
        v differ in width as at the published sizes."""
        return cls(vocab_size=vocab_size, hidden_size=64,
                   intermediate_size=128, moe_intermediate_size=32,
                   moe_shared_expert_intermediate_size=32,
                   num_hidden_layers=7, first_k_dense_replace=1,
                   num_attention_heads=4, head_dim=16, kv_lora_rank=32,
                   qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
                   rotary_dim=8, num_experts=16, num_experts_per_tok=2,
                   n_group=4, topk_group=2, kda_chunk=16,
                   max_position_embeddings=256)


@dataclasses.dataclass
class Xing4Config:
    """XingChen-AGI's Xing4.0 family (`model_type: xing4_0`): DeepSeek-V3's
    latent attention with a query latent in every layer, a SwiGLU MLP
    below `first_k_dense_replace` and sigmoid-routed experts above, an
    `hc_mult`-row residual stream mixed by manifold-constrained
    hyper-connections around every sublayer, and
    `num_nextn_predict_layers` multi-token-prediction modules — the
    published `config.json` keys (DESIGN.md §33 says which equation reads
    which), plus what this chip holds of each expert layer, as
    AfmoeConfig does.

    `n_routed_experts` is the router's width; `experts_held = (first,
    count)` the experts that live here; `vocab_size` the rows held;
    `mtp_loss_weight` the second loss's weight (the trainer's flag: 0
    drops the module from the step). Defaults are Xing4.0-29B-A4B's
    widths at one dense layer plus four sparse ones."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    attention_bias: bool = False
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    moe_layer_freq: int = 1
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 262144
    attention_impl: str = "auto"
    mtp_loss_weight: float = 0.3
    experts_held: Optional[tuple] = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        self.experts_held = tuple(int(x) for x in self.experts_held)
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f"experts_held={self.experts_held} is not a "
                             f"range of the {self.n_routed_experts} experts")
        rs = self.rope_scaling
        if (self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc"
                or self.tie_word_embeddings or self.n_shared_experts != 1
                or self.attention_bias or self.moe_layer_freq != 1
                or self.num_key_value_heads != self.num_attention_heads
                or self.num_nextn_predict_layers not in (0, 1)
                or self.n_routed_experts % self.n_group
                or (rs is not None and rs.get("type") != "yarn")):
            raise ValueError(
                "Xing4Config: only the published Xing4.0 form is "
                "implemented (sigmoid scores with a selection bias, an "
                "untied head, one shared expert, experts in every layer "
                "above the dense ones, as many key as query heads, no "
                "attention bias, at most one multi-token-prediction "
                "module, yarn rotary or none, groups that divide the "
                "experts)")

    def is_dense_layer(self, i: int) -> bool:
        return i < self.first_k_dense_replace

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def head_dim(self) -> int:
        """The width the in-loop FLOP estimate counts attention at."""
        return self.qk_head_dim

    @property
    def has_mtp(self) -> bool:
        """Whether the step runs the multi-token-prediction module."""
        return self.num_nextn_predict_layers > 0 and self.mtp_loss_weight > 0

    def with_expert_parallel(self, n: int, rank: int) -> "Xing4Config":
        """This chip as rank `rank` of `n` that share each expert layer:
        experts [rank·E/n, (rank+1)·E/n)."""
        if n < 1 or self.n_routed_experts % n or not 0 <= rank < n:
            raise ValueError(f"expert_parallel={n} rank={rank} does not "
                             f"divide {self.n_routed_experts} experts")
        per = self.n_routed_experts // n
        return dataclasses.replace(self, experts_held=(rank * per, per))

    @classmethod
    def from_pretrained(cls, model_dir: str) -> "Xing4Config":
        raw = _load_config_json(model_dir)
        return cls(**{f.name: raw[f.name] for f in dataclasses.fields(cls)
                      if f.name in raw})

    @classmethod
    def tiny(cls, vocab_size: int = 300) -> "Xing4Config":
        """Tiny config for tests: one dense layer and two sparse ones, 8
        experts top-2, MLA at 24 + 8 rotary / v 16 with both latents, a
        4-row stream, yarn over an original length of 16 (so a test of
        32 tokens runs past it), one multi-token-prediction module."""
        return cls(vocab_size=vocab_size, hidden_size=64,
                   intermediate_size=128, moe_intermediate_size=32,
                   num_hidden_layers=3, first_k_dense_replace=1,
                   num_attention_heads=4, num_key_value_heads=4,
                   q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=24,
                   qk_rope_head_dim=8, v_head_dim=16,
                   rope_scaling={"type": "yarn", "factor": 8,
                                 "original_max_position_embeddings": 16,
                                 "beta_fast": 4, "beta_slow": 1,
                                 "mscale": 1, "mscale_all_dim": 1},
                   n_routed_experts=8, num_experts_per_tok=2,
                   max_position_embeddings=128)


@dataclasses.dataclass
class Phi4FlashConfig:
    """Microsoft's Phi-4-mini-flash-reasoning (`model_type: phi4flash`),
    the SambaY decoder-hybrid-decoder: a self-decoder of Mamba layers
    (even indices) beside sliding-window attention (odd), then at
    L/2 one more Mamba layer whose scan readout is kept as the memory
    and at L/2 + 1 full attention whose keys and values are kept, then a
    cross-decoder of gated memory units (even) beside cross-attention over
    that one layer's keys and values (odd). Every attention is
    differential. The published `config.json` keys (DESIGN.md §34 says
    which equation reads which), plus the Mamba sizes the config leaves
    to the family's code (`mamba_*`: Mamba's and Samba's convention,
    assumed), `ssm_chunk`, the scan's chunk (ops/ssm.py), and
    `mixer_chunk`, the tokens a Mamba mixer and an MLP take at a time
    (models/phi4flash.by_pieces: what bounds a layer's backward). Defaults
    are the published model's, whole: nothing is cut."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    max_position_embeddings: int = 262144
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None
    ssm_chunk: int = 64
    mixer_chunk: int = 2048
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.hidden_size // 16)
        L = self.num_hidden_layers
        if (self.mb_per_layer != 2 or L % 4 or self.hidden_act != "silu"
                or not self.tie_word_embeddings or self.mlp_bias
                or self.lm_head_bias
                or self.num_key_value_heads % 2
                or self.num_attention_heads % self.num_key_value_heads
                or self.hidden_size % self.num_attention_heads):
            raise ValueError(
                "Phi4FlashConfig: only the published SambaY form is "
                "implemented (a Mamba layer every second layer, a layer "
                "count that halves into two even decoders, SiLU, a tied "
                "head without biases, an even number of key heads that "
                "divides the query heads' pairs)")

    def layer_kind(self, i: int) -> str:
        """mamba | swa | full | gmu | cross (the memory's Mamba layer
        at L/2 is `mamba`, the keys' and values' layer at L/2 + 1 is
        `full`)."""
        mid = self.num_hidden_layers // 2
        if i <= mid:
            return "mamba" if i % 2 == 0 else "swa"
        if i == mid + 1:
            return "full"
        return "gmu" if i % 2 == 0 else "cross"

    @property
    def layer_kinds(self) -> List[str]:
        return [self.layer_kind(i) for i in range(self.num_hidden_layers)]

    @property
    def num_pairs(self) -> int:
        """(Mamba, window attention) pairs of the self-decoder; the
        cross-decoder has one fewer (GMU, cross-attention) pairs."""
        return self.num_hidden_layers // 4

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def ssm_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def with_expert_parallel(self, n: int, rank: int) -> "Phi4FlashConfig":
        """The trainer's deployment flags: the family has no experts, so
        only the whole model (1, 0) is a deployment of it."""
        if (n, rank) != (1, 0):
            raise ValueError(f"expert_parallel={n} rank={rank}: "
                             "Phi-4-mini-flash has no experts to share")
        return self

    @classmethod
    def from_pretrained(cls, model_dir: str) -> "Phi4FlashConfig":
        raw = _load_config_json(model_dir)
        return cls(**{f.name: raw[f.name] for f in dataclasses.fields(cls)
                      if f.name in raw})

    @classmethod
    def tiny(cls, vocab_size: int = 300) -> "Phi4FlashConfig":
        """Tiny config for tests: 8 layers (m s m s | m f | g c: two
        self-decoder pairs, the middle pair, one cross pair), hidden 64
        in 8 query heads of 8 and 4 key heads (4 and 2 differential
        pairs), Mamba inner 128 with a state of 16, a window of 8, a scan
        chunk of 8 and pieces of 16 tokens (a test of 40 runs two whole
        pieces and a remainder)."""
        return cls(vocab_size=vocab_size, hidden_size=64,
                   intermediate_size=128, num_hidden_layers=8,
                   num_attention_heads=8, num_key_value_heads=4,
                   sliding_window=8, max_position_embeddings=256,
                   ssm_chunk=8, mixer_chunk=16)
