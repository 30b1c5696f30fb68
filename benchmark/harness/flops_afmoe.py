"""Operations and bytes of the AFMoE family from shapes — the yardstick's
arithmetic for `trinity_lora_s8192`, written against the configuration
FILE (the published HF keys plus `deployment`), like harness/flops.py.

Matmul parameters only (the embedding gathers). What a token meets on
this chip: every attention projection with the output gate, the dense
layers' MLP, the shared expert, the router at its published width and the
head's rows held here — and its routed experts ONLY where they are held,
which is a measured number (`moe_assign_here`: assignments to held
experts a step, summed over the expert layers), not an expectation.
Attention is counted over the pairs the mask admits: j <= i on full
layers, j <= i and j > i - window on sliding ones.
"""
from __future__ import annotations

from .flops import roofline_seconds  # noqa: F401  (re-exported)


def layer_counts(cfg: dict) -> tuple:
    nd = cfg["num_dense_layers"]
    return nd, cfg["num_hidden_layers"] - nd


def matmul_params(cfg: dict) -> dict:
    """Parameters of each matmul group, per layer / per expert."""
    H, D = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"attn": H * D * (3 * nq + 2 * nkv),      # q, gate, o; k, v
            "dense_mlp": 3 * H * cfg["intermediate_size"],
            "shared": 3 * H * cfg["moe_intermediate_size"]
            * cfg["num_shared_experts"],
            "router": H * cfg["deployment"]["router_width"],
            "expert": 3 * H * cfg["moe_intermediate_size"],
            "head": cfg["vocab_size"] * H}


def fixed_params_per_token(cfg: dict) -> int:
    """Matmul parameters EVERY token meets (all but the routed experts)."""
    p = matmul_params(cfg)
    nd, nm = layer_counts(cfg)
    return ((nd + nm) * p["attn"] + nd * p["dense_mlp"]
            + nm * (p["shared"] + p["router"]) + p["head"])


def lora_params(cfg: dict, rank: int, targets) -> int:
    H, D = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dims = {"q_proj": (H, nq * D), "k_proj": (H, nkv * D),
            "v_proj": (H, nkv * D), "o_proj": (nq * D, H)}
    return cfg["num_hidden_layers"] * sum(
        rank * (dims[t][0] + dims[t][1]) for t in targets)


def admitted_pairs(S: int, window=None) -> int:
    """(query, key) pairs of one sequence the mask admits."""
    if window is None or window >= S:
        return S * (S + 1) // 2
    return window * (window + 1) // 2 + (S - window) * window


def pairs_per_sequence(cfg: dict, S: int) -> int:
    """Admitted pairs summed over the layers, by each layer's kind."""
    return sum(admitted_pairs(S, cfg["sliding_window"]
                              if kind == "sliding_attention" else None)
               for kind in cfg["layer_types"][:cfg["num_hidden_layers"]])


def attention_flops(cfg: dict, B: int, S: int) -> int:
    """Forward QK^T and PV over the admitted pairs, 2 flops a
    multiply-add, all layers."""
    return 4 * B * cfg["num_attention_heads"] * cfg["head_dim"] \
        * pairs_per_sequence(cfg, S)


def required_train_flops(cfg: dict, B: int, S: int, *, n_lora: int,
                         assign_here: float) -> float:
    """Forward + backward the algorithm REQUIRES for one LoRA optimizer
    step (recomputation under remat does not count): forward 2NT and
    backward-to-x 2NT through every matmul executed — the routed experts
    by the measured assignments — dW for the adapters only, attention
    forward once and backward twice."""
    T = B * S
    fixed = fixed_params_per_token(cfg) + n_lora
    routed = assign_here * matmul_params(cfg)["expert"]
    return 4 * (fixed * T + routed) + 2 * n_lora * T \
        + 3 * attention_flops(cfg, B, S)


def gmm_work(cfg: dict, assign_here: float, *, remat: bool,
             itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the grouped products of one optimizer step,
    whatever implements them: three products an assignment (gate, up,
    down), forward (twice under remat) and once to x. Bytes a pass: the
    held experts' weights of every expert layer once, plus the rows in
    and out of each product."""
    H, Im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    _, nm = layer_counts(cfg)
    passes = (2 if remat else 1) + 1
    weights = nm * cfg["num_experts"] * matmul_params(cfg)["expert"]
    rows = assign_here * 3 * (H + Im)
    return {"flops": passes * 2 * assign_here * matmul_params(cfg)["expert"],
            "bytes": passes * (weights + rows) * itemsize}


def flash_mixed_work(cfg: dict, B: int, S: int, *, remat: bool,
                     itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the flash kernels in one optimizer step at
    head size D with GQA: forward (twice under remat) and backward over
    the admitted pairs of every layer; q, o, dO, dQ per query head, k, v,
    dK, dV per KV head."""
    nq, nkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    L = cfg["num_hidden_layers"]
    pairs = B * pairs_per_sequence(cfg, S)
    fwd = 4 * nq * D * pairs                   # QK^T, PV
    bwd = 10 * nq * D * pairs                  # S, dP, dV, dQ, dK
    n_fwd = 2 if remat else 1
    fwd_bytes = (2 * nq + 2 * nkv) * B * L * S * D * itemsize
    bwd_bytes = (4 * nq + 4 * nkv) * B * L * S * D * itemsize
    return {"flops": n_fwd * fwd + bwd,
            "bytes": n_fwd * fwd_bytes + bwd_bytes}
