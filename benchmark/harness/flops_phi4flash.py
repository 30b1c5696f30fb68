"""Operations and bytes of Phi-4-mini-flash-reasoning from shapes — the
yardstick's arithmetic for `phi4flash_lora_long`, written against the
configuration FILE (the published keys plus what `assumed` sets: the
Mamba sizes d_state 16, expand 2, dt_rank ceil(H / 16)), like
harness/flops_ling.py.

Matmul parameters only (the embedding gathers; the conv's taps, the
norms, A_log, D, the lambdas are element-wise). A token meets every
mixer's projections, every MLP and the tied head. Differential attention
is two maps a layer over the admitted (query, key) pairs — causal over S
in the full and cross layers, the window's pairs in the window layers —
q.k over the head size d and P v over 2 d, for nq / 2 query heads a map.
The selective scan is element-wise float32 work on the [d_in, N] state,
counted a token and layer:
  forward: Delta A, its exp, the decay times the state, (Delta u) B,
  the sum, times C and the sum over N: 7 d_in N;
  backward: twice that.
"""
from __future__ import annotations

from .flops import roofline_seconds  # noqa: F401  (re-exported)
from .flops_afmoe import admitted_pairs

SCAN_FLOPS_FWD = 7      # a state element a token, forward (above)


def dims(cfg: dict) -> dict:
    H = cfg["hidden_size"]
    return {"H": H, "I": cfg["intermediate_size"], "di": 2 * H, "N": 16,
            "r": -(-H // 16), "K": 4, "d": H // cfg["num_attention_heads"],
            "nq": cfg["num_attention_heads"],
            "nkv": cfg["num_key_value_heads"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "W": cfg["sliding_window"]}


def layer_kinds(cfg: dict) -> list:
    L = cfg["num_hidden_layers"]
    mid = L // 2
    return [("mamba" if l % 2 == 0 else "swa") if l <= mid
            else "full" if l == mid + 1
            else ("gmu" if l % 2 == 0 else "cross") for l in range(L)]


def layer_counts(cfg: dict) -> dict:
    kinds = layer_kinds(cfg)
    return {k: kinds.count(k) for k in ("mamba", "swa", "full", "gmu",
                                        "cross")}


def matmul_params(cfg: dict) -> dict:
    """Parameters of each matmul group, a layer."""
    m = dims(cfg)
    H, di, d = m["H"], m["di"], m["d"]
    q = H * m["nq"] * d
    kv = 2 * H * m["nkv"] * d
    return {"mamba": H * 2 * di + di * (m["r"] + 2 * m["N"])
            + m["r"] * di + di * H,
            "swa": 2 * q + kv, "full": 2 * q + kv, "cross": 2 * q,
            "gmu": 2 * H * di, "mlp": 3 * H * m["I"], "head": m["V"] * H}


def params_per_token(cfg: dict) -> int:
    """Matmul parameters a token meets."""
    p, n = matmul_params(cfg), layer_counts(cfg)
    return sum(n[k] * p[k] for k in n) + cfg["num_hidden_layers"] \
        * p["mlp"] + p["head"]


def param_count(cfg: dict) -> int:
    """Every parameter of the model as held (the tied head once)."""
    m = dims(cfg)
    H, di, d, N = m["H"], m["di"], m["d"], m["N"]
    p, n = matmul_params(cfg), layer_counts(cfg)
    mamba_extra = m["K"] * di + di + di + di * N + di     # conv, b, A, D
    attn_extra = 4 * d + 2 * d                            # lambdas, subln
    per_layer_norms = 4 * H
    return (sum(n[k] * p[k] for k in n)
            + n["mamba"] * mamba_extra
            + (n["swa"] + n["full"] + n["cross"]) * attn_extra
            + m["L"] * (p["mlp"] + per_layer_norms) + p["head"] + 2 * H)


def lora_dims(cfg: dict) -> dict:
    m = dims(cfg)
    H, di, d = m["H"], m["di"], m["d"]
    return {"q_proj": (H, m["nq"] * d), "k_proj": (H, m["nkv"] * d),
            "v_proj": (H, m["nkv"] * d), "o_proj": (m["nq"] * d, H),
            "in_proj": (H, 2 * di), "out_proj": (di, H),
            "gmu_in_proj": (H, di), "gmu_out_proj": (di, H)}


def lora_layers(cfg: dict) -> dict:
    """Layers each target's stack runs over."""
    n = layer_counts(cfg)
    attn = n["swa"] + n["full"] + n["cross"]
    return {"q_proj": attn, "o_proj": attn, "k_proj": n["swa"] + n["full"],
            "v_proj": n["swa"] + n["full"], "in_proj": n["mamba"],
            "out_proj": n["mamba"], "gmu_in_proj": n["gmu"],
            "gmu_out_proj": n["gmu"]}


def lora_params(cfg: dict, rank: int, targets) -> int:
    dims_, n = lora_dims(cfg), lora_layers(cfg)
    return sum(n[t] * rank * sum(dims_[t]) for t in targets)


def attention_pairs(cfg: dict, S: int) -> dict:
    """Admitted (query, key) pairs of one sequence, over the layers of
    each kind: the window's in the window layers, causal in the others."""
    n = layer_counts(cfg)
    return {"window": n["swa"] * admitted_pairs(S, cfg["sliding_window"]),
            "causal": (n["full"] + n["cross"]) * admitted_pairs(S)}


def attention_flops(cfg: dict, B: int, S: int) -> int:
    """Forward q.k^T over d and P v over 2 d, two maps of nq / 2 heads a
    layer, 2 flops a multiply-add, all attention layers."""
    m = dims(cfg)
    pairs = sum(attention_pairs(cfg, S).values())
    return 2 * 2 * B * (m["nq"] // 2) * (m["d"] + 2 * m["d"]) * pairs


def scan_flops(cfg: dict, B: int, S: int) -> int:
    """Forward element-wise flops of the selective scan, all Mamba
    layers."""
    m = dims(cfg)
    return SCAN_FLOPS_FWD * m["di"] * m["N"] * B * S \
        * layer_counts(cfg)["mamba"]


def required_train_flops(cfg: dict, B: int, S: int, *, n_lora: int
                         ) -> float:
    """Forward + backward the algorithm REQUIRES for one LoRA optimizer
    step (recomputation does not count): forward 2NT and backward-to-x
    2NT through every matmul, dW for the adapters only; attention and
    the scan forward once and backward twice."""
    T = B * S
    fixed = params_per_token(cfg) + n_lora
    return 4 * fixed * T + 2 * n_lora * T \
        + 3 * attention_flops(cfg, B, S) + 3 * scan_flops(cfg, B, S)


def ssm_scan_work(cfg: dict, B: int, S: int, *, itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the selective scan in one optimizer step,
    all Mamba layers: ONE forward and ONE backward at twice its flops —
    the work the scan requires whatever implements it, with no term for
    recomputation (what the program's remat and the chunks' backward
    recompute is its cost, not work). Bytes: the forward reads u (the
    activations' dtype, `itemsize`), Delta (float32), B and C (float32)
    and writes y (float32); the backward reads dy (float32) and writes
    du (`itemsize`), dDelta, dB, dC (float32); dA_log and dD are a
    layer's [d_in, N] and [d_in] once."""
    m = dims(cfg)
    di, N = m["di"], m["N"]
    T, L = B * S, layer_counts(cfg)["mamba"]
    fwd_bytes = (di * (itemsize + 4 + 4) + 2 * N * 4) * T * L
    bwd_bytes = (di * (4 + itemsize + 4) + 2 * N * 4) * T * L \
        + (di * N + di) * 4 * L
    return {"flops": 3 * scan_flops(cfg, B, S),
            "bytes": fwd_bytes + bwd_bytes}


def flash_diff_work(cfg: dict, B: int, S: int, *, remat: bool,
                    itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the flash kernels of differential attention
    in one optimizer step: two maps a layer, each forward (twice under
    remat: the kernels run again when the layer is recomputed) q.k^T
    over d and P v over 2 d; backward S, dP, dV, dQ, dK (3 d + 2 x 2 d);
    bytes a map: q (nq / 2 heads of d), k (nkv / 2 of d), v (nkv / 2 of
    2 d), o (nq / 2 of 2 d) forward; those, dO and the three gradients
    backward."""
    m = dims(cfg)
    nq, nkv, d = m["nq"] // 2, m["nkv"] // 2, m["d"]
    n = layer_counts(cfg)
    maps = 2 * (n["swa"] + n["full"] + n["cross"])
    pairs = 2 * B * sum(attention_pairs(cfg, S).values())  # two maps
    fwd = 2 * nq * (d + 2 * d) * pairs
    bwd = 2 * nq * (3 * d + 2 * 2 * d) * pairs
    io = nq * d + nkv * d + nkv * 2 * d + nq * 2 * d
    fwd_bytes = io * B * S * maps * itemsize
    bwd_bytes = (2 * io + nq * 2 * d) * B * S * maps * itemsize
    n_fwd = 2 if remat else 1
    return {"flops": n_fwd * fwd + bwd,
            "bytes": n_fwd * fwd_bytes + bwd_bytes}
