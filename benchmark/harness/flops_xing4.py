"""Operations and bytes of Xing4.0 from shapes — the yardstick's
arithmetic for `xing_lora_long`, written against the configuration FILE
(the published keys plus `deployment`), like harness/flops_afmoe.py and
flops_ling.py.

Blocks: `num_hidden_layers` layers (the first `first_k_dense_replace`
dense) and, beyond them, `num_nextn_predict_layers` multi-token-
prediction modules of one sparse block each. Matmul parameters only (the
embedding gathers; norms, the hyper-connections' b and alpha are
element-wise). A token meets every block's mixer and its two phi
products, the dense layers' MLP, the shared expert, the router, the
module's merge W_eh, and the head's rows held here TWICE (the main
model's logits and the module's) — and its routed experts by the
measured number of assignments (`moe_assign_here`, which counts the
module's block too). Latent attention is counted over the causal pairs
at its TRUE widths (q.k over nope + rope, v over v_head_dim: the zero
padding of q and k to a kernel width is not work).

The stream's mixing is bandwidth work: `mhc_mix_work` writes out the
bytes it must move a step, from shapes alone.
"""
from __future__ import annotations

from .flops import roofline_seconds  # noqa: F401  (re-exported)
from .flops_afmoe import admitted_pairs


def block_counts(cfg: dict) -> dict:
    nd, L = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    m = cfg.get("num_nextn_predict_layers", 0)
    return {"dense": nd, "moe": L - nd + m, "mtp": m, "blocks": L + m}


def matmul_params(cfg: dict) -> dict:
    """Parameters of each matmul group, per block / per expert."""
    H, n = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, r, hc = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["hc_mult"]
    return {
        "mla": H * rq + rq * n * (dn + dr) + H * (r + dr)
        + r * n * (dn + dv) + n * dv * H,
        # the two sublayers' phi: [hc H, 2 hc + hc^2] each
        "hyper": 2 * hc * H * (2 * hc + hc * hc),
        "dense_mlp": 3 * H * cfg["intermediate_size"],
        "shared": 3 * H * cfg["moe_intermediate_size"]
        * cfg["n_shared_experts"],
        "router": H * cfg["deployment"]["router_width"],
        "expert": 3 * H * cfg["moe_intermediate_size"],
        "merge": 2 * H * H,
        "head": cfg["vocab_size"] * H}


def held_params(cfg: dict) -> dict:
    """Parameters as HELD on this chip, by the issue's groups."""
    p, n = matmul_params(cfg), block_counts(cfg)
    experts = cfg["n_routed_experts"] * p["expert"]
    sparse = p["mla"] + p["hyper"] + experts + p["shared"] + p["router"]
    dense = p["mla"] + p["hyper"] + p["dense_mlp"]
    return {"mla": p["mla"], "experts_held_a_layer": experts,
            "sparse_layer": sparse, "dense_layer": dense,
            "mtp_module": n["mtp"] * (p["merge"] + sparse),
            "embedding_and_head": 2 * p["head"],
            "total": n["dense"] * dense + n["moe"] * sparse
            + n["mtp"] * p["merge"] + 2 * p["head"]}


def fixed_params_per_token(cfg: dict) -> int:
    """Matmul parameters EVERY token meets (all but the routed experts):
    the head once for each loss."""
    p, n = matmul_params(cfg), block_counts(cfg)
    return (n["blocks"] * (p["mla"] + p["hyper"])
            + n["dense"] * p["dense_mlp"]
            + n["moe"] * (p["shared"] + p["router"])
            + n["mtp"] * p["merge"] + (1 + n["mtp"]) * p["head"])


def lora_dims(cfg: dict) -> dict:
    H, n = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return {"q_a_proj": (H, rq), "q_b_proj": (rq, n * (dn + dr)),
            "kv_a_proj": (H, r + dr), "kv_b_proj": (r, n * (dn + dv)),
            "o_proj": (n * dv, H)}


def lora_params(cfg: dict, rank: int, targets) -> int:
    dims = lora_dims(cfg)
    return block_counts(cfg)["blocks"] * sum(rank * sum(dims[t])
                                             for t in targets)


def attention_flops(cfg: dict, B: int, S: int) -> int:
    """Forward q.k^T over nope + rope and P v over v_head_dim, causal
    pairs, 2 flops a multiply-add, every block."""
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2 * B * cfg["num_attention_heads"] * (dq + cfg["v_head_dim"]) \
        * block_counts(cfg)["blocks"] * admitted_pairs(S)


def required_train_flops(cfg: dict, B: int, S: int, *, n_lora: int,
                         assign_here: float) -> float:
    """Forward + backward the algorithm REQUIRES for one LoRA optimizer
    step (recomputation does not count): forward 2NT and backward-to-x
    2NT through every matmul executed — the routed experts by the
    measured assignments, the head twice, the phi products — dW for the
    adapters only; attention forward once and backward twice."""
    T = B * S
    fixed = fixed_params_per_token(cfg) + n_lora
    routed = assign_here * matmul_params(cfg)["expert"]
    return 4 * (fixed * T + routed) + 2 * n_lora * T \
        + 3 * attention_flops(cfg, B, S)


def mhc_mix_work(cfg: dict, B: int, S: int, *, itemsize: int = 2) -> dict:
    """HBM bytes the stream's mixing must move in one optimizer step, from
    shapes alone, at the compute dtype: ONE forward and the backward's
    reads and writes, no term for recomputation — the same whatever
    implements it, so a later fusion or remat policy moves the share only
    through the time. With X = hc·T·C·itemsize (the stream) and
    Y = T·C·itemsize (one row's worth), a sublayer:
      forward   read x once for the coefficients and the pre-mix (X),
                write u (Y); read x and y, write x' for the post-mix
                (2X + Y)                                   = 3X + 2Y
      backward  read x, dx', y and du (2X + 2Y), write dx and dy
                (X + Y)                                    = 3X + 3Y
    The coefficients themselves (24 floats a token) are not counted. Two
    sublayers a block; the phi products' FLOPs (tiny beside the bytes:
    2·hc·C·24 a token and sublayer) are returned for the bound's other
    side."""
    T, C, hc = B * S, cfg["hidden_size"], cfg["hc_mult"]
    Y = T * C * itemsize
    X = hc * Y
    sub = 2 * block_counts(cfg)["blocks"]
    K = 2 * hc + hc * hc
    return {"bytes": sub * (6 * X + 5 * Y),
            "flops": sub * 3 * 2 * T * hc * C * K}


def flash_latent_work(cfg: dict, B: int, S: int, *, remat: bool,
                      itemsize: int = 2) -> dict:
    """As flops_ling.flash_latent_work, every block a latent layer: FLOPs
    and HBM bytes of latent attention's kernels in one optimizer step at
    the TRUE widths: forward (twice under remat) q.k^T and P v; backward
    S, dP, dV, dQ, dK; bytes q, k at nope + rope and v, o, dO at
    v_head_dim, gradients likewise."""
    n = cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    L = block_counts(cfg)["blocks"]
    pairs = B * L * admitted_pairs(S)
    fwd = 2 * n * (dq + dv) * pairs
    bwd = 2 * n * (3 * dq + 2 * dv) * pairs
    n_fwd = 2 if remat else 1
    fwd_bytes = n * (2 * dq + 2 * dv) * B * L * S * itemsize
    bwd_bytes = n * (4 * dq + 4 * dv) * B * L * S * itemsize
    return {"flops": n_fwd * fwd + bwd,
            "bytes": n_fwd * fwd_bytes + bwd_bytes}


def gmm_work(cfg: dict, assign_here: float, *, remat: bool,
             itemsize: int = 2) -> dict:
    """As flops_afmoe.gmm_work, at this family's widths (K 3584 / N 1024
    and back, 64 held): three products an assignment, forward (twice
    under remat) and once to x; bytes a pass: the held experts' weights
    of every expert block once, plus the rows in and out."""
    H, Im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    passes = (2 if remat else 1) + 1
    weights = block_counts(cfg)["moe"] * cfg["n_routed_experts"] \
        * matmul_params(cfg)["expert"]
    rows = assign_here * 3 * (H + Im)
    return {"flops": passes * 2 * assign_here * matmul_params(cfg)["expert"],
            "bytes": passes * (weights + rows) * itemsize}
