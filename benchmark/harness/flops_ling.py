"""Operations and bytes of the Ling-3.0-flash hybrid from shapes — the
yardstick's arithmetic for `ling_lora_long`, written against the
configuration FILE (the published keys plus `deployment`), like
harness/flops_afmoe.py.

Matmul parameters only (the embedding gathers; the four conv taps, the
norms, A_log and dt_bias are element-wise). A token meets every mixer's
projections and gates, the dense layers' MLP, the shared expert, the
router at its published width and the head's rows held here — and its
routed experts ONLY where they are held, a measured number
(`moe_assign_here`). Latent attention is counted over the causal pairs
at its TRUE widths (q.k over nope + rope, v over v_head_dim: the zero
padding of q and k to a kernel width is not work). The delta rule is
counted in its chunked form (chunk C, head size d), the same work
whatever implements it:
  a chunk and head, forward: K K^T and Q K^T pair products 2 x 2C^2 d,
  the unit-triangular solve for [v ; k] 2C^2 d, A d' 2C^2 d, and three
  products with the d x d state (w S, q S, K~^T d') 3 x 2C d^2
  = 8 C^2 d + 6 C d^2, i.e. 8 C d + 6 d^2 a token and head;
  backward: twice that.
"""
from __future__ import annotations

from .flops import roofline_seconds  # noqa: F401  (re-exported)
from .flops_afmoe import admitted_pairs

KDA_CHUNK = 64      # the program's chunk (run_start.config.kda_chunk)


def layer_kinds(cfg: dict) -> list:
    g = cfg["layer_group_size"]
    return ["mla" if (i + 1) % g == 0 else "kda"
            for i in range(cfg["num_hidden_layers"])]


def layer_counts(cfg: dict) -> dict:
    kinds = layer_kinds(cfg)
    nd = cfg["first_k_dense_replace"]
    return {"kda": kinds.count("kda"), "mla": kinds.count("mla"),
            "dense": nd, "moe": cfg["num_hidden_layers"] - nd}


def matmul_params(cfg: dict) -> dict:
    """Parameters of each matmul group, per layer / per expert."""
    H, n, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r = cfg["kv_lora_rank"]
    return {
        # q, k, v, o, the full-rank gate W_f; beta and the output gate
        "kda": 5 * H * n * d + 2 * H * n,
        "mla": H * n * dq + H * (r + cfg["qk_rope_head_dim"])
        + r * n * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
        + n * cfg["v_head_dim"] * H + H * n,
        "dense_mlp": 3 * H * cfg["intermediate_size"],
        "shared": 3 * H * cfg["moe_shared_expert_intermediate_size"],
        "router": H * cfg["deployment"]["router_width"],
        "expert": 3 * H * cfg["moe_intermediate_size"],
        "head": cfg["vocab_size"] * H}


def conv_params(cfg: dict) -> int:
    """The three depthwise convs' taps of a kda layer (not matmuls)."""
    return 3 * cfg["short_conv_kernel_size"] \
        * cfg["num_attention_heads"] * cfg["head_dim"]


def held_params(cfg: dict) -> dict:
    """Parameters as HELD on this chip, by the issue's groups."""
    p, n = matmul_params(cfg), layer_counts(cfg)
    experts = cfg["num_experts"] * p["expert"]
    kda = p["kda"] + conv_params(cfg)
    sparse = experts + p["shared"] + p["router"]
    return {"kda_attention": kda, "mla_attention": p["mla"],
            "experts_held_a_layer": experts,
            "sparse_kda_layer": kda + sparse,
            "sparse_mla_layer": p["mla"] + sparse,
            "dense_kda_layer": kda + p["dense_mlp"],
            "embedding_and_head": 2 * p["head"],
            "total": n["kda"] * kda + n["mla"] * p["mla"]
            + n["dense"] * p["dense_mlp"] + n["moe"] * sparse
            + 2 * p["head"]}


def fixed_params_per_token(cfg: dict) -> int:
    """Matmul parameters EVERY token meets (all but the routed experts)."""
    p, n = matmul_params(cfg), layer_counts(cfg)
    return (n["kda"] * p["kda"] + n["mla"] * p["mla"]
            + n["dense"] * p["dense_mlp"]
            + n["moe"] * (p["shared"] + p["router"]) + p["head"])


def lora_dims(cfg: dict) -> dict:
    H, n, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    return {"kda_q_proj": (H, n * d), "kda_k_proj": (H, n * d),
            "kda_v_proj": (H, n * d), "kda_o_proj": (n * d, H),
            "mla_q_proj": (H, n * (dn + dr)), "mla_kv_a_proj": (H, r + dr),
            "mla_kv_b_proj": (r, n * (dn + dv)), "mla_o_proj": (n * dv, H)}


def lora_params(cfg: dict, rank: int, targets) -> int:
    dims, n = lora_dims(cfg), layer_counts(cfg)
    return sum(n[t.split("_")[0]] * rank * sum(dims[t]) for t in targets)


def latent_pairs(cfg: dict, S: int) -> int:
    """Causal (query, key) pairs of one sequence, over the mla layers."""
    return layer_counts(cfg)["mla"] * admitted_pairs(S)


def latent_attention_flops(cfg: dict, B: int, S: int) -> int:
    """Forward q.k^T over nope + rope and P v over v_head_dim, causal
    pairs, 2 flops a multiply-add, all mla layers."""
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2 * B * cfg["num_attention_heads"] * (dq + cfg["v_head_dim"]) \
        * latent_pairs(cfg, S)


def kda_flops_per_token(cfg: dict, chunk: int = KDA_CHUNK) -> int:
    """Forward flops of the chunked delta rule, a token and layer."""
    d = cfg["head_dim"]
    return cfg["num_attention_heads"] * (8 * chunk * d + 6 * d * d)


def required_train_flops(cfg: dict, B: int, S: int, *, n_lora: int,
                         assign_here: float) -> float:
    """Forward + backward the algorithm REQUIRES for one LoRA optimizer
    step (recomputation does not count): forward 2NT and backward-to-x
    2NT through every matmul executed — the routed experts by the
    measured assignments — dW for the adapters only; latent attention
    and the delta rule forward once and backward twice."""
    T = B * S
    fixed = fixed_params_per_token(cfg) + n_lora
    routed = assign_here * matmul_params(cfg)["expert"]
    return 4 * (fixed * T + routed) + 2 * n_lora * T \
        + 3 * latent_attention_flops(cfg, B, S) \
        + 3 * layer_counts(cfg)["kda"] * kda_flops_per_token(cfg) * T


def kda_scan_work(cfg: dict, B: int, S: int, *, itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the chunked delta rule in one optimizer
    step, all kda layers: ONE forward and the backward at twice that —
    the work the rule requires, with no term for recomputation. The
    program runs the forward scan once too (the layers' remat keeps the
    rule's output and block states: models/ling_hybrid.hidden_states),
    and what the backward recomputes inside a block is the
    implementation's cost, not work: the share moves with the time
    under `kda_scan` alone, whatever a later remat policy or kernel
    does. Bytes a forward: q, k, v in, o out at `itemsize`, the
    log-decay in float32 per channel, beta in float32 per head; a
    backward reads those and dO and writes a cotangent for each."""
    n, d = cfg["num_attention_heads"], cfg["head_dim"]
    T, L = B * S, layer_counts(cfg)["kda"]
    fwd = kda_flops_per_token(cfg) * T * L
    ins = n * (3 * d * itemsize + 4 * d + 4)        # q k v; g; beta
    out = n * d * itemsize
    fwd_bytes = (ins + out) * T * L
    bwd_bytes = (2 * ins + out) * T * L             # ins, dO; their grads
    return {"flops": 3 * fwd, "bytes": fwd_bytes + bwd_bytes}


def flash_latent_work(cfg: dict, B: int, S: int, *, remat: bool,
                      itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of latent attention's kernels in one optimizer
    step at the TRUE widths: forward (twice under remat) q.k^T and P v;
    backward S, dP, dV, dQ, dK; bytes q, k at nope + rope and v, o, dO
    at v_head_dim, gradients likewise."""
    n = cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    L = layer_counts(cfg)["mla"]
    pairs = B * latent_pairs(cfg, S)
    fwd = 2 * n * (dq + dv) * pairs
    bwd = 2 * n * (3 * dq + 2 * dv) * pairs
    n_fwd = 2 if remat else 1
    fwd_bytes = n * (2 * dq + 2 * dv) * B * L * S * itemsize
    bwd_bytes = n * (4 * dq + 4 * dv) * B * L * S * itemsize
    return {"flops": n_fwd * fwd + bwd,
            "bytes": n_fwd * fwd_bytes + bwd_bytes}


def gmm_work(cfg: dict, assign_here: float, *, remat: bool,
             itemsize: int = 2) -> dict:
    """As flops_afmoe.gmm_work, at this family's widths (K 2560 / N 768
    and back): three products an assignment, forward (twice under remat)
    and once to x; bytes a pass: the held experts' weights of every
    expert layer once, plus the rows in and out."""
    H, Im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    nm = layer_counts(cfg)["moe"]
    passes = (2 if remat else 1) + 1
    weights = nm * cfg["num_experts"] * matmul_params(cfg)["expert"]
    rows = assign_here * 3 * (H + Im)
    return {"flops": passes * 2 * assign_here * matmul_params(cfg)["expert"],
            "bytes": passes * (weights + rows) * itemsize}
