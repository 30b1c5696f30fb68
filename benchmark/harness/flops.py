"""Operations and bytes from shapes — the yardstick's arithmetic.

Copied from bench.py (`transformer_flops`, `executed_flops`,
`_attn_factor`; the originals are listed in PERF.md's Open questions
for a later PR to delete) and written against a configuration FILE
(the published HF keys), not a program object. Matmul parameters only:
the embedding tables gather, they do not multiply; the tied head is a
real [V, E] matmul.
"""
from __future__ import annotations


def gpt2_matmul_params(cfg: dict) -> dict:
    E, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    per_layer = E * 3 * E + E * E + E * 4 * E + 4 * E * E      # 12 E^2
    return {"blocks": L * per_layer, "head": V * E}


def gpt2_param_count(cfg: dict) -> int:
    E, L, V, P = (cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"],
                  cfg["n_positions"])
    per_layer = 12 * E * E + (3 * E + E + 4 * E + E) + 4 * E   # w + b + ln
    return V * E + P * E + L * per_layer + 2 * E


def lora_params(cfg: dict, rank: int, targets) -> int:
    E, L = cfg["n_embd"], cfg["n_layer"]
    dims = {"attn_qkv": (E, 3 * E), "attn_proj": (E, E),
            "mlp_fc_in": (E, 4 * E), "mlp_fc_out": (4 * E, E)}
    return L * sum(rank * (dims[t][0] + dims[t][1]) for t in targets)


def attention_flops(cfg: dict, B: int, S: int) -> int:
    """Dense causal-masked attention, forward: QK^T and PV, 2 flops per
    multiply-add, every S x S entry (what the mathematics defines; a
    kernel that skips masked blocks executes less)."""
    D = cfg["n_embd"] // cfg["n_head"]
    return 4 * B * cfg["n_layer"] * cfg["n_head"] * S * S * D


def required_train_flops(cfg: dict, B: int, S: int, *, full_ft: bool,
                         n_lora: int = 0) -> int:
    """Forward + backward the algorithm REQUIRES for one optimizer step
    (recomputation under remat does not count): matmul forward 2NT,
    backward dx 2NT through every weight (the loss gradient crosses
    frozen weights to reach the adapters), dW only for trained weights,
    attention forward once and backward twice."""
    mm = gpt2_matmul_params(cfg)
    N = mm["blocks"] + mm["head"] + n_lora
    T = B * S
    trained = N if full_ft else n_lora
    return 2 * N * T + 2 * N * T + 2 * trained * T \
        + 3 * attention_flops(cfg, B, S)


def flash_attn_factor(S: int, block: int = 512) -> float:
    """Share of the dense S^2 work a causal block-sparse kernel with
    `block`-row tiles executes: (nb+1)/(2 nb) for nb = S/block."""
    nb = max(S // block, 1)
    return (nb + 1) / (2 * nb)


def executed_train_flops(cfg: dict, B: int, S: int, *, full_ft: bool,
                         n_lora: int = 0, remat: bool = True,
                         attn_factor: float = 1.0) -> int:
    """What the compiled step executes: `required` plus the block
    stack's forward run a second time under remat, with attention
    scaled by the share of the S^2 work the kernel visits."""
    mm = gpt2_matmul_params(cfg)
    T = B * S
    attn = int(attention_flops(cfg, B, S) * attn_factor)
    allmm = mm["blocks"] + mm["head"] + n_lora
    fwd = 2 * T * allmm + attn
    recompute = (2 * T * (mm["blocks"] + n_lora) + attn) if remat else 0
    bwd_dx = 2 * T * allmm + 2 * attn
    bwd_dw = 2 * T * (allmm if full_ft else n_lora)
    return fwd + recompute + bwd_dx + bwd_dw


def flash_kernel_work(cfg: dict, B: int, S: int, *, remat: bool,
                      itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the flash kernels in one optimizer step:
    forward (twice under remat) and the merged backward, causal work
    only (half the S^2 square plus the diagonal), from shapes."""
    H, L = cfg["n_head"], cfg["n_layer"]
    D = cfg["n_embd"] // H
    causal = 0.5 * (1 + 1.0 / S)                     # (S^2+S)/2 of S^2
    fwd = 4 * B * L * H * S * S * D * causal          # QK^T, PV
    bwd = 10 * B * L * H * S * S * D * causal         # S, dP, dV, dQ, dK
    n_fwd = 2 if remat else 1
    qkvo = 4 * B * L * H * S * D * itemsize           # read q,k,v; write o
    bwd_bytes = 8 * B * L * H * S * D * itemsize      # q,k,v,o,do; dq,dk,dv
    return {"flops": n_fwd * fwd + bwd,
            "bytes": n_fwd * qkvo + bwd_bytes}


def paged_attention_bytes(cfg: dict, context_tokens: int,
                          itemsize: int = 2) -> int:
    """Bytes of cached K and V one decode step must read for
    `context_tokens` cached positions summed over the live slots."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * context_tokens * itemsize


def roofline_seconds(flops: float, bytes_: float, peaks: dict):
    """(least seconds the chip could take, which bound sets it)."""
    tc, tb = flops / peaks["flops_bf16"], bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "memory")
