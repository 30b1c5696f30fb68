"""Sparse-expert layer: a router over ALL experts and an expert layer that
is told which experts it holds and computes only their part.

The contract (DESIGN.md §30) is expert parallelism's, seen from one
rank: `route` scores every token against every one of the model's E
experts and picks k; `expert_layer` is handed the weights of the
`held = (first, count)` experts that live on this chip, computes
    f[t] = sum over e in sel[t] ∩ held of  w[t, e] · Expert_e(m[t])
and leaves out what the absent experts would add. Nothing here stands in
for the other ranks or for their exchange: with held = (0, E) the layer
is the whole layer, and the parts of all ranks add up to it
(tests/test_afmoe.py).

No capacity factor, no dropped assignment: the T·k assignments are
sorted by expert into one [T·k, H] buffer — held experts first, in
order, then every assignment to an absent expert as one last group that
is never computed — so the buffer holds the worst case (every assignment
held) by construction and its shape is static. The grouped product over
the held experts is the Pallas grouped matmul JAX ships
(`jax.experimental.pallas.ops.tpu.megablox`, custom VJP included), whose
grid visits only the tiles of the groups it is given: the absent tail
costs memory, not matmul time. Chosen on the chip (PR 27, PERF.md §6):
`jax.lax.ragged_dot` lowers to a grouped kernel of the compiler's own on
this installation and ties forward (3.1 ms for 8,192 held rows x 3072 x
3072 over 32 experts), but its transpose — the backward to x — takes
18.3 ms against 3.0 ms here.

Device scopes, all under the caller's `mlp` scope: `moe_router`,
`moe_dispatch` (sort, gather), `moe_experts` (the grouped products and
the SwiGLU between them), `moe_combine` (un-sort, weight, sum over k).
The shared expert is the model's (`moe_shared`, models/afmoe.py).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
# the package exports the VJP'd function (under its kernel module's name)
from jax.experimental.pallas.ops.tpu.megablox import gmm as _megablox_gmm

from mobilefinetuner_tpu.ops import pallas_util

# Rows a tile of the grouped product: small row tiles waste fewer rows on
# a group's ragged ends (measured below); also what the sorted buffer is
# padded to.
GMM_TM = 128


def gmm_tiling(K: int, N: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) of the grouped product rows x [K, N], from the
    product's shape: the whole contraction in one tile while K <= 3072
    (no accumulation over K steps, a [128, K] row tile is at most 768 KB
    in bf16), else its largest 128-multiple divisor under that; the
    widest column tile of 1024, 768, 512, 384, 256, 128 that divides N
    (N itself where none does: the CPU tests' sizes). Measured on a v5e:
    K = N = 3072, ~256 rows an expert over 32 (PR 27): 3.04 ms for
    (128, 3072, 1024) against 3.40 for (256, 1024, 1024) and 3.82 for
    (512, 1024, 1024); K 2560 / N 768, 256 rows an expert over 64
    (PR 31): see PERF.md section 6 for the readings of (128, 2560, 768)
    against (128, 2560, 384), (256, 2560, 768) and (128, 1280, 768)."""
    tk = K if K <= 3072 else max(d for d in range(128, 3073, 128)
                                 if K % d == 0)
    tn = next((t for t in (1024, 768, 512, 384, 256, 128) if N % t == 0), N)
    return GMM_TM, tk, tn


def kept_groups(biased, n_group: int, topk_group: int):
    """[T, E] biased scores -> [T, n_group] bool: the `topk_group` groups
    of E / n_group consecutive experts whose two largest biased scores
    sum highest (DeepSeek-V3's group-limited selection)."""
    T, E = biased.shape
    top2, _ = jax.lax.top_k(biased.reshape(T, n_group, E // n_group), 2)
    _, gi = jax.lax.top_k(top2.sum(-1), topk_group)
    return jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], gi].set(True)


def route(m, w_r, bias, k: int, norm: bool, scale: float,
          n_group: int = 1, topk_group: int = 1):
    """m [T, H] -> (idx [T, k] int32, w [T, k] float32, kept).

    Sigmoid scores over all E experts in float32 whatever m's dtype is
    (full-precision products: a bf16 pass over the 3072-term sum would
    flip near-ties that the activations' own rounding does not); `bias`
    (the frozen load-balancing buffer) enters the SELECTION only; the
    weights are the selected scores, normalised to sum 1 (`norm`) and
    multiplied by `scale`. Differentiable to m through the weights.

    n_group > 1: the top-k is taken among the experts of the
    `topk_group` kept groups only (`kept_groups`), and `kept` is their
    [T, n_group] mask (a counter's input); with one group idx and w are
    what they were and `kept` is None."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(m.astype(jnp.float32), w_r.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        s = jax.nn.sigmoid(logits)
        biased = s + bias.astype(jnp.float32)
        kept = None
        if n_group > 1:
            kept = kept_groups(biased, n_group, topk_group)
            biased = jnp.where(
                jnp.repeat(kept, s.shape[1] // n_group, axis=1), biased,
                -jnp.inf)
        _, idx = jax.lax.top_k(biased, k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if norm:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * scale, kept


# The sorted buffer and the tokens: two gathers that are each other's
# transpose, written as such (autodiff, which sees only a gather, would
# write the backward as a scatter-add), and never through a [T, k, H]
# view — k as the second-minor dimension re-tiles a 400 MB buffer.

def _spread_rows(m, perm, rows):
    """[T, H] -> [len(perm), H]: row j is the token of assignment
    perm[j], m[perm[j] // k]; zero in the rows sorting padded on."""
    k = rows // m.shape[0]
    out = m[jnp.minimum(perm, rows - 1) // k]
    return jnp.where((perm < rows)[:, None], out, 0)


def _sum_rows(ys, inv):
    """[padded, H] -> [T, H]: each token's k rows, found through the
    inverse permutation `inv` [T, k], summed in float32."""
    acc = ys[inv[:, 0]].astype(jnp.float32)
    for j in range(1, inv.shape[1]):
        acc = acc + ys[inv[:, j]].astype(jnp.float32)
    return acc.astype(ys.dtype)


@jax.custom_vjp
def _dispatch(m, perm, inv):
    return _spread_rows(m, perm, inv.size)


def _dispatch_fwd(m, perm, inv):
    return _dispatch(m, perm, inv), (perm, inv)


def _dispatch_bwd(res, g):
    return _sum_rows(g, res[1]), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect(ys, perm, inv):
    return _sum_rows(ys, inv)


def _collect_fwd(ys, perm, inv):
    return _collect(ys, perm, inv), (perm, inv)


def _collect_bwd(res, g):
    perm, inv = res
    return _spread_rows(g, perm, inv.size), None, None


_collect.defvjp(_collect_fwd, _collect_bwd)


def _gmm(x, w, sizes, layer=None):
    """Rows of x, sorted by group, times their group's [K, N] matrix;
    rows beyond the held groups come back zero. sizes: [n + 1], the held
    experts' row counts and the absent tail's. w: [n, K, N], or the
    whole stack [L, n, K, N] of a layer scan with `layer` the traced
    index: the kernel is then handed all L·n matrices as they lie in
    memory with every group but this layer's n empty — slicing one
    layer out first would copy its 1.8 GB at the published widths, on
    every pass (seen in the compiled step, PR 27)."""
    if w.ndim == 4:
        L, n = w.shape[:2]
        w = w.reshape(L * n, *w.shape[2:])
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * n + 1,), sizes.dtype).at[-1].set(sizes[-1]),
            sizes[:-1], (layer * n,))
    return _megablox_gmm(x, w, sizes, x.dtype,
                         gmm_tiling(x.shape[1], w.shape[2]), None, None,
                         False, pallas_util.interpret_mode())


def expert_layer(m, idx, w, experts, held: Tuple[int, int], layer=None):
    """The held experts' part of the routed sum, and its counters.

    m [T, H]; idx, w [T, k] from `route`; experts = {"gate_w", "up_w"
    [n, H, I], "down_w" [n, I, H]} for the n = held[1] experts
    held[0] .. held[0]+n-1 — or their stacks over the layers of a scan,
    [L, n, ...], with `layer` the traced index (`_gmm`). Returns (f [T, H] in m's dtype, counters)
    with counters = {"assign_here": assignments to held experts,
    "load_max_over_mean": the fullest held expert's rows over the mean
    (1 when none arrived), "dropped": assignments to held experts minus
    the rows the grouped product was given — 0 by construction, counted
    from the two sides independently}."""
    T, H = m.shape
    k = idx.shape[1]
    first, n = held
    tm = GMM_TM
    rows = T * k
    padded = -(-rows // tm) * tm
    with jax.named_scope("moe_dispatch"):
        local = idx.reshape(rows) - first
        here = (local >= 0) & (local < n)
        group = jnp.where(here, local, n)          # the absent: group n
        if padded != rows:      # rows the kernel's tiling asks for
            group = jnp.concatenate(
                [group, jnp.full((padded - rows,), n, jnp.int32)])
        perm = jnp.argsort(group, stable=True)
        inv = jnp.zeros((rows,), perm.dtype).at[perm].set(
            jnp.arange(padded, dtype=perm.dtype), mode="drop").reshape(T, k)
        sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)
        xs = _dispatch(m, perm, inv)
    with jax.named_scope("moe_experts"):
        g = _gmm(xs, experts["gate_w"], sizes, layer)
        u = _gmm(xs, experts["up_w"], sizes, layer)
        y = _gmm(jax.nn.silu(g) * u, experts["down_w"], sizes, layer)
    with jax.named_scope("moe_combine"):
        # each row times its routing weight (0 for the absent and the
        # padding), then each token's k rows summed
        ws = jnp.where(here, w.reshape(rows), 0.0)[
            jnp.minimum(perm, rows - 1)]
        ws = jnp.where(perm < rows, ws, 0.0)[:, None]
        ys = (y.astype(jnp.float32) * ws).astype(y.dtype)
        f = _collect(ys, perm, inv)
        assign = here.sum()
        held_sizes = sizes[:n]
        counters = {
            "assign_here": assign.astype(jnp.float32),
            "load_max_over_mean": jnp.where(
                assign > 0, held_sizes.max() * n / jnp.maximum(assign, 1),
                1.0).astype(jnp.float32),
            "dropped": (assign - held_sizes.sum()).astype(jnp.float32),
        }
        return f, counters
