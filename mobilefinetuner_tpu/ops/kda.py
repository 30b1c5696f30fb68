"""Gated delta-rule linear attention with a per-channel decay (Kimi Delta
Attention, arXiv:2510.26692) and the short causal convolution in front
of it.

Per head, with a state S in R^{dk x dv} carried in float32, log-decay
g_t in R^{dk} (<= 0), write strength beta_t in (0, 1):

    S~  = Diag(exp g_t) S_{t-1}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

`kda_recurrent` is that, token by token under a `lax.scan`: the oracle of
the unit tests. `kda_chunked` is the form that trains. Within a chunk of
C tokens, with G_t the running sum of g from the chunk's start,

    d'_s = beta_s (v_s - (k_s . exp G_s)^T S_0
                   - sum_{r<s} [(k_s . exp G_s) . (k_r . exp -G_r)] d'_r)

is a unit lower-triangular system (I + Diag(beta) M) d' = beta (V - K+ S_0)
whose matrix does not depend on the state, so u = T (beta V) and
w = T (beta K+) are solved for every chunk at once, and only

    d' = u - w S;  o = (q . exp G) S + A d';  S = exp(G_C) . S + K~^T d'

(A_ts = (q_t . exp G_t) . (k_s . exp -G_s) for s <= t, K~_s = k_s .
exp(G_C - G_s)) runs chunk after chunk. exp(-G_r) overflows float32 once
the cumulated gate passes -88: the pair products are therefore taken in
SUB-chunks, each against the gate cumulated to its own middle, so that
every exponent stays within sub x |lower bound| / 2 (16 x 5 / 2 = 40
under the family's `kda_lower_bound` of -5); products between sub-chunks
have a column factor <= 1. The gate is cumulated locally (within a
sub-chunk, and over whole sub-chunks), never as one running sum over the
chunk. Everything inside a block runs in float32 at "highest"
precision — the pair products, the solve and the products with the
state; q, k, v come in and o goes out in the activations' dtype.

The backward is written by hand, over BLOCKS of `block_chunks` chunks
(4: PERF.md section 6, PR 31, has the chip's readings at 2 to 32): the
forward keeps only the state that enters each block (2 MB a block at 32
heads of 128 x 128), the backward walks the blocks last to first,
recomputes a block from that state and pulls the cotangents of (state
out, o) back through it — so no intermediate of the within-chunk algebra
outlives its block, whatever S is.

Two implementations of that one algorithm, picked from the shapes
(`resolve_impl`), behind one `custom_vjp` with the same residuals (q, k,
v, g, beta, the block-entry states) and the same names for a remat
policy (`SAVE_NAMES`):

  `kernel`   ops/kda_pallas.py: a forward and a backward Pallas (Mosaic)
             kernel, grid (heads, blocks), a head's float32 state in a
             VMEM scratch across the blocks and a chunk's whole algebra
             on VMEM tiles; HBM sees the inputs, o, the block-entry
             states, dO and the five cotangents. Where S is whole
             chunks AND the head is whole 128-lane tiles (the published
             128). Its docstring says what lives where.
  `chunked`  this module: XLA einsums under a scan over blocks (one
             block is the unit of batched work: its chunks' solves and
             pair products are one batched op each), the backward
             `jax.vjp` of `_block` under a reverse scan. Where S is
             whole chunks and the head is not lane-aligned (the CPU
             tiny preset), and the oracle the kernel is held to.

A length that is not whole chunks takes `kda_recurrent`.

Layout: q, k, g [B, H, S, dk], v [B, H, S, dv], beta [B, H, S], as the
attention kernels take theirs. q and k arrive normalised and scaled by
the caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from mobilefinetuner_tpu.ops import kda_pallas, pallas_util

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def causal_conv(x, w):
    """Depthwise causal convolution over time: y[t, c] = sum_j w[j, c]
    x[t - (K-1) + j, c], zeros before the sequence's start. x [B, S, C],
    w [K, C]; float32 accumulation, x's dtype out. Written as K shifted
    products of one padded copy — on the chip (PR 31, [1, 16384, 4096] in
    bf16 with the SiLU behind it) 1.6 ms forward and 3.7 with its
    backward, against 3.9 and 4.3 for `lax.conv_general_dilated` with
    `feature_group_count = C`, whose compile alone took 35 s."""
    K = w.shape[0]
    S = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(F32)
    y = sum(padded[:, j:j + S].astype(F32) * wf[j] for j in range(K))
    return y.astype(x.dtype)


def kda_recurrent(q, k, v, g, beta, state=None):
    """The recurrence as written above, one token a scan step, float32.
    Returns (o [B, H, S, dv], final state [B, H, dk, dv])."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    s0 = jnp.zeros((B, H, dk, dv), F32) if state is None \
        else state.astype(F32)

    dot = functools.partial(jnp.einsum, precision=_HI,
                            preferred_element_type=F32)

    def step(s, xs):
        qt, kt, vt, gt, bt = xs          # [B, H, d], bt [B, H]
        s = jnp.exp(gt)[..., None] * s
        pred = dot("bhkv,bhk->bhv", s, kt)
        s = s + dot("bhk,bhv->bhkv", bt[..., None] * kt, vt - pred)
        return s, dot("bhkv,bhk->bhv", s, qt)

    t_first = lambda x: jnp.moveaxis(x, 2, 0)
    s, o = jax.lax.scan(step, s0, tuple(map(t_first, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 2), s


def _solve_unit_lower(a, rhs, sub: int):
    """x with a x = rhs for unit lower-triangular a [..., C, C], rhs
    [..., C, m]: the sub x sub diagonal blocks are inverted in ONE batched
    triangular solve (sub sequential rows, not C), and the blocks below
    the diagonal are substituted forward with products — on the chip the
    whole-C solve was half of the chunked rule's forward (PR 31)."""
    C = a.shape[-1]
    ns = C // sub
    lead = a.shape[:-2]
    ab = a.reshape(*lead, ns, sub, ns, sub)
    diag = jnp.stack([ab[..., i, :, i, :] for i in range(ns)], axis=-3)
    inv = jax.lax.linalg.triangular_solve(
        diag, jnp.broadcast_to(jnp.eye(sub, dtype=a.dtype), diag.shape),
        left_side=True, lower=True, unit_diagonal=True)
    rb = rhs.reshape(*lead, ns, sub, rhs.shape[-1])
    dot = functools.partial(jnp.einsum, "...st,...tm->...sm", precision=_HI,
                            preferred_element_type=F32)
    xs = []
    for i in range(ns):
        r = rb[..., i, :, :]
        for j in range(i):
            r = r - dot(ab[..., i, :, j, :], xs[j])
        xs.append(dot(inv[..., i, :, :], r))
    return jnp.concatenate(xs, axis=-2)


def _block(s0, q, k, v, g, beta, *, sub: int):
    """One block of nb chunks. s0 [B, H, dk, dv] float32; q, k, g
    [B, H, nb, C, dk], v [B, H, nb, C, dv], beta [B, H, nb, C].
    Returns (state out, o [B, H, nb, C, dv] float32)."""
    B, H, nb, C, dk = q.shape
    ns = C // sub
    qf, kf, vf, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    split = lambda x: x.reshape(B, H, nb, ns, sub, x.shape[-1])
    # The cumulated gate, kept as LOCAL sums (each within its sub-chunk,
    # |.| <= sub |lb|) and sums of whole sub-chunks: an exponent between
    # two nearby tokens is then a sum of small terms, not the difference
    # of two cumulated values of several hundred (whose rounding, times
    # e^x's slope, is what costs the chunked form its digits at the fast
    # end of the gate).
    Gl = jnp.cumsum(split(g), axis=-2)                # [.., ns, sub, dk]
    tot = Gl[..., -1, :]                              # [.., ns, dk]
    Rl = tot[..., None, :] - Gl                       # still to come, local
    n_ = jnp.arange(ns)
    whole = lambda spec, mask: jnp.einsum(spec, mask.astype(F32), tot,
                                          precision=_HI,
                                          preferred_element_type=F32)
    # sums of whole sub-chunks: before sub-chunk j, after it, and strictly
    # between j and i (exactly 0 where there is none)
    start = whole("jm,bhnmd->bhnjd", n_[None, :] < n_[:, None])
    after = whole("jm,bhnmd->bhnjd", n_[None, :] > n_[:, None])
    between = whole("ijm,bhnmd->bhnijd",
                    (n_[None, None, :] > n_[None, :, None])
                    & (n_[None, None, :] < n_[:, None, None]))
    G = (start[..., None, :] + Gl).reshape(B, H, nb, C, dk)
    # rows of strip i against the gate cumulated to the MIDDLE of their
    # sub-chunk, so that both factors of a pair within it stay inside
    # e^(+-sub |lb| / 2): against its start the row factor falls to e^-80,
    # where float32 flushes a key's small components to zero
    mid = Gl[..., sub // 2 - 1, :] if sub > 1 else jnp.zeros_like(tot)
    left = jnp.exp(Gl - mid[..., None, :])
    k_l = split(kf) * left
    q_l = split(qf) * left
    # columns r (in sub-chunk j) of row-strip i: exp(mid_i - Gl_r) within
    # the strip's own sub-chunk, exp(mid_i + between + Rl_r) <= 1 for an
    # earlier one; later ones are masked below and their exponent zeroed
    i_, j_ = n_[:, None, None, None], n_[None, :, None, None]
    m_i = mid[..., :, None, None, :]
    e = jnp.where(j_ < i_,
                  m_i + between[..., None, :] + Rl[..., None, :, :, :],
                  jnp.where(j_ == i_, m_i - Gl[..., None, :, :, :], 0.0))
    k_r = kf[..., None, :, :] * jnp.exp(e).reshape(B, H, nb, ns, C, dk)
    pairs = lambda x: jnp.einsum(
        "bhnisd,bhnicd->bhnisc", x, k_r, precision=_HI,
        preferred_element_type=F32).reshape(B, H, nb, C, C)
    row = jnp.arange(C)[:, None]
    col = jnp.arange(C)[None, :]
    M = jnp.where(col < row, pairs(k_l), 0.0)
    A = jnp.where(col <= row, pairs(q_l), 0.0)
    k_plus = kf * jnp.exp(G)
    rhs = beta[..., None] * jnp.concatenate([vf, k_plus], axis=-1)
    sol = _solve_unit_lower(jnp.eye(C, dtype=F32) + beta[..., None] * M,
                            rhs, sub)
    u, w = sol[..., :vf.shape[-1]], sol[..., vf.shape[-1]:]
    to_end = (after[..., None, :] + Rl).reshape(B, H, nb, C, dk)
    xs = (w, u, qf * jnp.exp(G), A, kf * jnp.exp(to_end),
          jnp.exp(tot.sum(-2)))
    # the products with the state at "highest" too: the state of a
    # slow-decaying channel is a sum over thousands of tokens, and a
    # bf16 pass over it (2^-9 of ITS size) is as large as what the last
    # chunks wrote — on the chip the bf16 step read 0.19 RMS on the
    # logits and a cosine of 0.994 on an adapter gradient against the
    # float32 reference with bf16 operands here (PR 31); the products
    # are [C, d] x [d, d] a head, far too small for the passes to show
    dot = functools.partial(jnp.einsum, precision=_HI,
                            preferred_element_type=F32)

    def chunk(s, x):
        w_c, u_c, q_c, a_c, kt_c, decay = x
        d = u_c - dot("bhck,bhkv->bhcv", w_c, s)
        o = dot("bhck,bhkv->bhcv", q_c, s) + dot("bhcs,bhsv->bhcv", a_c, d)
        s = decay[..., None] * s + dot("bhck,bhcv->bhkv", kt_c, d)
        return s, o

    s, o = jax.lax.scan(chunk, s0,
                        jax.tree.map(lambda x: jnp.moveaxis(x, 2, 0), xs))
    return s, jnp.moveaxis(o, 0, 2)


def _blocks(x, nB: int, nb: int, C: int):
    """[B, H, S, ...] -> [nB, B, H, nb, C, ...], the scan's leading axis
    first."""
    B, H = x.shape[:2]
    return jnp.moveaxis(x.reshape(B, H, nB, nb, C, *x.shape[3:]), 2, 0)


# Names a caller's `jax.checkpoint` policy can keep
# (`checkpoint_policies.save_only_these_names(*SAVE_NAMES)`): with the
# output and the blocks' entry states kept, a rematerialised layer does
# not run the forward scan a second time — the backward needs only them
# and the (cheaply recomputed) inputs.
SAVE_NAMES = ("kda_o", "kda_states")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _chunked(q, k, v, g, beta, C, sub, nb, impl):
    return _chunked_fwd(q, k, v, g, beta, C, sub, nb, impl)[0]


def _scan_fwd(q, k, v, g, beta, C, sub, nb):
    """The XLA form: a scan over blocks. (o [B, H, S, dv] in v's dtype,
    the state entering each block [nB, B, H, dk, dv])."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    nB = S // (nb * C)
    xs = tuple(_blocks(x, nB, nb, C) for x in (q, k, v, g, beta))
    fn = functools.partial(_block, sub=sub)

    def step(s, x):
        s_out, o = fn(s, *x)
        return s_out, (o.astype(v.dtype), s)

    _, (o, s_in) = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), F32), xs)
    return jnp.moveaxis(o, 0, 2).reshape(B, H, S, dv), s_in


def _scan_bwd(q, k, v, g, beta, s_in, do, C, sub, nb):
    """`jax.vjp` of a block under a reverse scan over the blocks."""
    S = q.shape[2]
    nB = S // (nb * C)
    xs = tuple(_blocks(x, nB, nb, C) for x in (q, k, v, g, beta))
    fn = functools.partial(_block, sub=sub)

    def step(ds, x):
        s0, do_b, args = x
        _, pull = jax.vjp(fn, s0, *args)
        ds0, *dargs = pull((ds, do_b.astype(F32)))
        return ds0, tuple(dargs)

    _, grads = jax.lax.scan(
        step, jnp.zeros_like(s_in[0]),
        (s_in, _blocks(do, nB, nb, C), xs), reverse=True)
    back = lambda d, x: jnp.moveaxis(d, 0, 2).reshape(x.shape) \
        .astype(x.dtype)
    return tuple(back(d, x) for d, x in zip(grads, (q, k, v, g, beta)))


def _impl_fns(impl):
    """(forward, backward) of an implementation. The kernels' calls are
    jitted: they get the interpret policy as read here, at the caller's
    trace, as a static argument."""
    if impl != "kernel":
        return _scan_fwd, _scan_bwd
    interpret = pallas_util.interpret_mode()
    return (functools.partial(kda_pallas.forward, interpret=interpret),
            functools.partial(kda_pallas.backward, interpret=interpret))


def _chunked_fwd(q, k, v, g, beta, C, sub, nb, impl):
    fwd = _impl_fns(impl)[0]
    with jax.named_scope("kda_scan"):
        o, s_in = fwd(q, k, v, g, beta, C, sub, nb)
        o = checkpoint_name(o, SAVE_NAMES[0])
        s_in = checkpoint_name(s_in, SAVE_NAMES[1])
    return o, (q, k, v, g, beta, s_in)


def _chunked_bwd(C, sub, nb, impl, res, do):
    bwd = _impl_fns(impl)[1]
    with jax.named_scope("kda_scan"):
        return bwd(*res, do, C, sub, nb)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def pick_block_chunks(S: int, chunk: int, want: int = 4) -> int:
    """Chunks a block: the largest count <= `want` that divides S/chunk."""
    n = S // chunk
    return max(b for b in range(1, min(want, n) + 1) if n % b == 0)


IMPLS = ("kernel", "chunked")


def resolve_impl(S: int, chunk: int, dk: int, dv: int,
                 sub: int = 16) -> str:
    """Which implementation a shape gets: `kernel` (ops/kda_pallas.py)
    where S is whole chunks and the head is whole 128-lane tiles;
    `chunked` (the XLA scans) where S is whole chunks and the head is
    not; `recurrent` (the token-by-token scan) otherwise."""
    if S % chunk or chunk % sub:
        return "recurrent"
    return "kernel" if kda_pallas.supported(S, chunk, sub, dk, dv) \
        else "chunked"


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64, sub: int = 16,
                block_chunks: int = 4, impl: str = "auto"):
    """o [B, H, S, dv] in v's dtype; see the module docstring. S must be
    a multiple of `chunk` and `chunk` of `sub`; g is expected in float32
    (the cumulated gate loses the decay's small differences in bf16).
    impl: `kernel` | `chunked` (IMPLS), or `auto`: `resolve_impl` of the
    shapes."""
    S = q.shape[2]
    if S % chunk or chunk % sub:
        raise ValueError(f"kda_chunked: S={S} must be a multiple of "
                         f"chunk={chunk}, and chunk of sub={sub}")
    dk, dv = q.shape[-1], v.shape[-1]
    if impl == "auto":
        impl = resolve_impl(S, chunk, dk, dv, sub)
    elif impl not in IMPLS:
        raise ValueError(f"kda_chunked: impl={impl!r} is none of {IMPLS}")
    elif impl == "kernel" and not kda_pallas.supported(S, chunk, sub, dk,
                                                       dv):
        raise ValueError(
            f"kda_chunked: impl='kernel' needs heads of whole 128-lane "
            f"tiles and sub-chunks of whole sublane tiles, got dk={dk}, "
            f"dv={dv}, sub={sub}")
    return _chunked(q, k, v, g, beta, chunk, sub,
                    pick_block_chunks(S, chunk, block_chunks), impl)
