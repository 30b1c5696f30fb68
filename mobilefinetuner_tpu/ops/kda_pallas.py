"""The chunked gated delta rule of ops/kda.py as two Pallas (Mosaic)
kernels: a forward and a hand-written backward. Same mathematics as
`kda._block` (the module docstring there): chunks of C tokens, the gate
cumulated locally in sub-chunks and the pair products taken against each
sub-chunk's middle, a unit lower-triangular system solved by
substitution, float32 everywhere inside and every product of two float32
operands at float32 contract precision. What differs is where things
live.

Grid (B*H / hb, S / (nb*C)): heads `parallel`, the sequence axis
`arbitrary`. One grid step is one BLOCK of nb chunks (4) of hb heads (2:
two heads' chains of small dependent products interleave in one step's
program; `pick_heads`).

  VMEM, across the steps of a head: the state, TRANSPOSED ([dv, dk]
  float32, 64 KiB at 128 x 128: the decay then scales lanes and every
  product with the state contracts lanes); in the backward the state's
  cotangent likewise.
  VMEM, within a step: a block's tiles of q, k, v, g, beta (and dO), and
  every intermediate of the within-chunk algebra — the gate sums, the
  exponentials, M, A, the inverse T, d — none of which ever reaches HBM.
  The forward stays inside the default 16 MiB of scoped VMEM; the
  backward, which holds a block's local terms while it walks the chunks
  back, asks for 32 (`BWD_VMEM`).
  HBM, forward: q, k, v (the activations' dtype), g, beta (float32) in;
  o (v's dtype) and the state ENTERING each block ([B*H, S/(nb*C), dv,
  dk] float32: 128 MiB a layer at 32 heads x 16,384 tokens, nb 4) out.
  HBM, backward: the same five inputs, the block-entry states and dO in;
  dq, dk, dv (their inputs' dtypes), dg, dbeta (float32) out. Nothing
  else but three constants of zeros and ones, fetched once (`constants`:
  the local cumulative sum as a [C, C] matrix and its transpose, 8 KiB
  each, and the selection that spreads a column of a diagonal block
  over its lanes, 256 KiB).

The forward walks the blocks first to last; the backward last to first
(the index maps count down), recomputes a block's chunks from the state
that entered it, then walks those chunks backward with dS carried. The
part of a chunk that does not depend on the state (`_local`) is written
for all nb chunks of the block at once, so that the chunks' short
dependent chains interleave; only `d = T beta (v - k+ S)`, `o = q+ S + A
d` and `S <- e^G S + K~^T d` run chunk after chunk.

The solve. (I + Diag(beta) M) is unit lower triangular. Its sub x sub
diagonal blocks are inverted row by row (sub - 1 dependent steps on the
VPU: after step c, row c + 1 of the inverse is final; all diagonal
blocks of the block's chunks side by side in the lanes), the strips
below the diagonal are substituted forward with MXU products into T = (I
+ Diag(beta) M)^-1, and the backward reuses T (dR = T^T dd). A
substitution, not a Neumann series: doubling the series is as fast and
loses its digits when keys repeat (PERF.md section 6, PR 31).

Products with a matrix of zeros and ones (the cumulative sum, the lane
spread) are exact in another way: the float32 operand is split into
three bf16 parts whose sum it is, and each part takes one bf16 pass with
float32 accumulation — every partial product is exact."""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mobilefinetuner_tpu.ops import pallas_util

F32, BF16 = jnp.float32, jnp.bfloat16
LANES = 128
_dg = functools.partial(jax.lax.dot_general,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=F32)
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _nn(a, b):
    return _dg(a, b, _NN)


def _nt(a, b):
    return _dg(a, b, _NT)


def _tn(a, b):
    return _dg(a, b, _TN)


def _each(fn, *xs):
    """fn over the leading (chunk) axis: the chunks' calls lie side by
    side in the program, their results stacked."""
    return jnp.stack([fn(*(x[n] for x in xs)) for n in range(xs[0].shape[0])])


def _split3(x):
    """Three bf16 arrays whose sum is the float32 x."""
    hi = x.astype(BF16)
    r = x - hi.astype(F32)
    mid = r.astype(BF16)
    return hi, mid, (r - mid.astype(F32)).astype(BF16)


def _exact(a, b):
    """a @ b where one of them is a bf16 matrix of 0 and +-1 and the
    other float32: exact partial products, float32 accumulation. The
    float32 operand's three parts share one pass over the matrix where
    it is the right-hand one (its rows stacked)."""
    dot = functools.partial(jax.lax.dot_general, dimension_numbers=_NN,
                            precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=F32)
    if a.dtype == BF16:
        return sum(dot(a, p) for p in _split3(b))
    m = a.shape[0]
    out = dot(jnp.concatenate(_split3(a), axis=0), b)
    return out[:m] + out[m:2 * m] + out[2 * m:]


def supported(S: int, chunk: int, sub: int, dk: int, dv: int) -> bool:
    """Shapes the kernels take: whole chunks of at most a lane tile,
    sub-chunks of whole float32 sublane tiles, heads of whole 128-lane
    tiles."""
    return (S % chunk == 0 and chunk % sub == 0 and sub % 8 == 0
            and chunk <= LANES and dk % LANES == 0 and dv % LANES == 0)


@functools.lru_cache(maxsize=None)
def constants(C: int, sub: int):
    """(cum [C, C], cum^T, spread [C, sub * 128]) in bf16. cum @ g is the
    gate summed within each sub-chunk up to and including each token;
    cum^T @ x sums x back the same way. Nd @ spread, for Nd [sub, C]
    holding the chunk's diagonal blocks side by side, holds in lanes
    [128 c, 128 c + C) column c of every block spread over that block's
    lanes."""
    t = np.arange(C)
    same = (t[:, None] // sub) == (t[None, :] // sub)
    cum = (same & (t[None, :] <= t[:, None])).astype(np.float32)
    spread = np.zeros((C, sub * LANES), np.float32)
    for c in range(sub):
        src = (t // sub) * sub + c                   # lane that is spread
        spread[src, c * LANES + t] = 1.0
    return tuple(x.astype(BF16) for x in (cum, cum.T, spread))


def _iota2(n, m):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, m), 0),
            jax.lax.broadcasted_iota(jnp.int32, (n, m), 1))


def _to_col(row):
    """[1, n] -> [n, 1] (a vector from lanes to sublanes)."""
    n = row.shape[1]
    r, c = _iota2(n, n)
    return jnp.sum(jnp.where(r == c, row, 0.0), axis=1, keepdims=True)


def _to_row(col):
    n = col.shape[0]
    r, c = _iota2(n, n)
    return jnp.sum(jnp.where(r == c, col, 0.0), axis=0, keepdims=True)


def _unit_lower_inverse(Nb, spread, sub: int):
    """(I + Nb)^-1 for strictly lower-triangular Nb [nb, C, C]."""
    nb, C, _ = Nb.shape
    ns = C // sub
    at = lambda i: slice(i * sub, (i + 1) * sub)
    r, c = _iota2(C, C)
    diag = jnp.where((r // sub) == (c // sub), Nb, 0.0)
    Nd = sum(diag[:, at(i)] for i in range(ns))            # [nb, sub, C]
    cols = _exact(Nd.reshape(nb * sub, C), spread).reshape(nb, sub, -1)
    rs, cs = _iota2(sub, C)
    # X: the inverses of the diagonal blocks D = I + N, side by side. Row
    # c of X is final when step c - 1 is done; step c takes N[r, c] times
    # it from every row r below
    X = jnp.broadcast_to((rs == cs % sub).astype(F32), (nb, sub, C))
    for cc in range(sub - 1):
        X = X - cols[:, :, cc * LANES:cc * LANES + C] * X[:, cc:cc + 1]
    eye = (r == c).astype(F32)
    strips = []
    for i in range(ns):
        R = jnp.broadcast_to(eye[at(i)], (nb, sub, C))
        if i:
            R = R - _each(_nn, Nb[:, at(i), :i * sub],
                          jnp.concatenate(strips, axis=1))
        strips.append(_each(_nn, X[:, :, at(i)], R))
    return jnp.concatenate(strips, axis=1)


def _local(q, k, g, brows, cum, spread, sub: int):
    """Everything of a block's chunks that does not depend on the state.
    q, k, g [nb, C, dk] float32, brows: nb rows [1, C] of beta."""
    nb, C, dk = q.shape
    ns = C // sub
    at = lambda i: slice(i * sub, (i + 1) * sub)
    cat = lambda xs: jnp.concatenate(xs, axis=1)
    Gl = _each(lambda x: _exact(cum, x), g)
    Gb = [Gl[:, at(m)] for m in range(ns)]
    tot = [x[:, sub - 1:sub] for x in Gb]                  # [nb, 1, dk]
    mid = [x[:, sub // 2 - 1:sub // 2] for x in Gb]
    zero = jnp.zeros_like(tot[0])
    start, after = [zero], [zero]
    for m in range(ns - 1):
        start.append(start[-1] + tot[m])
        after.insert(0, after[0] + tot[ns - 1 - m])
    Rb = [tot[m] - Gb[m] for m in range(ns)]
    EL = cat([Gb[m] - mid[m] for m in range(ns)])
    Rl = cat(Rb)
    G = cat([start[m] + Gb[m] for m in range(ns)])
    End = cat([after[m] + Rb[m] for m in range(ns)])
    left, ileft, eR = jnp.exp(EL), jnp.exp(-EL), jnp.exp(Rl)
    eG, eE = jnp.exp(G), jnp.exp(End)
    k_l, q_l, kD, kR = k * left, q * left, k * ileft, k * eR
    # f[i, j]: what a column of the earlier sub-chunk j carries besides
    # its own exp(Rl) when it meets row-strip i (gate from the end of j
    # to the middle of i): <= 1
    f = {}
    for i in range(ns):
        e = mid[i]
        for j in range(i - 1, -1, -1):
            f[i, j] = jnp.exp(e)
            e = e + tot[j]
    colmats, rows, P = [], [], []
    for i in range(ns):
        blocks = [kR[:, at(j)] * f[i, j] for j in range(i)] + [kD[:, at(i)]]
        if i < ns - 1:
            blocks.append(jnp.zeros((nb, (ns - 1 - i) * sub, dk), F32))
        colmats.append(cat(blocks))
        rows.append(cat([k_l[:, at(i)], q_l[:, at(i)]]))
        P.append(_each(_nt, rows[i], colmats[i]))          # [nb, 2 sub, C]
    r, c = _iota2(C, C)
    M = jnp.where(c < r, cat([p[:, :sub] for p in P]), 0.0)
    A = jnp.where(c <= r, cat([p[:, sub:] for p in P]), 0.0)
    bcol = jnp.stack([_to_col(b) for b in brows])
    return types.SimpleNamespace(
        q=q, k=k, bcol=bcol, left=left, ileft=ileft, eR=eR, eG=eG, eE=eE,
        k_l=k_l, q_l=q_l, kD=kD, kR=kR, f=f, colmats=colmats, rows=rows,
        M=M, A=A, T=_unit_lower_inverse(bcol * M, spread, sub), kp=k * eG,
        qp=q * eG, kt=k * eE, decay=eG[:, C - 1:C])


def _blocked(ref, C: int):
    """[hb, L, d] -> [hb * L / C, C, d] float32: head-major, a head's
    chunks in order."""
    x = ref[...].astype(F32)
    return x.reshape(x.shape[0] * x.shape[1] // C, C, x.shape[2])


def _beta_rows(b_ref, hb: int, nb: int, C: int):
    return [b_ref[h, :, n * C:(n + 1) * C] for h in range(hb)
            for n in range(nb)]


def _fwd_kernel(cum_ref, spread_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                o_ref, s_ref, st_ref, *, C: int, sub: int, nb: int,
                hb: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    s_ref[...] = st_ref[...]
    t = _local(_blocked(q_ref, C), _blocked(k_ref, C), _blocked(g_ref, C),
               _beta_rows(b_ref, hb, nb, C), cum_ref[...], spread_ref[...],
               sub)
    v = _blocked(v_ref, C)
    st = [st_ref[h] for h in range(hb)]
    for n in range(nb):
        for h in range(hb):
            i = h * nb + n
            both = _nt(jnp.concatenate([t.kp[i], t.qp[i]], axis=0), st[h])
            d = _nn(t.T[i], t.bcol[i] * (v[i] - both[:C]))
            o_ref[h, n * C:(n + 1) * C] = (
                both[C:] + _nn(t.A[i], d)).astype(o_ref.dtype)
            st[h] = t.decay[i] * st[h] + _tn(d, t.kt[i])
    for h in range(hb):
        st_ref[h] = st[h]


def _local_bwd(t, cumT, sub: int, dNb, dA, dqp, dkp, dkt, ddecay):
    """Cotangents of a block's (q, k, g, beta column) from those of what
    `_local` handed the state recurrence: dNb (of Diag(beta) M, strictly
    lower), dA (lower) [nb, C, C], dqp, dkp, dkt [nb, C, dk], ddecay
    [nb, 1, dk]; without beta's direct part."""
    nb, C, dk = t.q.shape
    ns = C // sub
    at = lambda i: slice(i * sub, (i + 1) * sub)
    cat = lambda xs: jnp.concatenate(xs, axis=1)
    down = lambda x: jnp.sum(x, axis=1, keepdims=True)     # [nb, 1, dk]
    dM = t.bcol * dNb
    dbcol = jnp.sum(dNb * t.M, axis=2, keepdims=True)
    zero = jnp.zeros((nb, 1, dk), F32)
    dkR = [jnp.zeros((nb, sub, dk), F32)] * ns
    dkD, drows = [], []
    dtot, dmid = [zero] * ns, [zero] * ns
    for i in range(ns):
        w = (i + 1) * sub         # the strip's columns end with its own
        D = cat([dM[:, at(i), :w], dA[:, at(i), :w]])      # [nb, 2 sub, w]
        drows.append(_each(_nn, D, t.colmats[i][:, :w]))
        dcol = _each(_tn, D, t.rows[i])                    # [nb, w, dk]
        dkD.append(dcol[:, at(i)])
        for j in range(i):
            dkR[j] = dkR[j] + dcol[:, at(j)] * t.f[i, j]
            de = down(dcol[:, at(j)] * t.kR[:, at(j)]) * t.f[i, j]
            dmid[i] = dmid[i] + de
            for m in range(j + 1, i):
                dtot[m] = dtot[m] + de
    dk_l = cat([x[:, :sub] for x in drows])
    dq_l = cat([x[:, sub:] for x in drows])
    dkD, dkR = cat(dkD), cat(dkR)
    dEL = dk_l * t.k_l + dq_l * t.q_l - dkD * t.kD
    dRl, dG, dEnd = dkR * t.kR, dkp * t.kp + dqp * t.qp, dkt * t.kt
    # back through EL = Gl - mid, Rl = tot - Gl, G = start + Gl, End =
    # after + Rl, decay = exp(sum of tot), with tot and mid rows of Gl
    sG = [down(dG[:, at(m)]) for m in range(ns)]
    sE = [down(dEnd[:, at(m)]) for m in range(ns)]
    row = jax.lax.broadcasted_iota(jnp.int32, (sub, dk), 0)
    dGb = []
    for m in range(ns):
        dt = (dtot[m] + down(dRl[:, at(m)]) + sE[m] + ddecay * t.decay
              + sum(sG[m + 1:], zero) + sum(sE[:m], zero))
        dm = dmid[m] - down(dEL[:, at(m)])
        dGb.append(dEL[:, at(m)] - dRl[:, at(m)] + dG[:, at(m)]
                   - dEnd[:, at(m)] + jnp.where(row == sub - 1, dt, 0.0)
                   + jnp.where(row == sub // 2 - 1, dm, 0.0))
    dg = _each(lambda x: _exact(cumT, x), cat(dGb))
    dk = (dk_l * t.left + dkD * t.ileft + dkR * t.eR + dkp * t.eG
          + dkt * t.eE)
    dq = dq_l * t.left + dqp * t.eG
    return dq, dk, dg, dbcol


def _bwd_kernel(cum_ref, cumT_ref, spread_ref, q_ref, k_ref, v_ref, g_ref,
                b_ref, s_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                db_ref, dst_ref, *, C: int, sub: int, nb: int, hb: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    t = _local(_blocked(q_ref, C), _blocked(k_ref, C), _blocked(g_ref, C),
               _beta_rows(b_ref, hb, nb, C), cum_ref[...], spread_ref[...],
               sub)
    v = _blocked(v_ref, C)
    do = _blocked(do_ref, C)
    st = [s_ref[h] for h in range(hb)]
    sts, es, ds = {}, {}, {}
    for n in range(nb):
        for h in range(hb):
            i = h * nb + n
            e = v[i] - _nt(t.kp[i], st[h])
            d = _nn(t.T[i], t.bcol[i] * e)
            sts[i], es[i], ds[i] = st[h], e, d
            if n < nb - 1:
                st[h] = t.decay[i] * st[h] + _tn(d, t.kt[i])
    dst = [dst_ref[h] for h in range(hb)]
    r, c = _iota2(C, C)
    out = [None] * (hb * nb)
    for n in reversed(range(nb)):
        for h in range(hb):
            i = h * nb + n
            dd = _tn(t.A[i], do[i]) + _nt(t.kt[i], dst[h])
            dR = _tn(t.T[i], dd)
            de = t.bcol[i] * dR
            x = jnp.concatenate([do[i], -de], axis=0)      # [2 C, dv]
            y = _nn(x, sts[i])                             # dq+ over dk+
            z = _nt(jnp.concatenate([dR, do[i]], axis=0), ds[i])
            out[i] = (jnp.where(c < r, -z[:C], 0.0),
                      jnp.where(c <= r, z[C:], 0.0), y[:C],
                      y[C:], _nn(ds[i], dst[h]),
                      jnp.sum(sts[i] * dst[h], axis=0, keepdims=True), de,
                      jnp.sum(dR * es[i], axis=1, keepdims=True))
            dst[h] = t.decay[i] * dst[h] + _tn(
                x, jnp.concatenate([t.qp[i], t.kp[i]], axis=0))
    for h in range(hb):
        dst_ref[h] = dst[h]
    dNb, dA, dqp, dkp, dkt, ddecay, dv, db = (jnp.stack(x)
                                              for x in zip(*out))
    dq, dk, dg, dbcol = _local_bwd(t, cumT_ref[...], sub, dNb, dA, dqp,
                                   dkp, dkt, ddecay)
    flat = lambda x, ref: x.reshape(ref.shape).astype(ref.dtype)
    dq_ref[...] = flat(dq, dq_ref)
    dk_ref[...] = flat(dk, dk_ref)
    dv_ref[...] = flat(dv, dv_ref)
    dg_ref[...] = flat(dg, dg_ref)
    for h in range(hb):
        for n in range(nb):
            db_ref[h, :, n * C:(n + 1) * C] = _to_row(
                dbcol[h * nb + n] + db[h * nb + n]).astype(db_ref.dtype)


# What the backward asks of VMEM: at two heads a step its live tiles (a
# block's local terms, the chunks' states, their cotangents) pass the
# default 16 MiB of scoped VMEM; a v5e core has 128 MiB
BWD_VMEM = 32 * 2 ** 20


def pick_heads(BH: int, want: int = 2) -> int:
    """Heads a grid step: the largest count <= `want` that divides B*H.
    Two heads' chains of small dependent products interleave in one
    step's program."""
    return max(h for h in range(1, min(want, BH) + 1) if BH % h == 0)


def _plan(q, k, v, g, beta, C: int, nb: int, back: bool):
    """What the two calls share: (heads a step, grid, the five inputs as
    the kernels take them, block specs of a constant, of [hb, L, dk] and
    [hb, L, dv] tiles, of beta's rows and of the heads' states at a
    block). The backward's index maps count the blocks down."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    BH, L = B * H, nb * C
    nB = S // L
    hb = pick_heads(BH)
    at = (lambda j: nB - 1 - j) if back else (lambda j: j)
    tile = lambda d: pl.BlockSpec((hb, L, d), lambda h, j: (h, at(j), 0))
    tb = pl.BlockSpec((hb, None, 1, L), lambda h, j: (h, at(j), 0, 0))
    ts = pl.BlockSpec((hb, None, dv, dk), lambda h, j: (h, at(j), 0, 0))
    const = lambda x: pl.BlockSpec(x.shape, lambda h, j: (0, 0))
    args = (q.reshape(BH, S, dk), k.reshape(BH, S, dk),
            v.reshape(BH, S, dv), g.astype(F32).reshape(BH, S, dk),
            beta.astype(F32).reshape(BH, nB, 1, L))
    return hb, (BH // hb, nB), args, (const, tile(dk), tile(dv), tb, ts)


def _call_params(interpret: bool, **kw) -> dict:
    """pallas_util's parameters with the caller's reading of the
    interpret policy: `forward` and `backward` are jitted, so the policy
    is read where they are called (at every trace of the caller) and
    keys their cache — a shape first traced interpreted is traced again
    when the same process compiles it for a described chip."""
    return {**pallas_util.tpu_call_params("parallel", "arbitrary", **kw),
            "interpret": interpret}


@functools.partial(jax.jit, static_argnames=("C", "sub", "nb", "interpret"))
def forward(q, k, v, g, beta, C: int, sub: int, nb: int, interpret: bool):
    """(o [B, H, S, dv] in v's dtype, the TRANSPOSED state entering each
    block [B*H, S/(nb*C), dv, dk] float32). Jitted, like `backward`, so
    that a model's layers share ONE trace and one lowering of the
    kernel's unrolled body (some ten thousand equations): six layers'
    forward and backward took 5.3 s to trace and lower without it, 0.9
    with — time every process start pays, compile cache or not. The
    call sites' scopes still prefix the kernel's op name in the compiled
    program."""
    cum, _, spread = constants(C, sub)
    hb, grid, args, (const, tk, tv, tb, ts) = _plan(q, k, v, g, beta, C,
                                                    nb, back=False)
    dk, dv = q.shape[-1], v.shape[-1]
    o, s_in = pl.pallas_call(
        functools.partial(_fwd_kernel, C=C, sub=sub, nb=nb, hb=hb),
        grid=grid,
        in_specs=[const(cum), const(spread), tk, tk, tv, tk, tb],
        out_specs=[tv, ts],
        out_shape=[jax.ShapeDtypeStruct(args[2].shape, v.dtype),
                   jax.ShapeDtypeStruct((grid[0] * hb, grid[1], dv, dk),
                                        F32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), F32)],
        name="kda_fwd",
        **_call_params(interpret),
    )(cum, spread, *args)
    return o.reshape(v.shape), s_in


@functools.partial(jax.jit, static_argnames=("C", "sub", "nb", "interpret"))
def backward(q, k, v, g, beta, s_in, do, C: int, sub: int, nb: int,
             interpret: bool):
    """(dq, dk, dv, dg, dbeta) in their inputs' shapes and dtypes."""
    cum, cumT, spread = constants(C, sub)
    hb, grid, args, (const, tk, tv, tb, ts) = _plan(q, k, v, g, beta, C,
                                                    nb, back=True)
    ins = (q, k, v, g, beta)
    grads = pl.pallas_call(
        functools.partial(_bwd_kernel, C=C, sub=sub, nb=nb, hb=hb),
        grid=grid,
        in_specs=[const(cum), const(cumT), const(spread), tk, tk, tv, tk,
                  tb, ts, tv],
        out_specs=[tk, tk, tv, tk, tb],
        out_shape=[jax.ShapeDtypeStruct(a.shape, x.dtype)
                   for a, x in zip(args, ins)],
        scratch_shapes=[pltpu.VMEM((hb, v.shape[-1], q.shape[-1]), F32)],
        name="kda_bwd",
        **_call_params(interpret, vmem_limit_bytes=BWD_VMEM),
    )(cum, cumT, spread, *args, s_in, do.reshape(args[2].shape))
    return tuple(d.reshape(x.shape) for d, x in zip(grads, ins))
