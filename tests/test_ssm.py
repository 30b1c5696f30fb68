"""ops/ssm.py at a tiny size on the CPU: the chunked selective scan
against its own token-by-token recurrence (`ssm_recurrent`), forward and
every gradient, at a length of whole chunks and at one that is not; the
control that a state carried in bfloat16 lies far outside the same
tolerance; the conv in front of it against a loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mobilefinetuner_tpu.ops import ssm

Bt, DI, N, CHUNK = 2, 24, 16, 8
# float32 rounding: the two forms add the same products in the same
# order within a chunk; what differs is where a sum is cut (chunk
# boundaries) and the padded tail, so max |got - want| / max |want|
# stays a few units of float32's 6e-8 at these lengths
F32_TOL = 2e-6
ARGS = ("u", "delta", "A_log", "B", "C", "D")


def inputs(S, seed=0):
    """Mamba's own initialisation (models/phi4flash.init_params): A_log =
    log(1 ... N) on every channel, Delta from softplus around a
    log-uniform [1e-3, 1e-1], D = 1 — slow and fast channels both."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt0 = jnp.exp(jax.random.uniform(k[0], (DI,), jnp.float32,
                                     np.log(1e-3), np.log(1e-1)))
    b_dt = dt0 + jnp.log(-jnp.expm1(-dt0))
    delta = jax.nn.softplus(b_dt + 0.3 * jax.random.normal(k[1],
                                                           (Bt, S, DI)))
    return {"u": jax.random.normal(k[2], (Bt, S, DI)), "delta": delta,
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (DI, N)),
            "B": jax.random.normal(k[3], (Bt, S, N)),
            "C": jax.random.normal(k[4], (Bt, S, N)),
            "D": jnp.ones((DI,), jnp.float32)}


def rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def chunked(*a):
    return ssm.selective_scan(*a, chunk=CHUNK)[0]


def recurrent(*a):
    return ssm.ssm_recurrent(*a)[0]


@pytest.mark.parametrize("S", [64, 37])
def test_chunked_scan_matches_the_recurrence_and_every_gradient(S):
    """8 chunks, and 4 chunks plus 5 steps (the padding's Delta = 0
    passes the state on unchanged)."""
    x = inputs(S)
    a = [x[k] for k in ARGS]
    y_c, h_c, absmax = jax.jit(ssm.selective_scan,
                               static_argnames="chunk")(*a, chunk=CHUNK)
    y_r, h_last = jax.jit(ssm.ssm_recurrent)(*a)
    assert y_c.dtype == jnp.float32
    assert rel(y_c, y_r) < F32_TOL
    assert rel(h_c, h_last) < F32_TOL
    # two pieces, the second from the first's last state, are the whole
    cut = 3 * CHUNK + 1
    first = jax.jit(ssm.selective_scan, static_argnames="chunk")(
        *[v[:, :cut] if v.ndim == 3 else v for v in a], chunk=CHUNK)
    second = jax.jit(ssm.selective_scan, static_argnames="chunk")(
        *[v[:, cut:] if v.ndim == 3 else v for v in a], chunk=CHUNK,
        h0=first[1])
    assert rel(jnp.concatenate([first[0], second[0]], 1), y_r) < F32_TOL
    # the counter: the largest |h| at a chunk boundary, the last state's
    # among them
    assert float(absmax) >= float(jnp.abs(h_last).max()) > 0
    w = jax.random.normal(jax.random.PRNGKey(9), y_r.shape)
    loss = lambda fn: lambda *a: (fn(*a) * w).sum()
    g_c = jax.jit(jax.grad(loss(chunked), argnums=range(6)))(*a)
    g_r = jax.jit(jax.grad(loss(recurrent), argnums=range(6)))(*a)
    for name, gc, gr in zip(ARGS, g_c, g_r):
        assert float(jnp.abs(gr).max()) > 0, name
        assert rel(gc, gr) < F32_TOL, name


def test_a_bfloat16_state_lies_far_outside_the_tolerance():
    """The control: the same recurrence with its state and decay rounded
    to bfloat16 every step (what a lower-precision scan would do) misses
    the float32 recurrence by far more than F32_TOL — the slow channels'
    decay exp(-Delta A) near 0.999 rounds to 1 in bfloat16's 8 bits."""
    x = inputs(64)
    a = [x[k] for k in ARGS]
    want = jax.jit(recurrent)(*a)
    At = -jnp.exp(x["A_log"]).T
    bf = lambda v: jax.lax.reduce_precision(v, 8, 7)

    def step(h, xs):
        ut, dt, bt, ct = xs
        h = bf(bf(jnp.exp(dt[:, None, :] * At)) * h
               + (dt * ut)[:, None, :] * bt[:, :, None])
        return h, (h * ct[:, :, None]).sum(1)

    tm = lambda v: jnp.swapaxes(v, 0, 1)
    _, y = jax.lax.scan(step, jnp.zeros((Bt, N, DI)),
                        (tm(x["u"]), tm(x["delta"]), tm(x["B"]),
                         tm(x["C"])))
    got = tm(y) + x["u"] * x["D"]
    assert rel(got, want) > 100 * F32_TOL


def test_conv_silu_is_a_causal_depthwise_conv():
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k[0], (2, 11, 6))
    w = jax.random.normal(k[1], (4, 6))
    b = jax.random.normal(k[2], (6,))
    want = np.zeros((2, 11, 6), np.float32)
    for t in range(11):
        for j in range(4):
            s = t - 3 + j
            if s >= 0:
                want[:, t] += np.asarray(w[j]) * np.asarray(x[:, s])
    want = np.asarray(jax.nn.silu(want + np.asarray(b)))
    np.testing.assert_allclose(jax.jit(ssm.conv_silu)(x, w, b), want,
                               rtol=1e-5,
                               atol=1e-6)
