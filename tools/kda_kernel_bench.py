"""The chunked delta rule alone, on the chip: ops/kda.py's Pallas kernel
against its XLA form at the shapes of `ling_lora_long` (32 heads of 128
over 16,384 tokens, bf16 activations) — milliseconds a layer, forward
and forward + backward — and the kernel as Mosaic compiled it against
the token-by-token recurrence (interpret mode on the CPU, which the unit
tests use, is not the compiled kernel).

  python tools/kda_kernel_bench.py [--blocks 4,2] [--S 16384] [--heads 32]
                                   [--skip_xla]

`--skip_xla` leaves the XLA form's timing out (its scans take the
compiler a minute of a chip call).

One JSON line a reading; the same lines in chiprun_out/kda_kernel_bench.jsonl.
Refuses to time anything off a TPU."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mobilefinetuner_tpu.ops import kda  # noqa: E402

OUT = os.path.join("chiprun_out", "kda_kernel_bench.jsonl")


def say(**kw):
    line = json.dumps(kw)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def inputs(seed, H, S, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = (unit(jax.random.normal(ks[0], (1, H, S, d))) * d ** -0.5)
    k = unit(jax.random.normal(ks[1], (1, H, S, d)))
    v = jax.random.normal(ks[2], (1, H, S, d))
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (1, H, S, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, H, S)))
    ct = jax.random.normal(ks[5], (1, H, S, d))
    lo = lambda x: x.astype(dtype)
    return (lo(q), lo(k), lo(v), g, beta), lo(ct)


def timed(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ts), min(ts)


def parity(S, H=2, d=128):
    """The compiled kernel and the XLA form against the recurrence,
    float32 inputs: max |error| of o, and of each gradient over the
    larger of 1 and the gradient's max."""
    args, ct = inputs(7, H, S, d, jnp.float32)
    want = jax.jit(lambda *a: kda.kda_recurrent(*a)[0])(*args)
    gw = jax.jit(jax.grad(lambda *a: (kda.kda_recurrent(*a)[0] * ct).sum(),
                          argnums=range(5)))(*args)
    for impl in kda.IMPLS:
        f = lambda *a: kda.kda_chunked(*a, impl=impl)
        got = jax.jit(f)(*args)
        gg = jax.jit(jax.grad(lambda *a: (f(*a) * ct).sum(),
                              argnums=range(5)))(*args)
        say(what="parity", impl=impl, S=S,
            o=float(jnp.abs(got - want).max()),
            **{f"d{n}": float(jnp.abs(a - b).max()
                              / max(float(jnp.abs(b).max()), 1.0))
               for n, a, b in zip("q k v g beta".split(), gg, gw)})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="4")
    ap.add_argument("--S", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--skip_xla", action="store_true")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU here ({dev.platform}): nothing timed")
    say(what="device", kind=dev.device_kind)
    parity(512)
    args, ct = inputs(1, a.heads, a.S, 128, jnp.bfloat16)
    cases = [("kernel", int(b)) for b in a.blocks.split(",")] \
        + ([] if a.skip_xla else [("chunked", 4)])
    for impl, nb in cases:
        f = lambda *x: kda.kda_chunked(*x, impl=impl, block_chunks=nb)
        fwd = jax.jit(f)
        # the cotangent is an argument: closed over, its 134 MB would be
        # a constant of the executable
        both = jax.jit(jax.grad(
            lambda ct, *x: (f(*x).astype(jnp.float32)
                            * ct.astype(jnp.float32)).sum(),
            argnums=range(1, 6)))
        t0 = time.perf_counter()
        f_ms = timed(fwd, *args)
        b_ms = timed(both, ct, *args)
        say(what="ms_a_layer", impl=impl, block_chunks=nb, S=a.S,
            heads=a.heads, fwd_ms=f_ms[0], fwd_min_ms=f_ms[1],
            fwd_bwd_ms=b_ms[0], fwd_bwd_min_ms=b_ms[1],
            wall_s=time.perf_counter() - t0)


if __name__ == "__main__":
    main()
