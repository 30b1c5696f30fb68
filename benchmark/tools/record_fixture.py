#!/usr/bin/env python3
"""Record the small trace the reduction is checked against
(benchmark/fixtures/tiny_tpu.xplane.pb): three calls of a jitted
two-scope program on the chip, each inside a `bench.fixture_step`
TraceAnnotation with a host sleep between them, so that the trace holds
device ops under the scopes `attention` and `mlp`, idle gaps of a known
cause, and nothing else. Run on the chip; writes into chiprun_out/.
"""
from __future__ import annotations

import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def main() -> int:
    import jax
    import jax.numpy as jnp
    from harness import common, xplane
    common.require_chip(1)

    @jax.jit
    def f(x, w):
        with jax.named_scope("attention"):
            y = jax.nn.softmax(x @ w, axis=-1)
        with jax.named_scope("mlp"):
            return jnp.tanh(y @ w).sum()

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    w = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x, w).block_until_ready()
    out = os.path.join(common.ROOT, "chiprun_out", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.fixture_step"):
            f(x, w).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.fixture_sleep"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(out)
    shutil.copy(path, os.path.join(common.ROOT, "chiprun_out",
                                   "tiny_tpu.xplane.pb"))
    print(path, os.path.getsize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main())
