"""The plain reference against the program at the tiny preset, the
`correct` comparison, and the trace reduction (run by hand:
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`; not tier-1)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

from harness import correct, xplane  # noqa: E402
from harness.xplane import Event, Line, Plane, Trace  # noqa: E402

TINY = {"vocab_size": 300, "n_positions": 64, "n_embd": 32, "n_layer": 2,
        "n_head": 2, "layer_norm_epsilon": 1e-5}


def _tiny():
    import jax
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.models import gpt2
    cfg = GPT2Config(vocab_size=300, n_positions=64, n_embd=32, n_layer=2,
                     n_head=2)
    return cfg, gpt2.init_params(cfg, jax.random.PRNGKey(3))


def _lora(cfg, seed=0):
    import jax
    from harness.train_child import seeded_lora
    return seeded_lora(cfg, {"rank": 4, "alpha": 8, "targets": [
        "attn_qkv", "attn_proj", "mlp_fc_in", "mlp_fc_out"]}, seed)


def test_reference_imports_nothing_from_the_program():
    src = open(os.path.join(BENCH, "reference", "gpt2.py")).read()
    assert "import mobilefinetuner_tpu" not in src
    assert "from mobilefinetuner_tpu" not in src
    assert "pallas" not in src and "lax.scan" not in src


@pytest.mark.parametrize("with_lora", [False, True])
def test_reference_matches_program_forward_loss_and_grads(with_lora):
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
    from reference import gpt2 as ref
    cfg, params = _tiny()
    lora = _lora(cfg) if with_lora else None
    ids = np.random.default_rng(0).integers(1, 299, (2, 48)).astype(np.int32)

    def prog_loss(p, l):
        s, n = lm_cross_entropy_sum(gpt2.forward(cfg, p, ids, lora=l), ids)
        return s / n

    with jax.default_matmul_precision("highest"):
        got = gpt2.forward(cfg, params, ids, lora=lora)
        want = ref.forward(TINY, params, ids, lora)
        assert correct.rel_max(got, want) < 1e-5
        assert abs(float(prog_loss(params, lora))
                   - float(ref.loss(want, jnp.asarray(ids)))) < 1e-5
        gp = jax.grad(prog_loss, argnums=(0, 1) if with_lora else 0)(
            params, lora)
        gr = jax.grad(lambda p, l: ref.loss_of(TINY, p, ids, ids, l),
                      argnums=(0, 1) if with_lora else 0)(params, lora)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        if np.size(a) > 1:
            assert correct.rel_max(a, b) < 1e-4
            assert correct.cosine(a, b) > 0.9999


def test_tail_gradient_is_the_exact_gradient_of_the_last_layers():
    import jax
    import jax.numpy as jnp
    from reference import gpt2 as ref
    cfg, params = _tiny()
    ids = np.random.default_rng(1).integers(1, 299, (1, 32)).astype(np.int32)
    full = jax.grad(lambda p: ref.loss_of(TINY, p, ids, ids))(params)
    x_mid = ref.run_blocks(TINY, params["blocks"], ref.embed(params, ids),
                           None, range(1))
    tail = jax.tree.map(lambda a: a[1:], params["blocks"])
    g = jax.grad(lambda bt: ref.loss(ref.head(TINY, params, ref.run_blocks(
        TINY, bt, x_mid, None, range(1))), jnp.asarray(ids)))(tail)
    assert correct.rel_max(g["mlp"]["fc_w"],
                           full["blocks"]["mlp"]["fc_w"][1:]) < 1e-5


def test_tolerance_refuses_a_lower_precision_and_a_dropped_term():
    """At two layers the limit is 8.5e-3: bf16 passes (5.6e-3 here); the
    same model with its weights rounded to an 8-bit float (e4m3, 3
    mantissa bits) fails (4.6e-2), and so does a model whose adapter was
    left out (7.5e-2)."""
    import jax
    import jax.numpy as jnp
    from mobilefinetuner_tpu.models import gpt2
    from reference import gpt2 as ref
    cfg, params = _tiny()
    lora = _lora(cfg)
    ids = np.random.default_rng(2).integers(1, 299, (2, 48)).astype(np.int32)
    want = ref.forward(TINY, params, ids, lora)
    bf16 = gpt2.forward(cfg, params, ids, lora=lora,
                        compute_dtype=jnp.bfloat16)
    tol = correct.logit_tol(cfg.n_layer)
    assert correct.logit_tol(24) == pytest.approx(2.94e-2, rel=1e-2)
    assert correct.rel_max(bf16, want) < tol
    fp8 = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(
        jnp.float32) if a.ndim >= 2 else a, params)
    low = gpt2.forward(cfg, fp8, ids, lora=lora)
    assert correct.rel_max(low, want) > 4 * tol
    dropped = gpt2.forward(cfg, params, ids, lora=None)
    assert correct.rel_max(dropped, want) > 4 * tol
    v = correct.verdict({"a": (1e-3, 1e-2, "max"), "b": (0.9, 0.995, "min")})
    assert not v["ok"] and v["checks"]["a"]["ok"] \
        and not v["checks"]["b"]["ok"]
    assert not correct.verdict({"nan": (float("nan"), 1.0, "max")})["ok"]


# --------------------------- the trace reduction ----------------------------

def synthetic_trace() -> Trace:
    """Two devices, 1 ms window. Device 0: ops at 0-200, 300-500 (scope
    attention), 500-600 (all-gather, exposed), 600-700 fusion overlapping
    nothing; a 100 us gap (200-300) under bench.engine_step and a 300 us
    gap (700-1000) under bench.wait_arrival. Times in us -> ns."""
    us = 1000
    ev = lambda n, s, d, t="": Event(n, s * us, d * us, t)
    ops0 = [ev("fusion.1", 0, 200, "jit(step)/mlp/dot_general"),
            ev("custom-call.7", 300, 200, "jit(step)/attention/flash_fwd/x"),
            ev("all-gather.3", 500, 100),
            ev("fusion.2", 600, 100),
            ev("copy.4", 999, 1)]
    ops1 = [ev("fusion.1", 0, 500, "jit(step)/mlp/dot_general"),
            ev("all-gather.3", 400, 200),      # 100 us hidden, 100 exposed
            ev("copy.4", 999, 1)]
    host = [ev("bench.engine_step", 150, 200), ev("bench.wait_arrival",
                                                  690, 320),
            ev("$python frame", 0, 1000)]
    return Trace([Plane("/device:TPU:0", [Line("XLA Ops", ops0)]),
                  Plane("/device:TPU:1", [Line("XLA Ops", ops1)]),
                  Plane("/host:CPU", [Line("python", host)])])


def test_reduction_on_a_hand_made_trace():
    tr = synthetic_trace()
    b = xplane.busy(tr)
    assert b["window_s"] == pytest.approx(1e-3)
    assert b["busy_s_by_device"]["/device:TPU:0"] == pytest.approx(601e-6)
    assert b["busy_s_by_device"]["/device:TPU:1"] == pytest.approx(601e-6)
    assert b["busy_s"] == pytest.approx(601e-6)
    assert b["idle_share_worst"] == pytest.approx(0.399)
    groups = dict(xplane.op_groups(tr))
    assert groups["mlp"] == pytest.approx((200 + 500) / 2 * 1e-6)
    assert groups["flash_fwd"] == pytest.approx(100e-6)   # innermost scope
    assert groups["all-gather"] == pytest.approx(150e-6)
    t, calls = xplane.op_time(tr, "flash_fwd")
    assert (t, calls) == (pytest.approx(100e-6), 0.5)
    c = xplane.collective_exposed(tr)
    assert c["exposed_s"] == pytest.approx(100e-6)        # both devices 100
    gaps = dict(xplane.idle_gaps(tr))
    assert gaps["bench.engine_step"] == pytest.approx(100e-6)
    assert gaps["bench.wait_arrival"] == pytest.approx(299e-6)


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 20]]) == [[0, 2], [3, 5]]
    assert xplane.subtract([[0, 10]], []) == [[0, 10]]
    assert xplane.total([[0, 3], [5, 7]]) == 5


FIXTURE = os.path.join(BENCH, "fixtures", "tiny_tpu.xplane.pb")


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded TPU trace in benchmark/fixtures")
def test_reduction_on_the_recorded_tpu_trace():
    """benchmark/tools/record_fixture.py on a TPU v5 lite (PR 22): three
    calls of a two-scope program with a 10 ms host sleep after each."""
    tr = xplane.load(FIXTURE)
    expect = json.load(open(os.path.join(BENCH, "fixtures",
                                         "tiny_tpu.expect.json")))
    b = xplane.busy(tr)
    assert len(b["busy_s_by_device"]) == expect["devices"]
    assert b["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-6)
    assert b["window_s"] == pytest.approx(expect["window_s"], rel=1e-6)
    assert 0 < b["busy_s"] < b["window_s"]
    groups = dict(xplane.op_groups(tr))
    for scope in expect["scopes"]:
        assert scope in groups, (scope, groups)
    gaps = dict(xplane.idle_gaps(tr))
    assert any(k.startswith("bench.fixture_sleep") for k in gaps), gaps
    # the two sleeps between the three calls are at least 10 ms each
    assert gaps["bench.fixture_sleep"] >= 0.02


# --------------------------- the rehearsal ----------------------------------

def test_rehearsal_runs_and_never_prints_a_metrics_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--only",
         "serve", "--seconds", "2", "--trace", "0"], env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln]
    assert lines and all(ln.get("rehearsal") is True for ln in lines)
    assert not any("metrics" in ln for ln in lines)
    # and the measuring command refuses to run without a TPU
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "md_batchgen_long", "--seed", "0", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0 and not out.stdout.strip()
