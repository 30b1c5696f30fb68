"""Ring attention vs the single-device oracle, on the virtual 8-device
mesh: forward AND gradients, causal / sliding-window / GQA / padding —
the long-context sequence-parallel path (parallel/ring_attention.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mobilefinetuner_tpu.ops.attention import dot_product_attention
from mobilefinetuner_tpu.parallel.mesh import make_mesh
from mobilefinetuner_tpu.parallel.ring_attention import ring_attention


def make_qkv(key, B=2, Hq=4, Hkv=2, S=64, D=16, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, Hq, S, D), dtype)
    k = jax.random.normal(k2, (B, Hkv, S, D), dtype)
    v = jax.random.normal(k3, (B, Hkv, S, D), dtype)
    return q, k, v


CASES = [
    dict(n=4),
    dict(n=8),
    dict(n=4, sliding_window=24),
    dict(n=4, Hkv=1),                       # extreme GQA
    dict(n=2, Hkv=4, S=96, D=32),           # MHA, odd shard size 48
    dict(n=4, is_causal=False),             # bidirectional
    dict(n=4, sliding_window=17),           # (w-1) % Sq == 0: 1 hop not 2
    dict(n=4, sliding_window=1),            # self-only window: 0 hops
]


def test_ring_hops_boundaries():
    """Hop t's nearest cell sits (t-1)*Sq+1 rows back, so the hop count is
    max(0, (w-2)//Sq + 1): a window of exactly Sq+1 needs ONE hop (the
    old (w-1)//Sq+1 formula shipped a fully-masked second hop), and w=1
    (self only) needs zero."""
    from mobilefinetuner_tpu.parallel.ring_attention import _ring_hops
    Sq = 16
    assert _ring_hops(8, None, Sq) == 7
    assert _ring_hops(8, 1, Sq) == 0
    assert _ring_hops(8, 2, Sq) == 1
    assert _ring_hops(8, Sq, Sq) == 1
    assert _ring_hops(8, Sq + 1, Sq) == 1
    assert _ring_hops(8, Sq + 2, Sq) == 2
    assert _ring_hops(8, 2 * Sq + 1, Sq) == 2
    assert _ring_hops(2, 10 * Sq, Sq) == 1     # clamped to n-1


@pytest.mark.parametrize("case", CASES)
def test_forward_matches_oracle(case):
    case = dict(case)
    n = case.pop("n")
    kw = {k: case.pop(k) for k in ("sliding_window", "is_causal")
          if k in case}
    mesh = make_mesh(data=1, fsdp=n, devices=jax.devices()[:n])
    q, k, v = make_qkv(jax.random.PRNGKey(0), **case)
    ours = ring_attention(q, k, v, mesh, **kw)
    ref_kw = dict(is_causal=True)
    ref_kw.update(kw)
    ref = dot_product_attention(q, k, v, **ref_kw)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_forward_with_padding():
    mesh = make_mesh(data=1, fsdp=4, devices=jax.devices()[:4])
    q, k, v = make_qkv(jax.random.PRNGKey(1))
    B, S = q.shape[0], q.shape[2]
    pad = np.ones((B, S), np.float32)
    pad[0, 50:] = 0.0
    pad = jnp.asarray(pad)
    ours = ring_attention(q, k, v, mesh, padding_mask=pad)
    ref = dot_product_attention(q, k, v, is_causal=True, padding_mask=pad)
    np.testing.assert_allclose(np.asarray(ours)[0, :, :50],
                               np.asarray(ref)[0, :, :50],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(ours)[1], np.asarray(ref)[1],
                               atol=2e-5, rtol=2e-5)


def test_gradients_match_oracle():
    """Reverse-mode through the ring (scan + ppermute transpose)."""
    mesh = make_mesh(data=1, fsdp=4, devices=jax.devices()[:4])
    q, k, v = make_qkv(jax.random.PRNGKey(2))

    def loss(fn, q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out * jnp.cos(out))

    ring = lambda q, k, v: ring_attention(q, k, v, mesh,
                                          sliding_window=24)
    ref = lambda q, k, v: dot_product_attention(q, k, v, is_causal=True,
                                                sliding_window=24)
    g_ours = jax.grad(lambda *a: loss(ring, *a), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: loss(ref, *a), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ours, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5, err_msg=name)


def test_gpt2_model_context_parallel_matches_single():
    """Whole-model sequence parallelism: GPT-2 forward with cp_mesh (ring
    attention, activations S-sharded by propagation) == the single-device
    forward — long-context capability end to end."""
    import dataclasses
    from mobilefinetuner_tpu.core.config import GPT2Config
    from mobilefinetuner_tpu.lora.lora import LoRASpec, init_lora_gpt2
    from mobilefinetuner_tpu.models import gpt2
    mesh = make_mesh(data=1, fsdp=4, devices=jax.devices()[:4])
    cfg = dataclasses.replace(GPT2Config.tiny(vocab_size=512),
                              n_positions=128)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    lora = init_lora_gpt2(cfg, LoRASpec(rank=4, alpha=8.0),
                          jax.random.PRNGKey(1))
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 128), 0, 512)
    ref = gpt2.forward(cfg, params, ids, lora=lora)
    out = jax.jit(lambda p, l, i: gpt2.forward(cfg, p, i, lora=l,
                                               cp_mesh=mesh))(
        params, lora, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

    # gradients through the sequence-parallel model reach the adapters
    def loss(l, cp):
        o = gpt2.forward(cfg, params, ids, lora=l,
                         cp_mesh=mesh if cp else None)
        return (o.astype(jnp.float32) ** 2).mean()

    g_cp = jax.jit(jax.grad(lambda l: loss(l, True)))(lora)
    g_ref = jax.grad(lambda l: loss(l, False))(lora)
    for a, b in zip(jax.tree.leaves(g_cp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_gemma_model_context_parallel_matches_single():
    """Gemma under cp_mesh: the per-layer global/local interleave rides
    lax.cond into ring attention with the right window."""
    from mobilefinetuner_tpu.core.config import Gemma3TextConfig
    from mobilefinetuner_tpu.models import gemma3
    mesh = make_mesh(data=1, fsdp=4, devices=jax.devices()[:4])
    cfg = Gemma3TextConfig.tiny()
    params = gemma3.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                             cfg.vocab_size)
    mask = jnp.ones((2, 64))
    ref = gemma3.forward(cfg, params, ids, attention_mask=mask)
    out = jax.jit(lambda p, i: gemma3.forward(cfg, p, i,
                                              attention_mask=mask,
                                              cp_mesh=mesh))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_sequence_parallel_cli_end_to_end(tmp_path):
    """--sequence_parallel through the real CLI: S sharded over the fsdp
    axis, ring attention in the compiled train step, loss decreases."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from fixtures import write_tiny_gpt2_dir, write_wikitext_dir
    from mobilefinetuner_tpu.cli.gpt2_lora_finetune import main
    gpt2_dir = str(tmp_path / "gpt2")
    write_tiny_gpt2_dir(gpt2_dir)
    wiki = write_wikitext_dir(str(tmp_path / "wiki"))
    csv_path = str(tmp_path / "m.csv")
    rc = main(["--pretrained_dir", gpt2_dir, "--data_dir", wiki,
               "--steps", "6", "--batch_size", "2", "--seq_len", "32",
               "--lr", "5e-3", "--mesh_data", "1", "--mesh_fsdp", "4",
               "--sequence_parallel",
               "--lora_out", str(tmp_path / "a.safetensors"),
               "--metrics_csv", csv_path])
    assert rc == 0
    import csv as csv_mod
    rows = list(csv_mod.DictReader(open(csv_path)))
    assert float(rows[-1]["loss"]) < float(rows[0]["loss"])


def test_under_jit_with_sharded_inputs():
    """The production shape: inputs already sequence-sharded on the mesh,
    ring attention under jit keeps them sharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh(data=1, fsdp=4, devices=jax.devices()[:4])
    q, k, v = make_qkv(jax.random.PRNGKey(3))
    sh = NamedSharding(mesh, P(None, None, "fsdp", None))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, mesh)

    out = f(q, k, v)
    assert out.sharding.spec == P(None, None, "fsdp", None)
    ref = dot_product_attention(jax.device_get(q), jax.device_get(k),
                                jax.device_get(v), is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

# ------------------------- flash-kernel ring body ---------------------------
# D >= 64 engages _ring_shard_flash (parallel/ring_attention.py): partial
# flash attention per hop with a static shifted-band mask, merged in
# (lse, out) space — per-device scores stay blockwise, never [Sq, Sk].

FLASH_CASES = [
    dict(n=8, S=512, D=64, Hq=4, Hkv=2),                  # GQA causal
    dict(n=8, S=512, D=64, Hq=4, Hkv=2, sliding_window=96),   # 2-hop band
    dict(n=4, S=256, D=64, Hq=2, Hkv=2, sliding_window=300),  # w > S/2
    dict(n=2, S=128, D=128, Hq=2, Hkv=1),                 # D=128, n=2
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_ring_forward_matches_oracle(case):
    case = dict(case)
    n = case.pop("n")
    kw = ({"sliding_window": case.pop("sliding_window")}
          if "sliding_window" in case else {})
    from mobilefinetuner_tpu.ops.flash_attention import \
        kernel_eligible
    assert kernel_eligible(case["S"] // n, case["D"])
    mesh = make_mesh(data=1, fsdp=n, devices=jax.devices()[:n])
    q, k, v = make_qkv(jax.random.PRNGKey(0), **case)
    ours = ring_attention(q, k, v, mesh, **kw)
    ref = dot_product_attention(q, k, v, is_causal=True, **kw)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_ring_with_padding():
    mesh = make_mesh(data=1, fsdp=8, devices=jax.devices()[:8])
    q, k, v = make_qkv(jax.random.PRNGKey(1), B=2, Hq=2, Hkv=1, S=512,
                       D=64)
    pad = np.ones((2, 512), np.float32)
    pad[0, 400:] = 0.0
    pad = jnp.asarray(pad)
    ours = ring_attention(q, k, v, mesh, padding_mask=pad)
    ref = dot_product_attention(q, k, v, is_causal=True, padding_mask=pad)
    np.testing.assert_allclose(np.asarray(ours)[0, :, :400],
                               np.asarray(ref)[0, :, :400],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(ours)[1], np.asarray(ref)[1],
                               atol=2e-5, rtol=2e-5)


def test_flash_ring_gradients_match_oracle():
    """Reverse-mode through the flash ring: the merge tree differentiates
    through BOTH out and lse of every hop's partial (the joint custom_vjp
    in ops/flash_attention.py)."""
    mesh = make_mesh(data=1, fsdp=4, devices=jax.devices()[:4])
    q, k, v = make_qkv(jax.random.PRNGKey(2), B=1, Hq=2, Hkv=1, S=256,
                       D=64)

    def loss(fn, q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out * jnp.cos(out))

    for kw in ({}, {"sliding_window": 96}):
        ring = lambda q, k, v: ring_attention(q, k, v, mesh, **kw)
        ref = lambda q, k, v: dot_product_attention(q, k, v,
                                                    is_causal=True, **kw)
        g_ours = jax.grad(lambda *a: loss(ring, *a),
                          argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda *a: loss(ref, *a),
                         argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_ours, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5,
                                       err_msg=f"{name} {kw}")


def test_flash_ring_long_context_8k():
    """The regime ring attention exists for: S=8192 over 8 devices. The
    flash body's equivalence oracle here is the DENSE ring body at the
    same sharding (the full [S, S] single-device oracle would be the
    memory blow-up this path avoids); fwd + grad run and agree."""
    from functools import partial as _p
    from mobilefinetuner_tpu.parallel import ring_attention as ra
    mesh = make_mesh(data=1, fsdp=8, devices=jax.devices()[:8])
    q, k, v = make_qkv(jax.random.PRNGKey(3), B=1, Hq=2, Hkv=1, S=8192,
                       D=64, dtype=jnp.float32)
    out = ring_attention(q, k, v, mesh, sliding_window=1024)
    assert np.isfinite(np.asarray(out)).all()
    # dense-body reference at the same sharding
    from jax.sharding import PartitionSpec as P
    pad = jnp.ones((1, 8192), jnp.float32)
    dense = jax.shard_map(
        _p(ra._ring_shard, axis="fsdp", n=8, scale=1.0 / 8.0, causal=True,
           window=1024),
        mesh=mesh,
        in_specs=(P(None, None, "fsdp", None),) * 3 + (P(None, "fsdp"),),
        out_specs=P(None, None, "fsdp", None), check_vma=False,
    )(q, k, v, pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=3e-5, rtol=3e-5)


def test_gemma_model_context_parallel_flash_body():
    """Full-size-shaped Gemma under cp_mesh with a FLASH-eligible shard
    (head_dim 64, Sq=128): the per-layer global/local lax.cond now selects
    between two flash-ring variants (shard_map + pallas inside cond
    branches) — the exact composition a real Gemma sequence-parallel run
    hits."""
    from mobilefinetuner_tpu.core.config import Gemma3TextConfig
    from mobilefinetuner_tpu.models import gemma3
    from mobilefinetuner_tpu.ops.flash_attention import \
        kernel_eligible
    mesh = make_mesh(data=1, fsdp=4, devices=jax.devices()[:4])
    cfg = Gemma3TextConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_hidden_layers=4, num_attention_heads=2, num_key_value_heads=1,
        head_dim=64, max_position_embeddings=1024, sliding_window=96,
        query_pre_attn_scalar=64.0, sliding_window_pattern=3)
    assert kernel_eligible(512 // 4, cfg.head_dim)
    params = gemma3.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 512), 0, 512)
    mask = jnp.ones((2, 512))
    ref = gemma3.forward(cfg, params, ids, attention_mask=mask)
    out = jax.jit(lambda p, i: gemma3.forward(cfg, p, i,
                                              attention_mask=mask,
                                              cp_mesh=mesh))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)
