"""Flash attention over a batch-parallel training mesh: the dispatcher
(ops/attention.attention) runs the unchanged kernels under shard_map over
the mesh's batch axes — forward, gradients, whole-model parity with the
one-device run, what it falls back to, and in-kernel dropout under the
wrap. Interpret mode on the virtual CPU mesh; that the TPU compiler takes
the wrapped kernels is tests/test_aot_tpu.py's."""

import argparse
import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from fixtures import write_tiny_gpt2_dir, write_wikitext_dir
from test_flash_attention import _masked_dropout_oracle, make_qkv

from mobilefinetuner_tpu.core.config import Gemma3TextConfig, GPT2Config
from mobilefinetuner_tpu.models import gemma3, gpt2
from mobilefinetuner_tpu.ops.attention import (attention,
                                               dot_product_attention)
from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
from mobilefinetuner_tpu.parallel.mesh import (batch_sharding, make_mesh,
                                               shard_batch, shard_params)

MESHES = [(1, 4), (2, 2)]


def mesh_of(data, fsdp):
    return make_mesh(data=data, fsdp=fsdp,
                     devices=jax.devices()[:data * fsdp])


def ragged_pad(B, S):
    """Key-padding mask with whole, nearly whole and short rows."""
    lens = np.resize([S, S - 7, S // 2, 3], B)
    return jnp.asarray(np.arange(S)[None, :] < lens[:, None], jnp.float32)


def has_scores(text: str, S: int) -> bool:
    """Does a lowered program hold an [..., S, S] operand of rank >= 4 —
    the XLA path's scores / probabilities (the interpreted kernel's tiles
    are rank 2)?"""
    return re.search(rf"tensor<(\d+x){{2,}}{S}x{S}x", text) is not None


# ------------------------------ the wrapped op ------------------------------

@pytest.mark.parametrize("padded", [False, True], ids=["whole", "padded"])
@pytest.mark.parametrize("shape", MESHES, ids=str)
def test_wrapped_flash_matches_oracle(shape, padded):
    """Forward and q/k/v gradients of flash under shard_map against the
    XLA oracle on the unsharded arrays (GQA, causal)."""
    mesh = mesh_of(*shape)
    q, k, v = make_qkv(jax.random.PRNGKey(0), B=8)
    pad = ragged_pad(8, 128) if padded else None
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, padding_mask=pad, **kw) * w)

    ours = jax.jit(lambda q, k, v: attention(
        q, k, v, impl="flash", mesh=mesh, padding_mask=pad))(q, k, v)
    ref = dot_product_attention(q, k, v, padding_mask=pad)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    g = jax.jit(jax.grad(loss(attention, impl="flash", mesh=mesh),
                         argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5, err_msg=name)


@pytest.mark.parametrize("impl,kernel", [("flash", True), ("xla", False)])
def test_lowered_attention_over_a_mesh(impl, kernel):
    """flash over a mesh lowers to the kernels under shard_map and holds
    no [B, H, S, S] operand, forward or backward; `xla` over the same
    mesh is still the XLA path."""
    mesh = mesh_of(1, 4)
    q, k, v = make_qkv(jax.random.PRNGKey(0), B=8)
    text = jax.jit(jax.grad(lambda q, k, v: attention(
        q, k, v, impl=impl, mesh=mesh).sum(), argnums=(0, 1, 2))).lower(
            q, k, v).as_text(debug_info=True)
    assert has_scores(text, 128) is not kernel
    for scope in ("flash_fwd", "flash_bwd_merged"):
        assert (scope in text) is kernel, scope
    assert ("shmap_body" in text or "shard_map" in text) is kernel


def test_batch_the_mesh_does_not_divide_takes_the_xla_path():
    """shard_map refuses an uneven split where GSPMD pads (an eval batch
    on a single-host mesh is not validated): 6 sequences over 4 devices
    run the XLA path and match."""
    mesh = mesh_of(1, 4)
    q, k, v = make_qkv(jax.random.PRNGKey(1), B=6)
    pad = ragged_pad(6, 128)
    fn = jax.jit(lambda q, k, v: attention(q, k, v, impl="flash", mesh=mesh,
                                           padding_mask=pad))
    assert has_scores(fn.lower(q, k, v).as_text(), 128)
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)),
        np.asarray(dot_product_attention(q, k, v, padding_mask=pad)),
        atol=2e-5, rtol=2e-5)


def test_one_device_mesh_lowers_to_the_same_text():
    """No shard_map of size one: a (1, 1) mesh is today's program."""
    q, k, v = make_qkv(jax.random.PRNGKey(2))
    lowered = lambda mesh: jax.jit(jax.grad(lambda q: attention(
        q, k, v, impl="flash", mesh=mesh).sum())).lower(q).as_text()
    assert lowered(mesh_of(1, 1)) == lowered(None)


@pytest.mark.parametrize("bwd_impl", ["merged", "split"])
def test_dropout_under_the_wrap(bwd_impl):
    """Every shard holds the SAME rows: the k-th sequence of two shards
    must not draw the same mask (the kernel hashes the LOCAL batch index),
    and both backward kernels regenerate exactly the forward's — checked
    against the dense oracle under the numpy-recomputed hash mask of each
    shard's folded key."""
    mesh = mesh_of(2, 2)
    q1, k1, v1 = make_qkv(jax.random.PRNGKey(3), B=2, Hq=2, Hkv=1)
    q, k, v = (jnp.tile(t, (4, 1, 1, 1)) for t in (q1, k1, v1))
    rng, p_drop = jax.random.PRNGKey(11), 0.2
    w = jax.random.normal(jax.random.PRNGKey(12), q.shape)

    def ours(q, k, v):
        return attention(q, k, v, impl="flash", mesh=mesh,
                         attn_dropout=p_drop, attn_dropout_rng=rng,
                         bwd_impl=bwd_impl)

    def oracle(q, k, v):
        outs = []
        for i in range(4):   # shard i = position i on ("data", "fsdp")
            seed = int(np.asarray(jax.lax.bitcast_convert_type(
                jax.random.bits(jax.random.fold_in(rng, i), (1,),
                                jnp.uint32), jnp.int32))[0])
            rows = slice(2 * i, 2 * i + 2)
            outs.append(_masked_dropout_oracle(q[rows], k[rows], v[rows],
                                               seed, p_drop))
        return jnp.concatenate(outs)

    out = np.asarray(jax.jit(ours)(q, k, v))
    for i in range(1, 4):
        assert np.abs(out[2 * i:2 * i + 2] - out[:2]).max() > 1e-3, i
    np.testing.assert_allclose(out, np.asarray(oracle(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    g = jax.jit(jax.grad(lambda *a: jnp.sum(ours(*a) * w),
                         argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(oracle(*a) * w),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5, err_msg=name)


# ------------------------------ whole models --------------------------------

@pytest.mark.parametrize("shape", MESHES, ids=str)
def test_gpt2_full_ft_on_the_mesh_matches_one_device(shape):
    """Full-FT loss and gradients, `auto` at a flash-eligible length
    (S = 512, D = 64), remat on: params FSDP-sharded and the batch over
    both axes, against the same step with no mesh."""
    mesh = mesh_of(*shape)
    cfg = dataclasses.replace(GPT2Config.tiny(vocab_size=256), n_embd=128,
                              n_head=2, n_positions=512)
    assert cfg.attention_impl == "auto"
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 512), 0, 256)
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1),
             "attention_mask": ragged_pad(4, 512)}

    def step(mesh):
        def loss(p, mb):
            logits = gpt2.forward(cfg, p, mb["input_ids"],
                                  attention_mask=mb["attention_mask"],
                                  remat=True, mesh=mesh)
            total, count = lm_cross_entropy_sum(logits, mb["labels"])
            return total / count
        return jax.jit(jax.value_and_grad(loss))

    l_ref, g_ref = step(None)(params, batch)
    sharded = step(mesh)
    p_m, b_m = shard_params(params, mesh, min_size=2 ** 12), \
        shard_batch(batch, mesh)
    text = sharded.lower(p_m, b_m).as_text(debug_info=True)
    assert "flash_bwd_merged" in text and not has_scores(text, 512)
    l, g = sharded(p_m, b_m)
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-4, err_msg=str(path))


def test_gemma_flash_on_the_mesh_matches_one_device():
    """Gemma reaches the dispatcher from inside a lax.cond per layer kind
    (global / sliding window): the same wrap, GQA, under the scan."""
    mesh = mesh_of(1, 4)
    cfg = Gemma3TextConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=2, num_key_value_heads=1,
        head_dim=64, max_position_embeddings=256, sliding_window=32,
        query_pre_attn_scalar=64.0, sliding_window_pattern=3,
        attention_impl="flash")
    params = gemma3.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0, 512)
    mask = ragged_pad(4, 128)

    def loss(mesh):
        return jax.jit(jax.value_and_grad(lambda p, ids, mask: jnp.mean(
            gemma3.forward(cfg, p, ids, attention_mask=mask,
                           mesh=mesh) ** 2)))

    l_ref, g_ref = loss(None)(params, ids, mask)
    sh = batch_sharding(mesh)
    l, g = loss(mesh)(shard_params(params, mesh, min_size=2 ** 12),
                      jax.device_put(ids, sh), jax.device_put(mask, sh))
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-4)


# ------------------------------ the CLI's part ------------------------------

@pytest.mark.parametrize("impl", ["auto", "flash", "xla"])
def test_build_mesh_leaves_attention_impl_as_given(impl):
    """Over a batch-parallel mesh --attention_impl keeps its one-chip
    meaning (`flash` by name was an error there, `auto` became `xla`);
    --lora_impl's kernel is not wrapped, so its downgrade stays."""
    from mobilefinetuner_tpu.cli.common import (build_mesh,
                                                with_attention_impl)
    args = argparse.Namespace(mesh_data=1, mesh_fsdp=4, batch_size=8,
                              seq_len=1024, attention_impl=impl,
                              lora_impl="auto")
    mesh, cp_mesh = build_mesh(args)
    assert mesh.size == 4 and cp_mesh is None
    assert args.attention_impl == impl and args.lora_impl == "naive"
    cfg = with_attention_impl(args, GPT2Config.gpt2_small())
    assert cfg.attention_impl == impl
    assert args.attention_impl_resolved == ("xla" if impl == "xla"
                                            else "flash")
    args.lora_impl = "fused"
    with pytest.raises(SystemExit, match="shard_map"):
        build_mesh(args)


def test_full_ft_cli_over_a_mesh_trains_on_flash(tmp_path):
    """gpt2_full_finetune --mesh_fsdp 4 --attention_impl flash (refused
    before) against the same run on one device: the manifest says flash
    and the losses agree."""
    from mobilefinetuner_tpu.cli.gpt2_full_finetune import main
    gpt2_dir, wiki = str(tmp_path / "gpt2"), str(tmp_path / "wiki")
    write_tiny_gpt2_dir(gpt2_dir, n_embd=128, n_head=2)
    write_wikitext_dir(wiki)

    def losses(tag, *mesh_flags):
        stream = str(tmp_path / f"{tag}.jsonl")
        assert main(["--pretrained_dir", gpt2_dir, "--data_dir", wiki,
                     "--steps", "3", "--batch_size", "4", "--seq_len", "64",
                     "--attention_impl", "flash", "--no_model_dropout",
                     "--log_interval", "1", "--telemetry_out", stream,
                     "--output_path", str(tmp_path / f"{tag}.safetensors"),
                     *mesh_flags]) == 0
        with open(stream) as f:
            events = [json.loads(line) for line in f]
        start = next(e for e in events if e["event"] == "run_start")
        assert start["config"]["attention_impl_resolved"] == "flash"
        return [e["loss"] for e in events if e["event"] == "step_stats"]

    one, four = losses("one"), losses("four", "--mesh_fsdp", "4")
    assert len(one) == 3
    np.testing.assert_allclose(four, one, rtol=2e-5)
