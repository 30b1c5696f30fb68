"""Phi-4-mini-flash-reasoning (SambaY) on the LoRA trainer, at a tiny size
on the CPU: models/phi4flash.py against the plain reference the
benchmark keeps (benchmark/reference/phi4flash.py, which imports nothing
from the program) — logits, the loss through the trainer's chunked
cross-entropy, and the adapter gradients that lie below the family's
mechanisms; differential attention at lambda = 0; the window of 512 in
the flash kernels against a dense mask; the cross-decoder's keys and
values; the loss's chunk count for every family's cell; the loader, the
PEFT round trip and the CLI through cli/common.run_training."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import common as bench_common  # noqa: E402
from harness.traffic import argv_value  # noqa: E402
from reference import phi4flash as ref  # noqa: E402

from mobilefinetuner_tpu.cli.expert_lora import loss_chunks  # noqa: E402
from mobilefinetuner_tpu.core.config import Phi4FlashConfig  # noqa: E402
from mobilefinetuner_tpu.lora.lora import (PHI4FLASH_TARGETS,  # noqa: E402
                                           LoRASpec, init_lora_phi4flash)
from mobilefinetuner_tpu.models import phi4flash  # noqa: E402
from mobilefinetuner_tpu.ops.attention import (attention,  # noqa: E402
                                               dot_product_attention)
from mobilefinetuner_tpu.ops.loss import (  # noqa: E402
    chunked_lm_cross_entropy_sum)

S = 40              # five scan chunks of 8, five windows of 8
# float32 parity of the whole model: max |got - want| over max |want|.
# The two sides differ in the scan's form (chunked against token by
# token), in attention's (the same softmax, summed in another order) and
# in the loss's (chunked), all in float32 at "highest": a few units of
# float32's 6e-8 a layer, over 8 layers and their backward
F32_TOL = 2e-5
# an adapter gradient's cosine against the reference's: the same sums in
# float32; anything under this is a wrong term, not rounding
GRAD_COS = 0.99999


@functools.lru_cache(maxsize=None)
def make(seed=0):
    """The tiny model's weights, an adapter on every target with both
    factors N(0, 0.05) (a fresh adapter's B = 0 hides A; numpy draws
    them) and a sequence. The weights are drawn op by op: the draws'
    kernels are compiled once per shape and shared with every later draw
    (the fixture writer's too)."""
    c = Phi4FlashConfig.tiny()
    params = phi4flash.init_params(c, jax.random.PRNGKey(seed))
    spec = LoRASpec(rank=4, alpha=8.0, init="peft",
                    targets=sorted(PHI4FLASH_TARGETS))

    shapes = jax.eval_shape(lambda: init_lora_phi4flash(
        c, spec, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(seed)
    lora = jax.tree.map(
        lambda l: jnp.asarray(spec.scale, l.dtype) if l.ndim == 0
        else jnp.asarray(0.05 * rng.standard_normal(l.shape), l.dtype),
        shapes)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 3), (1, S), 0,
                             c.vocab_size)
    return c, params, lora, ids


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def cos(got, want):
    g, w = np.ravel(got).astype(np.float64), np.ravel(want).astype(
        np.float64)
    return float(g @ w / (np.linalg.norm(g) * np.linalg.norm(w)))


def prog_loss(c, params, lora, ids, remat=False):
    """(mean loss through the trainer's chunked cross-entropy, (logits,
    aux))."""
    h, aux = phi4flash.hidden_states(c, params, ids, lora=lora,
                                     remat=remat, with_aux=True)
    s, n = chunked_lm_cross_entropy_sum(
        h, params["embed"], ids, num_chunks=loss_chunks(*ids.shape,
                                                        c.vocab_size))
    return s / n, (h @ params["embed"].T, aux)


@pytest.fixture(scope="module")
def tiny():
    """The program's loss, logits and adapter gradients (remat, as the
    cell trains) in one compile; the reference's logits, and its
    gradient over the tail from the self-decoder's last Mamba layer on
    (everything below the three mechanisms' leaves)."""
    c, params, lora, ids = make()
    cfg = dataclasses.asdict(c)
    (loss, (logits, aux)), grads = jax.jit(jax.value_and_grad(
        lambda l: prog_loss(c, params, l, ids, remat=True),
        has_aux=True))(lora)
    first = 2 * (c.num_pairs - 1)
    h, state = ref.run_layers(cfg, params, ref.embed(cfg, params, ids[0]),
                              range(first), lora)
    g_ref = ref.tail_grad(cfg, params, h, state, ids[0], lora, first)
    h, _ = ref.run_layers(cfg, params, h, range(first, c.num_hidden_layers),
                          lora, state)
    return {"c": c, "cfg": cfg, "params": params, "lora": lora, "ids": ids,
            "loss": loss, "aux": aux, "logits": logits, "grads": grads,
            "want": ref.head(cfg, params, h), "g_ref": g_ref,
            "first": first}


def test_logits_and_loss_match_the_reference(tiny):
    want = tiny["want"]
    assert rel(tiny["logits"][0], want) < F32_TOL
    assert abs(float(tiny["loss"]) - float(ref.loss(
        want, tiny["ids"][0]))) < F32_TOL
    assert float(tiny["aux"]["counters"]["max"]["ssm_state_absmax"]) > 0


# the leaves that lie BELOW the mechanisms: layer L/2's in_proj (through
# its own scan and the GMUs' memory), layer L/2 + 1's k_proj and v_proj
# (through every long differential-attention layer that reads them), and
# the self-decoder's last Mamba layer's in_proj (through its scan)
LEAVES = {"in_proj.B@memory": ("in_proj", "P"),
          "k_proj.B@full": ("k_proj", "P"),
          "v_proj.B@full": ("v_proj", "P"),
          "in_proj.B@self": ("in_proj", "P-1")}


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_adapter_gradients_below_the_mechanisms_match_the_reference(
        tiny, leaf):
    target, which = LEAVES[leaf]
    P = tiny["c"].num_pairs
    at = P if which == "P" else P - 1
    got = tiny["grads"]["blocks"][target]["B"][at]
    # the reference's stacks start at the tail's first layer of the kind
    lo = ref.stack_position(tiny["cfg"], target, next(
        l for l in range(tiny["first"], tiny["c"].num_hidden_layers)
        if ref.stack_position(tiny["cfg"], target, l) is not None))
    want = tiny["g_ref"][target]["B"][at - lo]
    assert float(np.abs(want).max()) > 0
    assert rel(got, want) < F32_TOL
    assert cos(got, want) > GRAD_COS


def test_the_stack_positions_agree_with_the_reference():
    c = Phi4FlashConfig()
    cfg = dataclasses.asdict(c)
    ids = phi4flash.stack_layer_ids(c)
    assert {t: len(v) for t, v in ids.items()} == phi4flash.stack_sizes(c)
    assert phi4flash.stack_sizes(c) == {
        "q_proj": 16, "o_proj": 16, "k_proj": 9, "v_proj": 9,
        "in_proj": 9, "out_proj": 9, "gmu_in_proj": 7, "gmu_out_proj": 7}
    for t, layers in ids.items():
        assert [ref.stack_position(cfg, t, l) for l in layers] == list(
            range(len(layers)))
    assert ids["in_proj"][-1] == 16 and ids["k_proj"][-1] == 17


def test_lambda_zero_is_plain_attention_with_the_head_norm():
    """With lambda_q2 = lambda_k2 = 0 and lambda_q1 . lambda_k1 = log(1 -
    lambda_init), lambda = 0: the layer is (1 - lambda_init) RMSNorm(the
    first map's attention) per head pair."""
    c, params, _, _ = make()
    B, H = 2, c.hidden_size
    hd, layer = c.head_dim, 3
    p = jax.tree.map(lambda a: a[1], params["self"]["attn"]["mixer"])
    lam0 = float(phi4flash.lambda_init(layer))
    e0 = jnp.zeros((hd,)).at[0].set(1.0)
    p = dict(p, lq1=np.log(1 - lam0) * e0, lk1=e0, lq2=0 * e0, lk2=0 * e0)
    h = jax.random.normal(jax.random.PRNGKey(5), (B, S, H))
    lo = lambda y, x, name: y
    out, (k1, k2, v) = jax.jit(lambda p, h: phi4flash._diff_attn(
        c, p, h, lo, jnp.float32, layer, None, None, None))(p, h)
    q1, _ = phi4flash._pairs(h @ p["q_w"], c.num_attention_heads)
    a1 = dot_product_attention(q1, k1, v, scale=hd ** -0.5)
    o = phi4flash.rms_norm(a1, p["subln"], phi4flash.DIFF_NORM_EPS) \
        * (1 - lam0)
    want = o.transpose(0, 2, 1, 3).reshape(B, S, -1) @ p["o_w"]
    assert rel(out, want) < 1e-5


def test_a_window_of_512_in_the_kernels_is_the_dense_mask():
    """The flash kernels (interpret mode) at the cell's window, head 64
    against a value of 128 and a group of 2, over two windows of keys:
    a query sees itself and the 511 keys before it."""
    k = jax.random.split(jax.random.PRNGKey(6), 3)
    Sw = 1024
    q = jax.random.normal(k[0], (1, 2, Sw, 64))
    kk = jax.random.normal(k[1], (1, 1, Sw, 64))
    v = jax.random.normal(k[2], (1, 1, Sw, 128))
    got = attention(q, kk, v, impl="flash", is_causal=True,
                    sliding_window=512)
    i, j = np.arange(Sw)[:, None], np.arange(Sw)[None, :]
    dense = jnp.asarray((j <= i) & (j > i - 512))
    want = dot_product_attention(q, kk, v, attn_mask=dense)
    assert rel(got, want) < 1e-5
    # the boundary itself: key i - 512 is out, i - 511 in
    assert not bool(dense[600, 88]) and bool(dense[600, 89])


def test_the_cross_layers_read_the_full_layer_s_keys_and_values(tiny):
    """The program agrees with the reference, whose cross layers read
    layer L/2 + 1's keys and values; handing them the last window layer's
    instead moves the logits by far more than rounding; a cross layer
    holds no key or value projection of its own."""
    cfg, params, lora, ids = (tiny["cfg"], tiny["params"], tiny["lora"],
                              tiny["ids"][0])
    L, mid = tiny["c"].num_hidden_layers, tiny["c"].num_hidden_layers // 2
    assert "k_w" not in params["cross"]["attn"]["mixer"]
    assert "v_w" not in params["cross"]["attn"]["mixer"]
    h, state = ref.run_layers(cfg, params, ref.embed(cfg, params, ids),
                              range(mid - 1), lora)
    # the last window layer's keys and values, as its own attention read
    lp = ref.to_f32(ref.layer_weights(cfg, params, mid - 1))
    lo = ref.lora_layer(cfg, lora, mid - 1).get
    a = ref.layer_norm(h, lp["ln1_w"], lp["ln1_b"], cfg["layer_norm_eps"])
    _, kv_swa = ref.diff_attention(cfg, lp["mixer"], a, lo, mid - 1, "swa")
    h, state = ref.run_layers(cfg, params, h, range(mid - 1, mid + 2),
                              lora, state)
    right, _ = ref.run_layers(cfg, params, h, range(mid + 2, L), lora,
                              state)
    wrong, _ = ref.run_layers(cfg, params, h, range(mid + 2, L), lora,
                              {**state, "kv": kv_swa})
    right, wrong = (ref.head(cfg, params, x) for x in (right, wrong))
    assert rel(tiny["want"], right) < F32_TOL
    assert rel(wrong, right) > 100 * F32_TOL


# every family on cli/expert_lora's entry point at its benchmark cell's
# shape: the families with heads of 16k to 25k rows keep 8 chunks (their
# lowered steps unchanged), the 200,064-row tied head at 16,384 tokens
# takes 32 (410 MB of float32 logits a chunk, not 1.64 GB)
CHUNKS = {"trinity_lora_s8192": 8, "ling_lora_long": 8,
          "xing_lora_long": 8, "phi4flash_lora_long": 32}


@pytest.mark.parametrize("cell", sorted(CHUNKS))
def test_the_loss_s_chunks_follow_the_vocabulary(cell):
    c = bench_common.load_cell(cell)
    argv = c["traffic_file"]["argv"]
    B, S = (int(argv_value(argv, f)) for f in ("--batch_size",
                                               "--seq_len"))
    assert loss_chunks(B, S, c["config_file"]["vocab_size"]) == CHUNKS[cell]


def test_unpublished_forms_are_refused():
    for over in ({"mb_per_layer": 3}, {"num_hidden_layers": 30},
                 {"tie_word_embeddings": False}, {"num_key_value_heads": 3}):
        with pytest.raises(ValueError, match="published SambaY form"):
            dataclasses.replace(Phi4FlashConfig.tiny(), **over)
    with pytest.raises(ValueError, match="no experts"):
        Phi4FlashConfig.tiny().with_expert_parallel(2, 0)


def test_the_loader_keeps_bf16_and_the_adapter_round_trips_through_peft(
        tmp_path):
    from fixtures import write_tiny_phi4flash_dir
    from mobilefinetuner_tpu.io.checkpoints import load_phi4flash
    from mobilefinetuner_tpu.io.safetensors_io import SafeTensorsReader
    from mobilefinetuner_tpu.lora import peft_io
    c, params = write_tiny_phi4flash_dir(str(tmp_path / "m"),
                                         dtype=jnp.bfloat16)
    _, got = load_phi4flash(str(tmp_path / "m"), config=c)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    mm = got["self"]["mamba"]["mixer"]
    assert mm["in_w"].dtype.name == "bfloat16"
    assert mm["A_log"].dtype == np.float32
    assert mm["conv_w"].shape == (c.num_pairs, c.mamba_d_conv, c.ssm_inner)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    _, _, lora, _ = make()
    spec = LoRASpec(rank=4, alpha=8.0, init="peft",
                    targets=sorted(PHI4FLASH_TARGETS))
    ids = phi4flash.stack_layer_ids(c)
    peft_io.export_peft(str(tmp_path / "p"), lora, spec, "phi4flash",
                        layer_ids=ids)
    keys = SafeTensorsReader(
        str(tmp_path / "p" / "adapter_model.safetensors")).keys()
    mid = c.num_hidden_layers // 2
    assert (f"base_model.model.model.layers.{mid}.attn.in_proj"
            ".lora_B.weight") in keys
    assert (f"base_model.model.model.layers.{mid + 2}.attn.in_proj"
            ".lora_A.weight") in keys          # the GMU's
    back, _ = peft_io.import_peft(str(tmp_path / "p"), "phi4flash",
                                  layer_ids=ids)
    for t, e in lora["blocks"].items():
        np.testing.assert_array_equal(back["blocks"][t]["B"], e["B"])


def test_the_cli_trains_through_run_training(tmp_path):
    from fixtures import write_tiny_phi4flash_dir, write_wikitext_dir
    from mobilefinetuner_tpu.cli import train_lora_phi4flash
    write_tiny_phi4flash_dir(str(tmp_path / "m"))
    write_wikitext_dir(str(tmp_path / "data"))
    tel = tmp_path / "tel.jsonl"
    rc = train_lora_phi4flash.main([
        "--pretrained_dir", str(tmp_path / "m"),
        "--data_dir", str(tmp_path / "data"), "--steps", "4",
        "--batch_size", "2", "--seq_len", "64", "--dtype", "float32",
        "--remat", "--lr", "1e-3", "--lora_targets",
        ",".join(sorted(PHI4FLASH_TARGETS)),
        "--log_interval", "1", "--telemetry_out", str(tel),
        "--lora_out", str(tmp_path / "out.safetensors"),
        "--peft_export_dir", str(tmp_path / "peft")])
    assert rc == 0
    recs = [json.loads(l) for l in open(tel)]
    start = next(r for r in recs if r["event"] == "run_start")["config"]
    assert start["attention_impl_resolved"] == "xla"     # S = 64 here
    assert (start["layers_mamba"], start["layers_swa"], start["layers_full"],
            start["layers_gmu"], start["layers_cross"]) == (3, 2, 1, 1, 1)
    assert (start["ssm_chunk"], start["ssm_state"], start["ssm_inner"]) == (
        8, 16, 128)
    flushes = [r for r in recs if r["event"] == "step_stats"]
    assert len(flushes) == 4 and flushes[-1]["loss"] < flushes[0]["loss"]
    for f in flushes:
        assert 0 < f["ssm_state_absmax"] < 1e3
    assert os.path.exists(tmp_path / "peft" / "adapter_model.safetensors")
