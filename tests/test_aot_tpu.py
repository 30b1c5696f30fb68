"""Compile for a DESCRIBED TPU v5e — no chip attached, nothing runs.

Interpret mode (every other test in this tree) cannot see what the TPU's
compiler refuses: a block shape the Pallas lowering will not tile, more
VMEM than a kernel may use, a program that does not fit HBM. The compiler
is installed here and compiles for a topology that is described and not
attached (`topologies.get_topology_desc`), so these tests hand it the
kernels of the main path at real widths and assert the kernel is IN the
executable (`tpu_custom_call`). A compile that passes is not a chip run:
it says nothing about results or times — chip_smoke.py is the chip run.

The tests steer the `auto` rules themselves (they patch
`jax.default_backend` to say "tpu", which is all `interpret_mode`, the
paged-decode rule, the LoRA rule and the serve engine's donation consult);
the program has no option for it. The persistent compile cache is turned
off around the compiles: an entry written for a described chip cannot be
read back without one.

Tier-1 holds the kernel-only cases (well under a second each). The
whole-program compiles — the GPT-2-small and gpt2-xl train steps, the
serve engine's prefill / decode-step / prefill-chunk programs, fused CE at
a 262k vocab — are marked `slow`: the builder runs them before spending
chip time (`pytest tests/test_aot_tpu.py -m slow`).
"""

from __future__ import annotations

import dataclasses
import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import NamedSharding, SingleDeviceSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from mobilefinetuner_tpu.core.config import (  # noqa: E402
    Gemma3TextConfig, GPT2Config)
from mobilefinetuner_tpu.core.xla_stats import (  # noqa: E402
    compiled_peak_bytes, device_ops_from, sized_copies, tpu_custom_calls)
from mobilefinetuner_tpu.ops import decode_attention as da  # noqa: E402
from mobilefinetuner_tpu.serve import paged_kv  # noqa: E402

BF16 = jnp.bfloat16
V5E_HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    """One described v5e 2x2 host for the whole module, with the
    persistent compile cache off around everything compiled for it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Every `auto` rule and interpret_mode() answer as on a TPU, and
    matmuls at the precision the program runs with there (conftest pins
    "highest" for CPU parity; Mosaic has no fp32-contract bf16 matmul)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.default_matmul_precision("default"):
        yield


def on_chip0(topo, tree):
    """Shapes of `tree` (arrays or ShapeDtypeStructs) placed on the
    described chip 0 — there is no device to hold an array."""
    sh = SingleDeviceSharding(topo.devices[0])
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sh),
        tree)


def sds(topo, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(
        topo.devices[0]))


def compile_for(fn, *shapes, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*shapes).compile()


def assert_kernel(compiled, at_least: int = 1):
    n = tpu_custom_calls(compiled.as_text())
    assert n >= at_least, f"{n} tpu_custom_call(s) in the executable"
    return n


def assert_fits(compiled):
    """Under a v5e chip's HBM with the admission preflight's headroom."""
    gib = compiled_peak_bytes(compiled) / 2 ** 30
    assert gib < 0.9 * V5E_HBM / 2 ** 30, f"{gib:.1f} GiB on a 16 GiB chip"


# --------------------------- flash attention --------------------------------

FLASH_SHAPES = [(4, 12, 1024, 64), (2, 25, 1024, 64)]   # gpt2-small, gpt2-xl


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_forward_compiles(topo, as_tpu, shape):
    from mobilefinetuner_tpu.ops.flash_attention import flash_attention
    q = sds(topo, shape, BF16)
    assert_kernel(compile_for(
        lambda q, k, v: flash_attention(q, k, v, is_causal=True), q, q, q))


@pytest.mark.parametrize("bwd_impl", ["merged", "split"])
@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_backward_compiles(topo, as_tpu, shape, bwd_impl):
    from mobilefinetuner_tpu.ops.flash_attention import flash_attention
    q = sds(topo, shape, BF16)

    def loss(q, k, v):
        return flash_attention(q, k, v, is_causal=True,
                               bwd_impl=bwd_impl).astype(jnp.float32).sum()

    # forward + the merged one-pass backward = 2 kernels; the split
    # pair's dK/dV and dQ passes make it 3
    n = assert_kernel(compile_for(jax.grad(loss, argnums=(0, 1, 2)),
                                  q, q, q), at_least=2)
    assert n == (2 if bwd_impl == "merged" else 3)


# --------------------------- AFMoE at S = 8192 -------------------------------

# trinity_lora_s8192's attention: head size 128, GQA 48/8, with and
# without the 4,096 window. From S = 4096 on the backward kernels hold
# more whole-S operands than the default 16 MiB of scoped VMEM
# (flash_attention.merged_bwd_fits): both implementations ask for more.
AFMOE_FLASH = (1, 48, 8, 8192, 128)


@pytest.mark.parametrize("data,fsdp", [(1, 4), (2, 2)])
def test_flash_over_the_batch_axes_compiles_on_four_chips(topo, as_tpu,
                                                          data, fsdp):
    """The training mesh's attention (ops/attention.attention with the
    mesh): the forward and the merged backward under shard_map over
    ("data", "fsdp"), at the FSDP cell's shape — 32 sequences of gpt2-xl
    over four described chips. Outside shard_map the TPU lowering refuses
    the kernel in a partitioned program."""
    from mobilefinetuner_tpu.ops.attention import attention
    from mobilefinetuner_tpu.parallel.mesh import batch_sharding, make_mesh
    mesh = make_mesh(data=data, fsdp=fsdp, devices=topo.devices)
    x = jax.ShapeDtypeStruct((32, 25, 1024, 64), BF16,
                             sharding=batch_sharding(mesh))
    pad = jax.ShapeDtypeStruct((32, 1024), jnp.float32,
                               sharding=batch_sharding(mesh))

    def grads(mesh):
        return jax.grad(lambda q, k, v, pad: attention(
            q, k, v, impl="auto", mesh=mesh, padding_mask=pad).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))

    assert_kernel(compile_for(grads(mesh), x, x, x, pad), at_least=2)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(grads(None)).lower(x, x, x, pad)


def full_ft_fsdp_step(topo, cfg: GPT2Config, B: int, S: int, data: int,
                      fsdp: int):
    """The full-FT optimizer step as cli/gpt2_full_finetune builds it over
    a (data, fsdp) mesh of the described chips: every parameter trainable
    and FSDP-sharded by the CLI's rule, Adam's state on the params'
    shardings, the batch over both axes, bf16, remat, `auto` attention.
    Compiled; and its parameter shapes."""
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
    from mobilefinetuner_tpu.parallel.mesh import (batch_sharding,
                                                   make_mesh,
                                                   params_shardings,
                                                   replicated_sharding)
    from mobilefinetuner_tpu.train.trainer import (TrainConfig,
                                                   init_optimizer,
                                                   make_train_step)
    mesh = make_mesh(data=data, fsdp=fsdp, devices=topo.devices)
    repl = replicated_sharding(mesh)
    tc = TrainConfig(total_steps=1000, schedule="constant")
    params = jax.eval_shape(
        lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(lambda p: init_optimizer(p, tc, None), params)

    def loss_fn(params_t, _unused, mb):
        logits = gpt2.forward(cfg, params_t, mb["input_ids"],
                              attention_mask=mb["attention_mask"],
                              compute_dtype=BF16, remat=True, mesh=mesh)
        return lm_cross_entropy_sum(logits, mb["labels"])

    psh, osh = params_shardings(params, mesh), params_shardings(opt, mesh)
    batch = {"input_ids": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "attention_mask": jax.ShapeDtypeStruct((B, S), jnp.float32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    step = make_train_step(loss_fn, tc, mask=None, donate=True,
                           out_shardings=(psh, osh, repl))
    return step.lower(
        place(params, psh), None, place(opt, osh),
        place(batch, jax.tree.map(lambda _: batch_sharding(mesh), batch)),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=repl)).compile()


def assert_layer_gather(compiled) -> dict:
    """The layer scan of an FSDP full-FT step (the ops under the block's
    `attention` / `mlp` scopes): weights gathered, no activation moved
    between layouts, and every gradient sent back to its shard by a
    reduce-scatter — the TPU's reduce-scatter fusion — in float32."""
    from mobilefinetuner_tpu.core.static_checks import (
        hlo_collective_census, hlo_named_scopes)
    text = compiled.as_text()
    census = hlo_collective_census(text, scopes=("attention", "mlp"))
    assert census["all-to-all"] == 0, census
    assert census["all-gather"] > 0 and census["reduce-scatter"] > 0, census
    for line in text.splitlines():
        if "calls=%all-reduce-scatter" in line and \
                {"attention", "mlp"} & hlo_named_scopes(line):
            out_types = line.split("=", 1)[1].split(" fusion(")[0]
            assert "f32[" in out_types and "bf16[" not in out_types, line
    return census


@pytest.mark.parametrize("data,fsdp", [(1, 4), (2, 2)])
def test_the_layer_gather_compiles_on_four_chips(topo, as_tpu, data, fsdp):
    """The full-FT step of a small GPT-2 at S = 1024 over four described
    chips (flash under shard_map, as in the cell): the layer gather's
    collectives and nothing else inside the scan."""
    cfg = dataclasses.replace(GPT2Config.tiny(vocab_size=512), n_embd=256,
                              n_head=4, n_layer=2, n_positions=1024,
                              embd_pdrop=0.0, resid_pdrop=0.0,
                              attn_pdrop=0.0)
    compiled = full_ft_fsdp_step(topo, cfg, 8, 1024, data, fsdp)
    assert_kernel(compiled, at_least=3)
    assert_layer_gather(compiled)


@pytest.mark.parametrize("bwd_impl", ["merged", "split"])
@pytest.mark.parametrize("window", [None, 4096])
def test_flash_compiles_at_the_expert_cell_s_shapes(topo, as_tpu, window,
                                                    bwd_impl):
    from mobilefinetuner_tpu.ops.flash_attention import (flash_attention,
                                                         resolve_bwd_impl)
    B, nq, nkv, S, D = AFMOE_FLASH
    q, kv = sds(topo, (B, nq, S, D), BF16), sds(topo, (B, nkv, S, D), BF16)
    assert resolve_bwd_impl(S, D, 512, 2) == "merged"

    def loss(q, k, v):
        return flash_attention(q, k, v, is_causal=True,
                               sliding_window=window, bwd_impl=bwd_impl
                               ).astype(jnp.float32).sum()

    c = compile_for(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert_kernel(c, 2 if bwd_impl == "merged" else 3)


def test_grouped_product_compiles_and_skips_the_absent_tail(topo, as_tpu):
    """The expert layer's three grouped products at the published widths
    over the whole [4, 32, ...] stack: Mosaic kernels, forward and the
    backward to x (no product for the frozen experts' own gradient), and
    no copy of a layer's experts out of the stack."""
    from mobilefinetuner_tpu.ops import moe
    T, k, H, I, L, n = 8192, 4, 3072, 3072, 4, 32
    ex = {"gate_w": sds(topo, (L, n, H, I), BF16),
          "up_w": sds(topo, (L, n, H, I), BF16),
          "down_w": sds(topo, (L, n, I, H), BF16)}

    def f(m, idx, w, ex, layer):
        return moe.expert_layer(m, idx, w, ex, (0, n), layer)[0] \
            .astype(jnp.float32).sum()

    c = compile_for(jax.grad(f, argnums=(0, 2)), sds(topo, (T, H), BF16),
                    sds(topo, (T, k), jnp.int32),
                    sds(topo, (T, k), jnp.float32), ex,
                    sds(topo, (), jnp.int32))
    assert assert_kernel(c, 6) == 6      # 3 forward + 3 to x, no tgmm
    assert not sized_copies(c, n * H * I)


@pytest.mark.slow
def test_the_expert_cell_s_train_step_compiles_and_fits(topo, as_tpu):
    """The LoRA step of trinity_lora_s8192 as benchmark/tools/size_afmoe.py
    builds it, at the cell's batch size: it compiles for a v5e, holds the
    flash and grouped-product kernels, and stays within 0.85 of
    bytes_limit."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path[:0] = [bench, os.path.join(bench, "tools")]
    import size_afmoe
    from harness import common
    from harness.traffic import argv_value
    B = int(argv_value(common.load_cell("trinity_lora_s8192")
                       ["traffic_file"]["argv"], "--batch_size"))
    c = size_afmoe.lower_step(B, topo.devices[0]).compile()
    assert_kernel(c, 30)
    assert compiled_peak_bytes(c) <= 0.85 * size_afmoe.BYTES_LIMIT


# the Ling hybrid's cell (ling_lora_long): latent attention at S = 16,384,
# q and k zero-padded 192 -> 256, v at 128: whole-S K and V are 24 MiB
# of VMEM (the forward and dQ kernels ask for it), the merged backward's
# operands 88 MiB as laid out, so `auto` takes the split pair
LING_FLASH = (1, 32, 16384, 192, 128)


def test_flash_compiles_at_two_head_sizes_at_the_latent_cell_s_length(
        topo, as_tpu):
    from mobilefinetuner_tpu.ops.attention import attention, resolve_impl
    from mobilefinetuner_tpu.ops.flash_attention import resolve_bwd_impl
    B, n, S, dq, dv = LING_FLASH
    qk, v = sds(topo, (B, n, S, dq), BF16), sds(topo, (B, n, S, dv), BF16)
    assert resolve_impl(S, dq) == "flash"
    assert resolve_bwd_impl(S, 256, 512, 2, dv) == "split"

    def loss(q, k, v):
        return attention(q, k, v, impl="auto", is_causal=True,
                         scale=dq ** -0.5).astype(jnp.float32).sum()

    c = compile_for(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    assert assert_kernel(c, 3) == 3         # forward, dQ, dK/dV


KDA_CELL = (1, 32, 16384, 128)


def kda_grad_for(topo, impl):
    """Forward + backward of the chunked delta rule at the cell's shapes
    (bf16 q, k, v; float32 gate and beta), compiled for the described
    chip."""
    from mobilefinetuner_tpu.ops import kda
    B, n, S, d = KDA_CELL
    x = sds(topo, (B, n, S, d), BF16)
    g = sds(topo, (B, n, S, d), jnp.float32)
    beta = sds(topo, (B, n, S), jnp.float32)

    def loss(q, k, v, g, beta):
        return kda.kda_chunked(q, k, v, g, beta, impl=impl) \
            .astype(jnp.float32).sum()

    return compile_for(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), x, x, x, g,
                       beta)


def test_the_chunked_delta_rule_compiles_at_the_cell_s_shapes(topo,
                                                              as_tpu):
    """ops/kda.py's XLA form, forward and its backward (the vjp of a
    block under a reverse scan), at 32 heads of 128 over 16,384 tokens:
    XLA ops only (no Mosaic kernel), and the blocks' intermediates do
    not outlive their block — the whole program stays under 3 GiB
    around 0.9 GiB of operands."""
    c = kda_grad_for(topo, "chunked")
    assert tpu_custom_calls(c.as_text()) == 0
    assert compiled_peak_bytes(c) < 3 * 2 ** 30


def test_the_delta_rule_kernel_compiles_at_the_cell_s_shapes(topo, as_tpu):
    """ops/kda_pallas.py at the same shapes, where the shape rule takes
    it: the program is TWO Mosaic calls — `kda_fwd` and `kda_bwd` — at
    float32 contract precision inside (Mosaic takes `Precision.HIGHEST`
    on float32 operands; it has no such product for bf16 ones), the
    forward within the default scoped VMEM and the backward within the
    32 MiB it asks for, and under the same 3 GiB of HBM: operands, o,
    the 128 MiB of block-entry states and the five cotangents."""
    from mobilefinetuner_tpu.ops import kda
    B, n, S, d = KDA_CELL
    assert kda.resolve_impl(S, 64, d, d) == "kernel"
    c = kda_grad_for(topo, "auto")
    text = c.as_text()
    assert assert_kernel(c, 2) == 2
    assert "kda_fwd" in text and "kda_bwd" in text
    assert compiled_peak_bytes(c) < 3 * 2 ** 30


def test_the_delta_rule_kernel_reads_the_interpret_policy_at_every_trace(
        topo, monkeypatch):
    """The kernels' calls are jitted (the layers share one trace), with
    the interpret policy a static argument read by their caller: a shape
    first traced interpreted, as the CPU tests trace it, is lowered to
    the two Mosaic calls when the same process then describes a chip."""
    from mobilefinetuner_tpu.ops import kda
    shapes = [jax.ShapeDtypeStruct((1, 2, 128, 128), jnp.float32)] * 4 \
        + [jax.ShapeDtypeStruct((1, 2, 128), jnp.float32)]

    def loss(*a):
        return kda.kda_chunked(*a, impl="kernel").sum()

    def mosaic_calls(xs):        # in the lowered (StableHLO) text
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *xs).as_text().count("custom_call @tpu_custom_call")

    with jax.default_matmul_precision("default"):
        assert mosaic_calls(shapes) == 0
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert mosaic_calls(on_chip0(topo, shapes)) == 2


def test_grouped_product_compiles_at_the_ling_cell_s_widths(topo, as_tpu):
    """K 2560 / N 768 and back over 64 held experts, 16,384 tokens top-8:
    the tiles ops/moe.gmm_tiling derives from the shape."""
    from mobilefinetuner_tpu.ops import moe
    T, k, H, I, n = 16384, 8, 2560, 768, 64
    ex = {"gate_w": sds(topo, (n, H, I), BF16),
          "up_w": sds(topo, (n, H, I), BF16),
          "down_w": sds(topo, (n, I, H), BF16)}

    def f(m, idx, w, ex):
        return moe.expert_layer(m, idx, w, ex, (0, n))[0] \
            .astype(jnp.float32).sum()

    c = compile_for(jax.grad(f, argnums=(0, 2)), sds(topo, (T, H), BF16),
                    sds(topo, (T, k), jnp.int32),
                    sds(topo, (T, k), jnp.float32), ex)
    assert assert_kernel(c, 6) == 6


@pytest.mark.slow
def test_the_ling_cell_s_train_step_compiles_and_fits(topo, as_tpu):
    """The LoRA step of ling_lora_long as benchmark/tools/size_ling.py
    builds it, at the cell's length and batch size: it compiles for a
    v5e, holds the flash (forward twice under remat, the split pair) and
    grouped-product kernels, and stays within 0.85 of bytes_limit."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path[:0] = [bench, os.path.join(bench, "tools")]
    import size_ling
    from harness import common
    from harness.traffic import argv_value
    B = int(argv_value(common.load_cell("ling_lora_long")
                       ["traffic_file"]["argv"], "--batch_size"))
    c = size_ling.lower_step(B, topo.devices[0]).compile()
    assert_kernel(c, 4 + 6 * 9)
    assert compiled_peak_bytes(c) <= 0.85 * size_ling.BYTES_LIMIT


# Xing4.0's cell (xing_lora_long): latent attention at S = 8,192 in
# every block, q and k zero-padded 192 -> 256, v at 128: whole-S K and V
# are 12 MiB of VMEM TO THE BYTE, which the default limit refuses (PR 33:
# `_kv_vmem_limit` now asks for it from 12 MiB on, not above), the merged
# backward's operands as laid out are over its budget, so `auto` takes
# the split pair
XING4_FLASH = (1, 32, 8192, 192, 128)


def test_flash_compiles_at_two_head_sizes_at_the_xing4_cell_s_length(
        topo, as_tpu):
    from mobilefinetuner_tpu.ops.attention import attention, resolve_impl
    from mobilefinetuner_tpu.ops.flash_attention import (_kv_vmem_limit,
                                                         resolve_bwd_impl)
    B, n, S, dq, dv = XING4_FLASH
    qk, v = sds(topo, (B, n, S, dq), BF16), sds(topo, (B, n, S, dv), BF16)
    assert resolve_impl(S, dq) == "flash"
    assert _kv_vmem_limit(S, 256, dv, 2) == 24 * 2 ** 20
    assert _kv_vmem_limit(S, 128, 128, 2) is None       # Trinity's shape
    bwd = resolve_bwd_impl(S, 256, 512, 2, dv)

    def loss(q, k, v):
        return attention(q, k, v, impl="auto", is_causal=True,
                         scale=0.1446796).astype(jnp.float32).sum()

    c = compile_for(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    assert assert_kernel(c, 2 if bwd == "merged" else 3) >= 2


def test_the_stream_s_mixes_compile_at_the_xing4_cell_s_shapes(topo,
                                                              as_tpu):
    """ops/hyper_connections.py forward and backward on a four-row bf16
    stream of 8,192 tokens at width 3584: XLA ops only, the token axis
    minor in every coefficient, and the program stays under 3 GiB around
    0.5 GiB of operands (no [T, 4, 4] tile-padded temporaries)."""
    from mobilefinetuner_tpu.ops import hyper_connections as hc
    n, T, C = 4, 8192, 3584
    x = sds(topo, (n, 1, T, C), BF16)
    hcp = {"phi": sds(topo, (n * C, 24), BF16),
           "b": sds(topo, (24,), jnp.float32),
           "alpha": sds(topo, (3,), jnp.float32)}

    def loss(x, hcp):
        pre, post, res, err = hc.coefficients(
            x, hcp, iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)
        u = hc.pre_mix(x, pre)
        return hc.post_mix(x, u * 2, post, res).astype(jnp.float32).sum() \
            + err

    c = compile_for(jax.grad(loss), x, hcp)
    assert tpu_custom_calls(c.as_text()) == 0
    assert compiled_peak_bytes(c) < 3 * 2 ** 30


def test_grouped_product_compiles_at_the_xing4_cell_s_widths(topo, as_tpu):
    """K 3584 / N 1024 and back over 64 held experts, 8,192 tokens top-4:
    the tiles ops/moe.gmm_tiling derives from the shape (K over 3072: its
    largest 128-multiple divisor, 1792)."""
    from mobilefinetuner_tpu.ops import moe
    T, k, H, I, n = 8192, 4, 3584, 1024, 64
    assert moe.gmm_tiling(H, I) == (128, 1792, 1024)
    assert moe.gmm_tiling(I, H) == (128, 1024, 512)
    ex = {"gate_w": sds(topo, (n, H, I), BF16),
          "up_w": sds(topo, (n, H, I), BF16),
          "down_w": sds(topo, (n, I, H), BF16)}

    def f(m, idx, w, ex):
        return moe.expert_layer(m, idx, w, ex, (0, n))[0] \
            .astype(jnp.float32).sum()

    c = compile_for(jax.grad(f, argnums=(0, 2)), sds(topo, (T, H), BF16),
                    sds(topo, (T, k), jnp.int32),
                    sds(topo, (T, k), jnp.float32), ex)
    assert assert_kernel(c, 6) == 6


@pytest.mark.slow
def test_the_xing4_cell_s_train_step_compiles_and_fits(topo, as_tpu):
    """The LoRA step of xing_lora_long as benchmark/tools/size_xing4.py
    builds it, at the cell's length and batch size: it compiles for a
    v5e, holds the flash and grouped-product kernels of six blocks, and
    stays within 0.85 of bytes_limit."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path[:0] = [bench, os.path.join(bench, "tools")]
    import size_xing4
    from harness import common
    from harness.traffic import argv_value
    B = int(argv_value(common.load_cell("xing_lora_long")
                       ["traffic_file"]["argv"], "--batch_size"))
    c = size_xing4.lower_step(B, topo.devices[0]).compile()
    assert tpu_custom_calls(c.as_text()) == 111
    assert compiled_peak_bytes(c) <= 0.85 * size_xing4.BYTES_LIMIT


# Phi-4-mini-flash's cell (phi4flash_lora_long): differential attention
# is two flash calls a layer at head 64 against a value of 128, 20 query
# heads over 10 key heads (a group of 2), 16,384 tokens, over a window of
# 512 or the whole causal prefix
PHI4FLASH_FLASH = (1, 20, 10, 16384, 64, 128)


@pytest.mark.parametrize("window", [512, None])
def test_flash_compiles_for_differential_attention_at_the_phi4flash_cell(
        topo, as_tpu, window):
    from mobilefinetuner_tpu.ops.attention import attention, resolve_impl
    B, nq, nkv, S, d, dv = PHI4FLASH_FLASH
    q, k = sds(topo, (B, nq, S, d), BF16), sds(topo, (B, nkv, S, d), BF16)
    v = sds(topo, (B, nkv, S, dv), BF16)
    assert resolve_impl(S, d) == "flash"

    def loss(q, k, v):
        return attention(q, k, v, impl="auto", is_causal=True,
                         sliding_window=window, scale=d ** -0.5).astype(
            jnp.float32).sum()

    c = compile_for(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert assert_kernel(c, 2) >= 2


@pytest.mark.slow
def test_the_phi4flash_cell_s_train_step_compiles_and_fits(topo, as_tpu):
    """The LoRA step of phi4flash_lora_long as
    benchmark/tools/size_phi4flash.py builds it, at the cell's length and
    batch size: it compiles for a v5e, holds the flash kernels of both
    maps of all 16 attention layers and the LoRA epilogues, and stays
    within 0.85 of bytes_limit."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path[:0] = [bench, os.path.join(bench, "tools")]
    import size_phi4flash
    from harness import common
    from harness.traffic import argv_value
    B = int(argv_value(common.load_cell("phi4flash_lora_long")
                       ["traffic_file"]["argv"], "--batch_size"))
    c = size_phi4flash.lower_step(B, topo.devices[0]).compile()
    assert_kernel(c, 3 * 2 * 3)        # fwd, recomputed fwd, bwd: 3 calls
    assert compiled_peak_bytes(c) <= 0.85 * size_phi4flash.BYTES_LIMIT


# --------------------------- paged decode attention -------------------------

# (S, KV, G, D, bT, M, NB, L) and the pages a grid step folds there
# (pick_pages): md_batchgen_long's engine (gpt2-medium, 16 slots, tables
# of 64 pages of 16 tokens: 16 pages = 256 columns a step); the smoke's
# engine (build_engine("gpt2s"), 8 slots, block_T 16, 256 blocks, prompt
# 64 + 32 new -> 6 pages a slot); a 128-token page; gpt2-xl's 25 KV
# heads, whose row of 1600 lanes fills no whole number of lane tiles,
# so the pipeline brings it a page a step; Gemma's GQA heads; and the
# two sides of the chunk's VMEM term — 36 and 40 heads of 128 at a
# 128-token page, where two pages a step need 11.4 and 12.9 MB of the
# 12 MB budget
PAGED_SHAPES = {
    "cell_md": ((16, 16, 1, 64, 16, 64, 1089, 24), 16),
    "smoke_engine": ((8, 12, 1, 64, 16, 6, 256, 12), 4),
    "bT128": ((8, 12, 1, 64, 128, 2, 64, 12), 2),
    "kv25": ((8, 25, 1, 64, 16, 6, 128, 4), 1),
    "gqa_d256": ((4, 1, 4, 256, 16, 6, 64, 2), 4),
    "vmem_under": ((4, 36, 1, 128, 128, 4, 16, 2), 2),
    "vmem_over": ((4, 40, 1, 128, 128, 4, 16, 2), 1),
}
# over the VMEM gate: the rule says no AND the compiler says no (a page
# is [bT, KV*D] with no lane padding, so it takes twice the tokens the
# head-major pages took to get there)
PAGED_REFUSED = {
    "kv25_bT2048": (8, 25, 1, 64, 2048, 2, 16, 2),
    "kv12_bT4096": (8, 12, 1, 64, 4096, 2, 16, 2),
}


def pool_sds(NB, L, KV, bT, D, sharding):
    """A pool as a shape: whatever serve/paged_kv.py says it is."""
    return jax.ShapeDtypeStruct(paged_kv.pool_shape(NB, L, KV, bT, D), BF16,
                                sharding=sharding)


def paged_operands(topo, shape, shard=lambda name: None):
    S, KV, G, D, bT, M, NB, L = shape
    one = SingleDeviceSharding(topo.devices[0])
    mk = lambda name, shp, dt: jax.ShapeDtypeStruct(
        shp, dt, sharding=shard(name) or one)
    pool = pool_sds(NB, L, KV, bT, D, shard("pool") or one)
    return (mk("q", (S, KV, G, D), BF16), pool, pool,
            mk("tbl", (S, M), jnp.int32),
            mk("ok", (S, M * bT), jnp.bool_))


@pytest.mark.parametrize("name", sorted(PAGED_SHAPES))
def test_paged_decode_compiles_where_eligible(topo, as_tpu, name):
    (S, KV, G, D, bT, M, NB, L), pages = shape, _ = PAGED_SHAPES[name]
    assert da.paged_eligible(KV, G, bT, D, 2)
    assert da.resolve_paged_impl("auto", KV, G, bT, D, 2) == "pallas"
    assert da.pick_pages(KV, G, bT, D, 2, M) == pages
    assert_kernel(compile_for(
        lambda q, pk, pv, tbl, ok: da.paged_decode_attention(
            q, pk, pv, tbl, L - 1, ok, D ** -0.5),
        *paged_operands(topo, shape)))


@pytest.mark.parametrize("name", sorted(PAGED_REFUSED))
def test_paged_rule_and_compiler_refuse_together(topo, as_tpu, name,
                                                 monkeypatch):
    """The eligibility rule and the compiler may not disagree: a shape
    the rule turns away is one Mosaic runs out of VMEM on — so `auto`
    never selects a kernel that cannot compile, and never gives up one
    that could (the eligible cases above are the other direction)."""
    S, KV, G, D, bT, M, NB, L = shape = PAGED_REFUSED[name]
    assert not da.paged_eligible(KV, G, bT, D, 2)
    assert da.resolve_paged_impl("auto", KV, G, bT, D, 2) == "xla"
    monkeypatch.setattr(da, "paged_eligible", lambda *a, **k: True)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|vmem"):
        compile_for(
            lambda q, pk, pv, tbl, ok: da.paged_decode_attention(
                q, pk, pv, tbl, 0, ok, D ** -0.5),
            *paged_operands(topo, shape))


@pytest.mark.parametrize("dp,tp", [(1, 4), (2, 2)])
def test_sharded_paged_attend_compiles_on_four_chips(topo, as_tpu, dp, tp):
    """The shard_map wrapper on a mesh built from the described devices:
    each shard's kernel gets KV/tp heads of its own pool slice, and the
    attention itself needs no collective. P follows the LOCAL row: 3
    heads of 64 (tp 4) fill no lane tile and go a page a step, 6 heads
    (tp 2) go 4 pages a step by the kernel's own copies."""
    from mobilefinetuner_tpu.serve.sharding import ServeSharding
    sh = ServeSharding.build("gpt2", GPT2Config.gpt2_small(), dp, tp,
                             devices=topo.devices)
    dpn = "dp" if dp > 1 else None
    specs = {"q": P(dpn, "tp", None, None),
             "pool": paged_kv.pool_partition_spec(True),
             "tbl": P(dpn, None), "ok": P(dpn, None)}
    ops = paged_operands(
        topo, PAGED_SHAPES["smoke_engine"][0],
        shard=lambda name: NamedSharding(sh.mesh, specs[name]))
    assert da.pick_pages(12, 1, 16, 64, 2, 6, tp) == {4: 1, 2: 4}[tp]
    attend = da.sharded_paged_attend(sh)
    compiled = compile_for(
        lambda q, pk, pv, tbl, ok: attend(q, pk, pv, tbl, 3, ok, 0.125),
        *ops)
    assert_kernel(compiled)
    text = compiled.as_text()
    assert not any(c in text for c in ("all-gather", "all-reduce",
                                       "all-to-all"))


# --------------------------- dense decode + LoRA epilogue -------------------

@pytest.mark.parametrize("T", [256, 1024])
def test_dense_decode_kernel_compiles(topo, as_tpu, T):
    B, KV, G, D = 8, 12, 1, 64
    assert da.decode_eligible(KV, T, D, 2, G)
    assert_kernel(compile_for(
        lambda q, k, v, ok: da.decode_attention(q, k, v, ok, D ** -0.5),
        sds(topo, (B, KV, G, D), BF16), sds(topo, (B, KV, T, D), BF16),
        sds(topo, (B, KV, T, D), BF16), sds(topo, (B, T), jnp.bool_)))


@pytest.mark.parametrize("N,d_out,r", [(8192, 2304, 8), (8192, 768, 8)])
def test_lora_epilogue_compiles(topo, as_tpu, N, d_out, r):
    """GPT-2 small's attn_qkv / attn_proj sites at B*S = 8192 tokens —
    the shapes `--lora_impl auto` resolves to fused on a TPU."""
    from mobilefinetuner_tpu.models.lora_apply import resolve_lora_impl
    from mobilefinetuner_tpu.ops.lora_fused import (lora_epilogue_add,
                                                    lora_epilogue_eligible)
    assert lora_epilogue_eligible(N, d_out, r, 2)
    assert resolve_lora_impl(N, 768, d_out, r, 2) == "fused"
    assert_kernel(compile_for(
        lora_epilogue_add, sds(topo, (N, d_out), BF16),
        sds(topo, (N, r), BF16), sds(topo, (r, d_out), BF16),
        sds(topo, (), jnp.float32)))


# --------------------------- the pool is never converted --------------------

def _gpt2_pool_case(cfg, S, NB, P):
    from mobilefinetuner_tpu.models import generate as g
    from mobilefinetuner_tpu.models import gpt2
    params = jax.eval_shape(
        lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    return dict(params=params, L=cfg.n_layer, KV=cfg.n_head,
                D=cfg.head_dim, S=S, NB=NB, P=P,
                step=functools.partial(g.gpt2_decode_step_paged, cfg,
                                       compute_dtype=BF16),
                chunk=functools.partial(g.gpt2_prefill_chunk, cfg,
                                        compute_dtype=BF16))


def _gemma_pool_case(cfg, S, NB, P):
    from mobilefinetuner_tpu.models import gemma3
    from mobilefinetuner_tpu.models import generate as g
    params = jax.eval_shape(
        lambda: gemma3.init_params(cfg, jax.random.PRNGKey(0)))
    return dict(params=params, L=cfg.num_hidden_layers,
                KV=cfg.num_key_value_heads, D=cfg.head_dim, S=S, NB=NB,
                P=P,
                step=functools.partial(g.gemma3_decode_step_paged, cfg,
                                       compute_dtype=BF16),
                chunk=functools.partial(g.gemma3_prefill_chunk, cfg,
                                        compute_dtype=BF16))


def test_sized_copies_sees_what_the_head_major_pool_compiled_to():
    """The witness below must not go blind: these are the three kinds of
    pool-sized instruction the head-major pool compiled to (the in-loop
    relayout, the entry copy out of the runtime's block-minor buffer,
    a copy of the pool as a bitcast flattened it), next to lines it has
    to leave alone."""
    class Text:
        def __init__(self, *lines):
            self.as_text = lambda: "\n".join(lines)
    n = 1089 * 24 * 16 * 16 * 64
    hits = [
        "  %copy.36 = bf16[1089,24,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} "
        "copy(%get-tuple-element.5), metadata={op_name=\"x\"}",
        "  ROOT %copy.22 = bf16[1089,24,16,16,64]{0,4,3,2,1:T(8,128)(2,1)}"
        " copy(%param_1), sharding={replicated}",
        "  %copy.9 = bf16[418176,1024]{1,0:T(8,128)(2,1)} copy(%bitcast.4)",
        "  %transpose.3 = bf16[24,1089,16,16,64]{4,3,2,1,0} "
        "transpose(%p), dimensions={1,0,2,3,4}",
    ]
    leave = [
        "  %copy.2 = bf16[16,1024]{1,0:T(8,128)(2,1)} copy(%fusion.7)",
        "  %scatter.1 = bf16[1089,24,16,1024]{3,2,1,0:T(8,128)(2,1)} "
        "scatter(%p, %i, %u), to_apply=%region",
        "  %fusion.141 = bf16[1089,24,16,1024]{3,2,1,0} fusion(%p, %u), "
        "kind=kLoop, calls=%fused_computation",
    ]
    assert sized_copies(Text(*hits, *leave), n) == [h.strip() for h in hits]
    assert sized_copies(Text(*leave), n) == []


# md_batchgen_long's engine (gpt2-medium: 24 layers, 16 heads of 64; 16
# slots, 1,089 blocks of 16, prompts to 512, f32 weights as build_engine
# hands them); gpt2-xl's 25 heads of 64 (a row of 1600 lanes, 12.5 lane
# tiles) at four layers; Gemma-3 270M's GQA heads (1 KV head of 256, 4
# query heads) at six layers, so one of them is global. Each pool holds
# more elements than the model's largest table, so "pool-sized" names
# the pools alone
POOL_CASES = {
    "cell_md": lambda: _gpt2_pool_case(GPT2Config.gpt2_medium(),
                                       16, 1089, 512),
    "kv25_d64": lambda: _gpt2_pool_case(
        dataclasses.replace(GPT2Config.gpt2_xl(), n_layer=4), 8, 1025, 256),
    "gqa_d256": lambda: _gemma_pool_case(
        Gemma3TextConfig(num_hidden_layers=6), 8, 8193, 256),
}


@pytest.mark.parametrize("program", ["decode_step", "write_prompt_blocks",
                                     "prefill_chunk"])
@pytest.mark.parametrize("case", sorted(POOL_CASES))
def test_no_program_converts_the_pool(topo, as_tpu, case, program):
    """serve/paged_kv.py's layout contract, witnessed at compile time:
    with the pools donated (as ServeEngine jits them) no program that
    touches a pool holds a pool-sized copy or transpose — not in the
    layer loop, not at the program's entry or exit, where the runtime's
    own buffer layout shows — and its compiled peak is the pools, the
    weights and under 1 GB of everything else. The head-major pool this
    replaced held 6 such instructions in the decode step (52 executed a
    token at 24 layers) and peaked at 13.7 GB where this asserts 4.1."""
    c = POOL_CASES[case]()
    L, KV, D, S, NB, P = (c[k] for k in ("L", "KV", "D", "S", "NB", "P"))
    bT = 16
    M = paged_kv.blocks_for(2 * P - 1, bT)
    params = on_chip0(topo, c["params"])
    pool = pool_sds(NB, L, KV, bT, D, SingleDeviceSharding(topo.devices[0]))
    i32 = lambda *shape: sds(topo, shape, jnp.int32)
    if program == "decode_step":
        fn, ops, donate = c["step"], (params, pool, pool, i32(S), i32(S),
                                      i32(S, M)), (1, 2)
    elif program == "prefill_chunk":
        fn, ops, donate = c["chunk"], (params, pool, pool, i32(1, P // 2),
                                       i32(), i32(), i32(1, M)), (1, 2)
    else:
        kv = sds(topo, (L, KV, P, D), BF16)
        fn, ops, donate = paged_kv.write_prompt_blocks, \
            (pool, pool, kv, kv, i32(P // bT)), (0, 1)
    compiled = compile_for(fn, *ops, donate_argnums=donate)
    n_pool = int(np.prod(pool.shape))
    assert sized_copies(compiled, n_pool) == []
    nbytes = lambda t: sum(int(np.prod(x.shape)) * x.dtype.itemsize
                           for x in jax.tree.leaves(t))
    held = 2 * nbytes(pool) + (nbytes(params) if fn is not
                               paged_kv.write_prompt_blocks else 0)
    assert compiled_peak_bytes(compiled) < held + 2 ** 30, (
        compiled_peak_bytes(compiled) / 1e9, held / 1e9)
    if program == "decode_step":
        assert_kernel(compiled)


def test_device_ops_from_reads_files_loops_and_fusions():
    """The witness below must not go blind either: an instruction counts
    by its innermost frame's FILE, a fusion once and its body not, a
    bitcast never — and `in_loops` goes by the computation a while
    names as its body, not by the op_name (a producer XLA sank into the
    loop keeps the op_name it had outside)."""
    class Text:
        def __init__(self, *lines):
            self.as_text = lambda: "\n".join(lines)
    text = Text(
        "HloModule m", "", "FileNames", '1 "/r/models/generate.py"',
        '2 "/r/ops/decode_attention.py"', "", "FunctionNames", '1 "f"', "",
        "FileLocations", "1 {file_name_id=1 function_name_id=1 line=3}",
        "2 {file_name_id=2 function_name_id=1 line=9}", "",
        "StackFrames", "1 {file_location_id=1 parent_frame_id=1}",
        "2 {file_location_id=2 parent_frame_id=1}", "",
        "%fused_computation.1 (p: s32[4]) -> s32[4] {",
        '  ROOT %c = s32[4] convert(%p), metadata={op_name="a/convert"'
        " stack_frame_id=2}", "}", "",
        "%body.sunk (t: (s32[], s32[4])) -> (s32[], s32[4]) {",
        '  %k = f32[4] custom-call(%x), custom_call_target="tpu_custom_call"'
        ', metadata={op_name="jit(f)/while/body/pallas_call"'
        " stack_frame_id=2}",
        "  %sunk = s32[4] fusion(%g), kind=kLoop, calls=%fused_computation.1,"
        ' metadata={op_name="jit(f)/attn/convert" stack_frame_id=2}',
        '  %b = s32[4] bitcast(%g), metadata={op_name="jit(f)/while/body/r"'
        " stack_frame_id=2}",
        '  %m = (f32[4]{0}, f32[4]{0}) fusion(%g), kind=kLoop,'
        ' calls=%fused_computation.2,'
        ' metadata={op_name="jit(f)/while/body/mlp" stack_frame_id=1}',
        "}", "", "ENTRY %main (p: s32[4]) -> s32[4] {",
        '  %plan = s32[4] fusion(%p), kind=kLoop, calls=%fused_computation.1,'
        ' metadata={op_name="jit(f)/attn/convert" stack_frame_id=2}',
        "  %w = (s32[], s32[4]) while(%t), condition=%cond, body=%body.sunk",
        "}")
    here = "ops/decode_attention.py"
    names = lambda lines: [ln.split(" = ")[0] for ln in lines]
    assert names(device_ops_from(text, here, in_loops=True)) \
        == ["%k", "%sunk"]
    assert names(device_ops_from(text, here)) == ["%k", "%sunk", "%plan"]
    assert names(device_ops_from(text, "models/generate.py")) == ["%m"]
    assert device_ops_from(text, "ops/flash_attention.py") == []


@pytest.mark.parametrize("case", sorted(POOL_CASES))
def test_decode_scan_holds_one_attention_op(topo, as_tpu, case):
    """Inside the layer scan the paged kernel's file owns ONE device op,
    the custom call: the mask's cut into chunks and the live ranges are
    made once a step, before the scan (generate._paged_attend), and XLA
    does not sink them back (left alone it does: the making is cheap and
    the loop's operand). The benchmark's paged_attn_roofline averages
    over every device op of that file, so a second small op a layer
    would double what it prints."""
    c = POOL_CASES[case]()
    L, KV, D, S, NB, P = (c[k] for k in ("L", "KV", "D", "S", "NB", "P"))
    M = paged_kv.blocks_for(2 * P - 1, 16)
    pool = pool_sds(NB, L, KV, 16, D, SingleDeviceSharding(topo.devices[0]))
    i32 = lambda *shape: sds(topo, shape, jnp.int32)
    compiled = compile_for(
        c["step"], on_chip0(topo, c["params"]), pool, pool, i32(S), i32(S),
        i32(S, M), donate_argnums=(1, 2))
    here = "ops/decode_attention.py"
    in_scan = device_ops_from(compiled, here, in_loops=True)
    assert len(in_scan) == 1 and "custom-call(" in in_scan[0], in_scan
    # and the once-a-step making of the plan is there, outside it
    assert len(device_ops_from(compiled, here)) > 1


# --------------------------- whole programs (slow) --------------------------

def gpt2_train_step(cfg: GPT2Config, B: int, S: int, remat: bool,
                    lora_impl: str = "auto", out_shardings=None, mesh=None):
    """The GPT-2 LoRA optimizer step as cli/gpt2_lora_finetune builds it
    (bf16, the smoke's four targets; `mesh` as the CLI hands it to the
    model), and its operands as shapes."""
    from mobilefinetuner_tpu.lora.lora import (LoRASpec, init_lora_gpt2,
                                               trainable_mask)
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
    from mobilefinetuner_tpu.train.trainer import (TrainConfig,
                                                   init_optimizer,
                                                   make_train_step)
    spec = LoRASpec(rank=8, alpha=16.0, init="gpt2", targets=[
        "attn_qkv", "attn_proj", "mlp_fc_in", "mlp_fc_out"])
    lora = init_lora_gpt2(cfg, spec, jax.random.PRNGKey(0))
    mask = trainable_mask(lora)
    tc = TrainConfig(total_steps=10)
    opt = init_optimizer(lora, tc, mask)
    params = jax.eval_shape(
        lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0)))

    def loss_fn(lora_t, frozen, mb):
        logits = gpt2.forward(cfg, frozen, mb["input_ids"],
                              attention_mask=mb["attention_mask"],
                              lora=lora_t, compute_dtype=BF16, remat=remat,
                              lora_impl=lora_impl, mesh=mesh)
        return lm_cross_entropy_sum(logits, mb["labels"])

    batch = {"input_ids": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "attention_mask": jax.ShapeDtypeStruct((B, S), jnp.float32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    step = make_train_step(loss_fn, tc, mask=mask, donate=True,
                           out_shardings=out_shardings)
    return step, (lora, params, opt, batch,
                  jax.ShapeDtypeStruct((), jnp.int32))


@pytest.mark.slow
@pytest.mark.parametrize("model,B,S,remat,kernels", [
    ("gpt2_small", 64, 128, False, True),    # LoRA epilogue (fused auto)
    ("gpt2_small", 4, 1024, False, True),    # + flash fwd, merged bwd
    ("gpt2_xl", 8, 128, True, False),        # d_out 1600/4800: no epilogue
    ("gpt2_xl", 2, 1024, True, True),        # flash at 25 heads
])
def test_train_step_compiles_and_fits(topo, as_tpu, model, B, S, remat,
                                      kernels):
    cfg = getattr(GPT2Config, model)()
    step, operands = gpt2_train_step(cfg, B, S, remat)
    compiled = step.lower(*on_chip0(topo, operands)).compile()
    if kernels:
        assert_kernel(compiled)
    assert_fits(compiled)


def place(tree, shardings):
    """Shapes of `tree` under a tree (or prefix) of shardings."""
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=s),
        tree, shardings)


def assert_fsdp_collectives(compiled) -> dict:
    from mobilefinetuner_tpu.core.static_checks import hlo_collective_census
    census = hlo_collective_census(compiled.as_text())
    assert census["all-gather"] > 0, census       # weights gathered
    assert census["all-reduce"] + census["reduce-scatter"] > 0, census
    return census


@pytest.mark.slow
@pytest.mark.parametrize("B,S,kernels", [(64, 128, False), (8, 1024, True)])
def test_fsdp_train_step_compiles_on_four_chips(topo, as_tpu, B, S, kernels):
    """chip_smoke.py --chips 4's FSDP runs, rehearsed before a four-chip
    call is paid for: the frozen base sharded by the CLI's own rule
    (parallel/mesh.params_shardings) over a (1, 4) mesh of the described
    devices, the batch over both axes, LoRA + Adam state replicated and
    pinned on the way out (cli/common.run_training's fixed point).

    The TPU lowering refuses a Mosaic kernel in a GSPMD-partitioned
    step ("cannot be automatically partitioned"), which no interpret-
    mode mesh test can see. Attention's `auto` compiles as it is: the
    model hands the dispatcher the mesh and the flash kernels sit under
    shard_map (in the executable from S = 1024, the XLA path at 128 by
    the one-chip rule). The LoRA epilogue is not wrapped: `auto` there
    compiles only after cli/common.build_mesh's partitioned_impl."""
    from mobilefinetuner_tpu.ops.pallas_util import partitioned_impl
    from mobilefinetuner_tpu.parallel.mesh import (batch_sharding,
                                                   make_mesh,
                                                   params_shardings,
                                                   replicated_sharding)
    mesh = make_mesh(data=1, fsdp=4, devices=topo.devices)
    repl = replicated_sharding(mesh)
    all_repl = lambda tree: jax.tree.map(lambda _: repl, tree)

    def lowered(lora_impl, mesh_arg):
        # (a sharding is a pytree prefix: one covers a whole subtree)
        step, (lora, params, opt, batch, _) = gpt2_train_step(
            GPT2Config.gpt2_small(), B, S, False, lora_impl=lora_impl,
            out_shardings=(repl, repl, repl), mesh=mesh_arg)
        return params, step.lower(
            place(lora, all_repl(lora)),
            place(params, params_shardings(params, mesh)),
            place(opt, all_repl(opt)),
            place(batch, jax.tree.map(lambda _: batch_sharding(mesh),
                                      batch)),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=repl))

    naive = partitioned_impl("lora_impl", "auto", "fused", "naive", 4)
    with pytest.raises(NotImplementedError, match="shard_map"):
        lowered("auto", mesh)          # the unwrapped LoRA epilogue
    if kernels:
        with pytest.raises(NotImplementedError, match="shard_map"):
            lowered(naive, None)       # flash with no mesh to wrap it over
    params, low = lowered(naive, mesh)
    compiled = low.compile()
    assert (tpu_custom_calls(compiled.as_text()) > 0) is kernels
    assert_fsdp_collectives(compiled)
    # per device: a quarter of the base, not all of it
    one_chip = 4 * sum(int(np.prod(x.shape)) for x in
                       jax.tree.leaves(params))
    assert compiled.memory_analysis().argument_size_in_bytes \
        < 0.4 * one_chip
    assert_fits(compiled)


@pytest.fixture(scope="module")
def fsdp_cell_step(topo):
    """`xl_fullft_fsdp4`'s step as cli/gpt2_full_finetune builds it:
    gpt2-xl over (1, 4), global B 32 x S 1024, bf16, remat, `auto`
    attention — the flash kernels under shard_map on 8 sequences a chip.
    Compiled once for the tests below (as_tpu's patches, module-wide)."""
    cfg = dataclasses.replace(GPT2Config.gpt2_xl(), embd_pdrop=0.0,
                              resid_pdrop=0.0, attn_pdrop=0.0)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with jax.default_matmul_precision("default"):
            return full_ft_fsdp_step(topo, cfg, 32, 1024, 1, 4)


@pytest.mark.slow
def test_the_fsdp_cell_s_full_ft_step_compiles_and_fits(fsdp_cell_step):
    """The cell's step fits a chip with the flash kernels in it. Prints
    the compiled peak and the census (PERF.md §4 and §6 quote them)."""
    from mobilefinetuner_tpu.core.static_checks import hlo_collective_census
    compiled = fsdp_cell_step
    assert_kernel(compiled, at_least=3)   # forward, its remat, backward
    assert_fsdp_collectives(compiled)
    census = {k: n for k, n in hlo_collective_census(
        compiled.as_text()).items() if n}
    peak = compiled_peak_bytes(compiled)
    print(f"\nxl_fullft_fsdp4 step, described v5e:2x2: compiled peak "
          f"{peak / 1e9:.2f} GB a device = {peak / 16_909_336_064:.3f} of "
          f"bytes_limit; collectives {census}")
    assert peak < 0.85 * 16_909_336_064


@pytest.mark.slow
def test_the_fsdp_cell_s_layer_scan_gathers_and_moves_no_activations(
        fsdp_cell_step):
    """Inside the cell's layer scan: the weight gathers and float32
    reduce-scatters, no all-to-all (the parent's partitioner put four
    [8, 1024, 4, 1600] all-to-alls in the MLP's backward alone)."""
    census = assert_layer_gather(fsdp_cell_step)
    print(f"\nxl_fullft_fsdp4 layer scan, described v5e:2x2: "
          f"{ {k: n for k, n in census.items() if n} }")


@pytest.mark.slow
def test_tp_decode_step_compiles_on_four_chips(topo, as_tpu):
    """ServeConfig.mesh_tp=4's decode step (12 heads / 4) through the
    model function the engine jits, on a (1, 4) mesh of the described
    devices: the paged kernel under shard_map, weights column/row
    parallel, pools sharded on their head axis."""
    from mobilefinetuner_tpu.models import generate as g
    from mobilefinetuner_tpu.models import gpt2
    from mobilefinetuner_tpu.serve.sharding import ServeSharding
    cfg = GPT2Config.gpt2_small()
    sh = ServeSharding.build("gpt2", cfg, 1, 4, devices=topo.devices)
    S, bT, NB, M = 8, 16, 256, 6
    params = jax.eval_shape(
        lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        params, sh.param_shardings(params))
    pool = pool_sds(NB, cfg.n_layer, cfg.n_head, bT, cfg.head_dim,
                    sh.pool_sharding())
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=sh.repl)
    compiled = compile_for(
        functools.partial(g.gpt2_decode_step_paged, cfg,
                          compute_dtype=BF16, shardings=sh),
        params, pool, pool, i32(S), i32(S), i32(S, M),
        donate_argnums=(1, 2),
        out_shardings=(sh.repl, sh.pool_sharding(), sh.pool_sharding()))
    assert_kernel(compiled)
    assert "all-reduce" in compiled.as_text()


@pytest.fixture(scope="module")
def smoke_engine():
    """The smoke's engine, built for real on the CPU (build_engine
    allocates its params and pools) — its jitted programs are then
    lowered for the described chip with the TPU rules."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import serve_bench
    eng, names = serve_bench.build_engine(
        "gpt2s", 8, 16, 256, 64, 32, adapters=2, dtype="bfloat16",
        on_step_error="raise", max_prompt_chunked=128)
    yield eng
    eng.close()


@pytest.mark.slow
def test_engine_programs_compile(topo, as_tpu, smoke_engine):
    """prefill, decode step (with the paged kernel in it) and a prefill
    chunk of the smoke's engine — the programs `ServeEngine` jits, at
    its num_slots / block_T / num_blocks, under the TPU `auto` rules."""
    eng = smoke_engine
    S, M, cfg = eng.cfg.num_slots, eng.M, eng.cfg
    bank = eng.bank.tree
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    args = on_chip0(topo, eng._step_args())
    compiled = eng._step.lower(*args).compile()
    assert_kernel(compiled)
    assert_fits(compiled)
    prefill = eng._prefill.lower(*on_chip0(topo, (
        eng.params, bank, i32(1, cfg.max_prompt), i32(1, cfg.max_prompt),
        i32(1)))).compile()
    assert_fits(prefill)
    W = eng.chunk_buckets[-1]
    chunk = eng._chunk.lower(*on_chip0(topo, (
        eng.params, bank, eng.pool_k, eng.pool_v, i32(1, W), i32(), i32(),
        i32(1, M), i32(1)))).compile()
    assert_fits(chunk)


@pytest.mark.slow
@pytest.mark.parametrize("program", ["decode_step", "prefill",
                                     "prefill_chunk"])
def test_gpt2_xl_serve_programs_compile_and_fit(topo, as_tpu, program):
    """The next PR's anchor model (gpt2-xl: H 1600, 25 heads, 48 layers)
    through the model functions the engine jits, from shapes alone —
    known to compile and to fit one 16 GiB chip before chip time is
    spent on it. The weights are handed over in bf16, as the engine
    hands them since PR 32 (it casts its tree once at build): a float32
    tree with the in-step cast needed 17.3 GiB here (6.2 f32 + the 3.1
    bf16 copy + pools) — over one chip."""
    from mobilefinetuner_tpu.models import generate as g
    from mobilefinetuner_tpu.models import gpt2
    cfg = GPT2Config.gpt2_xl()
    L, H, D = cfg.n_layer, cfg.n_head, cfg.head_dim
    S, bT, NB, Pmax, M = 8, 16, 256, 64, 6
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, BF16),
        jax.eval_shape(lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0))))
    pool = pool_sds(NB, L, H, bT, D, None)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if program == "decode_step":
        fn = functools.partial(g.gpt2_decode_step_paged, cfg,
                               compute_dtype=BF16)
        ops, kw = (params, pool, pool, i32(S), i32(S), i32(S, M)), \
            dict(donate_argnums=(1, 2))
    elif program == "prefill":
        fn = functools.partial(g.gpt2_prefill, cfg, compute_dtype=BF16)
        ops, kw = (params, i32(1, Pmax), i32(1, Pmax)), {}
    else:
        fn = functools.partial(g.gpt2_prefill_chunk, cfg,
                               compute_dtype=BF16)
        ops, kw = (params, pool, pool, i32(1, Pmax), i32(), i32(),
                   i32(1, M)), dict(donate_argnums=(1, 2))
    compiled = compile_for(fn, *on_chip0(topo, ops), **kw)
    if program == "decode_step":
        assert_kernel(compiled)
    assert_fits(compiled)


@pytest.mark.slow
def test_fused_ce_compiles_at_262k_vocab(topo, as_tpu):
    from mobilefinetuner_tpu.ops.fused_ce import (fused_ce_eligible,
                                                  fused_ce_rows)
    R, V, H = 1024, 262144, 640
    assert fused_ce_eligible(R, V, H, 2)

    def loss(h, w, labels):
        lse, gold = fused_ce_rows(h, w, labels)
        return (lse - gold).sum()

    assert_kernel(compile_for(
        jax.grad(loss, argnums=(0, 1)), sds(topo, (R, H), BF16),
        sds(topo, (V, H), BF16), sds(topo, (R,), jnp.int32)), at_least=2)
