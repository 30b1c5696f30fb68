"""The Ling-3.0-flash hybrid on the LoRA trainer, at a tiny size on the
CPU: ops/kda.py's chunked delta rule against its own token-by-token scan;
the short conv; flash attention at two head sizes; models/ling_hybrid.py
against the plain reference the benchmark keeps
(benchmark/reference/ling_hybrid.py, which imports nothing from the
program); group-limited routing; the shares of the expert layer; the
family's scopes; the loader; the CLI through cli/common.run_training;
the parameter counts of the benchmark's cut."""

import dataclasses
import json
import os
import re
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
from harness import correct_ling, flops_ling  # noqa: E402
from reference import ling_hybrid as ref  # noqa: E402

from mobilefinetuner_tpu.core.config import LingHybridConfig  # noqa: E402
from mobilefinetuner_tpu.lora.lora import (LING_TARGETS,  # noqa: E402
                                           LoRASpec, init_lora_ling)
from mobilefinetuner_tpu.models import ling_hybrid  # noqa: E402
from mobilefinetuner_tpu.models.afmoe import _swiglu  # noqa: E402
from mobilefinetuner_tpu.ops import kda, moe  # noqa: E402
from mobilefinetuner_tpu.ops.attention import (attention,  # noqa: E402
                                               dot_product_attention)
from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum  # noqa: E402

B, S = 2, 32        # two chunks of 16
# float32 parity of the whole model: max |got - want| over max |want|
F32_TOL = 2e-5
# the bf16 program against the float32 reference at THIS size, in the
# cell's measure (max error over the RMS of the reference's logits): 0.45
# here, where 7 layers of width 64 put every sublayer's rounding straight
# into a residual stream five times smaller than it (embedding 0.02
# against sublayer outputs of 0.1); the cell's own limit
# (correct_ling.LOGIT_TOL_RMS) is set from the chip's readings at the
# published widths
TINY_BF16_RMS = 0.7
# likewise the share of assignments outside the reference's selection:
# 3.6 % of the 384 assignments here (14 near-ties at width 64) against
# 1.94-1.97 % of 1.5 M on the chip, where the cell's limit
# (correct_ling.ROUTE_SHARE_TOL, 2.25 %) is set
TINY_ROUTE_SHARE = 0.06


def make(seed=0, dtype=jnp.float32, **over):
    c = dataclasses.replace(LingHybridConfig.tiny(), **over)
    params = ling_hybrid.init_params(c, jax.random.PRNGKey(seed), dtype)
    spec = LoRASpec(rank=4, alpha=8.0, init="peft",
                    targets=list(LING_TARGETS))
    lora = init_lora_ling(c, spec, jax.random.PRNGKey(seed + 1))
    kb = jax.random.split(jax.random.PRNGKey(seed + 2), len(LING_TARGETS))
    lora["blocks"] = {
        t: {**e, "B": jax.random.normal(k, e["B"].shape) * 0.05}
        for k, (t, e) in zip(kb, sorted(lora["blocks"].items()))}
    ids = jax.random.randint(jax.random.PRNGKey(seed + 3), (B, S), 0,
                             c.vocab_size)
    return c, params, lora, ids


def cfg_of(c) -> dict:
    return dataclasses.asdict(c)


def prog_loss(c, params, lora, ids, dt=jnp.float32, **kw):
    logits = ling_hybrid.forward(c, params, ids, lora=lora,
                                 compute_dtype=dt, **kw)
    s, n = lm_cross_entropy_sum(logits, ids)
    return s / n


def ref_logits(c, params, lora, ids, forced=None):
    f = None if forced is None else np.asarray(forced).reshape(
        c.num_moe_layers, B, S, -1)
    return jnp.stack([ref.forward(cfg_of(c), params, ids[b], lora,
                                  None if f is None else f[:, b])[0]
                      for b in range(B)])


# ----------------------------- (a) the delta rule ---------------------------

def kda_inputs(seed, S, lo, hi, H=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, H, S, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (1, H, S, d)))
    v = jax.random.normal(ks[2], (1, H, S, d))
    g = jax.random.uniform(ks[3], (1, H, S, d), minval=lo, maxval=hi)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, H, S)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (1, H, S, d))


# gates over the whole of (-5, 0), at the fast end (the state is gone
# within a token: exp(-G) reaches e^80 inside a sub-chunk) and at the
# slow end (nothing forgotten across every chunk and block boundary)
GATES = {"whole": (-5.0, 0.0), "fast_end": (-5.0, -4.9),
         "slow_end": (-0.01, 0.0)}


# the kernel (ops/kda_pallas.py, in Pallas interpret mode here) takes
# heads of whole 128-lane tiles; the XLA form runs at the small head too
KDA_IMPLS = {"chunked": dict(H=2, d=16), "kernel": dict(H=2, d=128)}


@pytest.mark.parametrize("impl", sorted(KDA_IMPLS))
@pytest.mark.parametrize("gate", sorted(GATES))
@pytest.mark.parametrize("S_,block_chunks", [(64, 16), (256, 2), (192, 16)])
def test_chunked_delta_rule_matches_the_recurrence(gate, S_, block_chunks,
                                                   impl):
    """Forward and every gradient, over chunk (64), sub-chunk (16) and
    block boundaries: 256 tokens in blocks of 2 chunks are two blocks,
    192 tokens are one block of three. Every case for both
    implementations of the chunked form."""
    args, ct = kda_inputs(3, S_, *GATES[gate], **KDA_IMPLS[impl])
    chunked = lambda *a: kda.kda_chunked(*a, chunk=64, sub=16,
                                         block_chunks=block_chunks,
                                         impl=impl)
    want = kda.kda_recurrent(*args)[0]
    got = chunked(*args)
    np.testing.assert_allclose(got, want, atol=2e-6)
    gw = jax.grad(lambda *a: (kda.kda_recurrent(*a)[0] * ct).sum(),
                  argnums=range(5))(*args)
    gg = jax.grad(lambda *a: (chunked(*a) * ct).sum(),
                  argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), gg, gw):
        scale = max(float(jnp.abs(b).max()), 1e-3)
        assert float(jnp.abs(a - b).max()) <= 2e-5 * max(scale, 1.0), name
        assert np.isfinite(np.asarray(a)).all(), name


@pytest.mark.parametrize("impl", sorted(KDA_IMPLS))
def test_chunked_delta_rule_takes_bf16_inputs_and_computes_in_float32(impl):
    """bf16 q, k, v in, bf16 out; inside, float32: the output is the
    float32 recurrence of the ROUNDED inputs to float32's own accuracy,
    and within bf16's rounding of the unrounded one."""
    args, _ = kda_inputs(5, 256, -5.0, 0.0, **KDA_IMPLS[impl])
    q, k, v, g, beta = args
    want = kda.kda_recurrent(*args)[0]
    lo = lambda x: x.astype(jnp.bfloat16)
    got = kda.kda_chunked(lo(q), lo(k), lo(v), g, beta, block_chunks=2,
                          impl=impl).astype(jnp.float32)
    ref_scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 2e-2 * ref_scale
    rounded = kda.kda_recurrent(lo(q), lo(k), lo(v), g, beta)[0]
    # element by element: the float32 result (the recurrence's to the 2e-6
    # of the float32 cases above) rounded to bf16 ONCE — half a unit in
    # the last of its 8 significant bits, at most 2^-8 of the element.
    # A bf16 pass over the state, or a second rounding, does not fit
    assert bool((jnp.abs(got - rounded)
                 <= 2.0 ** -8 * jnp.abs(rounded) + 2e-6).all())
    assert kda.kda_chunked(lo(q), lo(k), lo(v), g, beta,
                           impl=impl).dtype == jnp.bfloat16
    # the gradients come back in their inputs' dtypes
    grads = jax.grad(lambda *a: kda.kda_chunked(*a, impl=impl).astype(
        jnp.float32).sum(), argnums=range(5))(lo(q), lo(k), lo(v), g, beta)
    assert [x.dtype for x in grads] == [jnp.bfloat16] * 3 \
        + [jnp.float32] * 2


@pytest.mark.parametrize("impl", sorted(KDA_IMPLS))
def test_chunked_delta_rule_holds_its_digits_when_keys_repeat(impl):
    """One key, written again and again at beta 0.95 with almost no
    decay: (I + Diag(beta) M) is then beta under the whole diagonal, the
    case in which a Neumann series of the inverse cancels terms of
    binomial size (PERF.md section 6, PR 31). The substitution — in the
    XLA form and in the kernel's row-by-row blocks and strips — reads
    what it reads on random keys."""
    (q, k, v, g, _), ct = kda_inputs(7, 256, -0.01, 0.0, **KDA_IMPLS[impl])
    args = (q, jnp.broadcast_to(k[:, :, :1], k.shape), v, g,
            jnp.full(k.shape[:3], 0.95))
    chunked = lambda *a: kda.kda_chunked(*a, impl=impl)
    np.testing.assert_allclose(chunked(*args), kda.kda_recurrent(*args)[0],
                               atol=2e-6)
    gw = jax.grad(lambda *a: (kda.kda_recurrent(*a)[0] * ct).sum(),
                  argnums=range(5))(*args)
    gg = jax.grad(lambda *a: (chunked(*a) * ct).sum(),
                  argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), gg, gw):
        assert float(jnp.abs(a - b).max()) \
            <= 2e-5 * max(float(jnp.abs(b).max()), 1.0), name


def test_chunked_delta_rule_refuses_a_ragged_length():
    args, _ = kda_inputs(0, 80, -1.0, 0.0)
    with pytest.raises(ValueError, match="multiple of"):
        kda.kda_chunked(*args, chunk=64)


def test_the_delta_rule_s_implementation_is_read_from_shapes():
    """`kernel` where the length is whole chunks and the head whole
    128-lane tiles (the cell: 16,384 tokens, chunk 64, heads of 128),
    `chunked` at the tiny preset's small heads, `recurrent` at a ragged
    length (a mesh never reaches the rule: the entry point refuses it,
    test_the_cli_refuses_a_mesh_by_name)."""
    tiny = LingHybridConfig.tiny()
    assert ling_hybrid.resolve_kda_impl(16384, 64, 128) == "kernel"
    assert ling_hybrid.resolve_kda_impl(S, tiny.kda_chunk,
                                        tiny.head_dim) == "chunked"
    assert ling_hybrid.resolve_kda_impl(16384, 64, 64) == "chunked"
    assert ling_hybrid.resolve_kda_impl(80, 64, 128) == "recurrent"
    assert ling_hybrid.resolve_kda_impl(80, 64, tiny.head_dim) \
        == "recurrent"
    # asked for by name at a head it cannot tile: refused, not padded
    args, _ = kda_inputs(0, 64, -1.0, 0.0)
    with pytest.raises(ValueError, match="128-lane"):
        kda.kda_chunked(*args, impl="kernel")
    with pytest.raises(ValueError, match="none of"):
        kda.kda_chunked(*args, impl="pallas")


def test_the_conv_is_a_causal_depthwise_convolution():
    """The program's shifted products against XLA's grouped convolution
    and against the reference's own shifted sums."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    want = jax.lax.conv_general_dilated(
        x, w[:, None, :], window_strides=(1,), padding=[(3, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=6)
    np.testing.assert_allclose(kda.causal_conv(x, w), want, atol=1e-6)
    np.testing.assert_allclose(ref.short_conv(x[0], w), want[0], atol=1e-6)
    # causal: a change at t moves nothing before t
    y2 = kda.causal_conv(x.at[:, 7].add(1.0), w)
    np.testing.assert_array_equal(y2[:, :7], kda.causal_conv(x, w)[:, :7])
    assert kda.causal_conv(x.astype(jnp.bfloat16), w).dtype == jnp.bfloat16


# ----------------------------- (b) latent attention -------------------------

@pytest.mark.parametrize("bwd_impl", ["merged", "split"])
def test_flash_at_two_head_sizes_matches_xla_attention(bwd_impl):
    """q.k over 192 (zero-padded to the 256-wide kernel), v over 128,
    through the Pallas kernels in interpret mode, several blocks."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 2, 256, 192))
    k = jax.random.normal(ks[1], (1, 2, 256, 192))
    v = jax.random.normal(ks[2], (1, 2, 256, 128))
    ct = jax.random.normal(ks[3], (1, 2, 256, 128))
    flash = lambda q, k, v: attention(
        q, k, v, impl="flash", is_causal=True, block_q=64, block_k=128,
        bwd_impl=bwd_impl, scale=192 ** -0.5)
    plain = lambda q, k, v: dot_product_attention(q, k, v, is_causal=True)
    got, want = flash(q, k, v), plain(q, k, v)
    assert got.shape == (1, 2, 256, 128)
    np.testing.assert_allclose(got, want, atol=2e-6)
    gg = jax.grad(lambda *a: (flash(*a) * ct).sum(), argnums=(0, 1, 2))(
        q, k, v)
    gw = jax.grad(lambda *a: (plain(*a) * ct).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(gg, gw):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_auto_takes_the_kernels_at_the_cell_s_length_and_the_split_backward():
    from mobilefinetuner_tpu.ops.attention import resolve_impl
    from mobilefinetuner_tpu.ops.flash_attention import (kernel_eligible,
                                                         merged_bwd_fits,
                                                         resolve_bwd_impl)
    assert resolve_impl(16384, 192) == "flash"
    assert kernel_eligible(16384, 256, 128)
    assert not kernel_eligible(16384, 192, 128)      # hence the padding
    assert resolve_bwd_impl(16384, 256, 512, 2, 128) == "split"
    # the cells before this one resolve as they did
    assert resolve_bwd_impl(8192, 128, 512, 2) == "merged"
    assert resolve_bwd_impl(1024, 64, 512, 2) == "merged"
    assert merged_bwd_fits(2048, 256, 512, 2) \
        and not merged_bwd_fits(2048, 256, 512, 4)


# ----------------------------- (c) parity with the reference ----------------

@pytest.fixture(scope="module")
def tiny():
    c, params, lora, ids = make()
    want = ref_logits(c, params, lora, ids)
    return c, params, lora, ids, want


def test_layer_kinds_follow_the_published_rule():
    c = LingHybridConfig.tiny()
    assert "".join(k[0] for k in c.layer_kinds) == "kkkkkmk"
    assert ling_hybrid.kind_index(c) == [0, 1, 2, 3, 4, 0, 5]
    full = dataclasses.replace(c, num_hidden_layers=42,
                               first_k_dense_replace=2)
    assert full.layer_kinds.count("mla") == 7
    assert [ref.layer_kind(cfg_of(full), l) for l in range(42)] \
        == full.layer_kinds


def test_logits_and_loss_match_the_reference_in_float32(tiny):
    c, params, lora, ids, want = tiny
    got = ling_hybrid.forward(c, params, ids, lora=lora)
    assert got.shape == (B, S, c.vocab_size)
    assert correct_ling.rel_max(got, want) < F32_TOL
    np.testing.assert_allclose(prog_loss(c, params, lora, ids),
                               ref.loss(want, ids), atol=1e-5)


def test_remat_and_padding_mask_leave_the_logits_alone(tiny):
    c, params, lora, ids, want = tiny
    got = ling_hybrid.forward(c, params, ids, lora=lora, remat=True,
                              attention_mask=jnp.ones((B, S), jnp.float32))
    assert correct_ling.rel_max(got, want) < F32_TOL


def test_the_recurrent_path_serves_a_ragged_length(tiny):
    c, params, lora, ids, _ = tiny
    got = ling_hybrid.forward(c, params, ids[:, :24], lora=lora)
    want = jnp.stack([ref.forward(cfg_of(c), params, ids[b, :24], lora)[0]
                      for b in range(B)])
    assert correct_ling.rel_max(got, want) < F32_TOL


def test_every_adapter_gradient_matches_the_reference_in_float32(tiny):
    c, params, lora, ids, _ = tiny
    got = jax.grad(lambda l: prog_loss(c, params, l, ids, remat=True))(lora)
    want = jax.grad(lambda l: ref.loss(ref_logits(c, params, l, ids),
                                       ids))(lora)
    assert sorted(lora["blocks"]) == sorted(LING_TARGETS)
    for t in sorted(lora["blocks"]):
        for leaf in ("A", "B"):
            g, w = got["blocks"][t][leaf], want["blocks"][t][leaf]
            assert float(jnp.abs(w).max()) > 1e-6, (t, leaf)
            assert correct_ling.rel_max(g, w) < 1e-4, (t, leaf)
            assert correct_ling.cosine(g, w) > 0.99999, (t, leaf)


def test_the_tail_gradient_is_the_full_gradient_s_slice(tiny):
    """What the benchmark's child compares: the reference's gradient over
    the last two layers (one of each kind) equals the matching positions
    of the full gradient."""
    c, params, lora, ids, _ = tiny
    cfg = cfg_of(c)
    L, K = c.num_hidden_layers, 2
    full = jax.grad(lambda l: ref.loss(
        ref.forward(cfg, params, ids[0], l)[0], ids[0]))(lora)
    mid, _ = ref.run_layers(cfg, params, [ref.embed(cfg, params, ids[0])],
                            range(L - K), lora)
    _, routing = ref.forward(cfg, params, ids[0], lora)
    forced = np.stack([np.asarray(r["idx"]) for r in routing])
    tail = ref.tail_grad(cfg, params, mid[0], ids[0], lora, forced, K)
    np.testing.assert_allclose(tail["kda_q_proj"]["B"][0],
                               full["blocks"]["kda_q_proj"]["B"][5],
                               atol=1e-6)
    np.testing.assert_allclose(tail["mla_o_proj"]["A"][0],
                               full["blocks"]["mla_o_proj"]["A"][0],
                               atol=1e-6)
    assert tail["kda_q_proj"]["B"].shape[0] == 1


def test_bfloat16_stays_inside_the_cell_s_tolerances():
    """The comparison the benchmark's child makes, at the tiny size."""
    c, params, lora, ids = make(seed=3)
    pb = jax.tree.map(lambda t: t.astype(jnp.bfloat16)
                      if t.ndim >= 2 and t.shape[-1] != c.num_experts
                      else t, params)
    logits, aux = ling_hybrid.forward(c, pb, ids, lora=lora,
                                      compute_dtype=jnp.bfloat16,
                                      with_aux=True)
    p32 = jax.tree.map(lambda t: t.astype(jnp.float32), pb)
    want = ref_logits(c, p32, lora, ids, forced=aux["idx"])
    assert correct_ling.rms_err(logits.astype(jnp.float32), want) \
        < TINY_BF16_RMS
    s, n = lm_cross_entropy_sum(logits, ids)
    assert abs(float(s / n) - float(ref.loss(want, ids))) \
        < correct_ling.LOSS_TOL
    f = np.asarray(aux["idx"]).reshape(c.num_moe_layers, B, S, -1)
    for b in range(B):
        _, routings = ref.forward(cfg_of(c), p32, ids[b], lora, f[:, b])
        d = correct_ling.routing_disagreement_grouped(
            f[:, b], np.stack([r["biased"] for r in routings]),
            np.stack([r["groups"] for r in routings]), c.topk_group)
        assert d["share"] <= TINY_ROUTE_SHARE, d
        assert d["max_gap"] <= correct_ling.ROUTE_GAP_TOL, d


# What the benchmark's child compares on the chip, with the latent layer
# at head sizes the flash kernels take (q.k over 128 + 64, v over 128) and
# the cell's tail (a latent layer, then a delta-rule layer: k m k here):
# the tail gradient of the cell's leaves, program (flash forward and
# backward, interpret mode) against the reference.
LATENT_S = 256
CELL_LEAVES = ("kda_q_proj.B", "mla_q_proj.B", "mla_kv_b_proj.B")


@pytest.fixture(scope="module")
def latent_tail():
    c = dataclasses.replace(
        LingHybridConfig.tiny(), num_hidden_layers=3, layer_group_size=2,
        num_attention_heads=2, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rotary_dim=64, v_head_dim=128, attention_impl="flash")
    assert c.layer_kinds == ["kda", "mla", "kda"]
    params = ling_hybrid.init_params(c, jax.random.PRNGKey(0))
    lora = init_lora_ling(c, LoRASpec(rank=4, alpha=8.0, init="peft",
                                      targets=list(LING_TARGETS)),
                          jax.random.PRNGKey(1))
    kb = jax.random.split(jax.random.PRNGKey(2), len(LING_TARGETS))
    lora["blocks"] = {
        t: {**e, "B": jax.random.normal(k, e["B"].shape) * 0.05}
        for k, (t, e) in zip(kb, sorted(lora["blocks"].items()))}
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, LATENT_S), 0,
                             c.vocab_size)
    cfg, L, K = cfg_of(c), c.num_hidden_layers, 2
    _, aux = ling_hybrid.forward(c, params, ids, lora=lora, with_aux=True)
    forced = np.asarray(aux["idx"])
    mid, _ = ref.run_layers(cfg, params, [ref.embed(cfg, params, ids[0])],
                            range(L - K), lora, [forced])
    want = ref.tail_grad(cfg, params, mid[0], ids[0], lora, forced, K)
    return c, params, lora, ids, want


def tail_verdicts(latent_tail) -> dict:
    """leaf -> harness/correct_ling.py's verdict on the program's tail
    gradient of that leaf (the last of its kind's stack) against the
    reference's, under the cell's limits."""
    c, params, lora, ids, want = latent_tail
    jax.clear_caches()      # the backward is traced anew under a fault
    got = jax.jit(jax.grad(
        lambda l: prog_loss(c, params, l, ids, remat=True)))(lora)
    out = {}
    for leaf in CELL_LEAVES + ("mla_o_proj.A",):
        t, ab = leaf.split(".")
        g, w = got["blocks"][t][ab][-1:], want[t][ab]
        out[leaf] = correct_ling.verdict({
            "grad_rel": (correct_ling.rel_max(g, w), correct_ling.GRAD_TOL,
                         "max"),
            "grad_cos": (correct_ling.cosine(g, w), correct_ling.GRAD_COS,
                         "min")})
    return out


def test_the_cell_s_leaves_pass_through_the_flash_backward(latent_tail):
    for leaf, v in tail_verdicts(latent_tail).items():
        assert v["ok"] and v["checks"]["grad_rel"]["value"] < 1e-3, (leaf, v)


# a fault planted in ONE output of the flash backward — the softmax scale
# left out of dQ or of dK, a key block of dV never written — and the one
# leaf of the cell's that the faulty cotangent reaches
FLASH_FAULTS = {"dq_unscaled": "mla_q_proj.B",
                "dk_unscaled": "mla_kv_b_proj.B",
                "dv_block_unwritten": "mla_kv_b_proj.B"}


@pytest.mark.parametrize("fault", sorted(FLASH_FAULTS))
def test_a_fault_in_the_flash_backward_comes_out_as_not_correct(
        monkeypatch, latent_tail, fault):
    """The leaf BELOW the attention call that the faulty cotangent
    reaches is refused by the cell's limits; every other leaf passes —
    `mla_o_proj.A` among them, which lies above the call and sees the
    flash forward only, so the cell does not rest on it."""
    from mobilefinetuner_tpu.ops import flash_attention as fa
    real = fa._bwd

    def faulty(scale, *a, **kw):
        dq, dk, dv, *rest = real(scale, *a, **kw)
        if fault == "dq_unscaled":
            dq = dq / scale
        elif fault == "dk_unscaled":
            dk = dk / scale
        else:
            dv = dv.at[:, :, :LATENT_S // 4].set(0)
        return (dq, dk, dv, *rest)

    monkeypatch.setattr(fa, "_bwd", faulty)
    try:
        oks = {leaf: v["ok"] for leaf, v in
               tail_verdicts(latent_tail).items()}
    finally:
        jax.clear_caches()
    assert oks == {leaf: leaf != FLASH_FAULTS[fault] for leaf in oks}, oks


@pytest.mark.parametrize("term", ["output_gate", "conv", "beta", "decay",
                                  "q_norm", "rotary"])
def test_parity_fails_when_a_term_is_removed(monkeypatch, tiny, term):
    c, params, lora, ids, want = tiny
    if term == "output_gate":
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(x))
    elif term == "conv":
        monkeypatch.setattr(kda, "causal_conv", lambda x, w: x)
    elif term == "beta":
        real = kda.kda_chunked
        monkeypatch.setattr(kda, "kda_chunked", lambda q, k, v, g, b, **kw:
                            real(q, k, v, g, jnp.ones_like(b), **kw))
    elif term == "decay":
        real = kda.kda_chunked
        monkeypatch.setattr(kda, "kda_chunked", lambda q, k, v, g, b, **kw:
                            real(q, k, v, jnp.zeros_like(g), b, **kw))
    elif term == "q_norm":
        params = dict(params, layers=[
            {**lp, "mla": {**lp["mla"], "q_norm": 2.0 * lp["mla"]["q_norm"]}}
            if "mla" in lp else lp for lp in params["layers"]])
    elif term == "rotary":
        monkeypatch.setattr(ling_hybrid, "apply_rope", lambda x, *a: x)
    got = ling_hybrid.forward(c, params, ids, lora=lora)
    assert float(jnp.abs(got - want).max()) > 1e-3


def test_the_program_gives_the_same_with_the_kernel_as_with_the_scans(
        monkeypatch):
    """At a head of 128 the shape rule takes the Pallas kernels (chunks
    of 16: one sub-chunk a chunk, two chunks a block): logits and every
    adapter gradient equal those of the XLA form of the same algebra."""
    c, params, lora, ids = make(seed=4, head_dim=128)
    assert ling_hybrid.resolve_kda_impl(S, c.kda_chunk, c.head_dim) \
        == "kernel"
    run = jax.value_and_grad(
        lambda l: prog_loss(c, params, l, ids, remat=True))
    got, g_got = run(lora)
    monkeypatch.setattr(kda, "resolve_impl", lambda *a, **kw: "chunked")
    want, g_want = run(lora)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert float(jnp.abs(a - b).max()) \
            <= F32_TOL * max(float(jnp.abs(b).max()), 1e-3)


# ----------------------------- (d) the router -------------------------------

def router_inputs(seed=0, T=96, H=64, E=32):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (T, H)),
            jax.random.normal(k[1], (H, E)) * 0.1,
            jax.random.normal(k[2], (E,)) * 0.05)


def test_group_limited_routing_matches_a_plain_loop():
    m, w_r, bias = router_inputs()
    ng, kg, k = 8, 4, 4
    idx, w, kept = moe.route(m, w_r, bias, k, True, 2.5, ng, kg)
    s = 1.0 / (1.0 + np.exp(-np.asarray(m, np.float64)
                            @ np.asarray(w_r, np.float64)))
    b = s + np.asarray(bias, np.float64)
    per = b.shape[1] // ng
    for t in range(m.shape[0]):
        score = [np.sort(b[t, g * per:(g + 1) * per])[-2:].sum()
                 for g in range(ng)]
        keep = set(np.argsort(score)[-kg:].tolist())
        assert set(np.flatnonzero(np.asarray(kept[t])).tolist()) == keep
        allowed = [e for e in range(b.shape[1]) if e // per in keep]
        best = sorted(allowed, key=lambda e: -b[t, e])[:k]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(best)
        ws = s[t, np.asarray(idx[t])]
        np.testing.assert_allclose(w[t], 2.5 * ws / ws.sum(), rtol=1e-5)
    # the reference's router agrees
    cfg = {"num_experts": 32, "n_group": ng, "topk_group": kg,
           "num_experts_per_tok": k, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5}
    ridx, rw, _, _, _ = ref.router(cfg, m, {"router_w": w_r,
                                            "expert_bias": bias})
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(ridx, -1))


def test_one_group_is_the_router_it_was():
    """Trinity's routing must not change: with n_group 1 the function is
    the flat top-k bit for bit."""
    m, w_r, bias = router_inputs(seed=4, E=8)
    idx, w, kept = moe.route(m, w_r, bias, 2, True, 2.448)
    assert kept is None
    logits = jnp.dot(m.astype(jnp.float32), w_r.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, widx = jax.lax.top_k(s + bias, 2)
    ww = jnp.take_along_axis(s, widx, axis=-1)
    ww = ww / (ww.sum(-1, keepdims=True) + 1e-20) * 2.448
    np.testing.assert_array_equal(idx, widx)
    np.testing.assert_array_equal(w, ww)
    idx1, w1, _ = moe.route(m, w_r, bias, 2, True, 2.448, 1, 1)
    np.testing.assert_array_equal(idx1, idx)
    np.testing.assert_array_equal(w1, w)


def test_the_grouped_product_s_tiles_follow_its_shape():
    assert moe.gmm_tiling(3072, 3072) == (128, 3072, 1024)   # Trinity's
    assert moe.gmm_tiling(2560, 768) == (128, 2560, 768)
    assert moe.gmm_tiling(768, 2560) == (128, 768, 512)
    assert moe.gmm_tiling(64, 32) == (128, 64, 32)            # the tests'
    assert moe.gmm_tiling(6144, 256) == (128, 3072, 256)


# ----------------------------- (e) the shares add up ------------------------

def test_the_ranks_parts_and_one_shared_expert_add_up_to_the_uncut_layer():
    """--expert_parallel 4 at the tiny size: a rank's experts are one
    routing group; every rank routes over all 16 experts and computes
    its four; the four parts plus ONE shared-expert term are the uncut
    reference layer."""
    c, params, _, _ = make(seed=5)
    m = jax.random.normal(jax.random.PRNGKey(9), (B * S, c.hidden_size))
    lp = params["layers"][2]
    idx, w, _ = moe.route(m, lp["router_w"], lp["expert_bias"],
                          c.num_experts_per_tok, c.norm_topk_prob,
                          c.routed_scaling_factor, c.n_group, c.topk_group)
    total = _swiglu(m, lp["shared"], jnp.float32)
    here = 0.0
    for rank in range(4):
        held = c.with_expert_parallel(4, rank).experts_held
        assert held == (rank * 4, 4)        # routing group `rank`
        mine = jax.tree.map(lambda t: t[held[0]:held[0] + held[1]],
                            lp["experts"])
        part, counters = moe.expert_layer(m, idx, w, mine, held)
        assert float(counters["dropped"]) == 0.0
        here += float(counters["assign_here"])
        total = total + part
    assert here == B * S * c.num_experts_per_tok
    cfg = cfg_of(c)
    ridx, rw, _, _, _ = ref.router(cfg, m, lp)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(ridx, -1))
    want = ref.swiglu(m, lp["shared"]) + ref.routed_sum(
        cfg, m, ridx, rw, lp["experts"])
    np.testing.assert_allclose(total, want, atol=1e-5)


def test_a_rank_s_model_output_is_the_reference_given_the_same_share():
    c, params, lora, ids = make(seed=6)
    c4 = c.with_expert_parallel(4, 2)
    first, n = c4.experts_held
    mine = dict(params, layers=[
        {**lp, "experts": jax.tree.map(lambda t: t[first:first + n],
                                       lp["experts"])}
        if "experts" in lp else lp for lp in params["layers"]])
    got, aux = ling_hybrid.forward(c4, mine, ids, lora=lora, with_aux=True)
    want = ref_logits(c4, mine, lora, ids)
    assert correct_ling.rel_max(got, want) < F32_TOL
    whole = ling_hybrid.forward(c, params, ids, lora=lora)
    assert float(jnp.abs(got - whole).max()) > 1e-3    # a part, not the sum
    # two of four groups are kept a token: a rank's group about half the
    # time; its experts get fewer assignments than that
    share = float(aux["counters"]["sum"]["moe_groups_here"]) \
        / (B * S * c.num_moe_layers)
    assert 0.2 < share < 0.8
    assert float(aux["counters"]["sum"]["moe_assign_here"]) \
        <= share * B * S * c.num_moe_layers * c.num_experts_per_tok + 1e-3
    assert float(aux["counters"]["sum"]["moe_dropped"]) == 0.0


# ----------------------------- (f) refusals ---------------------------------

def test_a_non_zero_swiglu_limit_raises():
    with pytest.raises(ValueError, match="SwiGLU limit"):
        dataclasses.replace(LingHybridConfig.tiny(),
                            expert_swiglu_limit_list=[0, 0, 0, 0, 0, 4, 4])
    with pytest.raises(ValueError, match="SwiGLU limit"):
        dataclasses.replace(LingHybridConfig.tiny(),
                            share_expert_swiglu_limit_list=[5.0] * 7)
    # limits beyond the kept depth are not this model's
    dataclasses.replace(LingHybridConfig.tiny(),
                        expert_swiglu_limit_list=[0] * 7 + [4])
    c, params, lora, ids = make()
    with pytest.raises(ValueError, match="SwiGLU limit"):
        ref.forward({**cfg_of(c), "expert_swiglu_limit_list": [4.0] * 7},
                    params, ids[0])


def test_unpublished_forms_are_refused():
    for over in ({"q_lora_rank": 256}, {"kda_safe_gate": False},
                 {"score_function": "softmax"}, {"n_group": 3}):
        with pytest.raises(ValueError, match="published"):
            dataclasses.replace(LingHybridConfig.tiny(), **over)


# ----------------------------- (g) scopes -----------------------------------

@pytest.mark.parametrize("kda_impl,head_dim", [("chunked", 16),
                                               ("kernel", 128)])
def test_every_op_of_a_mixer_lies_under_one_scope_of_its_kind(kda_impl,
                                                              head_dim):
    """In the lowered train step, forward and backward, every op whose
    name path runs through `attention` runs through exactly one of the
    family's inner scopes, and every op of an expert MLP through exactly
    one moe_* scope (their device times add up to the layer's) — with
    the delta rule as XLA scans and, at a head of 128, as the Pallas
    kernels, whose ops lie under `kda_scan` like the scans'."""
    c, params, lora, ids = make(seed=9, head_dim=head_dim)
    assert ling_hybrid.resolve_kda_impl(S, c.kda_chunk, c.head_dim) \
        == kda_impl
    text = jax.jit(jax.grad(
        lambda l: prog_loss(c, params, l, ids, remat=True))).lower(
        lora).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    inner = (r"kda_proj_conv|kda_gate|kda_scan|kda_out|mla_proj"
             r"|attn_latent|mla_out")
    # op paths only (a bare "attention" is the dispatcher's function name
    # in a call-site location, a path with ".py" a file)
    in_attn = [n for n in names if re.search(r"\battention/", n)
               and ".py" not in n]
    assert len(in_attn) > 100
    seen = set()
    for n in in_attn:
        inside = set(re.findall(inner, n))
        assert len(inside) == 1, n
        seen |= inside
    assert seen == set(inner.replace("\n", "").split("|"))
    moe_seen = set()
    for n in names:
        if re.search(r"\bmlp\b", n) and "moe_" in n:
            inside = set(re.findall(r"moe_(?:router|dispatch|experts|shared"
                                    r"|combine)", n))
            assert len(inside) == 1, n
            moe_seen |= inside
    assert len(moe_seen) == 5


# ----------------------------- (h) loader, CLI ------------------------------

def test_the_loader_keeps_bf16_and_reads_only_the_held_experts(tmp_path):
    from fixtures import write_tiny_ling_dir
    from mobilefinetuner_tpu.io.checkpoints import load_ling_hybrid
    c, params = write_tiny_ling_dir(str(tmp_path), dtype=jnp.bfloat16)
    c2 = c.with_expert_parallel(4, 1)
    _, got = load_ling_hybrid(str(tmp_path), config=c2)
    lp, want = got["layers"][3], params["layers"][3]
    assert lp["experts"]["gate_w"].shape == (4, c.hidden_size,
                                             c.moe_intermediate_size)
    assert lp["experts"]["gate_w"].dtype.name == "bfloat16"
    np.testing.assert_array_equal(
        np.asarray(lp["experts"]["down_w"], np.float32),
        np.asarray(want["experts"]["down_w"][4:8], np.float32))
    assert lp["router_w"].dtype == np.float32
    assert lp["router_w"].shape == (c.hidden_size, c.num_experts)
    for name in ("q_conv", "A_log", "dt_bias", "o_norm", "f_w"):
        np.testing.assert_array_equal(
            np.asarray(lp["kda"][name], np.float32),
            np.asarray(want["kda"][name], np.float32))
    mla = got["layers"][5]["mla"]
    assert mla["q_w"].shape == (c.hidden_size,
                                c.num_attention_heads * c.qk_head_dim)
    assert mla["kv_b_w"].shape == (
        c.kv_lora_rank,
        c.num_attention_heads * (c.qk_nope_head_dim + c.v_head_dim))


def test_the_cli_trains_through_run_training_with_counters_and_an_adapter(
        tmp_path):
    from fixtures import write_tiny_ling_dir, write_wikitext_dir
    from mobilefinetuner_tpu.cli import train_lora_ling
    from mobilefinetuner_tpu.io.safetensors_io import SafeTensorsReader
    write_tiny_ling_dir(str(tmp_path / "m"))
    write_wikitext_dir(str(tmp_path / "data"))
    tel = tmp_path / "tel.jsonl"
    rc = train_lora_ling.main([
        "--pretrained_dir", str(tmp_path / "m"),
        "--data_dir", str(tmp_path / "data"), "--steps", "4",
        "--batch_size", "2", "--seq_len", "64", "--dtype", "float32",
        "--remat", "--expert_parallel", "4", "--expert_rank", "1",
        "--log_interval", "2", "--telemetry_out", str(tel),
        "--lora_out", str(tmp_path / "out.safetensors"),
        "--peft_export_dir", str(tmp_path / "peft")])
    assert rc == 0
    recs = [json.loads(l) for l in open(tel)]
    start = next(r for r in recs if r["event"] == "run_start")["config"]
    assert start["layer_kinds"] == "k,k,k,k,k,m,k"
    assert start["kda_chunk"] == 16
    assert start["kda_impl_resolved"] == "chunked"
    assert start["attention_impl_resolved"] == "xla"     # S = 64 here
    flushes = [r for r in recs if r["event"] == "step_stats"]
    assert len(flushes) == 2 and flushes[-1]["loss"] < flushes[0]["loss"]
    for f in flushes:
        assert f["moe_dropped"] == 0.0
        assert f["moe_assign_here"] > 0
        # a count of (token, expert layer) pairs a step: of 2 x 64 x 6
        assert 0.0 < f["moe_groups_here"] < 2 * 64 * 6
        assert f["moe_load_max_over_mean"] >= 1.0
    keys = SafeTensorsReader(
        str(tmp_path / "peft" / "adapter_model.safetensors")).keys()
    # a target's stack position is exported under its LAYER's number
    assert ("base_model.model.model.layers.5.attention.kv_b_proj"
            ".lora_A.weight") in keys
    assert ("base_model.model.model.layers.6.attention.q_proj"
            ".lora_B.weight") in keys
    assert not any(".layers.5.attention.k_proj" in k for k in keys)


def test_the_cli_refuses_a_mesh_by_name(tmp_path):
    """A Mosaic kernel outside shard_map cannot be partitioned by GSPMD,
    and the family runs on one device: a mesh of more than one is
    refused at the entry point, by the program's name, before any `auto`
    (the delta rule's among them) is resolved."""
    from fixtures import write_tiny_ling_dir, write_wikitext_dir
    from mobilefinetuner_tpu.cli import train_lora_ling
    write_tiny_ling_dir(str(tmp_path / "m"))
    write_wikitext_dir(str(tmp_path / "data"))
    with pytest.raises(SystemExit, match="train_lora_ling runs one "
                                         "expert-parallel rank on one "
                                         "device"):
        train_lora_ling.main([
            "--pretrained_dir", str(tmp_path / "m"),
            "--data_dir", str(tmp_path / "data"), "--steps", "1",
            "--batch_size", "2", "--seq_len", "64", "--dtype", "float32",
            "--mesh_fsdp", "2",
            "--lora_out", str(tmp_path / "out.safetensors")])


# ----------------------------- (i) the benchmark's cut ----------------------

def test_the_cut_s_parameter_counts():
    """ISSUE 31's arithmetic, re-reckoned from the configuration file
    and from the program's own parameter tree."""
    cell = bench_common.load_cell("ling_lora_long")
    cfg = cell["config_file"]
    held = flops_ling.held_params(cfg)
    M = 1e6
    assert round(held["kda_attention"] / M, 1) == 52.6
    assert round(held["mla_attention"] / M, 1) == 32.0
    assert flops_ling.matmul_params(cfg)["expert"] == 3 * 2560 * 768
    assert round(held["experts_held_a_layer"] / M, 1) == 377.5
    assert round(held["sparse_kda_layer"] / M, 1) == 437.3
    assert round(held["sparse_mla_layer"] / M, 1) == 416.7
    assert round(held["dense_kda_layer"] / M, 1) == 99.8
    assert round(held["embedding_and_head"] / M, 1) == 100.6
    assert round(held["total"] / 1e9, 2) == 2.80
    assert round(2 * held["total"] / 16_909_336_064, 2) == 0.33
    from harness.train_child_ling import model_config
    c = model_config(cfg)
    assert "".join(k[0] for k in c.layer_kinds) == "kkkkkmk"
    assert c.experts_held == (0, 64) and c.num_experts == 512
    tree = jax.eval_shape(lambda: ling_hybrid.init_params(
        c, jax.random.PRNGKey(0), jnp.bfloat16))
    leaves = jax.tree.leaves(tree)
    matrices = sum(int(np.prod(l.shape)) for l in leaves if l.ndim >= 2)
    # the tree's matrices are the held parameters (router included)
    assert matrices == held["total"]
    assert ling_hybrid.mixer_params(c) == {
        k: flops_ling.matmul_params(cfg)[k] for k in ("kda", "mla")}
