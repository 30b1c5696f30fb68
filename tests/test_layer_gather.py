"""The layer gather over an FSDP mesh (parallel/mesh.gathers_layers,
models/gpt2._layer_gather): each layer's weights gathered whole in the
scan body, the block's activations on the batch axes, every gradient
summed across devices in float32. What the compiled step holds, that the
gate engages only where the fsdp axis holds more than one device, and
how close the bf16 step comes to the float32 one-device reference. The
TPU compiler's side (the reduce-scatter fusion) is
tests/test_aot_tpu.py's."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from fixtures import write_tiny_gpt2_dir, write_wikitext_dir
from test_flash_mesh import MESHES, mesh_of

from mobilefinetuner_tpu.core.config import GPT2Config
from mobilefinetuner_tpu.core.static_checks import hlo_collective_census
from mobilefinetuner_tpu.models import gpt2
from mobilefinetuner_tpu.ops.loss import lm_cross_entropy_sum
from mobilefinetuner_tpu.parallel import mesh as mesh_lib
from mobilefinetuner_tpu.parallel.mesh import shard_batch, shard_params

BLOCK = ("attention", "mlp")      # the scopes only the layer scan's ops carry

# The parent commit's bf16 step on the same inputs (the model, batch and
# shardings of `problem` below, params placed by the CLI's rule), against
# the float32 one-device step: |loss - loss_f32|, the whole gradient's
# relative error ||g - g_f32|| / ||g_f32||, and the worst leaf's. The
# change may not be further from the reference than that.
PARENT_GAP = {(1, 4): (5.3882598876953125e-05, 9.045044830683918e-03,
                       1.7498562598995127e-02),
              (2, 2): (8.296966552734375e-05, 8.784802759656415e-03,
                       1.2441246438894634e-02)}


def problem():
    cfg = dataclasses.replace(GPT2Config.tiny(vocab_size=256), n_embd=128,
                              n_head=2, n_positions=128, embd_pdrop=0.0,
                              resid_pdrop=0.0, attn_pdrop=0.0)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (8, 128), 0, 256)
    return cfg, params, {"input_ids": ids,
                         "labels": jnp.roll(ids, -1, axis=1)}


def full_ft_step(cfg, mesh, dtype, cp_mesh=None):
    """Mean-loss value_and_grad of the full-FT forward, remat on."""
    def loss(p, mb):
        logits = gpt2.forward(cfg, p, mb["input_ids"], compute_dtype=dtype,
                              remat=True, mesh=mesh, cp_mesh=cp_mesh)
        total, count = lm_cross_entropy_sum(logits, mb["labels"])
        return total / count
    return jax.jit(jax.value_and_grad(loss))


def lowered_text(cfg, params, batch, mesh, cp_mesh=None):
    return full_ft_step(cfg, mesh, jnp.bfloat16, cp_mesh).lower(
        params, batch).as_text()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("shape", MESHES, ids=str)
def test_the_compiled_step_gathers_layers_and_moves_no_activations(shape):
    """Inside the layer scan the compiled full-FT step gathers weights
    (forward and the remat's recompute) and holds no all-to-all; the
    parent's partitioner put [B, S, k·E] all-to-alls there."""
    cfg, params, batch = problem()
    mesh = mesh_of(*shape)
    hlo = full_ft_step(cfg, mesh, jnp.bfloat16).lower(
        shard_params(params, mesh), shard_batch(batch, mesh)
    ).compile().as_text()
    census = hlo_collective_census(hlo, scopes=BLOCK)
    assert census["all-to-all"] == 0, census
    assert census["collective-permute"] == 0, census
    # the products' weights, forward and recomputed (qkv and the MLP's
    # two: the attention projection is below the CLI's size floor here)
    assert census["all-gather"] >= 6, census


@pytest.mark.parametrize("shape", MESHES, ids=str)
def test_bf16_step_on_the_mesh_is_no_further_from_float32(shape):
    """bf16 compute: loss and every gradient leaf on the mesh agree with
    the one-device bf16 step, and the gap to the float32 one-device step
    is no larger than the parent commit's on the same inputs (PARENT_GAP):
    the gradients' cross-device sums are float32."""
    cfg, params, batch = problem()
    mesh = mesh_of(*shape)
    l32, g32 = full_ft_step(cfg, None, jnp.float32)(params, batch)
    l16, g16 = full_ft_step(cfg, None, jnp.bfloat16)(params, batch)
    l, g = full_ft_step(cfg, mesh, jnp.bfloat16)(shard_params(params, mesh),
                                                 shard_batch(batch, mesh))
    leaves, ref, one = (jax.tree.leaves(t) for t in (g, g32, g16))
    np.testing.assert_allclose(float(l), float(l16), rtol=2e-5)
    for a, b in zip(leaves, one):
        assert rel(a, b) < 3e-2
    loss_gap, grad_gap, leaf_gap = PARENT_GAP[shape]
    assert abs(float(l) - float(l32)) <= loss_gap
    whole = np.concatenate([np.ravel(np.asarray(a, np.float64))
                            for a in leaves])
    whole_ref = np.concatenate([np.ravel(np.asarray(b, np.float64))
                                for b in ref])
    assert rel(whole, whole_ref) <= grad_gap
    assert max(rel(a, b) for a, b in zip(leaves, ref)) <= leaf_gap


def test_float32_gradients_match_one_device():
    """float32: the gathered step is the one-device step to rounding,
    parameters sharded by the CLI's rule (the norms and biases too)."""
    cfg, params, batch = problem()
    mesh = mesh_of(1, 4)
    l_ref, g_ref = full_ft_step(cfg, None, jnp.float32)(params, batch)
    l, g = full_ft_step(cfg, mesh, jnp.float32)(
        shard_params(params, mesh, min_size=2 ** 8),
        shard_batch(batch, mesh))
    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6,
                                   rtol=1e-4, err_msg=str(path))


def gate_off(monkeypatch):
    monkeypatch.setattr(mesh_lib, "gathers_layers", lambda mesh: False)


def test_one_device_mesh_lowers_to_the_ungathered_program(monkeypatch):
    """A (1, 1) mesh shards no weight: the same text as no mesh, and as
    the program with the gate forced off — the parent's path, which runs
    no line of the gather."""
    cfg, params, batch = problem()
    one = lowered_text(cfg, params, batch, mesh_of(1, 1))
    assert one == lowered_text(cfg, params, batch, None)
    gate_off(monkeypatch)
    assert one == lowered_text(cfg, params, batch, mesh_of(1, 1))


@pytest.mark.parametrize("mesh_arg", [False, True], ids=["cp", "cp+mesh"])
def test_sequence_parallel_lowers_to_the_ungathered_program(monkeypatch,
                                                            mesh_arg):
    """Sequence-parallel mode shards S over fsdp, so its activations are
    not the batch's: with cp_mesh given the gate stays off whatever mesh
    the step names."""
    cfg, params, batch = problem()
    mesh = mesh_of(1, 4)
    p, b = (shard_params(params, mesh),
            shard_batch(batch, mesh, sequence_parallel=True))
    on = lowered_text(cfg, p, b, mesh if mesh_arg else None, cp_mesh=mesh)
    gate_off(monkeypatch)
    assert on == lowered_text(cfg, p, b, mesh if mesh_arg else None,
                              cp_mesh=mesh)


def test_the_gate_engages_on_an_fsdp_mesh(monkeypatch):
    """The converse, so the two tests above can fail: over (1, 4) the
    lowered program is not the ungathered one."""
    cfg, params, batch = problem()
    mesh = mesh_of(1, 4)
    p, b = shard_params(params, mesh), shard_batch(batch, mesh)
    on = lowered_text(cfg, p, b, mesh)
    gate_off(monkeypatch)
    assert on != lowered_text(cfg, p, b, mesh)


def test_full_ft_cli_over_the_mesh_reports_the_census(tmp_path):
    """gpt2_full_finetune --mesh_fsdp 4 --dtype bfloat16 trains, and its
    `compile` event's census shows the layer gathers and no all-to-all
    in the block."""
    from mobilefinetuner_tpu.cli.gpt2_full_finetune import main
    gpt2_dir, wiki = str(tmp_path / "gpt2"), str(tmp_path / "wiki")
    write_tiny_gpt2_dir(gpt2_dir, n_embd=128, n_head=2)
    write_wikitext_dir(wiki)
    stream = str(tmp_path / "t.jsonl")
    assert main(["--pretrained_dir", gpt2_dir, "--data_dir", wiki,
                 "--steps", "4", "--batch_size", "4", "--seq_len", "64",
                 "--dtype", "bfloat16", "--remat", "--mesh_fsdp", "4",
                 "--no_model_dropout", "--lr", "1e-3", "--log_interval", "1",
                 "--telemetry_out", stream,
                 "--output_path", str(tmp_path / "out.safetensors")]) == 0
    with open(stream) as f:
        events = [json.loads(line) for line in f]
    census = next(e for e in events if e["event"] == "compile")["collectives"]
    assert census.get("all-gather", 0) > 0 and "all-to-all" not in census
    losses = [e["loss"] for e in events if e["event"] == "step_stats"]
    assert len(losses) == 4 and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
