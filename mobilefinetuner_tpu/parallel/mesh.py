"""Device mesh + sharding-spec utilities.

The TPU-native replacement for the reference's single-device ZeRO-style
ParameterSharder (reference: operators/opt_ops/sharding/parameter_sharder.h):
instead of tiering parameters between RAM and disk under a byte budget, we
shard parameters/gradients/optimizer state FSDP-style across chips over ICI
(axis "fsdp") and batch-shard over axis "data". XLA inserts the
all-gather/reduce-scatter collectives; we only annotate shardings.

Mesh axes:
  data — pure data parallelism (batch axis of activations)
  fsdp — ZeRO-3-style parameter/grad/optimizer-state sharding; activations'
         batch axis is also sharded over it (fsdp acts as a second DP axis),
         so the effective data-parallel world is data*fsdp.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(data: int = 1, fsdp: Optional[int] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a 2D ("data", "fsdp") mesh over the available devices.

    fsdp=None → use all remaining devices on the fsdp axis.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if fsdp is None:
        if n % data != 0:
            raise ValueError(f"{n} devices not divisible by data={data}")
        fsdp = n // data
    if data * fsdp != n:
        raise ValueError(f"data*fsdp={data * fsdp} != n_devices={n}")
    arr = np.asarray(devices).reshape(data, fsdp)
    return Mesh(arr, axis_names=("data", "fsdp"))


def single_device_mesh() -> Mesh:
    return make_mesh(data=1, fsdp=1, devices=jax.devices()[:1])


def fsdp_spec_for(shape: Tuple[int, ...], mesh: Mesh,
                  min_size: int = 2 ** 16) -> P:
    """FSDP sharding rule for one parameter: shard the largest axis that
    divides evenly by the fsdp mesh size; replicate small params.

    This is the weight-sharding analog of the reference sharder's per-param
    registration (parameter_sharder.cpp:215-232) — but across chips, not to
    disk. Small params (norms, biases) stay replicated: gathering them is
    cheaper than the latency of tiny collectives.
    """
    n_fsdp = mesh.shape.get("fsdp", 1)
    if n_fsdp <= 1 or int(np.prod(shape)) < min_size:
        return P()
    # Largest divisible axis, ties broken toward the first axis.
    best = None
    for i, d in enumerate(shape):
        if d % n_fsdp == 0 and (best is None or d > shape[best]):
            best = i
    if best is None:
        return P()
    spec = [None] * len(shape)
    spec[best] = "fsdp"
    return P(*spec)


def params_shardings(params, mesh: Mesh, min_size: int = 2 ** 16):
    """Pytree of NamedShardings implementing FSDP over `mesh`."""
    return jax.tree.map(
        lambda x: NamedSharding(mesh, fsdp_spec_for(x.shape, mesh, min_size)),
        params)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch axis sharded over both mesh axes (data-parallel over the full
    device set; fsdp doubles as a DP axis for activations)."""
    return NamedSharding(mesh, P(("data", "fsdp")))


# ------------------------ the layer gather (ZeRO-3) -------------------------
# A layer scan over an FSDP mesh, left to the partitioner with feature-
# sharded weights, computes parts of the block feature-sharded and moves
# [B, S, k·E] activations between layouts (all-to-all). ZeRO-3 does the
# opposite: each layer's weights are gathered whole, in the compute dtype,
# where the layer runs, and the activations stay on the batch axes — so
# every product is local, and the weight gradients go back to their shards
# as one reduce-scatter each, summed in float32 (DESIGN.md §31a).

def gathers_layers(mesh: Optional[Mesh]) -> bool:
    """Does a layer scan over `mesh` gather each layer's weights? Where
    the fsdp axis holds more than one device, and nowhere else: a mesh of
    one fsdp device shards no weight."""
    return mesh is not None and mesh.shape.get("fsdp", 1) > 1


def on_batch(x, mesh: Mesh):
    """x pinned to the batch axes (batch_sharding), its other axes whole."""
    return jax.lax.with_sharding_constraint(x, batch_sharding(mesh))


def gathered(t, mesh: Mesh, dtype):
    """One layer's slice of a sharded leaf, cast to `dtype` on its shard
    and gathered whole on every device."""
    return jax.lax.with_sharding_constraint(t.astype(dtype),
                                            replicated_sharding(mesh))


def layer_slice_sharding(stacked_shape: Tuple[int, ...],
                         mesh: Mesh) -> NamedSharding:
    """Where one layer's slice of a stacked [L, ...] leaf lives under
    params_shardings' rule: the stack's spec without its layer axis."""
    return NamedSharding(
        mesh, P(*tuple(fsdp_spec_for(stacked_shape, mesh))[1:]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def gathered_param(t, mesh: Mesh, dtype, n_layer: int):
    """`gathered`, handed back in t's own dtype: the values a product's
    weights take (rounded to `dtype`) for a leaf that is applied in
    float32, such as a norm's scale. Backward: the cotangent as it comes,
    float32 for a float32 leaf, constrained back to the slice's shard."""
    return gathered(t, mesh, dtype).astype(t.dtype)


def _gathered_param_fwd(t, mesh, dtype, n_layer):
    return gathered(t, mesh, dtype).astype(t.dtype), None


def _gathered_param_bwd(mesh, dtype, n_layer, _, ct):
    return (jax.lax.with_sharding_constraint(
        ct, layer_slice_sharding((n_layer,) + ct.shape, mesh)),)


gathered_param.defvjp(_gathered_param_fwd, _gathered_param_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gathered_dense(x, w, b, mesh: Mesh, dtype, n_layer: int):
    """x @ w + b with w [in, out] and b [out] this layer's slices of the
    FSDP-sharded stacks of `n_layer` layers: both gathered whole in
    `dtype`, x and the product on the batch axes.

    Backward: the weights are gathered again (nothing gathered is kept
    for it), dx is local, and dW / db are the device's own rows summed
    in float32 and constrained back to the slice's shard — one float32
    reduce-scatter each over the devices (an all-reduce where the leaf
    is replicated), where a bf16 product's cotangent would be summed in
    bf16 across chips."""
    return _gathered_product(x, w, b, mesh, dtype)


def _gathered_product(x, w, b, mesh, dtype):
    return on_batch(x @ gathered(w, mesh, dtype) + gathered(b, mesh, dtype),
                    mesh)


def _gathered_dense_fwd(x, w, b, mesh, dtype, n_layer):
    # the residuals: x, the shard in the compute dtype (what the stack's
    # cast kept before), and zero-size markers of the leaves' own dtypes
    return _gathered_product(x, w, b, mesh, dtype), (
        x, w.astype(dtype), jnp.zeros((0,), w.dtype),
        jnp.zeros((0,), b.dtype))


def _gathered_dense_bwd(mesh, dtype, n_layer, res, dy):
    x, w_c, w0, b0 = res
    rows = tuple(range(x.ndim - 1))
    dx = on_batch(dy @ gathered(w_c, mesh, dtype).T, mesh)
    dw = jax.lax.dot_general(x, dy, ((rows, rows), ((), ())),
                             preferred_element_type=jnp.float32)
    db = jnp.sum(dy, axis=rows, dtype=jnp.float32)
    dw, db = (jax.lax.with_sharding_constraint(
        g, layer_slice_sharding((n_layer,) + g.shape, mesh))
        for g in (dw, db))
    return dx, dw.astype(w0.dtype), db.astype(b0.dtype)


gathered_dense.defvjp(_gathered_dense_fwd, _gathered_dense_bwd)


def sp_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sequence-parallel placement: batch over "data", SEQUENCE over
    "fsdp" (ring attention consumes the S shards —
    parallel/ring_attention.py)."""
    return NamedSharding(mesh, P("data", "fsdp"))


def shard_params(params, mesh: Mesh, min_size: int = 2 ** 16):
    """Place a parameter pytree onto the mesh with FSDP shardings
    (multi-host safe: every process holds the same host copy and feeds its
    addressable shards)."""
    from mobilefinetuner_tpu.parallel.distributed import device_put_global
    shardings = params_shardings(params, mesh, min_size)
    return jax.tree.map(device_put_global, params, shardings)


def make_batch_placer(mesh: Optional[Mesh],
                      sequence_parallel: bool = False):
    """Build place(batch) -> placed once, NamedShardings precomputed —
    the per-step closure the async input pipeline (data/prefetch.py)
    issues for batch k+1 while step k computes. Placement is identical
    to `shard_batch`; only WHEN it runs differs. mesh=None returns
    identity (the single-process uncommitted-host-numpy fast path, where
    the jit transfers on dispatch)."""
    from mobilefinetuner_tpu.parallel.distributed import put_batch_global
    if mesh is None:
        return lambda batch: batch
    if not sequence_parallel:
        s = batch_sharding(mesh)
        return lambda batch: put_batch_global(batch, lambda k: s)
    sp = sp_batch_sharding(mesh)
    b_only = NamedSharding(mesh, P("data"))
    # per-sample leaves WITHOUT a sequence axis shard only the batch
    # dim: dropout keys and the fault harness's [B] grad_scale row
    # (a rank-2 S-sharding spec on a rank-1 leaf would reject)
    return lambda batch: put_batch_global(
        batch, lambda k: b_only if k in ("dropout_rng", "grad_scale")
        else sp)


def shard_batch(batch, mesh: Mesh, sequence_parallel: bool = False):
    """Place a batch pytree (leading batch axis) onto the mesh. In
    sequence-parallel mode, [B, S] token arrays shard S over "fsdp";
    per-sample leaves without a sequence axis (dropout_rng keys) shard
    only the batch dim. Multi-host: every process holds the same global
    batch and feeds only its addressable shards
    (parallel/distributed.device_put_global)."""
    return make_batch_placer(mesh, sequence_parallel)(batch)
