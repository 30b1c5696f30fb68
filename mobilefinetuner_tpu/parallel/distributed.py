"""Multi-host (multi-process) distributed backend.

The reference is strictly single-process (SURVEY.md §2.11: no NCCL/MPI/
sockets anywhere); its only "scale-out" axis is gradient accumulation. The
TPU-native rebuild gets real scale-out from XLA's compiled collectives, and
this module supplies the process-level runtime around them:

  * `initialize(...)` — bring up the JAX distributed service
    (`jax.distributed.initialize`), which wires the coordination service +
    per-host device visibility. On TPU pods every argument is auto-detected
    from the metadata environment; off-pod (CPU/GPU fleets or explicit
    testing) the coordinator address / process count / process id come from
    flags or the standard `JAX_COORDINATOR_ADDRESS` / `JAX_NUM_PROCESSES` /
    `JAX_PROCESS_ID` environment variables.
  * `make_hybrid_mesh(...)` — a ("data", "fsdp") mesh laid out so the
    "fsdp" axis (param all-gathers / grad reduce-scatters every step) rides
    ICI inside each host's slice, and the "data" axis (one grad all-reduce
    per step) crosses the DCN host boundary — the standard
    bandwidth-hierarchy-aware layout (scaling-book recipe; built on
    `mesh_utils.create_hybrid_device_mesh`).
  * `device_put_global(...)` / `put_batch_global(...)` — multi-host batch
    feeding. Under multi-host jit every argument must be a global
    `jax.Array` spanning all processes; `jax.device_put` of host numpy
    cannot produce one. Each process runs the SAME seeded data pipeline
    (identical global batch everywhere — WikiText-2 is small and
    tokenization is cheap/pretokenizable), and
    `jax.make_array_from_callback` slices out exactly the shards addressable
    from this process. No cross-host data exchange ever happens on the
    input path — which is also what makes the async prefetcher
    (data/prefetch.py) multi-host safe: placement is collective-free, so
    issuing batch k+1's put while step k computes needs no cross-process
    coordination, and every process's background producer yields the same
    seeded sequence.

Single-process runs (including every test and the single-chip bench)
pass through all of this untouched: `initialize` is a no-op without
a multi-process request, and `device_put_global` degrades to a plain
sharded device_put.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from mobilefinetuner_tpu.core.logging import get_logger

log = get_logger()

_INITIALIZED = False


def env_int(name: str) -> Optional[int]:
    v = os.environ.get(name, "")
    return int(v) if v else None


def initialize(coordinator: str = "", num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               force: bool = False, connect_retries: int = 4,
               connect_backoff_s: float = 1.0,
               connect_backoff_cap_s: float = 10.0) -> bool:
    """Start the JAX distributed runtime when a multi-process run is
    requested; returns True iff it was (or already had been) started.

    Resolution order per field: explicit argument > JAX_* env var > TPU-pod
    auto-detection (passing None lets jax probe the pod metadata server).
    `force=True` (the --multihost flag) starts the runtime even with no
    explicit addressing — the TPU-pod case, where every argument is
    auto-detected; off-pod, a failed auto-detection degrades to
    single-process with a warning instead of crashing, so the same command
    line works on a pod and on a dev box.

    A plain single-process invocation (no flag, no env, pod size 1) is a
    no-op so the CLI entry points never hang waiting for phantom peers.

    Explicitly-addressed connections RETRY with capped exponential
    backoff (`connect_retries`/`connect_backoff_s`) before failing: at
    fleet-restart time — exactly when the elastic controller relaunches
    everything at once — the workers race the coordinator coming back
    up, and failing fast on that race turns one recovered host into a
    second fleet restart. Every attempt is logged; after the budget the
    ORIGINAL error raises (not a wrapper), so the operator sees the
    real failure, not the retry machinery.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return True
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS", "")
    num_processes = num_processes if num_processes is not None \
        else env_int("JAX_NUM_PROCESSES")
    process_id = process_id if process_id is not None \
        else env_int("JAX_PROCESS_ID")
    want = force or bool(coordinator) or (num_processes or 1) > 1
    if not want:
        return False
    explicit = bool(coordinator) or (num_processes or 1) > 1
    budget = max(connect_retries, 0) if explicit else 0
    first_err: Optional[BaseException] = None
    for attempt in range(budget + 1):
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator or None,
                num_processes=num_processes, process_id=process_id)
            break
        except Exception as e:
            if not explicit:
                # --multihost with nothing to address: degrade to
                # single-process (dev-box behavior), never retry
                log.warning(f"--multihost: auto-detection failed ({e}); "
                            f"continuing single-process")
                return False
            first_err = first_err or e
            if attempt >= budget:
                raise first_err
            delay = min(connect_backoff_s * (2 ** attempt),
                        connect_backoff_cap_s)
            log.warning(
                f"distributed: coordinator connect attempt "
                f"{attempt + 1}/{budget + 1} failed "
                f"({type(e).__name__}: {e}); retrying in {delay:.1f}s")
            time.sleep(delay)
    _INITIALIZED = True
    log.info(f"distributed: process {jax.process_index()}"
             f"/{jax.process_count()} up, "
             f"{len(jax.local_devices())} local / "
             f"{len(jax.devices())} global devices")
    return True


def is_coordinator() -> bool:
    """True on the process that owns logging/checkpoint writes."""
    return jax.process_index() == 0


def make_hybrid_mesh(data: int = 1, fsdp: Optional[int] = None) -> Mesh:
    """("data", "fsdp") mesh over ALL processes' devices, DCN-aware.

    Layout rule: the fsdp axis is packed within each host's ICI domain
    (param all-gather + grad reduce-scatter are the per-step bandwidth
    hogs), and the data axis absorbs the cross-host DCN dimension (its
    only per-step collective is one gradient all-reduce). Concretely, with
    P processes × L local devices and a request (data=D, fsdp=F):

      * F must fit in one host's slice (F divides L): fsdp lives on ICI.
      * D = (L//F per host) × P: the data axis spans hosts.

    Requests that cannot honor the hierarchy (F > L) fall back to
    `mesh_utils.create_device_mesh`'s global layout with a warning rather
    than failing — correctness never depends on the layout, only the
    collective latency does.

    Single-process: equivalent to parallel.mesh.make_mesh (same axis
    names, same shapes), so downstream sharding code cannot tell the
    difference.
    """
    from jax.experimental import mesh_utils

    n_global = len(jax.devices())
    n_local = len(jax.local_devices())
    n_proc = jax.process_count()
    if fsdp is None or fsdp == 0:
        if n_global % data != 0:
            raise ValueError(f"{n_global} devices not divisible by "
                             f"data={data}")
        fsdp = n_global // data
    if data * fsdp != n_global:
        raise ValueError(
            f"data*fsdp={data * fsdp} != global devices={n_global}")
    if n_proc == 1:
        devices = mesh_utils.create_device_mesh((data, fsdp))
        return Mesh(devices, axis_names=("data", "fsdp"))
    if n_local % fsdp == 0:
        # fsdp within a host (ICI), data = local remainder × processes (DCN)
        ici_data = n_local // fsdp
        try:
            devices = mesh_utils.create_hybrid_device_mesh(
                mesh_shape=(ici_data, fsdp),
                dcn_mesh_shape=(n_proc, 1))
        except ValueError:
            # Platforms without slice_index granules (CPU multi-process
            # testing): group by process_index by hand — the data axis is
            # process-major, so fsdp still never crosses a process.
            by_proc = {}
            for d in sorted(jax.devices(), key=lambda d: (d.process_index,
                                                          d.id)):
                by_proc.setdefault(d.process_index, []).append(d)
            rows = [np.asarray(ds).reshape(ici_data, fsdp)
                    for _, ds in sorted(by_proc.items())]
            devices = np.concatenate(rows, axis=0)
        return Mesh(devices, axis_names=("data", "fsdp"))
    log.warning(
        f"fsdp={fsdp} does not fit one host's {n_local} local devices; "
        f"fsdp collectives will cross DCN (slower, still correct)")
    devices = mesh_utils.create_device_mesh((data, fsdp))
    return Mesh(devices, axis_names=("data", "fsdp"))


def device_put_global(x, sharding) -> jax.Array:
    """device_put that also works when `sharding` spans processes this
    host cannot address (multi-host jit inputs must be global jax.Arrays;
    plain device_put of host data cannot build one). `x` must hold the
    same global value on every process — true for checkpoint loads (every
    host reads the same file), the seeded data pipeline, and step-folded
    dropout keys. Single-process this is exactly device_put — device-
    resident leaves are NOT synced to host."""
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    x = np.asarray(x)  # multi-process only: feed shards from a host copy
    return jax.make_array_from_callback(x.shape, sharding,
                                        lambda idx: x[idx])


def put_batch_global(batch: dict, sharding_for) -> dict:
    """One placement pass over a batch dict: `sharding_for(key)` names
    each leaf's sharding, `device_put_global` makes the transfer (global
    under multi-host, plain async device_put single-process). This is the
    shard-aware placement primitive behind `mesh.shard_batch` and the
    input pipeline's lookahead placer (`mesh.make_batch_placer`)."""
    return {k: device_put_global(v, sharding_for(k))
            for k, v in batch.items()}


def allgather_scalars(value: float) -> list:
    """Every process's copy of a host-side scalar, as a plain list indexed
    by process: the straggler-attribution primitive (cli/common.py feeds
    each host's measured per-step wall time through on the
    --straggler_cadence boundary, and the coordinator compares the fleet).
    COLLECTIVE under multi-process — every process must call it at the
    same step, which the deterministic cadence guarantees. Single-process:
    [value], no device work at all, so the single-host path costs
    nothing."""
    if jax.process_count() == 1:
        return [float(value)]
    from jax.experimental import multihost_utils
    out = multihost_utils.process_allgather(
        np.asarray([value], np.float32))
    return [float(v) for v in np.asarray(out).reshape(-1)]


def gather_to_host(tree):
    """Bring a (possibly cross-process-sharded) pytree fully to host for
    checkpoint writing. COLLECTIVE under multi-process: every process must
    call it (process_allgather runs a psum under the hood); afterwards
    only the coordinator needs to write the result. Single-process:
    returns the tree unchanged (savers device_get as usual)."""
    if jax.process_count() == 1:
        return tree
    from jax.experimental import multihost_utils

    def pull(x):
        if not isinstance(x, jax.Array):
            return x
        if x.is_fully_addressable or x.is_fully_replicated:
            return np.asarray(x)
        return multihost_utils.process_allgather(x, tiled=True)

    return jax.tree.map(pull, tree)
