"""Which devices a process sees, decided BEFORE its JAX backend starts.

Three helpers, none of which initialises a backend:

  force_host_devices(n)  a virtual N-device CPU platform, the test and
      dryrun harness (the analog of the reference's mocked-telemetry
      testing culture, SURVEY.md §4.6): FSDP/mesh code paths must run
      without a TPU. Shared by tests/conftest.py, the tools' CPU contract
      paths and __graft_entry__.dryrun_multichip.
  pin_tpu_chip(k)        this process takes chip k of a multi-chip host
      and no other — a chip belongs to one process at a time, so N
      one-chip replicas (tools/serve_router.py) each pin their own.
  backend_initialized()  has this process touched a backend yet? A
      parent that has holds the chip, and a child that needs it then
      fails or hangs — launchers assert on this before they spawn.

Notes on the CPU platform: XLA reads
--xla_force_host_platform_device_count from XLA_FLAGS at backend init; an
existing entry with a DIFFERENT value must be rewritten, not just
detected by substring. The CPU backend never separates host from device
memory (host RAM IS its device memory), so the offload peak-memory proof
(tools/check_stream_memory.py) runs on the machine's default accelerator
platform in a subprocess and skips on cpu.
"""

from __future__ import annotations

import os
import re
import sys


def force_host_devices(n: int) -> None:
    """Arrange for jax to expose >= n virtual CPU devices.

    Must run before the first jax backend initialization to take full
    effect; afterwards it is best-effort (config update may raise if the
    backend is live — swallowed, callers assert on jax.devices()).
    """
    flag = "--xla_force_host_platform_device_count"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(flag + r"=(\d+)", flags)
    if m:
        if int(m.group(1)) < n:
            flags = re.sub(flag + r"=\d+", f"{flag}={n}", flags)
    else:
        flags = (flags + f" {flag}={n}").strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    if jax.config.jax_platforms != "cpu":
        # jax was imported before the env override (its config captured
        # JAX_PLATFORMS at import): force the platform via config
        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass


def pin_tpu_chip(k: int) -> None:
    """Restrict THIS process to chip k (0-based) of its host: libtpu
    reads these variables when the backend starts, so call before the
    first jax use. Each pinned process is its own one-chip "slice"
    (process bounds 1,1,1) and needs its own runtime port."""
    if backend_initialized():
        raise RuntimeError(
            "pin_tpu_chip must run before the JAX backend initialises")
    os.environ.update({
        "TPU_VISIBLE_CHIPS": str(k),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{8476 + k}",
        "TPU_PROCESS_PORT": str(8476 + k),
        "CLOUD_TPU_TASK_ID": "0",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    })


def backend_initialized() -> bool:
    """True once this process has initialised a JAX backend (importing
    jax alone does not)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()
