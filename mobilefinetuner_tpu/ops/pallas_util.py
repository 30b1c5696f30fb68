"""Shared plumbing for the Pallas kernels (flash attention, fused CE,
decode attention, LoRA epilogue)."""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def interpret_mode() -> bool:
    """Pallas interpret mode off-TPU (CPU test mesh, SURVEY.md §4.6) —
    the ONE copy of the policy every kernel consults. Kernels read it
    through this module at call time (tests/test_aot_tpu.py steers it
    to compile the real kernels for a described chip), so a process on
    a TPU backend never interprets and a CPU process never claims a
    kernel ran: `tpu_custom_call` in the compiled text is the proof."""
    return jax.default_backend() != "tpu"


def tpu_call_params(*dimension_semantics: str,
                    vmem_limit_bytes=None) -> dict:
    """The compiler_params + interpret kwargs every gridded pallas_call
    in this tree passes — one copy of the dimension-semantics plumbing
    so a kernel cannot set semantics without also consulting the
    interpret policy. Returns a dict to splat into pl.pallas_call.
    vmem_limit_bytes: ask for more than the 16 MiB of scoped VMEM a
    kernel gets by default (None: the default, the params every kernel
    had before one needed more)."""
    extra = {} if vmem_limit_bytes is None \
        else {"vmem_limit_bytes": int(vmem_limit_bytes)}
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=dimension_semantics, **extra),
        interpret=interpret_mode())


def partitioned_impl(flag: str, impl: str, kernel: str, plain: str,
                     n_devices: int) -> str:
    """An implementation switch's value for a step GSPMD partitions over
    `n_devices`, for a kernel that is NOT wrapped in shard_map where it
    is called. A Mosaic kernel cannot be partitioned automatically (the
    TPU lowering refuses: "wrap the call in a shard_map"), and interpret
    mode never shows it — the CPU mesh tests partition the interpreted
    kernel like any XLA op. So over more than one device `auto` means
    the XLA path `plain`, and asking for `kernel` by name is an error
    here rather than deep inside the compile. Its two callers: the LoRA
    epilogue over a training mesh (cli/common.build_mesh) and the serve
    engine's one-shot prefill, whose heads are sharded over `tp`
    (serve/engine.py). The kernels that DO run on a mesh are wrapped
    where they are called and never come here: training attention over
    the batch axes (ops/attention.attention), sharded_paged_attend,
    ring attention's partials, the vocab-parallel CE."""
    if n_devices <= 1 or impl == plain:
        return impl
    if impl == kernel:
        raise ValueError(
            f"{flag}={kernel!r} is a Pallas kernel outside shard_map: it "
            f"cannot be partitioned over a {n_devices}-device mesh; use "
            f"{plain!r} (what 'auto' selects there)")
    return plain
