"""Device-memory accounting shared by the training loop and benchmarks.

The compile-time peak is the TPU-native analog of the reference's RSS
reporting (reference: scripts/Finetune/measure_rss.sh:22-42,
performance_monitor.h:18-33 MemorySnapshot): XLA's memory analysis of a
compiled program is exact for static shapes, and unlike runtime
memory_stats() it is available on every platform (the CPU test backend
reports no memory_stats at all).
"""

from __future__ import annotations

import math
import re

import jax


def compiled_peak_bytes(compiled) -> int:
    """Peak device memory of a compiled program: arguments + temps +
    outputs minus donated aliases. Returns 0 when the backend does not
    report memory analysis."""
    try:
        ma = compiled.memory_analysis()
        return int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                   + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        return 0


def compiled_peak_mb(compiled) -> float:
    return compiled_peak_bytes(compiled) / 2 ** 20


_HLO_RESULT = re.compile(
    r"= \w+\[([\d,]*)\]\S* (copy|copy-start|transpose)\(")


def sized_copies(compiled, min_elems: int) -> list:
    """HLO lines of `compiled` (fused computations included) where a
    copy or transpose PRODUCES at least `min_elems` elements, whatever
    the layout or the dims a bitcast gave it — the "no program converts
    a whole KV pool between layouts" primitive (serve/paged_kv.py's
    contract; asserted by tests/test_aot_tpu.py, entry and exit copies
    included: they are ordinary `copy` instructions of the module)."""
    out = []
    for ln in compiled.as_text().splitlines():
        m = _HLO_RESULT.search(ln)
        if m and math.prod(int(d) for d in m.group(1).split(",") if d) \
                >= min_elems:
            out.append(ln.strip())
    return out


# opcodes that move nothing on the device: a trace shows none of them
_NO_DEVICE_OP = frozenset({"bitcast", "get-tuple-element", "parameter",
                           "constant", "tuple"})


def device_ops_from(compiled, path_part: str, in_loops: bool = False
                    ) -> list:
    """HLO lines of `compiled` that run as device ops of their own — a
    fusion counts once, its body not; no bitcast, tuple, parameter or
    constant — whose innermost source frame lies in a file whose path
    holds `path_part`; with `in_loops`, only those in the body of a
    while loop (the layer scan: XLA sinks a cheap producer of a loop
    operand INTO the body, whatever its op_name says). What a device
    trace attributes to that file: the "how many ops a layer does a
    reader that matches this file average over" primitive
    (tests/test_aot_tpu.py pins the paged kernel's file to ONE op a
    layer: the custom call)."""
    text = compiled.as_text()

    def table(name):
        if f"\n{name}\n" not in text:
            return {}
        rows = text.split(f"\n{name}\n", 1)[1].split("\n\n", 1)[0]
        return {int(k): v for k, v in re.findall(r"^(\d+) (.*)$", rows,
                                                  re.M)}

    def ids(rows, key, keep):
        return {k for k, v in rows.items()
                if int(re.search(rf"{key}=(\d+)", v).group(1)) in keep}

    files = {k for k, v in table("FileNames").items() if path_part in v}
    frames = ids(table("StackFrames"), "file_location_id",
                 ids(table("FileLocations"), "file_name_id", files))
    bodies = set(re.findall(r" while\(.*body=(%[\w.\-]+)", text))
    out, comp = [], ""
    for ln in text.splitlines():
        if ln and not ln[0].isspace():      # a computation's header / "}"
            comp = ln.split(" (")[0].replace("ENTRY ", "")
        m = re.search(r" = (?:\(.*?\)|\S+) ([\w\-]+)\(.*stack_frame_id=(\d+)",
                      ln)         # (a tuple-shaped result has spaces)
        if m and m.group(1) not in _NO_DEVICE_OP \
                and int(m.group(2)) in frames \
                and not comp.startswith(("%fused_computation", "%region")) \
                and (comp in bodies or not in_loops):
            out.append(ln.strip())
    return out


def shaped_all_gathers(compiled, shape, dtypes=("f32", "bf16")) -> list:
    """HLO lines of `compiled` where an all-gather involves a tensor of
    exactly `shape` — the sharding-assertion primitive behind "the
    V-sharded embed table is never all-gathered" (vocab-parallel CE,
    ops/loss.py; asserted by tests/test_multichip.py and
    __graft_entry__.dryrun_multichip)."""
    table = "[" + ",".join(str(d) for d in shape) + "]"
    needles = [f"{dt}{table}" for dt in dtypes]
    return [ln for ln in compiled.as_text().splitlines()
            if "all-gather" in ln and any(n in ln for n in needles)]


def memory_stat(device, key: str, default=None):
    """One guarded read of `device.memory_stats()[key]`. Platforms
    return None, {}, or PARTIAL dicts — e.g. bytes_in_use present but
    bytes_limit absent — and a consumer indexing the dict directly
    KeyErrors exactly on those backends. A missing, non-dict, or
    non-numeric entry is `default`, never an exception, so every
    memory_stats consumer (live_hbm_mb here, memory_guard's capacity
    probe, the observatory's backfill) shares one contract."""
    try:
        stats = device.memory_stats() or {}
    except Exception:
        return default
    if not hasattr(stats, "get"):
        return default
    v = stats.get(key, default)
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return default
    return v


_no_stats_logged = set()  # backends already warned about (log once)


def device_hbm_mb(devices=None) -> list:
    """bytes_in_use of each local device in MB, in jax.local_devices()
    order (None where a device reports none) — per device, not the max,
    because placement proofs need it: an FSDP-sharded base shows as N
    devices each holding about 1/N of what one device held.
    `devices`: override for tests."""
    if devices is None:
        devices = jax.local_devices()
    out = []
    for d in devices:
        in_use = memory_stat(d, "bytes_in_use")
        out.append(None if in_use is None else in_use / 2 ** 20)
    return out


def live_hbm_mb(devices=None):
    """MAX device bytes-in-use across the local devices, from
    memory_stats() (a directly attached TPU reports bytes_in_use,
    peak_bytes_in_use and bytes_limit per device; this jax's CPU backend
    returns None). The max — not device 0 — because shards can be
    imbalanced (e.g. a vocab-parallel embed remainder landing on one
    chip) and the binding constraint is the fullest device.

    Returns None — not 0.0 — when NO device reported a bytes_in_use:
    a zero would silently masquerade as "nothing allocated" in the
    telemetry hbm_mb field, when the truth is "this backend cannot
    say" (the field is emitted as null and a one-time log names the
    backend). `devices`: override for tests; defaults to
    jax.local_devices()."""
    if devices is None:
        try:
            devices = jax.local_devices()
        except Exception:
            return None
    per_device = [mb for mb in device_hbm_mb(devices) if mb is not None]
    if per_device:
        return max(per_device)
    platform = getattr(devices[0], "platform", "unknown") if devices \
        else "unknown"
    if platform not in _no_stats_logged:
        _no_stats_logged.add(platform)
        from mobilefinetuner_tpu.core.logging import get_logger
        get_logger().info(
            f"backend {platform!r} exposes no memory_stats bytes_in_use; "
            f"live-HBM telemetry will be null (compiled-peak estimates "
            f"still apply)")
    return None


def compiled_flops(compiled) -> float:
    """XLA's own FLOP count for a compiled executable: cost_analysis()
    is one flat dict on the installed jax. 0.0 when the backend reports
    no "flops" entry (the `compile` event then carries null)."""
    return float(compiled.cost_analysis().get("flops", 0.0))


def tpu_custom_calls(hlo_text: str) -> int:
    """How many Mosaic (Pallas TPU) kernels a compiled program's text
    (`compiled.as_text()`) contains — the observable behind "which
    implementation did `auto` resolve to": interpret mode and the XLA
    reference paths leave 0."""
    return hlo_text.count('custom_call_target="tpu_custom_call"')
