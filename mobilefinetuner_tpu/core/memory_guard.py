"""HBM admission: capacity resolution, preflight verdicts, and the
degradation-ladder vocabulary (DESIGN.md §21).

An out-of-memory config is the one fault class rounds 13-15 left
unrecoverable: a bad `--batch_size` kills a run minutes into setup, and
an XLA RESOURCE_EXHAUSTED mid-fleet burns controller restart budget on
a fault no restart can fix. This module turns the memory question into
an ADMISSION decision made immediately after AOT compile — when XLA's
memory analysis gives the exact per-device peak for free and nothing
expensive (data loading, stream threads, first dispatch) has happened
yet:

  est_mb   compiled peak (arguments + temps + outputs - donated
           aliases) plus any LIVE device bytes the step's own arguments
           do not account for (prefetched batches, ballast, a second
           compiled program's buffers);
  cap_mb   per-device capacity — `--hbm_cap_mb` override first (CPU
           tests drive the verdict deterministically with it), then
           the backend's memory_stats()["bytes_limit"] (what a
           directly attached TPU reports), then a device-kind table of
           public HBM sizes for backends that report no limit;
  verdict  "over" when est_mb exceeds cap_mb under the `--hbm_headroom`
           margin, "ok" when it fits, "unknown" when either side of
           the comparison is unavailable (never guess a refusal).

Consumers: cli/common.run_training (preflight + the remat -> accum x2
-> offload degradation ladder), the eval CLIs (preflight only), and
serve/engine.ServeEngine (analytic pool+params admission at build).
Every check lands in the telemetry stream as a `mem_check` event and
every ladder decision as a `degrade` event (core/telemetry.py).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

from mobilefinetuner_tpu.core.logging import get_logger
from mobilefinetuner_tpu.core.xla_stats import (compiled_peak_mb,
                                                device_hbm_mb, live_hbm_mb)

log = get_logger()


class MemoryAdmissionError(RuntimeError):
    """A config that cannot fit device memory was refused — at preflight
    (fail-fast, nothing ran) or after the degradation ladder ran dry
    (`ladder` records every rung attempted). Named so fleet tooling can
    tell an inadmissible CONFIG from a crash a restart might fix: the
    r13 controller must not burn restart budget re-launching it."""

    def __init__(self, message: str, check: "MemCheck" = None,
                 ladder: Tuple[str, ...] = ()):
        super().__init__(message)
        self.check = check
        self.ladder = tuple(ladder)


# Per-device HBM capacity in MB by device_kind substring (public chip
# specs) — the fallback when the platform exposes no
# memory_stats()["bytes_limit"].
# Matched longest-substring-first so "v5 lite" wins over "v5", same
# convention as telemetry.DEVICE_PEAK_FLOPS.
DEVICE_HBM_MB = {
    "v5 lite": 16 * 1024, "v5litepod": 16 * 1024, "v5e": 16 * 1024,
    "v6 lite": 32 * 1024, "v6e": 32 * 1024,
    "v5p": 95 * 1024,
    "v4": 32 * 1024,
    "v3": 16 * 1024,
    "v2": 8 * 1024,
}

# The ordered, bounded degradation ladder (DESIGN.md §21): cheapest
# semantic change first. Each rung recompiles and re-preflights; loss
# trajectory stays parity-pinned (remat recomputes identical math;
# accum x2 halves the scanned micro-batch at CONSTANT global batch —
# only float reassociation moves, <=1e-5; offload changes placement,
# not values).
LADDER = ("remat", "accum_x2", "offload")


def device_capacity_mb(override_mb: float = 0,
                       device=None) -> Tuple[Optional[float], str]:
    """(per-device capacity MB or None, source) — source is one of
    "flag" (--hbm_cap_mb), "memory_stats" (bytes_limit), "device_table"
    (DEVICE_HBM_MB by kind), "unknown". None means no refusal can be
    grounded: the verdict must be "unknown", never a guess."""
    if override_mb:
        return float(override_mb), "flag"
    if device is None:
        try:
            import jax
            device = jax.local_devices()[0]
        except Exception:
            return None, "unknown"
    # guarded via xla_stats.memory_stat: some platforms return PARTIAL
    # dicts (bytes_in_use without bytes_limit) — a missing key must fall
    # through to the device table, never raise
    from mobilefinetuner_tpu.core.xla_stats import memory_stat
    limit = memory_stat(device, "bytes_limit", 0)
    if limit:
        return limit / 2 ** 20, "memory_stats"
    kind = str(getattr(device, "device_kind", "")).lower()
    for sub in sorted(DEVICE_HBM_MB, key=len, reverse=True):
        if sub in kind:
            return float(DEVICE_HBM_MB[sub]), "device_table"
    return None, "unknown"


@dataclasses.dataclass
class MemCheck:
    """One admission verdict. `event()` is the `mem_check` telemetry
    payload; `describe()` the human line the error/log carries."""
    est_mb: Optional[float]        # compiled peak + unaccounted live
    cap_mb: Optional[float]        # per-device capacity (None: unknown)
    verdict: str                   # "ok" | "over" | "unknown"
    phase: str = "preflight"       # preflight | dispatch | serve_build
    headroom: float = 0.1
    compiled_mb: Optional[float] = None   # XLA memory-analysis peak
    live_mb: Optional[float] = None       # bytes_in_use at check time
                                          # (the fullest device)
    live_mb_by_device: Optional[list] = None  # ... and of every device
    cap_source: str = "unknown"

    @property
    def cap_frac(self) -> Optional[float]:
        """est / cap — the headline "how close to the ceiling" number
        (bench.py renders it next to peak_hbm_mb)."""
        if not self.est_mb or not self.cap_mb:
            return None
        return round(self.est_mb / self.cap_mb, 4)

    def event(self) -> dict:
        return {"est_mb": round(self.est_mb, 2) if self.est_mb else None,
                "cap_mb": round(self.cap_mb, 2) if self.cap_mb else None,
                "verdict": self.verdict, "phase": self.phase,
                "headroom": self.headroom, "cap_frac": self.cap_frac,
                "compiled_mb": (round(self.compiled_mb, 2)
                                if self.compiled_mb else None),
                "live_mb": (round(self.live_mb, 2)
                            if self.live_mb is not None else None),
                "live_mb_by_device": (
                    [None if mb is None else round(mb, 2)
                     for mb in self.live_mb_by_device]
                    if self.live_mb_by_device is not None else None),
                "cap_source": self.cap_source}

    def describe(self) -> str:
        est = f"{self.est_mb:.0f} MB" if self.est_mb else "unknown"
        cap = (f"{self.cap_mb:.0f} MB ({self.cap_source})"
               if self.cap_mb else "unknown")
        return (f"estimated {est} vs capacity {cap} under "
                f"{self.headroom:.0%} headroom -> {self.verdict}")


def _verdict(est_mb: Optional[float], cap_mb: Optional[float],
             headroom: float) -> str:
    if not est_mb or not cap_mb:
        return "unknown"
    return "over" if est_mb > cap_mb * (1.0 - headroom) else "ok"


def preflight(compiled, cap_mb: float = 0, headroom: float = 0.1,
              devices=None, phase: str = "preflight") -> MemCheck:
    """Admission check for a compiled executable: XLA's memory-analysis
    peak plus any live device bytes its own arguments do not cover
    (params already count as arguments — only the surplus beyond them
    is added, so nothing is double-billed), against per-device capacity
    under the headroom margin. Backends without memory analysis (or
    with no resolvable capacity) yield verdict "unknown": admission
    never refuses on a guess."""
    compiled_mb = compiled_peak_mb(compiled) if compiled is not None \
        else 0.0
    arg_mb = 0.0
    try:
        arg_mb = compiled.memory_analysis().argument_size_in_bytes / 2 ** 20
    except Exception:
        pass
    live = live_hbm_mb(devices)
    extra = max(live - arg_mb, 0.0) if live is not None else 0.0
    est = (compiled_mb + extra) if compiled_mb else None
    cap, source = device_capacity_mb(override_mb=cap_mb)
    return MemCheck(est_mb=est, cap_mb=cap,
                    verdict=_verdict(est, cap, headroom), phase=phase,
                    headroom=headroom, compiled_mb=compiled_mb or None,
                    live_mb=live,
                    live_mb_by_device=(device_hbm_mb(devices)
                                       if live is not None else None),
                    cap_source=source)


def analytic_check(est_mb: float, cap_mb: float = 0, headroom: float = 0.1,
                   phase: str = "serve_build") -> MemCheck:
    """Admission check from an ANALYTIC estimate (the serve engine's
    params + adapter bank + KV pool sum, computed before anything is
    allocated — a refusal must cost nothing)."""
    cap, source = device_capacity_mb(override_mb=cap_mb)
    return MemCheck(est_mb=float(est_mb), cap_mb=cap,
                    verdict=_verdict(est_mb, cap, headroom), phase=phase,
                    headroom=headroom, cap_source=source)


def is_resource_exhausted(err: BaseException) -> bool:
    """True for XLA's out-of-memory family (XlaRuntimeError carries the
    absl status name in its message) — the dispatch/compile signal the
    degradation ladder treats as a failed admission rather than a
    crash. Matched on the status text so the check needs no jaxlib
    import (and covers the injected simulation on CPU)."""
    return "RESOURCE_EXHAUSTED" in str(err)


_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def host_rss_mb() -> Optional[float]:
    """This process's resident set size in MB (Linux /proc/self/statm;
    None where unavailable) — the host-side pressure signal the
    prefetch producer's shed guard reads BEFORE the OS OOM-killer
    picks a victim (data/prefetch.py)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE / 2 ** 20
    except (OSError, ValueError, IndexError):
        return None
