"""Shared lazy-build + load machinery for the native C++ engines.

One copy of the scheme all three engines use (fast_bpe, fast_gemma_bpe,
fast_safetensors): compile the .cpp next to it with the system g++ on
first use (plain C ABI — no pybind11) and cache the .so beside the
source, NAMED BY A HASH OF THAT SOURCE (`libfast_bpe.<sha12>.so`). A
library built from any other .cpp — an older checkout's, a copied
disk's — has a different name and is never loaded; mtimes play no part
(a checkout or a disk copy rewrites them). The libraries are
git-ignored, so a fresh checkout builds its own on first use.

A build or load failure logs a warning and degrades to None so the
pure-Python reference path takes over; `engines_in_use()` says which
path each engine is on (chip_smoke.py prints it). An env kill switch per
engine forces the Python path (parity tests use it).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Callable, Optional

from mobilefinetuner_tpu.core.logging import get_logger

_lock = threading.Lock()
_caches: dict = {}  # lib_path -> [lib_or_None]


def library_path(src: str, lib_path: str) -> str:
    """`<stem>.<sha256(src)[:12]>.so` for the nominal `<stem>.so`."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    stem, ext = os.path.splitext(lib_path)
    return f"{stem}.{digest}{ext}"


def _build(src: str, out: str) -> bool:
    # unique temp output: concurrent builders (pytest-xdist, two CLIs)
    # must not interleave writes into one file and install a corrupt .so
    tmp = f"{out}.tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-shared", "-fPIC", src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        get_logger().warning(
            f"native build failed for {os.path.basename(src)} "
            f"({type(e).__name__}); using the Python path. "
            f"{detail.decode(errors='replace')[-300:]}")
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def load_native_library(src: str, lib_path: str, disable_env: str,
                        configure: Callable[[ctypes.CDLL], None]
                        ) -> Optional[ctypes.CDLL]:
    """Load (building if absent) the library for THIS source; `configure`
    sets the ctypes restype/argtypes. Returns None when disabled or
    unavailable."""
    if os.environ.get(disable_env) == "1":
        return None
    with _lock:
        cache = _caches.setdefault(lib_path, [])
        if cache:
            return cache[0]
        lib = None
        try:
            hashed = library_path(src, lib_path)
            if os.path.exists(hashed) or _build(src, hashed):
                lib = ctypes.CDLL(hashed)
                configure(lib)
                # libraries of other source versions are dead weight
                stem, ext = os.path.splitext(lib_path)
                for old in glob.glob(f"{stem}*{ext}"):
                    if old != hashed:
                        try:
                            os.unlink(old)
                        except OSError:
                            pass
        except (OSError, AttributeError) as e:
            get_logger().warning(
                f"native library {os.path.basename(lib_path)} unusable "
                f"({type(e).__name__}: {e}); using the Python path")
            lib = None
        cache.append(lib)
        return lib


def engines_in_use() -> dict:
    """{engine: "native" | "python"} after attempting each load — which
    implementation a run's tokenizer / checkpoint I/O is actually on."""
    from mobilefinetuner_tpu.native import (fast_bpe, fast_gemma_bpe,
                                            fast_safetensors)
    mods = {"bpe": fast_bpe, "gemma_bpe": fast_gemma_bpe,
            "safetensors": fast_safetensors}
    return {name: "native" if m.load_library() is not None else "python"
            for name, m in mods.items()}
