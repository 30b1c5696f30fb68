"""ctypes binding + lazy build for the native BPE merge engine.

The shared library is compiled from fast_bpe.cpp on first use with the
system g++ (no pybind11 dependency; plain C ABI + ctypes) and cached next
to the source under a name keyed on the source's hash (native/build.py),
so a library built from any other fast_bpe.cpp is never loaded. Any
failure — no compiler, unwritable dir, load error — logs a warning,
degrades to None, and the tokenizer keeps its pure-Python path
(data/tokenizer_bpe.py), which is the behavioral reference.

Set MFT_NO_NATIVE_BPE=1 to force the Python path (used by parity tests).
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional, Tuple

from mobilefinetuner_tpu.native.build import load_native_library

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "fast_bpe.cpp")
_LIB = os.path.join(_HERE, "libfast_bpe.so")


def _configure(lib: ctypes.CDLL) -> None:
    lib.bpe_create.restype = ctypes.c_void_p
    lib.bpe_destroy.argtypes = [ctypes.c_void_p]
    lib.bpe_add_merge.argtypes = [ctypes.c_void_p,
                                  ctypes.c_char_p,
                                  ctypes.c_char_p]
    lib.bpe_add_token.argtypes = [ctypes.c_void_p,
                                  ctypes.c_char_p,
                                  ctypes.c_int32]
    lib.bpe_load.argtypes = [ctypes.c_void_p,
                             ctypes.c_char_p,
                             ctypes.c_char_p,
                             ctypes.POINTER(ctypes.c_int32),
                             ctypes.c_int32]
    lib.bpe_encode_word.restype = ctypes.c_int32
    lib.bpe_encode_word.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
        ctypes.c_int32]


def load_library() -> Optional[ctypes.CDLL]:
    return load_native_library(_SRC, _LIB, "MFT_NO_NATIVE_BPE", _configure)


class NativeBPE:
    """One engine instance per tokenizer: merges + vocab loaded once."""

    def __init__(self, merges: List[Tuple[str, str]], vocab):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native BPE library unavailable")
        self._lib = lib
        self._h = lib.bpe_create()
        # one FFI call per table (not per entry — real GPT-2 has ~50k of
        # each); mapped tokens never contain ' ', '\n', or NUL
        merges_blob = "".join(f"{a} {b}\n" for a, b in merges)
        tokens = list(vocab)
        vocab_blob = "".join(t + "\n" for t in tokens)
        ids = (ctypes.c_int32 * len(tokens))(
            *(int(vocab[t]) for t in tokens))
        lib.bpe_load(self._h, merges_blob.encode("utf-8"),
                     vocab_blob.encode("utf-8"), ids, len(tokens))

    def encode_word(self, mapped_word: str, unk_id: int) -> List[int]:
        """ids for one byte->unicode-mapped word (matches the Python
        _bpe + vocab-lookup result exactly)."""
        raw = mapped_word.encode("utf-8")
        cap = max(len(mapped_word), 1)
        while True:
            buf = (ctypes.c_int32 * cap)()
            n = self._lib.bpe_encode_word(self._h, raw, buf, cap, unk_id)
            if n >= 0:
                return list(buf[:n])
            cap *= 2

    def __del__(self):
        try:
            self._lib.bpe_destroy(self._h)
        except Exception:
            pass
