"""SafeTensors reader/writer — self-contained implementation of the format
(8-byte little-endian header length + JSON header + raw blob).

Reference: operators/finetune_ops/graph/safetensors_loader.{h,cpp}
(`SafeTensorsReader`, safetensors_loader.h:45-92) and the hand-written writer
in gpt2_full_finetune/main.cpp:156-237 / graph/lora_saver.cpp. Like the
reference we parse the header ourselves and memory-map the blob; unlike the
reference (F32/F16 only, auto-promote to F32) we also handle BF16 — the
TPU-native parameter dtype.

Two interchangeable backends: the native C++ engine (native/
fast_safetensors.{cpp,py} — mmap + own JSON parser + streamed writer,
mirroring the reference's native loader role) is used automatically when it
builds; this module's pure-Python implementation is the behavioral
reference and the fallback. MFT_NO_NATIVE_ST=1 forces Python.
"""

from __future__ import annotations

import contextlib
import json
import os
import struct
import zlib
from typing import Dict, Optional, Tuple

import numpy as np


class CheckpointIntegrityError(ValueError):
    """A checkpoint failed its integrity contract: unreadable/truncated
    file, missing or stale checksum manifest, or a per-tensor checksum
    mismatch. A NAMED type so load paths can refuse corrupt artifacts
    distinctly from ordinary I/O errors — the serve AdapterBank and the
    train-path lineage fallback both key on it (DESIGN.md §20)."""


@contextlib.contextmanager
def atomic_publish(path: str):
    """Crash-safe file publication (DESIGN.md §15): yields a tmp path
    (`<path>.tmp.<pid>`) for the caller to write, then fsyncs it and
    atomically `os.replace`s it onto `path` (plus a best-effort fsync of
    the directory entry). A death at ANY instant before the rename —
    including SIGKILL from the energy governor's suspend path or a
    mid-write crash — leaves the previous `path` bytes untouched, so a
    resumable checkpoint can never be replaced by a truncated one
    (tests/test_async_ckpt.py kills a writer mid-write to pin this).
    On exception the tmp file is removed and the exception propagates;
    only a hard kill can leave a stale `.tmp.<pid>` file behind, which
    later successful saves ignore (the pid suffix keeps concurrent
    writers from colliding)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        yield tmp
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, path)
        try:  # durability of the rename itself (directory entry)
            dfd = os.open(os.path.dirname(os.path.abspath(path)) or ".",
                          os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass  # non-posix dir semantics: the data fsync already landed
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def _native_mod():
    try:
        from mobilefinetuner_tpu.native import fast_safetensors as m
        return m if m.load_library() is not None else None
    except Exception:
        return None

# safetensors dtype tag -> (numpy dtype used for raw decode, itemsize)
_DTYPES = {
    "F64": np.dtype("<f8"), "F32": np.dtype("<f4"), "F16": np.dtype("<f2"),
    "I64": np.dtype("<i8"), "I32": np.dtype("<i4"), "I16": np.dtype("<i2"),
    "I8": np.dtype("i1"), "U8": np.dtype("u1"), "BOOL": np.dtype("?"),
    # BF16 has no numpy dtype; decoded via uint16 bit tricks.
    "BF16": np.dtype("<u2"),
}
_TO_TAG = {
    np.dtype("float64"): "F64", np.dtype("float32"): "F32",
    np.dtype("float16"): "F16", np.dtype("int64"): "I64",
    np.dtype("int32"): "I32", np.dtype("int16"): "I16",
    np.dtype("int8"): "I8", np.dtype("uint8"): "U8", np.dtype("bool"): "BOOL",
}


def _bf16_to_f32(raw_u16: np.ndarray) -> np.ndarray:
    return (raw_u16.astype(np.uint32) << 16).view(np.float32)


def _f32_to_bf16_u16(x: np.ndarray) -> np.ndarray:
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    # round-to-nearest-even on the truncated mantissa
    rounding = 0x7FFF + ((u >> 16) & 1)
    return ((u + rounding) >> 16).astype(np.uint16)


class SafeTensorsReader:
    """Parses header eagerly, memory-maps the blob, loads tensors lazily.

    Backed by the native C++ engine when available (identical entries/
    metadata/load results — tests/test_native_safetensors.py asserts
    byte-level parity), else by the pure-Python parse below.
    """

    def __init__(self, path: str):
        self.path = path
        self._native = None
        nat = _native_mod()
        if nat is not None:
            try:
                self._native = nat.NativeReader(path)
            except MemoryError:
                self._native = None
            # ValueError (malformed file) propagates: both backends reject
        if self._native is not None:
            self.metadata = self._native.metadata
            self.entries = self._native.entries
            self._blob = None
            return
        with open(path, "rb") as f:
            # malformed files raise ValueError from BOTH backends (the
            # native reader's st_error path raises ValueError above):
            # struct.error on a truncated length prefix is the one stdlib
            # type here that is NOT already a ValueError subclass
            # (json.JSONDecodeError and UnicodeDecodeError are).
            try:
                (header_len,) = struct.unpack("<Q", f.read(8))
                # a corrupt length prefix can decode to e.g. 2^60 — bound it
                # by the file size BEFORE read() attempts the allocation, so
                # MemoryError never escapes the ValueError contract
                if header_len > os.path.getsize(path) - 8:
                    raise ValueError(
                        f"header length {header_len} exceeds file size")
                header = json.loads(f.read(header_len).decode("utf-8"))
            except (struct.error, ValueError) as e:
                raise ValueError(
                    f"{path}: malformed safetensors header: {e}") from e
            if not isinstance(header, dict):
                raise ValueError(f"{path}: malformed safetensors header: "
                                 f"not a JSON object")
        self.metadata: Dict[str, str] = header.pop("__metadata__", {}) or {}
        self.entries: Dict[str, dict] = header
        self._blob = np.memmap(path, dtype=np.uint8, mode="r",
                               offset=8 + header_len)

    def keys(self):
        return list(self.entries.keys())

    def shape_dtype(self, name: str) -> Tuple[tuple, str]:
        e = self.entries[name]
        return tuple(e["shape"]), e["dtype"]

    def load(self, name: str, promote_to_f32: bool = False,
             keep_bf16: bool = False) -> np.ndarray:
        """Load one tensor as a numpy array (copy).

        BF16 decodes to float32 (plain numpy has no bf16) unless
        `keep_bf16`, which returns the stored 16 bits as an
        `ml_dtypes.bfloat16` array — half the host memory, and the dtype a
        device array keeps (io/checkpoints.load_afmoe: a frozen base that
        stays bf16 from disk to the MXU); other dtypes keep their storage
        dtype unless promote_to_f32.
        """
        e = self.entries[name]
        tag = e["dtype"]
        if tag not in _DTYPES:
            raise ValueError(f"unsupported safetensors dtype {tag}")
        if self._native is not None:
            raw = np.frombuffer(self._native.raw(name), dtype=_DTYPES[tag])
        else:
            begin, end = e["data_offsets"]
            raw = np.frombuffer(self._blob[begin:end], dtype=_DTYPES[tag])
        if tag == "BF16" and keep_bf16 and not promote_to_f32:
            import ml_dtypes
            arr = raw.copy().view(ml_dtypes.bfloat16)
        elif tag == "BF16":
            arr = _bf16_to_f32(raw)
        else:
            arr = raw.copy()
        if promote_to_f32 and arr.dtype != np.float32:
            arr = arr.astype(np.float32)
        return arr.reshape(e["shape"])

    def load_all(self, promote_to_f32: bool = False) -> Dict[str, np.ndarray]:
        return {k: self.load(k, promote_to_f32) for k in self.entries}

    def raw_bytes(self, name: str) -> bytes:
        """One tensor's STORED payload bytes, undecoded — the unit the
        integrity manifest checksums (a BF16 tensor hashes its on-disk
        u16 bytes, not a decode). A truncated blob returns fewer bytes
        than the header promised; the verifier treats that as corruption
        rather than erroring here."""
        if self._native is not None:
            return bytes(self._native.raw(name))
        begin, end = self.entries[name]["data_offsets"]
        return bytes(self._blob[begin:min(end, len(self._blob))])


# --------------------------- integrity manifest ------------------------------

# The per-tensor checksum sidecar every writer publishes next to its
# safetensors file (`<path>.manifest.json`, via the same atomic_publish).
# Checksums cover the ENCODED payload bytes — exactly what lands on disk
# — so a bit flip anywhere in the blob, a truncation, or a stale/partial
# write is caught at load time instead of silently training/serving from
# a corrupt artifact. The manifest is written AFTER the main file's
# atomic rename: a crash in the window between the two leaves a stale
# manifest, which verification reports as corruption — the load paths
# then fall back down the checkpoint lineage (io/checkpoints.py), the
# conservative failure.
MANIFEST_VERSION = 1


def manifest_path(path: str) -> str:
    return path + ".manifest.json"


def _write_manifest(path: str, entries: Dict[str, dict]) -> str:
    mp = manifest_path(path)
    payload = {"version": MANIFEST_VERSION,
               "file": os.path.basename(path),
               "tensors": entries}
    with atomic_publish(mp) as tmp:
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(payload, f, separators=(",", ":"), sort_keys=True)
    return mp


def verify_report(path: str) -> Tuple[str, Optional[str]]:
    """Integrity verdict for one safetensors file against its manifest:
    ('ok', None) — manifest present, every tensor's stored bytes match;
    ('unverified', reason) — the file parses but carries NO manifest
    (pre-manifest checkpoint): loadable only as a last resort;
    ('corrupt', reason) — missing/unparseable file, unreadable or stale
    manifest, size or checksum mismatch. Never raises."""
    if not os.path.exists(path):
        return "corrupt", "missing_file"
    try:
        reader = SafeTensorsReader(path)
    except (ValueError, OSError, MemoryError) as e:
        return "corrupt", f"malformed:{type(e).__name__}"
    mp = manifest_path(path)
    if not os.path.exists(mp):
        return "unverified", "manifest_missing"
    try:
        with open(mp, "r", encoding="utf-8") as f:
            manifest = json.load(f)
        tensors = manifest["tensors"]
        assert isinstance(tensors, dict)
    except (ValueError, KeyError, AssertionError, OSError):
        return "corrupt", "manifest_unreadable"
    if set(tensors) != set(reader.entries):
        return "corrupt", "manifest_stale"
    for name, spec in tensors.items():
        try:
            raw = reader.raw_bytes(name)
        except Exception as e:  # mmap fault on a truncated blob etc.
            return "corrupt", f"payload_unreadable:{name}:{type(e).__name__}"
        if len(raw) != spec.get("nbytes"):
            return "corrupt", f"size_mismatch:{name}"
        if (zlib.crc32(raw) & 0xFFFFFFFF) != spec.get("crc32"):
            return "corrupt", f"checksum_mismatch:{name}"
    return "ok", None


def verify_file(path: str) -> None:
    """Raise CheckpointIntegrityError unless `path` verifies 'ok'
    against its manifest (a missing manifest fails too — strict form,
    used where an unverified artifact must not be trusted, e.g. the
    serve AdapterBank's hot-swap path)."""
    status, reason = verify_report(path)
    if status != "ok":
        raise CheckpointIntegrityError(
            f"{path}: integrity verification failed ({reason})")


def _tensor_spec(name, arr, bf16_keys):
    """(tag, shape, nbytes, encode) for one tensor — the single source of
    the dtype-tag/encoding rules for both writers. `encode()` materializes
    the payload bytes; the streamed native writer calls it one tensor at a
    time, so declarations never require encoding up front."""
    arr = np.asarray(arr)
    # jax bf16 arrays arrive as ml_dtypes.bfloat16 numpy arrays — store
    # them as BF16, not silently upcast to F32.
    is_bf16_input = arr.dtype.name == "bfloat16"
    shape = arr.shape
    n = int(np.prod(shape, dtype=np.int64))
    if is_bf16_input:   # already the 16 bits to store
        encode = lambda: np.ascontiguousarray(arr).view(np.uint16).tobytes()
        return "BF16", shape, n * 2, encode
    if bf16_keys and name in bf16_keys:
        encode = lambda: _f32_to_bf16_u16(arr.astype(np.float32)).tobytes()
        return "BF16", shape, n * 2, encode
    dtype = arr.dtype if arr.dtype in _TO_TAG else np.dtype(np.float32)
    encode = lambda: np.ascontiguousarray(arr.astype(dtype)
                                          if arr.dtype != dtype
                                          else arr).tobytes()
    return _TO_TAG[dtype], shape, n * dtype.itemsize, encode


def _encode_tensor(name, arr, bf16_keys) -> Tuple[str, tuple, bytes]:
    """(tag, shape, raw_bytes) — eager form, used by the Python writer."""
    tag, shape, _, encode = _tensor_spec(name, arr, bf16_keys)
    return tag, shape, encode()


def save_safetensors(path: str, tensors: Dict[str, np.ndarray],
                     metadata: Optional[Dict[str, str]] = None,
                     bf16_keys: Optional[set] = None,
                     manifest: bool = True):
    """Write a safetensors file. Keys in `bf16_keys` (or arrays already
    passed as jax bfloat16 via float32 conversion upstream) are stored BF16.
    Uses the native streamed writer when available; the Python writer below
    is the fallback and behavioral reference.

    EVERY write is atomically published (tmp + fsync + rename): since all
    checkpoint writers in the repo — adapters, full-model saves, the .opt
    optimizer sidecar — funnel through here, none of them can leave a
    truncated file where a resumable checkpoint used to be. With
    `manifest` (the default) a `<path>.manifest.json` checksum sidecar is
    published after the main rename, carrying crc32/nbytes per tensor
    over the stored payload bytes — the verify-on-load contract
    (`verify_report`/`verify_file`) every resume/rollback/adapter-swap
    path checks. The checksums are computed from the same encode pass
    the writer streams to disk, so the manifest costs no extra read.
    """
    sums: Dict[str, dict] = {}
    with atomic_publish(path) as tmp:
        _write_safetensors(tmp, tensors, metadata, bf16_keys,
                           checksums=sums if manifest else None)
    if manifest:
        _write_manifest(path, sums)


def _write_safetensors(path: str, tensors: Dict[str, np.ndarray],
                       metadata: Optional[Dict[str, str]] = None,
                       bf16_keys: Optional[set] = None,
                       checksums: Optional[Dict[str, dict]] = None):
    def _record(name, tag, shape, raw):
        if checksums is not None:
            checksums[name] = {"crc32": zlib.crc32(raw) & 0xFFFFFFFF,
                               "nbytes": len(raw), "dtype": tag,
                               "shape": list(shape)}

    nat = _native_mod()
    if nat is not None:
        # real write failures (IOError) propagate — a disk that rejects
        # the native writer would reject the Python writer too. Payloads
        # go in as callables: the native writer declares the header from
        # (tag, shape, nbytes) and encodes ONE tensor at a time during the
        # data pass, so peak host memory is a single tensor's bytes. The
        # checksum wrapper rides that same single encode call, so the
        # manifest never forces a second encode pass.
        items = []
        for name, arr in tensors.items():
            tag, shape, nbytes, encode = _tensor_spec(name, arr, bf16_keys)

            def wrap(name=name, tag=tag, shape=shape, encode=encode):
                raw = encode()
                _record(name, tag, shape, raw)
                return raw

            items.append((name, tag, shape, nbytes, wrap))
        nat.native_write(path, items, metadata)
        return
    header: Dict[str, object] = {}
    if metadata:
        header["__metadata__"] = {str(k): str(v)
                                  for k, v in metadata.items()}
    blobs = []
    offset = 0
    for name, arr in tensors.items():
        tag, shape, raw = _encode_tensor(name, arr, bf16_keys)
        _record(name, tag, shape, raw)
        header[name] = {"dtype": tag, "shape": list(shape),
                        "data_offsets": [offset, offset + len(raw)]}
        blobs.append(raw)
        offset += len(raw)
    hjson = json.dumps(header, separators=(",", ":")).encode("utf-8")
    # Pad header to 8-byte alignment (spec-conformant, matches HF writer).
    pad = (-(len(hjson)) % 8)
    hjson += b" " * pad
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(hjson)))
        f.write(hjson)
        for b in blobs:
            f.write(b)
