"""ctypes bindings for the native (C++) data-loader kernels.

The multithreaded C++ path (native/dllama_native.cpp) unpacks Q40 blocks
straight into the transposed device layout in one pass; the numpy fallback
keeps everything working when the library isn't built (`make -C native`).
Auto-builds on first use when a toolchain is present.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libdllama_native.so"))

_lib = None
_lib_tried = False


def _threads() -> int:
    return max(1, min(os.cpu_count() or 1, 16))


_ABI_VERSION = 4


def _needs_build() -> bool:
    if not os.path.isfile(_LIB_PATH):
        return True
    try:
        lib_mtime = os.path.getmtime(_LIB_PATH)
        nat = os.path.abspath(_NATIVE_DIR)
        return any(
            os.path.getmtime(os.path.join(nat, f)) > lib_mtime
            for f in ("dllama_native.cpp", "Makefile")
        )
    except OSError:
        return False


def _open_library():
    lib = ctypes.CDLL(_LIB_PATH)
    u8 = ctypes.POINTER(ctypes.c_uint8)
    i8 = ctypes.POINTER(ctypes.c_int8)
    f32 = ctypes.POINTER(ctypes.c_float)
    i32 = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i64 = ctypes.c_int64
    lib.q40_unpack_transposed.argtypes = [u8, i64, i64, i8, f32, ctypes.c_int]
    lib.q40_pack_transposed.argtypes = [u8, i64, i64, i32, f32, ctypes.c_int]
    lib.q40_dequant_transposed.argtypes = [u8, i64, i64, f32, ctypes.c_int]
    lib.q40_dequant.argtypes = [u8, i64, i64, f32, ctypes.c_int]
    lib.f32_transpose.argtypes = [f32, i64, i64, f32, ctypes.c_int]
    lib.bpe_index_new.argtypes = [u8, i64p, f32, i64, i64]
    lib.bpe_index_new.restype = ctypes.c_void_p
    lib.bpe_index_free.argtypes = [ctypes.c_void_p]
    lib.bpe_encode.argtypes = [
        ctypes.c_void_p, u8, i64, i64, ctypes.c_int, i32, i64,
    ]
    lib.bpe_encode.restype = i64
    lib.dllama_native_version.restype = ctypes.c_int
    return lib


def load_library(auto_build: bool = True):
    """Load (building if needed) the native library; None when unavailable.
    The staleness check, incremental `make`, AND the dlopen all happen
    under one file lock — a concurrent process must not dlopen a .so that
    another process's make is mid-way through writing."""
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    _lib_tried = True
    try:
        if auto_build and _needs_build():
            # build + dlopen under one lock so no process opens a .so
            # another's make is mid-way through writing; a current .so
            # takes the lock-free fast path (works on read-only installs)
            try:
                import fcntl

                with open(_LIB_PATH + ".lock", "w") as lock:
                    fcntl.flock(lock, fcntl.LOCK_EX)
                    if _needs_build():
                        subprocess.run(
                            ["make", "-C", os.path.abspath(_NATIVE_DIR)],
                            capture_output=True,
                            timeout=120,
                            check=True,
                        )
            except Exception:
                pass  # no toolchain / read-only tree: use whatever exists
        if not os.path.isfile(_LIB_PATH):
            return None
        lib = _open_library()
        if lib.dllama_native_version() != _ABI_VERSION:
            raise RuntimeError(
                "native library ABI version mismatch; run make -C native clean"
            )
        _lib = lib
    except Exception:
        _lib = None
    return _lib


def _u8ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def q40_unpack_transposed(
    raw: np.ndarray, rows: int, cols: int
) -> tuple[np.ndarray, np.ndarray] | None:
    """Packed Q40 bytes -> (q int8 [cols, rows], d f32 [cols//32, rows]),
    i.e. directly in quant_matmul's device layout. None if no native lib."""
    lib = load_library()
    if lib is None:
        return None
    raw = np.ascontiguousarray(np.frombuffer(raw, dtype=np.uint8))
    q = np.empty((cols, rows), dtype=np.int8)
    d = np.empty((cols // 32, rows), dtype=np.float32)
    lib.q40_unpack_transposed(
        _u8ptr(raw),
        rows,
        cols,
        q.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
        d.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        _threads(),
    )
    return q, d


def q40_pack_transposed(
    raw: np.ndarray, rows: int, cols: int
) -> tuple[np.ndarray, np.ndarray] | None:
    """Packed Q40 bytes -> (words int32 [cols // 8, rows], d f32
    [cols // 32, rows]): quant_matmul's packed device layout, straight from
    the wire's nibbles (formats.quants.pack_q40_device is the numpy twin).
    None if no native lib."""
    lib = load_library()
    if lib is None:
        return None
    raw = np.ascontiguousarray(np.frombuffer(raw, dtype=np.uint8))
    words = np.empty((cols // 8, rows), dtype=np.int32)
    d = np.empty((cols // 32, rows), dtype=np.float32)
    lib.q40_pack_transposed(
        _u8ptr(raw),
        rows,
        cols,
        words.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        d.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        _threads(),
    )
    return words, d


def q40_dequant_transposed(raw: np.ndarray, rows: int, cols: int) -> np.ndarray | None:
    """Packed Q40 bytes ([rows, cols] logical) -> dense f32 [cols, rows]."""
    lib = load_library()
    if lib is None:
        return None
    raw = np.ascontiguousarray(np.frombuffer(raw, dtype=np.uint8))
    out = np.empty((cols, rows), dtype=np.float32)
    lib.q40_dequant_transposed(
        _u8ptr(raw), rows, cols,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), _threads(),
    )
    return out


def f32_transpose(arr: np.ndarray) -> np.ndarray | None:
    """Tiled multithreaded [rows, cols] -> [cols, rows] transpose."""
    lib = load_library()
    if lib is None:
        return None
    arr = np.ascontiguousarray(arr, dtype=np.float32)
    rows, cols = arr.shape
    out = np.empty((cols, rows), dtype=np.float32)
    lib.f32_transpose(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), rows, cols,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), _threads(),
    )
    return out


class BpeIndex:
    """Owns a native BPE vocab index (hash map built once). Keeps the
    numpy arrays it points into alive for the handle's lifetime."""

    def __init__(
        self,
        vocab_blob: np.ndarray,  # uint8 concat of all vocab pieces
        offsets: np.ndarray,  # int64 [V + 1]
        scores: np.ndarray,  # float32 [V]
        regular_size: int,
    ):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        # keep referenced buffers alive as long as the handle exists
        self._blob = np.ascontiguousarray(vocab_blob)
        self._offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        self._scores = np.ascontiguousarray(scores, dtype=np.float32)
        self._handle = lib.bpe_index_new(
            _u8ptr(self._blob),
            self._offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            self._scores.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            len(self._scores),
            regular_size,
        )
        if not self._handle:
            raise RuntimeError(
                f"native BPE index rejected vocab (regular_size="
                f"{regular_size}, vocab={len(self._scores)})"
            )

    def encode(
        self, text: bytes, bos_id: int, add_specials: bool
    ) -> list[int] | None:
        """Token ids ([bos_id] prepended when >= 0, participating in the
        merge phase like the Python loop's list does), or None for
        un-tokenizable input — the caller's Python fallback raises the
        detailed error."""
        raw = np.frombuffer(text, dtype=np.uint8)
        cap = max(len(text) + 8, 64)
        out = np.empty(cap, dtype=np.int32)
        n = self._lib.bpe_encode(
            self._handle,
            _u8ptr(raw) if len(raw) else _u8ptr(np.zeros(1, np.uint8)),
            len(raw),
            bos_id,
            1 if add_specials else 0,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            cap,
        )
        if n < 0:
            return None  # -2 untokenizable / -1 capacity
        return out[:n].tolist()

    def __del__(self):
        handle = getattr(self, "_handle", None)
        lib = getattr(self, "_lib", None)
        if handle and lib is not None:
            try:
                lib.bpe_index_free(handle)
            except Exception:
                pass


def make_bpe_index(
    vocab_blob: np.ndarray,
    offsets: np.ndarray,
    scores: np.ndarray,
    regular_size: int,
) -> BpeIndex | None:
    """BpeIndex, or None when the native library is unavailable or the
    vocab metadata is rejected (callers fall back to the Python loop)."""
    if load_library() is None:
        return None
    try:
        return BpeIndex(vocab_blob, offsets, scores, regular_size)
    except RuntimeError:
        return None


def q40_dequant(raw: np.ndarray, rows: int, cols: int) -> np.ndarray | None:
    """Packed Q40 bytes -> dense f32 [rows, cols] (file order)."""
    lib = load_library()
    if lib is None:
        return None
    raw = np.ascontiguousarray(np.frombuffer(raw, dtype=np.uint8))
    out = np.empty((rows, cols), dtype=np.float32)
    lib.q40_dequant(
        _u8ptr(raw), rows, cols,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), _threads(),
    )
    return out
