"""ctypes binding + on-demand build of the native IO library.

Reference: the reference links dmlc-core/src/recordio.cc and the C++
iterator tier into libmxnet.so at build time (SURVEY.md §2.1).  Here the
library is a single translation unit compiled on first use with the
toolchain in the image (g++ -O3 -shared) and cached next to the sources;
every caller keeps a pure-Python fallback, so a missing compiler degrades
performance, never correctness.  ``mx.runtime.Features()["NATIVE_IO"]``
reports which path is active.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

from ..base import env_truthy

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src", "nativelib.cc")
_SO = os.path.join(_DIR, "libmxnet_tpu_native.so")
# sha256 of the source the cached .so was built from: the binary is a
# build product that copies of the tree carry along (mtimes do not
# survive a copy, and the ABI number does not move with every export),
# so staleness is keyed on the source's CONTENT
_STAMP = _SO + ".sha256"

_lock = threading.Lock()
_lib = None
_tried = False


def _src_digest() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _stale() -> bool:
    if not os.path.exists(_SO):
        return True
    try:
        with open(_STAMP) as f:
            return f.read().strip() != _src_digest()
    except OSError:
        return True


def _build() -> bool:
    tmp = f"{_SO}.{os.getpid()}.tmp"
    base = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
            "-o", tmp, _SRC]
    # libjpeg powers the threaded decode tier; hosts without it still
    # get the recordio/csv tier (decode falls back to Python/cv2)
    for cmd in (base + ["-ljpeg"], base + ["-DMXNATIVE_NO_JPEG"]):
        try:
            proc = subprocess.run(cmd, capture_output=True, timeout=120)
            if proc.returncode == 0 and os.path.exists(tmp):
                os.replace(tmp, _SO)
                with open(tmp, "w") as f:
                    f.write(_src_digest())
                os.replace(tmp, _STAMP)
                return True
        except (OSError, subprocess.TimeoutExpired):
            return False
    return False


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        # '0'/'' = off, like every other boolean knob
        if env_truthy("MXNET_TPU_DISABLE_NATIVE"):
            return None
        if _stale() and not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        if lib.mxnative_abi_version() != 1:
            return None
        lib.mxrec_open.restype = ctypes.c_void_p
        lib.mxrec_open.argtypes = [ctypes.c_char_p]
        lib.mxrec_close.argtypes = [ctypes.c_void_p]
        lib.mxrec_index.restype = ctypes.c_int64
        lib.mxrec_index.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_int64),
                                    ctypes.c_int64]
        lib.mxrec_read_at.restype = ctypes.c_int64
        lib.mxrec_read_at.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                      ctypes.c_char_p, ctypes.c_int64]
        lib.mxrec_create.restype = ctypes.c_void_p
        lib.mxrec_create.argtypes = [ctypes.c_char_p]
        lib.mxrec_write.restype = ctypes.c_int64
        lib.mxrec_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_int64]
        lib.mxcsv_shape.restype = ctypes.c_int64
        lib.mxcsv_shape.argtypes = [ctypes.c_char_p,
                                    ctypes.POINTER(ctypes.c_int64)]
        lib.mxcsv_parse.restype = ctypes.c_int64
        lib.mxcsv_parse.argtypes = [
            ctypes.c_char_p,
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            ctypes.c_int64]
        if lib.mxnative_has_jpeg():
            lib.mxjpeg_decode_batch.restype = ctypes.c_int64
            lib.mxjpeg_decode_batch.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                ctypes.c_int64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
                ctypes.c_int64]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# high-level wrappers (all raise RuntimeError when the lib is unavailable;
# callers gate on available())
# ---------------------------------------------------------------------------

class NativeRecordReader:
    """Random-access record reader over the C++ scanner."""

    def __init__(self, path: str):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.mxrec_open(path.encode())
        if not self._h:
            raise OSError(f"cannot open {path!r}")

    def close(self):
        if self._h:
            self._lib.mxrec_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def index(self) -> np.ndarray:
        """Byte offsets of every logical record (the .idx-less scan)."""
        count = self._lib.mxrec_index(self._h, None, 0)
        if count < 0:
            raise IOError("corrupt record file")
        offsets = np.zeros(count, np.int64)
        got = self._lib.mxrec_index(
            self._h,
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), count)
        if got != count:
            raise IOError("record file changed during scan")
        return offsets

    def read_at(self, offset: int) -> bytes:
        need = self._lib.mxrec_read_at(self._h, offset, None, 0)
        if need < 0:
            raise IOError(f"corrupt record at offset {offset}")
        buf = ctypes.create_string_buffer(need)
        got = self._lib.mxrec_read_at(self._h, offset, buf, need)
        if got != need:
            raise IOError(f"short read at offset {offset}")
        return buf.raw


class NativeRecordWriter:
    def __init__(self, path: str):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.mxrec_create(path.encode())
        if not self._h:
            raise OSError(f"cannot create {path!r}")

    def write(self, payload: bytes) -> int:
        n = self._lib.mxrec_write(self._h, payload, len(payload))
        if n < 0:
            raise IOError("record write failed")
        return n

    def close(self):
        if self._h:
            self._lib.mxrec_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def jpeg_available() -> bool:
    lib = _load()
    return lib is not None and bool(lib.mxnative_has_jpeg())


def decode_jpeg_batch(bufs, resize_min, out_h, out_w, cy_frac, cx_frac,
                      mirror, n_threads):
    """Decode a batch of JPEG byte strings on native OS threads.

    Returns (batch (n, 3, out_h, out_w) uint8, status (n,) uint8 —
    0 = decoded, nonzero = that image needs the Python fallback).
    Augmentation randomness (crop fractions, mirror flags) is supplied
    by the caller so the seeded-RNG contract is unchanged.
    """
    lib = _load()
    if lib is None or not lib.mxnative_has_jpeg():
        raise RuntimeError("native JPEG tier unavailable")
    n = len(bufs)
    arr = (ctypes.c_char_p * n)(*bufs)
    lens = np.array([len(b) for b in bufs], np.int64)
    out = np.empty((n, 3, out_h, out_w), np.uint8)
    status = np.ones(n, np.uint8)
    lib.mxjpeg_decode_batch(
        ctypes.cast(arr, ctypes.POINTER(ctypes.c_char_p)), lens, n,
        int(resize_min or 0), int(out_h), int(out_w),
        np.ascontiguousarray(cy_frac, np.float32),
        np.ascontiguousarray(cx_frac, np.float32),
        np.ascontiguousarray(mirror, np.uint8), out, status,
        int(n_threads))
    return out, status


def csv_load(path: str) -> np.ndarray:
    """Parse a numeric CSV into a (rows, cols) float32 array."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n_vals = ctypes.c_int64()
    rows = lib.mxcsv_shape(path.encode(), ctypes.byref(n_vals))
    if rows < 0:
        raise OSError(f"cannot open {path!r}")
    out = np.empty(n_vals.value, np.float32)
    got = lib.mxcsv_parse(path.encode(), out, n_vals.value)
    if got == -3:
        raise ValueError(
            f"non-numeric field in {path!r} (header line?) — "
            f"CSVIter expects numeric-only files")
    if got != n_vals.value:
        raise IOError(f"csv parse mismatch in {path!r}")
    if rows and n_vals.value % rows:
        raise IOError(f"ragged csv {path!r}")
    return out.reshape(rows, n_vals.value // rows) if rows else \
        out.reshape(0, 0)
