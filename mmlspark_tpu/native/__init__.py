"""ctypes bindings for the native runtime (libmmltpu.so).

The reference's native layer arrives as prebuilt JNI/SWIG jars extracted and
System.load-ed at runtime (core/env/src/main/scala/NativeLoader.java:28);
ours is in-repo C++ (csrc/) compiled on demand with the baked-in toolchain
and loaded here via ctypes. Every entry point has a pure-Python fallback at
its call site, so the package works (slower) without a compiler.

Set MMLSPARK_TPU_NO_NATIVE=1 to force the fallbacks. That and a missing
toolchain are the two causes for which the library is quietly absent; a
build or a load that fails where a toolchain exists is logged as an error
and named by ``unavailable_reason()``.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import shutil
import subprocess
import threading
from typing import Optional

import numpy as np

from ..core.utils import get_logger

log = get_logger("native")

_CSRC = os.path.join(os.path.dirname(__file__), "csrc")
_BUILD = os.path.join(os.path.dirname(__file__), "_build")
_SO = os.path.join(_BUILD, "libmmltpu.so")

# one-time-init lock: held across the native build + dlopen ON PURPOSE,
# so exactly one thread compiles while the rest wait for the result —
# blocking under it is the mechanism, not a contention bug.
# graftlint: disable-file=lock-blocking-call
_lock = threading.Lock()
_lib = None
_tried = False
# why _lib is None once _tried. The first two are the quiet causes: the
# package is meant to work without the library there. Any other text is a
# fault (a build or a load that failed where a toolchain exists).
_DISABLED = "disabled by MMLSPARK_TPU_NO_NATIVE"
_NO_TOOLCHAIN = "no make or C++ compiler on the path"
_QUIET = (_DISABLED, _NO_TOOLCHAIN)
_reason: Optional[str] = None


def _needs_build() -> bool:
    if not os.path.exists(_SO):
        return True
    so_mtime = os.path.getmtime(_SO)
    return any(
        os.path.getmtime(os.path.join(_CSRC, f)) > so_mtime
        for f in os.listdir(_CSRC))


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.mmltpu_free.argtypes = [ctypes.c_void_p]
    lib.mmltpu_free.restype = None
    lib.mmltpu_decode_image.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.POINTER(u8p),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.mmltpu_decode_image.restype = ctypes.c_int
    lib.mmltpu_resize_bilinear.argtypes = [
        u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        u8p, ctypes.c_int, ctypes.c_int]
    lib.mmltpu_resize_bilinear.restype = None
    lib.mmltpu_loader_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.mmltpu_loader_create.restype = ctypes.c_void_p
    lib.mmltpu_loader_next.argtypes = [
        ctypes.c_void_p, u8p, u8p, ctypes.POINTER(ctypes.c_int)]
    lib.mmltpu_loader_next.restype = ctypes.c_int
    lib.mmltpu_loader_ready.argtypes = [ctypes.c_void_p]
    lib.mmltpu_loader_ready.restype = ctypes.c_int
    lib.mmltpu_loader_destroy.argtypes = [ctypes.c_void_p]
    lib.mmltpu_loader_destroy.restype = None
    lib.mmltpu_csv_parse.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_char, ctypes.c_int,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
    lib.mmltpu_csv_parse.restype = ctypes.c_int
    fpp = ctypes.POINTER(ctypes.POINTER(ctypes.c_float))
    lib.mmltpu_interleave_f32.argtypes = [
        fpp, ctypes.c_int, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float), ctypes.c_int]
    lib.mmltpu_interleave_f32.restype = None
    lib.mmltpu_bin_data.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.c_int, u8p, ctypes.c_int,
        u8p, ctypes.c_int]
    lib.mmltpu_bin_data.restype = None
    return lib


def _build_and_load() -> tuple[Optional[ctypes.CDLL], Optional[str]]:
    """(library, None), or (None, why not).

    One writer: the staleness check, the make and the dlopen run under an
    exclusive flock on the source directory, so of the processes that
    start together on a fresh checkout one builds and the rest wait, find
    the library current and load it; none reads a half-linked file."""
    cxx = (os.environ.get("CXX") or "g++").split()[0]   # as the Makefile
    toolchain = bool(shutil.which("make") and shutil.which(cxx))
    try:
        fd = os.open(_CSRC, os.O_RDONLY)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX)
            if _needs_build():
                if not toolchain:
                    return None, _NO_TOOLCHAIN
                r = subprocess.run(
                    ["make", "-C", _CSRC, f"OUT={_BUILD}"],
                    capture_output=True, text=True)
                if r.returncode != 0:
                    return None, (f"build failed (make exit "
                                  f"{r.returncode}):\n{r.stderr[-2000:]}")
            return _bind(ctypes.CDLL(_SO)), None
        finally:
            os.close(fd)        # gives the lock up
    except (OSError, AttributeError) as e:
        # AttributeError = a stale prebuilt .so missing a newer symbol
        # (e.g. extracted with fresh mtimes so _needs_build says no):
        # the contract is None-when-unavailable, never a crash
        return None, (f"load failed ({_SO}): {e}" if toolchain
                      else _NO_TOOLCHAIN)


def get_lib() -> Optional[ctypes.CDLL]:
    """Build (if stale) and load libmmltpu.so; None when unavailable."""
    global _lib, _reason, _tried
    if _tried:
        return _lib
    with _lock:
        if _tried:
            return _lib
        if os.environ.get("MMLSPARK_TPU_NO_NATIVE"):
            _reason = _DISABLED
        else:
            _lib, _reason = _build_and_load()
        if _reason in _QUIET:
            log.info("native runtime unavailable: %s", _reason)
        elif _reason is not None:
            log.error("native runtime unavailable, using the Python "
                      "fallbacks: %s", _reason)
        _tried = True
        return _lib


def available() -> bool:
    return get_lib() is not None


def unavailable_reason() -> Optional[str]:
    """Why ``available()`` is False; None when the library is loaded."""
    get_lib()
    return _reason


def unavailable_quietly() -> bool:
    """True where the library is absent for a cause the package is meant
    to run with (disabled by the environment, or no toolchain): the one
    condition under which a test of the native path may be skipped. A
    build or a load that failed where a toolchain exists answers False."""
    return unavailable_reason() in _QUIET


def decode_image(data: bytes) -> Optional[np.ndarray]:
    """Encoded bytes -> HWC uint8 BGR array, or None if undecodable."""
    lib = get_lib()
    if lib is None:
        return None
    out = ctypes.POINTER(ctypes.c_uint8)()
    h = ctypes.c_int()
    w = ctypes.c_int()
    c = ctypes.c_int()
    rc = lib.mmltpu_decode_image(data, len(data), ctypes.byref(out),
                                 ctypes.byref(h), ctypes.byref(w),
                                 ctypes.byref(c))
    if rc != 0:
        return None
    try:
        n = h.value * w.value * c.value
        arr = np.ctypeslib.as_array(out, shape=(n,)).copy()
    finally:
        lib.mmltpu_free(out)
    return arr.reshape(h.value, w.value, c.value)


def resize_bilinear(img: np.ndarray, out_h: int, out_w: int) -> np.ndarray:
    """HWC uint8 bilinear resize through the native kernel."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    img = np.ascontiguousarray(img, dtype=np.uint8)
    h, w, c = img.shape
    dst = np.empty((out_h, out_w, c), dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.mmltpu_resize_bilinear(
        img.ctypes.data_as(u8p), h, w, c,
        dst.ctypes.data_as(u8p), out_h, out_w)
    return dst


class BatchLoader:
    """Iterate fixed-shape image batches decoded/resized by worker threads.

    Yields (batch[B,H,W,3] uint8 BGR, ok[B] bool, count). The arrays are
    persistent staging buffers reused across iterations — consumers must
    device_put (or copy) before advancing, which is exactly the intended
    use: jax.device_put snapshots into HBM, so the next decode overlaps
    with TPU compute.
    """

    def __init__(self, paths: list[str], batch: int, height: int, width: int,
                 threads: int = 0, prefetch: int = 4):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self.batch, self.height, self.width = batch, height, width
        if threads <= 0:
            threads = min(8, os.cpu_count() or 1)
        arr = (ctypes.c_char_p * len(paths))(
            *[p.encode() for p in paths])
        self._handle = lib.mmltpu_loader_create(
            arr, len(paths), batch, height, width, threads, prefetch)
        if not self._handle:
            raise RuntimeError("loader creation failed")
        self._buf = np.empty((batch, height, width, 3), dtype=np.uint8)
        self._ok = np.empty((batch,), dtype=np.uint8)

    def __iter__(self):
        u8p = ctypes.POINTER(ctypes.c_uint8)
        count = ctypes.c_int()
        while True:
            rc = self._lib.mmltpu_loader_next(
                self._handle, self._buf.ctypes.data_as(u8p),
                self._ok.ctypes.data_as(u8p), ctypes.byref(count))
            if rc == 0:
                return
            yield self._buf, self._ok.astype(bool), count.value

    def ready(self) -> int:
        """Decoded batches waiting in the queue: how far the worker
        threads are ahead of the consumer at this instant."""
        return self._lib.mmltpu_loader_ready(self._handle)

    def close(self):
        if self._handle:
            self._lib.mmltpu_loader_destroy(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def read_csv(path: str, skip_header: bool = False, delim: str = ",",
             threads: int = 0) -> Optional[np.ndarray]:
    """Delimited numeric file -> float32 matrix, or None w/o native lib."""
    lib = get_lib()
    if lib is None:
        return None
    if threads <= 0:
        threads = min(8, os.cpu_count() or 1)
    out = ctypes.POINTER(ctypes.c_float)()
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    rc = lib.mmltpu_csv_parse(path.encode(), int(skip_header),
                              delim.encode(), threads, ctypes.byref(out),
                              ctypes.byref(rows), ctypes.byref(cols))
    if rc != 0:
        return None
    try:
        n = rows.value * cols.value
        if n == 0:
            return np.zeros((0, max(cols.value, 0)), dtype=np.float32)
        mat = np.ctypeslib.as_array(out, shape=(n,)).copy()
    finally:
        lib.mmltpu_free(out)
    return mat.reshape(rows.value, cols.value)


def interleave_f32(cols: list, out: np.ndarray,
                   threads: int = 0) -> bool:
    """Columnar float32 arrays -> row-major ``out`` (n, d) staging matrix
    via the threaded cache-blocked C++ transpose (the Arrow->device bridge;
    replaces the reference's per-element JNI copies,
    CNTKModel.scala:67-74). Returns False without the native lib — callers
    fall back to np.stack."""
    lib = get_lib()
    if lib is None:
        return False
    n, d = out.shape
    if len(cols) != d:
        raise ValueError(f"{len(cols)} columns for a {d}-wide output")
    if out.dtype != np.float32 or not out.flags.c_contiguous:
        raise TypeError("output must be C-contiguous float32")
    fp = ctypes.POINTER(ctypes.c_float)
    ptrs = (fp * d)()
    for j, c in enumerate(cols):
        # real raises, not asserts: python -O must not hand C++ bad buffers
        if c.dtype != np.float32 or not c.flags.c_contiguous:
            raise TypeError(f"column {j} must be contiguous float32, "
                            f"got {c.dtype}")
        if len(c) != n:
            raise ValueError(f"column {j} has {len(c)} rows, output {n}")
        ptrs[j] = c.ctypes.data_as(fp)
    if threads <= 0:
        threads = min(8, os.cpu_count() or 1)
    lib.mmltpu_interleave_f32(ptrs, d, n, out.ctypes.data_as(fp), threads)
    return True


def bin_data_native(x: np.ndarray, edges: np.ndarray,
                    cat_mask: Optional[np.ndarray] = None,
                    max_bin: int = 256,
                    threads: int = 0) -> Optional[np.ndarray]:
    """GBDT quantile binning through the C++ kernel: (n, d) f32 ->
    (n, d) uint8, bit-identical to engine.bin_data (searchsorted
    side='left', NaN->0, categorical identity clip). Returns None when the
    native runtime is unavailable so the caller can fall back."""
    lib = get_lib()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.float32)
    edges = np.ascontiguousarray(edges, dtype=np.float32)
    n, d = x.shape
    if edges.shape[0] != d:
        raise ValueError(f"edges has {edges.shape[0]} feature rows for a "
                         f"{d}-wide matrix")
    out = np.empty((n, d), dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    cat_ptr = None
    if cat_mask is not None:
        cat_arr = np.ascontiguousarray(cat_mask, dtype=np.uint8)
        if len(cat_arr) != d:
            raise ValueError(f"cat_mask has {len(cat_arr)} entries for "
                             f"{d} features")
        cat_ptr = cat_arr.ctypes.data_as(u8p)
    fp = ctypes.POINTER(ctypes.c_float)
    lib.mmltpu_bin_data(x.ctypes.data_as(fp), n, d,
                        edges.ctypes.data_as(fp), int(edges.shape[1]),
                        cat_ptr, int(max_bin),
                        out.ctypes.data_as(u8p), int(threads))
    return out
