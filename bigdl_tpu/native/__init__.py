"""Native C++ runtime bindings (ctypes — no pybind11 in this image).

The reference offloads its hot host-side paths to native code (MKL JNI,
BigQuant, netty CRC); here the TPU compute is XLA/pallas and the native
layer covers the HOST side: CRC32C for the event writer and a
multi-threaded augmenting data loader that keeps the input pipeline off
the Python GIL. Builds lazily with `make` on first import; every entry
point has a pure-Python fallback so the framework works without a
compiler.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

# fallback for close() on partially-constructed loaders (init raised
# before _lock existed)
_NULL_LOCK = threading.Lock()

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libbigdl_native.so")
_lib: Optional[ctypes.CDLL] = None


def _build() -> bool:
    try:
        subprocess.run(["make", "-C", _DIR], check=True,
                       capture_output=True, timeout=120)
        return os.path.exists(_LIB_PATH)
    except Exception:
        return False


def load_library(build: bool = True) -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable.

    ``build=False`` only dlopens an existing .so — used by hot paths that
    must not block on a compile."""
    global _lib
    if _lib is not None:
        return _lib
    if not os.path.exists(_LIB_PATH) and (not build or not _build()):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    lib.bigdl_crc32c.restype = ctypes.c_uint32
    lib.bigdl_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                 ctypes.c_uint32]
    lib.bigdl_parse_idx.restype = ctypes.c_int
    lib.bigdl_parse_cifar.restype = ctypes.c_int
    lib.bigdl_loader_create.restype = ctypes.c_void_p
    lib.bigdl_loader_next.restype = ctypes.c_int
    lib.bigdl_loader_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_void_p]
    lib.bigdl_loader_destroy.argtypes = [ctypes.c_void_p]
    lib.bigdl_loader_u8_create.restype = ctypes.c_void_p
    lib.bigdl_loader_u8_next.restype = ctypes.c_int
    lib.bigdl_loader_u8_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                         ctypes.c_void_p]
    lib.bigdl_loader_u8_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def native_crc32c(data: bytes, crc: int = 0) -> int:
    lib = load_library()
    if lib is None:
        raise RuntimeError("native library unavailable")
    return lib.bigdl_crc32c(data, len(data), crc)


def native_available() -> bool:
    return load_library() is not None


def parse_idx(data: bytes) -> np.ndarray:
    """Parse an MNIST idx buffer natively; raises if unavailable."""
    lib = load_library()
    if lib is None:
        raise RuntimeError("native library unavailable")
    cap = len(data)  # one float per byte max
    out = np.empty(cap, np.float32)
    dims = np.zeros(4, np.int32)
    ndim = ctypes.c_int32(0)
    rc = lib.bigdl_parse_idx(
        data, ctypes.c_int64(len(data)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_int64(cap),
        dims.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.byref(ndim))
    if rc != 0:
        raise ValueError(f"idx parse failed (code {rc})")
    shape = tuple(int(d) for d in dims[:ndim.value])
    return out[:int(np.prod(shape))].reshape(shape)


def parse_cifar(data: bytes, max_records: int = 1 << 30):
    lib = load_library()
    if lib is None:
        raise RuntimeError("native library unavailable")
    rec = 1 + 3 * 32 * 32
    n = min(len(data) // rec, max_records)
    imgs = np.empty((n, 3, 32, 32), np.float32)
    lbls = np.empty((n,), np.float32)
    got = lib.bigdl_parse_cifar(
        data, ctypes.c_int64(len(data)),
        imgs.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        lbls.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_int64(n))
    return imgs[:got], lbls[:got]


class NativeBatchLoader:
    """Threaded augmenting loader over an in-memory [N,C,H,W] dataset
    (the MTLabeledBGRImgToBatch analogue). Yields (images, labels) float32
    batches: random pad-crop + h-flip + normalize in C++ threads."""

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 batch_size: int, *, crop: Optional[tuple] = None,
                 pad: int = 0, flip: bool = True, train: bool = True,
                 mean=None, std=None, num_threads: int = 4,
                 prefetch: int = 4, seed: int = 0):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.images = np.ascontiguousarray(images, np.float32)
        self.labels = np.ascontiguousarray(labels, np.float32)
        n, c, h, w = self.images.shape
        if n <= 0:
            raise ValueError("NativeBatchLoader needs a non-empty dataset")
        if len(self.labels) < n:
            raise ValueError(
                f"labels ({len(self.labels)}) shorter than images ({n}) "
                "— C++ workers index labels[0:n)")
        if c > 8:
            raise ValueError("NativeBatchLoader supports at most 8 "
                             "channels (mean/std are fixed-size in C++)")
        ch, cw = crop or (h, w)
        self.batch_size = batch_size
        self.out_shape = (batch_size, c, ch, cw)
        mean = np.asarray(mean if mean is not None else [0.0] * c,
                          np.float32)
        std = np.asarray(std if std is not None else [1.0] * c, np.float32)
        self._handle = lib.bigdl_loader_create(
            self.images.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self.labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.c_int64(n), c, h, w, ch, cw, pad, batch_size,
            int(flip), int(train),
            mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            num_threads, prefetch, ctypes.c_uint64(seed))
        if not self._handle:
            raise ValueError("bigdl_loader_create rejected the config")
        self._lock = threading.Lock()  # serializes next_batch vs close

    def next_batch(self):
        imgs = np.empty(self.out_shape, np.float32)
        lbls = np.empty((self.batch_size,), np.float32)
        with self._lock:
            if not self._handle:
                raise RuntimeError("loader is closed")
            got = self._lib.bigdl_loader_next(
                self._handle,
                imgs.ctypes.data_as(ctypes.c_void_p),
                lbls.ctypes.data_as(ctypes.c_void_p))
        if got == 0:  # loader is stopping; the buffers are uninitialized
            raise RuntimeError("loader stopped")
        return imgs, lbls

    def __iter__(self):
        while True:
            yield self.next_batch()

    def close(self):
        with getattr(self, "_lock", _NULL_LOCK):
            if getattr(self, "_handle", None):
                self._lib.bigdl_loader_destroy(self._handle)
                self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeBatchLoaderU8:
    """uint8 variant of NativeBatchLoader: crop+flip only, NO normalize.

    Batches cross the host->device link at 1/4 the float32 bytes; do
    ``(x - mean) / std`` on
    device, where XLA fuses it into the first conv.
    """

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 batch_size: int, *, crop: Optional[tuple] = None,
                 pad: int = 0, flip: bool = True, train: bool = True,
                 num_threads: int = 4, prefetch: int = 4, seed: int = 0):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.images = np.ascontiguousarray(images, np.uint8)
        self.labels = np.ascontiguousarray(labels, np.float32)
        n, c, h, w = self.images.shape
        if n <= 0:
            raise ValueError("NativeBatchLoaderU8 needs a non-empty dataset")
        if len(self.labels) < n:
            raise ValueError(
                f"labels ({len(self.labels)}) shorter than images ({n}) "
                "— C++ workers index labels[0:n)")
        ch, cw = crop or (h, w)
        self.batch_size = batch_size
        self.out_shape = (batch_size, c, ch, cw)
        self._handle = lib.bigdl_loader_u8_create(
            self.images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            self.labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.c_int64(n), c, h, w, ch, cw, pad, batch_size,
            int(flip), int(train), num_threads, prefetch,
            ctypes.c_uint64(seed))
        if not self._handle:
            raise ValueError("bigdl_loader_u8_create rejected the config")
        self._lock = threading.Lock()  # serializes next_batch vs close

    def next_batch(self):
        imgs = np.empty(self.out_shape, np.uint8)
        lbls = np.empty((self.batch_size,), np.float32)
        with self._lock:
            if not self._handle:
                raise RuntimeError("loader is closed")
            got = self._lib.bigdl_loader_u8_next(
                self._handle,
                imgs.ctypes.data_as(ctypes.c_void_p),
                lbls.ctypes.data_as(ctypes.c_void_p))
        if got == 0:  # loader is stopping; the buffers are uninitialized
            raise RuntimeError("loader stopped")
        return imgs, lbls

    def __iter__(self):
        while True:
            yield self.next_batch()

    def close(self):
        with getattr(self, "_lock", _NULL_LOCK):
            if getattr(self, "_handle", None):
                self._lib.bigdl_loader_u8_destroy(self._handle)
                self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
