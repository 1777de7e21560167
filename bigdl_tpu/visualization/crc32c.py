"""CRC32C (Castagnoli) with TFRecord masking.

Reference: spark/dl/src/main/java/netty/Crc32c.java (124 LoC) used by the
TensorBoard record writer (visualization/tensorboard/RecordWriter). A native
C++ implementation is loaded when available (bigdl_tpu/native); this pure
Python table-driven version is the portable fallback.
"""
from __future__ import annotations

import struct

_POLY = 0x82F63B78  # reversed CRC-32C polynomial
_TABLE = []
for _i in range(256):
    _c = _i
    for _ in range(8):
        _c = (_c >> 1) ^ _POLY if _c & 1 else _c >> 1
    _TABLE.append(_c)

_MASK_DELTA = 0xA282EAD8


def crc32c(data: bytes, crc: int = 0) -> int:
    crc = crc ^ 0xFFFFFFFF
    for b in data:
        crc = _TABLE[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def masked_crc32c(data: bytes) -> int:
    """TFRecord masked crc: rotate right 15 then add the mask delta."""
    crc = _crc_impl(data)
    return (((crc >> 15) | (crc << 17)) + _MASK_DELTA) & 0xFFFFFFFF


def unmask(masked: int) -> int:
    rot = (masked - _MASK_DELTA) & 0xFFFFFFFF
    return ((rot << 15) | (rot >> 17)) & 0xFFFFFFFF


def _crc_py(data: bytes) -> int:
    return crc32c(data)


def _crc_first(data: bytes) -> int:
    """The first checksum picks the implementation — importing this
    module opens no library."""
    _try_native()
    return _crc_impl(data)


_crc_impl = _crc_first


def _try_native():
    """Bind the C++ crc32c when the .so is ALREADY built (never compile
    on this path) and verified to work; the pure-Python one otherwise."""
    global _crc_impl
    _crc_impl = _crc_py
    try:
        from bigdl_tpu import native
        if native.load_library(build=False) is None:
            return
        if native.native_crc32c(b"123456789") != 0xE3069283:
            return
        _crc_impl = native.native_crc32c
    except Exception:
        pass
