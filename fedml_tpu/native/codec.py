"""Python binding for the C++ tensor-frame codec (``codec.cpp``).

Builds the shared library on first use with g++ (cached next to the
source; no pybind11 — plain ctypes over an ``extern "C"`` surface). Falls
back to pure-numpy implementations when no compiler is available, so the
transport layer never hard-depends on the native build.

Frame layout (see codec.cpp): little-endian header describing each tensor
(dtype, shape, nbytes) followed by 8-byte-aligned raw buffers. ``unpack``
returns zero-copy numpy views into the frame.
"""

from __future__ import annotations

import ctypes
import logging
import os
import struct
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "codec.cpp")
_LIB = os.path.join(_HERE, "_codec.so")
_lock = threading.Lock()
_lib = None
_tried = False

_DTYPES = [
    np.dtype("float32"), np.dtype("float64"), np.dtype("int32"),
    np.dtype("int64"), np.dtype("uint8"), np.dtype("bool"),
    np.dtype("float16"), np.dtype("int8"), np.dtype("uint16"),
    np.dtype("uint32"), np.dtype("uint64"), np.dtype("int16"),
]
_DTYPE_CODE = {d: i for i, d in enumerate(_DTYPES)}


def codec_supports(dtype) -> bool:
    """Whether the frame codec can carry this dtype (bf16/complex/object
    arrays must stay on the pickle path)."""
    try:
        return np.dtype(dtype) in _DTYPE_CODE
    except TypeError:
        return False


def _build() -> str | None:
    if os.path.exists(_LIB) and os.path.getmtime(_LIB) >= os.path.getmtime(
        _SRC
    ):
        return _LIB
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
             _SRC, "-o", _LIB],
            check=True, capture_output=True, timeout=120,
        )
        return _LIB
    except (OSError, subprocess.SubprocessError) as err:
        # said once, loudly: the wire then runs the numpy paths
        logging.getLogger(__name__).warning(
            "native codec not built (%s): falling back to the "
            "pure-numpy frame codec", err,
        )
        return None


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path = _build()
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        lib.fedml_crc32.restype = ctypes.c_uint32
        lib.fedml_crc32.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        PTRS = ctypes.POINTER(ctypes.c_void_p)
        U64S = ctypes.POINTER(ctypes.c_uint64)
        lib.fedml_copy_gather.argtypes = [
            ctypes.c_void_p, PTRS, U64S, U64S, ctypes.c_uint32,
            ctypes.c_uint32,
        ]
        lib.fedml_copy_scatter.argtypes = [
            ctypes.c_void_p, PTRS, U64S, U64S, ctypes.c_uint32,
            ctypes.c_uint32,
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def crc32(buf: bytes) -> int:
    lib = _load()
    if lib is None:
        import zlib

        return zlib.crc32(buf) & 0xFFFFFFFF
    return int(lib.fedml_crc32(buf, len(buf)))


_MAGIC = b"FTC1"


class TensorCodec:
    """Pack/unpack a flat list of numpy arrays into one contiguous frame."""

    def __init__(self, n_threads: int = 4):
        self.n_threads = n_threads

    # -- pack ---------------------------------------------------------------
    def pack(self, arrays: list[np.ndarray]) -> bytes:
        arrays = [np.ascontiguousarray(a) for a in arrays]
        header = bytearray()
        header += _MAGIC
        header += struct.pack("<I", len(arrays))
        offsets, sizes = [], []
        # compute payload offsets (8-byte aligned) after the header
        for a in arrays:
            code = _DTYPE_CODE[a.dtype]
            header += struct.pack("<II", code, a.ndim)
            header += struct.pack(f"<{a.ndim}q", *a.shape)
            header += struct.pack("<Q", a.nbytes)
        base = (len(header) + 8 + 7) & ~7  # + u64 payload start marker
        header += struct.pack("<Q", base)
        cur = base
        for a in arrays:
            offsets.append(cur)
            sizes.append(a.nbytes)
            cur = (cur + a.nbytes + 7) & ~7
        frame = bytearray(cur)
        frame[: len(header)] = header

        lib = _load()
        if lib is None or not arrays:
            for a, off in zip(arrays, offsets):
                frame[off:off + a.nbytes] = a.tobytes()
            return bytes(frame)

        n = len(arrays)
        src_ptrs = (ctypes.c_void_p * n)(
            *[a.ctypes.data for a in arrays]
        )
        size_arr = (ctypes.c_uint64 * n)(*sizes)
        off_arr = (ctypes.c_uint64 * n)(*offsets)
        dst = (ctypes.c_char * len(frame)).from_buffer(frame)
        lib.fedml_copy_gather(
            ctypes.addressof(dst),
            ctypes.cast(src_ptrs, ctypes.POINTER(ctypes.c_void_p)),
            size_arr, off_arr, n, self.n_threads,
        )
        return bytes(frame)

    # -- unpack -------------------------------------------------------------
    def unpack(self, frame: bytes) -> list[np.ndarray]:
        assert frame[:4] == _MAGIC, "bad tensor frame"
        view = memoryview(frame)
        pos = 4
        (n,) = struct.unpack_from("<I", view, pos)
        pos += 4
        metas = []
        for _ in range(n):
            code, ndim = struct.unpack_from("<II", view, pos)
            pos += 8
            shape = struct.unpack_from(f"<{ndim}q", view, pos)
            pos += 8 * ndim
            (nbytes,) = struct.unpack_from("<Q", view, pos)
            pos += 8
            metas.append((_DTYPES[code], shape, nbytes))
        (base,) = struct.unpack_from("<Q", view, pos)
        out = []
        cur = base
        for dtype, shape, nbytes in metas:
            arr = np.frombuffer(
                view[cur:cur + nbytes], dtype=dtype
            ).reshape(shape)
            out.append(arr)
            cur = (cur + nbytes + 7) & ~7
        return out
