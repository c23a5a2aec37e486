"""ctypes bindings for the native host runtime (``csrc/bigdl_host.cpp``).

The reference ships its native layer as prebuilt ``bigdl-core`` jars loaded
over JNI (SURVEY.md §2.6); here the C++ library is built from source with
``make``/:func:`build` and loaded with ctypes — no binding generator needed.
Every entry point has a numpy fallback, so the framework is fully functional
without the library; the native path is a host-side throughput optimization
(event-file CRC framing, fused image normalize+transpose, threaded minibatch
gather).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "csrc")
_LIB_PATH = os.path.join(_CSRC, "libbigdl_host.so")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def build(quiet: bool = True) -> bool:
    """Compile the library with make; returns True on success."""
    try:
        subprocess.run(
            ["make", "-C", _CSRC],
            check=True,
            capture_output=quiet,
        )
    except (OSError, subprocess.CalledProcessError):
        return False
    global _tried
    _tried = False  # allow the next load attempt to pick up the fresh build
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    path = os.environ.get("BIGDL_TPU_NATIVE_LIB", _LIB_PATH)
    if not os.path.exists(path):
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    lib.bigdl_crc32c.restype = ctypes.c_uint32
    lib.bigdl_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.bigdl_u8hwc_to_f32chw.restype = None
    lib.bigdl_u8hwc_to_f32chw.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.bigdl_gather_f32.restype = None
    lib.bigdl_gather_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int64,
    ]
    lib.bigdl_host_abi_version.restype = ctypes.c_int
    if lib.bigdl_host_abi_version() != 1:
        return None
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


# ------------------------------------------------------------------- crc32c
def crc32c(data: bytes) -> int:
    """Castagnoli CRC of ``data`` (native slice-by-8 when built)."""
    lib = _load()
    if lib is not None:
        return int(lib.bigdl_crc32c(data, len(data)))
    from .visualization.tb import _py_crc32c

    return _py_crc32c(data)


# --------------------------------------------------------- image batch prep
def u8hwc_to_f32chw(batch: np.ndarray, mean, std) -> np.ndarray:
    """Fused (x - mean)/std + HWC->CHW over a uint8 image batch (N, H, W, C).

    The host input pipeline's hot step (reference: OpenCV normalize +
    MatToTensor); native path threads across images.
    """
    batch = np.ascontiguousarray(batch)
    if batch.dtype != np.uint8 or batch.ndim != 4:
        raise ValueError(f"expected uint8 (N,H,W,C), got {batch.dtype} {batch.shape}")
    n, h, w, c = batch.shape
    mean = np.ascontiguousarray(np.broadcast_to(np.asarray(mean, np.float32), (c,)))
    std = np.ascontiguousarray(np.broadcast_to(np.asarray(std, np.float32), (c,)))
    lib = _load()
    if lib is None:
        out = (batch.astype(np.float32) - mean) / std
        return np.ascontiguousarray(out.transpose(0, 3, 1, 2))
    dst = np.empty((n, c, h, w), np.float32)
    lib.bigdl_u8hwc_to_f32chw(
        batch.ctypes.data, dst.ctypes.data, n, h, w, c,
        mean.ctypes.data, std.ctypes.data,
    )
    return dst


# ------------------------------------------------------------ batch gather
# below this, thread spawn/join overhead beats the memcpy win — stay serial
# (numpy) for small minibatches
_GATHER_NATIVE_MIN_BYTES = 1 << 20


def gather_rows(src: np.ndarray, indices: np.ndarray,
                out: Optional[np.ndarray] = None) -> np.ndarray:
    """dst[i] = src[indices[i]] over the leading axis (minibatch assembly).

    Native (threaded) only for float32 contiguous sources with enough bytes of
    work to amortize the thread pool; numpy fancy indexing otherwise.

    ``out`` (C-contiguous, writable, of the result's shape and ``src``'s
    dtype) is written in place of a fresh array and returned: a buffer the
    process has written before costs no page faults, which a fresh one of
    batch size pays on every 4 KB page.
    """
    indices = np.ascontiguousarray(np.asarray(indices, np.int64))
    # validate BEFORE choosing a path: the numpy fallback would otherwise
    # silently wrap negative indices while the native branch raises
    if indices.size and (indices.min() < 0 or indices.max() >= src.shape[0]):
        raise IndexError("gather index out of range")
    shape = (len(indices),) + src.shape[1:]
    if out is not None and (
        not isinstance(out, np.ndarray)
        or out.shape != shape
        or out.dtype != src.dtype
        or not out.flags["C_CONTIGUOUS"]
        or not out.flags["WRITEABLE"]
    ):
        raise ValueError(
            f"out must be a writable C-contiguous {src.dtype} array of shape "
            f"{shape}")
    row_len = int(np.prod(src.shape[1:], dtype=np.int64))
    work_bytes = len(indices) * row_len * 4
    lib = _load()
    if (
        lib is None
        or src.dtype != np.float32
        or not src.flags["C_CONTIGUOUS"]
        or work_bytes < _GATHER_NATIVE_MIN_BYTES
    ):
        if out is None:
            return np.ascontiguousarray(src[indices])
        # bounds were checked above; "clip" only spares numpy the bounce
        # buffer its default mode gathers into before copying to ``out``
        return np.take(src, indices, axis=0, out=out, mode="clip")
    dst = np.empty(shape, np.float32) if out is None else out
    lib.bigdl_gather_f32(
        src.ctypes.data, indices.ctypes.data, dst.ctypes.data,
        len(indices), row_len,
    )
    return dst
