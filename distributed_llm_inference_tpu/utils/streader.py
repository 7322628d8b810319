"""ctypes driver for the native safetensors reader (``native/streader.cc``).

The TPU-native analog of the Rust ``safetensors`` extension the reference
leans on (``/root/reference/distributed_llm_inference/utils/model.py:4,19``):
the C++ side mmaps the checkpoint and services tensor reads as multithreaded
copies out of the mapping (with ``madvise`` prefetch); the tiny JSON header
is parsed here. Falls back cleanly: callers should use
:func:`native_available` / catch and take the pure-Python ``safetensors``
path (``utils/checkpoint.py`` does).
"""

from __future__ import annotations

import ctypes
import json
import os
import warnings
from typing import Dict, List, Optional, Sequence

import numpy as np

from .native_build import build_shared

__all__ = ["NativeSafetensors", "build_native", "native_available", "DTYPES"]

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_SRC = os.path.join(_NATIVE_DIR, "streader.cc")
_SO = os.path.join(_NATIVE_DIR, "_streader.so")
_lib = None


def _bf16():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


# safetensors dtype tag → numpy dtype factory (bf16 needs ml_dtypes).
DTYPES = {
    "F64": lambda: np.dtype(np.float64),
    "F32": lambda: np.dtype(np.float32),
    "F16": lambda: np.dtype(np.float16),
    "BF16": _bf16,
    "I64": lambda: np.dtype(np.int64),
    "I32": lambda: np.dtype(np.int32),
    "I16": lambda: np.dtype(np.int16),
    "I8": lambda: np.dtype(np.int8),
    "U8": lambda: np.dtype(np.uint8),
    "BOOL": lambda: np.dtype(np.bool_),
}


def build_native(force: bool = False) -> str:
    """Compile ``streader.cc`` → ``_streader.so`` (reused while the source's
    hash is unchanged; see :func:`utils.native_build.build_shared`)."""
    return build_shared(_SRC, _SO, force)


def _load_lib():
    global _lib
    if _lib is False:
        raise RuntimeError("native streader unavailable (earlier build failed)")
    if _lib is not None:
        return _lib
    try:
        lib = ctypes.CDLL(build_native())
    except Exception as e:
        # Cache the failure: without this, every shard read on the startup
        # path would re-spawn a doomed g++ subprocess. Said once, since
        # native_available() turns the raise into a quiet False.
        _lib = False
        warnings.warn(
            f"native safetensors reader unavailable ({e!r}); checkpoints "
            "are read through the safetensors wheel"
        )
        raise
    lib.st_open.restype = ctypes.c_void_p
    lib.st_open.argtypes = [ctypes.c_char_p]
    lib.st_header_len.restype = ctypes.c_uint64
    lib.st_header_len.argtypes = [ctypes.c_void_p]
    lib.st_header.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.st_header.argtypes = [ctypes.c_void_p]
    lib.st_data_len.restype = ctypes.c_uint64
    lib.st_data_len.argtypes = [ctypes.c_void_p]
    lib.st_prefetch.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64]
    lib.st_copy.restype = ctypes.c_int32
    lib.st_copy.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_void_p
    ]
    lib.st_copy_many.restype = ctypes.c_int32
    lib.st_copy_many.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_int32,
        ctypes.c_int32,
    ]
    lib.st_close.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def native_available() -> bool:
    try:
        _load_lib()
        return True
    except Exception:
        return False


class NativeSafetensors:
    """One open safetensors file; read tensors by name.

    Usage::

        with NativeSafetensors(path) as f:
            state = f.read_many([k for k in f.keys() if wanted(k)])
    """

    def __init__(self, path: str, threads: Optional[int] = None):
        lib = _load_lib()
        self._lib = lib
        self._h = lib.st_open(path.encode())
        if not self._h:
            raise OSError(f"st_open failed for {path!r} (missing/truncated?)")
        self.threads = threads or min(8, os.cpu_count() or 1)
        hlen = lib.st_header_len(self._h)
        raw = ctypes.string_at(lib.st_header(self._h), hlen)
        header = json.loads(raw)
        header.pop("__metadata__", None)
        self._meta: Dict[str, dict] = header
        self._data_len = lib.st_data_len(self._h)

    def keys(self) -> List[str]:
        return list(self._meta)

    def _spec(self, name: str):
        m = self._meta[name]
        dtype = DTYPES[m["dtype"]]()
        begin, end = m["data_offsets"]
        shape = tuple(m["shape"])
        expect = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        if end - begin != expect or end > self._data_len:
            raise ValueError(f"corrupt tensor entry {name!r}")
        return dtype, shape, begin, end

    def read(self, name: str) -> np.ndarray:
        dtype, shape, begin, end = self._spec(name)
        out = np.empty(shape, dtype)
        if self._lib.st_copy(
            self._h, begin, end - begin, out.ctypes.data_as(ctypes.c_void_p)
        ):
            raise ValueError(f"out-of-range read for {name!r}")
        return out

    def read_many(self, names: Sequence[str]) -> Dict[str, np.ndarray]:
        """Allocate destinations, then drain all copies with the native
        thread pool (prefetching the spanned range first)."""
        specs = {n: self._spec(n) for n in names}
        if not specs:
            return {}
        lo = min(s[2] for s in specs.values())
        hi = max(s[3] for s in specs.values())
        self._lib.st_prefetch(self._h, lo, hi - lo)

        out = {n: np.empty(shape, dtype) for n, (dtype, shape, _, _) in specs.items()}
        n = len(names)
        offs = (ctypes.c_uint64 * n)(*(specs[k][2] for k in names))
        lens = (ctypes.c_uint64 * n)(*(specs[k][3] - specs[k][2] for k in names))
        dsts = (ctypes.c_void_p * n)(
            *(out[k].ctypes.data_as(ctypes.c_void_p).value for k in names)
        )
        if self._lib.st_copy_many(self._h, offs, lens, dsts, n, self.threads):
            raise ValueError("out-of-range read in batch")
        return out

    def close(self) -> None:
        if self._h:
            self._lib.st_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        except Exception:
            pass
