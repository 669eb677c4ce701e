"""ctypes bindings for the C++ host runtime (native/dl4j_host.cpp).

The reference's native layer was external C++ (libnd4j BLAS/CUDA + Canova
ETL, SURVEY §0/§2.2). Here the *compute* native layer is XLA/PJRT (bundled
with JAX); this module is the native *host* layer: record parsing and
read-ahead streaming off the Python heap.

The shared library is compiled on first use with g++ (no pybind11 in the
image; plain C ABI + ctypes) next to this file, under a name that carries a
hash of the committed source — a library built from any other source is
never loaded. Every entry point has a pure-Python fallback for machines
without the toolchain — ``is_available()`` is advisory. With g++ present a
failed build raises: the Python path is not a silent substitute for a
broken native one.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(os.path.dirname(_HERE)),
                    "native", "dl4j_host.cpp")
_SO_PREFIX = "_dl4j_host-"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _so_path() -> str:
    """Where the library built from the source AS COMMITTED lives: the
    name carries the source's content hash, so staleness never depends on
    mtimes (which a copy or checkout resets)."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"{_SO_PREFIX}{digest}.so")


def _build(so: str) -> bool:
    """Compile ``_SRC`` to ``so``. False when there is no g++ (callers
    take the Python path); a compile that fails raises."""
    # compile to a private temp path, then atomically publish: concurrent
    # processes (multi-host launcher workers) must never dlopen a torn .so
    tmp = f"{so}.build-{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        os.replace(tmp, so)
    except FileNotFoundError:
        return False
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"building {_SRC} failed (rc={e.returncode}):\n"
            f"{e.stderr.decode(errors='replace')[-2000:]}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # libraries of other source revisions are dead weight
    for name in os.listdir(_HERE):
        if (name.startswith(_SO_PREFIX) and name.endswith(".so")
                and os.path.join(_HERE, name) != so):
            os.unlink(os.path.join(_HERE, name))
    return True


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c_p, c_i64, c_i32 = ctypes.c_void_p, ctypes.c_int64, ctypes.c_int
    lib.dl4j_buf_data.restype = ctypes.POINTER(ctypes.c_float)
    lib.dl4j_buf_data.argtypes = [c_p]
    lib.dl4j_buf_size.restype = c_i64
    lib.dl4j_buf_size.argtypes = [c_p]
    lib.dl4j_buf_ndim.restype = c_i32
    lib.dl4j_buf_ndim.argtypes = [c_p]
    lib.dl4j_buf_dims.restype = None
    lib.dl4j_buf_dims.argtypes = [c_p, ctypes.POINTER(c_i64)]
    lib.dl4j_buf_free.restype = None
    lib.dl4j_buf_free.argtypes = [c_p]
    lib.dl4j_csv_parse.restype = c_p
    lib.dl4j_csv_parse.argtypes = [ctypes.c_char_p, ctypes.c_char, c_i64]
    lib.dl4j_svmlight_parse.restype = c_p
    lib.dl4j_svmlight_parse.argtypes = [ctypes.c_char_p, c_i64, c_i32]
    lib.dl4j_idx_parse.restype = c_p
    lib.dl4j_idx_parse.argtypes = [ctypes.c_char_p]
    lib.dl4j_stream_open.restype = c_p
    lib.dl4j_stream_open.argtypes = [ctypes.c_char_p, c_i64, c_i64]
    lib.dl4j_stream_next.restype = c_i64
    lib.dl4j_stream_next.argtypes = [c_p, ctypes.c_char_p]
    lib.dl4j_stream_close.restype = None
    lib.dl4j_stream_close.argtypes = [c_p]
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if not os.path.exists(_SRC):
            _load_failed = True
            return None
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            _load_failed = True
            return None
        _lib = _bind(ctypes.CDLL(so))
        return _lib


def is_available() -> bool:
    return _load() is not None


def _buf_to_flat(lib, handle) -> Tuple[np.ndarray, Tuple[int, ...]]:
    """Copy a native buffer out as (flat float32 array, header dims).
    The flat size may exceed prod(dims) — e.g. SVMLight appends labels."""
    try:
        size = lib.dl4j_buf_size(handle)
        ndim = lib.dl4j_buf_ndim(handle)
        dims = (ctypes.c_int64 * max(ndim, 1))()
        lib.dl4j_buf_dims(handle, dims)
        shape = tuple(dims[i] for i in range(ndim))
        if size == 0:  # empty vector: .data() is NULL
            return np.zeros((0,), np.float32), shape
        flat = np.ctypeslib.as_array(lib.dl4j_buf_data(handle),
                                     shape=(size,)).astype(np.float32,
                                                           copy=True)
        return flat, shape
    finally:
        lib.dl4j_buf_free(handle)


def csv_to_array(path: str, delimiter: str = ",",
                 skip_lines: int = 0) -> Optional[np.ndarray]:
    """Parse an all-numeric CSV into [rows, cols] float32. None when the
    file is non-numeric/ragged (caller uses the Python text path) or the
    native library is unavailable."""
    lib = _load()
    if lib is None or len(delimiter) != 1:
        return None
    h = lib.dl4j_csv_parse(path.encode(), delimiter.encode(), skip_lines)
    if not h:
        return None
    flat, shape = _buf_to_flat(lib, h)
    return flat.reshape(shape)


def svmlight_to_arrays(path: str, num_features: int,
                       zero_based: bool = False
                       ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Parse SVMLight into (features [rows, n], labels [rows])."""
    lib = _load()
    if lib is None:
        return None
    h = lib.dl4j_svmlight_parse(path.encode(), num_features,
                                1 if zero_based else 0)
    if not h:
        return None
    # buffer layout: rows*n features then rows labels (dims = [rows, n])
    flat, (rows, n) = _buf_to_flat(lib, h)
    feats = flat[:rows * n].reshape(rows, n)
    labels = flat[rows * n:rows * n + rows]
    return feats, labels


def idx_to_array(path: str) -> Optional[np.ndarray]:
    """Parse an idx (MNIST) file into a float32 array with header dims."""
    lib = _load()
    if lib is None:
        return None
    h = lib.dl4j_idx_parse(path.encode())
    if not h:
        return None
    flat, shape = _buf_to_flat(lib, h)
    return flat.reshape(shape)


class FileStreamer:
    """Background read-ahead over a binary file of fixed-size chunks.

    The native analogue of AsyncDataSetIterator's prefetch thread: a C++
    thread fills a bounded ring; ``next()`` blocks on the condition
    variable, never the file. Iterate to EOF or ``close()`` early.
    """

    def __init__(self, path: str, chunk_bytes: int, capacity: int = 4):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.chunk_bytes = chunk_bytes
        # one reusable receive buffer: next() calls are serialized per
        # streamer, and a fresh create_string_buffer per chunk would zero +
        # copy every chunk twice on the hot prefetch path
        self._buf = ctypes.create_string_buffer(chunk_bytes)
        self._h = lib.dl4j_stream_open(path.encode(), chunk_bytes, capacity)
        if not self._h:
            raise OSError(f"cannot stream {path}")

    def next(self) -> Optional[bytes]:
        if self._h is None:  # closed: C side would deref NULL
            return None
        got = self._lib.dl4j_stream_next(self._h, self._buf)
        if got == 0:
            return None
        return self._buf.raw[:got]

    def __iter__(self):
        while (b := self.next()) is not None:
            yield b

    def close(self) -> None:
        if self._h:
            self._lib.dl4j_stream_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        # a dropped streamer must release the C++ reader thread + FILE*
        try:
            self.close()
        except Exception:
            pass
