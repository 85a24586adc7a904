"""ctypes binding for the native (C++) prefetching token loader.

``NativeTokenLoader`` is the production twin of
:class:`utils.data.TokenFileDataset`: the same random-crop / next-token-shift
semantics (tested equivalent in distribution), but crop assembly runs in
background C++ threads over an mmap'd file with a bounded prefetch queue —
the Python thread's cost per batch is one memcpy. Build/fallback convention
matches :mod:`parallel.native` (the schedule engine): built on first use via
``csrc/Makefile``; callers that can live without it should check
:func:`native_loader_available` and fall back to ``TokenFileDataset``.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Iterator, Tuple

import numpy as np

from ..parallel.native import NativeLib

_DTYPE_CODES = {np.dtype(np.uint16): 0, np.dtype(np.int32): 1}


def _configure(lib: ctypes.CDLL) -> None:
    lib.dtpp_dl_open.restype = ctypes.c_void_p
    lib.dtpp_dl_open.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
        ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_int,
    ]
    lib.dtpp_dl_next.restype = ctypes.c_int
    lib.dtpp_dl_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.dtpp_dl_stop.restype = None
    lib.dtpp_dl_stop.argtypes = [ctypes.c_void_p]
    lib.dtpp_dl_close.restype = None
    lib.dtpp_dl_close.argtypes = [ctypes.c_void_p]


_loader_lib = NativeLib("libdata_loader.so", _configure)


def _load():
    return _loader_lib.get()


def native_loader_available() -> bool:
    return _load() is not None


class NativeTokenLoader:
    """Threaded native random-crop loader over a flat binary token file.

    Yields ``(tokens, targets)`` int32 ``[batch_size, seq_length]`` pairs,
    targets shifted by one (``TokenFileDataset.sample`` semantics). With
    ``n_threads=1`` the batch stream is deterministic in ``seed``.
    """

    def __init__(self, path: str, seq_length: int, batch_size: int,
                 dtype: np.dtype = np.uint16, seed: int = 0,
                 n_threads: int = 2, depth: int = 4):
        lib = _load()
        if lib is None:
            raise RuntimeError("native data loader unavailable (no compiler?)")
        code = _DTYPE_CODES.get(np.dtype(dtype))
        if code is None:
            raise ValueError(f"unsupported token dtype {dtype!r}; "
                             f"use uint16 or int32")
        err = ctypes.create_string_buffer(256)
        self._lib = lib
        self._handle = lib.dtpp_dl_open(
            os.fspath(path).encode(), seq_length, batch_size, code,
            seed, n_threads, depth, err, len(err))
        if not self._handle:
            raise ValueError(err.value.decode() or "dtpp_dl_open failed")
        self.seq_length = seq_length
        self.batch_size = batch_size
        # close() must not free the native Loader under a next() blocked in
        # C (ctypes releases the GIL): next() registers in-flight under this
        # condition, close() nulls the handle, stops the loader (which
        # unblocks readers), waits for in-flight to drain, then frees.
        self._cond = threading.Condition()
        self._inflight = 0

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        with self._cond:
            if self._handle is None:
                raise RuntimeError("loader is closed")
            handle = self._handle
            self._inflight += 1
        try:
            shape = (self.batch_size, self.seq_length)
            toks = np.empty(shape, np.int32)
            tgts = np.empty(shape, np.int32)
            rc = self._lib.dtpp_dl_next(
                handle,
                toks.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                tgts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        finally:
            with self._cond:
                self._inflight -= 1
                if self._inflight == 0:
                    self._cond.notify_all()
        if rc != 0:
            raise RuntimeError("loader closed while waiting for a batch")
        return toks, tgts

    def batches(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        while True:
            yield self.next()

    def close(self) -> None:
        with self._cond:
            if self._handle is None:
                return
            handle, self._handle = self._handle, None
            self._lib.dtpp_dl_stop(handle)  # unblocks in-flight next() calls
            while self._inflight:
                self._cond.wait()
        self._lib.dtpp_dl_close(handle)

    def __enter__(self) -> "NativeTokenLoader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
