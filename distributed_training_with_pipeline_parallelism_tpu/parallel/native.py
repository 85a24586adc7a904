"""ctypes binding for the native (C++) schedule-compilation engine.

``compile_schedule_native`` produces the same ``CompiledSchedule`` as the
Python compiler in :mod:`.schedules` (tables are asserted bit-identical in
tests); the Python path is the executable specification, this is the fast
production path. The shared library is built on first use with the repo's
``csrc/Makefile`` (plain g++, no external deps) from the sources in the
checkout; where that build cannot run, nothing is loaded and the caller
falls back to the Python compiler.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from ..utils.profiling import annotate

_CSRC = os.path.join(os.path.dirname(__file__), "..", "..", "csrc")


class NativeLib:
    """Lazy, cached loader for one csrc/ shared library.

    First use invokes make (mtime-incremental: a no-op when the .so is
    fresh, a rebuild when the source changed) and loads what make vouches
    for. Only that: a .so lying in ``csrc/`` (untracked — ``.gitignore``)
    that make could not check against the sources is never loaded, so what
    runs is always built from the ``.cpp`` files of this checkout.
    ``configure`` receives the CDLL to declare restype/argtypes. Build or
    load failure is cached; ``get()`` then returns None so callers can fall
    back to their Python twin.
    """

    def __init__(self, so_name: str, configure):
        self._so = os.path.abspath(os.path.join(_CSRC, so_name))
        self._configure = configure
        self._lock = threading.Lock()
        self._lib: Optional[ctypes.CDLL] = None
        self._failed = False

    def get(self) -> Optional[ctypes.CDLL]:
        with self._lock:
            if self._lib is not None or self._failed:
                return self._lib
            try:
                with annotate("setup/native_build"):
                    subprocess.run(["make", "-C", os.path.abspath(_CSRC)],
                                   check=True, capture_output=True)
                lib = ctypes.CDLL(self._so)
                self._configure(lib)
                self._lib = lib
            except (OSError, subprocess.CalledProcessError, AttributeError):
                self._failed = True  # no toolchain, failed build, bad symbols
            return self._lib


def _configure_schedule_engine(lib: ctypes.CDLL) -> None:
    lib.dtpp_compile_schedule.restype = ctypes.c_int
    lib.dtpp_compile_schedule.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.c_char_p, ctypes.c_int,
    ]


_engine = NativeLib("libschedule_engine.so", _configure_schedule_engine)


def _load() -> Optional[ctypes.CDLL]:
    return _engine.get()


def native_available() -> bool:
    return _load() is not None


@annotate("setup/schedule")
def compile_schedule_native(name: str, n_devices: int, n_virtual: int,
                            n_microbatches: int):
    """Native twin of ``schedules.compile_schedule`` (without the Action tick
    map — the table is the executor contract). Raises ScheduleError with the
    engine's message on invalid configs, RuntimeError if the library is
    unavailable."""
    from .schedules import (N_COLS, CompiledSchedule, ScheduleError,
                            verify_table)

    lib = _load()
    if lib is None:
        raise RuntimeError("native schedule engine unavailable (no compiler?)")
    S = n_devices * n_virtual
    n_actions = 3 * S * n_microbatches  # F + B + W upper bound
    cap_ticks = 4 * n_actions + 4 * S + 18
    table = np.full((cap_ticks, n_devices, N_COLS), -1, dtype=np.int32)
    t_out = ctypes.c_int()
    n_act = ctypes.c_int()
    n_grad = ctypes.c_int()
    err = ctypes.create_string_buffer(256)
    rc = lib.dtpp_compile_schedule(
        name.encode(), n_devices, n_virtual, n_microbatches,
        table.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), table.size,
        ctypes.byref(t_out), ctypes.byref(n_act), ctypes.byref(n_grad),
        err, len(err))
    if rc != 0:
        raise ScheduleError(err.value.decode())
    from .schedules import is_split_backward
    cs = CompiledSchedule(
        name=name, n_devices=n_devices, n_virtual=n_virtual,
        n_microbatches=n_microbatches, table=table[: t_out.value].copy(),
        makespan=t_out.value, ticks={}, n_act_slots=n_act.value,
        n_grad_slots=n_grad.value, split_backward=is_split_backward(name))
    verify_table(cs)
    return cs
