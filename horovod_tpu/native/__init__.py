"""Native core loader — builds (if needed) and binds libhvdt_core.so.

The reference loads its C++ core from Python via ctypes
(ref: horovod/common/basics.py:33-34 loading mpi_lib_v2); same pattern
here: a C API (native/include/hvdt.h) over the native runtime pieces that
remain host-side on TPU — the TCP host-collective backend (Gloo analog),
the async timeline writer, and Adasum host math.

The library is compiled on demand with the in-image g++ via native/Makefile
(no pip/pybind11 dependency — plain ctypes).  ``available()`` gates all
callers so pure-Python fallbacks keep working where a toolchain is absent.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

__all__ = ["available", "load", "NativeError", "TcpProcessGroup",
           "NativeTimeline", "adasum_combine"]

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, os.pardir, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libhvdt_core.so")
# Installed-wheel location: setup.py ships the prebuilt library inside the
# package (no source tree / toolchain on the install host).
_PKG_LIB_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "_lib", "libhvdt_core.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed: Optional[str] = None


class NativeError(RuntimeError):
    """A native-core call returned nonzero; message from hvdt_last_error."""


def _build() -> Optional[str]:
    """Run ``make`` in the source tree; returns None on success, else
    why the build failed."""
    try:
        # Cross-process lock: multiple ranks on one host all call load()
        # on startup; without it concurrent `make` invocations write the
        # same .o/.so and a rank can dlopen a half-written library.
        import fcntl

        with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            try:
                subprocess.run(["make", "-C", _NATIVE_DIR],
                               capture_output=True, check=True, timeout=300)
            finally:
                fcntl.flock(lockf, fcntl.LOCK_UN)
    except subprocess.CalledProcessError as e:
        tail = (e.stderr or e.stdout or b"").decode("utf-8", "replace")
        return f"make failed (rc {e.returncode}): {tail[-400:]}"
    except (subprocess.SubprocessError, OSError) as e:
        return repr(e)
    if not os.path.exists(_LIB_PATH):
        return f"make succeeded but left no {_LIB_PATH}"
    return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c_p, c_i, c_i64 = ctypes.c_void_p, ctypes.c_int, ctypes.c_int64
    c_pp = ctypes.POINTER(ctypes.c_void_p)
    c_i64p = ctypes.POINTER(c_i64)
    lib.hvdt_last_error.restype = ctypes.c_char_p
    lib.hvdt_dtype_size.restype = c_i64
    lib.hvdt_dtype_size.argtypes = [c_i]
    lib.hvdt_tcp_group_create.argtypes = [c_i, c_i, ctypes.c_char_p, c_i,
                                          c_pp]
    lib.hvdt_tcp_group_destroy.argtypes = [c_p]
    lib.hvdt_group_rank.argtypes = [c_p]
    lib.hvdt_group_size.argtypes = [c_p]
    lib.hvdt_allreduce.argtypes = [c_p, c_p, c_i64, c_i, c_i]
    lib.hvdt_allgatherv.argtypes = [c_p, c_p, c_i64, c_p, c_i64p, c_i]
    lib.hvdt_broadcast.argtypes = [c_p, c_p, c_i64, c_i]
    lib.hvdt_alltoallv.argtypes = [c_p, c_p, c_i64p, c_p, c_i64p, c_i]
    lib.hvdt_barrier.argtypes = [c_p]
    lib.hvdt_adasum_allreduce.argtypes = [c_p, c_p, c_i64, c_i]
    lib.hvdt_adasum_combine.argtypes = [c_p, c_p, c_i64, c_i]
    lib.hvdt_timeline_create.argtypes = [ctypes.c_char_p, c_pp]
    lib.hvdt_timeline_event.argtypes = [c_p, ctypes.c_char_p,
                                        ctypes.c_char_p, ctypes.c_char,
                                        c_i64, c_i64, ctypes.c_char_p]
    lib.hvdt_timeline_close.argtypes = [c_p]
    return lib


def load() -> ctypes.CDLL:
    """Load (building first if necessary) the native core; raises on
    failure — use available() to probe."""
    global _lib, _load_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _load_failed is not None:
            raise NativeError(_load_failed)
        # In a source tree always run make: the Makefile's dependency
        # tracking no-ops when the .so is current and rebuilds it when a
        # C++ source changed.  The .so is a build artifact (gitignored),
        # so a failed build is an error even when an older binary lies
        # around — a stale library must never stand in for the sources.
        # Installed wheels have no source tree; they use the library
        # setup.py packaged next to this module.
        if os.path.exists(os.path.join(_NATIVE_DIR, "Makefile")):
            err = _build()
            if err is not None:
                _load_failed = f"native core build failed: {err}"
                raise NativeError(_load_failed)
            lib_path = _LIB_PATH
        elif os.path.exists(_PKG_LIB_PATH):
            lib_path = _PKG_LIB_PATH
        else:
            _load_failed = ("native core unavailable (no source tree to "
                            "build and no packaged library)")
            raise NativeError(_load_failed)
        try:
            _lib = _bind(ctypes.CDLL(lib_path))
        except OSError as e:  # pragma: no cover - load error surface
            _load_failed = f"cannot load {lib_path}: {e}"
            raise NativeError(_load_failed)
        return _lib


def available() -> bool:
    try:
        load()
        return True
    except NativeError:
        return False


def _check(lib: ctypes.CDLL, rc: int) -> None:
    if rc != 0:
        raise NativeError(lib.hvdt_last_error().decode("utf-8", "replace"))


from .tcp import TcpProcessGroup, adasum_combine  # noqa: E402
from .timeline_native import NativeTimeline  # noqa: E402
