"""Native (C++) runtime components, built on first use with the system g++.

The compute path is JAX/XLA/Pallas; the runtime around it — checkpoint IO
and the serving scheduler — has native implementations here, mirroring how
the reference leans on native code for its runtime (Candle's kernels,
mmap'd loading; SURVEY.md §2.5). Python fallbacks exist for every
component, so the framework works even where no C++ toolchain does:

  * csrc/safetensors.cpp — mmap'd safetensors reader (zero-copy tensor
    views + madvise prefetch), wrapped in native/safetensors.py
  * csrc/scheduler.cpp — thread-safe continuous-batching scheduler,
    wrapped in native/scheduler.py

The shared object is compiled once into _build/ (keyed on a source hash)
and dlopened via ctypes; no pip, no pybind11, no build system beyond g++.
It is opened twice: the safetensors calls (file mapping, prefetch) through
a `CDLL`, which lets the interpreter lock go around each call, and the
scheduler's through a `PyDLL`, which keeps it: they take microseconds under
the library's own mutex, and the engine makes one a row a token, where a
lock let go is a lock some other thread takes.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

log = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_CSRC = os.path.join(_HERE, "csrc")
_BUILD = os.path.join(_HERE, "_build")
_SOURCES = ("safetensors.cpp", "scheduler.cpp")

_lock = threading.Lock()
_lib = None
_sched_lib = None
_lib_error: str | None = None


def _source_hash() -> str:
    h = hashlib.sha256()
    for src in _SOURCES:
        with open(os.path.join(_CSRC, src), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _build_library() -> str:
    os.makedirs(_BUILD, exist_ok=True)
    tag = _source_hash()
    so_path = os.path.join(_BUILD, f"libcake_native_{tag}.so")
    if os.path.exists(so_path):
        return so_path
    srcs = [os.path.join(_CSRC, s) for s in _SOURCES]
    tmp = f"{so_path}.{os.getpid()}.tmp"  # per-process; replace is atomic
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
           "-o", tmp, *srcs, "-lpthread"]
    log.info("building native library: %s", " ".join(cmd))
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, so_path)  # atomic vs concurrent builders
    return so_path


def _declare_safetensors(lib) -> None:
    c = ctypes
    lib.cake_st_open.restype = c.c_void_p
    lib.cake_st_open.argtypes = [c.c_char_p, c.c_char_p, c.c_int]
    lib.cake_st_num_tensors.restype = c.c_int64
    lib.cake_st_num_tensors.argtypes = [c.c_void_p]
    lib.cake_st_name.restype = c.c_char_p
    lib.cake_st_name.argtypes = [c.c_void_p, c.c_int64]
    lib.cake_st_dtype.restype = c.c_char_p
    lib.cake_st_dtype.argtypes = [c.c_void_p, c.c_int64]
    lib.cake_st_ndim.restype = c.c_int32
    lib.cake_st_ndim.argtypes = [c.c_void_p, c.c_int64]
    lib.cake_st_shape.restype = None
    lib.cake_st_shape.argtypes = [c.c_void_p, c.c_int64,
                                  c.POINTER(c.c_int64)]
    lib.cake_st_data.restype = c.POINTER(c.c_uint8)
    lib.cake_st_data.argtypes = [c.c_void_p, c.c_int64,
                                 c.POINTER(c.c_int64)]
    lib.cake_st_prefetch.restype = None
    lib.cake_st_prefetch.argtypes = [c.c_void_p, c.c_int64]
    lib.cake_st_close.restype = None
    lib.cake_st_close.argtypes = [c.c_void_p]


def _declare_scheduler(lib) -> None:
    c = ctypes
    lib.cake_sched_create.restype = c.c_void_p
    lib.cake_sched_create.argtypes = [c.c_int32, c.c_int32]
    lib.cake_sched_destroy.restype = None
    lib.cake_sched_destroy.argtypes = [c.c_void_p]
    lib.cake_sched_submit.restype = c.c_int32
    lib.cake_sched_submit.argtypes = [c.c_void_p, c.c_uint64, c.c_int32,
                                      c.c_int32]
    lib.cake_sched_cancel.restype = c.c_int32
    lib.cake_sched_cancel.argtypes = [c.c_void_p, c.c_uint64]
    lib.cake_sched_plan.restype = c.c_int32
    lib.cake_sched_plan.argtypes = [
        c.c_void_p,
        c.POINTER(c.c_uint64), c.POINTER(c.c_int32), c.POINTER(c.c_int32),
        c.POINTER(c.c_uint64), c.POINTER(c.c_int32), c.POINTER(c.c_int32),
    ]
    lib.cake_sched_report.restype = c.c_int32
    lib.cake_sched_report.argtypes = [c.c_void_p, c.c_uint64, c.c_int32,
                                      c.c_int32]
    lib.cake_sched_queue_depth.restype = c.c_int32
    lib.cake_sched_queue_depth.argtypes = [c.c_void_p]
    lib.cake_sched_active.restype = c.c_int32
    lib.cake_sched_active.argtypes = [c.c_void_p]
    lib.cake_sched_completed.restype = c.c_uint64
    lib.cake_sched_completed.argtypes = [c.c_void_p]


def _load() -> None:
    global _lib, _sched_lib, _lib_error
    with _lock:
        if _lib is not None or _lib_error is not None:
            return
        try:
            so_path = _build_library()
            lib, sched = ctypes.CDLL(so_path), ctypes.PyDLL(so_path)
            _declare_safetensors(lib)
            _declare_scheduler(sched)
            _lib, _sched_lib = lib, sched
        except Exception as e:  # toolchain missing, build error, ...
            _lib_error = str(e)
            log.warning("native library unavailable (%s); "
                        "using Python fallbacks", e)


def get_library():
    """Build (if needed) and dlopen the native library, for the
    `cake_st_*` calls; None on failure."""
    _load()
    return _lib


def get_scheduler_library():
    """The same library for the `cake_sched_*` calls, which keep the
    interpreter lock; None on failure."""
    _load()
    return _sched_lib


def is_available() -> bool:
    return get_library() is not None
