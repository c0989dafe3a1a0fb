"""Builds & loads the native C++ runtime library (csrc/) via ctypes.

No pybind11 in this environment — the C ABI + ctypes is the binding layer.
The build is lazy and kept in ``<checkout>/.native_build`` (or
``$PADDLE_TPU_CACHE``) under a name keyed by a hash of ``csrc/*.cc``, so
what loads is always built from the sources beside it — an older build
that merely exists is never reused. Failures leave `lib = None` and every
consumer falls back to pure Python.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import pathlib
import subprocess
import tempfile

_ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
_CSRC = _ROOT / "csrc"
_CACHE = pathlib.Path(
    os.environ.get("PADDLE_TPU_CACHE") or _ROOT / ".native_build")

lib = None


def _sources() -> list[pathlib.Path]:
    return sorted(_CSRC.glob("*.cc"))


def _so_path() -> pathlib.Path:
    h = hashlib.sha256()
    for src in _sources():
        h.update(src.name.encode())
        h.update(src.read_bytes())
    return _CACHE / f"libpaddle_tpu_runtime-{h.hexdigest()[:16]}.so"


def build(force=False):
    sources = _sources()
    if not sources:
        return None
    so = _so_path()
    if so.exists() and not force:
        return _load(so)
    _CACHE.mkdir(parents=True, exist_ok=True)
    # build beside the target and rename: a concurrent process never
    # loads a half-written library
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_CACHE)
    os.close(fd)
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
           "-o", tmp, *map(str, sources)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError):
        if os.path.exists(tmp):
            os.unlink(tmp)
        return None
    return _load(so)


def _load(so: pathlib.Path):
    global lib
    try:
        loaded = ctypes.CDLL(str(so))
    except OSError:
        lib = None
        return None
    _declare(loaded)
    lib = loaded
    return lib


def _declare(l):
    l.ptq_queue_new.restype = ctypes.c_void_p
    l.ptq_queue_new.argtypes = [ctypes.c_int]
    l.ptq_queue_put.restype = ctypes.c_int
    l.ptq_queue_put.argtypes = [ctypes.c_void_p, ctypes.c_long, ctypes.c_int]
    l.ptq_queue_get.restype = ctypes.c_long
    l.ptq_queue_get.argtypes = [ctypes.c_void_p, ctypes.c_int]
    l.ptq_queue_size.restype = ctypes.c_int
    l.ptq_queue_size.argtypes = [ctypes.c_void_p]
    l.ptq_queue_close.argtypes = [ctypes.c_void_p]
    # tcp store
    l.ptq_store_server_new.restype = ctypes.c_void_p
    l.ptq_store_server_new.argtypes = [ctypes.c_int]
    l.ptq_store_server_free.argtypes = [ctypes.c_void_p]
    l.ptq_store_client_new.restype = ctypes.c_void_p
    l.ptq_store_client_new.argtypes = [ctypes.c_char_p, ctypes.c_int]
    l.ptq_store_client_free.argtypes = [ctypes.c_void_p]
    l.ptq_store_set.restype = ctypes.c_int
    l.ptq_store_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
    l.ptq_store_get.restype = ctypes.c_int
    l.ptq_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                                ctypes.c_int, ctypes.c_int]
    l.ptq_store_add.restype = ctypes.c_long
    l.ptq_store_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long]
    l.ptq_store_wait.restype = ctypes.c_int
    l.ptq_store_wait.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    # ps tables (csrc/ps_table.cc)
    f32p = ctypes.POINTER(ctypes.c_float)
    i64p = ctypes.POINTER(ctypes.c_int64)
    l.ps_dense_new.restype = ctypes.c_void_p
    l.ps_dense_new.argtypes = [ctypes.c_int64]
    l.ps_dense_free.argtypes = [ctypes.c_void_p]
    l.ps_dense_assign.argtypes = [ctypes.c_void_p, f32p, ctypes.c_int64]
    l.ps_dense_read.argtypes = [ctypes.c_void_p, f32p, ctypes.c_int64]
    l.ps_dense_push_grad.argtypes = [ctypes.c_void_p, f32p, ctypes.c_int64]
    l.ps_dense_apply.restype = ctypes.c_double
    l.ps_dense_apply.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_float,
                                 ctypes.c_float]
    l.ps_sparse_new.restype = ctypes.c_void_p
    l.ps_sparse_new.argtypes = [ctypes.c_int, ctypes.c_uint64, ctypes.c_float]
    l.ps_sparse_free.argtypes = [ctypes.c_void_p]
    l.ps_sparse_size.restype = ctypes.c_int64
    l.ps_sparse_size.argtypes = [ctypes.c_void_p]
    l.ps_sparse_pull.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64, f32p]
    l.ps_sparse_assign.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64, f32p]
    l.ps_sparse_assign_state.argtypes = [ctypes.c_void_p, i64p,
                                         ctypes.c_int64, f32p, f32p]
    l.ps_sparse_export_state.restype = ctypes.c_int64
    l.ps_sparse_export_state.argtypes = [ctypes.c_void_p, i64p, f32p, f32p,
                                         ctypes.c_int64]
    l.ps_dense_read_acc.argtypes = [ctypes.c_void_p, f32p, ctypes.c_int64]
    l.ps_dense_assign_acc.argtypes = [ctypes.c_void_p, f32p, ctypes.c_int64]
    l.ps_sparse_push_grad.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64,
                                      f32p, ctypes.c_int, ctypes.c_float,
                                      ctypes.c_float]
    l.ps_sparse_export.restype = ctypes.c_int64
    l.ps_sparse_export.argtypes = [ctypes.c_void_p, i64p, f32p, ctypes.c_int64]
    l.ps_sparse_erase.restype = ctypes.c_int64
    l.ps_sparse_erase.argtypes = [ctypes.c_void_p, i64p, ctypes.c_int64]
    # host tracer (csrc/host_tracer.cc)
    l.host_tracer_new.restype = ctypes.c_void_p
    l.host_tracer_new.argtypes = [ctypes.c_int64]
    l.host_tracer_free.argtypes = [ctypes.c_void_p]
    l.host_tracer_now_ns.restype = ctypes.c_uint64
    l.host_tracer_record.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_uint64, ctypes.c_uint64,
                                     ctypes.c_uint64]
    l.host_tracer_count.restype = ctypes.c_int64
    l.host_tracer_count.argtypes = [ctypes.c_void_p]
    l.host_tracer_dropped.restype = ctypes.c_int64
    l.host_tracer_dropped.argtypes = [ctypes.c_void_p]
    l.host_tracer_clear.argtypes = [ctypes.c_void_p]
    l.host_tracer_export.restype = ctypes.c_int64
    l.host_tracer_export.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_char_p]


# attempt load of an existing build at import (no compile at import time)
if _sources() and _so_path().exists():
    _load(_so_path())
