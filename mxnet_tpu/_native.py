"""Loader + ctypes bindings for the native runtime (libmxtpu.so).

The reference ships its runtime as one C++ shared library loaded by the
Python frontend (reference: python/mxnet/base.py _load_lib / libinfo.py find_lib_path);
here the library holds the host-side runtime: the threaded dependency engine
(src/engine.cc), pooled host allocator (src/allocator.cc), sharded RecordIO
reader (src/recordio.cc) and the parameter-server transport (src/ps.cc).

Built on demand with `make` (g++) into mxnet_tpu/src/build/libmxtpu.so, which
git ignores: a fresh checkout builds it on first use. ``get_lib()`` returns
None if no toolchain is available — callers fall back to pure-python paths so
the framework stays importable anywhere — and ``status()`` says which of the
three happened.
"""
from __future__ import annotations

import contextlib
import ctypes
import fcntl
import logging
import os
import subprocess
import threading

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_LIB_PATH = os.path.join(_SRC_DIR, "build", "libmxtpu.so")

_lock = threading.Lock()
_lib = None
_tried = False
_status = "untried"


def status():
    """How this process came by the native runtime: ``"loaded"`` (found
    prebuilt), ``"built"`` (made by :func:`get_lib` in this process),
    ``"absent"`` (switched off, or the build or load failed) or
    ``"untried"`` (nothing has asked for it yet)."""
    return _status


def _build():
    try:
        subprocess.run(
            ["make", "-s", "-j4"], cwd=_SRC_DIR, check=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=300,
        )
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logging.getLogger(__name__).warning(
            "native runtime build failed (%s); using the pure-python paths: "
            "%s", type(e).__name__,
            (getattr(e, "stderr", None) or b"").decode(errors="replace")[-500:])
        return False


@contextlib.contextmanager
def _other_processes_wait():
    """An exclusive lock among PROCESSES over the look, the build and the
    load: workers that start together in a fresh checkout (six of pytest-
    xdist's) would each run ``make`` over the same files, and one would load
    what another's linker is still writing. The second waits for the first's
    ``make`` and loads the finished library. The lock is a file beside the
    library; a tree that cannot be written to has nothing to build either."""
    try:
        os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
        fd = os.open(_LIB_PATH + ".lock", os.O_CREAT | os.O_RDWR, 0o644)
    except OSError:
        yield
        return
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        os.close(fd)        # closing the description releases the lock


def _declare(lib):
    c = ctypes
    # engine
    lib.mxt_engine_create.restype = c.c_void_p
    lib.mxt_engine_create.argtypes = [c.c_int]
    lib.mxt_engine_destroy.argtypes = [c.c_void_p]
    lib.mxt_engine_new_var.restype = c.c_void_p
    lib.mxt_engine_new_var.argtypes = [c.c_void_p]
    lib.mxt_engine_delete_var.argtypes = [c.c_void_p, c.c_void_p]
    lib.mxt_engine_push.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p,
        c.POINTER(c.c_void_p), c.c_int, c.POINTER(c.c_void_p), c.c_int, c.c_int,
    ]
    lib.mxt_engine_wait_for_var.argtypes = [c.c_void_p, c.c_void_p]
    lib.mxt_engine_wait_all.argtypes = [c.c_void_p]
    lib.mxt_engine_outstanding.restype = c.c_longlong
    lib.mxt_engine_outstanding.argtypes = [c.c_void_p]
    # allocator
    lib.mxt_alloc.restype = c.c_void_p
    lib.mxt_alloc.argtypes = [c.c_size_t]
    lib.mxt_free.argtypes = [c.c_void_p, c.c_size_t]
    lib.mxt_pool_in_use.restype = c.c_longlong
    lib.mxt_pool_pooled.restype = c.c_longlong
    lib.mxt_pool_set_cap.argtypes = [c.c_longlong]
    # recordio
    lib.mxt_rec_reader_open.restype = c.c_void_p
    lib.mxt_rec_reader_open.argtypes = [c.c_char_p, c.c_int, c.c_int, c.c_int]
    lib.mxt_rec_reader_next.restype = c.c_int
    lib.mxt_rec_reader_next.argtypes = [
        c.c_void_p, c.POINTER(c.POINTER(c.c_char)), c.POINTER(c.c_size_t)]
    lib.mxt_rec_free.argtypes = [c.POINTER(c.c_char), c.c_size_t]
    lib.mxt_rec_reader_close.argtypes = [c.c_void_p]
    # decode pipeline (src/pipe.cc; a library built before the stage existed
    # simply reports no pipe support instead of failing the whole load)
    try:
        lib.mxt_pipe_create.restype = c.c_void_p
        lib.mxt_pipe_create.argtypes = [c.POINTER(MXTPipeConfig)]
        lib.mxt_pipe_next.restype = c.c_int
        lib.mxt_pipe_next.argtypes = [
            c.c_void_p, c.POINTER(c.c_uint8), c.POINTER(c.c_float),
            c.POINTER(c.c_int)]
        lib.mxt_pipe_pop.restype = c.c_int
        lib.mxt_pipe_pop.argtypes = [
            c.c_void_p, c.POINTER(c.POINTER(c.c_uint8)),
            c.POINTER(c.POINTER(c.c_float)), c.POINTER(c.c_int)]
        lib.mxt_pipe_release.argtypes = [
            c.c_void_p, c.POINTER(c.c_uint8), c.POINTER(c.c_float)]
        lib.mxt_pipe_error.restype = c.c_char_p
        lib.mxt_pipe_error.argtypes = [c.c_void_p]
        lib.mxt_pipe_stats.argtypes = [c.c_void_p, c.POINTER(c.c_double),
                                       c.c_int]
        lib.mxt_pipe_close.argtypes = [c.c_void_p]
        lib.mxt_pipe_decode_available.restype = c.c_int
        lib.mxt_decode_jpeg.restype = c.c_int
        lib.mxt_decode_jpeg.argtypes = [
            c.c_char_p, c.c_size_t, c.POINTER(c.POINTER(c.c_uint8)),
            c.POINTER(c.c_int), c.POINTER(c.c_int)]
        lib.mxt_resize_bilinear.argtypes = [
            c.c_char_p, c.c_int, c.c_int, c.c_int, c.POINTER(c.c_uint8),
            c.c_int, c.c_int]
        lib._mxt_has_pipe = True
    except AttributeError:
        lib._mxt_has_pipe = False
    # ps
    lib.mxt_ps_server_create.restype = c.c_void_p
    lib.mxt_ps_server_create.argtypes = [c.c_int, c.c_int, c.c_int]
    lib.mxt_ps_server_set_updater.argtypes = [c.c_void_p, c.c_void_p]
    lib.mxt_ps_server_set_command_handler.argtypes = [c.c_void_p, c.c_void_p]
    lib.mxt_ps_server_wait.argtypes = [c.c_void_p]
    lib.mxt_ps_server_trace_stats.restype = c.c_int
    lib.mxt_ps_server_trace_stats.argtypes = [
        c.c_void_p, c.POINTER(c.c_double), c.c_int]
    lib.mxt_ps_server_destroy.argtypes = [c.c_void_p]
    lib.mxt_ps_client_create.restype = c.c_void_p
    lib.mxt_ps_client_create.argtypes = [c.c_char_p, c.c_int]
    # server-HA surface (a library built before it existed reports no HA
    # support instead of failing the whole load)
    try:
        lib.mxt_ps_client_create2.restype = c.c_void_p
        lib.mxt_ps_client_create2.argtypes = [c.c_char_p, c.c_int, c.c_int]
        lib.mxt_ps_client_is_dead.restype = c.c_int
        lib.mxt_ps_client_is_dead.argtypes = [c.c_void_p]
        lib._mxt_has_ps_ha = True
    except AttributeError:
        lib._mxt_has_ps_ha = False
    lib.mxt_ps_client_push.restype = c.c_int
    lib.mxt_ps_client_push.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.c_float), c.c_ulonglong]
    lib.mxt_ps_client_init.restype = c.c_int
    lib.mxt_ps_client_init.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.c_float), c.c_ulonglong]
    lib.mxt_ps_client_set_epoch.argtypes = [c.c_void_p, c.c_longlong]
    lib.mxt_ps_client_set_identity.argtypes = [c.c_void_p, c.c_int]
    lib.mxt_ps_client_set_step.argtypes = [c.c_void_p, c.c_longlong]
    lib.mxt_ps_client_get_epoch.restype = c.c_longlong
    lib.mxt_ps_client_get_epoch.argtypes = [c.c_void_p]
    lib.mxt_ps_client_pull.restype = c.c_longlong
    lib.mxt_ps_client_pull.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.c_float), c.c_ulonglong]
    lib.mxt_ps_client_pushpull.restype = c.c_longlong
    lib.mxt_ps_client_pushpull.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.c_float), c.c_ulonglong,
        c.POINTER(c.c_float), c.c_ulonglong]
    lib.mxt_ps_client_barrier.restype = c.c_int
    lib.mxt_ps_client_barrier.argtypes = [c.c_void_p]
    lib.mxt_ps_client_command.restype = c.c_int
    lib.mxt_ps_client_command.argtypes = [c.c_void_p, c.c_char_p]
    lib.mxt_ps_client_probe.restype = c.c_int
    lib.mxt_ps_client_probe.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.mxt_ps_probe.restype = c.c_int
    lib.mxt_ps_probe.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.mxt_ps_client_stop.restype = c.c_int
    lib.mxt_ps_client_stop.argtypes = [c.c_void_p]
    lib.mxt_ps_client_destroy.argtypes = [c.c_void_p]
    return lib


def get_lib():
    """Return the loaded native library, building it if needed, or None."""
    global _lib, _tried, _status
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        _status = "absent"
        with _other_processes_wait():
            built = not os.path.exists(_LIB_PATH)
            if built:
                from .base import env_flag

                if env_flag("MXNET_TPU_NO_NATIVE") or not _build():
                    return None
            try:
                _lib = _declare(ctypes.CDLL(_LIB_PATH))
            except OSError:
                return None
        _status = "built" if built else "loaded"
        return _lib


class MXTPipeConfig(ctypes.Structure):
    """Mirror of src/include/pipe_api.h MXTPipeConfig (the native
    decode->augment->batch stage's construction parameters)."""

    _fields_ = [
        ("path", ctypes.c_char_p),
        ("part_index", ctypes.c_int),
        ("num_parts", ctypes.c_int),
        ("num_threads", ctypes.c_int),
        ("batch_size", ctypes.c_int),
        ("out_h", ctypes.c_int),
        ("out_w", ctypes.c_int),
        ("out_c", ctypes.c_int),
        ("label_width", ctypes.c_int),
        ("seed", ctypes.c_longlong),
        ("epoch", ctypes.c_longlong),
        ("resize", ctypes.c_int),
        ("crop", ctypes.c_int),
        ("mirror_prob", ctypes.c_double),
        ("max_bad", ctypes.c_longlong),
        ("prefetch", ctypes.c_int),
    ]


# C callback signatures
ENGINE_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
UPDATER_FN = ctypes.CFUNCTYPE(
    None, ctypes.c_int, ctypes.POINTER(ctypes.c_float),
    ctypes.POINTER(ctypes.c_float), ctypes.c_uint64)
COMMAND_FN = ctypes.CFUNCTYPE(None, ctypes.POINTER(ctypes.c_char), ctypes.c_uint64)
