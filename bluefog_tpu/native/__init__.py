"""Native (C++) runtime components, loaded via ctypes.

The reference's native layer bridges framework tensors to MPI/NCCL; on TPU
XLA supplies the data plane, so the native components here are the runtime
pieces AROUND the compute path (SURVEY.md §7.9):

* ``bf_native.cc`` — Chrome-tracing timeline writer (lock-free SPSC ring +
  writer thread, mirroring reference common/timeline.{h,cc});
* ``bf_data.cc`` — batch-gather data engine (worker pool filling a ring of
  pre-allocated host batch buffers; the input pipeline the reference gets
  from torch's C++ DataLoader).

The shared library is built lazily with g++ on first use and cached next to
the source; every consumer must degrade gracefully when ``available()`` is
False (no compiler, exotic platform).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_HERE, "bf_native.cc"),
         os.path.join(_HERE, "bf_data.cc")]
_LIB = os.path.join(_HERE, "libbf_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False
_rebuilt = False


def _build() -> bool:
    # per-process temp name: concurrent ranks (bfrun) may build at once and
    # must not clobber each other's output mid-write
    tmp = f"{_LIB}.tmp.{os.getpid()}"
    cmd = ["g++", "-std=c++17", "-O2", "-shared", "-fPIC", "-o", tmp,
           *_SRCS, "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120,
                       text=True)
        os.replace(tmp, _LIB)
        return True
    except subprocess.CalledProcessError as exc:
        _log_build_failure(exc.stderr)
        return False
    except (OSError, subprocess.SubprocessError) as exc:
        _log_build_failure(str(exc))
        return False
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _log_build_failure(detail: str):
    from bluefog_tpu.logging_util import get_logger

    get_logger().warning(
        "native library build failed; falling back to Python "
        "implementations. Compiler output:\n%s", detail)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed, _rebuilt
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        stale = (not os.path.exists(_LIB) or
                 os.path.getmtime(_LIB) < max(os.path.getmtime(s)
                                              for s in _SRCS))
        if stale:
            if not _build():
                _build_failed = True
                return None
            _rebuilt = True
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError:
            _build_failed = True
            return None
        lib.bf_timeline_open.restype = ctypes.c_void_p
        lib.bf_timeline_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.bf_timeline_record.restype = None
        lib.bf_timeline_record.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char]
        lib.bf_timeline_dropped.restype = ctypes.c_longlong
        lib.bf_timeline_dropped.argtypes = [ctypes.c_void_p]
        lib.bf_timeline_close.restype = None
        lib.bf_timeline_close.argtypes = [ctypes.c_void_p]
        lib.bfdata_create.restype = ctypes.c_void_p
        lib.bfdata_create.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int]
        lib.bfdata_start_epoch.restype = None
        lib.bfdata_start_epoch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
        lib.bfdata_num_batches.restype = ctypes.c_longlong
        lib.bfdata_num_batches.argtypes = [ctypes.c_void_p]
        lib.bfdata_next.restype = ctypes.c_longlong
        lib.bfdata_next.argtypes = [ctypes.c_void_p]
        lib.bfdata_release.restype = None
        lib.bfdata_release.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        lib.bfdata_slot_ptr.restype = ctypes.c_void_p
        lib.bfdata_slot_ptr.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_int]
        lib.bfdata_slot_count.restype = ctypes.c_longlong
        lib.bfdata_slot_count.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        lib.bfdata_destroy.restype = None
        lib.bfdata_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def status() -> str:
    """How this process got the library: ``"rebuilt"`` (compiled from
    the committed ``.cc`` sources just now), ``"loaded"`` (an existing
    build newer than the sources), or ``"unavailable"``.  The ``.so`` is
    not a committed file, so a fresh checkout always reads "rebuilt"."""
    if _load() is None:
        return "unavailable"
    return "rebuilt" if _rebuilt else "loaded"


class NativeTimelineWriter:
    """ctypes facade over the C++ TimelineWriter.  Single-producer: callers
    must serialize Record calls (the Python Timeline holds a lock)."""

    def __init__(self, path: str, rank: int):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._dropped_at_close = 0
        self._handle = lib.bf_timeline_open(path.encode(), rank)
        if not self._handle:
            raise OSError(f"cannot open timeline file {path}")

    def record(self, name: str, tid: str, phase: str):
        self._lib.bf_timeline_record(
            self._handle, name.encode(), tid.encode(), phase.encode())

    def dropped(self) -> int:
        if not self._handle:
            return self._dropped_at_close
        return int(self._lib.bf_timeline_dropped(self._handle))

    def close(self):
        if self._handle:
            self._dropped_at_close = int(
                self._lib.bf_timeline_dropped(self._handle))
            self._lib.bf_timeline_close(self._handle)
            self._handle = None


class NativeBatchPipeline:
    """ctypes facade over the C++ DataPipeline (bf_data.cc): multi-threaded
    gather of scattered records into a depth-deep ring of contiguous batch
    buffers, delivered strictly in order.

    ``fields`` are C-contiguous numpy arrays sharing a leading sample dim;
    the caller must keep them alive for the pipeline's lifetime (this class
    holds references).  Buffers returned by ``next()`` are views into ring
    slots — valid only until ``release(slot)``.
    """

    def __init__(self, fields, batch_size: int, depth: int = 3,
                 workers: int = 2):
        import numpy as np

        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._fields = [np.ascontiguousarray(f) for f in fields]
        n = self._fields[0].shape[0]
        for f in self._fields:
            if f.shape[0] != n:
                raise ValueError("all fields need the same sample count")
        self._batch = int(batch_size)
        self._item_shapes = [f.shape[1:] for f in self._fields]
        self._dtypes = [f.dtype for f in self._fields]
        item_bytes = [int(f.nbytes // max(n, 1)) for f in self._fields]
        ptrs = (ctypes.c_void_p * len(fields))(
            *[f.ctypes.data_as(ctypes.c_void_p).value for f in self._fields])
        bts = (ctypes.c_int64 * len(fields))(*item_bytes)
        self._handle = lib.bfdata_create(
            len(fields), ptrs, bts, n, self._batch, depth, workers)
        if not self._handle:
            raise RuntimeError("bfdata_create failed")

    def start_epoch(self, order) -> int:
        """Install this epoch's sample-index order; returns batch count."""
        import numpy as np

        order = np.ascontiguousarray(order, dtype=np.int64)
        self._lib.bfdata_start_epoch(
            self._handle, order.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(order))
        return int(self._lib.bfdata_num_batches(self._handle))

    def next(self):
        """Blocking: (slot, [field views]) or None at epoch end."""
        import numpy as np

        slot = int(self._lib.bfdata_next(self._handle))
        if slot < 0:
            return None
        count = int(self._lib.bfdata_slot_count(self._handle, slot))
        views = []
        for f, (shape, dtype) in enumerate(
                zip(self._item_shapes, self._dtypes)):
            ptr = self._lib.bfdata_slot_ptr(self._handle, slot, f)
            nbytes = count * int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
            raw = (ctypes.c_uint8 * nbytes).from_address(ptr)
            views.append(np.frombuffer(raw, dtype=dtype).reshape(
                (count,) + tuple(shape)))
        return slot, views

    def release(self, slot: int):
        self._lib.bfdata_release(self._handle, slot)

    def close(self):
        if getattr(self, "_handle", None):
            self._lib.bfdata_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
