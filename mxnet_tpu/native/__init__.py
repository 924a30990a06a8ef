"""Native runtime bindings: C++ engine, RecordIO, storage pool via ctypes.

The reference's runtime core is C++ behind a ctypes ABI
(``src/c_api/c_api.cc`` → ``python/mxnet/base.py``).  Same structure here:
``src/*.cc`` compiles into ``libmxtpu.<hash>.so`` (lazily, with g++ — no
external deps) and this module is the typed ctypes facade.  The file name
carries a hash of the sources, so a binary lying on disk that was built
from other sources (the ``.so`` is ignored by git, but a copy of the disk
takes it along) is never loaded.  If no toolchain is available the callers
fall back to pure-Python paths; :func:`status` says which happened.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src")

_lock = threading.Lock()
_lib = None
_tried = False
_status = "not loaded"


def _sources():
    return sorted(os.path.join(_SRC, f) for f in os.listdir(_SRC)
                  if f.endswith(".cc"))


def _lib_path():
    """``libmxtpu.<hash of src/*.cc>.so`` beside this file."""
    h = hashlib.sha256()
    for src in _sources():
        h.update(os.path.basename(src).encode())
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(_HERE, "libmxtpu.%s.so" % h.hexdigest()[:16])


def _build(path):
    # build to a temp name + atomic rename: concurrent first-use from
    # several processes must never CDLL a half-written .so
    tmp = "%s.%d.tmp" % (path, os.getpid())
    cmd = ["g++", "-std=c++17", "-O2", "-fPIC", "-shared", "-pthread",
           "-o", tmp] + _sources()
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, path)
    for stale in glob.glob(os.path.join(_HERE, "libmxtpu*.so")):
        if stale != path:
            os.unlink(stale)


def _declare(lib):
    i64, u64, vp = ctypes.c_int64, ctypes.c_uint64, ctypes.c_void_p
    lib.mxt_engine_create.restype = vp
    lib.mxt_engine_create.argtypes = [ctypes.c_int]
    lib.mxt_engine_destroy.argtypes = [vp]
    lib.mxt_engine_new_var.restype = i64
    lib.mxt_engine_new_var.argtypes = [vp]
    lib.mxt_engine_delete_var.argtypes = [vp, i64]
    lib.mxt_engine_push.argtypes = [vp, MXT_FN, vp,
                                    ctypes.POINTER(i64), ctypes.c_int,
                                    ctypes.POINTER(i64), ctypes.c_int,
                                    ctypes.c_int]
    lib.mxt_engine_wait_var.argtypes = [vp, i64]
    lib.mxt_engine_wait_all.argtypes = [vp]
    lib.mxt_engine_pending.restype = i64
    lib.mxt_engine_pending.argtypes = [vp]

    cpp = ctypes.POINTER(ctypes.c_char_p)
    lib.mxt_recio_reader_create.restype = vp
    lib.mxt_recio_reader_create.argtypes = [ctypes.c_char_p]
    lib.mxt_recio_reader_destroy.argtypes = [vp]
    lib.mxt_recio_read.restype = i64
    lib.mxt_recio_read.argtypes = [vp, cpp]
    lib.mxt_recio_reader_seek.argtypes = [vp, u64]
    lib.mxt_recio_reader_tell.restype = u64
    lib.mxt_recio_reader_tell.argtypes = [vp]
    lib.mxt_recio_writer_create.restype = vp
    lib.mxt_recio_writer_create.argtypes = [ctypes.c_char_p]
    lib.mxt_recio_writer_destroy.argtypes = [vp]
    lib.mxt_recio_write.restype = u64
    lib.mxt_recio_write.argtypes = [vp, ctypes.c_char_p, u64]
    lib.mxt_recio_writer_tell.restype = u64
    lib.mxt_recio_writer_tell.argtypes = [vp]
    lib.mxt_prefetch_create.restype = vp
    lib.mxt_prefetch_create.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.mxt_prefetch_destroy.argtypes = [vp]
    lib.mxt_prefetch_next.restype = i64
    lib.mxt_prefetch_next.argtypes = [vp, cpp]

    lib.mxt_storage_alloc.restype = vp
    lib.mxt_storage_alloc.argtypes = [u64]
    lib.mxt_storage_free.argtypes = [vp, u64]
    lib.mxt_storage_direct_free.argtypes = [vp, u64]
    lib.mxt_storage_release_all.argtypes = []
    lib.mxt_storage_used_bytes.restype = u64
    lib.mxt_storage_pooled_bytes.restype = u64
    return lib


MXT_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)


def lib():
    """The loaded native library, or None (no toolchain / build failure)."""
    global _lib, _tried, _status
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            path = _lib_path()
            built = not os.path.exists(path)
            if built:
                _build(path)
            _lib = _declare(ctypes.CDLL(path))
            _status = "built" if built else "loaded"
        except (OSError, subprocess.CalledProcessError) as e:
            _lib = None
            _status = "unavailable (%s: %s)" % (type(e).__name__, e)
        return _lib


def available():
    return lib() is not None


def status():
    """How the native runtime came up in this process: ``"built"`` (compiled
    just now from ``src/*.cc``), ``"loaded"`` (a binary with the sources'
    hash was on disk) or ``"unavailable (<why>)"`` (callers are on their
    pure-Python paths)."""
    lib()
    return _status


def storage_stats():
    """(used_bytes, pooled_bytes) of the native host storage pool
    (reference Storage::Get() pool counters; the RecordIO prefetcher's
    record buffers ride this pool)."""
    l = lib()
    if l is None:
        return (0, 0)
    return (int(l.mxt_storage_used_bytes()),
            int(l.mxt_storage_pooled_bytes()))


# ---------------------------------------------------------------------------
# Engine facade
# ---------------------------------------------------------------------------
class NativeEngine:
    """Host-task dependency engine (reference Engine::PushAsync semantics:
    ops with read/write var sets, serialized per var, parallel otherwise).

    >>> eng = NativeEngine(num_threads=4)
    >>> v = eng.new_var()
    >>> eng.push(lambda: do_io(), mutable_vars=[v])
    >>> eng.wait_for_var(v)
    """

    def __init__(self, num_threads=None):
        l = lib()
        if l is None:
            raise RuntimeError("native runtime unavailable")
        if num_threads is None:
            from ..base import get_env
            num_threads = int(get_env("MXNET_CPU_WORKER_NTHREADS"))
        self._lib = l
        self._h = l.mxt_engine_create(num_threads)
        self._cbs = {}
        self._next = [1]
        self._cb_lock = threading.Lock()

        def trampoline(token):
            with self._cb_lock:
                fn = self._cbs.pop(token, None)
            if fn is None:
                return
            try:
                fn()
            except Exception:  # never propagate into C
                import traceback
                traceback.print_exc()

        self._tramp = MXT_FN(lambda ctx: trampoline(ctx))

    def new_var(self):
        return self._lib.mxt_engine_new_var(self._h)

    def delete_var(self, var):
        self._lib.mxt_engine_delete_var(self._h, var)

    def push(self, fn, const_vars=(), mutable_vars=(), priority=0):
        with self._cb_lock:
            token = self._next[0]
            self._next[0] += 1
            self._cbs[token] = fn
        nc, nm = len(const_vars), len(mutable_vars)
        ca = (ctypes.c_int64 * max(nc, 1))(*const_vars)
        ma = (ctypes.c_int64 * max(nm, 1))(*mutable_vars)
        self._lib.mxt_engine_push(self._h, self._tramp, token, ca, nc,
                                  ma, nm, priority)

    def wait_for_var(self, var):
        self._lib.mxt_engine_wait_var(self._h, var)

    def wait_all(self):
        self._lib.mxt_engine_wait_all(self._h)

    @property
    def pending(self):
        return self._lib.mxt_engine_pending(self._h)

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.mxt_engine_destroy(self._h)
                self._h = None
        except Exception:
            pass


# ---------------------------------------------------------------------------
# RecordIO facades
# ---------------------------------------------------------------------------
class NativeRecordReader:
    def __init__(self, path):
        l = lib()
        if l is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = l
        self._h = l.mxt_recio_reader_create(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)

    def read(self):
        """Next record as bytes, or None at EOF."""
        data = ctypes.c_char_p()
        n = self._lib.mxt_recio_read(self._h, ctypes.byref(data))
        if n < 0:
            if n == -2:
                raise IOError("invalid recordio magic")
            return None
        return ctypes.string_at(data, n)

    def seek(self, pos):
        self._lib.mxt_recio_reader_seek(self._h, pos)

    def tell(self):
        return self._lib.mxt_recio_reader_tell(self._h)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxt_recio_reader_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeRecordWriter:
    def __init__(self, path):
        l = lib()
        if l is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = l
        self._h = l.mxt_recio_writer_create(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)

    def write(self, buf):
        """Append one record; returns its byte offset (for .idx files)."""
        return self._lib.mxt_recio_write(self._h, bytes(buf), len(buf))

    def tell(self):
        return self._lib.mxt_recio_writer_tell(self._h)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxt_recio_writer_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativePrefetcher:
    """Background-thread record prefetch (dmlc::ThreadedIter analog)."""

    def __init__(self, path, capacity=16):
        l = lib()
        if l is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = l
        self._h = l.mxt_prefetch_create(path.encode(), capacity)
        if not self._h:
            raise IOError("cannot open %s" % path)

    def __iter__(self):
        return self

    def __next__(self):
        data = ctypes.c_char_p()
        n = self._lib.mxt_prefetch_next(self._h, ctypes.byref(data))
        if n == -2:
            raise IOError("invalid recordio magic (corrupt record file)")
        if n < 0:
            raise StopIteration
        return ctypes.string_at(data, n)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxt_prefetch_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
