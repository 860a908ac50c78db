"""Native host-runtime kernels (C, ctypes-loaded).

Reference parity: the reference's host runtime (DataLoader readers,
buffer bookkeeping) is native C++ (SURVEY.md §2.1/§2.2) [UNVERIFIED —
empty reference mount].  Here the device runtime is PJRT/XLA; the
host-side batch assembly is the piece that benefits from native code,
implemented in collate.c and compiled on first use with the system cc
(`cc -O3 -shared -fPIC`) into the git-ignored ``_native/build/`` of the
checkout, under a name made from the source's content — so a library
is only ever loaded for the source it was built from.  Everything
degrades to numpy when no compiler is available — `available()` tells
you which path is live.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

__all__ = ["available", "fast_stack", "gather_rows",
           "tcp_store_available", "start_tcp_store_server",
           "stop_tcp_store_server"]

_lib = None
_tried = False
_lock = threading.Lock()
_store_lib = None
_store_tried = False



def _compile_native(src_name, so_name, compilers, flags):
    """Shared compile-once-then-load step for every native component
    (collate, tcp_store, shm_ring).  The library's name carries a hash
    of the source and the flags: a copy of the tree whose files all
    have fresh mtimes can never pick up a library built from other
    sources."""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, src_name)
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            f.read() + " ".join(flags).encode()).hexdigest()[:16]
    cache = os.path.join(here, "build")
    os.makedirs(cache, exist_ok=True)
    stem, ext = os.path.splitext(so_name)
    so = os.path.join(cache, f"{stem}-{digest}{ext}")
    if not os.path.exists(so):
        tmp = f"{so}.{os.getpid()}.tmp"  # per-pid: N ranks may race here
        for cc in compilers:
            try:
                subprocess.run(
                    [cc, *flags, "-o", tmp, src],
                    check=True, capture_output=True, timeout=180)
                os.replace(tmp, so)
                break
            except (OSError, subprocess.SubprocessError):
                continue
        else:
            return None
    return ctypes.CDLL(so)


def _build_and_load():
    lib = _compile_native("collate.c", "libptnative.so",
                          ("cc", "gcc", "clang"),
                          ("-O3", "-shared", "-fPIC"))
    if lib is None:
        return None
    lib.pt_stack_copy.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_char_p]
    lib.pt_gather_rows.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_char_p]
    return lib


def _get():
    global _lib, _tried
    if not _tried:
        with _lock:
            if not _tried:
                try:
                    _lib = _build_and_load()
                except Exception:
                    _lib = None
                _tried = True
    return _lib


def available() -> bool:
    return _get() is not None


def fast_stack(arrays):
    """np.stack for a list of same-shape contiguous arrays, with the
    copy loop in C (GIL released — worker threads overlap)."""
    lib = _get()
    first = np.asarray(arrays[0])
    if (lib is None or first.dtype == object
            or any(not isinstance(a, np.ndarray)
                   or a.shape != first.shape or a.dtype != first.dtype
                   for a in arrays)):
        return np.stack([np.asarray(a) for a in arrays])
    arrs = [np.ascontiguousarray(a) for a in arrays]
    n = len(arrs)
    nbytes = first.nbytes
    out = np.empty((n,) + first.shape, first.dtype)
    ptrs = (ctypes.c_char_p * n)(*[
        ctypes.cast(a.ctypes.data, ctypes.c_char_p) for a in arrs])
    lib.pt_stack_copy(ptrs, n, nbytes,
                      out.ctypes.data_as(ctypes.c_char_p))
    return out


def _build_store():
    """Build + load the C++ TCPStore server (tcp_store.cc)."""
    lib = _compile_native("tcp_store.cc", "libpttcpstore.so",
                          ("c++", "g++", "clang++"),
                          ("-O2", "-std=c++17", "-shared", "-fPIC",
                           "-pthread"))
    if lib is None:
        return None
    lib.pt_store_server_start.restype = ctypes.c_void_p
    lib.pt_store_server_start.argtypes = [
        ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
    lib.pt_store_server_stop.argtypes = [ctypes.c_void_p]
    return lib


def _get_store_lib():
    global _store_lib, _store_tried
    if not _store_tried:
        with _lock:
            if not _store_tried:
                try:
                    _store_lib = _build_store()
                except Exception:
                    _store_lib = None
                _store_tried = True
    return _store_lib


def tcp_store_available() -> bool:
    return _get_store_lib() is not None


def start_tcp_store_server(port=0):
    """Start the native TCPStore server; returns (handle, port)."""
    lib = _get_store_lib()
    if lib is None:
        raise RuntimeError("native TCPStore unavailable (no C++ "
                           "compiler); use the python fallback store")
    out_port = ctypes.c_int(0)
    h = lib.pt_store_server_start(int(port), ctypes.byref(out_port))
    if not h:
        raise RuntimeError(f"TCPStore: could not bind port {port}")
    return h, int(out_port.value)


def stop_tcp_store_server(handle):
    lib = _get_store_lib()
    if lib is not None and handle:
        lib.pt_store_server_stop(ctypes.c_void_p(handle))


def gather_rows(src, indices):
    """out[i] = src[indices[i]] over dim 0 (C memcpy per row)."""
    lib = _get()
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(np.asarray(indices, np.int64))
    if (lib is None or idx.size == 0 or idx.min() < 0
            or idx.max() >= src.shape[0]):
        # numpy path also owns negative/out-of-range semantics — the C
        # memcpy must never see an unchecked index
        return src[idx]
    row = int(np.prod(src.shape[1:])) * src.dtype.itemsize
    out = np.empty((len(idx),) + src.shape[1:], src.dtype)
    lib.pt_gather_rows(
        src.ctypes.data_as(ctypes.c_char_p),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(idx), row, out.ctypes.data_as(ctypes.c_char_p))
    return out


# ---------------------------------------------------------------------
# Shared-memory batch ring (shm_ring.c): the reference's C++ shared-mem
# DataLoader tensor path.  One SPSC ring per worker; numpy batch
# payloads cross process boundaries through shm instead of pickle pipes.
# ---------------------------------------------------------------------
_ring_lib = None
_ring_tried = False


def _build_ring_lib():
    lib = _compile_native("shm_ring.c", "libptshmring.so",
                          ("cc", "gcc", "clang"),
                          ("-O2", "-shared", "-fPIC", "-pthread"))
    if lib is None:
        return None
    lib.ptr_ring_create.restype = ctypes.c_void_p
    lib.ptr_ring_create.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                    ctypes.c_int64]
    lib.ptr_ring_attach.restype = ctypes.c_void_p
    lib.ptr_ring_attach.argtypes = [ctypes.c_char_p]
    lib.ptr_ring_slot_bytes.restype = ctypes.c_int64
    lib.ptr_ring_slot_bytes.argtypes = [ctypes.c_void_p]
    lib.ptr_ring_acquire_write.restype = ctypes.c_int64
    lib.ptr_ring_acquire_write.argtypes = [ctypes.c_void_p,
                                           ctypes.c_double]
    lib.ptr_ring_commit_write.argtypes = [ctypes.c_void_p,
                                          ctypes.c_int64]
    lib.ptr_ring_acquire_read.restype = ctypes.c_int64
    lib.ptr_ring_acquire_read.argtypes = [ctypes.c_void_p,
                                          ctypes.c_double]
    lib.ptr_ring_read_size.restype = ctypes.c_int64
    lib.ptr_ring_read_size.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.ptr_ring_release_read.argtypes = [ctypes.c_void_p]
    lib.ptr_ring_slot_ptr.restype = ctypes.c_void_p
    lib.ptr_ring_slot_ptr.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.ptr_ring_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
    return lib


def _get_ring_lib():
    global _ring_lib, _ring_tried
    if not _ring_tried:
        with _lock:
            if not _ring_tried:
                try:
                    _ring_lib = _build_ring_lib()
                except Exception:
                    _ring_lib = None
                _ring_tried = True
    return _ring_lib


def shm_ring_available() -> bool:
    return _get_ring_lib() is not None


class ShmRing:
    """ctypes face of shm_ring.c; create() in the parent, attach() in
    the worker.  Payloads are length-prefixed binary blobs."""

    def __init__(self, handle, lib, name, owner):
        self._h = handle
        self._lib = lib
        self.name = name
        self._owner = owner
        self.slot_bytes = lib.ptr_ring_slot_bytes(handle)

    @classmethod
    def create(cls, name, slots, slot_bytes):
        lib = _get_ring_lib()
        if lib is None:
            return None
        h = lib.ptr_ring_create(name.encode(), int(slots),
                                int(slot_bytes))
        return cls(h, lib, name, True) if h else None

    @classmethod
    def attach(cls, name):
        lib = _get_ring_lib()
        if lib is None:
            return None
        h = lib.ptr_ring_attach(name.encode())
        return cls(h, lib, name, False) if h else None

    def write(self, payload: bytes, timeout=120.0) -> bool:
        if len(payload) > self.slot_bytes:
            return False  # oversized: caller uses the pipe fallback
        slot = self._lib.ptr_ring_acquire_write(self._h, float(timeout))
        if slot < 0:
            raise TimeoutError("shm ring full")
        dst = (ctypes.c_char * self.slot_bytes).from_address(
            self._lib.ptr_ring_slot_ptr(self._h, slot))
        dst[:len(payload)] = payload
        self._lib.ptr_ring_commit_write(self._h, len(payload))
        return True

    def read(self, timeout=120.0) -> bytes:
        slot = self._lib.ptr_ring_acquire_read(self._h, float(timeout))
        if slot < 0:
            raise TimeoutError("shm ring empty")
        n = self._lib.ptr_ring_read_size(self._h, slot)
        src = (ctypes.c_char * n).from_address(
            self._lib.ptr_ring_slot_ptr(self._h, slot))
        data = bytes(src)
        self._lib.ptr_ring_release_read(self._h)
        return data

    def close(self, unlink=None):
        if self._h:
            self._lib.ptr_ring_close(
                self._h, 1 if (self._owner if unlink is None
                               else unlink) else 0)
            self._h = None
