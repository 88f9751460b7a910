"""ctypes binding for the native page store (``native/pagestore.cpp``).

The reference's backend pins pages by shared-memory offset over a Unix
socket (``src/storage/headers/DataProxy.h``); here the "protocol" is a
raw pointer into the C++ arena, wrapped as a NumPy view while pinned.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

import numpy as np

_POLICIES = {"lru": 0, "mru": 1, "random": 2}

_lib = None


def _load():
    """The built library; a failed build raises ``NativeBuildError``
    with the compiler's message (no silent Python stand-in)."""
    global _lib
    if _lib is not None:
        return _lib
    from netsdb_tpu.native.build import build_library

    lib = ctypes.CDLL(build_library())
    lib.ps_create.restype = ctypes.c_void_p
    lib.ps_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64,
                              ctypes.c_char_p, ctypes.c_int]
    lib.ps_destroy.argtypes = [ctypes.c_void_p]
    lib.ps_create_set.restype = ctypes.c_int
    lib.ps_create_set.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                  ctypes.c_int32]
    lib.ps_alloc_page.restype = ctypes.c_int64
    lib.ps_alloc_page.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                  ctypes.c_uint64]
    lib.ps_pin.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.ps_pin.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                           ctypes.POINTER(ctypes.c_uint64)]
    lib.ps_unpin.restype = ctypes.c_int
    lib.ps_unpin.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int]
    lib.ps_free_page.restype = ctypes.c_int
    lib.ps_free_page.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.ps_flush_set.restype = ctypes.c_int
    lib.ps_flush_set.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.ps_set_page_count.restype = ctypes.c_int64
    lib.ps_set_page_count.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.ps_set_page_id.restype = ctypes.c_int64
    lib.ps_set_page_id.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                   ctypes.c_uint64]
    lib.ps_page_size.restype = ctypes.c_int64
    lib.ps_page_size.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.ps_stats.argtypes = [ctypes.c_void_p,
                             ctypes.POINTER(ctypes.c_uint64)]
    _lib = lib
    return lib


def native_available() -> bool:
    """Explicit probe (tests skip on it): False only when the
    toolchain cannot build the library."""
    from netsdb_tpu.native.build import NativeBuildError

    try:
        _load()
    except NativeBuildError:
        return False
    return True


class NativePageStore:
    """Python handle on the C++ page store."""

    def __init__(self, pool_bytes: int, spill_dir: str,
                 evict_watermark: Optional[int] = None,
                 background_flush: bool = False):
        lib = _load()
        os.makedirs(spill_dir, exist_ok=True)
        watermark = evict_watermark or int(pool_bytes * 0.8)
        self._lib = lib
        self._h = lib.ps_create(pool_bytes, watermark,
                                spill_dir.encode(), int(background_flush))
        if not self._h:
            raise RuntimeError("failed to create native page store pool")

    def close(self):
        if getattr(self, "_h", None):
            self._lib.ps_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # --- sets / pages -------------------------------------------------
    def create_set(self, set_id: int, policy: str = "lru") -> None:
        rc = self._lib.ps_create_set(self._h, set_id, _POLICIES[policy])
        if rc != 0:
            raise RuntimeError(f"create_set failed rc={rc}")

    def write_page(self, set_id: int, payload: bytes | np.ndarray) -> int:
        """Allocate a page, copy payload in, unpin dirty; returns page id."""
        buf = np.frombuffer(payload if isinstance(payload, bytes)
                            else np.ascontiguousarray(payload).tobytes(),
                            dtype=np.uint8)
        pid = self._lib.ps_alloc_page(self._h, set_id, buf.nbytes)
        if pid < 0:
            raise MemoryError(f"alloc_page failed rc={pid} "
                              f"(pool exhausted or unknown set)")
        size = ctypes.c_uint64()
        ptr = self._lib.ps_pin(self._h, pid, ctypes.byref(size))
        try:
            view = np.ctypeslib.as_array(ptr, shape=(buf.nbytes,))
            view[:] = buf
        finally:
            self._lib.ps_unpin(self._h, pid, 1)  # the write pin
        self._lib.ps_unpin(self._h, pid, 1)      # the alloc pin
        return int(pid)

    def read_page(self, page_id: int) -> bytes:
        """Pin (reloading from spill if evicted), copy out, unpin."""
        size = ctypes.c_uint64()
        ptr = self._lib.ps_pin(self._h, page_id, ctypes.byref(size))
        if not ptr:
            raise KeyError(f"unknown or unloadable page {page_id}")
        try:
            return bytes(np.ctypeslib.as_array(ptr, shape=(size.value,)))
        finally:
            self._lib.ps_unpin(self._h, page_id, 0)

    def overwrite_page(self, page_id: int,
                       payload: bytes | np.ndarray) -> None:
        """Replace one page's bytes IN PLACE (same size): pin, copy,
        unpin dirty — the update-a-column-in-its-page path."""
        buf = np.frombuffer(payload if isinstance(payload, bytes)
                            else np.ascontiguousarray(payload).tobytes(),
                            dtype=np.uint8)
        size = ctypes.c_uint64()
        ptr = self._lib.ps_pin(self._h, page_id, ctypes.byref(size))
        if not ptr:
            raise KeyError(f"unknown or unloadable page {page_id}")
        try:
            if size.value != buf.nbytes:
                raise ValueError(
                    f"overwrite_page: size change {size.value} -> "
                    f"{buf.nbytes} not allowed")
            view = np.ctypeslib.as_array(ptr, shape=(buf.nbytes,))
            view[:] = buf
        finally:
            self._lib.ps_unpin(self._h, page_id, 1)

    def free_page(self, page_id: int) -> None:
        rc = self._lib.ps_free_page(self._h, page_id)
        if rc != 0:
            raise RuntimeError(f"free_page failed rc={rc}")

    def flush_set(self, set_id: int) -> None:
        rc = self._lib.ps_flush_set(self._h, set_id)
        if rc != 0:
            raise RuntimeError(f"flush_set failed rc={rc}")

    def set_pages(self, set_id: int) -> list:
        n = self._lib.ps_set_page_count(self._h, set_id)
        if n < 0:
            raise KeyError(f"unknown set {set_id}")
        return [int(self._lib.ps_set_page_id(self._h, set_id, i))
                for i in range(n)]

    def page_size(self, page_id: int) -> int:
        """Payload bytes of one page, metadata-only (no pin/reload)."""
        n = self._lib.ps_page_size(self._h, page_id)
        if n < 0:
            raise KeyError(f"unknown page {page_id}")
        return int(n)

    def stats(self) -> dict:
        arr = (ctypes.c_uint64 * 7)()
        self._lib.ps_stats(self._h, arr)
        keys = ("hits", "misses", "evictions", "spills", "loads",
                "bytes_allocated", "bytes_in_use")
        return dict(zip(keys, [int(v) for v in arr]))
