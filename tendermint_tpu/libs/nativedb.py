"""NativeDB — ctypes binding to the C++ log-structured KV store
(native/nativedb.cpp), the native-equivalent of the reference's
cgo→C++ LevelDB backend (libs/db/c_level_db.go, build tag `gcc`;
SURVEY §2.6 item 1).

Selected with db_backend = "native". Builds the shared library with
g++ on first use when it is absent or older than nativedb.cpp (the .so
is git-ignored: a checkout never ships one).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Iterator, Optional, Tuple

from .db import DB, Batch

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libnativedb.so")
_build_lock = threading.Lock()
_lib = None


def _load_lib() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        src = os.path.join(_NATIVE_DIR, "nativedb.cpp")
        if (not os.path.exists(_LIB_PATH)
                or os.path.getmtime(_LIB_PATH) < os.path.getmtime(src)):
            subprocess.run(
                ["g++", "-O2", "-std=c++17", "-fPIC", "-Wall", "-shared",
                 "-o", _LIB_PATH, src],
                check=True, capture_output=True,
            )
        lib = ctypes.CDLL(_LIB_PATH)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.ndb_open.restype = ctypes.c_void_p
        lib.ndb_open.argtypes = [ctypes.c_char_p]
        lib.ndb_close.argtypes = [ctypes.c_void_p]
        lib.ndb_put.restype = ctypes.c_int
        lib.ndb_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint32, ctypes.c_char_p,
                                ctypes.c_uint32]
        lib.ndb_delete.restype = ctypes.c_int
        lib.ndb_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_uint32]
        lib.ndb_get.restype = ctypes.c_int
        lib.ndb_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint32, ctypes.POINTER(u8p),
                                ctypes.POINTER(ctypes.c_uint32)]
        lib.ndb_free.argtypes = [u8p]
        lib.ndb_sync.restype = ctypes.c_int
        lib.ndb_sync.argtypes = [ctypes.c_void_p]
        lib.ndb_compact.restype = ctypes.c_int
        lib.ndb_compact.argtypes = [ctypes.c_void_p]
        lib.ndb_count.restype = ctypes.c_uint64
        lib.ndb_count.argtypes = [ctypes.c_void_p]
        lib.ndb_iter_new.restype = ctypes.c_void_p
        lib.ndb_iter_new.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_uint32, ctypes.c_char_p,
                                     ctypes.c_uint32, ctypes.c_int]
        lib.ndb_iter_next.restype = ctypes.c_int
        lib.ndb_iter_next.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(u8p),
                                      ctypes.POINTER(ctypes.c_uint32),
                                      ctypes.POINTER(u8p),
                                      ctypes.POINTER(ctypes.c_uint32)]
        lib.ndb_iter_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


def _take_bytes(lib, buf, ln) -> bytes:
    try:
        return ctypes.string_at(buf, ln.value)
    finally:
        lib.ndb_free(buf)


class NativeDB(DB):
    """DB interface over the C++ store."""

    def __init__(self, path: str):
        self._lib = _load_lib()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._h = self._lib.ndb_open(path.encode())
        if not self._h:
            raise OSError(f"nativedb: cannot open {path}")
        self._closed = False

    def get(self, key: bytes) -> Optional[bytes]:
        u8p = ctypes.POINTER(ctypes.c_uint8)
        val = u8p()
        vlen = ctypes.c_uint32()
        rc = self._lib.ndb_get(self._h, key, len(key),
                               ctypes.byref(val), ctypes.byref(vlen))
        if rc == 1:
            return None
        if rc != 0:
            raise OSError("nativedb get failed")
        return _take_bytes(self._lib, val, vlen)

    def set(self, key: bytes, value: bytes) -> None:
        if self._lib.ndb_put(self._h, key, len(key), value,
                             len(value)) != 0:
            raise OSError("nativedb put failed")

    def set_sync(self, key: bytes, value: bytes) -> None:
        self.set(key, value)
        self._lib.ndb_sync(self._h)

    def delete(self, key: bytes) -> None:
        if self._lib.ndb_delete(self._h, key, len(key)) != 0:
            raise OSError("nativedb delete failed")

    def _iter(self, start: Optional[bytes], end: Optional[bytes],
              reverse: bool) -> Iterator[Tuple[bytes, bytes]]:
        it = self._lib.ndb_iter_new(self._h, start or b"",
                                    len(start or b""), end or b"",
                                    len(end or b""), int(reverse))
        u8p = ctypes.POINTER(ctypes.c_uint8)
        try:
            while True:
                k, v = u8p(), u8p()
                klen, vlen = ctypes.c_uint32(), ctypes.c_uint32()
                rc = self._lib.ndb_iter_next(
                    it, ctypes.byref(k), ctypes.byref(klen),
                    ctypes.byref(v), ctypes.byref(vlen))
                if rc != 0:
                    return
                yield (_take_bytes(self._lib, k, klen),
                       _take_bytes(self._lib, v, vlen))
        finally:
            self._lib.ndb_iter_free(it)

    def iterator(self, start: Optional[bytes] = None,
                 end: Optional[bytes] = None):
        return self._iter(start, end, reverse=False)

    def reverse_iterator(self, start: Optional[bytes] = None,
                         end: Optional[bytes] = None):
        return self._iter(start, end, reverse=True)

    def compact(self) -> None:
        if self._lib.ndb_compact(self._h) != 0:
            raise OSError("nativedb compact failed")

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._lib.ndb_close(self._h)

    def stats(self) -> dict:
        return {"keys": int(self._lib.ndb_count(self._h)),
                "backend": "native"}
