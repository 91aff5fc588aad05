"""ctypes bindings for the native IO runtime (native/tmr_io.cc).

A C++ thread pool streams tar shards (the reference's `hadoop fs -get` +
tarfile layer, mapper.py:71-75) with inline ustar parsing and a bounded
prefetch queue, so storage IO and tar decoding overlap device compute
outside the GIL. The Python side receives (shard_index, member_name, bytes)
and keeps image decoding in PIL (decode is a small fraction of the byte
shuffling; the payload copy out of C is one memcpy).

The library is built lazily with the in-image g++ (``ensure_built``); when
no compiler or prebuilt .so is available every consumer falls back to the
pure-Python tarfile path, so the framework never hard-depends on the native
layer.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Iterator, Optional, Sequence, Tuple

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_SO_PATH = os.path.join(_NATIVE_DIR, "libtmr_io.so")
_lib = None


class _Item(ctypes.Structure):
    _fields_ = [
        ("name", ctypes.c_char_p),
        ("data", ctypes.POINTER(ctypes.c_uint8)),
        ("size", ctypes.c_int64),
        ("shard", ctypes.c_int32),
    ]


def ensure_built(quiet: bool = True) -> Optional[str]:
    """Build libtmr_io.so when it is missing or older than tmr_io.cc;
    returns its path, or None when the build fails (no g++, or a source
    that no longer compiles — a stale library must not mask that)."""
    src = os.path.join(_NATIVE_DIR, "tmr_io.cc")
    if os.path.exists(_SO_PATH) and (
        os.path.getmtime(_SO_PATH) >= os.path.getmtime(src)
    ):
        return _SO_PATH
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            check=True,
            capture_output=quiet,
        )
    except (OSError, subprocess.CalledProcessError):
        return None
    return _SO_PATH


def _load():
    global _lib
    if _lib is not None:
        return _lib
    path = ensure_built()
    if path is None:
        raise OSError("native IO library unavailable (no g++/make)")
    lib = ctypes.CDLL(path)
    lib.tmr_io_open.restype = ctypes.c_void_p
    lib.tmr_io_open.argtypes = [
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.tmr_io_next.restype = ctypes.c_int
    lib.tmr_io_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(_Item)]
    lib.tmr_io_free_item.argtypes = [ctypes.POINTER(_Item)]
    lib.tmr_io_error.restype = ctypes.c_int
    lib.tmr_io_error.argtypes = [ctypes.c_void_p]
    lib.tmr_io_close.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def available() -> bool:
    try:
        _load()
        return True
    except OSError:
        return False


class NativeTarStream:
    """Iterate (shard_index, member_name, payload bytes) over tar shards,
    decoded and prefetched by the C++ thread pool.

    Unreadable shards are skipped and counted (``errors``) — the same
    skip-and-log tolerance as the Python path (mapper.py:79-81).
    """

    def __init__(self, paths: Sequence[str], threads: int = 4,
                 queue_cap: int = 64):
        lib = _load()
        self._lib = lib
        self._errors_at_close = 0
        self._handle = None
        self._paths = [os.fsencode(p) for p in paths]
        arr = (ctypes.c_char_p * len(self._paths))(*self._paths)
        self._handle = lib.tmr_io_open(arr, len(self._paths), threads,
                                       queue_cap)
        if not self._handle:
            raise OSError("tmr_io_open failed")

    def __iter__(self) -> Iterator[Tuple[int, str, bytes]]:
        item = _Item()
        while True:
            rc = self._lib.tmr_io_next(self._handle, ctypes.byref(item))
            if rc == 0:
                return
            try:
                name = item.name.decode("utf-8", "replace")
                data = ctypes.string_at(item.data, item.size)
            finally:
                self._lib.tmr_io_free_item(ctypes.byref(item))
            yield int(item.shard), name, data

    @property
    def errors(self) -> int:
        if self._handle is None:
            return self._errors_at_close
        return int(self._lib.tmr_io_error(self._handle))

    def close(self) -> None:
        if self._handle:
            self._errors_at_close = int(
                self._lib.tmr_io_error(self._handle)
            )
            self._lib.tmr_io_close(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
