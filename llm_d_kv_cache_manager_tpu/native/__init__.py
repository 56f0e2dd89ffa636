"""Native runtime loader.

``get_library()`` returns the ctypes handle to the native library, building
it on first use when a compiler is available; returns None otherwise so
every caller can fall back to pure Python.  Set ``KVTPU_DISABLE_NATIVE=1``
to force the fallback.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.kvtpu_fnv1a64.restype = ctypes.c_uint64
    lib.kvtpu_fnv1a64.argtypes = [ctypes.c_char_p, ctypes.c_size_t]

    lib.kvtpu_hash_chain.restype = ctypes.c_size_t
    lib.kvtpu_hash_chain.argtypes = [
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_size_t,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_uint64),
    ]

    lib.kvtpu_engine_create.restype = ctypes.c_void_p
    lib.kvtpu_engine_create.argtypes = [ctypes.c_size_t, ctypes.c_int]
    lib.kvtpu_engine_destroy.argtypes = [ctypes.c_void_p]

    lib.kvtpu_engine_store.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_size_t,
        ctypes.c_int,
    ]
    lib.kvtpu_engine_load.argtypes = lib.kvtpu_engine_store.argtypes[:-1]
    lib.kvtpu_engine_get_finished.restype = ctypes.c_size_t
    lib.kvtpu_engine_get_finished.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_size_t,
    ]
    lib.kvtpu_engine_wait.restype = ctypes.c_int32
    lib.kvtpu_engine_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.kvtpu_file_exists.restype = ctypes.c_int
    lib.kvtpu_file_exists.argtypes = [ctypes.c_char_p]
    return lib


def get_library() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if os.environ.get("KVTPU_DISABLE_NATIVE"):
        return None
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        try:
            from llm_d_kv_cache_manager_tpu.native.build import build

            path = build()
            if path is None:
                return None
            _lib = _configure(ctypes.CDLL(path))
        except (OSError, RuntimeError) as exc:
            from llm_d_kv_cache_manager_tpu.utils.logging import get_logger

            get_logger("native").warning(
                "native library unavailable (%s); using the slower "
                "pure-Python fallback",
                exc,
            )
            _lib = None
        return _lib
