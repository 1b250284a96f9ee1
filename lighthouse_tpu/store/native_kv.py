"""Native embedded KV backend (the LevelDB seat, reference
beacon_node/store/src/leveldb_store.rs): a C++ log-structured store
(native/kvstore.cc) behind the same KeyValueStore interface as
MemoryStore/FileStore. ctypes binding (no pybind11 in the image); the
shared library is built on demand with g++.

Crash semantics match the reference's expectations of LevelDB:
`do_atomically` frames the ops between batch begin/commit records, and
replay drops uncommitted batches and torn tails."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

from .kv import KeyValueStore

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "kvstore.cc",
)
_BUILD_LOCK = threading.Lock()
_LIB = None


def _lib_path() -> str:
    """The shared library built from THIS kvstore.cc: keyed on a hash of
    the source, so a checkout never loads a library built from other
    source, whatever the files' mtimes say."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(_SRC), f"libkvstore-{digest}.so")


def _build_lib() -> str:
    lib = _lib_path()
    with _BUILD_LOCK:
        if os.path.exists(lib):
            return lib
        # built under a process-private name and renamed into place, so
        # a concurrent process never loads a half-written library
        tmp = f"{lib}.{os.getpid()}.tmp"
        # lint: allow[blocking-under-lock] -- the build lock exists to
        # serialize exactly this one-time g++ compile; every later call
        # takes the exists() fast path above
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, lib)
        return lib


# key pointer MUST be c_void_p: c_char_p would NUL-truncate before
# string_at reads the full length (keys are 32-byte roots full of NULs)
_KEY_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p)


def _load():
    global _LIB
    if _LIB is not None:
        return _LIB
    lib = ctypes.CDLL(_build_lib())
    lib.kv_open.restype = ctypes.c_void_p
    lib.kv_open.argtypes = [ctypes.c_char_p]
    lib.kv_close.argtypes = [ctypes.c_void_p]
    sz = ctypes.c_size_t
    buf = ctypes.c_char_p
    lib.kv_put.argtypes = [ctypes.c_void_p, buf, sz, buf, sz, buf, sz]
    lib.kv_delete.argtypes = [ctypes.c_void_p, buf, sz, buf, sz]
    lib.kv_get.restype = ctypes.c_long
    lib.kv_get.argtypes = [
        ctypes.c_void_p, buf, sz, buf, sz, ctypes.c_char_p, sz,
    ]
    lib.kv_batch_begin.argtypes = [ctypes.c_void_p]
    lib.kv_batch_put.argtypes = [ctypes.c_void_p, buf, sz, buf, sz, buf, sz]
    lib.kv_batch_delete.argtypes = [ctypes.c_void_p, buf, sz, buf, sz]
    lib.kv_batch_commit.argtypes = [ctypes.c_void_p]
    lib.kv_keys.argtypes = [ctypes.c_void_p, buf, sz, _KEY_CB, ctypes.c_void_p]
    lib.kv_compact.restype = ctypes.c_int
    lib.kv_compact.argtypes = [ctypes.c_void_p]
    lib.kv_len.restype = ctypes.c_size_t
    lib.kv_len.argtypes = [ctypes.c_void_p]
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.kv_recovery_stats.argtypes = [ctypes.c_void_p, u64p, u64p, u64p]
    _LIB = lib
    return lib


class NativeStore(KeyValueStore):
    """C++ log-structured store; one file per database."""

    def __init__(self, path: str):
        lib = _load()
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._lib = lib
        self._db = lib.kv_open(path.encode())
        if not self._db:
            raise OSError(f"kv_open failed for {path}")
        self._lock = threading.Lock()
        # surface the C++ log's open-time recovery outcomes into the
        # shared metrics registry (the python-WAL counters' native twin)
        self.recovery_stats = self._read_recovery_stats()
        from ..utils import metrics as M

        M.STORE_NATIVE_REPLAYED.inc(self.recovery_stats["replayed_batches"])
        M.STORE_NATIVE_ROLLED_BACK.inc(
            self.recovery_stats["rolled_back_batches"]
        )
        M.STORE_NATIVE_TRUNCATED.inc(self.recovery_stats["truncated_bytes"])

    def _read_recovery_stats(self) -> dict:
        replayed = ctypes.c_uint64()
        rolled_back = ctypes.c_uint64()
        truncated = ctypes.c_uint64()
        self._lib.kv_recovery_stats(
            self._db,
            ctypes.byref(replayed),
            ctypes.byref(rolled_back),
            ctypes.byref(truncated),
        )
        return {
            "replayed_batches": int(replayed.value),
            "rolled_back_batches": int(rolled_back.value),
            "truncated_bytes": int(truncated.value),
        }

    def close(self) -> None:
        with self._lock:
            if self._db:
                self._lib.kv_close(self._db)
                self._db = None

    def _handle(self):
        """The live C handle; raises (instead of letting the C side
        dereference NULL -> SIGSEGV) once the store is closed."""
        if self._db is None:
            raise OSError("store is closed")
        return self._db

    def get(self, column: bytes, key: bytes) -> bytes | None:
        with self._lock:
            n = self._lib.kv_get(
                self._handle(), column, len(column), key, len(key), None, 0
            )
            if n < 0:
                return None
            out = ctypes.create_string_buffer(n)
            self._lib.kv_get(
                self._handle(), column, len(column), key, len(key), out, n
            )
            return out.raw

    def put(self, column: bytes, key: bytes, value: bytes) -> None:
        value = bytes(value)
        with self._lock:
            self._lib.kv_put(
                self._handle(), column, len(column), key, len(key), value, len(value)
            )

    def delete(self, column: bytes, key: bytes) -> None:
        with self._lock:
            self._lib.kv_delete(self._handle(), column, len(column), key, len(key))

    def keys(self, column: bytes):
        out: list[bytes] = []

        @_KEY_CB
        def cb(ptr, n, _ctx):
            out.append(ctypes.string_at(ptr, n))

        with self._lock:
            self._lib.kv_keys(self._handle(), column, len(column), cb, None)
        return out

    def do_atomically(self, ops) -> None:
        """All-or-nothing batch: one commit record, one disk barrier.

        Ops are validated/converted BEFORE the BATCH_BEGIN record is
        written: a mid-batch exception would otherwise leave an
        unterminated batch marker that replay treats as the start of an
        uncommitted region, truncating every later write on reopen."""
        converted = []
        for op, column, key, value in ops:
            if op == "put":
                converted.append((op, bytes(column), bytes(key), bytes(value)))
            elif op == "delete":
                converted.append((op, bytes(column), bytes(key), None))
            else:
                raise ValueError(f"unknown batch op {op!r}")
        with self._lock:
            self._lib.kv_batch_begin(self._handle())
            for op, column, key, value in converted:
                if op == "put":
                    self._lib.kv_batch_put(
                        self._handle(), column, len(column), key, len(key),
                        value, len(value),
                    )
                else:
                    self._lib.kv_batch_delete(
                        self._handle(), column, len(column), key, len(key)
                    )
            self._lib.kv_batch_commit(self._handle())

    def compact(self) -> None:
        with self._lock:
            rc = self._lib.kv_compact(self._handle())
            if rc == -2:
                # the log handle could not be reopened: nothing further
                # can be persisted, fail loudly rather than corrupt
                self._lib.kv_close(self._handle())
                self._db = None
                raise OSError("kv_compact lost the log handle; store closed")
            if rc != 0:
                raise OSError("kv_compact failed")

    def __len__(self) -> int:
        with self._lock:
            return self._lib.kv_len(self._handle())
