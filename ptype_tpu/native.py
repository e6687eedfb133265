"""ctypes loader for the native wire library (native/ptype_wire.cpp).

The reference's whole runtime was compiled (Go); here the Python host
runtime gets a native transport tier: writev frame sends (no
concatenation copy) and GIL-free exact reads. Loading is best-effort —
``available()`` is False and callers fall back to pure Python when the
.so is absent and cannot be built (no compiler, read-only tree).

Build explicitly with ``make native``; ``load()`` also builds on
demand, the first time it runs from a writable checkout, whenever the
.so is missing or older than its source — the .so is git-ignored, so a
binary left on disk by an earlier checkout must never outlive a change
to ``native/ptype_wire.cpp``.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

from ptype_tpu import logs

log = logs.get_logger("native")

_SRC = os.path.join(os.path.dirname(__file__), "..", "native",
                    "ptype_wire.cpp")
_SO = os.path.join(os.path.dirname(__file__), "_ptype_wire.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _build() -> bool:
    src = os.path.abspath(_SRC)
    if not os.path.exists(src):
        return False
    try:
        subprocess.run(
            ["g++", "-O3", "-fPIC", "-shared", "-o", _SO, src],
            check=True, capture_output=True, timeout=120,
        )
        return True
    except (OSError, subprocess.SubprocessError) as e:
        log.debug("native build failed", kv={"err": str(e)})
        return False


def _stale() -> bool:
    """True when the .so is missing or older than the source."""
    try:
        return os.path.getmtime(_SO) < os.path.getmtime(_SRC)
    except OSError:
        return not os.path.exists(_SO)


def load() -> ctypes.CDLL | None:
    """The native library, building it on first use if possible.

    Lock-free fast path after the first call: every wire send/recv goes
    through here, so the steady state must not serialize all connection
    threads on a module lock (the one-time build inside the lock is
    acceptable: callers fall back to Python until it finishes)."""
    global _lib, _tried
    if _tried:
        return _lib
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if _stale() and not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            log.debug("native load failed", kv={"err": str(e)})
            return None
        lib.ptype_send_frame.restype = ctypes.c_int
        lib.ptype_send_frame.argtypes = [
            ctypes.c_int, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
        ]
        lib.ptype_recv_exact.restype = ctypes.c_int64
        lib.ptype_recv_exact.argtypes = [
            ctypes.c_int, ctypes.c_void_p, ctypes.c_uint64,
        ]
        lib.ptype_crc32c.restype = ctypes.c_uint32
        lib.ptype_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        _lib = lib
        log.debug("native wire library loaded", kv={"path": _SO})
        return _lib


def available() -> bool:
    return load() is not None


def send_frame(sock, header: bytes, blobs: list[bytes]) -> bool:
    """writev the frame [len][header][blobs...]; False → caller falls
    back to Python sends. Socket must be blocking."""
    lib = load()
    if lib is None:
        return False
    n = len(blobs)
    if n > 1000:
        # The C side caps its iovec array; very-many-leaf payloads take
        # the Python sendall fallback rather than erroring.
        return False
    blob_arr = (ctypes.c_char_p * n)(*blobs) if n else None
    len_arr = (ctypes.c_uint64 * n)(*[len(b) for b in blobs]) if n else None
    rc = lib.ptype_send_frame(
        sock.fileno(), header, len(header),
        ctypes.cast(blob_arr, ctypes.POINTER(ctypes.c_char_p)),
        ctypes.cast(len_arr, ctypes.POINTER(ctypes.c_uint64)),
        n,
    )
    if rc != 0:
        raise OSError(-rc, os.strerror(-rc))
    return True


def recv_exact_into(sock, buf: memoryview) -> int:
    """Read exactly len(buf) bytes into a writable buffer without the
    GIL. Returns bytes read (== len(buf)), 0 on clean EOF; raises
    ConnectionError on mid-frame EOF, OSError on socket error. Falls
    back by raising NotImplementedError when the library is absent."""
    lib = load()
    if lib is None:
        raise NotImplementedError("native wire library unavailable")
    addr = ctypes.addressof(ctypes.c_char.from_buffer(buf))
    rc = lib.ptype_recv_exact(sock.fileno(), addr, len(buf))
    if rc == -1000000:
        raise ConnectionError("EOF mid-frame")
    if rc < 0:
        raise OSError(int(-rc), os.strerror(int(-rc)))
    return int(rc)


def crc32c(data: bytes) -> int:
    lib = load()
    if lib is None:
        raise NotImplementedError("native wire library unavailable")
    return int(lib.ptype_crc32c(data, len(data)))
