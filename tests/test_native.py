"""Native wire transport: build, correctness vs. pure-Python fallback."""

import socket
import threading

import numpy as np
import pytest

from ptype_tpu import codec, native


@pytest.fixture(scope="module")
def lib():
    lib = native.load()
    if lib is None:
        pytest.skip("native library unavailable (no g++?)")
    return lib


def test_builds_and_loads(lib):
    assert native.available()


def test_crc32c_known_vectors(lib):
    # RFC 3720 test vector: 32 bytes of zeros.
    assert native.crc32c(b"\x00" * 32) == 0x8A9136AA
    assert native.crc32c(b"123456789") == 0xE3069283


def test_send_frame_roundtrip(lib):
    a, b = socket.socketpair()
    try:
        header = b'{"id":1}'
        blobs = [b"alpha", b"", np.arange(1000, dtype=np.float32).tobytes()]
        assert native.send_frame(a, header, blobs)
        want = (len(header)).to_bytes(4, "big") + header + b"".join(blobs)
        got = b""
        while len(got) < len(want):
            got += b.recv(65536)
        assert got == want
    finally:
        a.close()
        b.close()


def test_recv_exact_into(lib):
    a, b = socket.socketpair()
    try:
        payload = np.random.default_rng(0).bytes(1 << 20)
        threading.Thread(target=lambda: a.sendall(payload)).start()
        buf = memoryview(bytearray(len(payload)))
        got = native.recv_exact_into(b, buf)
        assert got == len(payload)
        assert bytes(buf) == payload
    finally:
        a.close()
        b.close()


def test_recv_exact_eof_midframe(lib):
    a, b = socket.socketpair()
    try:
        a.sendall(b"abc")
        a.close()
        buf = memoryview(bytearray(10))
        with pytest.raises(ConnectionError):
            native.recv_exact_into(b, buf)
    finally:
        b.close()


def test_encode_parts_equals_encode():
    payload = {"x": np.arange(12, dtype=np.int32).reshape(3, 4),
               "y": [1, "two", b"three"], "z": None}
    assert b"".join(codec.encode_parts(payload)) == codec.encode(payload)


def test_rpc_over_native_wire(lib):
    """End-to-end actor call with the native send path active on both
    sides (the integration, not just the primitives)."""
    from ptype_tpu.actor import ActorServer
    from ptype_tpu.registry import Node
    from ptype_tpu.rpc import _Conn

    srv = ActorServer("127.0.0.1", 0)
    srv.register_function("Echo.Sum", lambda a, b: a + b)
    srv.serve()
    try:
        conn = _Conn(Node("127.0.0.1", srv.port, "n", "echo"))
        arr = np.arange(5000, dtype=np.float64)
        out = conn.call_async("Echo.Sum", (arr, arr)).result(timeout=30)
        np.testing.assert_array_equal(np.asarray(out), arr * 2)
        conn.close()
    finally:
        srv.close()


def test_a_binary_older_than_its_source_is_stale(tmp_path, monkeypatch):
    """The .so is git-ignored: one left on disk by an earlier checkout
    must be rebuilt once native/ptype_wire.cpp is newer (and built when
    it is missing), never loaded as-is."""
    import os

    src, so = tmp_path / "wire.cpp", tmp_path / "wire.so"
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_SO", str(so))
    src.write_text("// source")
    assert native._stale()  # missing
    so.write_bytes(b"\x7fELF")
    os.utime(so, (1_000, 1_000))
    os.utime(src, (2_000, 2_000))
    assert native._stale()  # older than the source
    os.utime(so, (3_000, 3_000))
    assert not native._stale()
