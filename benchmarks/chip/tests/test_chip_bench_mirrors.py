"""The benchmark's mirror emulator on CPU loopback: pacing, RTT overlap,
stop and restart, counters, and a child that never loads JAX."""

from __future__ import annotations

import os
import socket
import sys
import threading
import time
import zlib

import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")))

from benchmarks.chip.mirrors import PIECE, Mirror  # noqa: E402

MiB = 1 << 20
PATH = "/blob"


@pytest.fixture(scope="module")
def blob():
    data = os.urandom(8 * MiB)
    fd = os.memfd_create("blob")
    os.write(fd, data)
    yield fd, data
    os.close(fd)


@pytest.fixture
def mirror(blob, request):
    rate, rtt = request.param
    m = Mirror({PATH: blob[0]}, rate, rtt)
    yield m
    m.close()


def _request(lo: int, hi: int) -> bytes:
    return (f"GET {PATH} HTTP/1.1\r\nHost: x\r\nRange: bytes={lo}-{hi}\r\n"
            f"Connection: keep-alive\r\n\r\n").encode()


def _read_response(sock, buf: bytearray) -> tuple[dict, bytes]:
    """One response off a keep-alive socket; ``buf`` carries read-ahead."""
    while b"\r\n\r\n" not in buf:
        data = sock.recv(65536)
        if not data:
            raise ConnectionError("closed in headers")
        buf += data
    end = buf.index(b"\r\n\r\n")
    lines = bytes(buf[:end]).decode("latin-1").split("\r\n")
    del buf[:end + 4]
    headers = {"status": int(lines[0].split()[1])}
    for line in lines[1:]:
        k, _, v = line.partition(":")
        headers[k.strip().lower()] = v.strip()
    n = int(headers["content-length"])
    while len(buf) < n:
        data = sock.recv(1 << 20)
        if not data:
            raise ConnectionError(f"closed mid-body ({len(buf)}/{n} B)")
        buf += data
    body = bytes(buf[:n])
    del buf[:n]
    return headers, body


def _get(port: int, lo: int, hi: int) -> bytes:
    with socket.create_connection(("127.0.0.1", port)) as s:
        s.sendall(_request(lo, hi))
        headers, body = _read_response(s, bytearray())
    assert headers["status"] == 206
    assert headers["content-range"] == f"bytes {lo}-{hi}/{8 * MiB}"
    assert int(headers["x-range-checksum"][len("crc32:"):], 16) == \
        zlib.crc32(body)
    return body


@pytest.mark.parametrize("mirror", [(4 * MiB, 0.0)], indirect=True)
def test_paced_mirror_never_beats_its_rate_across_connections(mirror, blob):
    """Four connections share one egress clock: 4 MiB take at least
    4 MiB / rate however the threads interleave, and not far more."""
    data = blob[1]
    got = [None] * 4
    t0 = time.monotonic()

    def pull(i):
        got[i] = _get(mirror.port, i * MiB, (i + 1) * MiB - 1)

    threads = [threading.Thread(target=pull, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    elapsed = time.monotonic() - t0
    assert all(not t.is_alive() for t in threads)
    assert b"".join(got) == data[:4 * MiB]
    floor = 4 * MiB / mirror.rate
    assert elapsed >= floor - PIECE / mirror.rate, (elapsed, floor)
    assert elapsed < 1.5 * floor + 0.5, (elapsed, floor)


@pytest.mark.parametrize("mirror", [(8 * MiB, 0.0)], indirect=True)
def test_link_time_lost_to_a_stalled_reader_stays_lost(mirror, blob):
    """A reader that leaves its socket full for a second gets, once it
    reads again, what the kernel buffered and then the rate: the mirror
    does not repay the stall.  Its bucket (rate x RTT, here one piece) and
    the piece blocked in the kernel are the only burst."""
    rate = mirror.rate
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 64 * 1024)
        s.connect(("127.0.0.1", mirror.port))
        s.sendall(_request(0, 8 * MiB - 1))
        head = bytearray()
        while b"\r\n\r\n" not in head:
            head += s.recv(1)
        body = bytearray()
        while len(body) < 1 * MiB:
            body += s.recv(1 << 20)
        time.sleep(1.0)
        read = len(body)
        buffered = mirror.stats()["served"] - read
        t0 = time.monotonic()
        while len(body) < 8 * MiB:
            data = s.recv(1 << 20)
            assert data, len(body)
            body += data
            repaid = len(body) - read - buffered
            assert repaid <= rate * (time.monotonic() - t0) + 3 * PIECE, (
                repaid, buffered, time.monotonic() - t0)
    assert body == blob[1][:8 * MiB]


@pytest.mark.parametrize("mirror", [(64 * MiB, 0.25)], indirect=True)
def test_pipelined_requests_overlap_their_rtt(mirror, blob):
    """Eight pipelined requests cost one RTT plus bytes/rate, not eight."""
    data = blob[1]
    n, size = 8, 256 * 1024
    with socket.create_connection(("127.0.0.1", mirror.port)) as s:
        t0 = time.monotonic()
        s.sendall(b"".join(_request(i * size, (i + 1) * size - 1)
                           for i in range(n)))
        buf = bytearray()
        bodies = [_read_response(s, buf)[1] for _ in range(n)]
        elapsed = time.monotonic() - t0
    assert b"".join(bodies) == data[:n * size]
    ideal = mirror.rtt + n * size / mirror.rate
    assert ideal - 0.01 <= elapsed < ideal + 0.5 * mirror.rtt, (elapsed,
                                                                ideal)


@pytest.mark.parametrize("mirror", [(1 * MiB, 0.0)], indirect=True)
def test_stop_severs_in_flight_bodies_and_restart_serves(mirror, blob):
    port = mirror.port
    with socket.create_connection(("127.0.0.1", port)) as s:
        s.sendall(_request(0, 4 * MiB - 1))
        threading.Timer(0.3, mirror.stop).start()
        with pytest.raises(ConnectionError, match="mid-body"):
            _read_response(s, bytearray())
    with pytest.raises(ConnectionRefusedError):
        socket.create_connection(("127.0.0.1", port)).close()
    mirror.start()
    assert mirror.port == port
    assert _get(port, 100, 100 + 65535) == blob[1][100:100 + 65536]
    alive = mirror.stats()["alive"]
    assert len(alive) == 2 and alive[0][1] is not None and alive[1][1] is None


@pytest.mark.parametrize("mirror", [(0.0, 0.0), (32 * MiB, 0.01)],
                         indirect=True)
def test_counters_agree_with_what_the_client_received(mirror, blob):
    """Served bytes are body bytes; the last-byte stamp is on the
    client's monotonic clock, inside the request's interval.  The child
    has loaded no jax or repro module."""
    t0 = time.monotonic()
    body = _get(mirror.port, 1000, 1000 + 3 * MiB - 1)
    t1 = time.monotonic()
    stats = mirror.stats()
    assert stats["served"] == len(body) == 3 * MiB
    assert t0 < stats["last_byte"] <= t1
    assert stats["requests"] == 1
    assert stats["imported"] == []
