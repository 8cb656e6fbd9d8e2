"""Duplex transport lanes: the independent writer coroutine's send
pipelining and its failure semantics (per-lane conservation, no deadlock
on a severed socket)."""

import asyncio
import hashlib
import threading

import numpy as np

from repro.core.chunking import ChunkParams
from repro.transfer import RangeServer, Replica, Throttle, fetch_blob
from repro.transfer.transport import _Conn

KB = 1024
MB = 1024 * 1024

_LANE_ERRORS = (ConnectionError, OSError, asyncio.IncompleteReadError)


def _blob(n: int, seed: int = 0) -> bytes:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()


def _sha(b) -> str:
    return hashlib.sha256(b).hexdigest()


def _into(n: int) -> memoryview:
    return memoryview(bytearray(n))


def test_duplex_writer_pipelines_while_body_in_flight():
    """Two lanes enqueued together: the writer puts the second request
    on the wire while the first body is still streaming, so the second
    reply is judged pipelined (its elapsed excludes request RTT)."""
    blob = _blob(2 * MB)
    s = RangeServer(
        throttle=Throttle(bytes_per_s=8 * MB, deterministic=True)).start()
    s.add_blob("/data", blob)

    async def run():
        conn = _Conn(Replica("127.0.0.1", s.port, "/data"))
        try:
            lane1 = asyncio.ensure_future(
                conn.fetch_range(0, MB - 1, _into(MB)))
            lane2 = asyncio.ensure_future(
                conn.fetch_range(MB, 2 * MB - 1, _into(MB)))
            r1, r2 = await asyncio.gather(lane1, lane2)
            assert bytes(r1.data) == blob[:MB]
            assert bytes(r2.data) == blob[MB:]
            assert r1.rtt_included          # first lane paid the RTT
            assert not r2.rtt_included      # second rode the full pipe
        finally:
            await conn.close()

    try:
        asyncio.run(run())
    finally:
        s.stop()


def test_conn_death_fails_every_queued_lane_exactly_once():
    """Sever the socket with lanes deep in the write queue: every lane
    resolves — success or exactly one ConnectionError — and none hangs.
    This is the conservation contract the client's re-pool rides on."""
    blob = _blob(8 * MB, seed=1)
    s = RangeServer(
        throttle=Throttle(bytes_per_s=2 * MB, deterministic=True)).start()
    s.add_blob("/data", blob)

    async def run():
        conn = _Conn(Replica("127.0.0.1", s.port, "/data"))
        try:
            lanes = [asyncio.ensure_future(
                conn.fetch_range(i * MB, (i + 1) * MB - 1, _into(MB)))
                for i in range(8)]
            await asyncio.sleep(0.2)        # queue fills behind body 1
            s.kill_connections()
            done = await asyncio.wait_for(
                asyncio.gather(*lanes, return_exceptions=True), timeout=30)
            assert len(done) == 8           # every lane resolved
            errs = [r for r in done if isinstance(r, BaseException)]
            assert errs                     # the kill landed mid-queue
            assert all(isinstance(e, _LANE_ERRORS) for e in errs)
            ok = [r for r in done if not isinstance(r, BaseException)]
            for i, r in enumerate(ok):
                assert bytes(r.data) == blob[i * MB:(i + 1) * MB]
        finally:
            await conn.close()

    try:
        asyncio.run(run())
    finally:
        s.stop()


def test_abort_does_not_deadlock_queued_writer():
    """A hedge winner severs the loser with ``abort()``: the loser's
    queued lanes must all fail promptly — the writer coroutine may not
    deadlock holding un-failed futures."""
    blob = _blob(4 * MB, seed=2)
    s = RangeServer(
        throttle=Throttle(bytes_per_s=2 * MB, deterministic=True)).start()
    s.add_blob("/data", blob)

    async def run():
        conn = _Conn(Replica("127.0.0.1", s.port, "/data"))
        try:
            lanes = [asyncio.ensure_future(
                conn.fetch_range(i * MB, (i + 1) * MB - 1, _into(MB)))
                for i in range(4)]
            await asyncio.sleep(0.2)        # body 1 mid-flight, 3 queued
            conn.abort()
            done = await asyncio.wait_for(
                asyncio.gather(*lanes, return_exceptions=True), timeout=10)
            errs = [r for r in done if isinstance(r, BaseException)]
            assert len(errs) >= 3           # queued lanes all failed
            assert all(isinstance(e, _LANE_ERRORS) for e in errs)
            assert conn.broken
        finally:
            await conn.close()

    try:
        asyncio.run(run())
    finally:
        s.stop()


def test_client_repools_duplex_queue_on_mirror_death():
    """End to end: a mirror dies with pipelined requests queued in the
    duplex writer; every owed range re-pools exactly once and the blob
    hash still matches (byte conservation across the re-pool)."""
    blob = _blob(8 * MB, seed=3) * 2
    victim = RangeServer(throttle=Throttle(bytes_per_s=4 * MB,
                                           deterministic=True)).start()
    victim.add_blob("/data", blob)
    survivor = RangeServer(throttle=Throttle(bytes_per_s=40 * MB,
                                             deterministic=True)).start()
    survivor.add_blob("/data", blob)
    try:
        replicas = [Replica("127.0.0.1", victim.port, "/data"),
                    Replica("127.0.0.1", survivor.port, "/data")]

        def kill():
            victim.kill_connections()
            victim.stop()

        threading.Timer(0.15, kill).start()
        data, report = fetch_blob(
            replicas, len(blob),
            params=ChunkParams(initial_chunk=256 * KB, large_chunk=MB),
            max_failures=50, pipeline_depth=6, retry_backoff_cap=0.2)
        assert _sha(data) == _sha(blob)
        assert sum(report.bytes_per_replica.values()) == len(blob)
    finally:
        survivor.stop()
        try:
            victim.stop()
        except Exception:
            pass
