"""Integration tests: real asyncio MDTP client over localhost HTTP mirrors."""

import hashlib

import numpy as np
import pytest

from repro.core.chunking import ChunkParams
from repro.transfer import MDTPClient, RangeServer, Replica, Throttle, fetch_blob

MB = 1024 * 1024


def _mirrors(blob, throttles):
    servers = []
    for th in throttles:
        s = RangeServer(throttle=th).start()
        s.add_blob("/data", blob)
        servers.append(s)
    return servers


@pytest.fixture
def blob():
    rng = np.random.default_rng(0)
    return rng.integers(0, 256, size=8 * MB, dtype=np.uint8).tobytes()


def test_roundtrip_integrity(blob):
    # deterministically paced mirrors: each piece pays its wire time as an
    # unconditional token-bucket sleep, so the 30/60/120 rate ratios hold
    # regardless of host load and the proportionality assertion needs no
    # retry guard (wall-clock compensation pacing could be erased by a
    # loaded box, transiently inverting the mirrors' relative rates)
    servers = _mirrors(blob, [
        Throttle(bytes_per_s=30 * MB, deterministic=True),
        Throttle(bytes_per_s=60 * MB, deterministic=True),
        Throttle(bytes_per_s=120 * MB, deterministic=True)])
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        params = ChunkParams(initial_chunk=256 * 1024, large_chunk=MB)
        data, report = fetch_blob(replicas, len(blob), params=params)
        assert hashlib.sha256(data).hexdigest() == \
            hashlib.sha256(blob).hexdigest()
        # every mirror contributed, and the 4x-faster mirror beat the
        # slowest.  (Strict ordering of the top two is NOT asserted:
        # the 60 vs 120 MB/s estimates sit too close.)
        contributions = [report.bytes_per_replica[r.name]
                         for r in replicas]
        assert all(c > 0 for c in contributions)
        assert report.failed_replicas == []
        # per-replica RTT was measured (connect + header turnaround):
        # every contributing mirror has a positive, sane sample
        for r in replicas:
            assert 0.0 < report.observed_rtts[r.name] < 5.0
        assert contributions[2] > contributions[0]
    finally:
        for s in servers:
            s.stop()


def test_retune_uses_measured_rtts():
    """retune feeds the fused tuner the MEASURED per-replica RTTs from the
    last transfer (falling back to the default only for replicas that
    never produced a sample), not a hardcoded constant — and the client's
    own pipeline depth, so the sweep models the runtime's actual request
    overlap."""
    from repro.core.autotune import autotune_chunk_params
    from repro.transfer.client import MDTPClient, Replica, TransferReport

    GB = 1024 * MB
    replicas = [Replica("h0", 1, "/b"), Replica("h1", 2, "/b")]
    client = MDTPClient(replicas, pipeline_depth=3)
    client.last_report = TransferReport(
        total_bytes=1, elapsed=1.0, bytes_per_replica={},
        requests_per_replica={}, failed_replicas=[], refetched_ranges=0,
        observed_throughputs={"h0:1": 50.0 * MB, "h1:2": 10.0 * MB},
        observed_rtts={"h0:1": 0.25, "h1:2": 0.0})  # h1 never sampled
    res = client.retune(2 * GB)
    expect = autotune_chunk_params(
        [50.0 * MB, 10.0 * MB], rtt=[0.25, MDTPClient.DEFAULT_RTT],
        file_size=2 * GB, pipeline_depth=3)
    assert res.predicted_times == expect.predicted_times
    assert res.params == expect.params
    # a quarter-second RTT penalizes small chunks: the winner must differ
    # from the low-latency tune unless both argmins coincide by chance —
    # at minimum the predicted times must reflect the measured latency
    low_lat = autotune_chunk_params(
        [50.0 * MB, 10.0 * MB], rtt=0.001, file_size=2 * GB,
        pipeline_depth=3)
    assert res.predicted_time > low_lat.predicted_time


def test_wire_elapsed_strips_request_rtt():
    """Regression for the observation-point bias correction: a serial
    (idle-pipe) chunk observation spans rtt + body time, and
    ``wire_elapsed`` recovers the on-wire body time exactly; impossible
    corrections pass the elapsed through unchanged."""
    from repro.transfer.client import wire_elapsed

    wire, rtt, chunk = 70.0 * MB, 0.5, 40.0 * MB
    elapsed = rtt + chunk / wire
    corrected = wire_elapsed(int(chunk), elapsed, rtt)
    assert corrected == pytest.approx(chunk / wire, rel=1e-9)
    assert int(chunk) / corrected == pytest.approx(wire, rel=1e-9)
    # no RTT sample -> passthrough; implied non-positive wire time ->
    # passthrough; degenerate inputs -> passthrough
    assert wire_elapsed(int(chunk), elapsed, 0.0) == elapsed
    assert wire_elapsed(int(chunk), 0.3, 0.5) == 0.3
    assert wire_elapsed(0, 1.0, 0.5) == 1.0
    assert wire_elapsed(int(chunk), 0.0, 0.5) == 0.0


def test_retune_passes_wire_rates_through():
    """``observed_throughputs`` are already wire rates (the RTT bias is
    stripped per observation via ``wire_elapsed``), so ``retune`` must
    feed them to the fused sweep UNCHANGED — re-applying
    ``rtt_corrected_bandwidth`` on top would overstate every high-RTT
    replica's capacity."""
    from repro.core.autotune import autotune_chunk_params
    from repro.core.throughput import rtt_corrected_bandwidth
    from repro.transfer.client import MDTPClient, Replica, TransferReport

    GB = 1024 * MB
    replicas = [Replica("h0", 1, "/b"), Replica("h1", 2, "/b")]
    wire = {"h0:1": 70.0 * MB, "h1:2": 12.0 * MB}
    rtts = {"h0:1": 0.5, "h1:2": 0.03}
    client = MDTPClient(replicas, pipeline_depth=1)
    client.last_report = TransferReport(
        total_bytes=1, elapsed=1.0,
        bytes_per_replica={n: 320 * MB for n in wire},
        requests_per_replica={n: 8 for n in wire},
        failed_replicas=[], refetched_ranges=0,
        observed_throughputs=dict(wire), observed_rtts=rtts)
    res = client.retune(2 * GB)
    expect = autotune_chunk_params(
        [wire["h0:1"], wire["h1:2"]], rtt=[rtts["h0:1"], rtts["h1:2"]],
        file_size=2 * GB, pipeline_depth=1)
    assert res.predicted_times == expect.predicted_times
    assert res.params == expect.params
    # and NOT a double-corrected (inflated) fleet
    inflated = [rtt_corrected_bandwidth(wire[n], rtts[n], 40.0 * MB)
                for n in ("h0:1", "h1:2")]
    assert inflated[0] > wire["h0:1"]               # the hazard is real
    double = autotune_chunk_params(
        inflated, rtt=[rtts["h0:1"], rtts["h1:2"]], file_size=2 * GB,
        pipeline_depth=1)
    assert res.predicted_times != double.predicted_times


def test_retune_all_dead_replica_telemetry():
    """A transfer whose every replica failed (or never produced a sample)
    must make retune raise — and leave the adopted params untouched — not
    feed a zero-bandwidth fleet into the simulated sweep, where any grid
    point would 'win' with an infinite predicted time."""
    from repro.transfer.client import MDTPClient, Replica, TransferReport

    GB = 1024 * MB
    replicas = [Replica("h0", 1, "/b"), Replica("h1", 2, "/b")]
    before = ChunkParams(initial_chunk=2 * MB, large_chunk=20 * MB)
    client = MDTPClient(replicas, params=before)
    client.last_report = TransferReport(
        total_bytes=1, elapsed=1.0, bytes_per_replica={},
        requests_per_replica={}, failed_replicas=["h0:1", "h1:2"],
        refetched_ranges=0,
        observed_throughputs={"h0:1": 0.0, "h1:2": 0.0},
        observed_rtts={"h0:1": 0.02, "h1:2": 0.02})
    with pytest.raises(RuntimeError, match="no throughput"):
        client.retune(2 * GB)
    assert client._params_arg == before
    # a single live replica is enough again
    client.last_report.observed_throughputs["h1:2"] = 40.0 * MB
    res = client.retune(2 * GB)
    assert client._params_arg == res.params


def test_adaptive_chunks_scale_with_throughput(blob):
    """Slow mirror must get smaller requests, not fewer-by-starvation —
    the paper's load-proportionality claim on the real runtime."""
    servers = _mirrors(blob, [
        Throttle(bytes_per_s=15 * MB, deterministic=True),
        Throttle(bytes_per_s=120 * MB, deterministic=True)])
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        params = ChunkParams(initial_chunk=128 * 1024, large_chunk=MB)
        data, report = fetch_blob(replicas, len(blob), params=params)
        assert bytes(data) == blob
        slow, fast = (report.bytes_per_replica[r.name] for r in replicas)
        assert fast > 2 * slow
        # request counts stay comparable (sizes adapt instead) — Fig. 5c
        rs, rf = (report.requests_per_replica[r.name] for r in replicas)
        assert rs >= max(1, rf // 4)
    finally:
        for s in servers:
            s.stop()


def test_mirror_death_mid_transfer(blob):
    """Kill a mirror while it still owes bytes: the range pool reassigns its
    outstanding range and the transfer completes exactly."""
    victim = RangeServer(throttle=Throttle(bytes_per_s=4 * MB)).start()
    victim.add_blob("/data", blob)
    healthy = RangeServer(throttle=Throttle(bytes_per_s=60 * MB)).start()
    healthy.add_blob("/data", blob)
    try:
        replicas = [Replica("127.0.0.1", victim.port, "/data"),
                    Replica("127.0.0.1", healthy.port, "/data")]
        import threading
        killer = threading.Timer(0.15, victim.stop)
        killer.start()
        params = ChunkParams(initial_chunk=256 * 1024, large_chunk=MB)
        data, report = fetch_blob(replicas, len(blob), params=params,
                                  max_failures=2)
        assert bytes(data) == blob
    finally:
        healthy.stop()
        try:
            victim.stop()
        except Exception:
            pass


def test_sink_exception_propagates_promptly(blob):
    """A raising sink (e.g. disk full mid-stream) must propagate out of
    fetch instead of stranding sibling workers on the in-flight range
    accounting."""
    import asyncio

    from repro.transfer.client import MDTPClient

    servers = _mirrors(blob, [Throttle(bytes_per_s=40 * MB),
                              Throttle(bytes_per_s=40 * MB)])
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        client = MDTPClient(
            replicas, params=ChunkParams(256 * 1024, MB))

        def bad_sink(start, data):
            raise ValueError("disk full")

        with pytest.raises(ValueError):
            asyncio.run(client.fetch(len(blob), sink=bad_sink))
    finally:
        for s in servers:
            s.stop()


def test_all_mirrors_dead_raises(blob):
    s = RangeServer().start()
    s.add_blob("/data", blob[:MB])
    port = s.port
    s.stop()
    with pytest.raises((IOError, OSError)):
        fetch_blob([Replica("127.0.0.1", port, "/data")], MB)


def test_blob_size_head(blob):
    s = RangeServer().start()
    s.add_blob("/data", blob)
    try:
        data, _ = fetch_blob([Replica("127.0.0.1", s.port, "/data")])
        assert bytes(data) == blob
    finally:
        s.stop()


def test_pipelined_connection_death_repools_every_owed_range(blob):
    """Kill a mirror while its connection holds a deep pipeline of
    in-flight ranges: every owed range must re-enter the pool exactly
    once, the survivors must finish the transfer, and the assembled bytes
    must hash identical — delivered-byte conservation (== size) is the
    exactly-once witness: a dropped range fails the fetch with IOError, a
    double-pooled one overshoots ``done_bytes``."""
    import threading

    # slow deterministic victim so several pipelined requests are still
    # in flight when it dies; small chunks keep the pipeline populated
    victim = RangeServer(
        throttle=Throttle(bytes_per_s=4 * MB, deterministic=True)).start()
    victim.add_blob("/data", blob)
    healthy = RangeServer(
        throttle=Throttle(bytes_per_s=30 * MB, deterministic=True)).start()
    healthy.add_blob("/data", blob)
    try:
        replicas = [Replica("127.0.0.1", victim.port, "/data"),
                    Replica("127.0.0.1", healthy.port, "/data")]

        def kill():
            # sever the live stream (pipelined requests mid-flight) AND
            # the listener, so reconnect attempts fail too
            victim.kill_connections()
            victim.stop()

        killer = threading.Timer(0.1, kill)
        killer.start()
        params = ChunkParams(initial_chunk=256 * 1024, large_chunk=MB)
        data, report = fetch_blob(replicas, len(blob), params=params,
                                  max_failures=2, pipeline_depth=4)
        assert hashlib.sha256(bytes(data)).hexdigest() == \
            hashlib.sha256(blob).hexdigest()
        # conservation: each byte delivered exactly once, no range lost
        # or duplicated across the re-pool
        assert sum(report.bytes_per_replica.values()) == len(blob)
        assert report.refetched_ranges >= 1
        assert report.failed_replicas == [replicas[0].name]
    finally:
        healthy.stop()
        try:
            victim.stop()
        except Exception:
            pass


@pytest.mark.parametrize("depth", [1, None], ids=["depth1", "default"])
@pytest.mark.parametrize("dest", ["memory", "into", "buffer_sink",
                                  "callable"])
def test_every_destination_lands_byte_exact(blob, dest, depth):
    """Every destination ``fetch`` accepts (its own buffer, ``into=``, a
    ``BufferSink``, a bare callable) lands the blob byte-exact and
    commits each byte once, on the serial plane (``pipeline_depth=1``)
    and the default pipelined one."""
    import asyncio

    from repro.transfer import BufferSink

    s = RangeServer().start()
    s.add_blob("/data", blob)
    try:
        kw = {} if depth is None else {"pipeline_depth": depth}
        client = MDTPClient([Replica("127.0.0.1", s.port, "/data")],
                            params=ChunkParams(256 * 1024, MB), **kw)
        into = bytearray(len(blob)) if dest == "into" else None
        got = bytearray(len(blob))
        times = np.zeros(len(blob), np.int64)

        def callable_sink(start, view):
            got[start:start + len(view)] = view
            times[start:start + len(view)] += 1

        sink = {"buffer_sink": BufferSink(len(blob)),
                "callable": callable_sink}.get(dest)
        buf, report = asyncio.run(client.fetch(len(blob), sink, into=into))
        assert sum(report.bytes_per_replica.values()) == len(blob)
        if dest == "memory":
            assert bytes(buf) == blob
        elif dest == "into":
            assert buf is into and bytes(into) == blob
        elif dest == "buffer_sink":
            assert buf is None and bytes(sink) == blob
            assert sink.duplicate_bytes == 0
            assert sink.covered_intervals() == [(0, len(blob))]
        else:
            assert buf is None and bytes(got) == blob
            assert (times == 1).all()
    finally:
        s.stop()


def test_callable_sink_receives_transient_memoryviews(blob):
    """Callable sinks get memoryviews (zero materialized ``bytes`` on the
    receive path) and must copy before returning — the client recycles
    nothing the sink can keep."""
    import asyncio

    from repro.transfer.client import MDTPClient

    s = RangeServer().start()
    s.add_blob("/data", blob)
    try:
        got = bytearray(len(blob))
        kinds = set()

        def sink(start, view):
            kinds.add(type(view))
            got[start:start + len(view)] = view

        client = MDTPClient(
            [Replica("127.0.0.1", s.port, "/data")],
            params=ChunkParams(256 * 1024, MB))
        asyncio.run(client.fetch(len(blob), sink=sink))
        assert bytes(got) == blob
        assert kinds == {memoryview}
    finally:
        s.stop()


def test_writable_commit_sink_is_zero_copy_destination(blob):
    """The ``writable``/``commit`` sink protocol: the client reads socket
    bytes straight into the buffer the sink exposes and commits exactly
    the landed spans (each byte exactly once)."""
    import asyncio

    from repro.transfer.client import MDTPClient

    s = RangeServer().start()
    s.add_blob("/data", blob)
    try:
        class ZeroCopySink:
            def __init__(self, size):
                self.buf = bytearray(size)
                self.committed = 0
                self.views = []

            def writable(self, start, length):
                view = memoryview(self.buf)[start:start + length]
                self.views.append((start, length))
                return view

            def commit(self, start, nbytes):
                self.committed += nbytes

        zc = ZeroCopySink(len(blob))
        client = MDTPClient(
            [Replica("127.0.0.1", s.port, "/data")],
            params=ChunkParams(256 * 1024, MB))
        asyncio.run(client.fetch(len(blob), sink=zc))
        assert bytes(zc.buf) == blob
        assert zc.committed == len(blob)     # exactly-once accounting
        assert zc.views                       # the zero-copy path was used
    finally:
        s.stop()


def test_half_sink_protocol_rejected(blob):
    """A sink with ``writable`` but no ``commit`` (or vice versa) is a
    contract bug — fail loudly instead of silently copying."""
    import asyncio

    from repro.transfer.client import MDTPClient

    class Half:
        def writable(self, start, length):
            return memoryview(bytearray(length))

    client = MDTPClient([Replica("127.0.0.1", 1, "/data")])
    with pytest.raises(TypeError, match="writable"):
        asyncio.run(client.fetch(MB, sink=Half()))
