"""Chaos-harness matrix: fault-injecting mirrors, integrity recovery,
crash-resume, and the simulator-side fault mirrors.

Every end-to-end case asserts the full-file checksum — the point of the
robustness layer is that injected corruption, truncation, stalls, resets,
and crashes are *invisible* in the delivered bytes, only in the report's
accounting (re-fetch counts, retries, resumed bytes, served-byte totals).
All fault draws are seeded so the matrix is reproducible.
"""

import asyncio
import hashlib
import os
import threading
import time
import zlib

import numpy as np
import pytest

from repro.core.chunking import ChunkParams
from repro.transfer import (
    FaultPolicy,
    FleetModel,
    MDTPClient,
    RangeServer,
    Replica,
    ResumeJournal,
    Throttle,
    TransferIncompleteError,
    TransferReport,
    fetch_blob,
)
from repro.transfer.journal import merge_intervals, uncovered_intervals

MB = 1024 * 1024


def _sha(b) -> str:
    return hashlib.sha256(bytes(b)).hexdigest()


@pytest.fixture
def blob():
    rng = np.random.default_rng(0)
    return rng.integers(0, 256, size=4 * MB, dtype=np.uint8).tobytes()


def _mirror(blob, throttle=None, faults=None):
    s = RangeServer(throttle=throttle, faults=faults).start()
    s.add_blob("/data", blob)
    return s


# --------------------------------------------------------------------------
# Resume journal (unit)
# --------------------------------------------------------------------------


def test_interval_helpers():
    assert merge_intervals([(0, 4), (4, 4), (12, 2)]) == [(0, 8), (12, 2)]
    # overlap across crash generations unions cleanly
    assert merge_intervals([(0, 6), (4, 6), (20, 1)]) == [(0, 10), (20, 1)]
    assert merge_intervals([]) == []
    assert uncovered_intervals([(0, 8), (12, 2)], 20) == [(8, 4), (14, 6)]
    assert uncovered_intervals([], 5) == [(0, 5)]
    assert uncovered_intervals([(0, 5)], 5) == []


def test_journal_roundtrip_and_replay(tmp_path):
    p = str(tmp_path / "j.log")
    meta = {"step": 7}
    with ResumeJournal.open(p, 100, meta=meta) as jr:
        jr.record(0, 40, zlib.crc32(b"a" * 40))
        jr.record(60, 20, zlib.crc32(b"b" * 20))
    # same identity => records replay; uncovered is the complement
    jr2 = ResumeJournal.open(p, 100, meta=meta)
    assert jr2.covered() == [(0, 40), (60, 20)]
    assert uncovered_intervals(jr2.covered(), 100) == [(40, 20), (80, 20)]
    jr2.close()
    # foreign identity (different total) => fresh journal, nothing trusted
    jr3 = ResumeJournal.open(p, 200, meta=meta)
    assert jr3.covered() == []
    jr3.close()


def test_journal_truncates_torn_tail(tmp_path):
    p = str(tmp_path / "j.log")
    with ResumeJournal.open(p, 100) as jr:
        jr.record(0, 50, 123)
    with open(p, "a", encoding="ascii") as f:
        f.write("60 40 99")           # no newline: torn mid-append
    jr = ResumeJournal.open(p, 100)
    assert jr.covered() == [(0, 50)]  # torn record dropped
    jr.record(50, 25, 7)              # appends stay parseable after truncate
    jr.close()
    assert ResumeJournal.open(p, 100).covered() == [(0, 75)]


def test_journal_complete_deletes(tmp_path):
    p = str(tmp_path / "j.log")
    jr = ResumeJournal.open(p, 10)
    jr.record(0, 10)
    jr.complete()
    assert not os.path.exists(p)


# --------------------------------------------------------------------------
# Integrity: corruption / truncation / garbage / resets over real HTTP
# --------------------------------------------------------------------------


def test_corruption_refetched_from_alternate_mirror(blob):
    """A mirror that corrupts EVERY body must contribute nothing: each
    mismatched range is re-pooled banned-for-that-replica, re-fetched
    from the clean mirror, and the chronically corrupt mirror is retired
    once it crosses ``max_failures`` — yet the file arrives intact."""
    bad = _mirror(blob, faults=FaultPolicy(corrupt_rate=1.0, seed=3))
    good = _mirror(blob)
    try:
        replicas = [Replica("127.0.0.1", bad.port, "/data"),
                    Replica("127.0.0.1", good.port, "/data")]
        data, report = fetch_blob(
            replicas, len(blob),
            params=ChunkParams(initial_chunk=256 * 1024, large_chunk=MB),
            max_failures=3)
        assert _sha(data) == _sha(blob)
        bad_name = replicas[0].name
        assert report.corrupt_ranges[bad_name] >= 3
        assert bad_name in report.failed_replicas
        assert report.refetched_ranges >= 3
        assert bad.fault_counts["corrupt"] >= 3
        # none of the corrupt mirror's bytes were counted as delivered
        assert report.bytes_per_replica[replicas[1].name] == len(blob)
    finally:
        bad.stop()
        good.stop()


def test_corruption_costs_only_its_ranges(blob):
    """Corruption is paid per range, not per transfer: with one of two
    mirrors corrupting 5% of its bodies (fixed seed) and every range
    exactly 256 KiB, the mirrors serve the blob once plus one range per
    re-fetch, and no more."""
    chunk = 256 * 1024
    bad = _mirror(blob, faults=FaultPolicy(corrupt_rate=0.05, seed=17))
    good = _mirror(blob)
    try:
        replicas = [Replica("127.0.0.1", bad.port, "/data"),
                    Replica("127.0.0.1", good.port, "/data")]
        data, report = fetch_blob(
            replicas, len(blob),
            params=ChunkParams(initial_chunk=chunk, large_chunk=chunk,
                               mode="static"),
            max_failures=50)
        assert _sha(data) == _sha(blob)
        corrupt = sum(report.corrupt_ranges.values())
        assert corrupt == bad.fault_counts.get("corrupt", 0)
        assert report.refetched_ranges == corrupt
        served = bad.served_bytes + good.served_bytes
        assert served <= len(blob) + report.refetched_ranges * chunk
    finally:
        bad.stop()
        good.stop()


def test_truncated_bodies_recovered(blob):
    """Mid-body truncation (connection cut) on one mirror: the short
    range re-pools and the fleet still assembles the exact file."""
    flaky = _mirror(blob, faults=FaultPolicy(truncate_rate=0.5, seed=11))
    good = _mirror(blob)
    try:
        replicas = [Replica("127.0.0.1", flaky.port, "/data"),
                    Replica("127.0.0.1", good.port, "/data")]
        data, report = fetch_blob(
            replicas, len(blob),
            params=ChunkParams(initial_chunk=256 * 1024, large_chunk=MB),
            max_failures=50)
        assert _sha(data) == _sha(blob)
        assert flaky.fault_counts["truncate"] >= 1
        assert sum(report.bytes_per_replica.values()) == len(blob)
    finally:
        flaky.stop()
        good.stop()


def test_garbage_and_resets_recovered(blob):
    """Garbage responses and TCP resets trigger reconnect-with-backoff;
    the retry accounting surfaces on the report and the bytes survive."""
    flaky = _mirror(blob, faults=FaultPolicy(garbage_rate=0.25,
                                             reset_rate=0.25, seed=5))
    good = _mirror(blob)
    try:
        replicas = [Replica("127.0.0.1", flaky.port, "/data"),
                    Replica("127.0.0.1", good.port, "/data")]
        data, report = fetch_blob(
            replicas, len(blob),
            params=ChunkParams(initial_chunk=256 * 1024, large_chunk=MB),
            max_failures=50, retry_backoff_cap=0.2)
        assert _sha(data) == _sha(blob)
        # only kinds that fired have a key; which of the two fires first
        # depends on the load-dependent request sequence, so don't index
        counts = flaky.fault_counts
        assert counts.get("garbage", 0) + counts.get("reset", 0) >= 1
        assert report.retries_per_replica[replicas[0].name] >= 1
        assert sum(report.bytes_per_replica.values()) == len(blob)
    finally:
        flaky.stop()
        good.stop()


def test_stall_timeout_fails_over(blob):
    """A mirror that stalls forever must not stall the transfer: the
    per-request inactivity timeout converts the dead air into a retry,
    and the healthy mirror finishes well before the stall would."""
    stall = _mirror(blob, faults=FaultPolicy(stall_rate=1.0, stall_s=8.0,
                                             seed=2))
    good = _mirror(blob)
    try:
        replicas = [Replica("127.0.0.1", stall.port, "/data"),
                    Replica("127.0.0.1", good.port, "/data")]
        t0 = time.monotonic()
        data, report = fetch_blob(
            replicas, len(blob),
            params=ChunkParams(initial_chunk=256 * 1024, large_chunk=MB),
            max_failures=2, read_timeout=0.4, retry_backoff_cap=0.2)
        wall = time.monotonic() - t0
        assert _sha(data) == _sha(blob)
        assert wall < 6.0          # never served a full 8 s stall
        assert report.bytes_per_replica[replicas[1].name] == len(blob)
    finally:
        stall.stop()
        good.stop()


def test_kill_mid_pipeline_under_faults(blob):
    """Crash a mirror with pipelined ranges in flight while the survivor
    injects occasional truncations: every owed range re-pools exactly
    once (byte conservation) and the hash still matches."""
    big = blob * 2                      # slow enough that the kill lands
    victim = _mirror(big, throttle=Throttle(bytes_per_s=4 * MB,
                                            deterministic=True))
    survivor = _mirror(big, throttle=Throttle(bytes_per_s=30 * MB,
                                              deterministic=True),
                       faults=FaultPolicy(truncate_rate=0.4, seed=9))
    try:
        replicas = [Replica("127.0.0.1", victim.port, "/data"),
                    Replica("127.0.0.1", survivor.port, "/data")]

        def kill():
            victim.kill_connections()
            victim.stop()

        threading.Timer(0.1, kill).start()
        data, report = fetch_blob(
            replicas, len(big),
            params=ChunkParams(initial_chunk=256 * 1024, large_chunk=MB),
            max_failures=50, pipeline_depth=4, retry_backoff_cap=0.2)
        assert _sha(data) == _sha(big)
        # conservation: each byte delivered exactly once across the
        # kill re-pool AND the truncation re-pools
        assert sum(report.bytes_per_replica.values()) == len(big)
        assert survivor.fault_counts["truncate"] >= 1
        # the killed mirror cost at least one reconnect attempt
        assert report.retries_per_replica[replicas[0].name] >= 1
    finally:
        survivor.stop()
        try:
            victim.stop()
        except Exception:
            pass


def test_incomplete_transfer_raises_typed_error(blob):
    """With every replica retired for corruption, fetch must raise the
    dedicated error (not return a silently short buffer) carrying the
    delivered-byte accounting."""
    bad = _mirror(blob, faults=FaultPolicy(corrupt_rate=1.0, seed=1))
    try:
        replicas = [Replica("127.0.0.1", bad.port, "/data")]
        with pytest.raises(TransferIncompleteError) as ei:
            fetch_blob(replicas, len(blob),
                       params=ChunkParams(initial_chunk=256 * 1024,
                                          large_chunk=MB),
                       max_failures=2)
        err = ei.value
        assert err.expected_bytes == len(blob)
        assert err.done_bytes < len(blob)
        assert replicas[0].name in err.failed_replicas
        assert isinstance(err, IOError)   # compatibility contract
    finally:
        bad.stop()


# --------------------------------------------------------------------------
# Crash-resume (client + checkpoint restore), verified by served bytes
# --------------------------------------------------------------------------


def test_resume_after_cancel_is_byte_exact(blob, tmp_path):
    """Cancel a journaled fetch mid-transfer, then resume into the same
    buffer: the second fetch asks the mirrors only for uncovered bytes
    (served-byte accounting on the servers is the witness) and the
    assembled file is byte-exact."""
    servers = [_mirror(blob, throttle=Throttle(bytes_per_s=6 * MB,
                                               deterministic=True)),
               _mirror(blob, throttle=Throttle(bytes_per_s=6 * MB,
                                               deterministic=True))]
    jpath = str(tmp_path / "resume.log")
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        params = ChunkParams(initial_chunk=128 * 1024, large_chunk=256 * 1024)
        buf = bytearray(len(blob))

        async def first_leg():
            jr = ResumeJournal.open(jpath, len(blob),
                                    sync_interval_bytes=256 * 1024)
            client = MDTPClient(replicas, params=params)
            task = asyncio.ensure_future(
                client.fetch(len(blob), resume=jr, into=buf))
            try:
                while sum(s.served_bytes for s in servers) < len(blob) // 3:
                    await asyncio.sleep(0.01)
                    if task.done():      # finished before the threshold?
                        return await task
                task.cancel()
                with pytest.raises(asyncio.CancelledError):
                    await task
            finally:
                jr.close()
            return None

        asyncio.run(first_leg())
        served_first = sum(s.served_bytes for s in servers)
        jr = ResumeJournal.open(jpath, len(blob),
                                sync_interval_bytes=256 * 1024)
        resumed_ranges = jr.covered()
        assert resumed_ranges, "cancel landed before any journal record"

        async def second_leg():
            client = MDTPClient(replicas, params=params)
            try:
                return await client.fetch(len(blob), resume=jr, into=buf)
            finally:
                jr.close()

        _, report = asyncio.run(second_leg())
        assert _sha(buf) == _sha(blob)
        assert report.resumed_bytes > 0
        assert report.resumed_bytes == sum(n for _, n in resumed_ranges)
        # the mirrors only served what the journal did not cover (plus
        # bounded slack for ranges cut off mid-body by the cancel)
        served_second = sum(s.served_bytes for s in servers) - served_first
        assert served_second <= len(blob) - report.resumed_bytes + 512 * 1024
    finally:
        for s in servers:
            s.stop()


def test_restore_resume_fetches_only_missing(tmp_path):
    """Checkpoint restore with ``resume=``: a scratch dir pre-seeded with
    the first half of the blob (spool + journal) makes the mirrors serve
    only the missing tail."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.checkpoint import restore_checkpoint, save_checkpoint

    state = {"w": jax.random.normal(jax.random.PRNGKey(0), (512, 512)),
             "step": jnp.int32(3)}
    d = save_checkpoint(str(tmp_path / "ckpt"), 300, state)
    total = os.path.getsize(os.path.join(d, "data.bin"))
    with open(os.path.join(d, "data.bin"), "rb") as f:
        payload = f.read()

    scratch = tmp_path / "scratch"
    scratch.mkdir()
    half = total // 2
    with open(scratch / "data.spool", "wb") as f:
        f.write(payload[:half])
        f.truncate(total)
    jr = ResumeJournal.open(str(scratch / "journal.log"), total,
                            meta={"step": 300})
    jr.record(0, half, zlib.crc32(payload[:half]))
    jr.close()

    srv = RangeServer().start()
    base = "/ckpt/step_0000000300"
    srv.add_file(base + "/manifest.json", os.path.join(d, "manifest.json"))
    srv.add_file(base + "/data.bin", os.path.join(d, "data.bin"))
    try:
        restored, step = restore_checkpoint(
            str(tmp_path / "ckpt"), state, step=300,
            replicas=[Replica("127.0.0.1", srv.port, "/ckpt")],
            resume=str(scratch))
        assert step == 300
        assert bool(jnp.all(restored["w"] == state["w"]))
        # the blob fetch skipped the journaled half (manifest riding on
        # the same server is tiny next to the half-blob margin)
        assert srv.served_bytes < total - half // 2
        # a completed restore cleans up after itself
        assert not os.path.exists(scratch / "journal.log")
        assert not os.path.exists(scratch / "data.spool")
    finally:
        srv.stop()


# --------------------------------------------------------------------------
# Fleet health: chronic corruption deprioritizes a mirror
# --------------------------------------------------------------------------


def test_fleet_model_health_decays_and_recovers():
    fm = FleetModel()
    fm.register("t1")
    for name in ("a:1", "b:2"):
        fm.observe_chunk("t1", name, nbytes=8 * MB, elapsed=1.0,
                         rtt_included=False)
    reps = [Replica("a", 1, "/d"), Replica("b", 2, "/d")]
    clean = fm.allocation_view("t1", reps, [8.0 * MB, 8.0 * MB])
    for _ in range(4):
        fm.observe_corruption("a:1")
    tainted = fm.allocation_view("t1", reps, [8.0 * MB, 8.0 * MB])
    assert tainted[0] < clean[0] * 0.5        # 0.7**4 ≈ 0.24
    assert tainted[1] == pytest.approx(clean[1])
    assert fm.snapshot()["a:1"]["corruptions"] == 4
    # clean evidence rebuilds trust, but slowly (asymmetric on purpose)
    for _ in range(10):
        fm.observe_chunk("t1", "a:1", nbytes=8 * MB, elapsed=1.0,
                         rtt_included=False)
    healed = fm.allocation_view("t1", reps, [8.0 * MB, 8.0 * MB])
    assert tainted[0] < healed[0] < clean[0]


# --------------------------------------------------------------------------
# Simulator mirrors: ServerSpec loss/corruption + SimConfig fault rates
# --------------------------------------------------------------------------


def test_python_sim_fault_traces_complete_and_pay_overhead():
    from repro.core import MDTPPolicy, simulate
    from repro.core.scenarios import fault_traces, paper_baseline

    size = 256 * MB
    params = ChunkParams(initial_chunk=4 * MB, large_chunk=32 * MB)
    clean = simulate(MDTPPolicy(params, retry_after=0.25),
                     paper_baseline(jitter=0.0), size, seed=4)
    clean.check_integrity()
    for trace in fault_traces():
        r = simulate(MDTPPolicy(params, retry_after=0.25),
                     list(trace.servers), size, seed=4)
        r.check_integrity()                       # every byte exactly once
        assert sum(r.bytes_per_server) == size
        assert r.total_time >= clean.total_time * 0.999, trace.name


def test_python_sim_fault_free_taint_is_identity():
    from repro.core import MDTPPolicy, simulate
    from repro.core.scenarios import paper_baseline, with_faults

    size = 128 * MB
    base = paper_baseline()
    a = simulate(MDTPPolicy(), base, size, seed=6)
    b = simulate(MDTPPolicy(), with_faults(base), size, seed=6)
    assert a.total_time == b.total_time           # zero rates draw no RNG
    assert a.bytes_per_server == b.bytes_per_server


def test_jax_sim_faults_slower_yet_complete():
    pytest.importorskip("jax")
    from repro.core.jax_sim import SimConfig, simulate_transfer

    bw = [30.0 * MB, 60.0 * MB, 120.0 * MB]
    size = 512 * MB
    params = ChunkParams(initial_chunk=8 * MB, large_chunk=64 * MB)
    for engine in ("event", "round"):
        clean = simulate_transfer(bw, 0.02, size, params, seed=11,
                                  engine=engine)
        faulty = simulate_transfer(
            bw, 0.02, size, params, seed=11, engine=engine,
            config=SimConfig(loss_rate=0.05, corruption_rate=0.10))
        assert float(faulty.total_time) > float(clean.total_time), engine
        # failed chunks roll back off the cursor and re-issue: delivered
        # bytes still equal the file size
        np.testing.assert_allclose(
            float(np.sum(np.asarray(faulty.bytes_per_server))), size,
            rtol=1e-5)


def test_jax_round_and_scan_agree_under_faults():
    pytest.importorskip("jax")
    from repro.core.autotune import _sized_config
    from repro.core.jax_sim import SimConfig, simulate_transfer

    bw = [30.0 * MB, 60.0 * MB, 120.0 * MB]
    size = 512 * MB
    params = ChunkParams(initial_chunk=8 * MB, large_chunk=64 * MB,
                         mode="proportional")
    cfg = SimConfig(loss_rate=0.05, corruption_rate=0.10, exact_sizes=False)
    cfg = _sized_config(cfg, "scan",
                        [(params.initial_chunk, params.large_chunk)], size)
    r = simulate_transfer(bw, 0.02, size, params, seed=11, engine="round",
                          config=cfg)
    s = simulate_transfer(bw, 0.02, size, params, seed=11, engine="scan",
                          config=cfg)
    assert float(r.total_time) == float(s.total_time)
    np.testing.assert_allclose(np.asarray(r.bytes_per_server),
                               np.asarray(s.bytes_per_server), rtol=1e-6)


def test_jax_sim_fault_free_replay_bit_identical():
    """Zero fault rates must not consume extra PRNG splits: results are
    bit-identical to a build that predates the fault axes."""
    pytest.importorskip("jax")
    from repro.core.jax_sim import SimConfig, simulate_transfer

    bw = [30.0 * MB, 60.0 * MB, 120.0 * MB]
    size = 256 * MB
    params = ChunkParams(initial_chunk=8 * MB, large_chunk=64 * MB)
    jittery = SimConfig(jitter=0.3)
    tainted = SimConfig(jitter=0.3, loss_rate=0.0, corruption_rate=0.0)
    for engine in ("event", "round"):
        a = simulate_transfer(bw, 0.02, size, params, seed=13,
                              engine=engine, config=jittery)
        b = simulate_transfer(bw, 0.02, size, params, seed=13,
                              engine=engine, config=tainted)
        assert float(a.total_time) == float(b.total_time), engine


def test_autotune_prices_in_fault_tax():
    """The fused sweep under corruption must predict strictly slower
    transfers (re-fetch overhead) while staying finite — the signal the
    online tuners use to shrink L under chronic corruption."""
    pytest.importorskip("jax")
    from repro.core.autotune import autotune_chunk_params

    bw = [30.0 * MB, 60.0 * MB, 120.0 * MB]
    size = 1024 * MB
    clean = autotune_chunk_params(bw, rtt=0.03, file_size=size)
    faulty = autotune_chunk_params(bw, rtt=0.03, file_size=size,
                                   corruption_rate=0.15, n_seeds=4)
    assert np.isfinite(faulty.predicted_time)
    assert faulty.predicted_time > clean.predicted_time


def test_retune_folds_observed_corruption_rate():
    """A client whose last transfer saw checksum failures re-tunes with
    the measured corruption rate (and a seed sweep), matching a direct
    autotune call with the same effective rate."""
    pytest.importorskip("jax")
    from repro.core.autotune import autotune_chunk_params

    GB = 1024 * MB
    replicas = [Replica("h0", 1, "/b"), Replica("h1", 2, "/b")]
    client = MDTPClient(replicas)
    client.last_report = TransferReport(
        total_bytes=1, elapsed=1.0, bytes_per_replica={},
        requests_per_replica={"h0:1": 30, "h1:2": 10},
        failed_replicas=[], refetched_ranges=8,
        observed_throughputs={"h0:1": 50.0 * MB, "h1:2": 10.0 * MB},
        observed_rtts={"h0:1": 0.03, "h1:2": 0.03},
        corrupt_ranges={"h0:1": 8, "h1:2": 0})
    res = client.retune(2 * GB)
    expect = autotune_chunk_params(
        [50.0 * MB, 10.0 * MB], rtt=[0.03, 0.03], file_size=2 * GB,
        pipeline_depth=client.pipeline_depth,
        corruption_rate=8 / 40, n_seeds=4)
    assert res.predicted_times == expect.predicted_times
    assert res.params == expect.params
