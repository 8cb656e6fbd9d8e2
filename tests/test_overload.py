"""Overload-robustness matrix: replica probation, admission control,
hedged endgame, and the flash-crowd scenario/simulator mirrors.

The probation cases drive ``FleetModel`` directly (no sockets — pure
state-machine checks on strikes, trips, and slow-start readmission).
The end-to-end cases run real loopback fleets and assert the full-file
checksum plus the report witnesses: robustness must be invisible in the
delivered bytes and visible only in the accounting.
"""

import asyncio
import hashlib
import random

import numpy as np
import pytest

from repro.core import MDTPPolicy, simulate
from repro.core.chunking import ChunkParams
from repro.core.scenarios import (
    flash_crowd_traces,
    paper_baseline,
    with_gray_degradation,
)
from repro.core.simulator import ServerSpec
from repro.transfer import (
    FaultPolicy,
    FleetModel,
    MDTPClient,
    RangeServer,
    Replica,
    Throttle,
    TransferIncompleteError,
    TransferJob,
    TransferManager,
)

MB = 1024 * 1024


def _sha(b) -> str:
    return hashlib.sha256(bytes(b)).hexdigest()


def _blob(size, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()


def _mirror(blob, throttle=None, faults=None):
    s = RangeServer(throttle=throttle, faults=faults).start()
    s.add_blob("/data", blob)
    return s


def _feed(fm, name, rate, n=1, tid="t"):
    """``n`` completed chunks served at ``rate`` bytes/s (1-second
    chunks, pipelined reading so no RTT correction applies)."""
    for _ in range(n):
        fm.observe_chunk(tid, name, int(rate), 1.0, rtt_included=False)


# --------------------------------------------------------------------------
# Replica probation (FleetModel unit)
# --------------------------------------------------------------------------


def test_slow_strikes_trip_probation():
    """``probation_strikes`` consecutive chunks far below the best
    trusted peer put a mirror on probation — the fast path for a gray
    mirror whose capacity EWMA is still coasting on its healthy past."""
    fm = FleetModel()
    _feed(fm, "a", 50 * MB, n=6)
    _feed(fm, "b", 45 * MB, n=4)          # trusted history, >= 4 chunks
    assert fm.probations == 0
    _feed(fm, "b", 1 * MB, n=fm.probation_strikes)
    assert fm.probations == 1
    assert fm.snapshot()["b"]["probation"] is True


def test_slow_strike_streak_resets_on_healthy_chunk():
    fm = FleetModel()
    _feed(fm, "a", 50 * MB, n=6)
    _feed(fm, "b", 45 * MB, n=4)
    _feed(fm, "b", 1 * MB, n=fm.probation_strikes - 1)
    _feed(fm, "b", 45 * MB)               # healthy chunk clears the streak
    _feed(fm, "b", 1 * MB, n=fm.probation_strikes - 1)
    assert fm.probations == 0


def test_probation_readmission_is_slow_start():
    """A probated mirror re-enters only after a clean streak of
    fast-probe chunks, at ``readmit_init`` of its fair share, and earns
    the rest back multiplicatively."""
    fm = FleetModel()
    _feed(fm, "a", 50 * MB, n=6)
    _feed(fm, "b", 45 * MB, n=4)
    _feed(fm, "b", 1 * MB, n=fm.probation_strikes)
    assert fm.snapshot()["b"]["probation"] is True
    _feed(fm, "b", 45 * MB, n=fm.probation_clean_streak)
    snap = fm.snapshot()["b"]
    assert snap["probation"] is False
    assert snap["readmit"] == pytest.approx(fm.readmit_init)
    _feed(fm, "b", 45 * MB)               # each clean chunk doubles it
    assert fm.snapshot()["b"]["readmit"] == pytest.approx(
        min(1.0, fm.readmit_init * 2.0))


def test_probation_slow_probes_do_not_readmit():
    """Clean is necessary but not sufficient: a mirror whose probe
    chunks still crawl stays parked however long the streak."""
    fm = FleetModel()
    _feed(fm, "a", 50 * MB, n=6)
    _feed(fm, "b", 45 * MB, n=4)
    _feed(fm, "b", 1 * MB, n=fm.probation_strikes)
    _feed(fm, "b", 1 * MB, n=3 * fm.probation_clean_streak)
    assert fm.snapshot()["b"]["probation"] is True


def test_single_replica_fleet_never_trips():
    """With nothing faster to shift toward, slowness is not a fault."""
    fm = FleetModel()
    _feed(fm, "solo", 1 * MB, n=20)
    assert fm.probations == 0


def test_corruption_decay_trips_probation():
    fm = FleetModel()
    for _ in range(5):                    # health 1.0 -> ~0.17 < 0.3
        fm.observe_corruption("bad")
    assert fm.snapshot()["bad"]["probation"] is True


def test_retry_storm_trips_probation_without_chunks():
    """A blackholed mirror that never completes a chunk still lands on
    probation once enough reconnects accumulate."""
    fm = FleetModel()
    for _ in range(fm.probation_retry_limit):
        fm.observe_retry("hole")
    assert fm.snapshot()["hole"]["probation"] is True


def test_probation_pins_allocation_at_probe_floor():
    fm = FleetModel()
    reps = [Replica("h1", 1, "/x"), Replica("h2", 2, "/x")]
    _feed(fm, reps[0].name, 50 * MB, n=6)
    _feed(fm, reps[1].name, 45 * MB, n=4)
    _feed(fm, reps[1].name, 1 * MB, n=fm.probation_strikes)
    view = fm.allocation_view("t2", reps, [40.0 * MB, 40.0 * MB])
    cap = fm.snapshot()[reps[1].name]["capacity"]
    assert view[1] == pytest.approx(cap * fm.probation_floor)
    assert view[0] > view[1]


def test_probation_disabled_never_trips():
    fm = FleetModel(probation=False)
    _feed(fm, "a", 50 * MB, n=6)
    _feed(fm, "b", 45 * MB, n=4)
    _feed(fm, "b", 1 * MB, n=20)
    assert fm.probations == 0


# --------------------------------------------------------------------------
# Admission control (manager, real sockets)
# --------------------------------------------------------------------------


def test_admission_gate_queues_excess_arrivals():
    blob = _blob(MB)
    servers = [_mirror(blob) for _ in range(2)]
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        mgr = TransferManager(
            replicas, params=ChunkParams(initial_chunk=128 * 1024,
                                         large_chunk=256 * 1024),
            max_active_transfers=1)
        results = mgr.run([TransferJob(size=len(blob)) for _ in range(3)])
        for buf, report in results:
            assert _sha(buf) == _sha(blob)
            assert report.total_bytes == len(blob)
        assert mgr.admission["admitted"] == 3
        assert mgr.admission["queued"] >= 2
        assert mgr.admission["wait_seconds"] > 0.0
    finally:
        for s in servers:
            s.stop()


def test_admission_shed_gives_degraded_service():
    """Arrivals past the shed depth run at trickle pace instead of
    waiting — bounded progress, and the bytes still verify."""
    blob = _blob(512 * 1024)
    servers = [_mirror(blob) for _ in range(2)]
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        mgr = TransferManager(
            replicas, params=ChunkParams(initial_chunk=128 * 1024,
                                         large_chunk=256 * 1024),
            max_active_transfers=1, shed_queue_depth=0,
            shed_trickle_bytes_per_s=64.0 * MB)
        results = mgr.run([TransferJob(size=len(blob)) for _ in range(3)])
        for buf, _ in results:
            assert _sha(buf) == _sha(blob)
        assert mgr.admission["shed"] >= 1
    finally:
        for s in servers:
            s.stop()


def test_srpt_queue_prefers_smallest_residual():
    """With one slot busy, the queued SMALL transfer finishes before the
    queued large one (smallest-remaining-processing-time order)."""
    blob = _blob(2 * MB)
    servers = [_mirror(blob, throttle=Throttle(bytes_per_s=8 * MB,
                                               deterministic=True))
               for _ in range(2)]
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        mgr = TransferManager(
            replicas, params=ChunkParams(initial_chunk=128 * 1024,
                                         large_chunk=256 * 1024),
            max_active_transfers=1)
        small = 256 * 1024
        mgr.run([
            TransferJob(size=len(blob)),                      # holds slot
            TransferJob(size=len(blob), start_delay=0.05),    # queued big
            TransferJob(size=small, start_delay=0.05),        # queued small
        ])
        sizes = [r.total_bytes for r in mgr.reports]
        assert sizes[0] == len(blob)
        assert sizes[1] == small          # small overtook the queued big
    finally:
        for s in servers:
            s.stop()


# --------------------------------------------------------------------------
# Hedged endgame (client, real sockets)
# --------------------------------------------------------------------------


def _gray_fetch(blob, degrade_to=1024, degrade_after=0.05):
    """One hedged transfer where the fast mirror silently starves
    mid-flight; returns (buf, report, servers' served-byte total)."""
    fast = _mirror(blob, throttle=Throttle(bytes_per_s=24 * MB,
                                           deterministic=True))
    slow = _mirror(blob, throttle=Throttle(bytes_per_s=8 * MB,
                                           deterministic=True))
    try:
        replicas = [Replica("127.0.0.1", fast.port, "/data"),
                    Replica("127.0.0.1", slow.port, "/data")]
        client = MDTPClient(
            replicas,
            params=ChunkParams(initial_chunk=128 * 1024,
                               large_chunk=256 * 1024),
            hedge_quantile=0.95, read_timeout=3.0)

        async def go():
            async def grayout():
                await asyncio.sleep(degrade_after)
                fast.set_throttle(Throttle(bytes_per_s=degrade_to,
                                           deterministic=True))
            task = asyncio.ensure_future(grayout())
            try:
                return await client.fetch(len(blob))
            finally:
                task.cancel()

        buf, report = asyncio.run(go())
        return buf, report, fast.served_bytes + slow.served_bytes
    finally:
        fast.stop()
        slow.stop()


def test_hedged_endgame_rescues_gray_straggler():
    """When the fast mirror silently starves, an endgame hedge must win
    the stuck range — and the duplicate bytes must be accounted, not
    silently double-credited."""
    blob = _blob(2 * MB, seed=3)
    buf, report, served = _gray_fetch(blob)
    assert _sha(buf) == _sha(blob)
    assert report.total_bytes == len(blob)     # no hedge over-credit
    assert report.hedges_issued >= 1
    assert report.hedges_won >= 1
    assert report.hedge_wasted_bytes >= 0


def test_hedge_waste_is_conserved_and_bounded():
    """The waste witness counts bytes that really crossed the wire twice
    (it can never exceed the servers' served-byte surplus), and the
    client's fractional budget bounds it at ``hedge_waste_frac * size``
    plus at most one exempted first range."""
    blob = _blob(2 * MB, seed=4)
    buf, report, served = _gray_fetch(blob)
    assert _sha(buf) == _sha(blob)
    assert report.hedge_wasted_bytes <= served - len(blob)
    cap = (MDTPClient([Replica("x", 1, "/")]).hedge_waste_frac * len(blob)
           + 256 * 1024)
    assert report.hedge_wasted_bytes <= cap


def test_clean_burst_wastes_no_hedge_byte():
    """Hedging is free on a clean fleet: a flash crowd of six arrivals
    through the robust manager (hedging and probation on, admission and
    a byte budget) over three healthy mirrors at 24, 8 and 8 MiB/s
    duplicates no byte, re-fetches no range and retries no connection.
    (Probation is not free here: slow strikes trip on healthy mirrors;
    ROADMAP.md, "Probation trips on a clean fleet".)"""
    blob = _blob(3 * MB, seed=29)
    servers = [_mirror(blob, throttle=Throttle(bytes_per_s=r * MB,
                                               deterministic=True))
               for r in (24, 8, 8)]
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        mgr = TransferManager(
            replicas, params=ChunkParams(initial_chunk=256 * 1024,
                                         large_chunk=MB),
            max_active_transfers=3, max_inflight_bytes=16 * MB)
        results = mgr.run([TransferJob(size=len(blob), start_delay=0.05 * j)
                           for j in range(6)])
        for buf, _ in results:
            assert _sha(buf) == _sha(blob)
        assert len(mgr.reports) == 6
        for r in mgr.reports:
            assert r.hedge_wasted_bytes == 0
            assert r.refetched_ranges == 0
            assert sum(r.retries_per_replica.values()) == 0
        assert sum(s.served_bytes for s in servers) == 6 * len(blob)
    finally:
        for s in servers:
            s.stop()


def test_hedging_disabled_reports_zero_witnesses():
    blob = _blob(MB, seed=5)
    servers = [_mirror(blob) for _ in range(2)]
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        client = MDTPClient(replicas, hedge_quantile=0.0)
        buf, report = asyncio.run(client.fetch(len(blob)))
        assert _sha(buf) == _sha(blob)
        assert report.hedges_issued == 0
        assert report.hedges_won == 0
        assert report.hedge_wasted_bytes == 0
    finally:
        for s in servers:
            s.stop()


def test_mixed_fault_incomplete_error_accounting():
    """Corruption, resets, and truncation on three distinct mirrors —
    with hedging enabled — must surface as the typed incomplete error
    with honest byte accounting, never a short or over-credited buffer."""
    blob = _blob(MB, seed=6)
    bad = [
        _mirror(blob, faults=FaultPolicy(corrupt_rate=1.0, seed=1)),
        _mirror(blob, faults=FaultPolicy(reset_rate=1.0, seed=2)),
        _mirror(blob, faults=FaultPolicy(truncate_rate=1.0, seed=3)),
    ]
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in bad]
        client = MDTPClient(
            replicas,
            params=ChunkParams(initial_chunk=128 * 1024,
                               large_chunk=256 * 1024),
            hedge_quantile=0.95, max_failures=2)
        with pytest.raises(TransferIncompleteError) as ei:
            asyncio.run(client.fetch(len(blob)))
        err = ei.value
        assert err.expected_bytes == len(blob)
        assert 0 <= err.done_bytes < len(blob)
        for r in replicas:
            assert r.name in err.failed_replicas
    finally:
        for s in bad:
            s.stop()


def test_seeded_backoff_rng_is_honored():
    """Chaos tests can pin reconnect-jitter: an injected seeded RNG is
    used as-is (and two equal seeds draw identical jitter streams)."""
    reps = [Replica("x", 1, "/")]
    c = MDTPClient(reps, rng=random.Random(7))
    twin = random.Random(7)
    assert [c._rng.random() for _ in range(4)] \
        == [twin.random() for _ in range(4)]
    assert MDTPClient(reps)._rng is random


# --------------------------------------------------------------------------
# Scenario + simulator mirrors
# --------------------------------------------------------------------------


def test_flash_crowd_traces_shapes():
    traces = {t.name: t for t in flash_crowd_traces()}
    assert set(traces) == {"burst", "diurnal", "gray-burst"}
    for t in traces.values():
        assert len(t.sizes) == len(t.arrivals)
        assert list(t.arrivals) == sorted(t.arrivals)
        assert all(s > 0 for s in t.sizes)
    grayed = [s for s in traces["gray-burst"].servers
              if s.degrade_factor != 1.0]
    assert len(grayed) == 1
    assert grayed[0].bandwidth == max(
        s.bandwidth for s in traces["gray-burst"].servers)
    assert not any(s.degrade_factor != 1.0 for s in traces["burst"].servers)


def test_with_gray_degradation_targets_one_replica():
    servers = paper_baseline(jitter=0.0)
    grayed = with_gray_degradation(servers, 1.5, 0.2, only=2)
    assert grayed[2].degrade_at == 1.5
    assert grayed[2].degrade_factor == 0.2
    for i, s in enumerate(grayed):
        if i != 2:
            assert s.degrade_factor == 1.0
    assert all(s.degrade_factor == 1.0 for s in servers)  # originals kept


def test_serverspec_gray_degradation_is_silent_and_permanent():
    spec = ServerSpec(name="s", bandwidth=100.0, degrade_at=1.0,
                      degrade_factor=0.25)
    assert spec.bandwidth_at(0.5) == 100.0
    assert spec.bandwidth_at(1.0) == 25.0
    assert spec.bandwidth_at(100.0) == 25.0
    assert 1.0 in spec.rate_boundaries()


def test_simulated_gray_fleet_pays_for_degradation():
    """The python simulator's gray mirror slows the transfer without
    breaking it — same seeds, same fleet, only ``degrade_at`` differs."""
    size = 64 * MB
    servers = paper_baseline(jitter=0.0)
    clean = simulate(MDTPPolicy(), servers, size, seed=0)
    gray = simulate(
        MDTPPolicy(),
        with_gray_degradation(servers, 0.5, 0.05,
                              only=int(np.argmax(
                                  [s.bandwidth for s in servers]))),
        size, seed=0)
    assert sum(clean.bytes_per_server) == size
    assert sum(gray.bytes_per_server) == size
    assert gray.total_time > clean.total_time
