"""Online tuning subsystem: MC-gradient tuner, bandit, client/restore wiring.

Contracts under test (see ``repro.core.online``):

* ``tune_chunk_params_mcgrad`` shares the grad tuner's never-worse-than-
  init guarantee on the exact metric, and its compiled value-and-grad is
  cached across file sizes (an online tuner re-plans every wave without
  recompiling the scan core);
* ``BanditTuner`` seeds its arms from the fused grid winner, explores
  every arm, exploits the measured-reward best, and resets confidence on
  bandwidth/RTT drift or replica death;
* ``MDTPClient.fetch(tuner=...)`` feeds live telemetry mid-transfer and
  adopts returned params (``report.retunes``); ``restore_checkpoint``
  re-tunes between waves and the wave/offset plumbing delivers exact
  bytes.
"""

import asyncio
import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.autotune import autotune_chunk_params  # noqa: E402
from repro.core.chunking import ChunkParams  # noqa: E402
from repro.core.online import (  # noqa: E402
    BanditTuner,
    GridTuner,
    MCGradTuner,
    Telemetry,
    _mc_value_and_grad,
    rtt_corrected_bandwidth,
    tune_chunk_params_mcgrad,
)
from repro.transfer import RangeServer, Replica, Throttle  # noqa: E402
from repro.transfer.client import MDTPClient, TunerFailureWarning  # noqa: E402

MB = 1024 * 1024
GB = 1024 * MB

BW = [50.0 * MB, 30.0 * MB, 10.0 * MB, 80.0 * MB]


def _tel(bw, rtt=0.03, remaining=512 * MB, throughput=0.0, elapsed=0.0):
    n = len(bw)
    rtt = (rtt,) * n if isinstance(rtt, float) else tuple(rtt)
    return Telemetry(bandwidth=tuple(bw), rtt=rtt,
                     remaining_bytes=float(remaining),
                     measured_throughput=float(throughput), elapsed=elapsed)


# -- Telemetry / estimator-correction helpers -------------------------------

def test_telemetry_live_filters_dead_and_fills_rtt():
    t = _tel([50.0 * MB, 0.0, 10.0 * MB], rtt=(0.25, 0.0, 0.0))
    bw, rtts = t.live(default_rtt=0.07)
    assert bw == [50.0 * MB, 10.0 * MB]
    assert rtts == [0.25, 0.07]          # dead slot dropped, gap filled


def test_rtt_corrected_bandwidth_inverts_estimator_bias():
    """est = s/(rtt + s/bw)  ==>  correction recovers bw exactly."""
    for bw, rtt, s in [(70 * MB, 0.5, 40 * MB), (12 * MB, 0.03, 2 * MB)]:
        est = s / (rtt + s / bw)
        assert est < bw                                  # bias is real
        assert rtt_corrected_bandwidth(est, rtt, s) == pytest.approx(
            bw, rel=1e-6)
    # impossible corrections pass the reading through unchanged
    assert rtt_corrected_bandwidth(5.0, 0.0, 1 * MB) == 5.0
    assert rtt_corrected_bandwidth(5.0, 0.5, 0.0) == 5.0
    assert rtt_corrected_bandwidth(0.0, 0.5, 1 * MB) == 0.0
    # implied non-positive wire time (reading faster than RTT allows)
    assert rtt_corrected_bandwidth(10 * MB, 1.0, 1 * MB) == 10 * MB


def test_telemetry_from_report_passes_wire_rates_through():
    """Regression: ``observed_throughputs`` are wire rates (the client
    strips the per-request RTT bias at the observation point), so the
    wave-boundary ``Telemetry.from_report`` path must NOT correct them a
    second time — only zero failed slots and carry the measured RTTs."""
    from repro.transfer.client import Replica, TransferReport

    replicas = [Replica("h0", 1, "/b"), Replica("h1", 2, "/b"),
                Replica("h2", 3, "/b")]
    wire, rtt = 70.0 * MB, 0.5
    report = TransferReport(
        total_bytes=1, elapsed=2.0,
        bytes_per_replica={"h0:1": 160 * MB, "h1:2": 8 * MB,
                           "h2:3": MB},
        requests_per_replica={"h0:1": 4, "h1:2": 2, "h2:3": 1},
        failed_replicas=["h2:3"], refetched_ranges=0,
        observed_throughputs={"h0:1": wire, "h1:2": 20.0 * MB,
                              "h2:3": 5.0 * MB},
        observed_rtts={"h0:1": rtt, "h1:2": 0.0, "h2:3": 0.02})
    tel = Telemetry.from_report(report, replicas, remaining_bytes=64 * MB)
    assert tel.bandwidth[0] == wire            # as-is (already de-biased;
    # a second rtt_corrected_bandwidth pass would inflate it past wire)
    assert tel.bandwidth[1] == 20.0 * MB
    assert tel.bandwidth[2] == 0.0             # failed slot preserved
    assert tel.rtt == (rtt, 0.0, 0.02)
    assert tel.remaining_bytes == 64 * MB


# -- MC-gradient tuner ------------------------------------------------------

def test_mcgrad_never_worse_than_grid_init():
    grid = [(2 * MB, 20 * MB), (4 * MB, 40 * MB), (8 * MB, 80 * MB)]
    seed = autotune_chunk_params(BW, 0.03, 512 * MB, grid=grid)
    res = tune_chunk_params_mcgrad(
        BW, 0.03, 512 * MB,
        init=(seed.params.initial_chunk, seed.params.large_chunk),
        steps=6, n_seeds=2, max_rounds=256)
    assert res.steps == 6
    assert all(np.isfinite(t) for t in res.loss_history)
    assert np.all(np.isfinite(res.final_grad))
    # exact-metric guarantee: adopted params no slower than the init
    from repro.core.jax_sim import SimConfig, simulate_transfer
    t_init = float(simulate_transfer(
        BW, 0.03, 512 * MB, seed.params, config=SimConfig(),
        engine="round").total_time)
    assert res.predicted_time <= t_init + 1e-6


def test_mcgrad_compiled_loss_cached_across_file_sizes():
    """File size and z-floors are traced args of the cached value-and-grad:
    re-planning for a different remaining byte count must reuse the same
    compiled executable (same lru entry, no scan-core retrace)."""
    _mc_value_and_grad.cache_clear()
    tune_chunk_params_mcgrad(BW, 0.03, 256 * MB, init=(4 * MB, 40 * MB),
                             steps=2, n_seeds=2, max_rounds=128)
    assert _mc_value_and_grad.cache_info().misses == 1
    tune_chunk_params_mcgrad(BW, 0.03, 200 * MB, init=(4 * MB, 40 * MB),
                             steps=2, n_seeds=2, max_rounds=128)
    info = _mc_value_and_grad.cache_info()
    assert info.misses == 1 and info.hits >= 1


def test_mcgrad_tuner_update_adopts_and_warm_starts():
    tun = MCGradTuner(steps=4, n_seeds=2, max_rounds=128)
    assert tun.update(_tel([0.0, 0.0])) is None           # nothing live
    p = tun.update(_tel(BW, remaining=256 * MB))
    assert isinstance(p, ChunkParams)
    assert tun.params == p and tun.updates == 1
    p2 = tun.update(_tel(BW, remaining=200 * MB))
    assert isinstance(p2, ChunkParams)                    # warm-started


# -- bandit -----------------------------------------------------------------

def test_bandit_seeds_arms_from_grid_winner():
    grid = [(2 * MB, 20 * MB), (4 * MB, 40 * MB), (8 * MB, 80 * MB),
            (16 * MB, 160 * MB)]
    tun = BanditTuner(n_arms=3, grid=grid)
    p = tun.update(_tel(BW))
    expect = autotune_chunk_params(BW, [0.03] * 4, 512 * MB, grid=grid)
    assert p == expect.params                 # arm 0 == the grid winner
    assert len(tun.arms) == 3
    # arms are distinct grid points ranked by predicted time
    assert len({(a.params.initial_chunk, a.params.large_chunk)
                for a in tun.arms}) == 3


def test_bandit_explores_then_exploits_measured_best():
    grid = [(2 * MB, 20 * MB), (4 * MB, 40 * MB), (8 * MB, 80 * MB)]
    tun = BanditTuner(n_arms=3, grid=grid, gamma=1.0, explore=0.05)
    tun.update(_tel(BW))                       # seed; plays arm 0
    # reward schedule: arm 0 mediocre, arm 1 great, arm 2 poor
    rewards = {0: 0.5, 1: 0.95, 2: 0.1}
    played = []
    for _ in range(8):
        idx = tun._current
        played.append(idx)
        tun.update(_tel(BW, throughput=rewards[idx] * sum(BW)))
    assert set(played[:3]) == {0, 1, 2}        # every arm tried once
    assert played[-1] == 1                     # converges on measured best
    assert tun.params == tun.arms[1].params


def test_bandit_drift_resets_on_throttle_death_and_latency():
    for mutate in (
        lambda bw, rtt: (tuple(b * 0.2 if i == 3 else b                # throttle
                               for i, b in enumerate(bw)), rtt),
        lambda bw, rtt: (tuple(0.0 if i == 3 else b                    # death
                               for i, b in enumerate(bw)), rtt),
        lambda bw, rtt: (bw, tuple(r + 0.5 for r in rtt)),             # latency
    ):
        tun = BanditTuner(n_arms=2)
        tun.update(_tel(BW))
        assert tun.drift_resets == 0
        bw2, rtt2 = mutate(tuple(BW), (0.03,) * 4)
        p = tun.update(Telemetry(bw2, rtt2, 256 * MB,
                                 measured_throughput=50 * MB))
        assert tun.drift_resets == 1
        assert p is not None
        # confidence was zeroed: every arm unplayed again
        assert all(a.n == 0.0 for a in tun.arms)


def test_bandit_steady_fleet_does_not_reset():
    tun = BanditTuner(n_arms=2, drift_threshold=0.6)
    tun.update(_tel(BW))
    # 20% wobble is below the 60% drift threshold
    wobble = tuple(b * 1.2 for b in BW)
    tun.update(_tel(wobble, throughput=60 * MB))
    assert tun.drift_resets == 0


def test_grid_tuner_tracks_fused_sweep():
    tun = GridTuner()
    p = tun.update(_tel(BW, remaining=256 * MB))
    expect = autotune_chunk_params(BW, [0.03] * 4, 256 * MB)
    assert p == expect.params
    assert tun.update(_tel([0.0] * 4)) is None


# -- client wiring ----------------------------------------------------------

class _ScriptedTuner:
    """Deterministic stand-in: records telemetry, returns a fixed param."""

    def __init__(self, params):
        self.params = params
        self.seen = []

    def update(self, t):
        self.seen.append(t)
        return self.params


def _mirrors(blob, rates):
    servers = []
    for r in rates:
        s = RangeServer(throttle=Throttle(bytes_per_s=r)).start()
        s.add_blob("/data", blob)
        servers.append(s)
    return servers


def test_fetch_tuner_hook_adopts_params_and_reports_retunes():
    rng = np.random.default_rng(1)
    blob = rng.integers(0, 256, size=6 * MB, dtype=np.uint8).tobytes()
    servers = _mirrors(blob, [40 * MB, 80 * MB])
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        adopted = ChunkParams(initial_chunk=128 * 1024, large_chunk=512 * 1024)
        tuner = _ScriptedTuner(adopted)
        client = MDTPClient(replicas,
                            params=ChunkParams(256 * 1024, MB))
        buf, report = asyncio.run(client.fetch(
            len(blob), tuner=tuner, tune_interval_bytes=MB))
        assert hashlib.sha256(bytes(buf)).digest() == \
            hashlib.sha256(blob).digest()
        assert report.retunes >= 1
        assert len(tuner.seen) >= 1
        tel = tuner.seen[0]
        # live telemetry: positional vectors over the full replica set,
        # measured window throughput, true remaining count
        assert len(tel.bandwidth) == 2 and len(tel.rtt) == 2
        assert any(b > 0 for b in tel.bandwidth)
        assert tel.measured_throughput > 0
        assert 0 <= tel.remaining_bytes < len(blob)
        # adoption persists for the next transfer
        assert client._params_arg == adopted
    finally:
        for s in servers:
            s.stop()


def test_fetch_tuner_without_adoption_leaves_params_unpinned():
    """A tuner that declines every update (returns None) must not pin this
    transfer's size-derived default params onto subsequent transfers."""
    rng = np.random.default_rng(4)
    blob = rng.integers(0, 256, size=4 * MB, dtype=np.uint8).tobytes()
    servers = _mirrors(blob, [80 * MB])
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]

        class DeclineTuner:
            def update(self, t):
                return None

        client = MDTPClient(replicas, tuner=DeclineTuner())
        buf, report = asyncio.run(client.fetch(
            len(blob), tune_interval_bytes=MB))
        assert bytes(buf) == blob
        assert report.retunes == 0
        assert client._params_arg is None
    finally:
        for s in servers:
            s.stop()


def test_fetch_tuner_exception_does_not_fail_transfer():
    """A tuner that raises (bad jit compile, tuner bug) must not fail a
    transfer whose bytes are flowing fine."""
    rng = np.random.default_rng(5)
    blob = rng.integers(0, 256, size=4 * MB, dtype=np.uint8).tobytes()
    servers = _mirrors(blob, [80 * MB])
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]

        class ExplodingTuner:
            def update(self, t):
                raise RuntimeError("tuner boom")

        client = MDTPClient(replicas, tuner=ExplodingTuner())
        with pytest.warns(TunerFailureWarning, match="tuner boom"):
            buf, report = asyncio.run(client.fetch(
                len(blob), tune_interval_bytes=MB))
        assert bytes(buf) == blob
        assert report.retunes == 0
        assert report.tuner_errors == 1
        assert "tuner boom" in report.tuner_error
    finally:
        for s in servers:
            s.stop()


def test_fetch_offset_requests_shifted_window():
    rng = np.random.default_rng(2)
    blob = rng.integers(0, 256, size=4 * MB, dtype=np.uint8).tobytes()
    servers = _mirrors(blob, [80 * MB])
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        client = MDTPClient(replicas, params=ChunkParams(256 * 1024, MB))
        buf, _ = asyncio.run(client.fetch(2 * MB, offset=1 * MB))
        assert bytes(buf) == blob[1 * MB:3 * MB]
    finally:
        for s in servers:
            s.stop()


def test_fetch_without_tuner_unchanged():
    rng = np.random.default_rng(3)
    blob = rng.integers(0, 256, size=2 * MB, dtype=np.uint8).tobytes()
    servers = _mirrors(blob, [80 * MB])
    try:
        replicas = [Replica("127.0.0.1", s.port, "/data") for s in servers]
        client = MDTPClient(replicas, params=ChunkParams(256 * 1024, MB))
        buf, report = asyncio.run(client.fetch(len(blob)))
        assert bytes(buf) == blob
        assert report.retunes == 0
    finally:
        for s in servers:
            s.stop()
