"""Checkpoint manager: atomicity, GC, async save, multi-source restore,
elastic resharding."""

import asyncio
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.transfer import RangeServer, Replica, Throttle

MB = 1024 * 1024


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (64, 64)),
                   "b": jnp.arange(64, dtype=jnp.float32)},
        "opt": {"m": jnp.zeros((64, 64)), "step": jnp.float32(7)},
        "step": jnp.int32(42),
    }


def _trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_save_restore_roundtrip(tmp_path):
    state = _state()
    save_checkpoint(str(tmp_path), 100, state)
    restored, step = restore_checkpoint(str(tmp_path), state)
    assert step == 100
    assert _trees_equal(state, restored)


def test_incomplete_checkpoint_ignored(tmp_path):
    state = _state()
    save_checkpoint(str(tmp_path), 100, state)
    # simulate a crash: newer dir without manifest
    crashed = tmp_path / "step_0000000200"
    crashed.mkdir()
    (crashed / "data.bin").write_bytes(b"garbage")
    assert latest_step(str(tmp_path)) == 100
    restored, step = restore_checkpoint(str(tmp_path), state)
    assert step == 100


def test_manager_gc_keeps_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every_steps=10, keep=2,
                            async_save=False)
    state = _state()
    for step in (10, 20, 30, 40):
        assert mgr.maybe_save(step, state)
    assert not mgr.maybe_save(41, state)
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path)
                   if n.startswith("step_"))
    assert steps == [30, 40]


def test_async_save_completes(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every_steps=1, keep=1,
                            async_save=True)
    mgr.maybe_save(1, _state())
    mgr.wait()
    assert latest_step(str(tmp_path)) == 1


def test_multi_source_restore(tmp_path):
    """Restore via MDTP from three throttled mirrors; bytes identical."""
    state = {"params": {"w": jax.random.normal(jax.random.PRNGKey(1),
                                               (512, 512))},
             "step": jnp.int32(5)}
    d = save_checkpoint(str(tmp_path), 300, state)

    servers = []
    for bw in (20 * MB, 40 * MB, 80 * MB):
        s = RangeServer(throttle=Throttle(bytes_per_s=bw)).start()
        base = "/ckpt/step_0000000300"
        s.add_file(base + "/manifest.json", os.path.join(d, "manifest.json"))
        s.add_file(base + "/data.bin", os.path.join(d, "data.bin"))
        servers.append(s)
    try:
        replicas = [Replica("127.0.0.1", s.port, "/ckpt") for s in servers]
        restored, step = restore_checkpoint(
            str(tmp_path), state, step=300, replicas=replicas)
        assert step == 300
        assert _trees_equal(state, restored)
    finally:
        for s in servers:
            s.stop()


def test_multi_source_restore_survives_mirror_death(tmp_path):
    state = {"params": {"w": jnp.ones((1024, 1024), jnp.float32)},
             "step": jnp.int32(1)}
    d = save_checkpoint(str(tmp_path), 7, state)
    victim = RangeServer(throttle=Throttle(bytes_per_s=2 * MB)).start()
    healthy = RangeServer(throttle=Throttle(bytes_per_s=50 * MB)).start()
    for s in (victim, healthy):
        base = "/ckpt/step_0000000007"
        s.add_file(base + "/manifest.json", os.path.join(d, "manifest.json"))
        s.add_file(base + "/data.bin", os.path.join(d, "data.bin"))
    try:
        threading.Timer(0.1, victim.stop).start()
        replicas = [Replica("127.0.0.1", victim.port, "/ckpt"),
                    Replica("127.0.0.1", healthy.port, "/ckpt")]
        restored, step = restore_checkpoint(str(tmp_path), state, step=7,
                                            replicas=replicas)
        assert _trees_equal(state, restored)
    finally:
        healthy.stop()
        try:
            victim.stop()
        except Exception:
            pass


def test_streaming_restore_materializes_leaves_incrementally(tmp_path):
    """The replica restore path streams: each leaf is device_put the
    moment its byte range completes, out-of-order and split deliveries
    included — exercised directly against the sink."""
    from repro.checkpoint.manager import _StreamingRestore, _MANIFEST, _DATA

    state = {"a": jnp.arange(1000, dtype=jnp.float32),
             "b": jnp.ones((3, 7), jnp.int32),
             "c": jnp.float32(2.5)}
    d = save_checkpoint(str(tmp_path), 1, state)
    manifest = json.load(open(os.path.join(d, _MANIFEST)))
    blob = open(os.path.join(d, _DATA), "rb").read()

    stream = _StreamingRestore(manifest, state)
    with pytest.raises(IOError):
        stream.finish()                      # nothing delivered yet
    # deliver in reverse order, split mid-leaf and across leaf boundaries
    n = len(blob)
    cuts = [0, 100, 1000, 2500, n]
    pieces = [(cuts[i], blob[cuts[i]:cuts[i + 1]])
              for i in range(len(cuts) - 1)]
    for start, data in reversed(pieces):
        stream.sink(start, data)
    restored = stream.finish()
    assert _trees_equal(state, restored)


def test_streaming_restore_tolerates_overlapping_duplicates(tmp_path):
    """Overlapping / repeated range deliveries (a retried wave, a
    speculative re-fetch) must not double-count leaf bytes: countdowns
    stay exact, every leaf materializes exactly once, finish() succeeds."""
    from repro.checkpoint.manager import _StreamingRestore, _MANIFEST, _DATA

    state = {"a": jnp.arange(1000, dtype=jnp.float32),
             "b": jnp.ones((3, 7), jnp.int32),
             "c": jnp.float32(2.5)}
    d = save_checkpoint(str(tmp_path), 1, state)
    manifest = json.load(open(os.path.join(d, _MANIFEST)))
    blob = open(os.path.join(d, _DATA), "rb").read()
    n = len(blob)

    stream = _StreamingRestore(manifest, state)
    # exact duplicate of a mid-blob range, delivered twice
    stream.sink(100, blob[100:1000])
    stream.sink(100, blob[100:1000])
    # partial overlaps on both sides, one spanning a leaf boundary
    stream.sink(0, blob[0:500])
    stream.sink(800, blob[800:4020])
    # duplicate covering everything seen so far plus the tail
    stream.sink(0, blob)
    restored = stream.finish()
    assert _trees_equal(state, restored)
    assert stream.duplicate_bytes > 0
    # countdowns never went negative (finish() already proves == 0, but
    # assert the accounting is visible)
    assert all(r == 0 for r in stream._remaining)

    # zero-length and fully-duplicate deliveries after completion are no-ops
    stream.sink(0, b"")
    stream.sink(0, blob[0:64])
    assert _trees_equal(state, stream.finish())


def test_multi_source_restore_waves_retune(tmp_path):
    """Wave-split restore: the blob arrives in several offset fetches with
    a grid re-tune between waves; bytes still land exactly once each."""
    state = {"params": {"w": jax.random.normal(jax.random.PRNGKey(4),
                                               (512, 512)),
                        "b": jnp.arange(128, dtype=jnp.float32)},
             "step": jnp.int32(9)}
    d = save_checkpoint(str(tmp_path), 400, state)
    servers = []
    for bw in (30 * MB, 60 * MB):
        s = RangeServer(throttle=Throttle(bytes_per_s=bw)).start()
        base = "/ckpt/step_0000000400"
        s.add_file(base + "/manifest.json", os.path.join(d, "manifest.json"))
        s.add_file(base + "/data.bin", os.path.join(d, "data.bin"))
        servers.append(s)
    try:
        replicas = [Replica("127.0.0.1", s.port, "/ckpt") for s in servers]
        total = os.path.getsize(os.path.join(d, "data.bin"))
        restored, step = restore_checkpoint(
            str(tmp_path), state, step=400, replicas=replicas,
            wave_bytes=total // 3 + 1)
        assert step == 400
        assert _trees_equal(state, restored)
    finally:
        for s in servers:
            s.stop()


def test_multi_source_restore_waves_with_online_tuner(tmp_path):
    """An online tuner rides the wave loop via the client's telemetry
    hook; restore correctness is unaffected by mid-wave param swaps."""
    from repro.core.chunking import ChunkParams

    class ScriptedTuner:
        def __init__(self):
            self.calls = 0

        def update(self, t):
            self.calls += 1
            return ChunkParams(initial_chunk=64 * 1024,
                               large_chunk=256 * 1024)

    state = {"w": jax.random.normal(jax.random.PRNGKey(5), (700, 700))}
    d = save_checkpoint(str(tmp_path), 500, state)
    s = RangeServer(throttle=Throttle(bytes_per_s=50 * MB)).start()
    base = "/ckpt/step_0000000500"
    s.add_file(base + "/manifest.json", os.path.join(d, "manifest.json"))
    s.add_file(base + "/data.bin", os.path.join(d, "data.bin"))
    try:
        replicas = [Replica("127.0.0.1", s.port, "/ckpt")]
        total = os.path.getsize(os.path.join(d, "data.bin"))
        tuner = ScriptedTuner()
        restored, _ = restore_checkpoint(
            str(tmp_path), state, step=500, replicas=replicas,
            tuner=tuner, wave_bytes=total // 2 + 1)
        assert _trees_equal(state, restored)
        assert tuner.calls >= 1
    finally:
        s.stop()


def test_multi_source_restore_via_manager(tmp_path):
    """``restore_checkpoint(manager=...)`` rides the shared fleet: the
    manifest and data fetches run as managed transfers (per-replica
    in-flight caps enforced), telemetry lands in the fleet model, and the
    geometry the restore's between-wave re-tune adopts warm-starts the
    manager's next transfer."""
    from repro.core.chunking import ChunkParams
    from repro.transfer import TransferManager

    state = {"w": jax.random.normal(jax.random.PRNGKey(6), (600, 600))}
    d = save_checkpoint(str(tmp_path), 600, state)
    servers = []
    for bw in (30 * MB, 60 * MB):
        s = RangeServer(throttle=Throttle(bytes_per_s=bw,
                                          deterministic=True)).start()
        base = "/ckpt/step_0000000600"
        s.add_file(base + "/manifest.json", os.path.join(d, "manifest.json"))
        s.add_file(base + "/data.bin", os.path.join(d, "data.bin"))
        servers.append(s)
    try:
        replicas = [Replica("127.0.0.1", s.port, "/ckpt") for s in servers]
        start_params = ChunkParams(initial_chunk=128 * 1024,
                                   large_chunk=512 * 1024)
        mgr = TransferManager(replicas, params=start_params,
                              max_inflight_per_replica=1)
        total = os.path.getsize(os.path.join(d, "data.bin"))
        restored, step = restore_checkpoint(
            str(tmp_path), state, step=600, replicas=replicas,
            manager=mgr, wave_bytes=total // 2 + 1)
        assert step == 600
        assert _trees_equal(state, restored)
        # the fleet model observed both mirrors through the restore
        snap = mgr.snapshot()
        assert {r.name for r in replicas} <= set(snap)
        assert all(v["chunks"] > 0 for v in snap.values())
        # the cap held across the manifest + wave fetches
        for s in servers:
            assert s.peak_concurrent_requests <= 1
        # the between-wave grid re-tune's adoption persisted: the next
        # managed transfer would start from the re-tuned geometry
        assert mgr.params is not None
        assert mgr.params != start_params
    finally:
        for s in servers:
            s.stop()


def test_streaming_restore_respects_shardings(tmp_path):
    """Streamed leaves land with the requested sharding (the H2D overlap
    must not lose the placement contract)."""
    state = {"w": jnp.arange(16 * 16, dtype=jnp.float32).reshape(16, 16)}
    d = save_checkpoint(str(tmp_path), 2, state)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    shardings = {"w": jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", "model"))}

    from repro.checkpoint.manager import _StreamingRestore, _MANIFEST, _DATA
    manifest = json.load(open(os.path.join(d, _MANIFEST)))
    blob = open(os.path.join(d, _DATA), "rb").read()
    stream = _StreamingRestore(manifest, state, shardings)
    stream.sink(0, blob)
    restored = stream.finish()
    assert _trees_equal(state, restored)
    assert restored["w"].sharding.spec == jax.sharding.PartitionSpec(
        "data", "model")


def test_elastic_restore_resharding(tmp_path):
    """Restore with explicit target shardings (single-device 'mesh' here;
    the dry-run exercises the 512-device version of the same call)."""
    state = {"w": jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64)}
    save_checkpoint(str(tmp_path), 11, state)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    shardings = {"w": jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", "model"))}
    restored, _ = restore_checkpoint(str(tmp_path), state,
                                     shardings=shardings)
    assert _trees_equal(state, restored)
    assert restored["w"].sharding.spec == jax.sharding.PartitionSpec(
        "data", "model")


def test_wave_tuner_failure_warns_and_restore_completes(tmp_path):
    """A tuner that raises between restore waves keeps the bytes flowing
    (the wave loop keeps its geometry) and says so with a warning."""
    from repro.transfer import TunerFailureWarning

    class ExplodingTuner:
        def update(self, t):
            raise RuntimeError("sweep refused")

    state = {"w": jax.random.normal(jax.random.PRNGKey(8), (512, 512))}
    d = save_checkpoint(str(tmp_path), 700, state)
    s = RangeServer(throttle=Throttle(bytes_per_s=50 * MB)).start()
    base = "/ckpt/step_0000000700"
    s.add_file(base + "/manifest.json", os.path.join(d, "manifest.json"))
    s.add_file(base + "/data.bin", os.path.join(d, "data.bin"))
    try:
        total = os.path.getsize(os.path.join(d, "data.bin"))
        with pytest.warns(TunerFailureWarning, match="sweep refused"):
            restored, _ = restore_checkpoint(
                str(tmp_path), state, step=700,
                replicas=[Replica("127.0.0.1", s.port, "/ckpt")],
                tuner=ExplodingTuner(), wave_bytes=total // 2 + 1)
        assert _trees_equal(state, restored)
    finally:
        s.stop()


# ------------------------- the landing buffer, an untouched anonymous map

def _serve(d, step, rates):
    """One throttled ``RangeServer`` per rate, serving step ``step``."""
    servers = []
    for bw in rates:
        s = RangeServer(throttle=Throttle(bytes_per_s=bw)).start()
        base = f"/ckpt/step_{step:010d}"
        s.add_file(base + "/manifest.json", os.path.join(d, "manifest.json"))
        s.add_file(base + "/data.bin", os.path.join(d, "data.bin"))
        servers.append(s)
    return servers


def _landing_state():
    return {"params": {"w": jax.random.normal(jax.random.PRNGKey(12),
                                              (512, 512)),
                       "b": jnp.arange(4096, dtype=jnp.float32)},
            "emb": jax.random.normal(jax.random.PRNGKey(13), (256, 384)),
            "step": jnp.int32(3)}


def test_streamed_restore_into_the_untouched_map_is_byte_identical(
        tmp_path):
    """Three waves over two mirrors land bit-exact in the anonymous map,
    whose pages the receive faults in as it fills them."""
    from repro import obs

    state = _landing_state()
    d = save_checkpoint(str(tmp_path), 21, state)
    total = os.path.getsize(os.path.join(d, "data.bin"))
    servers = _serve(d, 21, (30 * MB, 60 * MB))
    try:
        replicas = [Replica("127.0.0.1", s.port, "/ckpt") for s in servers]
        with obs.recording() as rec:
            restored, _ = restore_checkpoint(
                str(tmp_path), state, step=21, replicas=replicas,
                wave_bytes=total // 3 + 1)
            restored = jax.block_until_ready(restored)
    finally:
        for s in servers:
            s.stop()
    assert _trees_equal(state, restored)
    [buf] = rec.find("mdtp.buffer")
    assert buf.attrs["bytes"] == total
    waves = rec.find("mdtp.wave")
    assert len(waves) == 3
    assert sum(w.attrs["bytes"] for w in waves) == total


@pytest.mark.parametrize("landing", ["memory", "spool"])
def test_landing_buffer_is_a_map_of_the_blob(tmp_path, landing):
    """In memory the blob lands in an anonymous private map, kept out of
    ``_mmap`` (the spool's, whose leaves are copied out); a spool lands
    in its file map as before.  Both give the same leaves."""
    import mmap

    from repro.checkpoint.manager import _DATA, _MANIFEST, _StreamingRestore

    state = _landing_state()
    d = save_checkpoint(str(tmp_path), 22, state)
    manifest = json.load(open(os.path.join(d, _MANIFEST)))
    blob = open(os.path.join(d, _DATA), "rb").read()
    spool = str(tmp_path / "spool.bin") if landing == "spool" else None
    stream = _StreamingRestore(manifest, state, spool_path=spool)
    assert isinstance(stream._buf, mmap.mmap)
    assert len(stream._buf) == len(blob)
    assert (stream._mmap is None) == (landing == "memory")
    stream.sink(0, blob)
    restored = jax.block_until_ready(stream.finish())
    assert _trees_equal(state, restored)
    stream.close()


def test_sharded_restore_lands_only_its_own_span(tmp_path):
    """A sharded restore fetches ``[lo, hi)`` of its host into the
    blob-sized map and restores the leaves of that span."""
    from repro.transfer.shard import manifest_boundaries, plan_shards

    state = _landing_state()
    d = save_checkpoint(str(tmp_path), 23, state)
    manifest = json.load(open(os.path.join(d, "manifest.json")))
    lo, hi = plan_shards(int(manifest["total_bytes"]), 3,
                         manifest_boundaries(manifest)).span_of(1)
    assert 0 < lo < hi < int(manifest["total_bytes"])
    [srv] = _serve(d, 23, (64 * MB,))
    try:
        half, rep = restore_checkpoint(
            str(tmp_path), state, step=23,
            replicas=[Replica("127.0.0.1", srv.port, "/ckpt")],
            shard_plan=(1, 3))
    finally:
        srv.stop()
    held = [(x, y) for x, y in zip(
        jax.tree.leaves(half, is_leaf=lambda x: x is None),
        jax.tree.leaves(state)) if x is not None]
    assert held
    assert sum(np.asarray(y).nbytes for _, y in held) == hi - lo
    for x, y in held:
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_uncommitted_range_keeps_its_leaf_unmaterialized(tmp_path):
    """Untouched pages read as zeros, never data: a leaf whose range was
    never committed stays unmaterialized, ``finish()`` refuses the
    restore, and the sharded contract returns it as None."""
    from repro.checkpoint.manager import _DATA, _MANIFEST, _StreamingRestore

    state = {"a": jnp.arange(1000, dtype=jnp.float32),
             "b": jnp.full((64, 64), 3, jnp.int32),
             "c": jnp.float32(2.5)}
    d = save_checkpoint(str(tmp_path), 25, state)
    manifest = json.load(open(os.path.join(d, _MANIFEST)))
    blob = open(os.path.join(d, _DATA), "rb").read()
    stream = _StreamingRestore(manifest, state)
    skip = next(e for e in manifest["leaves"] if e["key"] == "b")
    lo, hi = skip["offset"], skip["offset"] + skip["nbytes"]
    stream.sink(0, blob[:lo])
    stream.sink(hi, blob[hi:])
    with pytest.raises(IOError, match="restore incomplete"):
        stream.finish()
    partial = stream.finish(require_all=False)
    assert partial["b"] is None
    for k in ("a", "c"):
        assert np.array_equal(np.asarray(partial[k]), np.asarray(state[k]))
    stream.close()


# ----------------------------------------------- page reuse in the landing map

def _four_leaves(n=16384):
    """Four leaves of ``n`` float32s each (key order a, b, c, d), every
    one drawn apart so a leaf landed in another's pages shows."""
    return {k: jax.random.normal(jax.random.PRNGKey(30 + i), (n,))
            for i, k in enumerate("abcd")}


def _stream_of(tmp_path, state, step, **kw):
    from repro.checkpoint.manager import _DATA, _MANIFEST, _StreamingRestore

    d = save_checkpoint(str(tmp_path), step, state)
    manifest = json.load(open(os.path.join(d, _MANIFEST)))
    blob = open(os.path.join(d, _DATA), "rb").read()
    at = {e["key"]: (e["offset"], e["offset"] + e["nbytes"])
          for e in manifest["leaves"]}
    return _StreamingRestore(manifest, state, **kw), blob, at


def _land(stream, blob, lo, hi):
    """Deliver ``[lo, hi)`` as the transfer client does: receive into
    ``writable``, then ``commit``."""
    stream.writable(lo, hi - lo)[:] = blob[lo:hi]
    stream.commit(lo, hi - lo)


def test_waved_restore_lands_later_leaves_in_given_back_pages(tmp_path):
    """Three waves over two mirrors, leaves b and c straddling the wave
    boundaries: c and d land in the pages a and b gave back once on the
    device, and every leaf is bit-exact — on the CPU only because the
    landing copies a leaf before ``device_put`` aliases its pages."""
    from repro import obs

    state = {k: jax.random.normal(jax.random.PRNGKey(40 + i), (160, 1024))
             for i, k in enumerate("abcd")}
    d = save_checkpoint(str(tmp_path), 31, state)
    total = os.path.getsize(os.path.join(d, "data.bin"))
    servers = _serve(d, 31, (30 * MB, 60 * MB))
    try:
        replicas = [Replica("127.0.0.1", s.port, "/ckpt") for s in servers]
        with obs.recording() as rec:
            restored, _ = restore_checkpoint(
                str(tmp_path), state, step=31, replicas=replicas,
                wave_bytes=total // 3 + 1)
            restored = jax.block_until_ready(restored)
    finally:
        for s in servers:
            s.stop()
    assert _trees_equal(state, restored)
    [fin] = rec.find("mdtp.finish")
    assert fin.attrs["fresh_bytes"] < total
    assert fin.attrs["reused_bytes"] > 0
    assert fin.attrs["fresh_bytes"] + fin.attrs["reused_bytes"] == total


def test_late_delivery_of_a_retired_leaf_spares_its_pages_new_owner(
        tmp_path):
    """A view of a's range handed out before a landed pins a's pages
    until its commit; once free they go to c, and late or duplicate
    deliveries of a's range, written with garbage, never reach c."""
    state = _four_leaves()
    stream, blob, at = _stream_of(tmp_path, state, 32)
    (a0, a1), (b0, b1), (c0, c1) = at["a"], at["b"], at["c"]
    early = stream.writable(a0 + 4096, 4096)   # open before a lands
    _land(stream, blob, a0, a1)                # a on the device
    _land(stream, blob, b0, b1)                # a pinned: b takes fresh pages
    assert stream.reused_bytes == 0
    early[:] = b"\xff" * 4096
    stream.commit(a0 + 4096, 4096)             # a duplicate; unpins a
    _land(stream, blob, c0, c0 + 4096)         # c gets a's and b's pages
    assert stream.reused_bytes == c1 - c0
    stream.writable(a0, 8192)[:] = b"\xee" * 8192
    stream.commit(a0, 8192)
    stream.sink(b0 - 100, b"\xdd" * 200)
    _land(stream, blob, c0 + 4096, c1)
    _land(stream, blob, *at["d"])
    assert _trees_equal(state, jax.block_until_ready(stream.finish()))
    assert stream.duplicate_bytes == 4096 + 8192 + 200


def test_range_crossing_differently_placed_leaves_lands_through_scratch(
        tmp_path):
    """c takes a's given-back pages, b its own slot: a range across the
    b/c boundary goes through scratch and lands bit-exact in both."""
    state = _four_leaves()
    stream, blob, at = _stream_of(tmp_path, state, 33)
    (b0, b1), (c0, c1) = at["b"], at["c"]
    _land(stream, blob, *at["a"])
    _land(stream, blob, c0 + 4096, c0 + 8192)  # c in a's pages
    assert stream.reused_bytes == c1 - c0
    view = stream.writable(b1 - 4096, 8192)    # b lands at its own slot
    assert view.obj is not stream._buf
    view[:] = blob[b1 - 4096:b1 + 4096]
    stream.commit(b1 - 4096, 8192)
    assert stream.scratch_bytes == 8192
    _land(stream, blob, b0, b1 - 4096)
    _land(stream, blob, c0 + 8192, c1)
    _land(stream, blob, *at["d"])
    assert _trees_equal(state, jax.block_until_ready(stream.finish()))
    assert stream.fresh_bytes + stream.reused_bytes == len(blob)


@pytest.mark.parametrize("keep", ["resume", "mirror"])
def test_spool_and_mirrored_restores_keep_blob_offsets(tmp_path, keep):
    """A spool (its journal re-verifies CRCs at blob offsets) and a
    restore a peer mirror serves keep every leaf at its blob offset: no
    page reused, and the mirror serves the blob's bytes after the leaves
    are on the device."""
    from repro import obs
    from repro.transfer import MDTPClient, PeerMirror

    state = _four_leaves(65536)
    d = save_checkpoint(str(tmp_path), 34, state)
    blob = open(os.path.join(d, "data.bin"), "rb").read()
    servers = _serve(d, 34, (64 * MB,))
    mirror = PeerMirror() if keep == "mirror" else None
    kw = ({"mirror": mirror} if mirror is not None
          else {"resume": str(tmp_path / "scratch")})
    try:
        with obs.recording() as rec:
            restored, _ = restore_checkpoint(
                str(tmp_path), state, step=34,
                replicas=[Replica("127.0.0.1", servers[0].port, "/ckpt")],
                wave_bytes=len(blob) // 4 + 1, **kw)
            restored = jax.block_until_ready(restored)
        assert _trees_equal(state, restored)
        if mirror is not None:
            served, _ = asyncio.run(
                MDTPClient([mirror.replica]).fetch(len(blob)))
            assert bytes(served) == blob
    finally:
        servers[0].stop()
        if mirror is not None:
            mirror.stop()
    [fin] = rec.find("mdtp.finish")
    assert fin.attrs["reused_bytes"] == 0
    assert fin.attrs["scratch_bytes"] == 0
    assert fin.attrs["fresh_bytes"] == len(blob)


def test_mirrored_stream_still_mounts_and_a_reusing_one_refuses(tmp_path):
    """``PeerMirror.bind`` mounts ``writable(0, total)`` as the blob: a
    stream that recycles pages says it cannot back a mirror."""
    from repro.transfer import PeerMirror

    state = _four_leaves(1024)
    stream, _, _ = _stream_of(tmp_path, state, 35)
    with pytest.raises(ValueError, match="cannot back a mirror"):
        PeerMirror(stream)
    mirrored, _, _ = _stream_of(tmp_path, state, 36, mirrored=True)
    PeerMirror(mirrored).stop()


def test_pages_of_a_leaf_whose_copy_is_in_flight_are_not_reused(
        tmp_path, monkeypatch):
    """While a's device array is not ready its pages stay a's: c takes
    fresh pages.  Once ready, the next leaf placed takes them."""
    state = _four_leaves()
    stream, blob, at = _stream_of(tmp_path, state, 37)
    _land(stream, blob, *at["a"])
    array_type = type(stream._out[0])
    monkeypatch.setattr(array_type, "is_ready", lambda self: False)
    _land(stream, blob, *at["c"])
    assert stream.reused_bytes == 0
    monkeypatch.undo()
    _land(stream, blob, *at["b"])
    assert stream.reused_bytes == at["b"][1] - at["b"][0]
    _land(stream, blob, *at["d"])
    assert _trees_equal(state, jax.block_until_ready(stream.finish()))


# -------------------------------------------- chip_smoke.py, rehearsed on CPU

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _chip_smoke():
    """``chip_smoke.py`` (repo root, not a package) as a module."""
    import importlib.util
    import sys

    mod = sys.modules.get("chip_smoke")
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = mod
        spec.loader.exec_module(mod)
    return mod


def _smoke_cfg(**kw):
    """Qwen3's reduced config with a vocab wide enough (about 50 MB of
    bf16) that each of four waves spans several default-size chunks, so
    every mirror serves data and the stopped one dies mid-range."""
    from repro.configs.qwen3_1_7b import reduced
    return reduced().replace(vocab_size=393216, **kw)


def test_chip_smoke_rehearsal_bf16_restore():
    """The smoke's own restore at reduced width on the CPU: a bf16
    ``init_params`` tree served by three throttled mirrors, the slowest
    stopped mid-restore, grid retunes between waves, every leaf bit-exact
    against ``data.bin`` and against the original, and the sweep's argmin
    agreeing across placements."""
    smoke = _chip_smoke()
    seen = {}
    res = smoke.smoke(_smoke_cfg(), seed=3, rates=[8 * MB, 16 * MB, 24 * MB],
                      rtt=0.03, emit=seen.__setitem__)
    run = res["restore"]
    assert run.stopped == 0
    assert all(b > 0 for b in run.served), run.served
    assert len(run.retunes) >= 2 and not run.tuner_failures
    assert res["shards"] == 13                 # one per leaf, one device
    assert res["rel"] == 0.0
    assert seen["tuner_choice_device_cpu"]


def test_chip_smoke_main_refuses_without_tpu():
    """The smoke never falls back to the CPU."""
    with pytest.raises(SystemExit) as e:
        _chip_smoke().main([])
    assert "no TPU" in str(e.value.code)


_SHARDED_SMOKE = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {tests!r})
from test_checkpoint import MB, _chip_smoke, _smoke_cfg
from repro.launch.mesh import make_local_mesh
res = _chip_smoke().smoke(
    _smoke_cfg(n_kv_heads=4), seed=5, rates=[8 * MB, 16 * MB, 24 * MB],
    rtt=0.03, mesh=make_local_mesh(data=1, model=4), emit=lambda *a: None)
assert res["split"] == 8 and res["shards"] == 13 * 4, res
assert res["restore"].stopped == 0
print("SHARDED_OK")
"""


def test_chip_smoke_rehearsal_sharded_four_devices():
    """``--chips 4``'s path on four virtual CPU devices (a subprocess: the
    device count is fixed when JAX starts): heads, mlp and vocab split 4
    ways, norms replicated, every shard bit-exact against ``data.bin``."""
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    prog = _SHARDED_SMOKE.format(tests=os.path.dirname(__file__))
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "SHARDED_OK" in res.stdout
