"""The in-program recorder (``repro.obs``): off, it costs a shared no-op
and records nothing; on, spans nest with their intervals and attributes,
fetches attach to the span they ran in, and an active JAX profiler trace
holds the spans on its host plane."""

from __future__ import annotations

import subprocess
import sys
import time
from pathlib import Path

import pytest

from repro import obs


def test_off_span_is_the_shared_no_op_and_nothing_is_recorded():
    assert obs.current() is None
    a, b = obs.span("mdtp.restore"), obs.span("mdtp.wave", wave=0)
    assert a is b
    with a as entered:
        assert entered is a
    with obs.recording() as rec:
        pass
    assert rec.spans == [] and rec.fetches == []
    assert obs.current() is None


def test_on_spans_nest_with_intervals_and_attributes():
    with obs.recording() as rec:
        assert obs.current() is rec
        with obs.span("mdtp.restore", step=7) as restore:
            for k in range(2):
                with obs.span("mdtp.wave", wave=k, bytes=100 + k):
                    f = rec.fetch(["a:1", "b:2"], params="C,L")
                    with obs.span("mdtp.device_put", leaf=f"w{k}"):
                        time.sleep(0.002)
            with obs.span("mdtp.finish"):
                pass
    assert obs.current() is None
    assert rec.spans == [restore]
    assert restore.attrs == {"step": 7}
    assert [c.name for c in restore.children] == \
        ["mdtp.wave", "mdtp.wave", "mdtp.finish"]
    waves = rec.find("mdtp.wave")
    assert [w.attrs for w in waves] == [{"wave": 0, "bytes": 100},
                                        {"wave": 1, "bytes": 101}]
    assert [w.fetch for w in waves] == rec.fetches
    assert [r.name for r in waves[0].fetch.replicas] == ["a:1", "b:2"]
    assert waves[1].fetch.params == "C,L"
    puts = restore.find("mdtp.device_put")
    assert [p.attrs["leaf"] for p in puts] == ["w0", "w1"]
    for outer, inner in [(restore, waves[0]), (waves[0], puts[0]),
                         (waves[1], puts[1])]:
        assert outer.start <= inner.start <= inner.end <= outer.end
    assert all(p.seconds >= 0.002 for p in puts)
    assert waves[0].end <= waves[1].start


def test_a_fetch_outside_any_span_is_kept_by_the_record():
    with obs.recording() as rec:
        f = rec.fetch(["a:1"])
    assert rec.fetches == [f] and rec.spans == []


def test_in_flight_time_counts_only_between_zero_and_one_outstanding():
    r = obs.ReplicaStats("a:1")
    r.assigned()
    r.assigned()                  # a second range: no new interval
    time.sleep(0.01)
    r.committed(0.001, 0.004)
    assert r.outstanding == 1 and r.inflight_s == 0.0
    r.settled()                   # given back: the interval closes
    assert r.outstanding == 0 and r.inflight_s >= 0.01
    assert r.first_commit is not None and r.first_commit == r.last_commit
    assert (r.header_wait_s, r.body_read_s) == (0.001, 0.004)
    f = obs.Fetch(start=r.first_commit - 1.0, replicas=[r],
                  end=r.first_commit)
    assert r.alive_s(f) == pytest.approx(1.0)
    r.failed_at = f.start + 0.25
    assert r.alive_s(f) == pytest.approx(0.25)


def test_recording_does_not_nest():
    with obs.recording():
        with pytest.raises(RuntimeError):
            with obs.recording():
                pass
    assert obs.current() is None


def test_importing_obs_loads_no_jax():
    code = ("import sys; import repro.obs; "
            "print(any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules))")
    src = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={"PYTHONPATH": src, "PATH": ""})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_a_restore_records_its_landing_buffer(tmp_path):
    """``mdtp.buffer`` names the landing buffer's set-up, between the
    manifest and the first wave, with the blob's size."""
    import os

    import jax
    import jax.numpy as jnp

    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.transfer import RangeServer, Replica, Throttle

    state = {"w": jax.random.normal(jax.random.PRNGKey(0), (512, 512)),
             "v": jnp.arange(2048, dtype=jnp.float32)}
    d = save_checkpoint(str(tmp_path), 5, state)
    total = os.path.getsize(os.path.join(d, "data.bin"))
    srv = RangeServer(throttle=Throttle(bytes_per_s=40 << 20)).start()
    for name in ("manifest.json", "data.bin"):
        srv.add_file(f"/ckpt/step_0000000005/{name}", os.path.join(d, name))
    try:
        with obs.recording() as rec:
            restore_checkpoint(str(tmp_path), state, step=5,
                               replicas=[Replica("127.0.0.1", srv.port,
                                                 "/ckpt")],
                               wave_bytes=total // 4 + 1)
    finally:
        srv.stop()
    [restore] = rec.find("mdtp.restore")
    assert [c.name for c in restore.children][:3] == \
        ["mdtp.manifest", "mdtp.buffer", "mdtp.wave"]
    [buf] = restore.find("mdtp.buffer")
    assert buf.attrs == {"bytes": total}
    waves = restore.find("mdtp.wave")
    assert len(waves) == 4
    assert sum(w.attrs["bytes"] for w in waves) == total


@pytest.mark.parametrize("landing", ["memory", "resume"])
def test_a_restore_records_its_landing_pages(tmp_path, landing):
    """``mdtp.finish`` carries how the leaves landed: in fresh pages, in
    pages earlier leaves gave back (none for a spool, which keeps blob
    offsets), and through scratch; fresh and reused add up to the
    blob."""
    import os

    import jax

    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.transfer import RangeServer, Replica, Throttle

    state = {k: jax.random.normal(jax.random.PRNGKey(i), (128, 1024))
             for i, k in enumerate("abcd")}
    d = save_checkpoint(str(tmp_path), 6, state)
    total = os.path.getsize(os.path.join(d, "data.bin"))
    srv = RangeServer(throttle=Throttle(bytes_per_s=40 << 20)).start()
    for name in ("manifest.json", "data.bin"):
        srv.add_file(f"/ckpt/step_0000000006/{name}", os.path.join(d, name))
    kw = {"resume": str(tmp_path / "scratch")} if landing == "resume" else {}
    try:
        with obs.recording() as rec:
            restore_checkpoint(str(tmp_path), state, step=6,
                               replicas=[Replica("127.0.0.1", srv.port,
                                                 "/ckpt")],
                               wave_bytes=total // 4 + 1, **kw)
    finally:
        srv.stop()
    [fin] = rec.find("mdtp.finish")
    assert set(fin.attrs) == {"fresh_bytes", "reused_bytes", "scratch_bytes"}
    assert fin.attrs["fresh_bytes"] + fin.attrs["reused_bytes"] == total
    assert 0 <= fin.attrs["scratch_bytes"] <= total
    if landing == "resume":
        assert fin.attrs["reused_bytes"] == 0
        assert fin.attrs["scratch_bytes"] == 0


def test_an_active_profiler_trace_holds_the_spans_on_the_host_plane(
        tmp_path):
    """The pattern of the chip benchmark's CPU trace test: record a trace
    with spans inside, read it back, find them on the host plane with the
    nesting and lengths the record has."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    d = str(tmp_path)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with obs.recording() as rec:
            with obs.span("mdtp.restore"):
                with obs.span("mdtp.wave", wave=0, bytes=123):
                    time.sleep(0.05)
                with obs.span("mdtp.device_put", leaf="w"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = next(Path(d).rglob("*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mdtp."):
                    found[ev.name] = (plane.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns)
    assert set(found) == {"mdtp.restore", "mdtp.wave", "mdtp.device_put"}
    assert all(p.startswith("/host:") for p, _, _ in found.values())
    _, r0, r1 = found["mdtp.restore"]
    for name in ("mdtp.wave", "mdtp.device_put"):
        _, s0, s1 = found[name]
        assert r0 <= s0 <= s1 <= r1
    for s in rec.find("mdtp.wave") + rec.find("mdtp.restore"):
        _, s0, s1 = found[s.name]
        assert (s1 - s0) / 1e9 == pytest.approx(s.seconds, abs=2e-3)


_PLACEMENT = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np
from repro import obs
from repro.checkpoint import save_checkpoint
from repro.checkpoint.manager import _StreamingRestore
mesh = Mesh(np.array(jax.devices()).reshape(1, 4), ("data", "model"))
state = {"strided": jnp.arange(64, dtype=jnp.float32).reshape(2, 8, 4),
         "contiguous": jnp.arange(32, dtype=jnp.float32).reshape(8, 4),
         "replicated": jnp.arange(16, dtype=jnp.float32)}
shardings = {"strided": NamedSharding(mesh, P(None, "model")),
             "contiguous": NamedSharding(mesh, P("model")),
             "replicated": NamedSharding(mesh, P())}
d = save_checkpoint(sys.argv[1], 1, state)
manifest = json.load(open(os.path.join(d, "manifest.json")))
blob = open(os.path.join(d, "data.bin"), "rb").read()
inspected = []
placement = obs.placement
obs.placement = lambda *a: inspected.append(a) or placement(*a)

def land():
    stream = _StreamingRestore(manifest, state, shardings)
    stream.writable(0, len(blob))[:] = blob
    stream.commit(0, len(blob))
    return jax.block_until_ready(stream.finish())

land()
off = len(inspected)
with obs.recording() as rec:
    out = land()
assert all(np.array_equal(out[k], state[k]) for k in state)
print("PUTS", json.dumps({"inspected_off": off, "spans": {
    s.attrs["leaf"]: s.attrs for s in rec.find("mdtp.device_put")}}))
"""


def test_device_put_records_where_each_leaf_lands(tmp_path):
    """On four virtual CPU devices (a subprocess: the device count is
    fixed when JAX starts): ``devices``, ``device_bytes`` and
    ``gathered_bytes`` of a strided, a contiguous and a replicated
    float32 leaf, against hand counts; with recording off no sharding is
    inspected."""
    import json
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _PLACEMENT, str(tmp_path)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.split("PUTS ", 1)[1])
    assert got["inspected_off"] == 0
    # [2, 8, 4] split on axis 1: four [2, 2, 4] shards of 64 bytes, each
    # two runs of 32 bytes in the blob
    assert got["spans"]["strided"] == {
        "leaf": "strided", "bytes": 256, "devices": 4, "device_bytes": 256,
        "gathered_bytes": 256}
    # [8, 4] split on axis 0: four [2, 4] shards, each one run of 32 bytes
    assert got["spans"]["contiguous"] == {
        "leaf": "contiguous", "bytes": 128, "devices": 4,
        "device_bytes": 128, "gathered_bytes": 0}
    # [16] on every device: four whole copies of 64 bytes
    assert got["spans"]["replicated"] == {
        "leaf": "replicated", "bytes": 64, "devices": 4, "device_bytes": 256,
        "gathered_bytes": 0}


def test_placement_without_a_sharding_is_one_whole_device():
    assert obs.placement(None, (3, 5), 2) == {
        "devices": 1, "device_bytes": 30, "gathered_bytes": 0}
