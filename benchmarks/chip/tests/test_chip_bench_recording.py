"""The program's own record of a restore (``repro.obs``), held against the
benchmark's mirrors on the CPU fleet the harness tests build: three
mirrors, the slowest stopped mid-restore.  Also: the program's ``mdtp.*``
spans in a trace leave the trace reduction's numbers as they were, and an
untraced harness run never turns the recorder on."""

from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[3]
for p in (str(REPO), str(REPO / "src"), str(Path(__file__).parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

import test_chip_bench_harness as bench_tests  # noqa: E402
from benchmarks.chip import trace  # noqa: E402
from benchmarks.chip.checkpoint import STEP, Checkpoint, leaves_of, nested  # noqa: E402
from benchmarks.chip.harness import Fleet  # noqa: E402
from benchmarks.chip.reference import Layout, compare  # noqa: E402
from repro import obs  # noqa: E402

CPU_FLEET = {"mirrors": [{"rate_MiBps": r, "rtt_ms": 30} for r in (16, 32, 48)],
             "events": [{"mirror": 0, "at_ideal": 0.35, "do": "stop"}]}


def test_a_recorded_restore_accounts_for_waves_leaves_and_bytes():
    from repro.checkpoint import restore_checkpoint

    config = bench_tests.small_config(False)
    leaves = leaves_of(config)
    layout = Layout(config["layout"], jax.devices()[:1])
    ckpt = Checkpoint(leaves, 2**33 + 11, layout.devices[0])
    fleet = None
    try:
        fleet = Fleet(CPU_FLEET, ckpt.files("/ckpt"))
        like = nested(leaves,
                      lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype))
        timers = fleet.schedule(ckpt.total)
        with obs.recording() as rec:
            state, _ = restore_checkpoint(
                "ckpt", like, step=STEP, replicas=fleet.replicas("/ckpt"),
                wave_bytes=ckpt.total // 4 + 1)
            state = jax.block_until_ready(state)
        fleet.reset(timers)
        last_bytes = [m["last_byte"] for m in fleet.stats()
                      if m["last_byte"] is not None]
        assert compare(state, ckpt, layout).bytes_differing == 0
    finally:
        if fleet is not None:
            fleet.close()
        ckpt.close()

    [restore] = rec.find("mdtp.restore")
    assert [c.name for c in restore.children][:3] == \
        ["mdtp.manifest", "mdtp.buffer", "mdtp.wave"]
    assert restore.find("mdtp.buffer")[0].attrs["bytes"] == ckpt.total
    assert len(restore.find("mdtp.finish")) == 1
    waves = restore.find("mdtp.wave")
    assert [w.attrs["wave"] for w in waves] == [0, 1, 2, 3]
    assert sum(w.attrs["bytes"] for w in waves) == ckpt.total
    assert len(restore.find("mdtp.retune")) == 3
    puts = restore.find("mdtp.device_put")
    assert sorted(p.attrs["leaf"] for p in puts) == \
        sorted(l.key for l in leaves)
    assert sum(p.attrs["bytes"] for p in puts) == ckpt.total
    for w in waves:
        assert restore.start <= w.start <= w.end <= restore.end
        f = w.fetch
        assert f.report is not None
        assert f.report.total_bytes == w.attrs["bytes"]
        assert f.params is not None
        assert w.start <= f.start <= f.end <= w.end
        for r in f.replicas:
            assert r.outstanding == 0
            assert 0.0 <= r.inflight_s <= r.alive_s(f) + 1e-3
            if f.report.bytes_per_replica[r.name]:
                assert r.first_commit <= r.last_commit
                assert r.header_wait_s > 0 and r.body_read_s > 0
    assert sum(w.fetch.crc_s for w in waves) > 0
    # the stopped mirror is retired in the wave it stops in, or the next
    assert any(w.fetch.replicas[0].failed_at is not None for w in waves)
    last_commit = max(r.last_commit for w in waves for r in w.fetch.replicas
                      if r.last_commit is not None)
    assert max(last_bytes) <= last_commit + 1e-3


def _ev(plane, line, name, start_ms, end_ms):
    return (plane, line, name, start_ms * 1e6, end_ms * 1e6)


@pytest.mark.parametrize("with_program_spans", [False, True])
def test_program_spans_leave_the_trace_reduction_as_it_was(
        with_program_spans):
    """Busy time, op time, modules and gap labels come out the same when
    the host plane also holds the program's ``mdtp.*`` spans."""
    host, dev = "/host:CPU", "/device:TPU:0"
    events = [
        _ev(host, "main", "chipbench.restore", 0, 100),
        _ev(host, "main", "chipbench.restore_checkpoint", 0, 80),
        _ev(host, "main", "chipbench.block_until_ready", 80, 100),
        _ev(dev, "XLA Ops", "copy", 10, 25),
        _ev(dev, "XLA Ops", "fusion", 40, 45),
        _ev(dev, "XLA Modules", "jit__sweep_core(1)", 40, 45),
    ]
    if with_program_spans:
        events += [
            _ev(host, "main", "mdtp.restore", 1, 79),
            _ev(host, "main", "mdtp.buffer", 1, 2),
            _ev(host, "main", "mdtp.wave", 2, 39),
            _ev(host, "main", "mdtp.device_put", 30, 31),
            _ev(host, "main", "mdtp.retune", 39, 46),
            _ev(host, "main", "mdtp.wave", 46, 78),
            _ev(host, "main", "mdtp.finish", 78, 79),
        ]
    s = trace.reduce(events)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx([0.020])
    assert s.op_s == pytest.approx({"copy": 0.015, "fusion": 0.005})
    assert s.modules == {"jit__sweep_core(1)": pytest.approx((0.005, 1))}
    assert s.gaps == pytest.approx([
        ("restore_checkpoint", 0.035), ("block_until_ready", 0.020),
        ("restore_checkpoint", 0.015), ("restore_checkpoint", 0.010)])


def test_an_untraced_harness_run_never_turns_the_recorder_on(tmp_path,
                                                              capsys):
    root, bench = bench_tests.scratch_root(tmp_path)
    seen = []

    def spy(ckpt, layout):
        restore = bench_tests._restore()

        def call(*a, **kw):
            seen.append(obs.current())
            return restore(*a, **kw)
        return call

    line = bench_tests.run_small(root, bench, seconds=0.1, restore=spy)
    capsys.readouterr()
    assert line["correct"] is True
    assert seen and all(r is None for r in seen)
