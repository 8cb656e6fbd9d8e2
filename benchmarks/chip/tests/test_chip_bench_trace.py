"""The trace reduction: union-of-intervals busy time, device time per name,
and idle gaps labelled with the harness's spans, on hand-made events and on
a small trace recorded here on the CPU."""

from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmarks.chip import trace  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, line, name, start_ms, end_ms):
    return (plane, line, name, start_ms * 1e6, end_ms * 1e6)


def test_union_clip_and_length():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert trace.length([(0, 3), (5, 8)]) == 6


def test_reduce_hand_made_events():
    """Window 0-100 ms; ops overlap at 10-30 and stand alone at 60-70 (one
    op straddles the window's end); two devices; module lines counted by
    name; gaps named by the innermost harness span around them."""
    events = [
        _ev(HOST, "main", "chipbench.restore", 0, 100),
        _ev(HOST, "main", "chipbench.restore_checkpoint", 0, 80),
        _ev(HOST, "main", "chipbench.block_until_ready", 80, 100),
        _ev(DEV, "XLA Ops", "copy", 10, 25),
        _ev(DEV, "XLA Ops", "fusion", 20, 30),
        _ev(DEV, "XLA Ops", "copy", 60, 70),
        _ev(DEV, "XLA Ops", "copy", 95, 120),
        _ev(DEV, "XLA Modules", "jit__fused_sweep(1)", 20, 30),
        _ev(DEV, "XLA Modules", "jit__fused_sweep(1)", 60, 62),
        _ev("/device:TPU:1", "XLA Ops", "copy", 40, 50),
    ]
    s = trace.reduce(events)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx([0.035, 0.010])
    assert s.op_s["copy"] == pytest.approx(0.015 + 0.010 + 0.005 + 0.010)
    assert s.op_s["fusion"] == pytest.approx(0.010)
    assert s.module_time("_fused_sweep") == pytest.approx((0.012, 2))
    assert s.gaps == pytest.approx([
        ("restore_checkpoint", 0.030), ("block_until_ready", 0.015),
        ("restore_checkpoint", 0.010), ("restore_checkpoint", 0.010)])
    assert s.lines[DEV] == {"XLA Ops": 4, "XLA Modules": 2}


def test_reduce_without_window_or_device_is_silent():
    assert trace.reduce([_ev(DEV, "XLA Ops", "copy", 0, 1)]) is None
    assert trace.reduce([_ev(HOST, "main", "chipbench.restore", 0, 1)]) \
        is None


def test_reduce_a_trace_recorded_on_the_cpu(tmp_path):
    """The CPU backend has no device plane: its XLA client threads stand
    in for one.  The reduction's numbers equal a direct reading of the
    same events, and the sleep inside ``restore_checkpoint`` is its
    longest gap."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    d = str(tmp_path)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.restore"):
        with jax.profiler.TraceAnnotation("chipbench.restore_checkpoint"):
            f(x).block_until_ready()
            time.sleep(0.2)
        with jax.profiler.TraceAnnotation("chipbench.block_until_ready"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()

    def cpu_ops(plane, line):
        return plane == HOST and line.startswith("tf_XLAPjRtCpuClient")

    s = trace.read(d, is_op_line=cpu_ops)
    assert s is not None
    path = next(Path(d).rglob("*.xplane.pb"))
    events = list(trace._events(ProfileData.from_file(str(path)).planes))
    lo, hi = next((a, b) for _, _, n, a, b in events
                  if n == "chipbench.restore")
    ops = [(a, b, n) for p, l, n, a, b in events if cpu_ops(p, l)]
    assert ops
    busy = trace.union(trace.clip([(a, b) for a, b, _ in ops], lo, hi))
    assert sum(s.busy_s) == pytest.approx(trace.length(busy) / 1e9)
    name = ops[0][2]
    assert s.op_s[name] == pytest.approx(sum(
        trace.length(trace.clip([(a, b)], lo, hi))
        for a, b, n in ops if n == name) / 1e9)
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    assert s.window_s > 0.2
    label, secs = s.gaps[0]
    assert label == "restore_checkpoint" and secs >= 0.19
