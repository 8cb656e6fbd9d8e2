"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

A trace (``*.xplane.pb``, read with ``jax.profiler.ProfileData``) holds
planes: one per device (``/device:TPU:<n>``) and the host's.  The device
planes' op lines carry every operation that ran on a chip; the harness's
own spans (``jax.profiler.TraceAnnotation`` named ``chipbench.<what>``)
sit on the host plane, on the same clock.

From those this module gives, inside the timed interval of the traced
restore (the harness's ``chipbench.restore`` span): each device's busy
time as the union of its op intervals, the device time of every op name,
the device time and count of the program executions (module line) whose
name contains a given word, and the idle gaps of the first device, each
cut where a harness span starts or ends and labelled with the innermost
harness span around each piece.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

SPAN = "chipbench."
WINDOW_SPAN = SPAN + "restore"


def tpu_op_line(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


def tpu_module_line(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU:") and line == "XLA Modules"


def union(intervals) -> list[tuple[float, float]]:
    """Sorted disjoint cover of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


@dataclass
class Summary:
    """Seconds throughout; ``window`` is the traced restore's interval."""
    window_s: float
    busy_s: list[float]                     # per device
    op_s: dict[str, float]                  # op name -> device seconds
    modules: dict[str, tuple[float, int]]   # module name -> (seconds, n)
    gaps: list[tuple[str, float]]           # (host span, seconds), longest first
    lines: dict[str, dict[str, int]] = field(default_factory=dict)

    def module_time(self, word: str) -> tuple[float, int]:
        secs = sum(s for name, (s, _) in self.modules.items() if word in name)
        calls = sum(n for name, (_, n) in self.modules.items() if word in name)
        return secs, calls


def _events(plane_list):
    """``(plane, line, name, start_ns, end_ns)`` of every event."""
    for plane in plane_list:
        for line in plane.lines:
            for ev in line.events:
                yield (plane.name, line.name, ev.name, ev.start_ns,
                       ev.start_ns + ev.duration_ns)


def reduce(events, is_op_line=tpu_op_line,
           is_module_line=tpu_module_line) -> Summary | None:
    """``events`` as :func:`_events` yields them.  None when the trace has
    no harness window span or no device operation."""
    events = list(events)
    spans = [(s, e, name) for _, _, name, s, e in events
             if name.startswith(SPAN)]
    windows = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    ops: dict[str, list] = defaultdict(list)
    op_s: dict[str, float] = defaultdict(float)
    modules: dict[str, list] = defaultdict(lambda: [0.0, 0])
    lines: dict[str, dict[str, int]] = defaultdict(lambda: defaultdict(int))
    for plane, line, name, s, e in events:
        lines[plane][line] += 1
        if is_op_line(plane, line):
            ops[plane].append((s, e))
            # TPU op events are named by their whole HLO instruction
            op_s[name.split(" = ")[0]] += length(clip([(s, e)], lo, hi)) / 1e9
        elif is_module_line(plane, line):
            inside = length(clip([(s, e)], lo, hi))
            if inside > 0:
                modules[name][0] += inside / 1e9
                modules[name][1] += 1
    if not ops:
        return None
    busy = {p: union(clip(iv, lo, hi)) for p, iv in ops.items()}
    first = busy[sorted(busy)[0]]
    # idle pieces of the first device, cut where a harness span starts or
    # ends, each named by the innermost span around it
    cuts = sorted({t for s, e, _ in spans for t in (s, e) if lo < t < hi})
    gaps = []
    t = lo
    for s, e in first + [(hi, hi)]:
        if s > t:
            edges = [t, *(c for c in cuts if t < c < s), s]
            gaps += [(_label(spans, (a + b) / 2), (b - a) / 1e9)
                     for a, b in zip(edges, edges[1:])]
        t = max(t, e)
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=[length(b) / 1e9 for _, b in sorted(busy.items())],
        op_s=dict(op_s),
        modules={k: (v[0], v[1]) for k, v in modules.items()},
        gaps=gaps,
        lines={p: dict(c) for p, c in lines.items()})


def _label(spans, t: float) -> str:
    """The innermost harness span around instant ``t``."""
    around = [(s, name) for s, e, name in spans if s <= t <= e]
    if not around:
        return "outside the harness's spans"
    return max(around)[1][len(SPAN):]


def read(trace_dir: str, **kw) -> Summary | None:
    """Reduce the one trace the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    return reduce(_events(data.planes), **kw)
