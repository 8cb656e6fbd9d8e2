"""Shared helpers for the paper-reproduction benchmarks.

Every benchmark emits CSV rows ``name,us_per_call,derived`` where
``us_per_call`` is the wall-clock cost of producing the cell (the simulator
call) and ``derived`` is the metric the paper's figure plots (transfer
seconds, utilization %, ...).  Extra context columns follow ``derived``.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if os.path.abspath(_SRC) not in (os.path.abspath(p) for p in sys.path):
    sys.path.insert(0, os.path.abspath(_SRC))

from repro.core import (  # noqa: E402
    Aria2Policy,
    BitTorrentPolicy,
    MDTPPolicy,
    StaticChunkingPolicy,
    simulate,
)

GB = 1024**3

POLICIES = {
    "mdtp": MDTPPolicy,
    "static": StaticChunkingPolicy,
    "aria2": Aria2Policy,
    "bittorrent": BitTorrentPolicy,
}


def emit(name: str, us_per_call: float, derived, *extra) -> None:
    cols = [name, f"{us_per_call:.1f}", str(derived)] + [str(e) for e in extra]
    print(",".join(cols), flush=True)


def run_cells(name, policy_name, servers, file_size, reps: int, policy_kwargs=None):
    """Average ``reps`` seeded simulations; returns (mean_s, stderr_s)."""
    times = []
    t0 = time.perf_counter()
    for seed in range(reps):
        pol = POLICIES[policy_name](**(policy_kwargs or {}))
        res = simulate(pol, servers, file_size, seed=seed)
        res.check_integrity()
        times.append(res.total_time)
    wall_us = (time.perf_counter() - t0) * 1e6 / max(reps, 1)
    mean = float(np.mean(times))
    stderr = float(np.std(times) / np.sqrt(len(times))) if len(times) > 1 else 0.0
    emit(name, wall_us, f"{mean:.2f}", f"stderr={stderr:.3f}")
    return mean, stderr
