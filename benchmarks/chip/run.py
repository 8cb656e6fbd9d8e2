"""Chip benchmark of MDTP's restore-to-HBM path: one run of one cell.

    python3 benchmarks/chip/run.py --workload qwen3-1.7b.paper-fleet \\
        --seed 7 --seconds 40 --trace 0

Run from the repository root, on a machine whose JAX sees TPU chips; the
cell's needs (configuration, traffic, chips) come from ``BENCHMARK.json``.
Earlier lines of standard output are ``name: value`` readings; the last is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with its limit, also the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# the repository root, not this directory: its trace.py would shadow the
# standard library's
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness

    bench = harness.load_benchmark()
    cell, _, _ = harness.load_cell(bench, args.workload)

    import jax

    t_import = time.monotonic()
    devices = jax.devices()
    t_devices = time.monotonic()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU; JAX found {devices[0].platform!r} devices",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    harness.emit("start_phases_s", json.dumps({
        "import": t_import - T_START, "devices": t_devices - t_import}))
    result = harness.run_cell(
        bench, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices[:cell["chips"]],
        t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
