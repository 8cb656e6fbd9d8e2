"""Readings that set the limits of ``correct``, several seeds in one process.

    python3 benchmarks/chip/control.py --workload qwen3-1.7b.nic-fleet \\
        --restore control --seeds 11 12 13 --seconds 1

``--restore program`` runs the cell as ``run.py`` does (the lower readings:
sound runs of the program); ``--restore control`` puts the plain reference
in the program's place with every value rounded to float8 (e4m3) on the
way, the precision below the configuration's bf16 (the upper readings).
One line per seed: the numbers compared, as ``run.py`` reports them under
``checks``.  The benchmark's own runs never run this.  Needs the TPU chips
the cell asks for, like ``run.py``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--restore", choices=("program", "control"),
                    required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.chip import harness
    from benchmarks.chip.reference import plain_restore

    bench = harness.load_benchmark()
    cell, _, _ = harness.load_cell(bench, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"control.py: {args.workload} needs {cell['chips']} TPU "
              f"chips; JAX found {len(devices)} {devices[0].platform!r}",
              file=sys.stderr)
        return 2

    def control(ckpt, layout):
        return lambda *a, step, **kw: (
            plain_restore(ckpt, layout, jnp.float8_e4m3fn), step)

    t = T_START
    for seed in args.seeds:
        line = harness.run_cell(
            bench, args.workload, seed=seed, seconds=args.seconds,
            trace=False, devices=devices[:cell["chips"]], t_start=t,
            restore_for=control if args.restore == "control" else None)
        print(json.dumps({"seed": seed, "restore": args.restore,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": {k: v["value"]
                                     for k, v in line["checks"].items()}}),
              flush=True)
        t = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
