"""The four-chip Moonlight cell's path rehearsed on four virtual CPU devices.

A reduced Moonlight-16B-A3B leaf table, written in the format of
``configs/moonlight-16b-a3b-ep4.json`` (same keys, same 1x4 ``model``
mesh, specs from the same rules), runs through ``harness.run_cell`` under
the cell's own traffic, ``nic-fleet``, in a scratch repository root:
through the program, through the plain reference, and through the
reference rounded to float8 (the control, which must not be correct).
The device count is fixed when JAX starts, so the runs are a subprocess.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[3]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "moonlight-16b-a3b-ep4.nic-fleet"
#: leaves of the tree: the dense layer's 2 norms, 5 MLA and 3 MLP leaves;
#: the MoE stack's 2 norms, 5 MLA, router, router_bias, 3 routed-expert
#: and 3 shared-expert leaves; embed, unembed and final_norm
LEAVES = 10 + 15 + 3

_RUN = """
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{repo!r}, {src!r}]
from pathlib import Path
import jax, jax.numpy as jnp
from benchmarks.chip import harness
from benchmarks.chip.reference import plain_restore
root = Path({root!r})
bench = harness.load_benchmark(root)

def reference(lower):
    return lambda ckpt, layout: (
        lambda *a, step, **kw: (plain_restore(ckpt, layout, lower), step))

for name, restore_for in (("program", None), ("reference", reference(None)),
                          ("control", reference(jnp.float8_e4m3fn))):
    line = harness.run_cell(bench, {cell!r}, seed=2**33 + 15, seconds=0.1,
                            trace=False, devices=jax.devices(),
                            t_start=time.monotonic(),
                            restore_for=restore_for, root=root)
    print("RESULT", name, json.dumps(line), flush=True)
"""


def scratch_root(tmp: Path) -> Path:
    """The benchmark's data directories and ``BENCHMARK.json``, with the
    cell's configuration file replaced by the reduced tree's."""
    from jax.sharding import AbstractMesh

    from repro.configs import moonlight_16b_a3b as moonlight

    bench_dir = REPO / "benchmarks" / "chip"
    d = tmp / "benchmarks" / "chip"
    d.mkdir(parents=True)
    for sub in ("configs", "traffic", "metrics"):
        (d / sub).mkdir()
        for f in (bench_dir / sub).iterdir():
            (d / sub / f.name).write_bytes(f.read_bytes())
    (tmp / "BENCHMARK.json").write_bytes(
        (REPO / "BENCHMARK.json").read_bytes())
    path = d / "configs" / "moonlight-16b-a3b-ep4.json"
    frozen = json.loads(path.read_text())
    small = moonlight.reduced()
    mesh = AbstractMesh(tuple(frozen["layout"]["mesh"].values()),
                        tuple(frozen["layout"]["mesh"]))
    frozen.update(small)
    frozen["leaves"] = moonlight.leaf_table(
        moonlight.checkpoint_specs(cfg=small), mesh)
    path.write_text(json.dumps(frozen))
    return tmp


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The cell run by the program, by the plain reference, and by the
    reference through float8 (the control), in one subprocess: result
    lines and ``shards_checked`` readings by name."""
    root = scratch_root(tmp_path_factory.mktemp("root"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    prog = _RUN.format(repo=str(REPO), src=str(REPO / "src"),
                       root=str(root), cell=CELL)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=600, cwd=str(REPO))
    assert res.returncode == 0, res.stderr[-3000:]
    lines, checked = {}, []
    for line in res.stdout.splitlines():
        if line.startswith("RESULT "):
            _, name, body = line.split(" ", 2)
            lines[name] = json.loads(body)
        elif line.startswith("shards_checked: "):
            checked.append(int(line.split(": ", 1)[1]))
    assert list(lines) == ["program", "reference", "control"], res.stdout
    return lines, dict(zip(lines, checked))


def test_reduced_moonlight_cell_on_four_devices(runs):
    """Correct, every restore's 28 leaves checked on all 4 devices."""
    lines, checked = runs
    line = lines["program"]
    assert line["correct"] is True, line
    assert all(c["value"] == 0 for c in line["checks"].values()), line
    assert set(line["metrics"]) == {"restore_goodput", "egress_per_byte",
                                    "setup_s"}
    assert checked["program"] == line["attempted"] * LEAVES * 4


@pytest.mark.parametrize("name,correct", [("reference", True),
                                          ("control", False)])
def test_reference_passes_and_its_float8_control_fails(runs, name,
                                                       correct):
    """The plain reference in the program's place is correct on the
    four-device layout; the same through float8 is not, by its bytes."""
    line = runs[0][name]
    assert line["correct"] is correct, line
    assert (line["checks"]["bytes_differing"]["value"] == 0) is correct
    assert line["checks"]["shards_misplaced"]["value"] == 0
