"""The harness's functions rehearsed on the CPU, steered by the test.

``run.py`` itself refuses any device but a TPU; these tests call
``harness.run_cell`` on the CPU backend with a reduced Qwen3 leaf table and
a CPU-sized fleet, in a scratch repository root that holds a copy of the
benchmark's data directories plus a configuration, a traffic mix and a
metric of their own, found by name like any other.  They also break the
timed path underneath the harness, once for each fault a restore cell can
have, and see ``correct`` come out false.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[3]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.checkpoint import leaves_of, lookup, nested  # noqa: E402
from benchmarks.chip.reference import plain_restore  # noqa: E402

MiB = 1 << 20
TP_AXES = ("qheads", "kv_heads", "mlp", "vocab")
#: a metric that only this test's scratch root has
COUNT_METRIC = '''"""Restores in the window (a test's own metric)."""


def read(run):
    return float(len(run.restores))
'''


def small_config(tp4: bool) -> dict:
    """Qwen3's reduced leaf table, with a vocabulary wide enough (about
    50 MB of bf16) that every wave spans several chunks."""
    from repro.configs.qwen3_1_7b import reduced
    from repro.models.common import ParamSpec
    from repro.models.transformer import model_specs

    specs = model_specs(reduced().replace(vocab_size=393216, n_kv_heads=4))
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))[0]
    leaves = []
    for path, s in flat:
        e = {"key": "/".join(str(q.key) for q in path),
             "shape": list(s.shape), "dtype": "bfloat16",
             "mean": 1.0 if s.init == "ones" else 0.0,
             "std": 0.02 if s.init == "ones" else s.scale}
        if tp4:
            spec = [None] * len(s.shape)
            tp = [d for d, name in enumerate(s.logical) if name in TP_AXES]
            if tp:
                spec[tp[0]] = "model"
            while spec and spec[-1] is None:
                spec.pop()
            e["spec"] = spec
        leaves.append(e)
    layout = {"mesh": {"data": 1, "model": 4}} if tp4 else {"devices": 1}
    return {"name": "small", "layout": layout, "restore": {"waves": 4},
            "leaves": leaves}


def scratch_root(tmp: Path, tp4: bool = False) -> tuple[Path, dict]:
    """A repository root holding the benchmark's data directories, and a
    configuration, a traffic mix and a metric dropped in beside them."""
    d = tmp / harness.BENCH_DIR
    d.mkdir(parents=True)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(REPO / harness.BENCH_DIR / sub, d / sub)
    (d / "configs" / "small.json").write_text(json.dumps(small_config(tp4)))
    (d / "traffic" / "cpu-fleet.json").write_text(json.dumps({
        "mirrors": [{"rate_MiBps": r, "rtt_ms": 30} for r in (16, 32, 48)],
        "events": [{"mirror": 0, "at_ideal": 0.35, "do": "stop"}]}))
    (d / "metrics" / "restores.count.py").write_text(COUNT_METRIC)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "small", "file": f"{harness.BENCH_DIR}/configs/small.json"})
    bench["workloads"] = [{"name": "small.cpu-fleet", "config": "small",
                           "traffic": "cpu-fleet",
                           "chips": 4 if tp4 else 1}]
    bench["end_to_end"].append({"name": "restores.count", "unit": "1",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp, bench


def run_small(root: Path, bench: dict, seconds: float, restore=None,
              trace: bool = False, seed: int = 2**33 + 5) -> dict:
    """``restore``: a function of (checkpoint, layout) giving what stands
    in for ``restore_checkpoint``."""
    return harness.run_cell(bench, "small.cpu-fleet", seed=seed,
                            seconds=seconds, trace=trace,
                            devices=jax.devices(), t_start=time.monotonic(),
                            restore_for=restore, root=root)


def restore_lines(out: str) -> list[dict]:
    return [json.loads(line.split(": ", 1)[1]) for line in out.splitlines()
            if line.startswith("restore[")]


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return scratch_root(tmp_path_factory.mktemp("root"))


def test_two_restores_from_three_mirrors_one_stopped(cell, capsys):
    """The program's restore from the benchmark's mirrors, back to back,
    with the slowest mirror stopped mid-restore each time; the last line
    parses, correct, with the dropped-in traffic and metric found."""
    root, bench = cell
    result = run_small(root, bench, seconds=5.0)
    out = capsys.readouterr()
    line = json.loads(json.dumps(result))
    assert line["correct"] is True, out.err[-2000:]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert all(c["value"] == 0 for c in line["checks"].values())
    restores = restore_lines(out.out)
    assert len(restores) == line["attempted"]
    for r in restores:
        assert r["stopped"] == [0] and len(r["retunes"]) == 3, r
        assert r["compiles"] == 0, r
    m = line["metrics"]
    assert set(m) == {"restore_goodput", "egress_per_byte", "setup_s",
                      "restores.count"}
    assert m["restores.count"]["value"] == line["attempted"]
    assert 1.0 <= m["egress_per_byte"]["value"] < 1.2
    assert "check restores_failed: 0 (limit 0)" in out.err.splitlines()[-1]


def test_traced_run_reports_the_per_layer_metrics(cell, capsys):
    """With ``trace`` the metrics are the per-layer ones; the CPU has no
    device plane, so the trace readers find nothing and stay silent."""
    root, bench = cell
    line = run_small(root, bench, seconds=0.1, trace=True)
    capsys.readouterr()
    assert line["correct"] is True
    assert set(line["metrics"]) == {"sched.wire_efficiency",
                                    "client.cpu_s_per_GiB",
                                    "landing.tail_ms"}
    assert 0 < line["metrics"]["sched.wire_efficiency"]["value"] <= 100
    assert line["device"]["window_s"] > 0


def _restore():
    from repro.checkpoint import restore_checkpoint
    return restore_checkpoint


def _fault_unchanged(ckpt, layout):
    """A restore that hands back its first tree every time after."""
    first = []

    def restore(*a, **kw):
        if not first:
            first.append(_restore()(*a, **kw))
        return first[0]
    return restore


def _fault_half_left_out(ckpt, layout):
    """Every other leaf never lands: zeros in its place."""
    def restore(*a, **kw):
        state, step = _restore()(*a, **kw)
        leaves, treedef = jax.tree.flatten(state)
        leaves = [jnp.zeros_like(x) if i % 2 else x
                  for i, x in enumerate(leaves)]
        return jax.tree.unflatten(treedef, leaves), step
    return restore


def _fault_flipped_byte(ckpt, layout):
    """One byte of the largest leaf altered where it lands."""
    def restore(*a, **kw):
        state, step = _restore()(*a, **kw)
        leaves, treedef = jax.tree.flatten(state)
        big = max(range(len(leaves)), key=lambda i: leaves[i].size)
        host = np.array(leaves[big])
        host.reshape(-1).view(np.uint8)[12345] ^= 0x01
        leaves[big] = jax.device_put(host, leaves[big].sharding)
        return jax.tree.unflatten(treedef, leaves), step
    return restore


@pytest.mark.parametrize("fault", [_fault_unchanged, _fault_half_left_out,
                                   _fault_flipped_byte])
def test_a_broken_restore_is_not_correct(cell, capsys, fault):
    root, bench = cell
    line = run_small(root, bench, seconds=3.0, restore=fault)
    capsys.readouterr()
    assert line["correct"] is False
    assert line["checks"]["bytes_differing"]["value"] > 0
    assert line["failed"] >= 1


@pytest.mark.parametrize("lower,correct", [(None, True),
                                           (jnp.float8_e4m3fn, False)])
def test_reference_in_the_programs_place(cell, capsys, lower, correct):
    """The plain reference passes; the control, the reference through
    float8, fails."""
    root, bench = cell

    def reference(ckpt, layout):
        return lambda *a, step, **kw: (plain_restore(ckpt, layout, lower),
                                       step)

    line = run_small(root, bench, seconds=0.5, restore=reference)
    capsys.readouterr()
    assert line["correct"] is correct
    assert (line["checks"]["bytes_differing"]["value"] == 0) is correct


def test_nested_tree_round_trip():
    leaves = leaves_of(small_config(False))
    tree = nested(leaves, lambda l: l.key)
    assert [lookup(tree, l.key) for l in leaves] == [l.key for l in leaves]


_FOUR_DEVICES = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {tests!r})
from pathlib import Path
import jax
import test_chip_bench_harness as t
root, bench = t.scratch_root(Path({tmp!r}), tp4=True)
good = t.run_small(root, bench, seconds=0.1)
assert good["correct"], good
def whole_on_one(ckpt, layout):
    # the exchange between chips left out: every leaf whole on device 0
    def restore(*a, **kw):
        state, step = t._restore()(*a, **kw)
        return jax.tree.map(lambda x: jax.device_put(x, jax.devices()[0]),
                            state), step
    return restore
bad = t.run_small(root, bench, seconds=0.1, restore=whole_on_one)
assert not bad["correct"] and bad["checks"]["shards_misplaced"]["value"], bad
assert bad["checks"]["bytes_differing"]["value"] == 0, bad
print("FOUR_OK", json.dumps(good["checks"]))
"""


def test_four_devices_sharded_restore_and_missing_exchange(tmp_path):
    """The 1x4 mesh path on four virtual CPU devices (a subprocess: the
    device count is fixed when JAX starts): every shard of the frozen
    specs lands right; leaves left whole on one device fail the layout."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    prog = _FOUR_DEVICES.format(tests=str(Path(__file__).parent),
                                tmp=str(tmp_path))
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=600, cwd=str(REPO))
    assert res.returncode == 0, res.stderr[-3000:]
    assert "FOUR_OK" in res.stdout


@pytest.mark.parametrize("only_benchmark", [False, True])
def test_command_refuses_without_a_tpu(tmp_path, only_benchmark):
    """The command never falls back to the CPU, and prints no result;
    nor does it run from a directory holding only the benchmark's files."""
    cwd = REPO
    if only_benchmark:
        cwd = tmp_path
        shutil.copy(REPO / "BENCHMARK.json", cwd / "BENCHMARK.json")
        shutil.copytree(REPO / harness.BENCH_DIR, cwd / harness.BENCH_DIR,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    res = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen3-1.7b.paper-fleet", "--seed", "3", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300, cwd=str(cwd))
    assert res.returncode != 0
    assert res.stdout.strip() == ""
