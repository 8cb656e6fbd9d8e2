"""Smoke-bench guard: the autotune section of ``benchmarks.run`` must
complete (and demonstrate its speedups) quickly on one CPU core, so the
tuner-fusion and round-engine claims stay continuously verified, and the
``--json`` artifact (BENCH_autotune.json — the cross-PR perf trajectory)
must be valid machine-readable JSON."""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def bench_run(tmp_path_factory):
    out_dir = tmp_path_factory.mktemp("bench")
    json_path = out_dir / "BENCH_autotune.json"
    # a fresh compile cache per run: the bench's cold-compile rows stay
    # cold, and the repo's own cache directory is left alone
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=str(out_dir / "jax_cache"))
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.run",
         "--skip", "fig2", "fig3", "fig4", "fig5", "table2", "roofline",
         "restore", "--json", str(json_path)],
        capture_output=True, text=True, cwd=_ROOT, timeout=420, env=env,
    )
    return res, json_path


def test_autotune_bench_smoke(bench_run):
    res, _ = bench_run
    assert res.returncode == 0, res.stderr[-2000:]
    out = res.stdout
    assert "# === autotune ===" in out
    # The bench's own output carries the headline number (>= 5x on an idle
    # box); the guard only enforces a loose floor so a loaded CI core
    # can't flake the suite while a true regression to per-point-compile
    # behavior (ratio ~1x) still fails.
    cold = [l for l in out.splitlines() if l.startswith("autotune/fused_cold")]
    assert cold, out
    speedup = float(cold[0].rsplit("speedup=", 1)[1].rstrip("x"))
    assert speedup >= 2.0, cold[0]
    agree = [l for l in out.splitlines()
             if l.startswith("autotune/argmin_agree")]
    assert agree and agree[0].endswith("True"), agree


def test_round_engine_bench_speedup(bench_run):
    """The round engine's steady-state win over the event engine shows in
    the bench (headline >= 5x idle; loose 2x floor against CI noise), and
    its argmin regret under the event metric stays small."""
    res, _ = bench_run
    out = res.stdout
    row = [l for l in out.splitlines()
           if l.startswith("autotune/engine_round")]
    assert row, out
    speedup = float(row[0].rsplit("speedup=", 1)[1].split(",")[0].rstrip("x"))
    assert speedup >= 2.0, row[0]
    regret = [l for l in out.splitlines()
              if l.startswith("autotune/engine_regret")]
    assert regret, out
    assert float(regret[0].split(",")[2]) <= 0.02, regret[0]


def test_bench_json_artifact_valid(bench_run):
    """--json writes well-formed JSON carrying µs/call for every emitted
    row, including the event-vs-round engine comparison."""
    res, json_path = bench_run
    assert res.returncode == 0, res.stderr[-2000:]
    assert json_path.exists()
    payload = json.loads(json_path.read_text())
    assert payload["schema"] == 1
    assert payload["failed_sections"] == []
    names = [r["name"] for r in payload["rows"]]
    assert any(n.startswith("autotune/engine_event") for n in names)
    assert any(n.startswith("autotune/engine_round") for n in names)
    for row in payload["rows"]:
        assert isinstance(row["us_per_call"], float)


def test_contention_bench_rows(bench_run):
    """The contention section emits manager-vs-greedy rows for every
    trace (makespan derived, vs_greedy extra on the manager rows)."""
    res, json_path = bench_run
    out = res.stdout
    assert "# === contention ===" in out
    rows = [l for l in out.splitlines() if l.startswith("contention/")]
    mgr_rows = [l for l in rows if "/manager," in l]
    greedy_rows = [l for l in rows if "/greedy," in l]
    assert len(mgr_rows) == 3 and len(greedy_rows) == 3, rows
    assert all("vs_greedy=" in l for l in mgr_rows)


def test_committed_bench_online_contention_wins():
    """The committed BENCH_online.json carries the contention rows and
    records the shared-fleet manager beating K independent greedy clients
    on aggregate completion time (makespan) for >= 2 of 3 traces."""
    path = os.path.join(_ROOT, "BENCH_online.json")
    assert os.path.exists(path), "BENCH_online.json must be committed"
    payload = json.loads(open(path).read())
    rows = {r["name"]: r for r in payload["rows"]}
    traces = ("simultaneous", "staggered", "bottleneck")
    wins = 0
    for t in traces:
        greedy = rows[f"contention/{t}/greedy"]
        manager = rows[f"contention/{t}/manager"]
        if float(manager["derived"]) < float(greedy["derived"]):
            wins += 1
    assert wins >= 2, {t: (rows[f"contention/{t}/greedy"]["derived"],
                           rows[f"contention/{t}/manager"]["derived"])
                       for t in traces}


def test_dataplane_bench_rows(bench_run):
    """The dataplane section emits the loopback copy/zero-copy/pipelined
    goodput rows and the high-RTT serial-vs-pipelined pair, and on the
    high-RTT trace the pipelined client wins (the same invariant the
    ``run.py --check`` win-guard enforces in CI)."""
    res, _ = bench_run
    out = res.stdout
    assert "# === dataplane ===" in out
    rows = [l for l in out.splitlines() if l.startswith("dataplane/")]
    names = [r.split(",")[0] for r in rows]
    for n in ("dataplane/loopback/1rep/copy_serial",
              "dataplane/loopback/1rep/zerocopy_serial",
              "dataplane/loopback/1rep/zerocopy_pipelined",
              "dataplane/loopback/3rep/zerocopy_pipelined",
              "dataplane/highrtt/serial",
              "dataplane/highrtt/pipelined"):
        assert n in names, rows
    by_name = {r.split(",")[0]: r.split(",") for r in rows}
    serial = float(by_name["dataplane/highrtt/serial"][2])
    piped = float(by_name["dataplane/highrtt/pipelined"][2])
    assert piped >= serial, (serial, piped)


def test_committed_bench_dataplane_pipelined_wins():
    """The committed BENCH_dataplane.json records the pipelined zero-copy
    path beating the serial path on loopback goodput for the high-RTT
    throttled trace — the tentpole claim, pinned as an artifact."""
    path = os.path.join(_ROOT, "BENCH_dataplane.json")
    assert os.path.exists(path), "BENCH_dataplane.json must be committed"
    payload = json.loads(open(path).read())
    rows = {r["name"]: r for r in payload["rows"]}
    serial = float(rows["dataplane/highrtt/serial"]["derived"])
    piped = float(rows["dataplane/highrtt/pipelined"]["derived"])
    assert piped > serial, (serial, piped)
    # and the zero-copy receive path is not slower than the copy path
    # (loopback assembly goodput, 1-replica)
    copy = float(rows["dataplane/loopback/1rep/copy_serial"]["derived"])
    zc = float(rows["dataplane/loopback/1rep/zerocopy_serial"]["derived"])
    assert zc >= copy, (copy, zc)


def test_committed_bench_json_tracks_engines():
    """The committed BENCH_autotune.json (perf trajectory across PRs) is
    valid and records both simulator engines."""
    path = os.path.join(_ROOT, "BENCH_autotune.json")
    assert os.path.exists(path), "BENCH_autotune.json must be committed"
    payload = json.loads(open(path).read())
    names = [r["name"] for r in payload["rows"]]
    assert any(n.startswith("autotune/engine_event") for n in names)
    assert any(n.startswith("autotune/engine_round") for n in names)
