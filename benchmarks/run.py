"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived[,extra...]`` CSV rows.

Default is a *quick* pass (reduced reps/sizes, everything still paper-shaped)
so ``python -m benchmarks.run`` finishes in a few minutes on one CPU core;
``--full`` matches the paper's 10 repetitions and full size ladder.
Framework-layer benchmarks (roofline, restore) appear as sections when their
artifacts are available.

``--json PATH`` serializes the emitted rows.  An existing file is MERGED,
not clobbered: rows re-emitted this run replace their previous versions,
rows from skipped sections survive — so ``BENCH_autotune.json`` and
``BENCH_online.json`` each accumulate a per-PR trajectory no matter which
section subset a given invocation ran.

``--check [PATH]`` is the CI perf guard: re-run the smoke-sized autotune
sweep and compare its steady-state rows against the committed bench JSON
(default ``BENCH_autotune.json``); any row slower than ``3x`` the
committed number exits nonzero.  The tolerance is deliberately generous —
CI machines differ from the machines that produced the artifact — so only
an order-of-magnitude-class regression (a lost fusion, a retrace per grid
point, an accidentally-eager loop) trips it.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import traceback

#: perf-guard tolerance: fail only on > 3x the committed steady-state cost.
CHECK_TOLERANCE = 3.0

#: row-name prefixes the guard compares — jit-compiled steady-state (warm)
#: numbers only: they are stable across machines at the tens-of-ms scale.
#: Cold-compile rows, correctness/derived rows, and the pure-Python
#: microsecond micros (pysim/*) are machine noise, not perf signal.
CHECK_ROW_PREFIXES = (
    "autotune/fused_warm/",
    "autotune/engine_round/",
    "autotune/engine_scan/",
)

#: everything ``--check`` guards: per committed artifact, the smoke bench
#: that regenerates comparable rows and the steady-state prefixes to
#:  compare.  ``contention/*`` rows time a WARM full-policy replay
#: (fused sweeps + round-core sims, all jit-cached), so they are
#: steady-state signal like the autotune rows.  ``dataplane/highrtt/*``
#: rows are deterministic-token-bucket + emulated-RTT transfers, so
#: their wall times are pacing-dominated and machine-stable (the raw
#: ``dataplane/loopback/*`` rows are CPU-bound like the pysim micros and
#: deliberately excluded); the dataplane suite ALSO enforces the
#: win-guard: pipelined goodput must stay >= serial on the high-RTT
#: trace (see ``_check_dataplane_wins``).
#: ``faults/*`` rows are deterministic-token-bucket transfers with a
#: seeded fault policy, so they are pacing-dominated and machine-stable
#: too; the suite ALSO enforces the corruption win-guard: managed
#: per-chunk re-fetch must beat restart-from-zero on goodput (see
#: ``_check_fault_wins``).
#: ``flashcrowd/*`` p95-makespan rows are pacing-dominated storm replays;
#: the waste row (``flashcrowd/gray/waste``, an absolute byte count) is
#: deliberately NOT in the 3x comparison — the win-guard bounds it as a
#: percentage instead (see ``_check_flashcrowd_wins``).
#: ``broadcast/*`` makespan rows are pacing-dominated swarm replays
#: (every uplink a deterministic shared token bucket); the
#: ``origin_x`` row (an absolute byte count) is deliberately NOT in the
#: 3x comparison — the win-guard bounds it as an egress ratio instead
#: (see ``_check_broadcast_wins``).
#: ``shard/*`` makespan rows are pacing-dominated sharded-restore
#: replays (one slow origin, deterministic buckets); the ``stolen_x``
#: row (an absolute byte count) is NOT in the 3x comparison — the
#: win-guard uses it as the theft witness instead (see
#: ``_check_shard_wins``).
CHECK_SUITES = (
    ("BENCH_autotune.json", "autotune", CHECK_ROW_PREFIXES),
    ("BENCH_online.json", "contention", ("contention/",)),
    ("BENCH_dataplane.json", "dataplane",
     ("dataplane/highrtt/", "dataplane/compressed/raw",
      "dataplane/compressed/zblock")),
    ("BENCH_online.json", "faults", ("faults/",)),
    ("BENCH_online.json", "flashcrowd",
     ("flashcrowd/burst/", "flashcrowd/gray/plain",
      "flashcrowd/gray/robust")),
    ("BENCH_online.json", "broadcast",
     ("broadcast/independent/", "broadcast/swarm/n")),
    ("BENCH_online.json", "shard",
     ("shard/independent/", "shard/workstealing/k")),
)


def _check_dataplane_wins(rows) -> int:
    """The data-plane win-guards, on the freshly-run traces:

    - High-RTT trace: the pipelined (half-duplex) client's goodput
      (derived column, MB/s) must not fall below the serial client's,
      and the duplex client's must not fall below the pipelined one's —
      a lost-overlap regression (broken request splitting, a writer
      coroutine that serializes behind bodies again) shows up here long
      before the 3x wall-time tolerance trips.
    - Compressed trace: decoded/wire bytes on the compressible payload
      (the ``wire_ratio`` row's derived column) must stay >= 1.3x —
      the goodput-per-wire-byte win the zblock codec exists for.
    """
    by_name = {r["name"]: float(r["derived"]) for r in rows
               if r["name"].startswith("dataplane/")}
    serial = by_name.get("dataplane/highrtt/serial", 0.0)
    piped = by_name.get("dataplane/highrtt/pipelined", 0.0)
    duplex = by_name.get("dataplane/highrtt/duplex", 0.0)
    ratio = by_name.get("dataplane/compressed/wire_ratio", 0.0)
    if serial <= 0.0 or piped <= 0.0 or duplex <= 0.0 or ratio <= 0.0:
        print("# check: dataplane win-guard rows missing", file=sys.stderr)
        return 1
    rc = 0
    verdict = "ok" if piped >= serial else "REGRESSION"
    print(f"# check dataplane win-guard: pipelined {piped:.1f} MB/s vs "
          f"serial {serial:.1f} MB/s {verdict}", flush=True)
    if piped < serial:
        print("# check FAILED: pipelined goodput fell below serial on "
              "the high-RTT trace", file=sys.stderr)
        rc = 1
    verdict = "ok" if duplex >= piped else "REGRESSION"
    print(f"# check dataplane duplex win-guard: duplex {duplex:.1f} MB/s "
          f"vs pipelined {piped:.1f} MB/s {verdict}", flush=True)
    if duplex < piped:
        print("# check FAILED: duplex goodput fell below half-duplex "
              "pipelined on the high-RTT trace", file=sys.stderr)
        rc = 1
    verdict = "ok" if ratio >= 1.3 else "REGRESSION"
    print(f"# check dataplane compression-guard: {ratio:.2f}x decoded/"
          f"wire bytes (bar 1.3x) {verdict}", flush=True)
    if ratio < 1.3:
        print("# check FAILED: compressed goodput-per-wire-byte fell "
              "below 1.3x raw on the compressible payload",
              file=sys.stderr)
        rc = 1
    return rc


def _check_fault_wins(rows) -> int:
    """The corruption win-guard: on the freshly-run seeded-fault trace,
    the managed client's goodput (derived column, MB/s — per-chunk CRC
    verify + banned re-pool) must beat the restart-from-zero baseline.
    A verification regression that silently re-fetches everything, or a
    re-pool bug that restarts work, shows up here long before the 3x
    wall-time tolerance trips."""
    by_name = {r["name"]: float(r["derived"]) for r in rows
               if r["name"].startswith("faults/corruption/")}
    managed = by_name.get("faults/corruption/managed", 0.0)
    restart = by_name.get("faults/corruption/restart", 0.0)
    if managed <= 0.0 or restart <= 0.0:
        print("# check: corruption win-guard rows missing", file=sys.stderr)
        return 1
    verdict = "ok" if managed >= restart else "REGRESSION"
    print(f"# check corruption win-guard: managed {managed:.1f} MB/s vs "
          f"restart {restart:.1f} MB/s {verdict}", flush=True)
    if managed < restart:
        print("# check FAILED: managed re-fetch goodput fell below "
              "restart-from-zero under corruption", file=sys.stderr)
        return 1
    return 0


def _check_flashcrowd_wins(rows) -> int:
    """The flash-crowd win-guard, on the freshly-run storm replays:

    - GRAY storm: the robust manager's p95 makespan (us_per_call) must
      not exceed the plain manager's — hedging + probation + admission
      exist precisely to cut this tail, and a regression here means one
      of the three quietly stopped working.
    - CLEAN burst: robust p95 may not exceed 1.25x plain — the
      robustness machinery must be near-free when nothing is wrong
      (a tie is expected; a blowup means hedges or probation are firing
      on a healthy fleet).
    - Hedge waste on the gray storm (derived column of the waste row,
      a percentage) must stay <= 5% of the delivered bytes.
    """
    by_name = {r["name"]: r for r in rows
               if r["name"].startswith("flashcrowd/")}

    def p95(name: str) -> float:
        row = by_name.get(name)
        return float(row["us_per_call"]) if row else 0.0

    gray_plain = p95("flashcrowd/gray/plain")
    gray_robust = p95("flashcrowd/gray/robust")
    burst_plain = p95("flashcrowd/burst/plain")
    burst_robust = p95("flashcrowd/burst/robust")
    waste_row = by_name.get("flashcrowd/gray/waste")
    if 0.0 in (gray_plain, gray_robust, burst_plain, burst_robust) \
            or waste_row is None:
        print("# check: flash-crowd win-guard rows missing",
              file=sys.stderr)
        return 1
    rc = 0
    verdict = "ok" if gray_robust <= gray_plain else "REGRESSION"
    print(f"# check flash-crowd gray win-guard: robust p95 "
          f"{gray_robust / 1e6:.2f}s vs plain {gray_plain / 1e6:.2f}s "
          f"{verdict}", flush=True)
    if gray_robust > gray_plain:
        print("# check FAILED: robust p95 makespan exceeded plain on the "
              "gray storm", file=sys.stderr)
        rc = 1
    burst_bar = 1.25 * burst_plain
    verdict = "ok" if burst_robust <= burst_bar else "REGRESSION"
    print(f"# check flash-crowd burst overhead-guard: robust p95 "
          f"{burst_robust / 1e6:.2f}s vs plain {burst_plain / 1e6:.2f}s "
          f"(bar 1.25x) {verdict}", flush=True)
    if burst_robust > burst_bar:
        print("# check FAILED: robustness overhead exceeded 1.25x plain "
              "p95 on the clean burst", file=sys.stderr)
        rc = 1
    waste_pct = float(waste_row["derived"])
    verdict = "ok" if waste_pct <= 5.0 else "REGRESSION"
    print(f"# check flash-crowd waste-guard: hedge waste {waste_pct:.2f}% "
          f"of delivered bytes (bar 5%) {verdict}", flush=True)
    if waste_pct > 5.0:
        print("# check FAILED: hedge waste exceeded 5% of delivered bytes "
              "on the gray storm", file=sys.stderr)
        rc = 1
    return rc


def _check_broadcast_wins(rows) -> int:
    """The peer-assisted broadcast win-guard, on the freshly-run N=4
    swarm replay:

    - Swarm makespan (us_per_call) must not exceed the N-independent
      baseline's — peers serving each other must at least match N
      clients splitting the origin's uplink, or striping/coverage/
      offload quietly stopped working.
    - Origin egress on the swarm run (derived column of the
      ``origin_x`` row, bytes served over blob size) must stay <= 1.5x
      — the dissemination bound is ~1 copy; N independent clients pay
      N.  A coverage-polling or origin-offload regression shows up here
      as the origin re-serving every stripe.
    """
    by_name = {r["name"]: r for r in rows
               if r["name"].startswith("broadcast/")}
    swarm = by_name.get("broadcast/swarm/n4")
    indep = by_name.get("broadcast/independent/n4")
    origin = by_name.get("broadcast/swarm/origin_x")
    if swarm is None or indep is None or origin is None:
        print("# check: broadcast win-guard rows missing", file=sys.stderr)
        return 1
    rc = 0
    swarm_s = float(swarm["us_per_call"]) / 1e6
    indep_s = float(indep["us_per_call"]) / 1e6
    verdict = "ok" if swarm_s <= indep_s else "REGRESSION"
    print(f"# check broadcast makespan win-guard: swarm {swarm_s:.2f}s vs "
          f"independent {indep_s:.2f}s {verdict}", flush=True)
    if swarm_s > indep_s:
        print("# check FAILED: swarm makespan exceeded the N-independent "
              "baseline", file=sys.stderr)
        rc = 1
    ratio = float(origin["derived"])
    verdict = "ok" if ratio <= 1.5 else "REGRESSION"
    print(f"# check broadcast egress-guard: origin served {ratio:.2f}x the "
          f"blob at N=4 (bar 1.5x) {verdict}", flush=True)
    if ratio > 1.5:
        print("# check FAILED: origin egress exceeded 1.5x the blob on the "
              "swarm run", file=sys.stderr)
        rc = 1
    return rc


def _check_shard_wins(rows) -> int:
    """The sharded-restore win-guard, on the freshly-run K=4 straggler
    replay:

    - Work-stealing makespan (us_per_call) must not exceed the
      independent-shards baseline's — the fast hosts draining the
      straggler's span through their mirrors is the whole point, and a
      regression here means stealing, mirror advertisement, or the
      victim's coverage-gated drain quietly stopped working.
    - Stolen bytes (the ``stolen_x`` row) must be > 0 — a ledger that
      never grants a steal makes the makespan comparison vacuous (both
      runs degenerate to independent and the guard would pass while the
      feature is dead).
    """
    by_name = {r["name"]: r for r in rows
               if r["name"].startswith("shard/")}
    ws = by_name.get("shard/workstealing/k4")
    indep = by_name.get("shard/independent/k4")
    stolen = by_name.get("shard/workstealing/stolen_x")
    if ws is None or indep is None or stolen is None:
        print("# check: shard win-guard rows missing", file=sys.stderr)
        return 1
    rc = 0
    ws_s = float(ws["us_per_call"]) / 1e6
    indep_s = float(indep["us_per_call"]) / 1e6
    verdict = "ok" if ws_s <= indep_s else "REGRESSION"
    print(f"# check shard makespan win-guard: workstealing {ws_s:.2f}s vs "
          f"independent {indep_s:.2f}s {verdict}", flush=True)
    if ws_s > indep_s:
        print("# check FAILED: work-stealing makespan exceeded the "
              "independent-shards baseline", file=sys.stderr)
        rc = 1
    stolen_b = float(stolen["us_per_call"])
    verdict = "ok" if stolen_b > 0 else "REGRESSION"
    print(f"# check shard theft witness: {stolen_b / (1024 * 1024):.1f} MB "
          f"stolen on the straggler regime {verdict}", flush=True)
    if stolen_b <= 0:
        print("# check FAILED: no bytes were stolen — the ledger never "
              "granted a steal on the straggler regime", file=sys.stderr)
        rc = 1
    return rc


def _section(title: str) -> None:
    print(f"# === {title} ===", flush=True)


def _merged_rows(path: str, new_rows: list[dict]) -> list[dict]:
    """Merge this run's rows into an existing bench file's rows: re-emitted
    names are replaced in place, absent ones survive, brand-new ones
    append — a partial (``--skip``-heavy) run can't erase history."""
    try:
        with open(path) as f:
            old_rows = json.load(f).get("rows", [])
    except (OSError, ValueError):
        return new_rows
    by_name = {r["name"]: r for r in new_rows}
    merged = [by_name.pop(r["name"], r) for r in old_rows]
    return merged + [r for r in new_rows if r["name"] in by_name]


def _run_check_suite(path: str, section: str, prefixes) -> int:
    """One guard suite: re-run ``section``'s smoke bench and compare its
    steady-state rows (by ``prefixes``) against the artifact at ``path``."""
    from .common import emitted_rows, reset_rows

    try:
        with open(path) as f:
            committed = json.load(f)
    except (OSError, ValueError) as e:
        print(f"# check: cannot read {path}: {e}", file=sys.stderr)
        return 1
    base = {r["name"]: float(r["us_per_call"]) for r in committed["rows"]}

    reset_rows()
    _section(f"perf-check smoke sweep ({section})")
    if section == "autotune":
        from . import autotune_bench
        autotune_bench.main(["--quick"])
    elif section == "contention":
        from . import contention_bench
        contention_bench.main(["--quick"])
    elif section == "dataplane":
        from . import dataplane_bench
        dataplane_bench.main(["--quick"])
    elif section == "faults":
        from . import faults_bench
        faults_bench.main(["--quick"])
    elif section == "flashcrowd":
        from . import flashcrowd_bench
        flashcrowd_bench.main(["--quick"])
    elif section == "broadcast":
        from . import broadcast_bench
        broadcast_bench.main(["--quick"])
    elif section == "shard":
        from . import shard_bench
        shard_bench.main(["--quick"])
    else:
        raise ValueError(f"unknown check section: {section!r}")

    rc_extra = 0
    if section == "dataplane":
        rc_extra = _check_dataplane_wins(emitted_rows())
        if rc_extra:
            # The high-RTT trace races wall clocks like the storm
            # replays: a host-load spike can shave the duplex margin
            # without a code regression.  One replay decides.
            print("# check dataplane: guard failed, replaying the trace "
                  "once to rule out host load", flush=True)
            reset_rows()
            from . import dataplane_bench
            dataplane_bench.main(["--quick"])
            rc_extra = _check_dataplane_wins(emitted_rows())
    elif section == "faults":
        rc_extra = _check_fault_wins(emitted_rows())
    elif section == "broadcast":
        rc_extra = _check_broadcast_wins(emitted_rows())
        if rc_extra:
            # Same wall-clock-race caveat as the flash-crowd storm: a
            # host-load spike can push the swarm makespan past the
            # baseline without a code regression.  One replay decides.
            print("# check broadcast: guard failed, replaying the swarm "
                  "once to rule out host load", flush=True)
            reset_rows()
            from . import broadcast_bench
            broadcast_bench.main(["--quick"])
            rc_extra = _check_broadcast_wins(emitted_rows())
    elif section == "shard":
        rc_extra = _check_shard_wins(emitted_rows())
        if rc_extra:
            # Same wall-clock-race caveat: a host-load spike during the
            # replay can push the work-stealing makespan past the
            # baseline without a code regression.  One replay decides.
            print("# check shard: guard failed, replaying the sharded "
                  "restore once to rule out host load", flush=True)
            reset_rows()
            from . import shard_bench
            shard_bench.main(["--quick"])
            rc_extra = _check_shard_wins(emitted_rows())
    elif section == "flashcrowd":
        rc_extra = _check_flashcrowd_wins(emitted_rows())
        if rc_extra:
            # The storm replay races real wall clocks; a host-load spike
            # during the run can push the p95s or the hedge-waste pct
            # over their bars without any code regression.  One full
            # replay decides: a genuine regression fails both runs.
            print("# check flash-crowd: guard failed, replaying the "
                  "storm once to rule out host load", flush=True)
            reset_rows()
            flashcrowd_bench.main(["--quick"])
            rc_extra = _check_flashcrowd_wins(emitted_rows())

    compared, failures = 0, []
    for row in emitted_rows():
        name = row["name"]
        if not any(name.startswith(p) for p in prefixes):
            continue
        ref = base.get(name, 0.0)
        if ref <= 0.0:
            continue
        ratio = row["us_per_call"] / ref
        compared += 1
        verdict = "ok" if ratio <= CHECK_TOLERANCE else "REGRESSION"
        print(f"# check {name}: {row['us_per_call']:.0f}us vs committed "
              f"{ref:.0f}us ({ratio:.2f}x) {verdict}", flush=True)
        if ratio > CHECK_TOLERANCE:
            failures.append(name)
    if compared == 0:
        print(f"# check: no comparable steady-state rows found in {path}",
              file=sys.stderr)
        return 1
    if failures:
        print(f"# check FAILED (>{CHECK_TOLERANCE:g}x): {failures}",
              file=sys.stderr)
        return 1
    print(f"# check passed: {compared} rows within "
          f"{CHECK_TOLERANCE:g}x of {path}", flush=True)
    return rc_extra


def perf_check(path: str) -> int:
    """CI perf guard over every suite in ``CHECK_SUITES``.

    ``path`` overrides the FIRST suite's artifact (the historical
    ``--check [PATH]`` contract); the remaining suites guard their
    default artifacts.  Any suite failing (regressed row, unreadable
    artifact, or no comparable rows) fails the whole check.
    """
    rc = 0
    for i, (default_path, section, prefixes) in enumerate(CHECK_SUITES):
        suite_path = path if i == 0 else default_path
        rc |= _run_check_suite(suite_path, section, prefixes)
    return rc


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-fidelity reps/sizes (slow)")
    ap.add_argument("--skip", nargs="*", default=[],
                    help="section names to skip (fig2 fig3 fig4 fig5 table2 "
                         "autotune online contention dataplane faults "
                         "flashcrowd broadcast shard restore roofline)")
    ap.add_argument("--json", nargs="?", const="BENCH_autotune.json",
                    default=None, metavar="PATH",
                    help="also dump every emitted row as machine-readable "
                         "JSON (default path: BENCH_autotune.json); an "
                         "existing file is merged, not clobbered, so the "
                         "perf trajectory accumulates across PRs")
    ap.add_argument("--check", nargs="?", const="BENCH_autotune.json",
                    default=None, metavar="PATH",
                    help="CI perf guard: compare a smoke sweep against the "
                         "committed bench JSON; exit nonzero on any "
                         f"steady-state row regressing past "
                         f"{CHECK_TOLERANCE:g}x")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.check:
        sys.exit(perf_check(args.check))

    from .common import reset_rows
    reset_rows()

    reps = 10 if args.full else 2
    sizes = [1, 2, 4, 8, 16, 32, 64] if args.full else [1, 4, 16, 64]
    failures = []

    def run(name, fn):
        if name in args.skip:
            return
        _section(name)
        try:
            fn()
        except Exception:
            failures.append(name)
            traceback.print_exc()

    from . import paper_fig2, paper_fig3, paper_fig4, paper_fig5, paper_table2

    run("fig2", lambda: (
        paper_fig2.seeder_trace(reps=min(reps, 5)),
        paper_fig2.transfer_times(sizes, reps),
    ))
    run("fig3", lambda: paper_fig3.main(["--reps", str(reps)]))
    run("fig4", lambda: paper_fig4.main(["--reps", str(reps)]))
    run("fig5", lambda: paper_fig5.main(["--reps", str(reps)]))
    run("table2", lambda: paper_table2.main(
        ["--reps", str(max(reps // 2, 1))]
        + (["--sizes", "2", "32"] if not args.full
           else ["--sizes", "2", "8", "32", "64"])
    ))

    from . import autotune_bench
    run("autotune", lambda: autotune_bench.main(
        [] if args.full else ["--quick"]))

    from . import online_bench
    run("online", lambda: online_bench.main(
        [] if args.full else ["--quick"]))

    from . import contention_bench
    run("contention", lambda: contention_bench.main(
        [] if args.full else ["--quick"]))

    from . import dataplane_bench
    run("dataplane", lambda: dataplane_bench.main(
        [] if args.full else ["--quick"]))

    from . import faults_bench
    run("faults", lambda: faults_bench.main(
        [] if args.full else ["--quick"]))

    from . import flashcrowd_bench
    run("flashcrowd", lambda: flashcrowd_bench.main(
        [] if args.full else ["--quick"]))

    from . import broadcast_bench
    run("broadcast", lambda: broadcast_bench.main(
        [] if args.full else ["--quick"]))

    from . import shard_bench
    run("shard", lambda: shard_bench.main(
        [] if args.full else ["--quick"]))

    # Framework-layer benches (present once the substrates land).
    try:
        from . import restore_bench
        run("restore", lambda: restore_bench.main(["--quick"] if not args.full else []))
    except ImportError:
        pass
    try:
        from . import roofline
        run("roofline", lambda: roofline.report_main([]))
    except ImportError:
        pass

    if args.json:
        from .common import emitted_rows
        rows = emitted_rows()
        if os.path.exists(args.json):
            rows = _merged_rows(args.json, rows)
        payload = {
            "schema": 1,
            "driver": "benchmarks.run",
            "args": {"full": args.full, "skip": args.skip},
            "platform": {
                "python": platform.python_version(),
                "machine": platform.machine(),
            },
            "failed_sections": failures,
            "rows": rows,
        }
        try:
            import jax
            payload["platform"]["jax"] = jax.__version__
            payload["platform"]["backend"] = jax.default_backend()
        except Exception:
            pass
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")       # keep the committed artifact newline-
            # terminated (tools/format_check.py gates this repo-wide)
        print(f"# wrote {args.json} ({len(payload['rows'])} rows)",
              flush=True)

    if failures:
        print(f"# FAILED sections: {failures}", file=sys.stderr)
        sys.exit(1)
    print("# benchmarks complete", flush=True)


if __name__ == "__main__":
    main()
