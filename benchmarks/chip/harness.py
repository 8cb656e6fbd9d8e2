"""One run of one cell: set-up, the measured window, the check, the result.

``run.py`` is the command and refuses to run without a TPU; tests call
:func:`run_cell` themselves.  Everything that belongs to one configuration,
one traffic mix or one metric is a file of its own that this module finds
by the name ``BENCHMARK.json`` gives it: ``configs/<config>.json`` (as the
configuration's ``file``), ``traffic/<traffic>.json`` and
``metrics/<metric>.py``, whose ``read(run)`` returns the metric's value or
None when the run has nothing for it to read.

The window is a closed loop: one restore at a time through the program's
``restore_checkpoint(..., replicas=..., wave_bytes=...)``, timed from the
call to ``block_until_ready`` on the returned tree, then compared with
``data.bin`` and dropped, so the device holds one restored copy, as a
restoring replica does.  Restores follow each other until the next one
would not end inside ``seconds``, judged from the last one's duration;
there is always at least one.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import resource
import shutil
import statistics
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import numpy as np

from benchmarks.chip import trace as tracing
from benchmarks.chip.checkpoint import STEP, Checkpoint, Leaf, leaves_of, nested
from benchmarks.chip.mirrors import Mirror
from benchmarks.chip.reference import Layout, Verdict, compare

MiB = 1 << 20
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: this directory, as a path under the repository root
BENCH_DIR = HERE.relative_to(ROOT)
#: bytes the loopback ceiling reads from one unthrottled mirror
CEILING_BYTES = 256 * MiB
#: the warm-up restore: one leaf split like the cell's, one kept whole
WARM_LEAVES = (("warm/split", (8, 1024, 1024), (None, None, "model")),
               ("warm/whole", (1024, 1024), ()))
LIMITS = {"bytes_differing": 0, "shards_misplaced": 0, "restores_failed": 0}


@dataclass
class Restore:
    """One timed restore of the window (monotonic seconds)."""
    t_call: float
    t_ready: float
    nbytes: int
    cpu_s: float
    #: per mirror: ``served`` body bytes in this restore, ``last_byte``
    #: (None if it served nothing), ``alive`` as the mirror reports it,
    #: ``rate`` (bytes/s, 0 unthrottled)
    mirrors: list[dict]
    compiles: int = 0
    error: str | None = None
    verdict: Verdict = field(default_factory=Verdict)

    @property
    def seconds(self) -> float:
        return self.t_ready - self.t_call


@dataclass
class Run:
    """What the metric readers see."""
    setup_s: float
    restores: list[Restore]
    chips: int
    trace: tracing.Summary | None = None

    @property
    def done(self) -> list[Restore]:
        return [r for r in self.restores if r.error is None]


# -- the cell, from files found by name ---------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(bench: dict, name: str, root: Path = ROOT):
    """``(cell, configuration, traffic)`` of the workload ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(root / files[cell["config"]]) as f:
        config = json.load(f)
    with open(root / BENCH_DIR / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    return cell, config, traffic


def metrics_of(bench: dict, cell: str, trace: bool) -> list[dict]:
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def metric_reader(name: str, root: Path = ROOT):
    path = root / BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# -- instruments ----------------------------------------------------------------

class _Compiles:
    """Counts programs JAX compiles or loads from its cache."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, *_a, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class _Retunes(logging.Handler):
    """Keeps the restore's between-wave retune records with their time."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.records: list[tuple[float, str]] = []

    def emit(self, record: logging.LogRecord) -> None:
        if "between-wave retune" in str(record.msg):
            self.records.append((time.monotonic(), record.getMessage()))


def _cpu_s() -> float:
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


def emit(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


# -- the fleet ----------------------------------------------------------------

def _live_counts(traffic: dict) -> list[int]:
    """Mirror counts the retune can see: all, then one fewer per stop."""
    n = len(traffic["mirrors"])
    return list(range(n, n - len(traffic["events"]) - 1, -1))


class Fleet:
    """The traffic's mirrors, serving the files they are given.  An event
    ``{"mirror": i, "at_ideal": f, "do": "stop"}`` stops mirror ``i`` once
    a share ``f`` of the restore's ideal time (bytes over the sum of the
    rates) has passed; it serves again from the next restore on."""

    def __init__(self, traffic: dict, files: dict[str, int]):
        unknown = [e for e in traffic["events"] if e.get("do") != "stop"]
        if unknown:
            raise ValueError(f"events other than stop: {unknown}")
        self.traffic = traffic
        self.mirrors: list[Mirror] = []
        try:
            for m in traffic["mirrors"]:
                self.mirrors.append(Mirror(files, m["rate_MiBps"] * MiB,
                                           m["rtt_ms"] / 1e3))
            for m in self.mirrors:     # the processes start side by side
                m.port
        except BaseException:
            self.close()
            raise

    def replicas(self, prefix: str):
        from repro.transfer import Replica
        return [Replica("127.0.0.1", m.port, prefix) for m in self.mirrors]

    def schedule(self, nbytes: int) -> list[threading.Timer]:
        rate = sum(m.rate for m in self.mirrors)
        timers = [threading.Timer(e["at_ideal"] * nbytes / rate,
                                  self.mirrors[e["mirror"]].stop)
                  for e in self.traffic["events"]]
        for t in timers:
            t.start()
        return timers

    def reset(self, timers) -> None:
        """Cancel what has not fired; restart the mirrors the events stop."""
        for t in timers:
            t.cancel()
            t.join()
        for e in self.traffic["events"]:
            self.mirrors[e["mirror"]].start()

    def stats(self) -> list[dict]:
        return [m.stats() for m in self.mirrors]

    def close(self) -> None:
        for m in self.mirrors:
            m.close()


def _per_restore(before, after, fleet: Fleet, t_call: float) -> list[dict]:
    out = []
    for b, a, m in zip(before, after, fleet.mirrors):
        served = a["served"] - b["served"]
        out.append({"served": served, "rate": m.rate,
                    "last_byte": a["last_byte"]
                    if served and a["last_byte"] >= t_call else None,
                    "alive": a["alive"]})
    return out


# -- ceilings (printed, not metrics) ------------------------------------------

def _loopback_ceiling(ckpt: Checkpoint) -> float:
    """MiB/s one unthrottled mirror gives a bare stdlib reader."""
    import socket

    mirror = Mirror({"/d": ckpt.data_fd}, 0.0, 0.0)
    try:
        n = min(CEILING_BYTES, ckpt.total)
        buf = memoryview(bytearray(64 * MiB))
        with socket.create_connection(("127.0.0.1", mirror.port)) as s:
            t0 = time.perf_counter()
            for lo in range(0, n, len(buf)):
                hi = min(lo + len(buf), n) - 1
                s.sendall(f"GET /d HTTP/1.1\r\nHost: x\r\nRange: bytes="
                          f"{lo}-{hi}\r\n\r\n".encode())
                head = b""
                while b"\r\n\r\n" not in head:
                    head += s.recv(1)
                want, got = hi - lo + 1, 0
                while got < want:
                    got += s.recv_into(buf[got:want])
            return n / (time.perf_counter() - t0) / MiB
    finally:
        mirror.close()


def _device_put_ceiling(ckpt: Checkpoint, layout: Layout) -> float:
    leaf = max(ckpt.leaves, key=lambda l: l.nbytes)
    host = np.array(ckpt.reference(leaf))
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(host, layout.sharding(leaf)))
    return leaf.nbytes / (time.perf_counter() - t0) / MiB


# -- the run --------------------------------------------------------------------

def run_cell(bench: dict, name: str, *, seed: int, seconds: float,
             trace: bool, devices, t_start: float, restore_for=None,
             root: Path = ROOT) -> dict:
    """One run of cell ``name`` on ``devices``; returns the result line's
    object.  ``restore_for(checkpoint, layout)``, where given, returns what
    stands in for ``repro.checkpoint.restore_checkpoint`` (the tests' faults
    and the control)."""
    from repro.compile_cache import enable_compile_cache

    cell, config, traffic = load_cell(bench, name, root)
    enable_compile_cache()
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    retunes = _Retunes()
    log = logging.getLogger("repro.checkpoint.manager")
    level = log.level
    log.addHandler(retunes)
    log.setLevel(logging.INFO)
    stack = contextlib.ExitStack()
    try:
        with stack:
            return _run(bench, cell, config, traffic, seed, seconds, trace,
                        devices, t_start, restore_for, stack, compiles,
                        retunes, root)
    finally:
        log.removeHandler(retunes)
        log.setLevel(level)
        jax.monitoring.unregister_event_duration_listener(compiles)


def _warm_up(restore, fleet: Fleet, warm: Checkpoint, layout: Layout,
             wave_bytes: int) -> None:
    """The retune's sweep at every live-mirror count this traffic gives,
    then one small restore over the same mirrors."""
    from repro.core.autotune import autotune_chunk_params
    from repro.transfer import MDTPClient

    pipeline_depth = MDTPClient(fleet.replicas("/warm")).pipeline_depth
    for n in _live_counts(fleet.traffic):
        autotune_chunk_params([1e8] * n, rtt=[0.03] * n, file_size=wave_bytes,
                              pipeline_depth=pipeline_depth)
    state, _ = restore(
        "ckpt", nested(warm.leaves,
                       lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype)),
        step=STEP,
        shardings=(nested(warm.leaves, layout.sharding)
                   if layout.mesh is not None else None),
        replicas=fleet.replicas("/warm"))
    jax.block_until_ready(state)
    emit("warm_restore_bytes_differing",
         compare(state, warm, layout).bytes_differing)


def _run(bench, cell, config, traffic, seed, seconds, trace, devices,
         t_start, restore_for, stack, compiles, retunes, root) -> dict:
    phases = {"start": time.monotonic() - t_start}
    mark = time.monotonic()

    def phase(name: str) -> None:
        nonlocal mark
        phases[name] = time.monotonic() - mark
        mark = time.monotonic()

    layout = Layout(config["layout"], devices)
    leaves = leaves_of(config)
    like = nested(leaves, lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype))
    shardings = (nested(leaves, layout.sharding) if layout.mesh is not None
                 else None)
    ckpt = Checkpoint(leaves, seed, layout.devices[0])
    stack.callback(ckpt.close)
    phase("checkpoint")
    phases.update({f"checkpoint.{k}": v for k, v in ckpt.fill_s.items()})
    if restore_for is None:
        from repro.checkpoint import restore_checkpoint as restore
    else:
        restore = restore_for(ckpt, layout)
    warm_leaves = [Leaf(k, s, "bfloat16", 0.0, 1.0,
                        spec if layout.mesh is not None else None)
                   for k, s, spec in WARM_LEAVES]
    warm = Checkpoint(warm_leaves, seed + 1, layout.devices[0])
    stack.callback(warm.close)
    fleet = Fleet(traffic, {**ckpt.files("/ckpt"), **warm.files("/warm")})
    stack.callback(fleet.close)
    phase("mirrors")
    wave_bytes = ckpt.total // config["restore"]["waves"] + 1
    _warm_up(restore, fleet, warm, layout, wave_bytes)
    phase("warm_up")
    emit("ceiling_loopback_MiBps", _loopback_ceiling(ckpt))
    emit("ceiling_device_put_MiBps", _device_put_ceiling(ckpt, layout))
    emit("checkpoint_bytes", ckpt.total)
    emit("wave_bytes", wave_bytes)
    phase("ceilings")
    emit("setup_phases_s", json.dumps(phases))

    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    if trace_dir:
        stack.callback(shutil.rmtree, trace_dir, True)
    replicas = fleet.replicas("/ckpt")
    restores: list[Restore] = []
    setup_s = time.monotonic() - t_start
    t_end = time.monotonic() + seconds
    while True:
        n = len(restores)
        ckpt.stamp(n)
        tracing_now = trace_dir is not None and n == 0
        if tracing_now:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        before = fleet.stats()
        c0, r0 = compiles.n, len(retunes.records)
        state, error = None, None
        cpu0 = _cpu_s()
        t_call = time.monotonic()
        timers = fleet.schedule(ckpt.total)
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                with jax.profiler.TraceAnnotation(
                        tracing.SPAN + "restore_checkpoint"):
                    state, _ = restore("ckpt", like, step=STEP,
                                       shardings=shardings,
                                       replicas=replicas,
                                       wave_bytes=wave_bytes)
                with jax.profiler.TraceAnnotation(
                        tracing.SPAN + "block_until_ready"):
                    state = jax.block_until_ready(state)
        except Exception as e:                # counted as failed, run goes on
            error = f"{type(e).__name__}: {e}"
        t_ready = time.monotonic()
        cpu = _cpu_s() - cpu0
        fleet.reset(timers)
        rec = Restore(t_call, t_ready, ckpt.total, cpu,
                      _per_restore(before, fleet.stats(), fleet, t_call),
                      compiles.n - c0, error)
        t_compare = time.monotonic()
        if state is not None:
            with jax.profiler.TraceAnnotation(tracing.SPAN + "compare"):
                rec.verdict = compare(state, ckpt, layout)
        del state
        t_compare = time.monotonic() - t_compare
        if tracing_now:
            jax.profiler.stop_trace()
        restores.append(rec)
        emit(f"restore[{n}]", json.dumps({
            "s": rec.seconds, "MiBps": rec.nbytes / rec.seconds / MiB,
            "served": [m["served"] for m in rec.mirrors],
            "stopped": [i for i, m in enumerate(rec.mirrors)
                        if any(e is not None and t_call < e < t_ready
                               for _, e in m["alive"])],
            "retunes": [[round(t - t_call, 3), m.split(": ", 1)[-1]]
                        for t, m in retunes.records[r0:]],
            "compiles": rec.compiles,
            "compare_s": t_compare,
            "bytes_differing": rec.verdict.bytes_differing,
            "error": rec.error}))
        if time.monotonic() + rec.seconds > t_end:
            break

    peak = _memory_peak(layout.devices)
    run = Run(setup_s, restores, len(layout.devices))
    if trace_dir:
        run.trace = tracing.read(trace_dir)
    fleet_imports = {i for s in fleet.stats() for i in s["imported"]}
    if fleet_imports:
        raise RuntimeError(f"a mirror loaded {sorted(fleet_imports)}")
    return _result(bench, cell, run, trace, layout, peak, root)


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _result(bench, cell, run: Run, trace: bool, layout: Layout,
            peak: int, root: Path) -> dict:
    metrics = {}
    for m in metrics_of(bench, cell["name"], trace):
        value = metric_reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    total = Verdict()
    for r in run.restores:
        total.add(r.verdict)
    failed = sum(1 for r in run.restores
                 if r.error is not None or r.verdict.bytes_differing
                 or r.verdict.shards_misplaced)
    checks = {"bytes_differing": total.bytes_differing,
              "shards_misplaced": total.shards_misplaced,
              "restores_failed": sum(r.error is not None
                                     for r in run.restores)}
    correct = all(checks[k] <= LIMITS[k] for k in LIMITS)
    d0 = layout.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    emit("shards_checked", total.shards_checked)
    emit("compiles_in_window", sum(r.compiles for r in run.restores))
    emit("restore_s_median", statistics.median(r.seconds
                                               for r in run.restores))
    out = {"correct": correct, "attempted": len(run.restores),
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        s = run.trace
        if s is not None:
            device["busy_s"] = sum(s.busy_s) / run.chips
            device["window_s"] = s.window_s
            emit("trace_lines", json.dumps(s.lines))
            emit("trace_modules", json.dumps(s.modules))
            ops = sorted(s.op_s.items(), key=lambda kv: -kv[1])[:10]
            out["breakdown"] = {"device_ops": [list(kv) for kv in ops],
                                "idle_gaps": [list(g) for g in s.gaps[:10]]}
        else:
            device["busy_s"] = 0.0
            device["window_s"] = run.restores[0].seconds
    out["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                     for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k}: {v} (limit {LIMITS[k]})", file=sys.stderr,
              flush=True)
    return out
