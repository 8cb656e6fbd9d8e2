"""Bring-up smoke of the checkpoint-restore path on a TPU.

Qwen3-1.7B's parameters (bf16, random from ``--seed``) are built on the
chip, saved, and restored through ``restore_checkpoint(..., replicas=...)``
from six in-process mirrors at the MDTP paper's baseline rates
(``core.scenarios.paper_baseline``: 12-70 MiB/s, 30 ms RTT).  The slowest
mirror is stopped partway through, and the restore runs in waves so the
fused (C, L) retune sweep runs on the chip between them.  Every restored
byte is then checked against ``data.bin`` and against the original.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded restore onto a 1x4 mesh only

Earlier lines of standard output are ``name: value`` measurements.  The last
line is ``{"ok": true, "device": {...}}``; any failure exits non-zero with
the traceback and without that line.  The program refuses to run when JAX
finds no TPU.  Everything runs in this one process (the mirrors are
threads), because a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import tempfile
import threading
import time
import warnings
from dataclasses import dataclass, field

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import restore_checkpoint, save_checkpoint  # noqa: E402
from repro.checkpoint.manager import _leaf_paths  # noqa: E402
from repro.configs.qwen3_1_7b import CONFIG as QWEN3_1_7B  # noqa: E402
from repro.core.autotune import _fused_sweep, default_grid, sweep_call  # noqa: E402
from repro.core.jax_sim import SimConfig  # noqa: E402
from repro.core.scenarios import paper_baseline  # noqa: E402
from repro.distributed.context import activate  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.models.common import (ParamSpec, abstract_params,  # noqa: E402
                                 init_params, sharding_tree)
from repro.models.transformer import model_specs  # noqa: E402
from repro.transfer import (RangeServer, Replica, Throttle,  # noqa: E402
                            TunerFailureWarning)

STEP = 1
SWEEP_SEEDS = 4
#: logical axes the default rules shard over ``model`` (tensor parallel)
TP_AXES = ("qheads", "kv_heads", "mlp", "vocab")


def emit(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


def check(ok: bool, detail=None) -> None:
    """A smoke check: raises (whatever the interpreter's ``-O``)."""
    if not ok:
        raise AssertionError(detail)


def build_checkpoint(cfg, seed: int, root: str):
    """Parameters of ``cfg`` on the default device, saved under ``root``.
    Returns ``(specs, params, step_dir, timings)``."""
    specs = model_specs(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(jax.random.PRNGKey(seed), specs, jnp.bfloat16))
    t1 = time.perf_counter()
    step_dir = save_checkpoint(root, STEP, params)
    t2 = time.perf_counter()
    return specs, params, step_dir, {"init_s": t1 - t0, "save_s": t2 - t1}


def read_checkpoint(step_dir: str) -> tuple[dict, bytes, bytes]:
    """``(manifest, manifest bytes, data.bin)``, each file read once."""
    with open(os.path.join(step_dir, "manifest.json"), "rb") as f:
        manifest_raw = f.read()
    with open(os.path.join(step_dir, "data.bin"), "rb") as f:
        blob = f.read()
    return json.loads(manifest_raw), manifest_raw, blob


def start_mirrors(manifest_raw: bytes, blob: bytes, rates, rtt: float):
    """One throttled loopback ``RangeServer`` per rate, all serving the SAME
    in-memory ``data.bin`` (``add_blob``: no per-mirror copy)."""
    base = f"/ckpt/step_{STEP:010d}"
    servers = []
    try:
        for rate in rates:
            s = RangeServer(throttle=Throttle(
                bytes_per_s=rate, latency_s=rtt, shared=True)).start()
            servers.append(s)
            s.add_blob(base + "/manifest.json", manifest_raw)
            s.add_blob(base + "/data.bin", blob)
    except BaseException:
        for s in servers:
            s.stop()
        raise
    return servers


class _RetuneLog(logging.Handler):
    """Collects the restore's between-wave retune records."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.messages: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        if "retune" in record.msg:
            self.messages.append(record.getMessage())


@dataclass
class RestoreRun:
    state: object
    seconds: float                       # call -> block_until_ready
    retunes: list = field(default_factory=list)
    tuner_failures: list = field(default_factory=list)
    served: list = field(default_factory=list)
    stopped: int = -1


def restore_from_mirrors(root: str, like, servers, *, wave_bytes: int,
                         stop: int, stop_after_s: float,
                         shardings=None) -> RestoreRun:
    """``restore_checkpoint`` from ``servers`` with mirror ``stop`` stopped
    (listener and open connections) ``stop_after_s`` into the restore.
    Timed until every leaf is resident (``block_until_ready``)."""
    replicas = [Replica("127.0.0.1", s.port, "/ckpt") for s in servers]
    stopped = threading.Event()

    def kill():
        servers[stop].stop()
        servers[stop].kill_connections()
        stopped.set()

    timer = threading.Timer(stop_after_s, kill)
    handler = _RetuneLog()
    logger = logging.getLogger("repro.checkpoint.manager")
    prev_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always", TunerFailureWarning)
            t0 = time.perf_counter()
            timer.start()
            state, step = restore_checkpoint(
                root, like, step=STEP, shardings=shardings,
                replicas=replicas, wave_bytes=wave_bytes)
            state = jax.block_until_ready(state)
            seconds = time.perf_counter() - t0
    finally:
        timer.cancel()
        timer.join()
        logger.removeHandler(handler)
        logger.setLevel(prev_level)
    check(step == STEP, step)
    return RestoreRun(
        state=state, seconds=seconds, retunes=handler.messages,
        tuner_failures=[str(w.message) for w in caught
                        if issubclass(w.category, TunerFailureWarning)],
        served=[s.served_bytes for s in servers],
        stopped=stop if stopped.is_set() else -1)


def _leaf_bytes(blob: bytes, entry: dict) -> np.ndarray:
    """The leaf's array, read straight from its byte slice of ``data.bin``
    — the single-source reference."""
    count = int(np.prod(entry["shape"])) if entry["shape"] else 1
    return np.frombuffer(blob, np.dtype(entry["dtype"]), count,
                         int(entry["offset"])).reshape(entry["shape"])


def _same_bytes(a: np.ndarray, b: np.ndarray) -> bool:
    return a.shape == b.shape and a.dtype == b.dtype and np.array_equal(
        np.ascontiguousarray(a).view(np.uint8),
        np.ascontiguousarray(b).view(np.uint8))


def verify_against_blob(state, manifest: dict, blob: bytes) -> int:
    """Every addressable shard of every restored leaf, brought back to the
    host, equals its slice of ``data.bin`` bit for bit.  Returns the number
    of shards checked."""
    by_key = {e["key"]: e for e in manifest["leaves"]}
    leaves, _ = _leaf_paths(state)
    check(len(leaves) == len(by_key), (len(leaves), len(by_key)))
    checked = 0
    for key, leaf in leaves:
        ref = _leaf_bytes(blob, by_key[key])
        for shard in leaf.addressable_shards:
            got = np.asarray(shard.data)
            if not _same_bytes(got, ref[shard.index]):
                raise AssertionError(f"{key} shard {shard.index} on "
                                     f"{shard.device} differs from data.bin")
            checked += 1
    return checked


@jax.jit
def _same_bits(a, b):
    u = jnp.dtype(f"uint{8 * a.dtype.itemsize}")
    return jnp.array_equal(jax.lax.bitcast_convert_type(a, u),
                           jax.lax.bitcast_convert_type(b, u))


def verify_against_original(restored, original) -> int:
    """Each restored leaf equals the original on the device, compared as
    raw bits.  Returns the number of leaves checked."""
    pairs = list(zip(_leaf_paths(restored)[0], _leaf_paths(original)[0]))
    for (key, a), (key_b, b) in pairs:
        check(key == key_b, (key, key_b))
        if not bool(_same_bits(a, b)):
            raise AssertionError(f"{key} differs from the original")
    return len(pairs)


def check_shard_spread(state, specs, ctx) -> int:
    """Every leaf is laid out as the active sharding rules say: tensor-
    parallel dims (heads, mlp, vocab) split over all of ``model``, the rest
    replicated, and every device holds a shard.  Returns the number of
    split leaves."""
    spec_leaves = jax.tree.leaves(specs,
                                  is_leaf=lambda x: isinstance(x, ParamSpec))
    devices = set(ctx.mesh.devices.flat)
    n_model = ctx.axis_size("model")
    split = 0
    for (key, leaf), spec in zip(_leaf_paths(state)[0], spec_leaves):
        want = ctx.spec(spec.logical, spec.shape)
        check(leaf.sharding.spec == want, (key, leaf.sharding.spec, want))
        shards = leaf.addressable_shards
        check({s.device for s in shards} == devices, key)
        tp = [d for d, name in enumerate(spec.logical) if name in TP_AXES]
        if tp and n_model > 1:
            d = tp[0]
            check(want[d] == "model", (key, want))
            starts = {s.index[d].start or 0 for s in shards}
            check(len(starts) == n_model, (key, starts))
            check(all(s.data.shape[d] == leaf.shape[d] // n_model
                      for s in shards), key)
            split += 1
        else:
            check(all(s.data.shape == leaf.shape for s in shards), key)
    return split


def run_sweep(device, rates, rtt, file_size: int):
    """The retune's fused sweep (Table II grid, ``SWEEP_SEEDS`` seeds, no
    jitter) placed on ``device``.  Returns ``(times [G, K] on the host,
    devices of the output, first-call seconds, warm-call seconds)``."""
    args, static = sweep_call(rates, rtt, file_size, default_grid(),
                              SimConfig(), n_seeds=SWEEP_SEEDS)
    args = jax.device_put(args, device)
    t0 = time.perf_counter()
    out = jax.block_until_ready(_fused_sweep(*args, **static))
    t1 = time.perf_counter()
    jax.block_until_ready(_fused_sweep(*args, **static))
    t2 = time.perf_counter()
    return np.asarray(out), out.devices(), t1 - t0, t2 - t1


def _count_cache_events() -> dict:
    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def smoke(cfg, *, seed: int, rates, rtt: float, mesh=None,
          emit=emit) -> dict:
    """The whole check at ``cfg``'s widths on the default device, or with
    ``mesh`` the sharded restore onto it and nothing else.  ``emit``
    receives the measurements; any failed check raises."""
    stop = int(np.argmin(rates))            # the slowest mirror
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        specs, params, step_dir, t = build_checkpoint(cfg, seed, root)
        manifest, manifest_raw, blob = read_checkpoint(step_dir)
    # the checkpoint directory is gone: the mirrors serve from memory
    total = len(blob)
    wave_bytes = total // 4 + 1             # three between-wave retunes
    stop_after_s = 0.35 * total / sum(rates)
    emit("model", f"{cfg.name}, {sum(p.size for p in jax.tree.leaves(params))}"
         f" bf16 parameters in {len(manifest['leaves'])} leaves")
    emit("init_s", t["init_s"])
    emit("save_s", t["save_s"])
    emit("checkpoint_bytes", total)
    emit("wave_bytes", wave_bytes)
    emit("mirror_rates_MiBps", [r / 2**20 for r in rates])
    emit("mirror_stopped", f"#{stop} after {stop_after_s:.3f} s")

    servers = start_mirrors(manifest_raw, blob, rates, rtt)
    try:
        if mesh is None:
            run = restore_from_mirrors(
                root, abstract_params(specs), servers, wave_bytes=wave_bytes,
                stop=stop, stop_after_s=stop_after_s)
        else:
            del params                      # compared with data.bin only
            with activate(mesh) as ctx:
                run = restore_from_mirrors(
                    root, abstract_params(specs), servers,
                    wave_bytes=wave_bytes, stop=stop,
                    stop_after_s=stop_after_s,
                    shardings=sharding_tree(specs))
                split = check_shard_spread(run.state, specs, ctx)
            emit("tp_split_leaves", f"{split} of {len(manifest['leaves'])}, "
                 f"over {mesh.devices.size} devices")
    finally:
        for s in servers:
            s.stop()                        # idempotent for the stopped one
    emit("restore_to_resident_s", run.seconds)
    emit("restore_MiBps", total / run.seconds / 2**20)
    emit("served_bytes_per_mirror", run.served)
    emit("retunes", len(run.retunes))
    for m in run.retunes:
        emit("retune", m)
    emit("tuner_failures", len(run.tuner_failures))
    emit("fused_sweep_cache_entries", _fused_sweep._cache_size())
    check(run.stopped == stop, "the slow mirror was not stopped mid-restore")
    check(sum(run.served) >= total, run.served)
    check(not run.tuner_failures, run.tuner_failures)
    check(len(run.retunes) >= 2, run.retunes)
    check(_fused_sweep._cache_size() > 0)

    t0 = time.perf_counter()
    shards = verify_against_blob(run.state, manifest, blob)
    if mesh is None:
        verify_against_original(run.state, params)
    emit("verified_shards_bit_exact", shards)
    emit("verify_s", time.perf_counter() - t0)
    result = {"restore": run, "shards": shards}
    if mesh is not None:
        result["split"] = split
        return result

    device = jax.devices()[0]
    dev_times, dev_on, first_s, warm_s = run_sweep(device, rates, rtt,
                                                   wave_bytes)
    cpu_times, _, _, _ = run_sweep(jax.devices("cpu")[0], rates, rtt,
                                   wave_bytes)
    grid = default_grid()
    best_dev = int(np.argmin(dev_times.mean(axis=1)))
    best_cpu = int(np.argmin(cpu_times.mean(axis=1)))
    rel = float(np.max(np.abs(dev_times - cpu_times) / np.abs(cpu_times)))
    emit("tuner_sweep_device", f"{sorted(str(d) for d in dev_on)} "
         f"shape {list(dev_times.shape)}")
    emit("tuner_first_call_s", first_s)
    emit("tuner_sweep_s", warm_s)
    emit("tuner_choice_device_cpu", f"{grid[best_dev]} {grid[best_cpu]}")
    emit("tuner_max_rel_diff_device_cpu", rel)
    check(dev_on == {device}, dev_on)
    check(dev_times.shape == (len(grid), SWEEP_SEEDS), dev_times.shape)
    check(best_dev == best_cpu, (grid[best_dev], grid[best_cpu]))
    check(rel <= 1e-4, rel)
    result["rel"] = rel
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded restore onto a 1x4 mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random parameters")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU; JAX found "
                         f"{devices[0].platform!r} devices")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX found {len(devices)}")

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    cache = _count_cache_events()
    emit("device_kind", devices[0].device_kind)
    emit("device_count", len(devices))
    emit("compile_cache", f"{cache_dir}, {n_cached} entries at start")

    fleet = paper_baseline()
    mesh = make_local_mesh(data=1, model=4) if args.chips == 4 else None
    smoke(QWEN3_1_7B, seed=args.seed, rates=[s.bandwidth for s in fleet],
          rtt=fleet[0].rtt, mesh=mesh)

    emit("compile_cache_hits_misses", f"{cache['hits']} {cache['misses']}")
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        emit(f"peak_bytes_in_use[{d.id}]", stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
