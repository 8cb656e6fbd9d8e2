"""Moonlight-16B-A3B's checkpoint shape (``repro.configs.moonlight_16b_a3b``):
its size as published, the chip benchmark's frozen leaf table, and a
restore of the reduced tree onto four devices through
``restore_checkpoint``, held shard by shard to ``data.bin``."""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest
from jax.sharding import AbstractMesh

from repro.configs import moonlight_16b_a3b as moonlight
from repro.models.common import spec_tree_num_params

REPO = Path(__file__).resolve().parents[1]
FROZEN = REPO / "benchmarks/chip/configs/moonlight-16b-a3b-ep4.json"


def test_published_config_is_16b_parameters():
    n = spec_tree_num_params(moonlight.checkpoint_specs())
    assert abs(n - 15.96e9) <= 0.005 * 15.96e9, n


def test_frozen_leaf_table_is_the_specs_under_the_default_rules():
    """The benchmark's file is ``checkpoint_specs(depth=5)`` on a 1x4
    mesh: the same keys, shapes, dtypes, values and specs, in order, and
    the published config with only its depth cut."""
    frozen = json.loads(FROZEN.read_text())
    mesh = AbstractMesh((1, 4), ("data", "model"))
    assert frozen["layout"]["mesh"] == {"data": 1, "model": 4}
    assert frozen["leaves"] == moonlight.leaf_table(
        moonlight.checkpoint_specs(depth=5), mesh)
    for key, value in moonlight.CONFIG.items():
        want = 5 if key == "num_hidden_layers" else value
        assert frozen[key] == want, key
    assert frozen["source"] == moonlight.SOURCE
    nbytes = sum(math.prod(e["shape"])
                 * (4 if e["dtype"] == "float32" else 2)
                 for e in frozen["leaves"])
    assert nbytes == 6_186_911_744


def test_reduced_keeps_every_leaf_kind_and_placement():
    """The CPU tree has the full tree's leaves, logical axes and
    placements at small widths, with 2 experts for each of 4 devices."""
    mesh = AbstractMesh((1, 4), ("data", "model"))
    full = moonlight.leaf_table(moonlight.checkpoint_specs(depth=3), mesh)
    small = moonlight.leaf_table(
        moonlight.checkpoint_specs(cfg=moonlight.reduced()), mesh)
    assert [(e["key"], e["dtype"], e["spec"]) for e in small] == \
        [(e["key"], e["dtype"], e["spec"]) for e in full]
    assert moonlight.reduced()["n_routed_experts"] >= 8
    with pytest.raises(ValueError):
        moonlight.checkpoint_specs(depth=1)


_FOUR_DEVICE_RESTORE = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {tests!r})
import jax
from jax.sharding import NamedSharding, PartitionSpec
from test_moonlight import restore_reduced, check
state, placed, data = restore_reduced({tmp!r})
moe = state["blocks"]["moe"]
replicated = NamedSharding(placed["blocks"]["moe"]["wi"].mesh, PartitionSpec())
faults = {{
    None: state,
    "experts_replicated": dict(state, blocks=dict(state["blocks"], moe=dict(
        moe, **{{k: jax.device_put(moe[k], replicated)
                for k in ("wi", "wg", "wo")}}))),
    "whole_on_device0": jax.tree.map(
        lambda x: jax.device_put(x, jax.devices()[0]), state),
}}
print("VERDICTS", json.dumps({{str(k): check(v, placed, data)
                              for k, v in faults.items()}}))
"""


def restore_reduced(tmp: str):
    """The reduced tree saved, served by three throttled mirrors, the
    slowest stopped mid-restore, and restored in four waves onto a 1x4
    mesh.  Returns the restored tree, its shardings and the ``data.bin``
    directory.  (Run in a process with four devices.)"""
    import threading

    import jax
    import jax.numpy as jnp

    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.distributed.context import activate
    from repro.launch.mesh import make_local_mesh
    from repro.models.common import init_params, sharding_tree
    from repro.transfer import RangeServer, Replica, Throttle

    specs = moonlight.checkpoint_specs(cfg=moonlight.reduced())
    table = moonlight.leaf_table(specs, AbstractMesh((1, 4),
                                                     ("data", "model")))
    values = init_params(jax.random.PRNGKey(15), specs, jnp.float32)
    dtypes = iter(e["dtype"] for e in table)
    state = jax.tree.map(lambda x: x.astype(next(dtypes)), values)
    d = save_checkpoint(tmp, 15, state)
    total = os.path.getsize(os.path.join(d, "data.bin"))
    mesh = make_local_mesh(data=1, model=4)
    with activate(mesh):
        placed = sharding_tree(specs)
    MB = 1 << 20
    servers = []
    for rate in (2 * MB, 4 * MB, 8 * MB):
        s = RangeServer(throttle=Throttle(bytes_per_s=rate)).start()
        for name in ("manifest.json", "data.bin"):
            s.add_file(f"/ckpt/step_0000000015/{name}", os.path.join(d, name))
        servers.append(s)
    victim, killed = servers[0], threading.Event()

    def kill():         # the accept loop and every open connection
        victim.stop()
        victim.kill_connections()
        killed.set()

    stop = threading.Timer(0.15, kill)
    try:
        stop.start()
        restored, _ = restore_checkpoint(
            tmp, state, step=15, shardings=placed,
            replicas=[Replica("127.0.0.1", s.port, "/ckpt")
                      for s in servers],
            wave_bytes=total // 4 + 1)
        restored = jax.block_until_ready(restored)
    finally:
        stop.cancel()
        for s in servers[1:] if killed.is_set() else servers:
            s.stop()
    assert killed.is_set() and 0 < victim.served_bytes < total // 2

    return restored, placed, d


def check(state, placed, d: str) -> dict:
    """Every shard of ``state`` against a plain single-source reference:
    its leaf read with ``np.frombuffer`` from ``data.bin`` and indexed as
    ``placed`` (the same shardings) puts it on each device."""
    import jax
    import numpy as np

    from repro.checkpoint.manager import _leaf_paths

    manifest = json.load(open(os.path.join(d, "manifest.json")))
    blob = open(os.path.join(d, "data.bin"), "rb").read()
    by_key = {e["key"]: e for e in manifest["leaves"]}
    got, _ = _leaf_paths(state)
    want = dict(_leaf_paths(placed)[0])
    out = {"bytes_differing": 0, "shards_misplaced": 0, "shards": 0}
    for key, arr in got:
        e = by_key[key]
        ref = np.frombuffer(blob, jax.numpy.dtype(e["dtype"]),
                            int(np.prod(e["shape"])),
                            e["offset"]).reshape(e["shape"])
        places = want[key].devices_indices_map(tuple(e["shape"]))
        seen = set()
        for shard in arr.addressable_shards:
            place = places.get(shard.device)
            span = [s.indices(n) for s, n in zip(shard.index, e["shape"])]
            if (place is None or shard.device in seen
                    or span != [s.indices(n)
                                for s, n in zip(place, e["shape"])]):
                out["shards_misplaced"] += 1
            seen.add(shard.device)
            a = np.asarray(shard.data).reshape(-1).view(np.uint8)
            b = np.ascontiguousarray(ref[shard.index]).reshape(-1).view(
                np.uint8)
            out["bytes_differing"] += (int(np.count_nonzero(a != b))
                                       if a.size == b.size else b.size)
            out["shards"] += 1
        out["shards_misplaced"] += len(set(places) - seen)
    return out


@pytest.fixture(scope="module")
def verdicts(tmp_path_factory):
    """One restore in a subprocess with four virtual CPU devices (the
    device count is fixed when JAX starts), checked as it landed and with
    each planted fault."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    prog = _FOUR_DEVICE_RESTORE.format(
        tests=os.path.dirname(__file__),
        tmp=str(tmp_path_factory.mktemp("moonlight")))
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=300, cwd=str(REPO))
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.split("VERDICTS ", 1)[1])


@pytest.mark.parametrize("fault", [None, "experts_replicated",
                                   "whole_on_device0"])
def test_reduced_restore_onto_four_devices(verdicts, fault):
    """Sound, every shard lands byte-exact where the rules put it; with a
    planted fault (routed experts replicated, or every leaf whole on
    device 0) the same check finds misplaced shards."""
    verdict = verdicts[str(fault)]
    assert verdict["bytes_differing"] == 0, verdict
    if fault is None:
        # 28 leaves, each on all 4 devices (split or replicated)
        assert verdict == {"bytes_differing": 0, "shards_misplaced": 0,
                           "shards": 28 * 4}
    else:
        assert verdict["shards_misplaced"] > 0, verdict
