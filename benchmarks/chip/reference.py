"""What decides ``correct``, and the plain reference restore.

:func:`compare` holds every leaf of a restored tree, shard by shard as it
sits on the device, to its byte slice of ``data.bin`` (the single-source
reference), and its layout to the one the configuration states.  It counts
the bytes that differ and the shards that are not where the layout puts
them; both have the limit 0.  :func:`plain_restore` is the reference put in
the program's place; with ``lower`` it is the control, every value passed
through a narrower dtype on the way.  Nothing here imports the program.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from benchmarks.chip.checkpoint import Checkpoint, Leaf, lookup, nested


@dataclass
class Verdict:
    bytes_differing: int = 0
    shards_misplaced: int = 0
    shards_checked: int = 0

    def add(self, other: "Verdict") -> None:
        self.bytes_differing += other.bytes_differing
        self.shards_misplaced += other.shards_misplaced
        self.shards_checked += other.shards_checked


class Layout:
    """Where each leaf lands: the first device, or a mesh of the devices
    under each leaf's frozen ``PartitionSpec``."""

    def __init__(self, layout: dict, devices):
        self.mesh = None
        if "mesh" in layout:
            axes = layout["mesh"]
            shape = tuple(axes.values())
            n = int(np.prod(shape))
            if len(devices) < n:
                raise ValueError(f"the layout needs {n} devices, "
                                 f"{len(devices)} given")
            self.mesh = Mesh(np.array(devices[:n]).reshape(shape),
                             tuple(axes))
            self.devices = list(devices[:n])
        else:
            self.devices = list(devices[:1])

    def sharding(self, leaf: Leaf):
        if self.mesh is None:
            return SingleDeviceSharding(self.devices[0])
        return NamedSharding(self.mesh, PartitionSpec(*leaf.spec))


def _span(index, shape) -> tuple:
    return tuple(s.indices(d) for s, d in zip(index, shape))


def _bytes(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a).reshape(-1).view(np.uint8)


def _differing(got: np.ndarray, want: np.ndarray) -> int:
    if got.shape != want.shape or got.dtype != want.dtype:
        return want.nbytes
    g, w = _bytes(got), _bytes(want)
    if g.size % 8 == 0:                 # compare 8 bytes at a time first
        if np.array_equal(g.view(np.uint64), w.view(np.uint64)):
            return 0
    elif np.array_equal(g, w):
        return 0
    return int(np.count_nonzero(g != w))


def _prefetch(state, leaves) -> None:
    """Start every shard's copy to the host, so the copies overlap the
    comparisons of the leaves before them."""
    for leaf in leaves:
        try:
            for shard in lookup(state, leaf.key).addressable_shards:
                shard.data.copy_to_host_async()
        except (KeyError, TypeError, AttributeError):
            pass


def compare(state, ckpt: Checkpoint, layout: Layout) -> Verdict:
    v = Verdict()
    _prefetch(state, ckpt.leaves)
    for leaf in ckpt.leaves:
        want = layout.sharding(leaf).devices_indices_map(leaf.shape)
        try:
            arr = lookup(state, leaf.key)
            ok = (tuple(arr.shape) == leaf.shape
                  and arr.dtype == jnp.dtype(leaf.dtype))
        except (KeyError, TypeError, AttributeError):
            ok = False
        if not ok:
            v.bytes_differing += leaf.nbytes
            v.shards_misplaced += len(want)
            continue
        ref = ckpt.reference(leaf)
        seen = set()
        for shard in arr.addressable_shards:
            place = want.get(shard.device)
            if (place is None or shard.device in seen
                    or _span(shard.index, leaf.shape)
                    != _span(place, leaf.shape)):
                v.shards_misplaced += 1
            seen.add(shard.device)
            v.bytes_differing += _differing(np.asarray(shard.data),
                                            ref[shard.index])
            v.shards_checked += 1
        v.shards_misplaced += len(set(want) - seen)
        del ref
    return v


def plain_restore(ckpt: Checkpoint, layout: Layout, lower=None):
    """Every leaf read from its slice of ``data.bin`` and put on the
    device as the layout says; with ``lower`` (a dtype), each value is
    first rounded to it and back on the host.  (Not on the device: the
    TPU compiler removes a convert pair inside one program, and the
    control then reads exactly the reference.)"""
    def put(leaf: Leaf):
        host = np.array(ckpt.reference(leaf))
        if lower is not None:
            host = host.astype(lower).astype(host.dtype)
        return jax.device_put(host, layout.sharding(leaf))

    return nested(ckpt.leaves, put)
