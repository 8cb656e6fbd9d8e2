"""The checkpoint the mirrors serve: made from the seed, held in memory.

``data.bin`` and ``manifest.json`` are written in checkpoint format 1, the
format ``repro.checkpoint.save_checkpoint`` writes: every leaf's bytes
packed back to back, and a manifest with each leaf's key, shape, dtype,
offset and length.  The benchmark writes them itself, so that the
reference bytes owe nothing to the program under test, and it writes them
into memory files (``memfd``), which the mirror processes inherit and map:
nothing goes to disk, and no mirror holds a copy of its own.

The values are drawn on the device by one jitted program from the seed,
``mean + std * N(0, 1)`` per leaf in float32, then cast to the leaf's
dtype, so a later compressed-range cell reads weight-like bytes.
"""

from __future__ import annotations

import json
import math
import mmap
import os
import threading
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

STEP = 1
#: values redrawn in each leaf before every restore of a window
STAMP_VALUES = 256


@dataclass(frozen=True)
class Leaf:
    key: str
    shape: tuple
    dtype: str
    mean: float
    std: float
    spec: tuple | None = None      # PartitionSpec entries on a mesh layout

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * np.dtype(self.dtype).itemsize


def leaves_of(config: dict) -> list[Leaf]:
    return [Leaf(e["key"], tuple(e["shape"]), e["dtype"], float(e["mean"]),
                 float(e["std"]),
                 tuple(e["spec"]) if "spec" in e else None)
            for e in config["leaves"]]


def nested(leaves, value) -> dict:
    """A tree of nested dicts keyed by the parts of each leaf's key."""
    tree: dict = {}
    for leaf in leaves:
        *parents, name = leaf.key.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = value(leaf)
    return tree


def lookup(tree, key: str):
    for part in key.split("/"):
        tree = tree[part]
    return tree


def _seed_words(seed: int) -> np.ndarray:
    s = int(seed) % (1 << 64)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def _generator(leaves):
    """One jitted program: threefry key data ``uint32[2]`` -> every leaf.
    The seed is an argument, so every seed runs the one compiled program."""
    def gen(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        out = []
        for i, leaf in enumerate(leaves):
            x = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            out.append((x * leaf.std + leaf.mean).astype(leaf.dtype))
        return tuple(out)

    return jax.jit(gen)


class Checkpoint:
    """``data.bin`` and ``manifest.json`` of one seed, in memory files.

    ``files(prefix)`` gives the URL paths and descriptors a mirror serves;
    ``reference(leaf)`` is the leaf's array read straight from its byte
    slice of ``data.bin``, the single-source reference; ``stamp(n)``
    redraws a few values of every leaf before restore ``n`` of a window."""

    def __init__(self, leaves, seed: int, device=None):
        self.leaves = list(leaves)
        self.seed = int(seed)
        offsets, off = [], 0
        for leaf in self.leaves:
            offsets.append(off)
            off += leaf.nbytes
        self.total = off
        self.offsets = dict(zip((l.key for l in self.leaves), offsets))
        manifest = {"step": STEP, "format": 1, "total_bytes": self.total,
                    "leaves": [{"key": l.key, "shape": list(l.shape),
                                "dtype": l.dtype, "offset": o,
                                "nbytes": l.nbytes}
                               for l, o in zip(self.leaves, offsets)]}
        self._fds: list[int] = []
        self.data_fd = self._memfd("data.bin", self.total)
        self._manifest_fd = self._memfd("manifest.json", 0)
        os.write(self._manifest_fd, json.dumps(manifest).encode())
        #: seconds of the fill: ``generate`` on the device, ``to_host``,
        #: ``pages`` (waiting for the memory file's pages), ``write``
        self.fill_s: dict[str, float] = {}
        self._fill(device)

    def _memfd(self, name: str, size: int) -> int:
        fd = os.memfd_create(name)
        self._fds.append(fd)
        os.ftruncate(fd, size)
        return fd

    def _map_pages(self) -> None:
        """Allocate and map every page of ``data.bin`` up front (the
        kernel's part of the fill, run beside the device's part)."""
        if self.total:
            os.posix_fallocate(self.data_fd, 0, self.total)
        self._map = mmap.mmap(self.data_fd, self.total,
                              flags=mmap.MAP_SHARED | mmap.MAP_POPULATE)

    def _fill(self, device) -> None:
        pages = threading.Thread(target=self._map_pages)
        pages.start()
        try:
            gen = _generator(self.leaves)
            key = _seed_words(self.seed)
            if device is not None:
                key = jax.device_put(key, device)
            t0 = time.monotonic()
            arrays = jax.block_until_ready(gen(key))
            t1 = time.monotonic()
            for a in arrays:
                a.copy_to_host_async()
            host = [np.asarray(a) for a in arrays]
            del arrays
            t2 = time.monotonic()
        finally:
            pages.join()
        t3 = time.monotonic()
        for leaf, h in zip(self.leaves, host):
            dst = np.frombuffer(self._map, np.uint8, leaf.nbytes,
                                self.offsets[leaf.key])
            dst[:] = h.reshape(-1).view(np.uint8)
        del host, dst
        self.fill_s = {"generate": t1 - t0, "to_host": t2 - t1,
                       "pages": t3 - t2, "write": time.monotonic() - t3}

    def files(self, prefix: str) -> dict[str, int]:
        base = f"{prefix}/step_{STEP:010d}"
        return {f"{base}/manifest.json": self._manifest_fd,
                f"{base}/data.bin": self.data_fd}

    def reference(self, leaf: Leaf) -> np.ndarray:
        return np.frombuffer(self._map, np.dtype(leaf.dtype),
                             math.prod(leaf.shape),
                             self.offsets[leaf.key]).reshape(leaf.shape)

    def stamp(self, n: int) -> None:
        """Redraw ``STAMP_VALUES`` consecutive values of every leaf, at
        places drawn from ``(seed, n)``: a restore that hands back an
        earlier restore's tree no longer matches ``data.bin``."""
        rng = np.random.default_rng([self.seed % (1 << 63), n])
        for leaf in self.leaves:
            count = math.prod(leaf.shape)
            k = min(STAMP_VALUES, count)
            at = int(rng.integers(0, count - k + 1))
            vals = (rng.standard_normal(k, np.float32) * leaf.std
                    + leaf.mean).astype(jnp.dtype(leaf.dtype))
            size = np.dtype(leaf.dtype).itemsize
            off = self.offsets[leaf.key] + at * size
            self._map[off:off + k * size] = vals.tobytes()

    def close(self) -> None:
        try:
            self._map.close()
        except BufferError:
            # a reference view is still alive somewhere; the map goes with
            # the process
            pass
        for fd in self._fds:
            os.close(fd)
        self._fds = []
