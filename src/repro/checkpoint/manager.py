"""Sharded checkpointing with atomic commits and MDTP multi-source restore.

Format (one directory per step):
    step_00001000/
      data.bin        all leaves packed back-to-back (byte offsets in manifest)
      manifest.json   step, leaf paths/shapes/dtypes/offsets; written LAST via
                      tmp+rename => a directory with a manifest is complete.

Packing everything into one blob is deliberate: a restore is then exactly
the paper's problem — one large object, replicated on several mirrors —
and ``restore(..., replicas=...)`` pulls it with MDTP adaptive byte-range
chunking across all mirrors at once (``repro.transfer.MDTPClient``).  After
a node failure or an elastic re-scale this is the path that gets thousands
of hosts back to work; a dead mirror mid-restore just means its range goes
back to the pool (each byte still fetched exactly once).

Elasticity: ``restore`` takes target shardings — leaves are ``device_put``
to whatever mesh the NEW job runs, so restoring 16x16 state onto 2x16x16
(or a reduced salvage mesh) is the same call.

Fault-tolerance inventory (tested in tests/test_checkpoint.py):
  * atomic manifests -> a crashed save never corrupts restore state,
  * keep-last-k GC never deletes the newest complete step,
  * async save thread -> training continues during serialization,
  * multi-source restore tolerates mirror death mid-transfer.
"""

from __future__ import annotations

import bisect
import contextlib
import gc
import json
import logging
import mmap
import os
import shutil
import threading
import warnings
from dataclasses import dataclass, replace as _dc_replace
from typing import Any, Optional, Sequence

import jax
import numpy as np

from repro import obs
from repro.transfer.client import (MDTPClient, NoTelemetryError, Replica,
                                   TunerFailureWarning)
from repro.transfer.journal import ResumeJournal, claim_interval

__all__ = ["CheckpointManager", "RestoreOptions", "save_checkpoint",
           "restore_checkpoint", "latest_step"]

log = logging.getLogger(__name__)

_MANIFEST = "manifest.json"
_DATA = "data.bin"


def _step_dir(root: str, step: int) -> str:
    return os.path.join(root, f"step_{step:010d}")


def _leaf_paths(tree: Any):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in leaves:
        key = "/".join(
            str(p.key) if hasattr(p, "key") else str(p.idx) for p in path)
        out.append((key, leaf))
    return out, treedef


def save_checkpoint(root: str, step: int, state: Any) -> str:
    """Blocking save.  Returns the committed directory."""
    d = _step_dir(root, step)
    tmp = d + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)

    leaves, _ = _leaf_paths(state)
    manifest = {"step": step, "format": 1, "leaves": []}
    offset = 0
    with open(os.path.join(tmp, _DATA), "wb") as f:
        for key, leaf in leaves:
            arr = np.asarray(jax.device_get(leaf))
            raw = arr.tobytes()
            manifest["leaves"].append({
                "key": key, "shape": list(arr.shape), "dtype": str(arr.dtype),
                "offset": offset, "nbytes": len(raw),
            })
            f.write(raw)
            offset += len(raw)
        f.flush()
        os.fsync(f.fileno())
    manifest["total_bytes"] = offset
    mpath = os.path.join(tmp, _MANIFEST)
    with open(mpath + ".tmp", "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    os.rename(mpath + ".tmp", mpath)     # manifest-last commit inside tmp
    if os.path.exists(d):
        shutil.rmtree(d)
    os.rename(tmp, d)                    # atomic publish
    return d


def latest_step(root: str) -> Optional[int]:
    """Newest step with a COMPLETE manifest (crashed saves are ignored)."""
    if not os.path.isdir(root):
        return None
    steps = []
    for name in os.listdir(root):
        if name.startswith("step_") and not name.endswith(".tmp"):
            if os.path.exists(os.path.join(root, name, _MANIFEST)):
                try:
                    steps.append(int(name.split("_")[1]))
                except ValueError:
                    pass
    return max(steps) if steps else None


def _on_cpu(sharding: Any) -> bool:
    """Whether ``jax.device_put(x, sharding)`` lands on a CPU device, where
    it may alias ``x``'s memory instead of copying it."""
    if sharding is None:
        sharding = jax.config.jax_default_device or jax.devices()[0]
    if isinstance(sharding, str):           # a platform name
        return sharding == "cpu"
    devices = (sharding.device_set
               if isinstance(sharding, jax.sharding.Sharding) else {sharding})
    return any(d.platform == "cpu" for d in devices)


class _StreamingRestore:
    """Range sink for ``MDTPClient.fetch``: overlap network with H2D.

    Ranges land in one buffer of the whole blob, and the moment the last
    byte of a leaf's range arrives that leaf is ``device_put`` — so
    host→device transfers of early leaves run while later leaves are
    still on the wire, instead of serially after the whole blob is
    buffered.  In memory the buffer is an anonymous private map, which
    costs nothing to allocate: the kernel zero-fills each page on its
    first touch, so the receive that fills a page also faults it in.  A
    crash-resumable restore lands in a file-backed spool map instead.

    **Page reuse.**  An in-memory restore that no peer mirror reads
    treats the map as a pool of pages: a leaf takes a region when it is
    first written, preferring pages that earlier leaves gave back (right
    after its blob predecessor's region if that is free, else the
    best-fitting given-back region), and only when none fits untouched
    pages at the map's high-water mark.  A leaf gives its region back
    once its device array ``is_ready()``: the host→device copy has
    consumed the pages.  So the restore faults in the pages of its live
    leaves, not the whole blob, and never more than the blob's.  A
    spool keeps every leaf at its blob offset (the resume journal
    re-verifies CRCs there), and so does a ``mirrored`` restore (peers
    are served the blob's bytes from this buffer after the leaves are
    on the device).

    Implements the client's **sink protocol**
    (``writable(start, length) -> memoryview`` + ``commit(start,
    nbytes)``): the transfer layer receives socket bytes directly into
    this sink's landing buffer, so the restore path is copy-free from
    socket to leaf buffer (the only remaining move is the inherent
    host→device ``device_put``).  Under page reuse, a range that
    crosses into a differently placed leaf, or whose bytes are partly
    landed already, is received into recycled scratch and copied into
    place at ``commit``.

    Deliveries may **overlap or repeat**: the sink tracks covered byte
    intervals and only decrements per-leaf countdowns for bytes seen for
    the first time, so a duplicated or partially-overlapping range (a
    retried wave, a speculative re-fetch, a buggy transport) can neither
    double-materialize a leaf nor drive a countdown negative.  The normal
    client path still delivers each byte exactly once — the interval set
    then holds one entry per contiguous landed region and costs O(log n)
    per call.
    """

    def __init__(self, manifest: dict, like: Any,
                 shardings: Optional[Any] = None,
                 spool_path: Optional[str] = None,
                 mirrored: bool = False):
        self._covered: list[tuple[int, int]] = []   # disjoint [s, e), sorted
        self.duplicate_bytes = 0                    # re-delivered byte count
        #: leaf bytes landed in pages no leaf had touched (under page
        #: reuse, the map's high-water mark), in pages an earlier leaf
        #: gave back, and bytes copied in from scratch
        self.fresh_bytes = 0
        self.reused_bytes = 0
        self.scratch_bytes = 0
        leaves, self._treedef = _leaf_paths(like)
        by_key = {e["key"]: e for e in manifest["leaves"]}
        shard_leaves = (jax.tree_util.tree_leaves(shardings)
                        if shardings is not None else [None] * len(leaves))
        total = int(manifest["total_bytes"])
        self.total_bytes = total
        self._mmap = None
        self._spool_file = None
        if spool_path is None or total == 0:
            # allocated untouched (mmap(2) maps no page).  Not self._mmap,
            # which is the spool's: that would copy every leaf out and
            # unmap in close().  This map lives as long as the leaf views
            # and device_put inputs that refer to it
            self._buf = (mmap.mmap(-1, total, flags=mmap.MAP_PRIVATE)
                         if total else bytearray())
        else:
            # crash-resumable restore: the landing buffer is a file-backed
            # mmap, so bytes that reached the page cache (and were then
            # journaled + fsync'd by the client) survive a process death.
            # An existing spool's content is preserved — the resume path
            # re-verifies journaled CRCs against exactly these bytes.
            f = open(spool_path, "a+b")
            try:
                f.seek(0, os.SEEK_END)
                if f.tell() != total:
                    f.truncate(total)
                self._mmap = mmap.mmap(f.fileno(), total)
            except BaseException:
                f.close()
                raise
            self._spool_file = f
            self._buf = self._mmap
        self._out: list = [None] * len(leaves)
        # slots ordered by blob offset for bisect lookup of landed ranges
        order = sorted(
            range(len(leaves)), key=lambda i: by_key[leaves[i][0]]["offset"])
        self._entries = []
        self._remaining = []
        self._slot_of = []
        self._shards = []
        self._starts = []
        self._sizes = []
        for i in order:
            e = by_key[leaves[i][0]]
            self._entries.append(e)
            self._remaining.append(int(e["nbytes"]))
            self._slot_of.append(i)
            self._shards.append(shard_leaves[i])
            self._starts.append(int(e["offset"]))
            self._sizes.append(int(e["nbytes"]))
        self._reuse = spool_path is None and not mirrored and total > 0
        #: a PeerMirror serves ``writable(0, total)`` as the blob: not
        #: once leaves are placed elsewhere and their pages recycled
        self.mirrorable = not self._reuse
        n = len(self._entries)
        self._base: list[Optional[int]] = [None] * n   # region in the map
        self._pins = [0] * n            # open writable() views per leaf
        self._retiring: list[int] = []  # on device, region not yet free
        self._free: list[tuple[int, int]] = []    # given back, [s, e)
        #: blob start -> (scratch or None, pinned leaves) of each view
        #: writable() handed out and not yet committed
        self._open: dict[int, tuple] = {}
        self._scratch: list[bytearray] = []       # recycled scratch
        # zero-byte leaves (empty arrays) have nothing on the wire
        for j, rem in enumerate(self._remaining):
            if rem == 0:
                self._materialize(j)

    def _claim_new(self, start: int, end: int) -> list[tuple[int, int]]:
        """Merge ``[start, end)`` into the covered set; return only the
        subspans that were not already covered (first-time bytes).  The
        merge itself is ``journal.claim_interval`` — the same code that
        backs the resume journal, so the peer-mirror advertisement
        (:meth:`covered_intervals`) has exactly one source of truth."""
        return claim_interval(self._covered, start, end)

    def covered_intervals(self) -> list[tuple[int, int]]:
        """Committed coverage as sorted disjoint ``(start, nbytes)`` pairs
        — the :class:`repro.transfer.Sink` accessor a peer mirror
        advertises over the wire.  Safe to call from server threads while
        the restore is still streaming: the covered list only ever grows,
        and each commit replaces it with a single atomic slice assign."""
        return [(s, e - s) for s, e in list(self._covered)]

    def writable(self, start: int, length: int) -> memoryview:
        """Zero-copy destination for ``[start, start + length)``: the
        transfer layer reads socket bytes straight into this view, then
        calls :meth:`commit` for the bytes that actually landed.  Under
        page reuse that is the leaves' regions when no byte of the range
        has landed yet and its leaves sit in the map as in the blob;
        otherwise scratch, which :meth:`commit` copies into the leaves
        still landing (a landed leaf's pages may be a later leaf's by
        then)."""
        if not self._reuse:
            return memoryview(self._buf)[start:start + length]
        # the client gives a range to one fetch at a time: a range opened
        # again at a start that is still open follows a fetch that failed
        self._close(start)
        leaves = self._unlanded_leaves(start, start + length)
        if leaves:
            shift = {self._region(j) - self._starts[j] for j in leaves}
            if len(shift) == 1:
                # pinned until closed: a leaf with a view open keeps its
                # pages even once it is on the device
                for j in leaves:
                    self._pins[j] += 1
                self._open[start] = (None, leaves)
                lo = start + shift.pop()
                return memoryview(self._buf)[lo:lo + length]
        buf = self._take_scratch(length)
        self._open[start] = (buf, ())
        return memoryview(buf)[:length]

    def commit(self, start: int, nbytes: int) -> None:
        """Account for ``nbytes`` landed at ``start`` (already in the
        buffer via :meth:`writable`; under page reuse, copied in here
        from the scratch :meth:`writable` handed out)."""
        end = start + nbytes
        scratch = self._close(start)
        if scratch is not None:
            self._copy_in(scratch, start, end)
            self._scratch.append(scratch)
        if end <= start:
            return
        fresh = self._claim_new(start, end)
        self.duplicate_bytes += (end - start) - sum(e - s for s, e in fresh)
        # Two phases so an exception can't corrupt the accounting: pure
        # counter arithmetic first (cannot throw; coverage is already
        # committed, so a re-delivery after a failure below is a clean
        # duplicate no-op), then the device_puts.  A leaf whose
        # _materialize raises keeps remaining == 0 with its bytes safely
        # in the buffer — finish() retries it from there.
        completed = []
        for span_start, span_end in fresh:
            completed.extend(self._account(span_start, span_end))
        for j in completed:
            self._materialize(j)

    def _account(self, start: int, end: int) -> list[int]:
        """Decrement leaf countdowns for a first-time byte span; return the
        indices of leaves that just completed."""
        completed = []
        j = max(bisect.bisect_right(self._starts, start) - 1, 0)
        while j < len(self._entries) and self._starts[j] < end:
            e = self._entries[j]
            leaf_end = self._starts[j] + int(e["nbytes"])
            overlap = min(end, leaf_end) - max(start, self._starts[j])
            if overlap > 0:
                self._remaining[j] -= overlap
                if self._remaining[j] == 0:
                    completed.append(j)
            j += 1
        return completed

    def _close(self, start: int) -> Optional[bytearray]:
        """Close the view :meth:`writable` opened at ``start``, if any:
        unpin its leaves; return its scratch (None for a direct view)."""
        scratch, pinned = self._open.pop(start, (None, ()))
        for j in pinned:
            self._pins[j] -= 1
        return scratch

    def _unlanded_leaves(self, start: int, end: int) -> Optional[list[int]]:
        """The leaves ``[start, end)`` lies in, if every byte of it is in
        some leaf and none has landed yet; else None."""
        i = bisect.bisect_left(self._covered, (end,)) - 1
        if i >= 0 and self._covered[i][1] > start:
            return None
        leaves, inside = [], 0
        j = max(bisect.bisect_right(self._starts, start) - 1, 0)
        while j < len(self._entries) and self._starts[j] < end:
            overlap = (min(end, self._starts[j] + self._sizes[j])
                       - max(start, self._starts[j]))
            if overlap > 0:
                leaves.append(j)
                inside += overlap
            j += 1
        return leaves if inside == end - start else None

    def _region(self, j: int) -> int:
        """Map offset of leaf ``j``'s bytes, placing it on first use."""
        base = self._base[j]
        if base is None:
            base = self._base[j] = self._place(j)
        return base

    def _place(self, j: int) -> int:
        n = self._sizes[j]
        if not self._reuse or not n:
            self.fresh_bytes += n
            return self._starts[j]
        self._give_back()
        top = self.fresh_bytes
        base = self._fit(j, n)
        self.fresh_bytes = max(top, base + n)
        self.reused_bytes += n - (self.fresh_bytes - top)
        return base

    def _give_back(self) -> None:
        """Free the regions of leaves whose device arrays are ready (the
        host→device copy has read the pages) and that no open view
        pins.  Never waits: a copy still in flight keeps its pages."""
        keep = []
        for j in self._retiring:
            out = self._out[self._slot_of[j]]
            if self._pins[j] or out is None or not out.is_ready():
                keep.append(j)
                continue
            base, self._base[j] = self._base[j], None
            claim_interval(self._free, base, base + self._sizes[j])
        self._retiring = keep

    def _fit(self, j: int, n: int) -> int:
        """Where ``n`` bytes of leaf ``j`` go: right after leaf ``j - 1``'s
        region if that much is free there, so a range crossing the two
        lands in one view; else the smallest free region that fits; else
        the free region below the high-water mark, grown past it; else
        untouched pages at the mark.  Never past the map: the mark only
        moves by bytes of leaves placed, which sum to the blob."""
        prev = self._base[j - 1] if j else None
        if prev is not None:
            base = prev + self._sizes[j - 1]
            if any(s <= base and base + n <= e for s, e in self._free):
                return self._take(base, n)
        fits = [(e - s, s) for s, e in self._free if e - s >= n]
        if fits:
            return self._take(min(fits)[1], n)
        if self._free and self._free[-1][1] == self.fresh_bytes:
            return self._free.pop()[0]
        return self.fresh_bytes

    def _take(self, base: int, n: int) -> int:
        """Remove ``[base, base + n)`` from the free regions."""
        i = bisect.bisect_right(self._free, (base, float("inf"))) - 1
        s, e = self._free[i]
        self._free[i:i + 1] = [(a, b) for a, b in ((s, base), (base + n, e))
                               if b > a]
        return base

    def _take_scratch(self, length: int) -> bytearray:
        """The smallest recycled scratch buffer of ``length`` bytes or
        more, else a new one."""
        fits = [(len(b), i) for i, b in enumerate(self._scratch)
                if len(b) >= length]
        return self._scratch.pop(min(fits)[1]) if fits else bytearray(length)

    def _copy_in(self, scratch: bytearray, start: int, end: int) -> None:
        """Copy blob bytes ``[start, end)`` from ``scratch`` into the
        regions of their leaves that are still landing.  A landed leaf's
        pages may belong to a later leaf by now; a landing one's landed
        bytes are overwritten with the same verified bytes."""
        src = memoryview(scratch)
        dst = memoryview(self._buf)
        j = max(bisect.bisect_right(self._starts, start) - 1, 0)
        while j < len(self._entries) and self._starts[j] < end:
            s0 = self._starts[j]
            lo = max(start, s0)
            hi = min(end, s0 + self._sizes[j])
            if hi > lo and self._remaining[j]:
                base = self._region(j) + lo - s0
                dst[base:base + hi - lo] = src[lo - start:hi - start]
                self.scratch_bytes += hi - lo
            j += 1

    def _materialize(self, j: int) -> None:
        e = self._entries[j]
        shd = self._shards[j]
        arr = np.frombuffer(
            self._buf, dtype=np.dtype(e["dtype"]),
            count=int(np.prod(e["shape"])) if e["shape"] else 1,
            offset=self._region(j)).reshape(e["shape"])
        if self._mmap is not None or (self._reuse and _on_cpu(shd)):
            # device_put may alias aligned host memory on CPU backends;
            # never hand XLA a view of the spool mmap we intend to unmap,
            # nor of pages a later leaf will be given
            arr = arr.copy()
        where = ({} if obs.current() is None
                 else obs.placement(shd, arr.shape, arr.itemsize))
        with obs.span("mdtp.device_put", leaf=e["key"],
                      bytes=int(e["nbytes"]), **where):
            self._out[self._slot_of[j]] = (
                jax.device_put(arr, shd) if shd is not None
                else jax.device_put(arr))
        if self._reuse and self._sizes[j]:
            self._retiring.append(j)

    def finish(self, require_all: bool = True) -> Any:
        """Assemble the restored pytree.  ``require_all=False`` is the
        sharded-restore contract: leaves this host's span never covered
        stay ``None`` in the tree (they belong to other hosts)."""
        missing = [self._entries[j]["key"]
                   for j, r in enumerate(self._remaining) if r != 0]
        if missing and require_all:
            raise IOError(f"restore incomplete, leaves missing bytes: "
                          f"{missing[:5]}")
        # retry any leaf whose earlier device_put failed transiently mid-
        # stream (its bytes are complete in the buffer)
        for j, r in enumerate(self._remaining):
            if r == 0 and self._out[self._slot_of[j]] is None:
                self._materialize(j)
        return jax.tree_util.tree_unflatten(self._treedef, self._out)

    def close(self) -> None:
        """Release the spool mmap (no-op for in-memory restores).  Only
        safe once every materialized leaf is off the buffer — the restore
        path blocks on the device arrays before calling this."""
        if self._mmap is not None:
            try:
                self._mmap.close()
            except BufferError:
                # A transient view (e.g. a writable() slice pinned by a
                # traceback) is still exported; collect and retry, and if
                # one survives even that, leave the map for process exit —
                # the spool is scratch state, leaking it is benign.
                gc.collect()
                with contextlib.suppress(BufferError):
                    self._mmap.close()
            self._mmap = None
        if self._spool_file is not None:
            self._spool_file.close()
            self._spool_file = None


def _rebuild(manifest: dict, blob: bytes, like: Any,
             shardings: Optional[Any] = None) -> Any:
    leaves, treedef = _leaf_paths(like)
    by_key = {e["key"]: e for e in manifest["leaves"]}
    shard_leaves = (jax.tree_util.tree_leaves(shardings)
                    if shardings is not None else [None] * len(leaves))
    out = []
    for (key, leaf), shd in zip(leaves, shard_leaves):
        e = by_key[key]
        arr = np.frombuffer(
            blob, dtype=np.dtype(e["dtype"]), count=int(
                np.prod(e["shape"])) if e["shape"] else 1,
            offset=e["offset"]).reshape(e["shape"])
        if shd is not None:
            out.append(jax.device_put(arr, shd))
        else:
            out.append(jax.device_put(arr))
    return jax.tree_util.tree_unflatten(treedef, out)


def _finish_restore(stream: _StreamingRestore, jr, spool: Optional[str],
                    require_all: bool = True):
    """Assemble the restored tree; for resumable restores, retire the
    scratch state (journal + spool) once every leaf is safely on device —
    ``device_put`` dispatch is async, so block before unmapping the spool
    the arrays were read from."""
    with obs.span("mdtp.finish") as span:
        state = stream.finish(require_all)
        if obs.current() is not None:
            span.attrs.update(fresh_bytes=stream.fresh_bytes,
                              reused_bytes=stream.reused_bytes,
                              scratch_bytes=stream.scratch_bytes)
        if jr is not None:
            jax.block_until_ready(state)
            jr.complete()
            stream.close()
            if spool is not None:
                with contextlib.suppress(OSError):
                    os.remove(spool)
    return state


@dataclass(frozen=True)
class RestoreOptions:
    """Consolidated tail options for :func:`restore_checkpoint`.

    Groups what used to be a growing tail of bare keyword arguments; the
    bare kwargs still work (a compatibility shim folds them in, explicit
    kwargs overriding the dataclass) so no existing caller changes.

    ``mirror`` is the peer-assisted broadcast hook: a
    ``repro.transfer.PeerMirror`` that is bound to the restore's
    streaming sink as soon as the blob size is known — committed ranges
    become servable to other restoring nodes while this restore is still
    in flight.  For crash-resumable restores (``resume=``) the mirror is
    unbound when the restore ends (the spool mmap dies with it);
    in-memory restores keep serving until the caller stops the mirror.
    """

    tuner: Any = None
    wave_bytes: Optional[int] = None
    manager: Any = None
    resume: Optional[str] = None
    mirror: Any = None
    #: sharded restore: ``(host, plan_or_k)`` — fetch only this host's
    #: span of the blob.  ``plan_or_k`` is a ``repro.transfer.ShardPlan``
    #: or an int K (the plan is then derived here, snapped to manifest
    #: leaf boundaries so every tensor lands whole).  Leaves outside the
    #: span come back ``None``; pair with ``mirror=`` so peers (or a
    #: work-stealing ``fetch_sharded`` fleet) can drain this host's span.
    shard_plan: Any = None


def restore_checkpoint(
    root: str,
    like: Any,
    step: Optional[int] = None,
    shardings: Optional[Any] = None,
    replicas: Optional[Sequence[Replica]] = None,
    options: Optional[RestoreOptions] = None,
    *,
    tuner: Any = None,
    wave_bytes: Optional[int] = None,
    manager: Any = None,
    resume: Optional[str] = None,
    mirror: Any = None,
    shard_plan: Any = None,
) -> tuple[Any, int]:
    """Restore (state, step).

    ``like``: a pytree with the target structure (shapes are taken from the
    manifest, so this may be abstract).  ``replicas``: mirror list — when
    given, ``data.bin`` is fetched with MDTP multi-source ranges instead of
    local reads (``root`` is then only used to discover the step if not
    given and may not exist locally), **streamed**: each leaf is
    ``device_put`` as soon as its byte range completes, overlapping the
    network transfer with host→device copies instead of buffering the
    whole blob first.

    ``wave_bytes`` splits the blob fetch into sequential waves of that
    many bytes and **re-tunes chunk geometry between waves** from the
    previous wave's measured per-replica throughput and RTT — a long
    multi-leaf restore then tracks mirror throttles and latency steps
    mid-restore instead of riding its initial (C, L) to the end.  With a
    ``tuner`` (a ``repro.core.online`` policy: ``BanditTuner``,
    ``MCGradTuner``, ``GridTuner``) each wave boundary feeds the tuner
    one telemetry snapshot — exactly one update per wave, so a bandit's
    reward attribution stays aligned with the params the wave actually
    ran under; without one, each boundary runs the client's fused grid
    ``retune`` (skipped quietly when a wave produced no usable
    observations).  A single-fetch restore (no ``wave_bytes``) instead
    passes the tuner to the client's in-transfer telemetry hook.

    ``manager`` (a ``repro.transfer.TransferManager``) routes the
    manifest and blob fetches through a shared fleet: per-replica
    in-flight caps apply across every transfer the manager runs,
    telemetry aggregates into its fleet model, residual-capacity packing
    shapes this restore's rounds, and the geometry this restore adopts
    warm-starts the manager's next transfer.  With a manager that owns a
    tuner (and no explicit ``tuner=``), adaptation happens through the
    manager's shared in-fetch hook and the between-wave grid re-tune is
    skipped — one owner for reward attribution.  An explicit ``tuner=``
    always wins: the manager's hook is silenced for this restore and the
    wave-boundary updates feed the given tuner exactly as without a
    manager.

    ``resume`` (a scratch directory path; replica restores only) makes
    the restore **crash-resumable**: ranges land in a file-backed spool
    (``<resume>/data.spool``) and every committed range is journaled with
    its CRC32 (``<resume>/journal.log``, fsync'd at the journal's
    checkpoint interval).  Re-running the same restore after a crash
    replays the journal, re-verifies each journaled range against the
    spool, and fetches only what is missing — the mirrors serve the
    uncovered bytes, not the whole blob again.  On success both files
    are deleted (a completed restore has nothing to resume).

    ``shard_plan`` (``(host, plan_or_k)``; replica restores only) makes
    this a **sharded** restore: the process fetches only its host's span
    of ``data.bin`` (a ``repro.transfer.ShardPlan``, or an int K from
    which the plan is derived on the spot, snapped to manifest leaf
    boundaries).  Leaves outside the span come back ``None`` — the other
    hosts of the mesh restore them; combine with ``mirror=`` so peers
    can pull this host's span, and see ``repro.transfer.fetch_sharded``
    for the in-process work-stealing orchestration of K such fetches.

    ``options`` (a :class:`RestoreOptions`) is the consolidated form of
    the tail kwargs above plus ``mirror=`` — a
    ``repro.transfer.PeerMirror`` that serves this restore's landed
    ranges to other restoring nodes (peer-assisted broadcast).  Bare
    kwargs keep working and override the dataclass field-for-field.
    """
    opts = options if options is not None else RestoreOptions()
    overrides = {k: v for k, v in {
        "tuner": tuner, "wave_bytes": wave_bytes, "manager": manager,
        "resume": resume, "mirror": mirror,
        "shard_plan": shard_plan}.items() if v is not None}
    if overrides:
        opts = _dc_replace(opts, **overrides)
    tuner, wave_bytes, manager = opts.tuner, opts.wave_bytes, opts.manager
    resume, mirror, shard_plan = opts.resume, opts.mirror, opts.shard_plan

    if step is None:
        step = latest_step(root)
        if step is None:
            raise FileNotFoundError(f"no complete checkpoint under {root}")
    d = _step_dir(root, step)

    if replicas:
        base = [Replica(r.host, r.port,
                        r.path.rstrip("/") + f"/step_{step:010d}")
                for r in replicas]
        import asyncio

        @contextlib.asynccontextmanager
        async def client_for(reps):
            """A transfer client for this restore: fleet-managed (shared
            caps/telemetry/params) when a manager is given, standalone
            otherwise.  An explicit ``tuner=`` silences the manager's
            in-fetch hook so wave-boundary updates are the only feed."""
            if manager is not None:
                kw = {"tuner": None} if tuner is not None else {}
                async with manager.session(replicas=reps, **kw) as c:
                    yield c
            else:
                yield MDTPClient(reps)

        # the between-wave fused grid re-tune runs only when nobody else
        # owns adaptation (no explicit tuner, no manager-shared tuner)
        grid_retune = tuner is None and getattr(manager, "tuner", None) is None

        async def run():
            with obs.span("mdtp.manifest"):
                async with client_for(
                        [Replica(r.host, r.port, r.path + "/" + _MANIFEST)
                         for r in base]) as mclient:
                    msize = await mclient.blob_size()
                    mbuf, _ = await mclient.fetch(msize)
            manifest = json.loads(bytes(mbuf).decode())
            total = int(manifest["total_bytes"])
            lo, hi = 0, total
            if shard_plan is not None:
                # (host, plan-or-K): this process fetches only its span.
                # An int K derives the plan here, snapped to manifest
                # leaf boundaries — every host computes the same cuts
                # from the same manifest, no coordination needed.
                from repro.transfer.shard import (ShardPlan,
                                                  manifest_boundaries,
                                                  plan_shards)

                host, plan = shard_plan
                if not isinstance(plan, ShardPlan):
                    plan = plan_shards(total, int(plan),
                                       manifest_boundaries(manifest))
                lo, hi = plan.span_of(int(host))
            jr = None
            spool = None
            if resume is not None:
                os.makedirs(resume, exist_ok=True)
                spool = os.path.join(resume, "data.spool")
                # the journal is bound to (total, step): a scratch dir
                # left over from a DIFFERENT restore fails the header
                # check and starts fresh instead of poisoning this one
                jr = ResumeJournal.open(
                    os.path.join(resume, "journal.log"),
                    total_bytes=total, meta={"step": int(step)})
            with obs.span("mdtp.buffer", bytes=total):
                stream = _StreamingRestore(manifest, like, shardings,
                                           spool_path=spool,
                                           mirrored=mirror is not None)
            if mirror is not None:
                # peer-assisted broadcast: landed ranges become servable
                # to other restorers while this restore is in flight
                mirror.bind(stream, total)
            try:
                return await _restore_waves(stream, jr, spool, lo, hi,
                                            dclient_factory=lambda: client_for(
                                                [Replica(r.host, r.port,
                                                         r.path + "/" + _DATA)
                                                 for r in base]))
            finally:
                # idempotent: a successful restore already retired these;
                # on failure the journal handle is released with its
                # records flushed (the client syncs on the way out), so a
                # re-run — same process or not — can resume cleanly
                if jr is not None:
                    jr.close()
                if mirror is not None and spool is not None:
                    # the spool mmap dies with the restore — stop serving
                    # from it before it is unmapped (in-memory restores
                    # keep serving; their buffer outlives the call)
                    mirror.unbind()
                stream.close()

        async def _restore_waves(stream, jr, spool, lo, hi, dclient_factory):
            # sharded restores fetch only [lo, hi) of the blob; the rest
            # of the tree stays unmaterialized (require_all=False below)
            span = hi - lo
            require_all = shard_plan is None
            async with dclient_factory() as dclient:
                # the stream object carries the writable/commit zero-copy
                # protocol: ranges are received straight into its buffer
                if not wave_bytes or wave_bytes >= span:
                    if span > 0:
                        with obs.span("mdtp.wave", wave=0, bytes=span):
                            await dclient.fetch(span, sink=stream,
                                                offset=lo, tuner=tuner,
                                                resume=jr)
                    return _finish_restore(stream, jr, spool, require_all)
                pos = lo
                wave = 0
                while pos < hi:
                    n = min(int(wave_bytes), hi - pos)
                    with obs.span("mdtp.wave", wave=wave, bytes=n):
                        _, report = await dclient.fetch(
                            n, sink=stream, offset=pos, resume=jr)
                    pos += n
                    wave += 1
                    if pos >= hi:
                        break
                    next_wave = min(int(wave_bytes), hi - pos)
                    if tuner is None:
                        if not grid_retune:
                            continue    # the manager's shared tuner owns
                            # adaptation via the in-fetch hook
                        try:
                            with obs.span("mdtp.retune"):
                                res = dclient.retune(next_wave)
                        except NoTelemetryError:
                            continue    # wave yielded no live observations;
                            # a real sweep failure (XlaRuntimeError)
                            # propagates
                        log.info("between-wave retune at byte %d: C=%d "
                                 "L=%d, predicted %.4f s for the next "
                                 "%d bytes", pos, res.params.initial_chunk,
                                 res.params.large_chunk, res.predicted_time,
                                 next_wave)
                    else:
                        # per-wave telemetry snapshot from the wave's report.
                        # The tuner is fed HERE only (not via the client's
                        # in-fetch hook): one update per wave keeps a
                        # bandit's reward attributed to the params the whole
                        # wave actually ran under.
                        from repro.core.online import Telemetry

                        try:
                            new = tuner.update(Telemetry.from_report(
                                report, dclient.replicas, next_wave))
                        except Exception as e:
                            # same contract as the client's in-transfer hook:
                            # a failing tuner must never fail a restore whose
                            # waves are streaming fine — keep the current
                            # geometry, carry on, and say so
                            warnings.warn(
                                f"tuner update failed between restore "
                                f"waves; keeping the current chunk "
                                f"geometry: {e!r}",
                                TunerFailureWarning, stacklevel=2)
                            new = None
                        if new is not None:
                            dclient.adopt_params(new)
            return _finish_restore(stream, jr, spool, require_all)

        with obs.span("mdtp.restore", step=int(step)):
            return asyncio.run(run()), step

    with open(os.path.join(d, _MANIFEST)) as f:
        manifest = json.load(f)
    with open(os.path.join(d, _DATA), "rb") as f:
        blob = f.read()
    return _rebuild(manifest, blob, like, shardings), step


@dataclass
class CheckpointManager:
    """Save-every-N with async commit and keep-last-k GC."""

    root: str
    every_steps: int = 100
    keep: int = 3
    async_save: bool = True

    def __post_init__(self):
        os.makedirs(self.root, exist_ok=True)
        self._thread: Optional[threading.Thread] = None

    def maybe_save(self, step: int, state: Any) -> bool:
        if step % self.every_steps != 0:
            return False
        self.wait()
        # snapshot on the host before handing off (training may mutate)
        host_state = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                  state)
        if self.async_save:
            self._thread = threading.Thread(
                target=self._save_and_gc, args=(step, host_state), daemon=True)
            self._thread.start()
        else:
            self._save_and_gc(step, host_state)
        return True

    def _save_and_gc(self, step: int, state: Any) -> None:
        save_checkpoint(self.root, step, state)
        steps = sorted(
            int(n.split("_")[1]) for n in os.listdir(self.root)
            if n.startswith("step_") and not n.endswith(".tmp")
            and os.path.exists(os.path.join(self.root, n, _MANIFEST)))
        for s in steps[:-self.keep]:
            shutil.rmtree(_step_dir(self.root, s), ignore_errors=True)

    def wait(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            self._thread.join()
