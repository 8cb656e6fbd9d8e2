"""In-program record of what a restore does: spans and per-fetch counters.

Off by default.  ``with obs.recording() as rec:`` turns it on for the
calling process; every restore and transfer inside the block then leaves
its spans and counters in ``rec``::

    from repro import obs
    from repro.checkpoint import restore_checkpoint

    with obs.recording() as rec:
        state, step = restore_checkpoint(root, like, replicas=mirrors,
                                         wave_bytes=total // 4 + 1)
    restore = rec.find("mdtp.restore")[0]
    for wave in restore.find("mdtp.wave"):
        print(wave.attrs["wave"], wave.seconds, wave.fetch.crc_s,
              wave.fetch.report.bytes_per_replica)

A **span** is one interval of work done synchronously on the caller's
thread (the restore's event loop), timed with ``time.monotonic()``; spans
opened inside another nest under it.  Work that overlaps on several
lanes is not spanned but counted: each ``MDTPClient.fetch`` fills one
:class:`Fetch` with plain per-replica fields, attached to the innermost
span open when it started.  While a JAX profiler session runs, every span
is also a ``jax.profiler.TraceAnnotation``, so it sits on the host plane
of the same trace as the device's operations.

Stdlib only: importing this module loads no JAX, and a span reaches
``jax.profiler`` only when the process has already imported JAX.  With
recording off, :func:`span` returns one shared no-op object and
:func:`current` returns None, which is all the transfer client tests per
range.
"""

from __future__ import annotations

import contextlib
import math
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional

__all__ = ["Span", "ReplicaStats", "Fetch", "Record", "recording", "span",
           "current", "placement"]

#: the record being filled, None while recording is off
_record: Optional["Record"] = None


@dataclass(eq=False)
class Span:
    """One interval (monotonic seconds) and what was recorded inside it."""

    name: str
    attrs: dict = field(default_factory=dict)
    start: float = 0.0
    end: Optional[float] = None
    children: list["Span"] = field(default_factory=list)
    #: the transfer that started while this was the innermost open span
    fetch: Optional["Fetch"] = None
    _record: Optional["Record"] = field(default=None, repr=False)
    _annotation: Any = field(default=None, repr=False)

    @property
    def seconds(self) -> float:
        return (self.end if self.end is not None else time.monotonic()) \
            - self.start

    def find(self, name: str) -> list["Span"]:
        """Every span named ``name`` below this one, in the order they
        opened."""
        return _find(self.children, name)

    def __enter__(self) -> "Span":
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is not None:
            self._annotation = profiler.TraceAnnotation(self.name, **{
                k: v for k, v in self.attrs.items()
                if isinstance(v, (int, float, str))})
            self._annotation.__enter__()
        self._record._push(self)
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.monotonic()
        self._record._pop(self)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        return False


def _find(spans: list[Span], name: str) -> list[Span]:
    out = []
    for s in spans:
        if s.name == name:
            out.append(s)
        out.extend(_find(s.children, name))
    return out


class _NoSpan:
    """What :func:`span` returns while recording is off."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


@dataclass(eq=False)
class ReplicaStats:
    """One replica's share of one fetch (monotonic seconds)."""

    name: str
    #: time with at least one of its ranges assigned and not yet
    #: committed or given back
    inflight_s: float = 0.0
    #: instants of its first and last committed range (None: none)
    first_commit: Optional[float] = None
    last_commit: Optional[float] = None
    #: over its replies: the connection's turn to read the reply until
    #: the response header is parsed (no body byte moves: a round trip
    #: the pipeline did not hide), and header to last body byte
    header_wait_s: float = 0.0
    body_read_s: float = 0.0
    #: when the client retired it (None: it served to the end)
    failed_at: Optional[float] = None
    #: its ranges assigned and not yet committed or given back, now
    outstanding: int = 0
    _busy_since: float = field(default=0.0, repr=False)

    def assigned(self) -> None:
        if self.outstanding == 0:
            self._busy_since = time.monotonic()
        self.outstanding += 1

    def settled(self) -> None:
        self.outstanding -= 1
        if self.outstanding == 0:
            self.inflight_s += time.monotonic() - self._busy_since

    def committed(self, header_wait: float, body_read: float) -> None:
        self.header_wait_s += header_wait
        self.body_read_s += body_read
        self.settled()
        self.last_commit = time.monotonic()
        if self.first_commit is None:
            self.first_commit = self.last_commit

    def alive_s(self, fetch: "Fetch") -> float:
        """Seconds of the fetch before it was retired."""
        end = fetch.end if fetch.end is not None else time.monotonic()
        if self.failed_at is not None:
            end = min(end, self.failed_at)
        return max(0.0, end - fetch.start)


@dataclass(eq=False)
class Fetch:
    """Counters of one ``MDTPClient.fetch``, filled in by the client."""

    start: float
    replicas: list[ReplicaStats]
    #: the chunk geometry (``repro.core.chunking.ChunkParams``) it began on
    params: Any = None
    end: Optional[float] = None
    #: seconds inside ``zlib.crc32`` over its ranges, on whichever thread
    #: hashed them
    crc_s: float = 0.0
    #: its ``TransferReport`` (None if it raised)
    report: Any = None


class Record:
    """Everything recorded inside one :func:`recording` block."""

    def __init__(self):
        #: outermost spans, in the order they opened
        self.spans: list[Span] = []
        #: every fetch, in the order they started
        self.fetches: list[Fetch] = []
        self._stacks: dict[int, list[Span]] = {}

    def find(self, name: str) -> list[Span]:
        """Every span named ``name``, in the order they opened."""
        return _find(self.spans, name)

    def fetch(self, names: list[str], params: Any = None) -> Fetch:
        """A new fetch's counters, one :class:`ReplicaStats` per name."""
        f = Fetch(time.monotonic(), [ReplicaStats(n) for n in names],
                  params)
        self.fetches.append(f)
        stack = self._stacks.get(threading.get_ident())
        if stack and stack[-1].fetch is None:
            stack[-1].fetch = f
        return f

    def _push(self, s: Span) -> None:
        stack = self._stacks.setdefault(threading.get_ident(), [])
        (stack[-1].children if stack else self.spans).append(s)
        stack.append(s)

    def _pop(self, s: Span) -> None:
        stack = self._stacks.get(threading.get_ident(), [])
        if s in stack:
            stack.remove(s)


@contextlib.contextmanager
def recording() -> Iterator[Record]:
    """Record for the calling process until the block ends."""
    global _record
    if _record is not None:
        raise RuntimeError("already recording")
    _record = Record()
    try:
        yield _record
    finally:
        _record = None


def current() -> Optional[Record]:
    """The record being filled, or None while recording is off."""
    return _record


def span(name: str, **attrs):
    """A context manager that records ``name`` over its block, with
    ``attrs``; the shared no-op while recording is off."""
    rec = _record
    if rec is None:
        return _NO_SPAN
    return Span(name, attrs, _record=rec)


def placement(sharding, shape: tuple, itemsize: int) -> dict:
    """Where a leaf of ``shape`` lands under ``sharding`` (a JAX
    ``Sharding``; None: whole on one device), as ``mdtp.device_put``'s
    attributes: ``devices`` it lands on, ``device_bytes`` (the bytes of
    every device's shard, a replica counted once per device) and
    ``gathered_bytes`` (those of shards that are no single contiguous run
    of the leaf's row-major bytes, so must be gathered before the copy)."""
    if sharding is None:
        return {"devices": 1, "device_bytes": math.prod(shape) * itemsize,
                "gathered_bytes": 0}
    device_bytes = gathered = 0
    index_map = sharding.devices_indices_map(tuple(shape))
    for index in index_map.values():
        extents = [len(range(*s.indices(n))) for s, n in zip(index, shape)]
        nbytes = math.prod(extents) * itemsize
        device_bytes += nbytes
        # row-major contiguous: every dimension after the last partial
        # one is whole, and every one before it has extent 1
        partial = [i for i, (e, n) in enumerate(zip(extents, shape))
                   if e != n]
        if partial and any(e != 1 for e in extents[:partial[-1]]):
            gathered += nbytes
    return {"devices": len(index_map), "device_bytes": device_bytes,
            "gathered_bytes": gathered}
