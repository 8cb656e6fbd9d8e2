"""Real asyncio transfer runtime: MDTP client + range-serving HTTP server
plus the fleet-level multi-transfer scheduler, end-to-end integrity
(per-range CRC32 verification), crash-resume journaling, a
fault-injecting chaos harness, peer-assisted broadcast (restoring nodes
re-serve what they have via :class:`PeerMirror`), and sharded
work-stealing restore planning (:mod:`repro.transfer.shard`).

Exports resolve lazily (PEP 562) so the sans-I/O scheduling core
(``repro.transfer.sched``) stays importable without dragging in the
event loop, sockets, or JAX — the layering contract
``tools/layercheck.py`` enforces.
"""

from importlib import import_module

#: export name -> defining submodule (resolved on first attribute access)
_EXPORTS = {
    "MDTPClient": ".client", "ClientOptions": ".client",
    "Replica": ".client", "TransferReport": ".client",
    "TransferIncompleteError": ".client", "fetch_blob": ".client",
    "TunerFailureWarning": ".client",
    "ResumeJournal": ".journal", "claim_interval": ".journal",
    "merge_intervals": ".journal", "uncovered_intervals": ".journal",
    "FleetModel": ".manager", "TransferJob": ".manager",
    "TransferManager": ".manager",
    "RangeServer": ".server", "Throttle": ".server",
    "FaultPolicy": ".server",
    "PeerMirror": ".mirror",
    "Sink": ".sink", "BufferSink": ".sink", "CallableSink": ".sink",
    "ChunkScheduler": ".sched",
    "ShardPlan": ".shard", "StealLedger": ".shard",
    "plan_shards": ".shard", "plan_for_mesh": ".shard",
    "fetch_sharded": ".shard",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    target = _EXPORTS.get(name)
    if target is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    value = getattr(import_module(target, __name__), name)
    globals()[name] = value          # cache: __getattr__ runs once per name
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
