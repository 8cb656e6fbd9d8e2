"""Sharding context: logical-axis rules resolved against an active mesh.

Models are written against *logical* axis names ("batch", "heads", "mlp",
"expert", ...).  The launcher activates a ``ShardingCtx`` binding those
names to physical mesh axes; ``constrain`` then emits
``with_sharding_constraint`` hints and ``axis_size``/``has_axis`` let
blocks (MoE all-to-all) discover the topology.  With no active context
(unit tests, single-CPU smoke runs) everything degrades to a no-op, so the
same model code runs anywhere.

Hillclimbing edits the *rules*, never the models.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingRules",
    "ShardingCtx",
    "activate",
    "active_ctx",
    "constrain",
    "logical_to_spec",
    "named_sharding",
]

#: Baseline logical->mesh rules (megatron-style TP over "model", DP over
#: "pod"+"data").  Values are a mesh axis name, a tuple of axis names, or
#: None (replicated).  Per-arch overrides live in the arch config.
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "attn_in": None,        # attention-weight d dims (FSDP lever)
    "attn_out_d": None,
    "qheads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "expert_mlp": None,
    "moe_seq": "model",     # seq resharding at the MoE a2a boundary
    "layers": None,
    "state": None,          # SSM state dim
    "conv": None,
    "cache_seq": None,      # KV-cache sequence dim (seq-sharded for 500k)
    "frames": None,         # audio/vision source positions
    "fsdp": None,           # extra storage-only shard dim; "data" = FSDP
}


@dataclass(frozen=True)
class ShardingRules:
    rules: dict = field(default_factory=lambda: dict(DEFAULT_RULES))

    def override(self, **kw) -> "ShardingRules":
        d = dict(self.rules)
        d.update(kw)
        return ShardingRules(d)

    def resolve_entries(self, logical: Sequence[Optional[str]],
                        axes_present: frozenset) -> list:
        """Raw per-dim entries (mesh axis name / tuple / None), dropping
        mesh axes the active mesh does not have (no "pod" on one pod)."""
        out = []
        for name in logical:
            if name is None:
                out.append(None)
                continue
            target = self.rules.get(name)
            if target is None:
                out.append(None)
            elif isinstance(target, tuple):
                present = tuple(a for a in target if a in axes_present)
                out.append(present if present else None)
            else:
                out.append(target if target in axes_present else None)
        return out

    def resolve(self, logical: Sequence[Optional[str]],
                axes_present: frozenset) -> P:
        out = _dedupe(self.resolve_entries(logical, axes_present))
        while out and out[-1] is None:
            out.pop()
        return P(*out)


@dataclass
class ShardingCtx:
    mesh: Mesh
    rules: ShardingRules

    @property
    def axes(self) -> frozenset:
        return frozenset(self.mesh.axis_names)

    def spec(self, logical: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> P:
        entries = self.rules.resolve_entries(logical, self.axes)
        if shape is not None:
            # Divisibility masking for INPUT/storage shardings: jit argument
            # shardings must tile evenly (GSPMD only pads intermediates), so
            # an axis that doesn't divide the dim drops to replicated.  E.g.
            # GQA kv=8 heads cannot shard over model=16 -> wk/wv replicate
            # and the decode cache seq-shards instead (dryrun.py rules).
            entries = entries + [None] * (len(shape) - len(entries))
            masked = []
            for dim, entry in zip(shape, entries):
                if entry is None:
                    masked.append(None)
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                factor = 1
                for a in axes:
                    factor *= self.mesh.shape[a]
                masked.append(entry if dim % factor == 0 else None)
            entries = masked
        out = _dedupe(entries)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding(self, logical: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, shape))

    def axis_size(self, name: str) -> int:
        if name not in self.mesh.axis_names:
            return 1
        return self.mesh.shape[name]

    def batch_axes(self) -> tuple[str, ...]:
        """Physical axes the batch is sharded over (for psum in loss)."""
        target = self.rules.rules.get("batch")
        if target is None:
            return ()
        if isinstance(target, str):
            target = (target,)
        return tuple(a for a in target if a in self.mesh.axis_names)


def _dedupe(entries: list) -> list:
    """Drop mesh axes already claimed by an earlier dim (masking can free an
    axis — e.g. batch=1 decode frees 'data' for the cache_seq dim)."""
    seen: set = set()
    out = []
    for entry in entries:
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = tuple(a for a in axes if a not in seen)
        seen.update(keep)
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(keep)
    return out


_tls = threading.local()


def active_ctx() -> Optional[ShardingCtx]:
    return getattr(_tls, "ctx", None)


@contextmanager
def activate(mesh: Mesh, rules: Optional[ShardingRules] = None):
    """Bind a mesh + rules for the duration of a trace/lower call."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ShardingCtx(mesh=mesh, rules=rules or ShardingRules())
    try:
        yield _tls.ctx
    finally:
        _tls.ctx = prev


def logical_to_spec(logical: Sequence[Optional[str]]) -> P:
    ctx = active_ctx()
    if ctx is None:
        return P()
    return ctx.spec(logical)


def named_sharding(logical: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    ctx = active_ctx()
    if ctx is None:
        return None
    return ctx.sharding(logical)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Annotate ``x`` with the sharding its logical dims resolve to.

    No-op without an active context so model code is mesh-agnostic, and
    no-op inside a ``shard_map`` manual region (vma-varying values cannot
    take auto-axis constraints; the surrounding shard_map specs govern).
    """
    ctx = active_ctx()
    if ctx is None:
        return x
    if jax.typeof(x).vma or jax.sharding.get_abstract_mesh().manual_axes:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.sharding(logical))
