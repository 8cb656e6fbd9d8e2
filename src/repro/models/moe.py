"""Mixture-of-Experts block with expert parallelism.

Two dispatch paths, chosen statically per call site:

* **a2a path** (training / prefill): tokens are sequence-sharded over the
  ``model`` mesh axis; each device routes its own tokens, buckets them by
  destination expert shard with a capacity limit, and exchanges buckets via
  ``jax.lax.all_to_all`` inside a ``shard_map``.  Expert weights are sharded
  over ``model`` (expert dim) — classic expert parallelism with explicit,
  inspectable collectives (the roofline's all-to-all bytes come straight
  from here).  Optional FSDP storage sharding of the expert weights over the
  data axes all-gathers them inside the block (and its AD transpose
  reduce-scatters the grads — ``check_vma`` keeps this correct).

* **one-hot path** (decode, tiny token counts, or no mesh): the classic
  Switch-style dispatch einsum.  Its FLOPs are O(T·E·cap·d), catastrophic at
  training token counts but optimal for a 128-token decode step, and it
  needs no divisibility constraints.

Capacity overflows drop tokens (they ride the residual), standard practice;
an auxiliary load-balance loss (Switch-style) is returned for training.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.context import active_ctx, constrain
from repro.models.common import ModelConfig, ParamSpec

__all__ = ["moe_specs", "moe_block"]


def moe_specs(cfg: ModelConfig) -> dict:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    specs = {
        "router": ParamSpec((d, E), ("embed", None), "normal", s_in),
        "wi": ParamSpec((E, d, f), ("expert", "expert_mlp", None), "normal", s_in),
        "wg": ParamSpec((E, d, f), ("expert", "expert_mlp", None), "normal", s_in),
        "wo": ParamSpec((E, f, d), ("expert", "expert_mlp", None), "normal", s_out),
    }
    if cfg.mlp_act != "swiglu":
        del specs["wg"]
    return specs


def _gates(cfg: ModelConfig, xt: jax.Array, router: jax.Array):
    """Router: returns (weights [T,k] f32, indices [T,k] i32, lb_loss)."""
    with jax.named_scope("f32c"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )
        # Switch-style load-balance loss: E * sum_e f_e * P_e
        E = cfg.n_experts
        me = jnp.mean(probs, axis=0)                        # [E] mean prob
        one_hot_top1 = jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32)
        ce = jnp.mean(one_hot_top1, axis=0)                 # [E] top1 fraction
        lb_loss = E * jnp.sum(me * ce)
    return gate_vals, gate_idx, lb_loss


def _expert_mlp(cfg: ModelConfig, xs: jax.Array, wi, wg, wo) -> jax.Array:
    """xs [E_loc, C, d] -> [E_loc, C, d] through each local expert."""
    h = jnp.einsum("ecd,edf->ecf", xs, wi)
    if wg is not None:
        g = jnp.einsum("ecd,edf->ecf", xs, wg)
        h = jax.nn.silu(g) * h
    else:
        h = jnp.square(jax.nn.relu(h)) if cfg.mlp_act == "relu2" else jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, wo)


# ------------------------------------------------------------ one-hot path

def _moe_onehot(cfg: ModelConfig, xt, gate_vals, gate_idx, wi, wg, wo):
    """Switch dispatch-einsum; T must be small (decode) for sane FLOPs."""
    T = xt.shape[0]
    E, k = cfg.n_experts, cfg.top_k
    cap = max(int(math.ceil(T * k / E * cfg.capacity_factor)), 1)

    flat_e = gate_idx.reshape(-1)                           # [T*k]
    onehot_e = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)   # [T*k, E]
    pos = jnp.cumsum(onehot_e, axis=0) - 1                  # position in expert
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    # dispatch tensor [T*k, E, cap]
    disp = (jax.nn.one_hot(flat_e, E, dtype=xt.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=xt.dtype)[:, None, :cap])
    xe = jnp.einsum("sec,sd->ecd", disp, jnp.repeat(xt, k, axis=0))
    ye = _expert_mlp(cfg, xe, wi, wg, wo)
    weights = gate_vals.reshape(-1).astype(xt.dtype)        # [T*k]
    out_sel = jnp.einsum("sec,ecd->sd", disp, ye) * weights[:, None]
    return out_sel.reshape(T, k, -1).sum(axis=1)


# --------------------------------------------------------------- a2a path

def _moe_a2a_local(cfg: ModelConfig, xt, gate_vals, gate_idx, wi, wg, wo,
                   *, n_shards: int, fsdp_axes: tuple):
    """Per-device body (inside shard_map).  xt [T_loc, d] are THIS device's
    tokens; wi/wg/wo [E_loc, ...] are THIS device's experts (possibly
    FSDP-sharded on dim 1 over ``fsdp_axes``)."""
    if fsdp_axes:
        wi = jax.lax.all_gather(wi, fsdp_axes, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, fsdp_axes, axis=1, tiled=True)
        if wg is not None:
            wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)

    T_loc, d = xt.shape
    E, k, M = cfg.n_experts, cfg.top_k, n_shards
    E_loc = E // M
    cap = max(int(math.ceil(T_loc * k / M * cfg.capacity_factor)), 1)

    flat_e = gate_idx.reshape(-1)                       # [T_loc*k] global ids
    dest = flat_e // E_loc                              # destination shard
    local_e = flat_e - dest * E_loc                     # id on that shard

    # position within destination bucket
    onehot_d = jax.nn.one_hot(dest, M, dtype=jnp.int32)
    pos = jnp.cumsum(onehot_d, axis=0) - 1
    pos = jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap)                   # cap = drop slot

    token_of = jnp.arange(T_loc * k) // k
    send_x = jnp.zeros((M, cap, d), xt.dtype).at[dest, pos_c].set(
        xt[token_of], mode="drop")
    send_e = jnp.full((M, cap), E_loc, jnp.int32).at[dest, pos_c].set(
        local_e, mode="drop")                           # E_loc = empty slot

    recv_x = jax.lax.all_to_all(send_x, "model", split_axis=0, concat_axis=0)
    recv_e = jax.lax.all_to_all(send_e, "model", split_axis=0, concat_axis=0)

    R = M * cap
    rx, re = recv_x.reshape(R, d), recv_e.reshape(R)
    cap2 = max(int(math.ceil(R / E_loc * cfg.capacity_factor)), 1)
    onehot_e = jax.nn.one_hot(re, E_loc, dtype=jnp.int32)   # empty rows: all 0
    pos2 = jnp.cumsum(onehot_e, axis=0) - 1
    pos2 = jnp.take_along_axis(
        pos2, jnp.minimum(re, E_loc - 1)[:, None], axis=1)[:, 0]
    keep2 = (re < E_loc) & (pos2 < cap2)
    e_c = jnp.where(keep2, re, 0)
    p_c = jnp.where(keep2, pos2, cap2)

    buf = jnp.zeros((E_loc, cap2, d), xt.dtype).at[e_c, p_c].set(
        jnp.where(keep2[:, None], rx, 0), mode="drop")
    yb = _expert_mlp(cfg, buf, wi, wg, wo)              # [E_loc, cap2, d]

    y_rows = yb[e_c, jnp.where(keep2, pos2, 0)] * keep2[:, None].astype(xt.dtype)
    back = jax.lax.all_to_all(
        y_rows.reshape(M, cap, d), "model", split_axis=0, concat_axis=0)

    sel = back[dest, jnp.where(keep, pos, 0)] * keep[:, None].astype(xt.dtype)
    weights = gate_vals.reshape(-1).astype(xt.dtype)
    out = (sel * weights[:, None]).reshape(T_loc, k, d).sum(axis=1)
    return out


# ----------------------------------------------------------------- entry

def moe_block(p: dict, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x [B, S, d] -> (y [B, S, d], lb_loss scalar)."""
    B, S, d = x.shape
    ctx = active_ctx()
    wi, wo = p["wi"], p["wo"]
    wg = p.get("wg")

    use_a2a = False
    if ctx is not None:
        M = ctx.axis_size("model")
        # a2a path needs the sequence divisible across expert shards and
        # enough tokens to be worth it (decode steps use the one-hot path).
        use_a2a = S % max(M, 1) == 0 and B * S >= 4 * M

    if not use_a2a:
        xt = x.reshape(B * S, d)
        gate_vals, gate_idx, lb = _gates(cfg, xt, p["router"])
        y = _moe_onehot(cfg, xt, gate_vals.astype(x.dtype), gate_idx,
                        wi, wg, wo)
        return y.reshape(B, S, d), lb

    # ---- a2a path: reshard activations seq-wise over 'model' ----
    x = constrain(x, "batch", "moe_seq", "embed")
    xt = x.reshape(B * S, d)
    gate_vals, gate_idx, lb = _gates(cfg, xt, p["router"])
    gate_vals = gate_vals.astype(x.dtype)

    mesh = ctx.mesh
    batch_axes = ctx.batch_axes()
    fsdp = ctx.rules.rules.get("expert_mlp")
    if isinstance(fsdp, str):
        fsdp = (fsdp,)
    fsdp_axes = tuple(a for a in (fsdp or ()) if a in mesh.axis_names)

    x_spec = P((*batch_axes, "model"))
    w_spec = P("model", fsdp_axes if fsdp_axes else None, None)

    local = lambda xt_, gv_, gi_, wi_, wg_, wo_: _moe_a2a_local(
        cfg, xt_, gv_, gi_, wi_, wg_, wo_,
        n_shards=ctx.axis_size("model"), fsdp_axes=fsdp_axes,
    )
    if wg is None:
        fn = jax.shard_map(
            lambda xt_, gv_, gi_, wi_, wo_: local(xt_, gv_, gi_, wi_, None, wo_),
            mesh=mesh,
            in_specs=(P((*batch_axes, "model"), None), P((*batch_axes, "model"), None),
                      P((*batch_axes, "model"), None), w_spec, w_spec),
            out_specs=P((*batch_axes, "model"), None),
        )
        yt = fn(xt, gate_vals, gate_idx, wi, wo)
    else:
        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P((*batch_axes, "model"), None), P((*batch_axes, "model"), None),
                      P((*batch_axes, "model"), None), w_spec, w_spec, w_spec),
            out_specs=P((*batch_axes, "model"), None),
        )
        yt = fn(xt, gate_vals, gate_idx, wi, wg, wo)

    y = yt.reshape(B, S, d)
    y = constrain(y, "batch", "seq", "embed")
    return y, lb
