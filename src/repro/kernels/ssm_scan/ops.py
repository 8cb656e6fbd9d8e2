"""jit'd public wrapper for the SSD scan kernel.

Splits the SSD heads into VMEM-sized blocks (state [Hb, P, N] f32 must
fit scratch alongside the [Q, Q, Hb] decay tensor), pads the sequence to
the chunk size (zero dt ⇒ identity state update, zero C ⇒ zero output:
padding is exact), and runs one pallas_call per head block.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from .kernel import ssm_scan_bh

__all__ = ["ssm_scan"]


def ssm_scan(
    x: jax.Array,            # [B, S, H, P]
    dt: jax.Array,           # [B, S, H] (f32 or bf16)
    A: jax.Array,            # [H] f32 (negative)
    Bm: jax.Array,           # [B, S, N]
    Cm: jax.Array,           # [B, S, N]
    *,
    chunk: int = 128,
    head_block: int = 8,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, P = x.shape
    pad_s = (-S) % chunk
    if pad_s:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_s), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad_s), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad_s), (0, 0)))
    Sp = S + pad_s

    hb = min(head_block, H)
    assert H % hb == 0, (H, hb)
    outs = []
    Ab = jnp.broadcast_to(A[None, :], (B, H)).astype(jnp.float32)
    for h0 in range(0, H, hb):
        sl = slice(h0, h0 + hb)
        outs.append(ssm_scan_bh(
            x[:, :, sl, :], dt[:, :, sl].astype(jnp.float32),
            Ab[:, sl], Bm, Cm, chunk=chunk, interpret=interpret))
    y = jnp.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]
    return y[:, :S]
