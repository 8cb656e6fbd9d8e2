"""Pallas TPU chunked SSD (Mamba2) forward scan.

One kernel instance owns a block of SSD heads for one batch element and
walks the sequence chunk by chunk (grid k-axis sequential on TPU), so the
recurrent state lives in f32 VMEM scratch for the whole sequence — the HBM
I/O is bf16 x/B/C in, bf16 y out, exactly the dtype contract the roofline
walker assumes for the SSD math (DESIGN.md §6).

The kernel sees head-major layouts (the wrapper transposes), so every
per-head operand is a 2-D tile and every contraction a plain 2-D matmul —
Mosaic lowers neither ``cumsum`` nor 3-D broadcasts of a ``[Q, Q, Hb]``
decay tensor:

  x tile    [Hb, Q, P]   VMEM (bf16 in, f32 compute)
  dt tile   [Hb, Q]      VMEM f32
  A         [Hb, 1]      VMEM f32
  Bᵀ tile   [N, Q]       VMEM
  C tile    [Q, N]       VMEM
  hᵀ state  [Hb, N, P]   VMEM scratch, f32, persists across chunks

Within a chunk, per head, the standard SSD decomposition:
  y = (C·Bᵀ ∘ L) · (dt·x)  +  (C · hᵀ) ∘ exp(cum)        (intra + inter)
  hᵀ_out = hᵀ_in * exp(total) + Bᵀ · (dt·x ∘ exp(total-cum))
with ``cum`` the in-chunk prefix sum of dt·A, taken as a lower-triangular
matmul (lane-major) and a masked lane reduction (sublane-major).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_scan_bh"]


def _kernel(x_ref, dt_ref, a_ref, bt_ref, c_ref, y_ref, h_ref, *,
            chunk, n_heads):
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    dt = dt_ref[0].astype(f32)                # [Hb, Q]
    dA = dt * a_ref[0].astype(f32)            # [Hb, Q]  (A [Hb, 1] < 0)
    Bt = bt_ref[0].astype(f32)                # [N, Q]
    Cm = c_ref[0].astype(f32)                 # [Q, N]

    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = kj <= qi                            # [Q, Q] lower triangle
    tri_f = tri.astype(f32)
    eye = (kj == qi).astype(f32)

    # cum[h, q] = sum_{k <= q} dA[h, k]: the in-chunk prefix sum, lane-major
    cum = jax.lax.dot_general(
        dA, tri_f, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32)
    scores = jnp.dot(Cm, Bt, preferred_element_type=f32)    # [Q, Q] = C·Bᵀ

    for h in range(n_heads):
        da_h = dA[h:h + 1, :]                                # [1, Q]
        row = cum[h:h + 1, :]                                # [1, Q]
        col = jnp.sum(tri_f * da_h, axis=1, keepdims=True)   # [Q, 1]
        total = jnp.sum(da_h, axis=1, keepdims=True)         # [1, 1]
        dt_col = jnp.sum(eye * dt[h:h + 1, :], axis=1, keepdims=True)

        # decay L[q, k] = exp(cum_q - cum_k) for k <= q
        L = jnp.where(tri, jnp.exp(col - row), 0.0)          # [Q, Q]
        xdt = x_ref[0, h].astype(f32) * dt_col               # [Q, P]
        y_intra = jnp.dot(scores * L, xdt, preferred_element_type=f32)

        ht = h_ref[h]                                        # [N, P]
        y_inter = jnp.dot(Cm, ht, preferred_element_type=f32) * jnp.exp(col)
        upd = jnp.dot(Bt, xdt * jnp.exp(total - col),
                      preferred_element_type=f32)            # [N, P]
        h_ref[h] = ht * jnp.exp(total) + upd
        y_ref[0, h] = (y_intra + y_inter).astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan_bh(
    x: jax.Array,            # [B, S, Hb, P]
    dt: jax.Array,           # [B, S, Hb] f32
    A: jax.Array,            # [B, Hb] f32 (negative; per-block slice)
    Bm: jax.Array,           # [B, S, N]
    Cm: jax.Array,           # [B, S, N]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, S, Hb, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk

    kernel = functools.partial(_kernel, chunk=chunk, n_heads=Hb)
    y = pl.pallas_call(
        kernel,
        grid=(B, n_chunks),
        in_specs=[
            pl.BlockSpec((1, Hb, chunk, P), lambda b, c: (b, 0, c, 0)),
            pl.BlockSpec((1, Hb, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, Hb, 1), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, N, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hb, chunk, P), lambda b, c: (b, 0, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hb, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((Hb, N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), A[:, :, None],
      Bm.transpose(0, 2, 1), Cm)
    return y.transpose(0, 2, 1, 3)
