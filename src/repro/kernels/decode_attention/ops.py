"""jit'd public wrapper for decode attention.

Maps the model convention (q [B,1,H,hd], caches [B,S,KV,hd], GQA) onto
the kernel convention ([B*KV, G, hd] / [B*KV, S, hd]), pads head_dim to
the 128-lane MXU width and the cache length to the block size (padded
positions are masked via ``pos``), and broadcasts KV heads.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import decode_attention_bkv

__all__ = ["decode_attention"]

_LANES = 128


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def decode_attention(
    q: jax.Array,               # [B, 1, H, hd]
    k_cache: jax.Array,         # [B, S_max, KV, hd]
    v_cache: jax.Array,         # [B, S_max, KV, hd]
    pos: jax.Array,             # scalar int32
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    blk_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, _, H, hd = q.shape
    S_max, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    qg = q[:, 0].reshape(B, KV, G, hd).reshape(B * KV, G, hd)
    kk = k_cache.transpose(0, 2, 1, 3).reshape(B * KV, S_max, hd)
    vv = v_cache.transpose(0, 2, 1, 3).reshape(B * KV, S_max, hd)

    qg = _pad_axis(qg, 2, _LANES)
    kk = _pad_axis(_pad_axis(kk, 2, _LANES), 1, blk_k)
    vv = _pad_axis(_pad_axis(vv, 2, _LANES), 1, blk_k)

    out = decode_attention_bkv(
        qg, kk, vv, pos, scale=scale, window=window, blk_k=blk_k,
        interpret=interpret)
    out = out[:, :, :hd].reshape(B, KV, G, hd).reshape(B, 1, H, hd)
    return out.astype(q.dtype)
