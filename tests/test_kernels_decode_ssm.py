"""Shape/dtype sweeps for the decode_attention and ssm_scan Pallas kernels
(interpret mode) against their pure-jnp oracles."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    decode_attention, decode_attention_ref, ssm_scan, ssm_scan_ref,
)

RNG = np.random.default_rng(42)


def _decode_case(B=2, KV=2, G=4, hd=64, S=512, dtype=jnp.bfloat16):
    q = jnp.asarray(RNG.standard_normal((B, 1, KV * G, hd)), dtype)
    kc = jnp.asarray(RNG.standard_normal((B, S, KV, hd)), dtype)
    vc = jnp.asarray(RNG.standard_normal((B, S, KV, hd)), dtype)
    return q, kc, vc


def _decode_ref(q, kc, vc, pos, window=None, scale=None):
    B, _, H, hd = q.shape
    S, KV = kc.shape[1], kc.shape[2]
    G = H // KV
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qg = q[:, 0].reshape(B, KV, G, hd).reshape(B * KV, G, hd)
    kk = kc.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vv = vc.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    out = decode_attention_ref(qg, kk, vv, pos, scale=scale, window=window)
    return out.reshape(B, KV, G, hd).reshape(B, 1, H, hd)


def _close(a, b, tol):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("hd", [64, 112, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_head_dims(hd, dtype):
    q, kc, vc = _decode_case(hd=hd, dtype=dtype)
    pos = jnp.int32(300)
    out = decode_attention(q, kc, vc, pos, blk_k=128, interpret=True)
    _close(out, _decode_ref(q, kc, vc, pos),
           2e-2 if dtype == jnp.bfloat16 else 2e-5)


@pytest.mark.parametrize("G", [1, 2, 8])
def test_decode_gqa_ratios(G):
    q, kc, vc = _decode_case(G=G)
    pos = jnp.int32(511)  # full cache valid
    out = decode_attention(q, kc, vc, pos, blk_k=256, interpret=True)
    _close(out, _decode_ref(q, kc, vc, pos), 2e-2)


@pytest.mark.parametrize("pos", [0, 1, 255, 256, 500])
def test_decode_positions(pos):
    """Block skipping must be exact at block boundaries and tiny caches."""
    q, kc, vc = _decode_case()
    out = decode_attention(q, kc, vc, jnp.int32(pos), blk_k=256,
                           interpret=True)
    _close(out, _decode_ref(q, kc, vc, jnp.int32(pos)), 2e-2)


@pytest.mark.parametrize("window", [32, 256, 1 << 20])
def test_decode_sliding_window(window):
    q, kc, vc = _decode_case(S=1024)
    pos = jnp.int32(900)
    out = decode_attention(q, kc, vc, pos, blk_k=256, window=window,
                           interpret=True)
    _close(out, _decode_ref(q, kc, vc, pos, window=window), 2e-2)


def test_decode_ragged_cache_padding():
    """Cache length not a multiple of blk_k pads and stays exact."""
    q, kc, vc = _decode_case(S=700)
    pos = jnp.int32(600)
    out = decode_attention(q, kc, vc, pos, blk_k=256, interpret=True)
    _close(out, _decode_ref(q, kc, vc, pos), 2e-2)


def test_decode_matches_model_decode_attention():
    """Same numbers as the XLA decode path in repro.models.layers."""
    from repro.configs import get_config
    from repro.models.common import init_params
    from repro.models.layers import attention_from_cache, attention_specs

    cfg = get_config("qwen3-1.7b").replace(
        n_layers=1, d_model=128, n_heads=4, n_kv_heads=2, vocab_size=64)
    p = init_params(jax.random.key(0), attention_specs(cfg), jnp.bfloat16)
    B, S = 2, 256
    x = jnp.asarray(RNG.standard_normal((B, 1, 128)) * 0.1, jnp.bfloat16)
    kc = jnp.asarray(RNG.standard_normal((B, S, 2, cfg.hd)), jnp.bfloat16)
    vc = jnp.asarray(RNG.standard_normal((B, S, 2, cfg.hd)), jnp.bfloat16)
    pos = jnp.int32(100)
    y_ref, k2, v2 = attention_from_cache(p, cfg, x, kc, vc, pos)

    # recompute with the kernel on the UPDATED caches, then out-project
    from repro.models.layers import _qkv
    q, _, _ = _qkv(p, cfg, x, x, pos[None], pos[None], True)
    q = q.reshape(B, 1, cfg.n_heads, cfg.hd)
    out = decode_attention(q, k2, v2, pos, blk_k=128,
                           scale=1.0 / math.sqrt(cfg.hd), interpret=True)
    y = jnp.einsum("bsnh,nhd->bsd", out.reshape(B, 1, cfg.n_heads, cfg.hd),
                   p["wo"])
    _close(y, y_ref, 3e-2)


# ------------------------------------------------------------------ ssm_scan

def _ssm_case(B=2, S=256, H=8, P=32, N=16, dtype=jnp.bfloat16):
    x = jnp.asarray(RNG.standard_normal((B, S, H, P)) * 0.5, dtype)
    dt = jnp.asarray(np.abs(RNG.standard_normal((B, S, H))) * 0.1,
                     jnp.float32)
    A = jnp.asarray(-np.abs(RNG.standard_normal(H)) - 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)) * 0.3, dtype)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)) * 0.3, dtype)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_ssm_chunk_sweep(chunk):
    args = _ssm_case()
    y = ssm_scan(*args, chunk=chunk, head_block=4, interpret=True)
    _close(y, ssm_scan_ref(*args), 2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_dtypes(dtype):
    args = _ssm_case(dtype=dtype)
    y = ssm_scan(*args, chunk=64, interpret=True)
    _close(y, ssm_scan_ref(*args), 2e-2 if dtype == jnp.bfloat16 else 2e-4)


@pytest.mark.parametrize("HP", [(4, 16), (8, 64), (16, 32)])
def test_ssm_head_shapes(HP):
    H, P = HP
    args = _ssm_case(H=H, P=P)
    y = ssm_scan(*args, chunk=64, head_block=min(4, H),
                 interpret=True)
    _close(y, ssm_scan_ref(*args), 2e-2)


def test_ssm_ragged_seq():
    args = _ssm_case(S=200)
    y = ssm_scan(*args, chunk=64, interpret=True)
    _close(y, ssm_scan_ref(*args), 2e-2)


def test_ssm_state_continuity():
    """Chunk boundaries must carry exact state: one long scan == the
    reference sequential recurrence everywhere, including the tail."""
    args = _ssm_case(S=512)
    y = ssm_scan(*args, chunk=128, interpret=True)
    ref = ssm_scan_ref(*args)
    _close(y[:, -32:], ref[:, -32:], 2e-2)


def test_ssm_matches_model_ssd():
    """Kernel vs the model's chunked SSD implementation."""
    from repro.models.ssm import _ssd_chunked
    x, dt, A, Bm, Cm = _ssm_case(S=256)
    y_model, _ = _ssd_chunked(x, dt, A, Bm, Cm, chunk=64)
    y_kernel = ssm_scan(x, dt, A, Bm, Cm, chunk=64, interpret=True)
    _close(y_kernel, y_model.astype(y_kernel.dtype), 2e-2)
