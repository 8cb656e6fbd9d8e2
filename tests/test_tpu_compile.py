"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what the chip would refuse (a
primitive Mosaic cannot lower, a block that overflows VMEM, a program that
does not fit HBM).  Interpret-mode tests cannot see any of that.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.autotune import _fused_sweep, default_grid, sweep_call
from repro.core.jax_sim import SimConfig
from repro.core.scenarios import paper_baseline
from repro.kernels import decode_attention, flash_attention, rmsnorm, ssm_scan
from repro.models.ssm import _mamba_dims


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described ``v5e:2x2`` host, with the persistent
    compilation cache off: a described-chip compile written there could not
    be read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else it logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


def _on(sharding, *arrays):
    return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in arrays]


def _compile(fn, sharding, *arrays):
    return jax.jit(fn).lower(*_on(sharding, *arrays)).compile()


@pytest.mark.parametrize("engine", ["round", "scan", "event"])
def test_retune_sweep_compiles_for_v5e(one_chip, engine):
    """The between-wave retune's fused (C, L) sweep at its real size: the
    six ``paper_baseline`` mirrors, the 16-point Table II grid, 4 seeds."""
    fleet = paper_baseline()
    args, static = sweep_call(
        [s.bandwidth for s in fleet], [s.rtt for s in fleet], 860 << 20,
        default_grid(), SimConfig(jitter=0.02), n_seeds=4, engine=engine)
    compiled = _fused_sweep.lower(*_on(one_chip, *args), **static).compile()
    out = compiled.out_info
    assert out.shape == (16, 4) and out.dtype == jnp.float32


def test_flash_attention_compiles_for_v5e(one_chip):
    cfg = get_config("qwen3-1.7b")
    q = jnp.zeros((1, 2048, cfg.n_heads, cfg.hd), jnp.bfloat16)
    kv = jnp.zeros((1, 2048, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    compiled = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
                        one_chip, q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_compiles_for_v5e(one_chip):
    cfg = get_config("qwen3-1.7b")
    x = jnp.zeros((4096, cfg.d_model), jnp.bfloat16)
    scale = jnp.zeros((cfg.d_model,), jnp.bfloat16)
    compiled = _compile(lambda x, s: rmsnorm(x, s, residual=x),
                        one_chip, x, scale)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_for_v5e(one_chip):
    cfg = get_config("qwen3-1.7b")
    q = jnp.zeros((2, 1, cfg.n_heads, cfg.hd), jnp.bfloat16)
    cache = jnp.zeros((2, 4096, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    pos = jnp.zeros((), jnp.int32)
    compiled = _compile(
        lambda q, k, v, p: decode_attention(q, k, v, p),
        one_chip, q, cache, cache, pos)
    assert "tpu_custom_call" in compiled.as_text()


def test_ssm_scan_compiles_for_v5e(one_chip):
    """Zamba2-7B's SSD widths (112 heads of 64, state 64).  Mosaic has no
    ``cumsum`` lowering; the kernel's in-chunk prefix sum must not use it."""
    cfg = get_config("zamba2-7b")
    _, H, P = _mamba_dims(cfg)
    N, S = cfg.ssm_state, 2048
    x = jnp.zeros((1, S, H, P), jnp.bfloat16)
    dt = jnp.zeros((1, S, H), jnp.float32)
    A = jnp.zeros((H,), jnp.float32)
    bc = jnp.zeros((1, S, N), jnp.bfloat16)
    compiled = _compile(ssm_scan, one_chip, x, dt, A, bc, bc)
    assert "tpu_custom_call" in compiled.as_text()
