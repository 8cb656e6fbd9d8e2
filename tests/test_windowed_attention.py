"""Sliding-window attention must SKIP out-of-window keys (sliced k/v per
q block) with bit-level equivalence to the masked-full-keys form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.layers import attention, attention_specs
from repro.models.common import init_params


def _setup(window, S=512, dtype="float32"):
    cfg = get_config("gemma3-1b").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=1, head_dim=16,
        vocab_size=128, attn_window=window, dtype=dtype)
    p = init_params(jax.random.key(0), attention_specs(cfg), cfg.jdtype)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, S, 64)) * 0.3, cfg.jdtype)
    return cfg, p, x


@pytest.mark.parametrize("window", [32, 96, 128])
def test_windowed_slice_equals_masked(window):
    cfg, p, x = _setup(window)
    y_win = attention(p, cfg, x, causal=True, window=window, q_block=128)
    y_ref = attention(p, cfg, x, causal=True, window=window, q_block=512)
    np.testing.assert_allclose(np.asarray(y_win), np.asarray(y_ref),
                               atol=2e-5, rtol=2e-5)


def test_windowed_slice_fewer_flops():
    """The compiled windowed path must do substantially fewer dot FLOPs
    than the masked-full-keys path (that is the point of the skip)."""
    cfg, p, x = _setup(window=64, S=1024)

    def run(qb):
        return jax.jit(lambda x: attention(
            p, cfg, x, causal=True, window=64, q_block=qb))

    fl_win = run(128).lower(x).compile().cost_analysis()["flops"]
    fl_ref = run(1024).lower(x).compile().cost_analysis()["flops"]
    assert fl_win < fl_ref * 0.5, (fl_win, fl_ref)
