"""Gradient-compression tests: quantization exactness bounds, error
feedback convergence, and multi-device wire semantics.

The multi-device cases run in a subprocess with 8 forced host devices
(jax locks the device count at first init, and the main test process
must keep seeing ONE device for every other test)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.compression import dequantize_int8, quantize_int8

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_quantize_roundtrip_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(4096), jnp.float32)
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    # symmetric int8: |err| <= scale/2 = max|x|/254
    assert err.max() <= float(np.abs(np.asarray(x)).max()) / 254 + 1e-7


def test_quantize_preserves_zero_and_extremes():
    x = jnp.asarray([0.0, 1.0, -1.0, 0.5], jnp.float32)
    q, s = quantize_int8(x)
    d = np.asarray(dequantize_int8(q, s))
    assert d[0] == 0.0
    np.testing.assert_allclose(d[1:3], [1.0, -1.0], rtol=1e-2)


def test_error_feedback_tracks_exact_mean():
    """EF compressed SGD sum tracks the exact sum over steps (single
    'device' = quantization error only)."""
    rng = np.random.default_rng(1)
    exact_acc = np.zeros(512, np.float32)
    comp_acc = np.zeros(512, np.float32)
    err = jnp.zeros(512, jnp.float32)
    for _ in range(50):
        g = jnp.asarray(rng.standard_normal(512), jnp.float32)
        exact_acc += np.asarray(g)
        q, s = quantize_int8(g + err)
        sent = dequantize_int8(q, s)
        err = (g + err) - sent
        comp_acc += np.asarray(sent)
    # error feedback: the residual is bounded (one quantization step),
    # not accumulating over the 50 steps
    resid = np.abs(exact_acc - comp_acc)
    one_step_bound = np.abs(exact_acc).max() / 254 * 5  # loose
    assert resid.max() < max(one_step_bound, 0.2), resid.max()


_MULTIDEV_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.optim.compression import (
        compressed_mean, compressed_reduce_scatter)

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    g_local = jnp.asarray(rng.standard_normal((8, 1024)), jnp.float32)

    # ---- compressed_reduce_scatter: int8 wire, f32 shard out
    def rs(g):
        return compressed_reduce_scatter(g[0], "data")
    out = shard_map(rs, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"))(g_local)
    got = np.asarray(out).reshape(-1)          # concat of 8 shards
    want = np.asarray(jnp.mean(g_local, axis=0)).reshape(-1)
    err = np.abs(got - want)
    tol = np.abs(want).max() / 100  # int8 quant bound, 8-way mean
    assert err.max() < max(tol, 0.05), ("rs", err.max())

    # ---- wire dtype check: the only full-size collective is int8
    txt = jax.jit(shard_map(rs, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data"))
                  ).lower(g_local).compile().as_text()
    a2a = [l for l in txt.splitlines() if "all-to-all" in l
           and "s8[" in l]
    big_f32 = [l for l in txt.splitlines()
               if ("all-to-all" in l or "all-gather" in l)
               and "f32[8,1024]" in l]
    assert a2a, "int8 all-to-all missing from compiled HLO"
    assert not big_f32, "full-size f32 collective leaked onto the wire"

    # ---- compressed_mean matches exact within quant tolerance
    def cm(g):
        return compressed_mean(g[0], ("data",))
    out2 = shard_map(cm, mesh=mesh, in_specs=P("data"),
                         out_specs=P())(g_local)
    err2 = np.abs(np.asarray(out2) - want)
    assert err2.max() < max(tol, 0.05), ("mean", err2.max())
    print("MULTIDEV_OK")
""")


def test_multidevice_wire_semantics():
    env = dict(os.environ, PYTHONPATH=_SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _MULTIDEV_PROG],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "MULTIDEV_OK" in res.stdout
