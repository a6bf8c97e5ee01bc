"""Gradient compression: quantization error bounds + error feedback."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.optim import compress


def test_int8_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5000,)) * 3.0, jnp.float32)
    y = compress.compress_roundtrip(x)
    err = np.abs(np.asarray(y - x))
    scale_bound = float(jnp.max(jnp.abs(x))) / 127.0
    assert err.max() <= scale_bound * 0.5 + 1e-6


def test_int8_handles_odd_shapes_and_zeros():
    for shape in [(1,), (3, 5), (2049,), (7, 11, 13)]:
        x = jnp.zeros(shape, jnp.float32)
        y = compress.compress_roundtrip(x)
        assert y.shape == shape
        np.testing.assert_allclose(np.asarray(y), 0.0, atol=1e-9)


def test_error_feedback_accumulates_residual():
    opt = compress.with_error_feedback(optim.sgd(), scheme="int8")
    params = {"w": jnp.zeros((4,), jnp.float32)}
    state = opt.init(params)
    # tiny gradient that quantizes to ~0 against its own scale is still
    # eventually applied thanks to EF accumulation across steps
    g = {"w": jnp.asarray([1e-4, -1e-4, 1e-4, -1e-4], jnp.float32)}
    p = params
    for _ in range(50):
        p, state = opt.apply(p, g, state, 1.0)
    moved = np.abs(np.asarray(p["w"]))
    assert (moved > 1e-4).all()   # ~50 steps x 1e-4 each = 5e-3 expected


def test_ef_sgd_converges_on_quadratic():
    """min ||x - t||^2 with int8-EF gradients converges like plain SGD."""
    t = jnp.asarray([1.0, -2.0, 3.0, 0.5])
    opt = compress.with_error_feedback(optim.sgd(), "int8")
    params = {"x": jnp.zeros((4,), jnp.float32)}
    state = opt.init(params)
    for _ in range(100):
        g = {"x": 2 * (params["x"] - t)}
        params, state = opt.apply(params, g, state, 0.1)
    np.testing.assert_allclose(np.asarray(params["x"]), np.asarray(t),
                               atol=1e-2)


def test_compressed_psum_matches_mean_psum():
    """shard_map int8 all-reduce approximates the exact mean."""
    import os
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compress import compressed_psum
from jax import shard_map

mesh = jax.make_mesh((4,), ("d",))
x = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.0

def f(xs):
    return compressed_psum(xs[0], "d")

y = jax.jit(shard_map(f, mesh=mesh, in_specs=P("d", None),
                      out_specs=P()))(x)
want = np.asarray(x).mean(0)
np.testing.assert_allclose(np.asarray(y), want, rtol=0.02, atol=0.02)
print("OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, cwd=".")
    assert "OK" in r.stdout, r.stderr[-2000:]
