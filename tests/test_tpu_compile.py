"""Mosaic compile rehearsals of the main-path Pallas kernels for a
described TPU v5e chip, at deployment widths.

Interpret mode never runs Mosaic, so tiling, layout and lowering faults
(lane reshapes, reversals, unsupported dot forms, block shapes off the
(8, 128) tiling) only show when the kernel is compiled for the chip. The
chip needs not be attached: ``jax.experimental.topologies`` describes one
and XLA's TPU compiler compiles against it. Nothing runs here; results
are checked by the interpret-mode parity suites and by ``chip_smoke.py``
on the chip.

The topology is described inside a module fixture (never at import or
collection time: only one process may hold the TPU library), and the
persistent compilation cache is off around these compiles (an entry
compiled for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dispatch_topl import DispatchPlan, adc_dispatch_topl_pallas
from repro.kernels.gather_topl import adc_gather_topl_pallas
from repro.kernels.rerank_dist import rerank_gather_dist_pallas
from repro.kernels.topl_scan import adc_scan_topl_pallas
from repro.kernels.unq_encode import unq_encode_pallas

L = 500          # the paper's rerank budget (stage-1 top-L)
K = 256


@pytest.fixture(scope="module")
def v5e_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes):
    """Compile ``fn`` for the described chip; the kernel must be in it."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m", [8, 16])
def test_adc_scan_topl_compiles(v5e_chip, m):
    n = 1 << 20
    _compile(v5e_chip,
             lambda c, lut, b: adc_scan_topl_pallas(c, lut, b, topl=L,
                                                    n_valid=n - 7),
             ((n, m), jnp.uint8), ((8, m, K), jnp.float32),
             ((n,), jnp.float32))


def test_adc_gather_topl_compiles(v5e_chip):
    q, w, m = 8, 4096, 8
    _compile(v5e_chip,
             lambda c, g, b, lut: adc_gather_topl_pallas(c, g, b, lut,
                                                         topl=L),
             ((q, w, m), jnp.uint8), ((q, w), jnp.int32),
             ((q, w), jnp.float32), ((q, m, K), jnp.float32))


@pytest.mark.parametrize("cap", [1, 8])
def test_adc_dispatch_topl_compiles(v5e_chip, cap):
    e1, tiles, n, q, m = 65, 256, 1 << 16, 8, 8
    plan = [((e1, cap), jnp.int32)] + [((tiles,), jnp.int32)] * 5
    _compile(v5e_chip,
             lambda c, g, r, lut, ct, *p: adc_dispatch_topl_pallas(
                 c, g, r, lut, ct, DispatchPlan(*p), topl=L),
             ((n, m), jnp.uint8), ((n,), jnp.int32), ((n,), jnp.float32),
             ((q, m, K), jnp.float32), ((e1, cap), jnp.float32), *plan)


@pytest.mark.parametrize("m", [8, 9])      # 9: residual IVF's cell column
def test_rerank_gather_dist_compiles(v5e_chip, m):
    q, l, d = 8, 512, 96
    _compile(v5e_chip, rerank_gather_dist_pallas,
             ((q, l, m), jnp.uint8), ((q, d), jnp.float32),
             ((m, K, d), jnp.float32))


def test_unq_encode_compiles(v5e_chip):
    b, m, d_c = 8192, 8, 256
    _compile(v5e_chip, unq_encode_pallas,
             ((b, m, d_c), jnp.float32), ((m, K, d_c), jnp.float32))
