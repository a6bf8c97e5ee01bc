"""Multi-device behaviour via subprocesses with forced host device counts
(the main test process must keep seeing 1 device — see conftest)."""
import os
import subprocess
import sys

import pytest


def _run(script: str) -> str:
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    return r.stdout


def test_pjit_train_step_on_2x4_mesh():
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs, optim
from repro.models import registry
from repro.parallel import hints, sharding as shard_lib, steps as steps_lib

assert len(jax.devices()) == 8
cfg = configs.get("yi-6b", smoke=True)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = dict(shard_lib.RULES_SINGLE_POD)
params_ps = shard_lib.params_pspecs(registry.logical_axes(cfg), rules)
train_step, opt = steps_lib.make_train_step(cfg, lr_fn=optim.constant(1e-3))

with mesh, hints.activation_sharding(rules, mesh):
    params = jax.jit(lambda: registry.init(jax.random.PRNGKey(0), cfg),
                     out_shardings=jax.tree.map(
                         lambda s: NamedSharding(mesh, s), params_ps,
                         is_leaf=lambda x: isinstance(x, P)))()
    opt_state = jax.jit(opt.init)(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, 17)), jnp.int32)}
    step = jax.jit(train_step)
    p1, o1, m1 = step(params, opt_state, batch, jnp.asarray(0))
    p2, o2, m2 = step(p1, o1, batch, jnp.asarray(1))
    assert np.isfinite(float(m2["loss"]))
    # loss decreases on a repeated batch
    assert float(m2["loss"]) < float(m1["loss"])
print("MESH-TRAIN-OK")
""")
    assert "MESH-TRAIN-OK" in out


def test_sharded_equals_single_device_loss():
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import registry
from repro.parallel import hints, sharding as shard_lib

cfg = configs.get("deepseek-moe-16b", smoke=True)
params = registry.init(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(
    rng.integers(0, cfg.vocab_size, (4, 17)), jnp.int32)}

loss_single, _ = jax.jit(
    lambda p, b: registry.loss_fn(p, cfg, b))(params, batch)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = dict(shard_lib.RULES_SINGLE_POD)
ps = shard_lib.params_pspecs(registry.logical_axes(cfg), rules)
with mesh, hints.activation_sharding(rules, mesh):
    sharded_params = jax.device_put(
        params, jax.tree.map(lambda s: NamedSharding(mesh, s), ps,
                             is_leaf=lambda x: isinstance(x, P)))
    loss_sharded, _ = jax.jit(
        lambda p, b: registry.loss_fn(p, cfg, b))(sharded_params, batch)

np.testing.assert_allclose(float(loss_single), float(loss_sharded),
                           rtol=2e-4)
print("SPMD-EQUIV-OK")
""")
    assert "SPMD-EQUIV-OK" in out


def test_elastic_restore_8_to_4_devices():
    """Save on an 8-device (2,4) mesh, restore onto a (4,) subset mesh with
    different sharding — the elastic-restart path."""
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np, tempfile, pathlib
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_elastic_mesh

tmp = tempfile.mkdtemp()
mesh8 = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
tree = {"w": jax.device_put(
    jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
    NamedSharding(mesh8, P("data", "model")))}
mgr = CheckpointManager(tmp)
mgr.save(3, tree)

mesh4 = make_elastic_mesh(jax.devices()[:4], model_parallel=2)
assert dict(mesh4.shape) == {"data": 2, "model": 2}
sh = {"w": NamedSharding(mesh4, P("data", "model"))}
restored, manifest = mgr.restore_latest(
    {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}, shardings=sh)
assert manifest["step"] == 3
np.testing.assert_array_equal(
    np.asarray(restored["w"]),
    np.arange(64, dtype=np.float32).reshape(8, 8))
assert restored["w"].sharding.mesh.devices.size == 4
print("ELASTIC-OK")
""")
    assert "ELASTIC-OK" in out


def test_device_resident_sharded_search_matches_flat():
    """ShardedIndex device placement: code shards resident on 8 devices
    under shard_map, per-device streaming scan+top-L, all-gather merge —
    bit-exact vs the flat single-device search (ragged tail included)."""
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.index import ShardedIndex, StreamingTopL, index_factory
from repro.data.descriptors import make_synthetic_dataset

assert len(jax.devices()) == 8
ds = make_synthetic_dataset("deep", n_train=800, n_base=3001, n_query=30,
                            seed=0)   # 3001: ragged tail shard
index = index_factory("RVQ2x32,Rerank60", dim=ds.dim)   # RVQ: bias shards
index.train(ds.train, iters=3).add(ds.base)
queries = jnp.asarray(ds.queries[:20])

d_flat, i_flat = index.search(queries, 15)
sharded = ShardedIndex(index, num_shards=8)
assert sharded.resolved_placement == "device"
d_dev, i_dev = sharded.search(queries, 15)
np.testing.assert_array_equal(np.asarray(i_flat), np.asarray(i_dev))
np.testing.assert_array_equal(np.asarray(d_flat), np.asarray(d_dev))

# the merged stage-1 pool itself is also bit-exact, bias included
luts = index._build_luts(queries)
ws, wi = StreamingTopL("xla").topl(index.codes, luts, index.bias, topl=60)
gs, gi = sharded.stage1_candidates(queries, topl=60)
np.testing.assert_array_equal(np.asarray(wi), np.asarray(gi))
np.testing.assert_array_equal(np.asarray(ws), np.asarray(gs))
print("DEVICE-SHARD-OK")
""")
    assert "DEVICE-SHARD-OK" in out


def test_device_resident_ivf_and_filtered_search_match_flat():
    """By-cell device sharding of an IVF index (each device probes only
    the cells it owns) and the filtered device path both reproduce the
    single-device results bit-for-bit."""
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.index import ShardedIndex, index_factory
from repro.data.descriptors import make_synthetic_dataset

assert len(jax.devices()) == 8
ds = make_synthetic_dataset("deep", n_train=800, n_base=3001, n_query=30,
                            seed=0)
queries = jnp.asarray(ds.queries[:20])

# IVF (RVQ inner: the bias stream threads the per-device plans)
ivf = index_factory("IVF16,RVQ2x32,Rerank60", dim=ds.dim)
ivf.train(ds.train, iters=3).add(ds.base)
sharded = ShardedIndex(ivf, num_shards=8)
assert sharded.resolved_placement == "device"
for nprobe in (3, 16):
    d_flat, i_flat = ivf.search(queries, 15, nprobe=nprobe)
    d_dev, i_dev = sharded.search(queries, 15, nprobe=nprobe)
    np.testing.assert_array_equal(np.asarray(i_flat), np.asarray(i_dev))
    np.testing.assert_array_equal(np.asarray(d_flat), np.asarray(d_dev))

# residual IVF (IVFADC): the per-(query, cell) correction composes onto
# each device's slot-bias stream host-side before the plans ship
res = index_factory("IVF16,Residual,PQ4x32,Rerank60", dim=ds.dim)
res.train(ds.train, iters=3).add(ds.base)
shr = ShardedIndex(res, num_shards=8)
assert shr.resolved_placement == "device"
for nprobe in (3, 16):
    d_flat, i_flat = res.search(queries, 15, nprobe=nprobe)
    d_dev, i_dev = shr.search(queries, 15, nprobe=nprobe)
    np.testing.assert_array_equal(np.asarray(i_flat), np.asarray(i_dev))
    np.testing.assert_array_equal(np.asarray(d_flat), np.asarray(d_dev))

# flat index + filter masks through the device path's qbias stream
flat = index_factory("RVQ2x32,Rerank60", dim=ds.dim)
flat.train(ds.train, iters=3).add(ds.base)
shf = ShardedIndex(flat, num_shards=8)
assert shf.resolved_placement == "device"
rng = np.random.default_rng(0)
for mask in (rng.integers(0, 2, flat.ntotal).astype(bool),
             rng.integers(0, 2, (20, flat.ntotal)).astype(bool)):
    d_flat, i_flat = flat.search(queries, 15, filter_mask=mask)
    d_dev, i_dev = shf.search(queries, 15, filter_mask=mask)
    np.testing.assert_array_equal(np.asarray(i_flat), np.asarray(i_dev))
    np.testing.assert_array_equal(np.asarray(d_flat), np.asarray(d_dev))
print("DEVICE-IVF-OK")
""")
    assert "DEVICE-IVF-OK" in out


def test_unq_data_parallel_search_matches():
    """The paper's scan sharded over 8 devices == single-device scan."""
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels import ops

rng = np.random.default_rng(0)
codes = jnp.asarray(rng.integers(0, 256, (4096, 8)), jnp.uint8)
lut = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
single = ops.adc_scan(codes, lut, impl="xla")

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
codes_sh = jax.device_put(codes, NamedSharding(mesh, P("data", None)))
lut_sh = jax.device_put(lut, NamedSharding(mesh, P()))
with mesh:
    sharded = jax.jit(lambda c, l: ops.adc_scan(c, l, impl="xla"))(
        codes_sh, lut_sh)
np.testing.assert_allclose(np.asarray(single), np.asarray(sharded),
                           rtol=1e-5, atol=1e-5)
print("UNQ-SPMD-OK")
""")
    assert "UNQ-SPMD-OK" in out
