"""Perf-path equivalence: the optimized implementations must match the
paper-faithful baselines exactly (EXPERIMENTS.md §Perf iterations 5-7)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import rwkv6
from repro.models.config import ModelConfig


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_wkv_matches_sequential(chunk):
    rng = np.random.default_rng(chunk)
    b, t, h, dh = 2, 32, 2, 8
    r, k, v = (jnp.asarray(rng.normal(size=(b, t, h, dh)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.8, 0.999, (b, t, h, dh)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, dh)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(b, h, dh, dh)), jnp.float32)
    o_seq, s_seq = rwkv6._wkv_scan(r, k, v, w, u, s0)
    o_ch, s_ch = rwkv6._wkv_chunked(r, k, v, w, u, s0, chunk)
    np.testing.assert_allclose(np.asarray(o_ch), np.asarray(o_seq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_ch), np.asarray(s_seq),
                               rtol=1e-4, atol=1e-4)


def test_chunked_wkv_model_level():
    cfg_s = ModelConfig(name="t", family="rwkv6", num_layers=2, d_model=128,
                        d_ff=256, vocab_size=64, compute_dtype=jnp.float32)
    params = rwkv6.init(jax.random.PRNGKey(0), cfg_s)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    l_seq = rwkv6.forward(params, cfg_s, {"tokens": toks})
    l_ch = rwkv6.forward(params, cfg_s.with_(rwkv_chunk=8),
                         {"tokens": toks})
    np.testing.assert_allclose(np.asarray(l_ch), np.asarray(l_seq),
                               rtol=2e-4, atol=2e-4)


def test_moe_ep_matches_baseline_on_mesh():
    """shard_map expert parallelism == pjit baseline (dropless capacity)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import registry
from repro.parallel import hints, sharding as shard_lib

cfg = configs.get("deepseek-moe-16b", smoke=True).with_(capacity_factor=8.0)
params = registry.init(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 17)),
                               jnp.int32)}
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = dict(shard_lib.RULES_SINGLE_POD)
ps = shard_lib.params_pspecs(registry.logical_axes(cfg), rules)
with mesh, hints.activation_sharding(rules, mesh):
    sp = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), ps,
        is_leaf=lambda x: isinstance(x, P)))
    l_base, _ = jax.jit(lambda p, b: registry.loss_fn(p, cfg, b))(sp, batch)
    l_ep, _ = jax.jit(lambda p, b: registry.loss_fn(
        p, cfg.with_(moe_ep=True), b))(sp, batch)
np.testing.assert_allclose(float(l_base), float(l_ep), rtol=2e-3)
print("EP-MATCH-OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=540)
    assert "EP-MATCH-OK" in r.stdout, (r.stdout[-500:], r.stderr[-2000:])


def test_microbatched_train_step_matches_single():
    """Gradient accumulation == single-batch step (up to fp summation)."""
    from repro import configs, optim
    from repro.models import registry
    from repro.parallel import steps as steps_lib

    cfg = configs.get("yi-6b", smoke=True)
    params = registry.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, 17)), jnp.int32)}

    outs = {}
    for mb in (1, 2, 4):
        step, opt = steps_lib.make_train_step(
            cfg, lr_fn=optim.constant(1e-3), microbatches=mb)
        p, o, m = jax.jit(step)(params, opt.init(params), batch,
                                jnp.asarray(0))
        outs[mb] = (p, float(m["loss"]))
    np.testing.assert_allclose(outs[1][1], outs[2][1], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(outs[1][0]), jax.tree.leaves(outs[4][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)
