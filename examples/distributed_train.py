"""Data+tensor-parallel training with fault tolerance, on 8 forced host
devices (run this script directly — it sets XLA_FLAGS before importing jax):

  * pjit train step on a (2, 4) ("data", "model") mesh
  * gradient compression (int8 + error feedback) on the DP reduction
  * checkpoint mid-run, kill (simulated), auto-resume, finish

    PYTHONPATH=src python examples/distributed_train.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import shutil
import tempfile

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs, optim
from repro.data.tokens import TokenStream
from repro.models import registry
from repro.parallel import hints, sharding as shard_lib
from repro.parallel import steps as steps_lib
from repro.runtime import Trainer, TrainerConfig
from repro.utils.pytree import param_count


def main():
    ckpt_dir = tempfile.mkdtemp(prefix="repro_dist_")
    cfg = configs.get("deepseek-moe-16b", smoke=True)
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = dict(shard_lib.RULES_SINGLE_POD)
    print(f"devices={len(jax.devices())} mesh={dict(mesh.shape)} "
          f"arch={cfg.name}")

    params_ps = shard_lib.params_pspecs(registry.logical_axes(cfg), rules)
    train_step, opt = steps_lib.make_train_step(
        cfg, lr_fn=optim.constant(3e-4), grad_compress="int8",
        microbatches=2)

    def build():
        with mesh, hints.activation_sharding(rules, mesh):
            params = jax.jit(
                lambda: registry.init(jax.random.PRNGKey(0), cfg),
                out_shardings=jax.tree.map(
                    lambda s: NamedSharding(mesh, s), params_ps,
                    is_leaf=lambda x: isinstance(x, P)))()
            opt_state = jax.jit(opt.init)(params)
        return params, opt_state

    params, opt_state = build()
    print(f"params={param_count(params):,} (sharded over {mesh.size} dev)")
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=32, batch_size=8)

    # --- phase 1: run until an injected failure at step 7 ---
    tcfg = TrainerConfig(total_steps=12, checkpoint_every=3,
                         checkpoint_dir=ckpt_dir, crash_at_step=7,
                         log_every=2, async_checkpoint=False)
    with mesh, hints.activation_sharding(rules, mesh):
        t1 = Trainer(tcfg, jax.jit(train_step), params, opt_state, stream)
        try:
            t1.run()
        except RuntimeError as e:
            print(f"!! {e} — restarting from the latest checkpoint")

    # --- phase 2: fresh process state, auto-resume, finish ---
    params, opt_state = build()
    stream2 = TokenStream(vocab_size=cfg.vocab_size, seq_len=32, batch_size=8)
    tcfg2 = TrainerConfig(total_steps=12, checkpoint_every=3,
                          checkpoint_dir=ckpt_dir, log_every=2,
                          async_checkpoint=False)
    with mesh, hints.activation_sharding(rules, mesh):
        t2 = Trainer(tcfg2, jax.jit(train_step), params, opt_state, stream2)
        final = t2.run()
    print(f"resumed at step {6}, finished at {t2.step}: "
          f"loss={final['loss']:.4f}")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    print("OK")


if __name__ == "__main__":
    main()
