"""Explicit expert-parallel MoE dispatch via shard_map (the §Perf
iteration-6 path; `repro/models/moe.py` is the pjit-auto baseline).

Under pjit, the sort-based dispatch lowers to GSPMD-chosen collectives that
measured 336 s of projected wire time on deepseek-moe train_4k (global
argsort + gathers materialized via all-gather). This path pins the
communication pattern to the textbook EP schedule instead:

  1. tokens are sequence-split across the "model" axis (each of the 16
     model ranks routes a disjoint 1/16 of the local tokens);
  2. local top-k routing + capacity into per-expert buffers (E, C_loc, d);
  3. all-to-all over "model": each rank keeps its E/16 experts and
     receives those experts' rows from all 16 peers;
  4. batched expert FFN on (E/16, 16*C_loc, d);
  5. reverse all-to-all + local combine;
  6. all-gather the token slices to restore the replicated activation.

Wire cost = 2 all-to-alls of the dispatched activations + one activation
all-gather — the information-theoretic minimum for EP + the SP boundary.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import moe as moe_lib


def _local_moe(p_local, cfg: ModelConfig, x_loc, n_model: int):
    """Per-device body. x_loc: (n_loc, d) this rank's token slice;
    p_local: router replicated, expert weights sliced (E/n_model, ...)."""
    n_loc, d = x_loc.shape
    e, k = cfg.num_experts, cfg.top_k
    e_loc = e // n_model
    cap = int(math.ceil(n_loc * k * cfg.capacity_factor / e))
    cap = min(max(cap, cfg.min_capacity), n_loc * k)

    gates, idx, balance = moe_lib.route(p_local, cfg, x_loc)
    flat_e = idx.reshape(-1)
    flat_gate = gates.reshape(-1)
    flat_tok = jnp.arange(n_loc * k, dtype=jnp.int32) // k

    sort_idx = jnp.argsort(flat_e, stable=True)          # local sort only
    sorted_e = flat_e[sort_idx]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e))
    rank = jnp.arange(n_loc * k, dtype=jnp.int32) - seg_start[sorted_e]
    kept = rank < cap
    dest_e = jnp.where(kept, sorted_e, e)
    dest_c = jnp.where(kept, rank, 0)

    buf = jnp.zeros((e + 1, cap, d), cfg.compute_dtype)
    buf = buf.at[dest_e, dest_c].set(x_loc[flat_tok[sort_idx]])
    send = buf[:e].reshape(n_model, e_loc, cap, d)

    # dispatch a2a: axis 0 = destination rank -> axis 0 = source rank
    recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                              tiled=True)                # (n_model*e_loc? ...)
    recv = recv.reshape(n_model, e_loc, cap, d).transpose(1, 0, 2, 3)
    expert_in = recv.reshape(e_loc, n_model * cap, d)

    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    w_g, w_u, w_d = p_local["w_gate"], p_local["w_up"], p_local["w_down"]
    h_g = act(jnp.einsum("ecd,edf->ecf", expert_in,
                         w_g.astype(cfg.compute_dtype)))
    h_u = jnp.einsum("ecd,edf->ecf", expert_in,
                     w_u.astype(cfg.compute_dtype))
    out = jnp.einsum("ecf,efd->ecd", h_g * h_u,
                     w_d.astype(cfg.compute_dtype))      # (e_loc, n*cap, d)

    # combine a2a (reverse)
    back = out.reshape(e_loc, n_model, cap, d).transpose(1, 0, 2, 3)
    back = back.reshape(n_model * e_loc, cap, d)
    mine = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0,
                              tiled=True)
    mine = mine.reshape(e, cap, d)

    out_pad = jnp.concatenate(
        [mine, jnp.zeros((1, cap, d), mine.dtype)], axis=0)
    gathered = out_pad[dest_e, dest_c]
    weighted = gathered * flat_gate[sort_idx][:, None].astype(gathered.dtype)
    combined = jnp.zeros((n_loc, d), cfg.compute_dtype).at[
        flat_tok[sort_idx]].add(weighted)

    if cfg.num_shared_experts:
        from repro.models import layers
        combined = combined + layers.mlp_block(p_local["shared"], cfg, x_loc)
    return combined, balance


def moe_block_ep(p, cfg: ModelConfig, x, mesh):
    """shard_map expert-parallel MoE. x: (B, T, d), consumed in the
    sequence-parallel layout P("data","model",None) — each device routes
    its own (B/data, T/model) token slice (the SP residual layout the
    scan body already maintains, so entering EP costs no extra reshard).
    Returns (out, balance)."""
    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape["model"]

    def body(p_local, x_blk):
        bb, t_loc, d = x_blk.shape              # local (B/data, T/model, d)
        x_loc = x_blk.reshape(bb * t_loc, d)
        out_loc, balance = _local_moe(p_local, cfg, x_loc, n_model)
        balance = jax.lax.pmean(jax.lax.pmean(balance, "model"), "data")
        return out_loc.reshape(bb, t_loc, d), balance

    param_specs = {
        "router": P(),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    if cfg.num_shared_experts:
        param_specs["shared"] = jax.tree.map(lambda _: P(), p["shared"])

    from repro.parallel import hints
    with hints.disabled():   # no sharding constraints inside manual bodies
        out, balance = jax.shard_map(
            body, mesh=mesh,
            in_specs=(param_specs, P("data", "model", None)),
            out_specs=(P("data", "model", None), P()),
            check_vma=False,
        )(p, x)
    return out, balance
