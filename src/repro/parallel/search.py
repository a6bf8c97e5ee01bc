"""Device-resident distributed stage 1: per-device streaming scan+top-L
under ``shard_map``, merged with an all-gather of (L, 2) candidate tuples.

This is the pod-scale shape of the paper's billion-vector experiments: the
uint8 code matrix (and RVQ-style bias) lives SHARDED across devices —
``place_shards`` puts each contiguous row block on its own device once,
and searches reuse the placement — each device runs the streaming
scan+top-L engine over its own shard with replicated query LUTs, and the
per-device (Q, L) score/index tuples are all-gathered so the host-side
caller reranks ONE merged pool through the streaming stage-2 engine
(``Index._rerank_topk`` -> ``repro.index.rerank``). Stage 2 deliberately
runs after the merge rather than per shard: bit-parity with the flat
search requires reranking exactly the global top-L pool (a per-shard
local rerank would rank a superset and can disagree on the final top-k),
and the uint8 candidate-code gather is ~100x smaller than shipping
reconstructions between devices. A device-side merged rerank is a
ROADMAP open item.

Merge exactness: device d's global ids are ``local + d * shard_rows`` and
the gathered pools are concatenated device-major, so among equal scores
positions are in ascending-global-index order — the final ``lax.top_k``
therefore reproduces flat-search tie resolution bit-for-bit. Rows added to
pad the database to a device multiple get a +inf bias, so they can never
surface (the same -inf-in-the-negated-domain masking the kernel applies to
its own block padding).

``device_gather_topl`` is the IVF face: shards are CELL ranges of the
cell-grouped buffer, each device receives only its own ragged probe plan
(slots of cells it owns — "probes only owning shards" by construction),
runs the gathered scan+top-L (``ops.adc_gather_topl``), and the
all-gathered pools merge lexicographically by (score, GLOBAL id) on the
host — cell-grouped shards interleave global ids, so the device-major
positional argument above does not apply and the merge is explicit
(``candidates.merge_topl``).

``device_dispatch_topl`` is the same face over the cell-batched dispatch
engine: the router (``repro.index.dispatch.build_shard_dispatch``) routes
the global probe against each shard's clip-restricted CSR offsets ON
DEVICE — non-owned cells are empty spans, so shards need no probe
masking and the host never builds a ragged plan — each device streams
its owned cells once through ``ops.adc_dispatch_topl``, scatter-merges
its own per-cell partials to a per-query pool (``combine_pools``), and
the all-gathered pools merge lexicographically exactly like the gathered
face. Cell-sharded serving never touches host numpy on the hot path.

The memory/collective shape of these paths is pinned by the
``sharded.stage1.device`` / ``sharded.stage1.dispatch`` contracts in
``repro.analysis.contracts``: no device materializes a (Q, N) or even
(Q, N/D) score matrix, and the only cross-device collective is the
candidate-tuple all-gather.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

_IMAX = np.iinfo(np.int32).max


@functools.lru_cache(maxsize=16)
def _device_topl_fn(mesh, topl_local: int, shard_rows: int, impl: str,
                    has_qbias: bool):
    """Compiled per-device scan+top-L + all-gather for one mesh/shape."""
    from jax.sharding import PartitionSpec as P

    def per_device(codes, bias, luts, *qbias):
        scores, idx = ops.adc_scan_topl(
            codes, luts, topl=topl_local, bias=bias,
            qbias=qbias[0] if has_qbias else None, impl=impl)
        offset = jax.lax.axis_index("shard").astype(jnp.int32) * shard_rows
        # +inf slots (device pad rows, filtered-out points) keep the _IMAX
        # sentinel instead of a wrapped/out-of-range "global" id
        idx = jnp.where(jnp.isposinf(scores), _IMAX, idx + offset)
        # all-gather of the per-device (L, 2) candidate tuples -> every
        # device (and the host) sees the full (D, Q, L) pool
        return (jax.lax.all_gather(scores, "shard"),
                jax.lax.all_gather(idx, "shard"))

    in_specs = [P("shard"), P("shard"), P()]
    if has_qbias:
        in_specs.append(P(None, "shard"))
    f = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(f)


class PlacedShards(NamedTuple):
    """A flat database cut into one contiguous shard per device, each
    shard resident on its own device (see ``place_shards``)."""
    codes: jax.Array          # (D * shard_rows, M), sharded along rows
    bias: jax.Array           # (D * shard_rows,) f32; +inf on pad rows
    n: int                    # real rows (the rest is tail padding)
    shard_rows: int
    mesh: jax.sharding.Mesh


def place_shards(codes, bias, devices=None) -> PlacedShards:
    """Put row block s of ``codes`` (and ``bias``) on device s.

    Each shard is sliced and transferred on its own, so no device ever
    holds a padded copy of the whole database; the tail shard is padded
    on its own device to the common row count, its pad rows carrying a
    +inf bias so they can never surface (one SPMD program then serves
    the ragged tail)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = list(devices if devices is not None else jax.devices())
    d = len(devices)
    n, m = codes.shape
    shard_rows = -(-n // d)
    bias = bias if bias is not None else jnp.zeros((n,), jnp.float32)
    parts_c, parts_b = [], []
    for s, dev in enumerate(devices):
        lo, hi = min(s * shard_rows, n), min((s + 1) * shard_rows, n)
        c = jax.device_put(codes[lo:hi], dev)
        b = jax.device_put(bias[lo:hi].astype(jnp.float32), dev)
        pad = shard_rows - (hi - lo)
        if pad:
            c = jnp.pad(c, ((0, pad), (0, 0)))
            b = jnp.pad(b, (0, pad), constant_values=jnp.inf)
        parts_c.append(c)
        parts_b.append(b)
    mesh = jax.sharding.Mesh(np.asarray(devices), ("shard",))
    rows = NamedSharding(mesh, P("shard"))
    return PlacedShards(
        jax.make_array_from_single_device_arrays((d * shard_rows, m), rows,
                                                 parts_c),
        jax.make_array_from_single_device_arrays((d * shard_rows,), rows,
                                                 parts_b),
        n, shard_rows, mesh)


def device_stage1_topl(placed: PlacedShards, luts, *, topl: int, impl: str,
                       qbias=None):
    """Sharded stage 1 over a database placed by ``place_shards``.

    luts (Q, M, K) f32 (replicated), qbias None | (Q, N) per-(query,
    point) bias stream (the lowered filter mask), sharded along N
    alongside the codes -> (scores, indices), each (Q, min(topl, N)),
    bit-identical to the flat single-device search.
    """
    d = placed.mesh.devices.size
    q = luts.shape[0]
    topl = min(topl, placed.n)
    args = [placed.codes, placed.bias, luts.astype(jnp.float32)]
    if qbias is not None:
        pad = d * placed.shard_rows - placed.n
        args.append(jnp.pad(qbias.astype(jnp.float32), ((0, 0), (0, pad))))

    topl_local = min(topl, placed.shard_rows)
    fn = _device_topl_fn(placed.mesh, topl_local, placed.shard_rows, impl,
                         qbias is not None)
    s_all, i_all = fn(*args)

    # (D, Q, L) -> (Q, D*L) device-major, then one top-L over the pool
    pool_s = jnp.swapaxes(s_all, 0, 1).reshape(q, d * topl_local)
    pool_i = jnp.swapaxes(i_all, 0, 1).reshape(q, d * topl_local)
    neg, order = jax.lax.top_k(-pool_s, topl)
    return -neg, jnp.take_along_axis(pool_i, order, axis=1)


@functools.lru_cache(maxsize=16)
def _device_gather_fn(mesh, topl_local: int, impl: str):
    """Compiled per-device gathered scan+top-L + all-gather."""
    from jax.sharding import PartitionSpec as P

    def per_device(codes, rows, gids, rowbias, luts):
        scores, ids = ops.adc_gather_topl(
            codes[0], rows[0], gids[0], luts, rowbias=rowbias[0],
            topl=topl_local, impl=impl)
        return (jax.lax.all_gather(scores, "shard"),
                jax.lax.all_gather(ids, "shard"))

    f = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P("shard"), P()),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(f)


def device_gather_topl(codes, bias, plans, luts, rowbias_fn, *, topl: int,
                       impl: str, devices=None):
    """Device-resident IVF stage 1: one cell-range shard per device, each
    probing only the cells it owns.

    codes (N, M) the cell-grouped buffer; bias None | (N,) its per-point
    stream; plans: per shard ``(row_lo, row_hi, rows, gids, cells)`` —
    the shard-local ragged probe plan from ``IVFIndex._probe_plan`` (rows
    already shifted by ``row_lo``; cells are each slot's coarse cell, the
    residual correction's bias key); rowbias_fn(rows, gids, cells,
    shard_bias) -> the (Q, W) slot bias (gathered norms/residual cross
    terms + per-(query, cell) residual correction + lowered filter) or
    None. The slot bias is composed host-side BEFORE the shard plans ship
    to devices, so the per-device kernel contract is unchanged.

    Every shard's buffer slice is padded to a common row count and every
    plan to a common width, so one SPMD program serves the ragged shards;
    pad slots carry gid ``_IMAX`` and can never surface. The all-gathered
    (D, Q, L) pools merge lexicographically by (score, global id) — the
    exact flat-search tie-break over interleaved id ranges.

    Returns (scores, global ids), each (Q, min(topl, pool width)).
    """
    from repro.index.candidates import merge_topl

    devices = list(devices if devices is not None else jax.devices())
    d = len(devices)
    if len(plans) != d:
        raise ValueError(f"{len(plans)} shard plans for {d} devices")
    q = luts.shape[0]
    rmax = max(max(hi - lo for lo, hi, *_ in plans), 1)
    w = max(max(rows.shape[1] for _, _, rows, _, _ in plans), 1)

    codes_sh, rows_sh, gids_sh, rb_sh = [], [], [], []
    for row_lo, row_hi, rows, gids, cells in plans:
        shard_codes = codes[row_lo:row_hi]
        shard_codes = jnp.pad(
            shard_codes, ((0, rmax - shard_codes.shape[0]), (0, 0)))
        shard_bias = None if bias is None else bias[row_lo:row_hi]
        rows_j = jnp.asarray(rows)
        gids_j = jnp.asarray(gids)
        rb = rowbias_fn(rows_j, gids_j, cells, shard_bias)
        if rb is None:
            rb = jnp.zeros(rows_j.shape, jnp.float32)
        pad_w = w - rows.shape[1]
        codes_sh.append(shard_codes)
        rows_sh.append(jnp.pad(rows_j, ((0, 0), (0, pad_w))))
        gids_sh.append(jnp.pad(gids_j, ((0, 0), (0, pad_w)),
                               constant_values=_IMAX))
        rb_sh.append(jnp.pad(rb, ((0, 0), (0, pad_w))))

    mesh = jax.sharding.Mesh(np.asarray(devices), ("shard",))
    topl_local = min(topl, w)
    fn = _device_gather_fn(mesh, topl_local, impl)
    s_all, i_all = fn(jnp.stack(codes_sh), jnp.stack(rows_sh),
                      jnp.stack(gids_sh), jnp.stack(rb_sh),
                      luts.astype(jnp.float32))

    pool_s = jnp.swapaxes(s_all, 0, 1).reshape(q, d * topl_local)
    pool_i = jnp.swapaxes(i_all, 0, 1).reshape(q, d * topl_local)
    return merge_topl(pool_s, pool_i, topl)


@functools.lru_cache(maxsize=16)
def _device_dispatch_fn(mesh, topl_local: int, impl: str, has_qkeep: bool):
    """Compiled per-device routed dispatch + pool combine + all-gather."""
    from jax.sharding import PartitionSpec as P
    from repro.index.dispatch import combine_pools
    from repro.kernels.dispatch_topl import DispatchPlan

    def per_device(codes, ids, rowbias, qidx, te, tb, tf, tlo, thi,
                   comb_e, comb_slot, cellterm, luts, *qkeep):
        plan = DispatchPlan(qidx[0], te[0], tb[0], tf[0], tlo[0], thi[0])
        part_s, part_g = ops.adc_dispatch_topl(
            codes[0], ids[0], rowbias[0], luts, cellterm[0], plan,
            topl=topl_local, qkeep=qkeep[0][0] if has_qkeep else None,
            impl=impl)
        s, g = combine_pools(part_s, part_g, comb_e[0], comb_slot[0],
                             topl=topl_local)
        return (jax.lax.all_gather(s, "shard"),
                jax.lax.all_gather(g, "shard"))

    in_specs = [P("shard")] * 12 + [P()]
    if has_qkeep:
        in_specs.append(P("shard"))
    f = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(f)


def device_dispatch_topl(codes, shards, luts, *, topl: int, impl: str,
                         devices=None):
    """Device-resident IVF stage 1 over the cell-batched dispatch engine:
    one cell-range shard per device, routed on device against its own
    clip-restricted CSR offsets.

    codes (N, M) the cell-grouped buffer; shards: per device
    ``(row_lo, row_hi, routing, ids, rowbias, qkeep, cellterm)`` — the
    shard's buffer row range, its ``repro.index.dispatch.Routing`` from
    ``build_shard_dispatch`` (common shape buckets across shards), and
    the shard-local bias streams from ``IVFIndex._dispatch_streams``
    (ids (n_s,) row -> GLOBAL id; rowbias None | (n_s,) with (N,)
    filters folded to +inf; qkeep None | (Q, n_s); cellterm (E+1, cap)).

    Every shard's buffer slice / id / bias streams pad to a common row
    count so one SPMD program serves the ragged shards; pad rows sit
    beyond every owned cell's ``[lo, hi)`` window and can never surface.
    Each device combines its own partial pools before the all-gather, so
    the collective ships (Q, L) tuples — same shape as the gathered
    face — and the host merge is the same exact lexicographic
    (score, global id) ``merge_topl``.

    Returns (scores, global ids), each (Q, min(topl, pool width)).
    """
    from repro.index.candidates import merge_topl

    devices = list(devices if devices is not None else jax.devices())
    d = len(devices)
    if len(shards) != d:
        raise ValueError(f"{len(shards)} shard specs for {d} devices")
    q = luts.shape[0]
    rmax = max(max(hi - lo for lo, hi, *_ in shards), 1)
    has_qkeep = any(s[5] is not None for s in shards)

    codes_sh, ids_sh, rb_sh, qk_sh, ct_sh = [], [], [], [], []
    plan_sh = {f: [] for f in ("qidx", "tile_e", "tile_block",
                               "tile_first", "tile_lo", "tile_hi")}
    ce_sh, cs_sh = [], []
    for row_lo, row_hi, routing, ids, rowbias, qkeep, cellterm in shards:
        n_s = row_hi - row_lo
        pad = rmax - n_s
        codes_sh.append(jnp.pad(codes[row_lo:row_hi],
                                ((0, pad), (0, 0))))
        ids_sh.append(jnp.pad(ids, (0, pad), constant_values=_IMAX))
        rb = rowbias if rowbias is not None \
            else jnp.zeros((n_s,), jnp.float32)
        rb_sh.append(jnp.pad(rb.astype(jnp.float32), (0, pad)))
        if has_qkeep:
            qk = qkeep if qkeep is not None \
                else jnp.ones((q, n_s), jnp.float32)
            qk_sh.append(jnp.pad(qk.astype(jnp.float32),
                                 ((0, 0), (0, pad))))
        for field in plan_sh:
            plan_sh[field].append(getattr(routing.plan, field))
        ce_sh.append(routing.comb_e)
        cs_sh.append(routing.comb_slot)
        ct_sh.append(cellterm)

    mesh = jax.sharding.Mesh(np.asarray(devices), ("shard",))
    topl_local = min(topl, rmax)
    fn = _device_dispatch_fn(mesh, topl_local, impl, has_qkeep)
    args = [jnp.stack(codes_sh), jnp.stack(ids_sh), jnp.stack(rb_sh)]
    args += [jnp.stack(plan_sh[f]) for f in ("qidx", "tile_e", "tile_block",
                                             "tile_first", "tile_lo",
                                             "tile_hi")]
    args += [jnp.stack(ce_sh), jnp.stack(cs_sh), jnp.stack(ct_sh),
             luts.astype(jnp.float32)]
    if has_qkeep:
        args.append(jnp.stack(qk_sh))
    s_all, i_all = fn(*args)

    l = s_all.shape[-1]
    pool_s = jnp.swapaxes(s_all, 0, 1).reshape(q, d * l)
    pool_i = jnp.swapaxes(i_all, 0, 1).reshape(q, d * l)
    return merge_topl(pool_s, pool_i, topl)
