"""Cell-batched dispatch scan + top-L kernels (the MoE-routed IVF stage 1).

The gathered face (``gather_topl.py``) streams a PER-QUERY slot list: every
query re-reads the code rows of every cell it probes, and the padded (Q, W)
plan is built host-side in numpy per batch. Here the roles flip — coarse
cells are the experts, probed queries are the routed tokens
(tensor2tensor-style expert dispatch, cf. ``parallel/ep.py``): the router
(``repro.index.dispatch``) groups the (Q, nprobe) probe matrix BY CELL into
dense per-cell query batches, and each cell's contiguous code range streams
from HBM exactly once for ALL queries probing it.

Work arrives as a static-shape tile plan (``DispatchPlan``): the probed
cells' code ranges are cut into chunk-ALIGNED tiles of the cell-grouped
buffer, so a tile index IS a block index into ``codes`` — the scalar-
prefetched plan arrays drive data-dependent tile DMA without any gather.

Memory model per grid step (grid = (T,), one step per tile, tiles of one
cell consecutive):

  * the (cap, H) score/id heap (H = ``merge.heap_width(L)``) of the
    tile's cell lives in the OUTPUT blocks, whose index map follows
    ``tile_e`` — consecutive tiles of one cell map to the same block,
    so the heap stays VMEM-resident across the cell's whole code range
    and is initialized when ``tile_first`` fires;
  * the (chunk, M) uint8 code tile plus its (chunk,) global-id and
    row-bias streams flow HBM->VMEM addressed by ``tile_block`` — the
    codes are read IN PLACE from the cell-grouped buffer (no gathered
    (Q, W, M) batch exists anywhere);
  * the cell's (cap,) query batch gathers its LUT rows in-kernel via an
    exact one-hot matmul (one nonzero per row — a copy, not an
    approximation), so routed LUTs are never duplicated per cell in HBM;
  * scoring reuses the per-m one-hot contraction and the left-to-right m
    accumulation of ``adc_scan_ref``; the bias composition is
    ``chain + (row_bias + cellterm)`` then the (Q, N) keep mask — exactly
    the padded path's ``_plan_rowbias`` order, which is what keeps every
    mixed-stream score bit-identical;
  * rows outside the tile's [lo, hi) validity window, slots with
    ``qidx < 0`` and filtered rows score +inf and are canonicalized to
    gid ``_IMAX`` — identical bits to the gathered kernels' pad handling.

Tie semantics are EXACTLY those of flat search: the in-kernel merge is the
same shared (score asc, global id asc) pre-top-L merge
(``kernels/merge.py``) as ``gather_topl``, so per-cell partial top-Ls
merged across cells
(``index.dispatch.combine_pools`` -> ``candidates.merge_topl``) reproduce
the padded-plan results bit-for-bit, scores AND ids.

The chunked ``lax.scan`` fallback carries the full (E+1, cap, L) heap and
merges each tile with ``lax.top_k``; exactness relies on the buffer
contract that rows WITHIN a cell are ascending in global id (stable
cell-grouping of add order), so the positional tie-break over
[heap | tile] is the ascending-gid tie-break — the same argument as
``adc_gather_topl_stream_xla``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import merge

DEFAULT_DISPATCH_CHUNK = 128

_IMAX = jnp.iinfo(jnp.int32).max
# one-hot contractions copy table entries exactly only with full f32 passes
_EXACT = jax.lax.Precision.HIGHEST


class DispatchPlan(NamedTuple):
    """The routed work-list the dispatch kernels execute (all int32).

    ``qidx`` (E+1, cap): the query batch of each routed cell (row E is the
    dummy row pad tiles target); -1 marks empty slots. The tile arrays
    (T,) each describe one chunk-aligned tile of the cell-grouped code
    buffer: ``tile_e`` the routed-cell row it scores into (tiles of one
    cell are CONSECUTIVE — the heap-residency contract), ``tile_block``
    its block index (rows [block*chunk, block*chunk + chunk)),
    ``tile_first`` 1 on the first tile of its cell (heap init),
    ``tile_lo``/``tile_hi`` the cell's true row range (rows outside score
    +inf). Pad tiles target the dummy row with lo == hi == 0.
    """
    qidx: jax.Array
    tile_e: jax.Array
    tile_block: jax.Array
    tile_first: jax.Array
    tile_lo: jax.Array
    tile_hi: jax.Array


def _adc_dispatch_topl_kernel(tile_e_ref, tile_block_ref, tile_first_ref,
                              tile_lo_ref, tile_hi_ref, codes_ref, gid_ref,
                              rowb_ref, qidx_ref, cellterm_ref, luts_ref,
                              *rest, chunk: int, cap: int, num_q: int,
                              num_books: int, book_size: int,
                              has_qkeep: bool, has_scale: bool):
    rest = list(rest)
    qkeep_ref = rest.pop(0) if has_qkeep else None
    scale_ref = rest.pop(0) if has_scale else None
    scores_ref, idx_ref = rest
    t = pl.program_id(0)
    heap_w = scores_ref.shape[-1]

    @pl.when(tile_first_ref[t] == 1)
    def _init():                  # fresh heap at the first tile of each cell
        scores_ref[...] = jnp.full((1, cap, heap_w), jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full((1, cap, heap_w), _IMAX, jnp.int32)

    # --- gather the cell's LUT batch: exact one-hot copy (one nonzero per
    # row), so the routed (cap, M*K) tables never materialize in HBM ---
    qidx = qidx_ref[0]                                         # (cap, 1)
    iota_q = jax.lax.broadcasted_iota(jnp.int32, (cap, num_q), 1)
    onehot_q = (qidx == iota_q).astype(jnp.float32)            # (cap, Q)
    # quantized tables are f32-cast for the routing dot (an exact copy of
    # the f32-cast entries — one nonzero per row), so scoring below sees
    # exactly f32(qlut); a no-op for the default f32 tables
    lut_e = jax.lax.dot(onehot_q, luts_ref[...].astype(jnp.float32),
                        precision=_EXACT,
                        preferred_element_type=jnp.float32)    # (cap, M*K)
    scale_e = None
    if has_scale:                      # routed copy of the int8 scales
        scale_e = jax.lax.dot(onehot_q, scale_ref[...], precision=_EXACT,
                              preferred_element_type=jnp.float32)  # (cap, M)

    # --- score the code tile once for the whole query batch: per-m one-hot
    # contraction, left-to-right m accumulation (adc_scan_ref chain); int8
    # scales multiply each per-m part BEFORE the chain (q_ref's order) ---
    codes = codes_ref[...].astype(jnp.int32)                   # (chunk, M)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, book_size), 1)
    acc = None
    for m in range(num_books):                                 # M is static
        onehot_c = (codes[:, m:m + 1] == iota_k).astype(jnp.float32)
        part = jax.lax.dot_general(
            lut_e[:, m * book_size:(m + 1) * book_size], onehot_c,
            dimension_numbers=(((1,), (1,)), ((), ())), precision=_EXACT,
            preferred_element_type=jnp.float32)                # (cap, chunk)
        if has_scale:
            part = part * scale_e[:, m:m + 1]
        acc = part if acc is None else acc + part

    # bias composition order is the padded path's _plan_rowbias order:
    # (row stream + per-(query, cell) term) added as ONE slot value, the
    # (Q, N) keep mask applied after — bit-identical for any stream mix
    acc = acc + (rowb_ref[...] + cellterm_ref[0])              # (cap, chunk)
    if has_qkeep:
        keep = jax.lax.dot(onehot_q, qkeep_ref[...], precision=_EXACT,
                           preferred_element_type=jnp.float32)  # (cap, chunk)
        acc = jnp.where(keep > 0.5, acc, jnp.inf)

    # rows outside the cell's [lo, hi) window and empty batch slots score
    # +inf; +inf entries take the canonical _IMAX gid (identical bits to
    # the gathered kernels' pad handling)
    grow = tile_block_ref[t] * chunk + jax.lax.broadcasted_iota(
        jnp.int32, (1, chunk), 1)
    acc = jnp.where((grow >= tile_lo_ref[t]) & (grow < tile_hi_ref[t]),
                    acc, jnp.inf)
    acc = jnp.where(qidx >= 0, acc, jnp.inf)
    gids = jnp.broadcast_to(gid_ref[...], (cap, chunk))
    gids = jnp.where(acc == jnp.inf, _IMAX, gids)

    # --- merge the tile into the cell's running heap: shared pre-top-L +
    # merge (kernels/merge.py) — same tie semantics as gather_topl, so tie
    # resolution is identical everywhere ---
    out_s, out_g = merge.merge_block_topl(
        scores_ref[0], idx_ref[0], acc, gids, heap_w)
    scores_ref[0] = out_s
    idx_ref[0] = out_g


@functools.partial(jax.jit, static_argnames=("topl", "chunk", "interpret"))
def adc_dispatch_topl_pallas(codes: jax.Array, gids_rows: jax.Array,
                             rowbias: jax.Array, luts: jax.Array,
                             cellterm: jax.Array, plan: DispatchPlan,
                             qkeep: jax.Array | None = None,
                             scale: jax.Array | None = None, *, topl: int,
                             chunk: int = DEFAULT_DISPATCH_CHUNK,
                             interpret: bool = False):
    """Fused cell-batched scan+top-L over a routed tile plan.

    codes:     (NP, M) uint8 cell-grouped buffer, NP % chunk == 0
               (ops.py pads; tile blocks index it directly).
    gids_rows: (NP,) int32 buffer row -> global id stream.
    rowbias:   (NP,) float32 per-row additive stream (per-point bias with
               any (N,) filter already folded to +inf).
    luts:      (Q, M, K) float32 per-query tables (whole-array resident).
    cellterm:  (E+1, cap) float32 per-(routed cell, slot) additive term
               (the IVFADC per-(query, cell) residual correction).
    plan:      the DispatchPlan tile work-list (see class doc).
    qkeep:     None | (Q, NP) float32 0/1 keep stream in BUFFER-ROW column
               order (the lowered per-query filter mask).
    scale:     None | (Q, M) float32 int8 affine scales (``luts`` may be
               the float16/int8 quantized tables of ``lut_quant``).

    Returns (scores, ids): ((E+1, cap, topl) f32, (E+1, cap, topl) i32) —
    per-cell partial pools, each slot's top-L sorted by (score asc, global
    id asc). Rows never routed to carry undefined values; ``ops`` masks
    them via the all-invalid ``qidx`` row before anything reads them.
    """
    np_, num_books = codes.shape
    e1, cap = plan.qidx.shape
    num_q, _, book_size = luts.shape
    t_b = plan.tile_e.shape[0]
    assert np_ % chunk == 0, f"N={np_} must be padded to a multiple of {chunk}"
    kernel = functools.partial(
        _adc_dispatch_topl_kernel, chunk=chunk, cap=cap, num_q=num_q,
        num_books=num_books, book_size=book_size,
        has_qkeep=qkeep is not None, has_scale=scale is not None)
    # per-cell rows ride as (E+1, cap, 1) columns: a block of one cell is
    # then a whole (cap, 1) tile, and the batch broadcasts along lanes
    in_specs = [
        pl.BlockSpec((chunk, num_books),
                     lambda t, te, tb, tf, tlo, thi: (tb[t], 0)),
        pl.BlockSpec((1, chunk), lambda t, te, tb, tf, tlo, thi: (0, tb[t])),
        pl.BlockSpec((1, chunk), lambda t, te, tb, tf, tlo, thi: (0, tb[t])),
        pl.BlockSpec((1, cap, 1),
                     lambda t, te, tb, tf, tlo, thi: (te[t], 0, 0)),
        pl.BlockSpec((1, cap, 1),
                     lambda t, te, tb, tf, tlo, thi: (te[t], 0, 0)),
        pl.BlockSpec((num_q, num_books * book_size),
                     lambda t, te, tb, tf, tlo, thi: (0, 0)),
    ]
    args = [codes, gids_rows[None, :], rowbias[None, :],
            plan.qidx[:, :, None], cellterm[:, :, None],
            luts.reshape(num_q, num_books * book_size)]
    if qkeep is not None:
        in_specs.append(pl.BlockSpec(
            (num_q, chunk), lambda t, te, tb, tf, tlo, thi: (0, tb[t])))
        args.append(qkeep)
    if scale is not None:
        in_specs.append(pl.BlockSpec(
            (num_q, num_books), lambda t, te, tb, tf, tlo, thi: (0, 0)))
        args.append(scale)
    heap_w = merge.heap_width(topl)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(t_b,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, cap, heap_w),
                         lambda t, te, tb, tf, tlo, thi: (te[t], 0, 0)),
            pl.BlockSpec((1, cap, heap_w),
                         lambda t, te, tb, tf, tlo, thi: (te[t], 0, 0)),
        ],
    )
    scores, ids = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((e1, cap, heap_w), jnp.float32),
            jax.ShapeDtypeStruct((e1, cap, heap_w), jnp.int32),
        ],
        interpret=interpret,
    )(plan.tile_e, plan.tile_block, plan.tile_first, plan.tile_lo,
      plan.tile_hi, *args)
    return scores[..., :topl], ids[..., :topl]


@functools.partial(jax.jit, static_argnames=("topl", "chunk"))
def adc_dispatch_topl_stream_xla(codes: jax.Array, gids_rows: jax.Array,
                                 rowbias: jax.Array, luts: jax.Array,
                                 cellterm: jax.Array, plan: DispatchPlan,
                                 qkeep: jax.Array | None = None,
                                 scale: jax.Array | None = None, *,
                                 topl: int,
                                 chunk: int = DEFAULT_DISPATCH_CHUNK):
    """XLA fallback with the same streaming semantics: a ``lax.scan`` over
    the tile work-list carrying the full (E+1, cap, L) heap. Each step
    slices one chunk-aligned code tile in place (no gathered batch),
    scores it against the tile's cell batch, and merges that cell's heap
    slice with ``lax.top_k`` — exact because buffer rows within a cell
    ascend in global id (see module doc). Peak working set is
    O(cap * chunk) scores per step plus the output-sized heap carry.
    """
    num_books = codes.shape[1]
    e1, cap = plan.qidx.shape
    num_q = luts.shape[0]
    if luts.dtype != jnp.float32:      # dequantize ONCE, outside the scan
        # bitwise-identical and faster than the narrow gather+convert —
        # same argument as topl_scan.adc_scan_topl_stream_xla
        luts = luts.astype(jnp.float32)
        if scale is not None:
            luts = luts * scale[:, :, None]

    def step(carry, inp):
        hs, hg = carry                                     # (E+1, cap, L)
        te, tb, tlo, thi = inp
        r0 = tb * chunk
        codes_t = jax.lax.dynamic_slice(
            codes, (r0, 0), (chunk, num_books)).astype(jnp.int32)
        gid_t = jax.lax.dynamic_slice(gids_rows, (r0,), (chunk,))
        rowb_t = jax.lax.dynamic_slice(rowbias, (r0,), (chunk,))
        qe = jax.lax.dynamic_slice(plan.qidx, (te, 0), (1, cap))[0]
        ct = jax.lax.dynamic_slice(cellterm, (te, 0), (1, cap))[0]
        safe_q = jnp.clip(qe, 0, num_q - 1)
        lut_e = jnp.take(luts, safe_q, axis=0)             # (cap, M, K)
        picked = jnp.take_along_axis(
            lut_e[:, None, :, :],
            codes_t[None, :, :, None], axis=3)[..., 0]     # (cap, chunk, M)
        s = picked[:, :, 0]
        for m in range(1, num_books):                      # adc_scan_ref chain
            s = s + picked[:, :, m]
        s = s + (rowb_t[None, :] + ct[:, None])
        if qkeep is not None:
            qk = jax.lax.dynamic_slice(qkeep, (0, r0), (num_q, chunk))
            keep = jnp.take(qk, safe_q, axis=0)            # (cap, chunk)
            s = jnp.where(keep > 0.5, s, jnp.inf)
        grow = r0 + jnp.arange(chunk, dtype=jnp.int32)
        s = jnp.where((grow >= tlo) & (grow < thi), s, jnp.inf)
        s = jnp.where((qe >= 0)[:, None], s, jnp.inf)
        g = jnp.where(jnp.isposinf(s), _IMAX,
                      jnp.broadcast_to(gid_t[None, :], (cap, chunk)))
        he_s = jax.lax.dynamic_slice(hs, (te, 0, 0), (1, cap, topl))[0]
        he_g = jax.lax.dynamic_slice(hg, (te, 0, 0), (1, cap, topl))[0]
        neg, pos = jax.lax.top_k(-jnp.concatenate([he_s, s], axis=1), topl)
        ng = jnp.take_along_axis(
            jnp.concatenate([he_g, g], axis=1), pos, axis=1)
        hs = jax.lax.dynamic_update_slice(hs, (-neg)[None], (te, 0, 0))
        hg = jax.lax.dynamic_update_slice(hg, ng[None], (te, 0, 0))
        return (hs, hg), None

    init = (jnp.full((e1, cap, topl), jnp.inf, jnp.float32),
            jnp.full((e1, cap, topl), _IMAX, jnp.int32))
    (hs, hg), _ = jax.lax.scan(
        step, init, (plan.tile_e, plan.tile_block, plan.tile_lo,
                     plan.tile_hi))
    return hs, hg
