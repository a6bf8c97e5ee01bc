"""Fused gathered scan + top-L kernels (the IVF stage-1 engine).

IVF search scores a PER-QUERY slot list — the padded ragged batch built by
concatenating the inverted lists of each query's probed cells — instead of
the whole database. The flat streaming kernel (``topl_scan.py``) shares
one (N, M) code block across all queries; here each query block carries
its OWN gathered code tile, so the one-hot scoring contraction runs once
per query row of the block and everything else — the running (block_q, L)
heap in VMEM, the lexicographic (score, global-id) merge, +inf masking of
pad slots — is inherited unchanged.

Memory model per grid step (grid = (Q/block_q, W/block_w), w innermost):

  * the (block_q, H) score/id heap (H = ``merge.heap_width(L)``) lives in
    the OUTPUT blocks, whose index map ignores the w axis — VMEM-resident
    across the whole w sweep;
  * the (block_q, block_w, M) uint8 gathered-code tile, the (block_q,
    block_w) global-id tile and the (block_q, block_w) slot-bias tile
    stream HBM->VMEM (the gather itself happens outside the kernel: the
    gathered batch is Q*W*M BYTES — the d2 score values are what must
    never materialize at (Q, N) scale);
  * slots with gid == _IMAX (the ragged pad) score +inf; slots whose bias
    carries +inf (filtered out) are canonicalized to gid _IMAX, so +inf
    entries are identical bits across every implementation;
  * the (block_q, block_w) slot-bias tile is ONE pre-composed stream
    (``ops.adc_gather_topl`` docstring): per-point biases, the residual
    IVF correction's per-(query, cell) term, and lowered filter masks are
    summed host-side in a fixed order, so the kernel adds exactly one
    value per slot and stays bit-identical to the oracle for any mix.

Tie semantics are EXACTLY those of flat search: the merge selects
lexicographic (score asc, global id asc) minima, so at nprobe == nlist
(every point listed exactly once) the result is bit-identical to
``ref.adc_scan_topl_ref`` over the same database — scores AND ids.

The chunked ``lax.scan`` fallback additionally relies on the plan
CONTRACT (gids ascending within each query row, pads last): every chunk
slot then has a gid >= every carried heap entry, so ``lax.top_k``'s
positional tie-break reproduces the ascending-gid tie-break — the same
argument that makes ``topl_scan.adc_scan_topl_stream_xla`` exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import merge

DEFAULT_GATHER_BLOCK_W = 512
DEFAULT_GATHER_BLOCK_Q = 8
DEFAULT_CHUNK_W = 2048

_IMAX = jnp.iinfo(jnp.int32).max


def _adc_gather_topl_kernel(codes_ref, gids_ref, bias_ref, luts_ref,
                            *refs, block_q: int, num_books: int,
                            book_size: int, has_scale: bool):
    refs = list(refs)
    scale_ref = refs.pop(0) if has_scale else None
    scores_ref, idx_ref = refs
    wi = pl.program_id(1)
    heap_w = scores_ref.shape[-1]

    @pl.when(wi == 0)
    def _init():                      # fresh heap at the start of each w sweep
        scores_ref[...] = jnp.full((block_q, heap_w), jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full((block_q, heap_w), _IMAX, jnp.int32)

    # --- score the gathered tile: per query row, per codebook, the one-hot
    # contraction of the flat kernel — the same per-m partial values (and
    # the same left-to-right m accumulation), so a slot's score is
    # bit-identical to the same point's flat score. Each row's chain is
    # selected into its row of the tile ---
    luts = luts_ref[...]                               # (Bq, M, K)
    scale = scale_ref[...] if has_scale else None      # (Bq, M)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, book_size), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 0)
    acc = jnp.zeros(bias_ref.shape, jnp.float32)       # (Bq, Bw)
    for b in range(block_q):                           # Bq is static (8/16)
        codes = codes_ref[b].astype(jnp.int32)         # (Bw, M)
        chain = None
        for m in range(num_books):                     # M is static (8 or 16)
            onehot = (codes[:, m:m + 1] == iota_k).astype(jnp.float32)
            part = jax.lax.dot_general(
                luts[b, m:m + 1, :].astype(jnp.float32), onehot,
                dimension_numbers=(((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)    # (1, Bw)
            if has_scale:              # int8: per-(query, book) scale on
                part = part * scale[b:b + 1, m:m + 1]  # each part BEFORE
            chain = part if chain is None else chain + part   # the chain
        acc = jnp.where(row == b, chain, acc)
    acc = acc + bias_ref[...]

    # pad slots (gid == _IMAX) score +inf; +inf slots (filtered) get the
    # canonical _IMAX gid so +inf entries are identical across paths
    gids = gids_ref[...]
    acc = jnp.where(gids == _IMAX, jnp.inf, acc)
    gids = jnp.where(acc == jnp.inf, _IMAX, gids)

    # --- merge tile into the running heap: shared pre-top-L + merge
    # (kernels/merge.py) — identical tie semantics to topl_scan ---
    out_s, out_g = merge.merge_block_topl(
        scores_ref[...], idx_ref[...], acc, gids, heap_w)
    scores_ref[...] = out_s
    idx_ref[...] = out_g


@functools.partial(jax.jit, static_argnames=("topl", "block_w", "block_q",
                                             "interpret"))
def adc_gather_topl_pallas(gathered_codes: jax.Array, gids: jax.Array,
                           rowbias: jax.Array, luts: jax.Array,
                           scale: jax.Array | None = None, *, topl: int,
                           block_w: int = DEFAULT_GATHER_BLOCK_W,
                           block_q: int = DEFAULT_GATHER_BLOCK_Q,
                           interpret: bool = False):
    """Fused gathered scan+top-L over per-query slot lists.

    gathered_codes: (Q, W, M) uint8/int32, W % block_w == 0 (ops.py pads).
    gids:           (Q, W) int32 global ids; _IMAX marks pad slots.
    rowbias:        (Q, W) float32 additive per-slot term (+inf filters).
    luts:           (Q, M, K) float32, Q % block_q == 0 (ops.py pads) —
                    or the float16/int8 quantized tables of ``lut_quant``.
    scale:          optional (Q, M) float32 int8 affine scales (None for
                    f32/f16 tables).
    Returns (scores, ids): ((Q, topl) f32, (Q, topl) i32), sorted by
    (score asc, global id asc).
    """
    q, w, num_books = gathered_codes.shape
    book_size = luts.shape[-1]
    assert w % block_w == 0, f"W={w} must be padded to a multiple of {block_w}"
    assert q % block_q == 0, f"Q={q} must be padded to a multiple of {block_q}"
    assert 0 < topl <= w, (topl, w)
    grid = (q // block_q, w // block_w)
    kernel = functools.partial(
        _adc_gather_topl_kernel, block_q=block_q, num_books=num_books,
        book_size=book_size, has_scale=scale is not None)
    in_specs = [
        pl.BlockSpec((block_q, block_w, num_books),
                     lambda qi, wi: (qi, wi, 0)),
        pl.BlockSpec((block_q, block_w), lambda qi, wi: (qi, wi)),
        pl.BlockSpec((block_q, block_w), lambda qi, wi: (qi, wi)),
        pl.BlockSpec((block_q, num_books, book_size),
                     lambda qi, wi: (qi, 0, 0)),
    ]
    operands = [gathered_codes, gids, rowbias, luts]
    if scale is not None:
        in_specs.append(pl.BlockSpec((block_q, num_books),
                                     lambda qi, wi: (qi, 0)))
        operands.append(scale)
    heap_w = merge.heap_width(topl)
    scores, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_q, heap_w), lambda qi, wi: (qi, 0)),
            pl.BlockSpec((block_q, heap_w), lambda qi, wi: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, heap_w), jnp.float32),
            jax.ShapeDtypeStruct((q, heap_w), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return scores[:, :topl], idx[:, :topl]


@functools.partial(jax.jit, static_argnames=("topl", "chunk_w"))
def adc_gather_topl_stream_xla(codes: jax.Array, rows: jax.Array,
                               gids: jax.Array, rowbias: jax.Array,
                               luts: jax.Array,
                               scale: jax.Array | None = None, *, topl: int,
                               chunk_w: int = DEFAULT_CHUNK_W):
    """XLA fallback with the same streaming semantics: a ``lax.scan`` over
    (Q, chunk_w) slot chunks carrying the (Q, L) heap. The gather happens
    per chunk (``codes[rows_chunk]``), so peak gathered memory is
    O(Q * chunk_w * M) bytes and the (Q, W) score batch never exists.

    Exactness relies on the plan contract (gids ascending per query row,
    pads last): every chunk slot's gid is >= every carried entry's, so the
    incremental ``lax.top_k`` positional tie-break IS the ascending-gid
    tie-break — bit-identical to ``ref.adc_gather_topl_ref``.
    """
    q, w = rows.shape
    num_books = codes.shape[1]
    if luts.dtype != jnp.float32:      # dequantize ONCE, outside the scan
        # bitwise-identical to gathering in the reduced dtype and
        # converting/scaling per part (f32 widening is exact; the int8
        # scale multiply is the same IEEE op either side of the gather),
        # and ~2x faster: CPU XLA's narrow gather+convert lowering loses
        # to the plain f32 gather (see topl_scan.adc_scan_topl_stream_xla)
        luts = luts.astype(jnp.float32)
        if scale is not None:
            luts = luts * scale[:, :, None]
    pad = (-w) % chunk_w
    rows_c = jnp.moveaxis(
        jnp.pad(rows, ((0, 0), (0, pad))).reshape(q, -1, chunk_w), 1, 0)
    gids_c = jnp.moveaxis(
        jnp.pad(gids, ((0, 0), (0, pad)), constant_values=_IMAX)
        .reshape(q, -1, chunk_w), 1, 0)
    bias_c = jnp.moveaxis(
        jnp.pad(rowbias, ((0, 0), (0, pad))).reshape(q, -1, chunk_w), 1, 0)

    def step(carry, inp):
        vals, idx = carry                              # (Q, L) x2
        rows_i, gids_i, bias_i = inp
        chunk = jnp.take(codes, rows_i, axis=0).astype(jnp.int32)
        picked = jnp.take_along_axis(
            luts[:, None, :, :], chunk[:, :, :, None], axis=3)[..., 0]
        s = picked[:, :, 0]
        for m in range(1, num_books):                  # adc_scan_ref chain
            s = s + picked[:, :, m]
        s = s + bias_i
        s = jnp.where(gids_i == _IMAX, jnp.inf, s)
        g = jnp.where(jnp.isposinf(s), _IMAX, gids_i)
        cand_s = jnp.concatenate([vals, s], axis=1)
        cand_g = jnp.concatenate([idx, g], axis=1)
        neg, pos = jax.lax.top_k(-cand_s, topl)
        return (-neg, jnp.take_along_axis(cand_g, pos, axis=1)), None

    init = (jnp.full((q, topl), jnp.inf, jnp.float32),
            jnp.full((q, topl), _IMAX, jnp.int32))
    (vals, idx), _ = jax.lax.scan(step, init, (rows_c, gids_c, bias_c))
    return vals, idx
