"""Fused streaming scan + top-L Pallas TPU kernel (the stage-1 engine).

The classic stage 1 materializes the full (Q, N) score matrix and runs
``jax.lax.top_k`` over it. At the billion-vector scale the paper targets
that matrix must never exist: this kernel keeps a running (block_q, L)
top-L heap resident in VMEM while uint8 code blocks stream HBM->VMEM, so
peak memory for stage 1 drops from O(Q*N) to O(Q*L).

Memory model per grid step (grid = (Q/block_q, N/block_n), n innermost):

  * the (block_q, H) score/index heap (H = ``merge.heap_width(L)``, a
    lane-aligned power of two >= L) lives in the OUTPUT blocks, whose
    index map ignores the n axis — Pallas keeps them in VMEM across the
    whole n sweep and writes them back to HBM once per query block;
  * the (block_n, M) uint8 code block and (block_n,) bias block stream in
    (double-buffered by the grid), are scored with the same one-hot MXU
    contraction as ``adc_scan_batch``, and are merged into the heap;
  * rows past ``n_valid`` (the pad the wrapper added to reach a block_n
    multiple) are masked to +inf score so they can never surface.

Tie semantics are EXACTLY those of ``lax.top_k`` over the full matrix:
candidates are ordered by (score asc, global index asc). The merge is the
shared pre-top-L of ``kernels/merge.py`` — block-local sort under
the total lexicographic order, then one merge pass with the sorted
heap — so the streaming result is bit-identical to the materialized oracle
(``ref.adc_scan_topl_ref``), not merely set-equal. The same argument makes
the chunked ``lax.scan`` fallback below exact: within the concatenated
[heap | chunk] array, positions are always in ascending-global-index order
among equal scores, and ``lax.top_k`` breaks ties by position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import merge, ref

DEFAULT_TOPL_BLOCK_N = 1024
DEFAULT_TOPL_BLOCK_Q = 8
DEFAULT_CHUNK_N = 4096

_IMAX = jnp.iinfo(jnp.int32).max


def _adc_scan_topl_kernel(codes_ref, luts_ref, bias_ref, *refs,
                          block_n: int, block_q: int,
                          num_books: int, book_size: int, n_valid: int,
                          has_qbias: bool, has_scale: bool):
    refs = list(refs)
    qbias_ref = refs.pop(0) if has_qbias else None
    scale_ref = refs.pop(0) if has_scale else None
    scores_ref, idx_ref = refs
    ni = pl.program_id(1)
    heap_w = scores_ref.shape[-1]

    @pl.when(ni == 0)
    def _init():                      # fresh heap at the start of each n sweep
        scores_ref[...] = jnp.full((block_q, heap_w), jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full((block_q, heap_w), _IMAX, jnp.int32)

    # --- score the streamed block: same one-hot MXU contraction as
    # adc_scan_batch (bit-identical scores, so ties resolve identically).
    # Quantized tables ride the same contraction: the one-hot dot copies
    # the f32-cast entry exactly (one nonzero per column), and the int8
    # per-(query, book) scale multiplies each per-m part BEFORE the
    # chain — the op order of ``ref.adc_scan_batch_q_ref`` ---
    codes = codes_ref[...].astype(jnp.int32)           # (Bn, M)
    luts = luts_ref[...]                               # (Bq, M, K)
    scale = scale_ref[...] if has_scale else None      # (Bq, M)
    acc = jnp.zeros((block_q, block_n), jnp.float32)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, book_size), 1)
    for m in range(num_books):                         # M is static (8 or 16)
        onehot = (codes[:, m:m + 1] == iota_k).astype(jnp.float32)
        part = jax.lax.dot_general(
            luts[:, m, :].astype(jnp.float32), onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        if has_scale:
            part = part * scale[:, m][:, None]
        acc = acc + part
    acc = acc + bias_ref[...][None, :]
    if has_qbias:
        # the per-query bias stream: lowered filter masks (0 = keep,
        # +inf = drop) and any other per-(query, point) additive term
        acc = acc + qbias_ref[...]

    # global ids of this block; pad rows (>= n_valid) masked to +inf score
    gids = ni * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1)                    # (1, Bn)
    acc = jnp.where(gids < n_valid, acc, jnp.inf)
    gids = jnp.broadcast_to(gids, (block_q, block_n))

    # --- merge block into the running heap: block-local sort pre-top-L
    # then one merge pass with the sorted heap (kernels/merge.py) —
    # compare/where ops only, bit-identical to the lexicographic
    # (score asc, global id asc) select it replaced ---
    out_s, out_g = merge.merge_block_topl(
        scores_ref[...], idx_ref[...], acc, gids, heap_w)
    scores_ref[...] = out_s
    idx_ref[...] = out_g


@functools.partial(jax.jit, static_argnames=("topl", "n_valid", "block_n",
                                             "block_q", "interpret"))
def adc_scan_topl_pallas(codes: jax.Array, luts: jax.Array, bias: jax.Array,
                         qbias: jax.Array | None = None,
                         scale: jax.Array | None = None, *, topl: int,
                         n_valid: int,
                         block_n: int = DEFAULT_TOPL_BLOCK_N,
                         block_q: int = DEFAULT_TOPL_BLOCK_Q,
                         interpret: bool = False):
    """Streaming stage 1: per-query top-L without a (Q, N) score matrix.

    codes: (N, M) uint8/int32, N % block_n == 0 (ops.py pads; rows at or
           past ``n_valid`` are the pad and are masked out).
    luts:  (Q, M, K) float32, Q % block_q == 0 (ops.py pads) — or the
           float16/int8 quantized tables of ``lut_quant`` for the
           reduced-precision pool scan.
    bias:  (N,) float32 per-point additive score term (zeros when unused).
    qbias: optional (Q, N) float32 per-(query, point) additive stream —
           the lowering target of the filtered-search API (+inf drops a
           point for one query). Streamed in (block_q, block_n) tiles, so
           the filter rides the fused path with no extra peak memory.
    scale: optional (Q, M) float32 per-(query, book) affine scales —
           REQUIRED with int8 ``luts``, None otherwise.
    Returns (scores, indices): ((Q, topl) f32, (Q, topl) i32), sorted by
    (score asc, index asc) — bit-identical to ``lax.top_k`` over the full
    score matrix (``ref.adc_scan_topl_ref`` for f32 tables,
    ``ref.adc_scan_topl_q_ref`` for quantized ones).
    """
    n, num_books = codes.shape
    q, _, book_size = luts.shape
    assert n % block_n == 0, f"N={n} must be padded to a multiple of {block_n}"
    assert q % block_q == 0, f"Q={q} must be padded to a multiple of {block_q}"
    assert 0 < topl <= n_valid <= n, (topl, n_valid, n)
    grid = (q // block_q, n // block_n)
    kernel = functools.partial(
        _adc_scan_topl_kernel, block_n=block_n, block_q=block_q,
        num_books=num_books, book_size=book_size, n_valid=n_valid,
        has_qbias=qbias is not None, has_scale=scale is not None)
    in_specs = [
        pl.BlockSpec((block_n, num_books), lambda qi, ni: (ni, 0)),
        pl.BlockSpec((block_q, num_books, book_size),
                     lambda qi, ni: (qi, 0, 0)),
        pl.BlockSpec((block_n,), lambda qi, ni: (ni,)),
    ]
    operands = [codes, luts, bias]
    if qbias is not None:
        in_specs.append(pl.BlockSpec((block_q, block_n),
                                     lambda qi, ni: (qi, ni)))
        operands.append(qbias)
    if scale is not None:
        in_specs.append(pl.BlockSpec((block_q, num_books),
                                     lambda qi, ni: (qi, 0)))
        operands.append(scale)
    heap_w = merge.heap_width(topl)
    scores, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_q, heap_w), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, heap_w), lambda qi, ni: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, heap_w), jnp.float32),
            jax.ShapeDtypeStruct((q, heap_w), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return scores[:, :topl], idx[:, :topl]


@functools.partial(jax.jit, static_argnames=("topl", "n_valid", "chunk_n"))
def adc_scan_topl_stream_xla(codes: jax.Array, luts: jax.Array,
                             bias: jax.Array,
                             qbias: jax.Array | None = None,
                             scale: jax.Array | None = None, *, topl: int,
                             n_valid: int, chunk_n: int = DEFAULT_CHUNK_N):
    """XLA fallback with the SAME streaming semantics as the Pallas kernel:
    a ``lax.scan`` over (Q, chunk_n) code chunks carrying the (Q, L) heap,
    merged with an incremental ``lax.top_k``. Peak live memory is
    O(Q * (L + chunk_n)) — the (Q, N) matrix is never built (asserted by
    the HLO peak-memory test).

    ``qbias`` is the optional (Q, N) per-(query, point) bias stream (the
    lowered filter mask), consumed in (Q, chunk_n) slices alongside the
    code chunks. Quantized (f16/i8) ``luts`` ride the same scan after a
    one-time up-front dequantization of the (Q, M, K) tables to f32
    (``scale`` is the int8 per-(query, book) scale): per-chunk scoring is
    then EXACTLY the f32 path's, so the fallback pays zero per-row
    quantization cost — CPU XLA's reduced-dtype gather+convert lowering
    is ~2x slower than the f32 gather, and the tables are a few hundred
    KB while the codes stream is the real traffic. Bit-exactness vs
    ``ref.adc_scan_batch_q_ref`` is preserved: f32(f16)[idx] ==
    f32(f16[idx]) (widening is exact), and pre-multiplying the int8
    table entry by its scale is the same IEEE multiply as scaling the
    gathered part. The Pallas kernel, by contrast, keeps the tiles in
    the reduced dtype inside VMEM — there the 2-4x tile shrink is the
    point (see ``_adc_scan_topl_q`` variants).

    Exactness: the carry is sorted by (score, index) and every chunk entry
    has a larger global index than every carried entry, so ``lax.top_k``'s
    positional tie-break IS the ascending-global-index tie-break — the
    result is bit-identical to the materialized oracle.
    """
    n, m = codes.shape
    q = luts.shape[0]
    if luts.dtype != jnp.float32:      # dequantize ONCE, outside the scan
        luts = luts.astype(jnp.float32)
        if scale is not None:
            luts = luts * scale[:, :, None]
    pad = (-n) % chunk_n
    codes_c = jnp.pad(codes, ((0, pad), (0, 0))).reshape(-1, chunk_n, m)
    bias_c = jnp.pad(bias, (0, pad)).reshape(-1, chunk_n)
    starts = (jnp.arange(codes_c.shape[0]) * chunk_n).astype(jnp.int32)
    qbias_c = None if qbias is None else jnp.moveaxis(
        jnp.pad(qbias, ((0, 0), (0, pad))).reshape(q, -1, chunk_n), 1, 0)

    def step(carry, inp):
        vals, idx = carry                       # (Q, L), (Q, L)
        chunk, bias_i, start, qbias_i = inp
        s = ref.adc_scan_batch_ref(chunk, luts) + bias_i[None, :]
        if qbias_i is not None:
            s = s + qbias_i
        gids = start + jnp.arange(chunk_n, dtype=jnp.int32)
        s = jnp.where(gids[None, :] < n_valid, s, jnp.inf)
        cand_s = jnp.concatenate([vals, s], axis=1)
        cand_g = jnp.concatenate(
            [idx, jnp.broadcast_to(gids[None, :], (q, chunk_n))], axis=1)
        neg, pos = jax.lax.top_k(-cand_s, topl)
        return (-neg, jnp.take_along_axis(cand_g, pos, axis=1)), None

    init = (jnp.full((q, topl), jnp.inf, jnp.float32),
            jnp.full((q, topl), _IMAX, jnp.int32))
    (vals, idx), _ = jax.lax.scan(step, init,
                                  (codes_c, bias_c, starts, qbias_c))
    return vals, idx
