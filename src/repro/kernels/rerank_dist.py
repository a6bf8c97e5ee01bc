"""Fused gather-decode-distance Pallas TPU kernel (the stage-2 engine).

The classic stage 2 gathers each query's candidate codes, decodes them to
full-dimensional reconstructions and reduces — materializing a (Q, L, D)
float tensor (~200 MB at Q=1024, L=500, D=96) that exists only to be
summed over D immediately. For table-decodable quantizers (PQ / OPQ /
RVQ: ``recon = sum_m table[m, code_m]``) this kernel streams (block_q,
block_l, M) uint8 candidate-code tiles HBM->VMEM, gathers sub-codewords
from the VMEM-resident (M, K, D) decode table via the same one-hot MXU
contraction the stage-1 scan uses, and reduces ``||q - recon||^2``
per (query, candidate) in place — the only reconstruction that ever
exists is the (block_q, block_l, D) VMEM tile.

Memory model per grid step (grid = (Q/block_q, L/block_l)):

  * the (M, K, D) decode table is replicated to every step and stays
    VMEM-resident (e.g. 8x256x96 f32 = 786 KB);
  * the (block_q, block_l, M) uint8 code tile and the (block_q, D) query
    block stream in (double-buffered by the grid);
  * output is the dense (block_q, block_l) distance tile — no top-k in
    the kernel, so no masking is needed: the wrapper slices padding off.

Exactness: the one-hot contraction sums exactly one non-zero term per
(candidate, m), so each partial equals the gathered table row bit-for-bit,
the per-m accumulation is the same left-to-right chain as
``ref.decode_with_table``, and the D reduction follows
``ref.fold_halves`` — the kernel, the chunked ``lax.scan`` fallback
below, and the materialized oracle (``ref.rerank_gather_dist_ref``) are
bit-identical, not merely allclose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

DEFAULT_RERANK_BLOCK_L = 128
DEFAULT_RERANK_BLOCK_Q = 8
# 64 beat 128/256/512 on CPU at Q=32, L=500, D=96 (BENCH_stage2.json);
# re-tune on real TPU hardware alongside the stage-1 blocks
DEFAULT_RERANK_CHUNK_L = 64


def _fold_rows(x: jax.Array) -> jax.Array:
    """``ref.fold_halves`` over the rows of a (Dp, Bl) tile, Dp a multiple
    of 8 -> (1, Bl). Halvings down to one 8-row tile slice at multiples of
    8; the last three pair rows i and i + h of the tile with a sublane
    roll (row 0 ends up holding the sum)."""
    w = x.shape[0]
    while w > 8:
        h = 1 << (w - 1).bit_length() - 1
        lo = x[:w - h] + x[h:w]
        x = lo if w - h == h else jnp.concatenate([lo, x[w - h:h]], axis=0)
        w = h
    for h in (4, 2, 1):
        x = x + pltpu.roll(x, 8 - h, 0)           # row i += row i + h
    return x[0:1]


def _rerank_gather_dist_kernel(codes_ref, queries_ref, table_ref, out_ref,
                               *, block_q: int, num_books: int,
                               book_size: int):
    # codes_ref (Bq, M, Bl) int32, queries_ref (Bq, Dp, 1), table_ref
    # (M, Dp, K): candidates run along lanes, D along sublanes, so the
    # decode is a plain (Dp, K) x (K, Bl) contraction and the D reduction
    # slices rows, never lanes.
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (book_size, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    out = jnp.zeros(out_ref.shape, jnp.float32)
    for b in range(block_q):                           # Bq is static (8)
        # --- decode: per-m one-hot MXU contraction against the resident
        # table. Exactly one non-zero per (k, l) column, so each partial
        # is the gathered table column and the chained adds reproduce
        # ref.decode_with_table exactly. ---
        recon = None
        for m in range(num_books):                     # M is static (<= 17)
            onehot = (codes_ref[b, m:m + 1, :] == iota_k).astype(jnp.float32)
            part = jax.lax.dot_general(
                table_ref[m], onehot,
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)    # (Dp, Bl)
            recon = part if recon is None else recon + part
        # --- distance: the (Dp, Bl) recon tile is the only reconstruction
        # that ever exists; reduced in ref.fold_halves order ---
        dist = _fold_rows(ref.squares(recon - queries_ref[b]))  # (1, Bl)
        out = jnp.where(row == b, dist, out)
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("block_l", "block_q",
                                             "interpret"))
def rerank_gather_dist_pallas(cand_codes: jax.Array, queries: jax.Array,
                              table: jax.Array, *,
                              block_l: int = DEFAULT_RERANK_BLOCK_L,
                              block_q: int = DEFAULT_RERANK_BLOCK_Q,
                              interpret: bool = False) -> jax.Array:
    """Fused stage 2: d1 distances without a (Q, L, D) reconstruction.

    cand_codes: (Q, L, M) uint8/int32, Q % block_q == 0 and
                L % block_l == 0 (ops.py pads; pad rows/cols produce
                garbage distances the wrapper slices off).
    queries:    (Q, D) float32.
    table:      (M, K, D) float32 additive decode table
                (``ref.decode_with_table`` semantics).
    Returns d1 (Q, L) float32, bit-identical to
    ``ref.rerank_gather_dist_ref``.

    The operands enter the kernel transposed (codes (Q, M, L), table
    (M, Dp, K), queries (Q, Dp, 1)) with D zero-padded to Dp, a multiple
    of 8; the zero rows add exact zeros to the fold.
    """
    q, l, num_books = cand_codes.shape
    _, book_size, dim = table.shape
    assert q % block_q == 0, f"Q={q} must be padded to a multiple of {block_q}"
    assert l % block_l == 0, f"L={l} must be padded to a multiple of {block_l}"
    dim_p = max(8, -(-dim // 8) * 8)
    codes_t = jnp.swapaxes(cand_codes.astype(jnp.int32), 1, 2)
    table_t = jnp.pad(jnp.swapaxes(table, 1, 2),
                      ((0, 0), (0, dim_p - dim), (0, 0)))
    queries_t = jnp.pad(queries, ((0, 0), (0, dim_p - dim)))[:, :, None]
    grid = (q // block_q, l // block_l)
    kernel = functools.partial(
        _rerank_gather_dist_kernel, block_q=block_q, num_books=num_books,
        book_size=book_size)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, num_books, block_l),
                         lambda qi, li: (qi, 0, li)),
            pl.BlockSpec((block_q, dim_p, 1), lambda qi, li: (qi, 0, 0)),
            pl.BlockSpec((num_books, dim_p, book_size),
                         lambda qi, li: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_l), lambda qi, li: (qi, li)),
        out_shape=jax.ShapeDtypeStruct((q, l), jnp.float32),
        interpret=interpret,
    )(codes_t, queries_t, table_t)


@functools.partial(jax.jit, static_argnames=("chunk_l",))
def rerank_gather_dist_chunked_xla(cand_codes: jax.Array, queries: jax.Array,
                                   table: jax.Array, *,
                                   chunk_l: int = DEFAULT_RERANK_CHUNK_L
                                   ) -> jax.Array:
    """XLA fallback with the SAME streaming semantics as the Pallas
    kernel: a ``lax.scan`` over (Q, chunk_l) candidate-code chunks, each
    decoded and reduced before the next chunk's reconstruction exists.
    Peak live reconstruction is O(Q * chunk_l * D) — the (Q, L, D) tensor
    is never built (asserted by the HLO test in tests/test_rerank.py).

    Exactness: distances are independent per (query, candidate) and
    reduced in ``ref.fold_halves`` order — the chunk split changes no
    reduction order inside any element — so the result is bit-identical
    to the materialized oracle.
    """
    q, l, m = cand_codes.shape
    pad = (-l) % chunk_l
    cc = jnp.pad(cand_codes, ((0, 0), (0, pad), (0, 0)))
    cc = jnp.moveaxis(cc.reshape(q, -1, chunk_l, m), 1, 0)  # (nc, Q, c, M)

    def step(_, chunk):
        recon = ref.decode_with_table(chunk, table)         # (Q, c, D)
        return None, ref.sq_dist(recon, queries[:, None, :])

    _, ds = jax.lax.scan(step, None, cc)                    # (nc, Q, c)
    return jnp.moveaxis(ds, 0, 1).reshape(q, -1)[:, :l]
