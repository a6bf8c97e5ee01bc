"""Pallas TPU kernel for the compressed-domain ADC scan (paper Eq. 8).

TPU adaptation (see DESIGN.md §3): the CPU algorithm is M scalar table
lookups + adds per database point. Gathers run on the TPU VPU at a fraction
of peak, so the kernel re-expresses the lookup as a one-hot contraction that
runs on the MXU:

    scores_block = sum_m onehot(codes[:, m]) @ lut[m]        # (Bn,K) @ (K,)

The LUT (M*K floats, 16 KB at M=16/K=256) stays resident in VMEM for the
whole scan while uint8 code blocks stream HBM->VMEM; the Pallas grid gives
automatic double-buffering of the code stream, so the scan is purely
HBM-bandwidth-bound — the roofline optimum for this operation (the LUT
gather version is VPU-issue-bound instead).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_N = 1024
DEFAULT_BLOCK_Q = 128


def _adc_scan_kernel(codes_ref, lut_ref, out_ref, *, block_n: int, num_books: int,
                     book_size: int):
    codes = codes_ref[...].astype(jnp.int32)          # (Bn, M)
    lut = lut_ref[...]                                 # (M, K)
    acc = jnp.zeros((block_n,), jnp.float32)
    # K-dim iota, 2D as required on TPU.
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, book_size), 1)  # (1, K)
    for m in range(num_books):                         # M is static (8 or 16)
        onehot = (codes[:, m:m + 1] == iota_k).astype(jnp.float32)   # (Bn, K)
        # (Bn, K) @ (K,) matvec on the MXU.
        acc = acc + jax.lax.dot_general(
            onehot, lut[m].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def adc_scan_pallas(codes: jax.Array, lut: jax.Array, *,
                    block_n: int = DEFAULT_BLOCK_N,
                    interpret: bool = False) -> jax.Array:
    """scores[n] = sum_m lut[m, codes[n, m]] via a Pallas TPU kernel.

    codes: (N, M) uint8/int32 with N % block_n == 0 (ops.py pads).
    lut:   (M, K) float32.
    Returns (N,) float32.
    """
    n, num_books = codes.shape
    _, book_size = lut.shape
    assert n % block_n == 0, f"N={n} must be padded to a multiple of {block_n}"
    grid = (n // block_n,)
    kernel = functools.partial(
        _adc_scan_kernel, block_n=block_n, num_books=num_books,
        book_size=book_size)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, num_books), lambda i: (i, 0)),
            pl.BlockSpec((num_books, book_size), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
    )(codes, lut)


def _adc_scan_batch_kernel(codes_ref, luts_ref, out_ref, *, block_n: int,
                           block_q: int, num_books: int, book_size: int):
    codes = codes_ref[...].astype(jnp.int32)          # (Bn, M)
    luts = luts_ref[...]                               # (Bq, M, K)
    acc = jnp.zeros((block_q, block_n), jnp.float32)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, book_size), 1)  # (1, K)
    for m in range(num_books):                         # M is static (8 or 16)
        onehot = (codes[:, m:m + 1] == iota_k).astype(jnp.float32)   # (Bn, K)
        # (Bq, K) x (Bn, K) -> (Bq, Bn) on the MXU: every query's LUT row
        # contracts against the SAME one-hot block, so the uint8 code
        # stream is read from HBM once for all Bq queries.
        acc = acc + jax.lax.dot_general(
            luts[:, m, :].astype(jnp.float32), onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_q", "interpret"))
def adc_scan_batch_pallas(codes: jax.Array, luts: jax.Array, *,
                          block_n: int = DEFAULT_BLOCK_N,
                          block_q: int = DEFAULT_BLOCK_Q,
                          interpret: bool = False) -> jax.Array:
    """scores[q, n] = sum_m luts[q, m, codes[n, m]] via one fused TPU kernel.

    The multi-query formulation of the ADC scan: the grid streams each code
    block HBM->VMEM once and contracts it against ALL Q lookup tables
    (grid order is n-outer / q-inner, and the code block index only depends
    on n, so Pallas keeps the block resident across the q sweep). Compared
    with vmapping the single-query kernel this amortizes the HBM code
    stream Q-fold — the scan stays bandwidth-bound at the roofline of ONE
    pass over the compressed database instead of Q passes.

    codes: (N, M) uint8/int32 with N % block_n == 0 (ops.py pads).
    luts:  (Q, M, K) float32 with Q % block_q == 0 (ops.py pads).
    Returns (Q, N) float32.
    """
    n, num_books = codes.shape
    q, _, book_size = luts.shape
    assert n % block_n == 0, f"N={n} must be padded to a multiple of {block_n}"
    assert q % block_q == 0, f"Q={q} must be padded to a multiple of {block_q}"
    grid = (n // block_n, q // block_q)
    kernel = functools.partial(
        _adc_scan_batch_kernel, block_n=block_n, block_q=block_q,
        num_books=num_books, book_size=book_size)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, num_books), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, num_books, book_size),
                         lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.float32),
        interpret=interpret,
    )(codes, luts)
