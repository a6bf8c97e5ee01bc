"""Pallas TPU kernel for fused UNQ codeword assignment (paper Eq. 4).

Computes, for a block of encoder heads, the argmax over codewords of the
dot-product score — fusing the (B, M, d_c) x (M, K, d_c) contraction with the
argmax so the (B, K) score matrix never leaves VMEM. The codebooks
(M*K*d_c floats; 2 MB at M=8, K=256, d_c=256) are VMEM-resident across the
whole batch; head blocks stream in through the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_B = 256


def _unq_encode_kernel(heads_ref, books_ref, out_ref, *, num_books: int):
    heads = heads_ref[...]                        # (Bb, M, d_c)
    books = books_ref[...]                        # (M, K, d_c)
    cols = []
    for m in range(num_books):                    # static M
        # (Bb, d_c) @ (d_c, K) on the MXU; argmax fused in-register.
        scores = jax.lax.dot_general(
            heads[:, m, :], books[m],
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)   # (Bb, K)
        cols.append(jnp.argmax(scores, axis=-1).astype(jnp.int32))
    out_ref[...] = jnp.stack(cols, axis=1)        # (Bb, M)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def unq_encode_pallas(heads: jax.Array, codebooks: jax.Array, *,
                      block_b: int = DEFAULT_BLOCK_B,
                      interpret: bool = False) -> jax.Array:
    """codes[b, m] = argmax_k <heads[b, m], codebooks[m, k]>.

    heads: (B, M, d_c) with B % block_b == 0 (ops.py pads); codebooks
    (M, K, d_c). Returns (B, M) int32.
    """
    b, num_books, d_c = heads.shape
    _, book_size, _ = codebooks.shape
    assert b % block_b == 0, f"B={b} must be padded to a multiple of {block_b}"
    grid = (b // block_b,)
    kernel = functools.partial(_unq_encode_kernel, num_books=num_books)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, num_books, d_c), lambda i: (i, 0, 0)),
            pl.BlockSpec((num_books, book_size, d_c), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, num_books), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, num_books), jnp.int32),
        interpret=interpret,
    )(heads, codebooks)
