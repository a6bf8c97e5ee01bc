"""Shared in-kernel top-L merge: Batcher odd-even sort/merge networks.

The three streaming kernels (``topl_scan`` / ``gather_topl`` /
``dispatch_topl``) each carry a VMEM-resident (rows, H) heap of
(score, gid) pairs ordered by (score asc, gid asc) and must fold every
streamed candidate block into it. Per block:

  1. ``sort_pairs`` — Batcher's odd-even merge sort over the candidate
     block (O(block * log^2 block) compare-exchanges);
  2. keep the block's first H columns (its exact top-H);
  3. ``merge_sorted_pairs`` — the last odd-even merge pass (log2(2H)
     stages) over the concatenation [heap | block prefix].

Every compare-exchange stage is built from operations Mosaic lowers on
the last (lane) axis: ``pltpu.roll`` by a static shift fetches each
element's partner, 2-D ``broadcasted_iota`` lane masks say which
elements pair and which side keeps the minimum, and ``where`` selects.
There are no lane reshapes, reversals, gathers, sorts or rank-1 iotas,
so the same code runs in compiled kernels, in interpret mode and on the
host (``pltpu.roll`` lowers to ``jnp.roll`` off-TPU; it has no eager
rule, so the public helpers are jitted). Widths are padded to a power
of two with canonical pad pairs; a kernel keeps its heap ``heap_width``
wide and its blocks whole 128-lane vregs wide, so its body pads and
slices only on vreg boundaries.

Exactness: the dual-key compare ``(s1, g1) <= (s2, g2)`` is a total
order over all real candidates (gids are distinct within a block and
against the heap), and pad entries are the identical-bit canonical pair
(+inf, INT32_MAX), so a sorting network's output is unique —
bit-identical to ``lax.top_k`` over the full score matrix (whose
positional tie-break is the ascending-gid tie-break). The heap stays
sorted ascending across grid steps: it initializes to all pads
(trivially sorted) and every merge emits a sorted prefix.

``tests/test_merge.py`` proves these helpers against a lexsort oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

_IMAX = jnp.iinfo(jnp.int32).max


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


def heap_width(topl: int) -> int:
    """Kernel heap width for a top-``topl`` request: a power of two and a
    whole number of 128-lane vregs, so every slice, concatenation and
    roll in the kernel body is lane-aligned. The heap holds the exact
    top-H (H >= topl), whose first ``topl`` columns are the answer."""
    return max(128, _next_pow2(topl))


def _lex_le(s1, g1, s2, g2):
    """(s1, g1) <= (s2, g2) under (score asc, gid asc) — the tie order of
    ``lax.top_k`` over ascending global ids."""
    return (s1 < s2) | ((s1 == s2) & (g1 <= g2))


def _pad_pairs(s, g, width: int):
    """Right-pad the last axis to ``width`` with the canonical pad pair."""
    extra = width - s.shape[-1]
    if extra <= 0:
        return s, g
    shape = s.shape[:-1] + (extra,)
    return (jnp.concatenate([s, jnp.full(shape, jnp.inf, s.dtype)], -1),
            jnp.concatenate([g, jnp.full(shape, _IMAX, g.dtype)], -1))


def _roll(x, shift: int):
    """out[..., i] = x[..., (i - shift) mod w] (``jnp.roll`` semantics)."""
    shift %= x.shape[-1]
    return x if shift == 0 else pltpu.roll(x, shift, x.ndim - 1)


def _stage(s, g, k: int, p: int):
    """One stage of Batcher's odd-even merge of sorted runs of length
    ``p`` into runs of ``2p``: element i compares with i + k. For k == p
    every element of a run pairs with its twin in the next run; for
    k < p the lower element i has bit k set, the upper (i + k) has it
    clear, and both stay inside one 2p run (ends of a run sit out)."""
    w = s.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    if k == p:
        lower = (lane & k) == 0
        active = None
    else:
        r = lane & (2 * p - 1)
        lower = ((lane & k) != 0) & (r < 2 * p - k)
        active = lower | (((lane & k) == 0) & (r >= 2 * k))
    ps = jnp.where(lower, _roll(s, w - k), _roll(s, k))
    pg = jnp.where(lower, _roll(g, w - k), _roll(g, k))
    # the lower element keeps the minimum, the upper the maximum; an
    # identical pair (two pads) swaps to identical bits
    take_self = _lex_le(s, g, ps, pg) == lower
    if active is not None:
        take_self = take_self | jnp.logical_not(active)
    return jnp.where(take_self, s, ps), jnp.where(take_self, g, pg)


def _merge_pass(s, g, p: int):
    """Merge adjacent sorted runs of length ``p`` into sorted runs of 2p."""
    k = p
    while k >= 1:
        s, g = _stage(s, g, k, p)
        k //= 2
    return s, g


@jax.jit
def sort_pairs(s, g):
    """Sort (score, gid) pairs ascending by (score, gid) along the last
    axis. Any width (padded internally to a power of two); any leading
    batch dims. Returns arrays of the input width."""
    w = s.shape[-1]
    wp = _next_pow2(w)
    s, g = _pad_pairs(s, g, wp)
    p = 1
    while p < wp:
        s, g = _merge_pass(s, g, p)
        p *= 2
    return s[..., :w], g[..., :w]


@functools.partial(jax.jit, static_argnames=("topl",))
def merge_sorted_pairs(heap_s, heap_g, sorted_s, sorted_g, topl: int):
    """Merge two ascending-sorted (score, gid) runs into their exact
    sorted top-``topl``: both runs are padded to a common power-of-two
    width P, concatenated, and collapsed by the odd-even merge pass of
    run length P. Returns min(topl, heap_w + block_w) columns."""
    keep = min(topl, heap_s.shape[-1] + sorted_s.shape[-1])
    p = _next_pow2(max(heap_s.shape[-1], sorted_s.shape[-1]))
    heap_s, heap_g = _pad_pairs(heap_s, heap_g, p)
    sorted_s, sorted_g = _pad_pairs(sorted_s, sorted_g, p)
    s = jnp.concatenate([heap_s, sorted_s], axis=-1)
    g = jnp.concatenate([heap_g, sorted_g], axis=-1)
    s, g = _merge_pass(s, g, p)
    return s[..., :keep], g[..., :keep]


@functools.partial(jax.jit, static_argnames=("topl",))
def merge_block_topl(heap_s, heap_g, cand_s, cand_g, topl: int):
    """Fold an UNSORTED candidate block into the sorted (rows, topl) heap:
    block-local sort, keep the block's top-``topl`` prefix, one merge
    with the heap. Returns the new sorted heap — bit-identical to the
    lexicographic (score, gid) top-``topl`` of heap + block by the
    total-order argument in the module docstring."""
    cand_s, cand_g = sort_pairs(cand_s, cand_g)
    keep = min(topl, cand_s.shape[-1])
    return merge_sorted_pairs(heap_s, heap_g, cand_s[..., :keep],
                              cand_g[..., :keep], topl)
