"""In-kernel merge helpers (``kernels/merge.py``) vs a lexsort
oracle: the block-local sort, the sorted-run merge, and the combined
``merge_block_topl`` fold must all be bit-identical to lexicographic
(score asc, gid asc) selection — pads, ties and non-pow2 widths
included. These are the primitives the three streaming kernels trust
for exactness, so the oracle here is deliberately independent (numpy
lexsort, no jax sorting)."""
import numpy as np
from _hypothesis_shim import given, settings, st

from repro.kernels import merge

_IMAX = np.iinfo(np.int32).max


def _oracle_sort(s, g):
    """Ascending (score, gid) lexicographic sort along the last axis —
    numpy lexsort's last key is primary."""
    s, g = np.asarray(s), np.asarray(g)
    out_s, out_g = np.empty_like(s), np.empty_like(g)
    for idx in np.ndindex(s.shape[:-1]):
        order = np.lexsort((g[idx], s[idx]))
        out_s[idx], out_g[idx] = s[idx][order], g[idx][order]
    return out_s, out_g


def _case(rng, shape, *, tie_heavy, pad_frac=0.0):
    """(scores, gids) with distinct gids per row — the kernels' invariant
    (global ids are unique) — plus optional canonical pad pairs."""
    s = (rng.integers(0, 4, size=shape).astype(np.float32) if tie_heavy
         else rng.standard_normal(shape).astype(np.float32))
    w = shape[-1]
    g = np.empty(shape, np.int32)
    for idx in np.ndindex(shape[:-1]):
        g[idx] = np.sort(rng.choice(10 * w, size=w, replace=False))
        rng.shuffle(g[idx])
    if pad_frac:
        pad = rng.random(shape) < pad_frac
        s = np.where(pad, np.inf, s)
        g = np.where(pad, _IMAX, g).astype(np.int32)
    return s, g


@settings(max_examples=25, deadline=None)
@given(
    w=st.integers(1, 97),
    rows=st.integers(1, 5),
    tie_heavy=st.sampled_from([False, True]),
    seed=st.integers(0, 2**31 - 1),
)
def test_bitonic_sort_matches_lexsort(w, rows, tie_heavy, seed):
    """Property: any width (pow2 or not), batched rows, tie-heavy scores
    and pad pairs — the sorting network's output is bitwise the lexsort
    order."""
    rng = np.random.default_rng(seed)
    s, g = _case(rng, (rows, w), tie_heavy=tie_heavy, pad_frac=0.15)
    got_s, got_g = merge.sort_pairs(s, g)
    want_s, want_g = _oracle_sort(s, g)
    np.testing.assert_array_equal(np.asarray(got_s), want_s)
    np.testing.assert_array_equal(np.asarray(got_g), want_g)


@settings(max_examples=25, deadline=None)
@given(
    heap_w=st.integers(1, 64),
    block_w=st.integers(1, 64),
    topl=st.integers(1, 64),
    seed=st.integers(0, 2**31 - 1),
)
def test_merge_sorted_pairs_matches_lexsort_prefix(heap_w, block_w, topl,
                                                   seed):
    """Merging two ascending runs == the sorted prefix of their
    concatenation (runs drawn from disjoint gid ranges, as heap and block
    are in the kernels)."""
    rng = np.random.default_rng(seed)
    hs, hg = _case(rng, (3, heap_w), tie_heavy=True, pad_frac=0.2)
    bs, bg = _case(rng, (3, block_w), tie_heavy=True, pad_frac=0.2)
    bg = np.where(bg == _IMAX, _IMAX, bg + 10 * heap_w * 10).astype(np.int32)
    hs, hg = _oracle_sort(hs, hg)
    bs, bg = _oracle_sort(bs, bg)
    got_s, got_g = merge.merge_sorted_pairs(hs, hg, bs, bg, topl)
    want_s, want_g = _oracle_sort(np.concatenate([hs, bs], -1),
                                  np.concatenate([hg, bg], -1))
    keep = min(topl, heap_w + block_w)
    np.testing.assert_array_equal(np.asarray(got_s), want_s[:, :keep])
    np.testing.assert_array_equal(np.asarray(got_g), want_g[:, :keep])


@settings(max_examples=25, deadline=None)
@given(
    topl=st.integers(1, 48),
    block_w=st.integers(1, 80),
    tie_heavy=st.sampled_from([False, True]),
    seed=st.integers(0, 2**31 - 1),
)
def test_merge_block_topl_is_exact_fold(topl, block_w, tie_heavy, seed):
    """The kernels' actual step: a sorted (rows, topl) heap folded with an
    UNSORTED candidate block == lexsort top-L of heap + block. This is the
    exactness claim of the in-kernel merge."""
    rng = np.random.default_rng(seed)
    hs, hg = _case(rng, (4, topl), tie_heavy=tie_heavy, pad_frac=0.3)
    hs, hg = _oracle_sort(hs, hg)
    bs, bg = _case(rng, (4, block_w), tie_heavy=tie_heavy, pad_frac=0.1)
    bg = np.where(bg == _IMAX, _IMAX, bg + 10 * topl * 10).astype(np.int32)
    got_s, got_g = merge.merge_block_topl(hs, hg, bs, bg, topl)
    want_s, want_g = _oracle_sort(np.concatenate([hs, bs], -1),
                                  np.concatenate([hg, bg], -1))
    np.testing.assert_array_equal(np.asarray(got_s), want_s[:, :topl])
    np.testing.assert_array_equal(np.asarray(got_g), want_g[:, :topl])


def test_all_pad_heap_and_degenerate_widths():
    """The heap's initial state (all canonical pads) and width-1 inputs
    are handled without special cases."""
    hs = np.full((2, 8), np.inf, np.float32)
    hg = np.full((2, 8), _IMAX, np.int32)
    bs = np.asarray([[3.0], [1.0]], np.float32)
    bg = np.asarray([[5], [9]], np.int32)
    got_s, got_g = merge.merge_block_topl(hs, hg, bs, bg, 8)
    np.testing.assert_array_equal(np.asarray(got_s)[:, 0], [3.0, 1.0])
    np.testing.assert_array_equal(np.asarray(got_g)[:, 0], [5, 9])
    np.testing.assert_array_equal(np.asarray(got_s)[:, 1:], hs[:, 1:])
    np.testing.assert_array_equal(np.asarray(got_g)[:, 1:], hg[:, 1:])

    s1, g1 = merge.sort_pairs(bs, bg)
    np.testing.assert_array_equal(np.asarray(s1), bs)
    np.testing.assert_array_equal(np.asarray(g1), bg)
