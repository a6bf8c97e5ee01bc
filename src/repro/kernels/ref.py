"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (tests sweep
shapes/dtypes and assert_allclose kernel-vs-oracle). They are also the
fallback implementation used on backends without Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def adc_scan_ref(codes: jax.Array, lut: jax.Array) -> jax.Array:
    """Compressed-domain distance scan (paper Eq. 8, the ADC hot loop).

    codes: (N, M) integer codes (uint8/int32), lut: (M, K) float table with
    ``lut[m, k] = -<net(q)_m, c_mk>`` (or any per-codebook score table).
    Returns scores (N,): ``scores[n] = sum_m lut[m, codes[n, m]]``.

    The M accumulation is an explicit left-to-right chain (M is 8/16, so
    this unrolls to M-1 adds) — the same association the Pallas kernels
    use, which makes kernel-vs-oracle comparisons bit-exact instead of
    association-dependent.
    """
    m_idx = jnp.arange(lut.shape[0])[None, :]            # (1, M)
    gathered = lut[m_idx, codes.astype(jnp.int32)]       # (N, M)
    acc = gathered[:, 0]
    for m in range(1, lut.shape[0]):
        acc = acc + gathered[:, m]
    return acc


def adc_scan_batch_ref(codes: jax.Array, luts: jax.Array) -> jax.Array:
    """Multi-query ADC scan: codes (N, M), luts (Q, M, K) -> scores (Q, N).

    Defined as the vmap of the single-query oracle over the LUT axis, so
    per-query rows are bit-identical to ``adc_scan_ref`` — the batched
    kernel is validated against exactly this.
    """
    return jax.vmap(adc_scan_ref, in_axes=(None, 0))(codes, luts)


def smallest(scores: jax.Array, k: int):
    """The ``k`` smallest entries of each row, ascending, ties to the lower
    position: ``-lax.top_k(-scores, k)`` as documented. Written as a
    stable sort because on a TPU ``lax.top_k`` over 2^16 or more columns
    lowers to a TopK custom call, which does not promise that tie order.
    Returns (values, positions)."""
    order = jnp.argsort(scores, axis=-1, stable=True)[..., :k]
    return jnp.take_along_axis(scores, order, axis=-1), order


def adc_scan_topl_ref(codes: jax.Array, luts: jax.Array,
                      bias: jax.Array | None, topl: int):
    """Materialized oracle for the streaming scan+top-L: the full (Q, N)
    score matrix followed by ``lax.top_k``. Ground truth for the fused
    Pallas kernel and the chunked xla fallback — both must match this
    bit-for-bit in (score, index), including tie resolution (top_k breaks
    ties toward the smaller database index).

    codes (N, M), luts (Q, M, K), bias None | (N,) -> ((Q, L), (Q, L))
    with L = min(topl, N), sorted by (score asc, index asc).
    """
    scores = adc_scan_batch_ref(codes, luts)            # (Q, N)
    if bias is not None:
        scores = scores + bias[None, :]
    return smallest(scores, min(topl, codes.shape[0]))


_IMAX = jnp.iinfo(jnp.int32).max


def adc_gather_topl_ref(codes: jax.Array, rows: jax.Array, gids: jax.Array,
                        luts: jax.Array, rowbias: jax.Array | None,
                        topl: int):
    """Materialized oracle for the gathered (IVF-style) scan+top-L.

    Instead of scanning the whole database, each query scans its own
    PER-QUERY slot list — the padded ragged batch an IVF index builds by
    concatenating the inverted lists of its probed cells:

      codes   (N, M)  the contiguous code buffer (cell-grouped for IVF);
      rows    (Q, W)  buffer rows to score for each query (pad slots may
                      repeat any valid row — they are masked via gids);
      gids    (Q, W)  the GLOBAL id each slot stands for (what search
                      returns); ``_IMAX`` marks pad slots, which score
                      +inf and can never surface as real candidates;
      rowbias (Q, W)  additive per-slot score term or None: the gathered
                      per-point bias (RVQ norms) and the lowered
                      filter-mask stream (+inf = filtered out);
      luts    (Q, M, K) per-query score tables.

    Per-slot scores use the same left-to-right M chain as ``adc_scan_ref``
    on the same code row, so a gathered slot is bit-identical to the same
    point's score in the flat scan — the whole IVF==flat-at-full-probe
    guarantee reduces to tie handling.

    CONTRACT: within each query row, ``gids`` must be ascending (pads
    last). Then ``lax.top_k``'s positional tie-break IS the
    ascending-global-id tie-break of the flat oracle, and the result is
    bit-identical to flat search restricted to the listed slots.

    Slots whose score is +inf (pads, filtered) are canonicalized to
    gid ``_IMAX`` so every implementation returns identical bits even
    when +inf entries surface (pool smaller than L); the index layer maps
    them to id -1.

    Returns (scores, gids), each (Q, min(topl, W)), sorted by
    (score asc, gid asc).
    """
    q, w = rows.shape
    m_idx = jnp.arange(luts.shape[1])[None, None, :]          # (1, 1, M)
    gathered_codes = jnp.take(codes, rows, axis=0).astype(jnp.int32)
    picked = jnp.take_along_axis(
        luts[:, None, :, :],                                  # (Q, 1, M, K)
        gathered_codes[:, :, :, None], axis=3)[..., 0]        # (Q, W, M)
    acc = picked[:, :, 0]
    for m in range(1, luts.shape[1]):                         # adc_scan_ref
        acc = acc + picked[:, :, m]                           # association
    if rowbias is not None:
        acc = acc + rowbias
    acc = jnp.where(gids == _IMAX, jnp.inf, acc)
    gids = jnp.where(jnp.isposinf(acc), _IMAX, gids)
    s, pos = smallest(acc, min(topl, w))
    return s, jnp.take_along_axis(gids, pos, axis=1)


def adc_dispatch_topl_ref(codes: jax.Array, gids_rows: jax.Array,
                          rowbias: jax.Array, luts: jax.Array,
                          cellterm: jax.Array, qidx: jax.Array,
                          cell_lo: jax.Array, cell_hi: jax.Array,
                          topl: int, qkeep: jax.Array | None = None):
    """Materialized oracle for the cell-batched dispatch scan+top-L.

    The MoE-routed IVF stage 1 flips the gathered face's roles: instead of
    each query gathering the rows of its probed cells, each probed CELL
    scores its contiguous code range once for the dense batch of queries
    routed to it:

      codes    (N, M)     the cell-grouped code buffer;
      gids_rows (N,)      buffer row -> global id;
      rowbias  (N,)       per-row additive stream (per-point bias, with
                          any (N,) filter mask already folded to +inf);
      luts     (Q, M, K)  per-query score tables;
      cellterm (E, cap)   per-(routed cell, slot) additive term (the
                          IVFADC per-(query, cell) residual correction);
      qidx     (E, cap)   each routed cell's query batch, -1 = empty slot;
      cell_lo/cell_hi (E,) each routed cell's buffer row range;
      qkeep    None | (Q, N) 0/1 keep stream in buffer-row column order
                          (the lowered per-query filter mask).

    Scores use the same left-to-right M chain as ``adc_scan_ref`` and the
    same bias-composition order as the padded plan
    (``chain + (rowbias + cellterm)``, keep mask applied after), so a
    routed slot is bit-identical to the same (query, point) score on the
    gathered path. Rows outside [lo, hi), empty slots and filtered rows
    score +inf with the canonical ``_IMAX`` gid.

    Deliberately materializes the (E, cap, N) score tensor — ground truth
    only. Returns (scores, gids), each (E, cap, min(topl, N)), every slot
    sorted by (score asc, global id asc): ``lax.top_k`` over ascending
    buffer rows IS that order, because rows within a cell ascend in
    global id (stable cell-grouping of add order).
    """
    n = codes.shape[0]
    num_q, num_books = luts.shape[0], luts.shape[1]
    safe_q = jnp.clip(qidx, 0, num_q - 1)
    lut_e = luts[safe_q]                                     # (E, cap, M, K)
    m_idx = jnp.arange(num_books)[None, None, None, :]
    picked = lut_e[
        jnp.arange(qidx.shape[0])[:, None, None, None],
        jnp.arange(qidx.shape[1])[None, :, None, None],
        m_idx, codes.astype(jnp.int32)[None, None, :, :]]    # (E, cap, N, M)
    acc = picked[..., 0]
    for m in range(1, num_books):                            # adc_scan_ref
        acc = acc + picked[..., m]                           # association
    acc = acc + (rowbias[None, None, :] + cellterm[..., None])
    if qkeep is not None:
        keep = jnp.take(qkeep, safe_q, axis=0)               # (E, cap, N)
        acc = jnp.where(keep > 0.5, acc, jnp.inf)
    rows = jnp.arange(n, dtype=jnp.int32)
    window = (rows[None, None, :] >= cell_lo[:, None, None]) & \
        (rows[None, None, :] < cell_hi[:, None, None])
    acc = jnp.where(window, acc, jnp.inf)
    acc = jnp.where((qidx >= 0)[..., None], acc, jnp.inf)
    gids = jnp.broadcast_to(gids_rows[None, None, :], acc.shape)
    gids = jnp.where(jnp.isposinf(acc), _IMAX, gids)
    s, pos = smallest(acc, min(topl, n))
    return s, jnp.take_along_axis(gids, pos, axis=-1)


def adc_scan_batch_q_ref(codes: jax.Array, qluts: jax.Array,
                         scale: jax.Array | None = None) -> jax.Array:
    """Quantized-LUT multi-query scan oracle (the reduced-precision pool
    selector of ``kernels/lut_quant.py``).

    codes (N, M) integer; qluts (Q, M, K) float16 (scale None) or int8
    with scale (Q, M) f32 per-(query, book) affine scales -> (Q, N) f32.

    The quantized score is ``sum_m f32(qlut[m, code_m])`` (fp16) or
    ``sum_m f32(q8[m, code_m]) * scale[m]`` (int8), accumulated with the
    same left-to-right chain as ``adc_scan_ref`` — each per-m part is
    converted/scaled elementwise BEFORE the chain, which is the exact op
    order of both kernel impls, so pools match bit-for-bit. The int8
    zero-point offset is per-query constant and deliberately omitted
    (rank-invariant; see lut_quant module doc).
    """
    m_idx = jnp.arange(qluts.shape[1])[None, :]              # (1, M)

    def one(lut_q, sc_q):
        g = lut_q[m_idx, codes.astype(jnp.int32)].astype(jnp.float32)
        parts = g if sc_q is None else g * sc_q[None, :]     # (N, M)
        acc = parts[:, 0]
        for m in range(1, qluts.shape[1]):
            acc = acc + parts[:, m]
        return acc

    if scale is None:
        return jax.vmap(lambda l: one(l, None))(qluts)
    return jax.vmap(one)(qluts, scale)


def adc_scan_topl_q_ref(codes: jax.Array, qluts: jax.Array,
                        scale: jax.Array | None,
                        bias: jax.Array | None, topl: int,
                        qbias: jax.Array | None = None):
    """Materialized oracle for the quantized streaming scan+top-L': the
    full quantized (Q, N) matrix (``adc_scan_batch_q_ref``), the SAME f32
    bias streams as the exact path, then ``lax.top_k``. Defines the pool
    the quantized kernels must select bit-for-bit."""
    s = adc_scan_batch_q_ref(codes, qluts, scale)
    if bias is not None:
        s = s + bias[None, :]
    if qbias is not None:
        s = s + qbias
    return smallest(s, min(topl, codes.shape[0]))


def adc_gather_topl_q_ref(codes: jax.Array, rows: jax.Array,
                          gids: jax.Array, qluts: jax.Array,
                          scale: jax.Array | None,
                          rowbias: jax.Array | None, topl: int):
    """Materialized oracle for the quantized gathered scan+top-L': the
    quantized per-slot chain (fp16 gather->f32 or i8 gather->f32*scale,
    parts converted before the chain), the exact f32 rowbias stream, pad
    and +inf canonicalization exactly as ``adc_gather_topl_ref``."""
    q, w = rows.shape
    gathered_codes = jnp.take(codes, rows, axis=0).astype(jnp.int32)
    picked = jnp.take_along_axis(
        qluts[:, None, :, :],
        gathered_codes[:, :, :, None], axis=3)[..., 0]       # (Q, W, M)
    picked = picked.astype(jnp.float32)
    if scale is not None:
        picked = picked * scale[:, None, :]
    acc = picked[:, :, 0]
    for m in range(1, qluts.shape[1]):
        acc = acc + picked[:, :, m]
    if rowbias is not None:
        acc = acc + rowbias
    acc = jnp.where(gids == _IMAX, jnp.inf, acc)
    gids = jnp.where(jnp.isposinf(acc), _IMAX, gids)
    s, pos = smallest(acc, min(topl, w))
    return s, jnp.take_along_axis(gids, pos, axis=1)


def adc_dispatch_topl_q_ref(codes: jax.Array, gids_rows: jax.Array,
                            rowbias: jax.Array, qluts: jax.Array,
                            scale: jax.Array | None, cellterm: jax.Array,
                            qidx: jax.Array, cell_lo: jax.Array,
                            cell_hi: jax.Array, topl: int,
                            qkeep: jax.Array | None = None):
    """Materialized oracle for the quantized dispatch scan+top-L': the
    quantized chain per routed slot with the exact f32 bias composition
    ``chain + (rowbias + cellterm)`` and masks of
    ``adc_dispatch_topl_ref``."""
    n = codes.shape[0]
    num_q, num_books = qluts.shape[0], qluts.shape[1]
    safe_q = jnp.clip(qidx, 0, num_q - 1)
    lut_e = qluts[safe_q]                                    # (E, cap, M, K)
    m_idx = jnp.arange(num_books)[None, None, None, :]
    picked = lut_e[
        jnp.arange(qidx.shape[0])[:, None, None, None],
        jnp.arange(qidx.shape[1])[None, :, None, None],
        m_idx, codes.astype(jnp.int32)[None, None, :, :]]    # (E, cap, N, M)
    picked = picked.astype(jnp.float32)
    if scale is not None:
        picked = picked * scale[safe_q][:, :, None, :]
    acc = picked[..., 0]
    for m in range(1, num_books):
        acc = acc + picked[..., m]
    acc = acc + (rowbias[None, None, :] + cellterm[..., None])
    if qkeep is not None:
        keep = jnp.take(qkeep, safe_q, axis=0)               # (E, cap, N)
        acc = jnp.where(keep > 0.5, acc, jnp.inf)
    rows = jnp.arange(n, dtype=jnp.int32)
    window = (rows[None, None, :] >= cell_lo[:, None, None]) & \
        (rows[None, None, :] < cell_hi[:, None, None])
    acc = jnp.where(window, acc, jnp.inf)
    acc = jnp.where((qidx >= 0)[..., None], acc, jnp.inf)
    gids = jnp.broadcast_to(gids_rows[None, None, :], acc.shape)
    gids = jnp.where(jnp.isposinf(acc), _IMAX, gids)
    s, pos = smallest(acc, min(topl, n))
    return s, jnp.take_along_axis(gids, pos, axis=-1)


def decode_with_table(codes: jax.Array, table: jax.Array) -> jax.Array:
    """Additive table decode: ``recon = sum_m table[m, codes[..., m]]``.

    codes (..., M) integer, table (M, K, D) float32 -> (..., D).

    This is THE reconstruction the stage-2 rerank engine is defined over:
    PQ embeds each sub-codebook into its D-slice (zero elsewhere), OPQ
    additionally rotates each embedded sub-codeword, RVQ's codebooks are
    already full-dimensional. The M accumulation is an explicit
    left-to-right chain (like ``adc_scan_ref``) so the fused kernel, the
    chunked fallback, and the vmap oracle are bit-identical instead of
    association-dependent.
    """
    c = codes.astype(jnp.int32)
    acc = table[0][c[..., 0]]
    for m in range(1, table.shape[0]):
        acc = acc + table[m][c[..., m]]
    return acc


def fold_halves(x: jax.Array, axis: int = -1) -> jax.Array:
    """Sum over ``axis`` in one fixed pairwise order, keeping the axis
    (width 1): with P the power of two >= width and h = P / 2, element i
    is added to element i + h (when that exists), and the halving
    repeats until one element is left — the tree of a sum over the axis
    zero-padded to P.

    Every d1 path (the oracles, the chunked fallbacks, the dedup
    reranker and the fused Pallas kernel) reduces ``(recon - q)^2`` in
    this order, so their distances are the same bits on every platform
    and for every batch shape. ``jnp.sum`` leaves the order to the
    compiler, which picks it per shape and per device. The summands are
    squares (never -0), so padding the axis with more zeros, as the
    kernel does, leaves every partial sum unchanged.
    """
    axis = axis % x.ndim
    w = x.shape[axis]
    while w > 1:
        h = 1 << (w - 1).bit_length() - 1              # P / 2
        lo = jax.lax.slice_in_dim(x, 0, w - h, axis=axis)
        hi = jax.lax.slice_in_dim(x, h, w, axis=axis)
        x = jnp.concatenate(
            [lo + hi, jax.lax.slice_in_dim(x, w - h, h, axis=axis)],
            axis=axis)
        w = h
    return x


def squares(diff: jax.Array) -> jax.Array:
    """``diff^2`` as the summands of a d1 fold. The ``max(., 0)`` changes
    no value (a square is >= 0); it keeps a compiler from contracting a
    square and the first fold add into one fused multiply-add, which
    rounds once where the definition rounds twice (XLA's CPU backend
    does so inside fusions)."""
    return jnp.maximum(jnp.square(diff), 0.0)


def sq_dist(recon: jax.Array, q: jax.Array) -> jax.Array:
    """Exact d1 (paper Eq. 7): ``||q - recon||^2`` over the last axis,
    reduced in the ``fold_halves`` order. recon (..., D), q broadcastable
    to it -> (...)."""
    return fold_halves(squares(recon - q))[..., 0]


def rerank_gather_dist_ref(cand_codes: jax.Array, queries: jax.Array,
                           table: jax.Array) -> jax.Array:
    """Materialized oracle for the fused gather-decode-distance kernel
    (stage 2, paper Eq. 7 over a table-decodable quantizer).

    cand_codes (Q, L, M) integer candidate codes (already gathered from
    the database by candidate id), queries (Q, D), table (M, K, D) ->
    d1 distances (Q, L): ``||q - sum_m table[m, code_m]||^2``.

    Deliberately materializes the (Q, L, D) reconstruction — it is the
    ground truth the streaming paths are validated against bit-for-bit.
    """
    recon = decode_with_table(cand_codes, table)         # (Q, L, D)
    return sq_dist(recon, queries[:, None, :])


def unq_encode_ref(heads: jax.Array, codebooks: jax.Array) -> jax.Array:
    """Codeword assignment (paper Eq. 4).

    heads: (B, M, d_c) = net(x); codebooks: (M, K, d_c).
    Returns codes (B, M) int32: argmax_k <heads[b, m], codebooks[m, k]>.
    """
    scores = jnp.einsum("bmd,mkd->bmk", heads, codebooks)
    return jnp.argmax(scores, axis=-1).astype(jnp.int32)


def kv_adc_attention_ref(q: jax.Array, k_codes: jax.Array, v_codes: jax.Array,
                         k_books: jax.Array, v_books: jax.Array,
                         length: jax.Array | int | None = None) -> jax.Array:
    """Beyond-paper: single-step decode attention over an MCQ-compressed KV
    cache, entirely in the compressed domain.

    The attention logit against a compressed key IS the paper's d2 scan:
        q . k_s  ~=  sum_m <q_m, cK_{m, i_{s,m}}>
    and the value aggregation folds the softmax weights into a per-codeword
    histogram before a single (M*K, d) matmul:
        sum_s w_s v_s ~= sum_m sum_k (sum_{s: code=k} w_s) cV_{m,k}
    so the per-token work is O(M) adds instead of O(d) MACs.

    q:        (H, d)         query for one new token (per kv-head group or head)
    k_codes:  (S, H, M) int  compressed keys
    v_codes:  (S, H, M) int  compressed values
    k_books:  (H, M, K, d/M) key codebooks (PQ-style subspace split)
    v_books:  (H, M, K, d/M) value codebooks
    length:   optional valid prefix length (<= S) for masking.
    Returns attention output (H, d).
    """
    H, d = q.shape
    S, _, M = k_codes.shape
    K = k_books.shape[2]
    d_sub = d // M
    q_sub = q.reshape(H, M, d_sub)

    # LUT build: one pass, O(H*M*K*d_sub) — independent of S.
    lut = jnp.einsum("hms,hmks->hmk", q_sub, k_books)            # (H, M, K)

    # ADC scan over the cache: O(S*H*M) lookups.
    m_idx = jnp.arange(M)[None, None, :]
    h_idx = jnp.arange(H)[None, :, None]
    logits = jnp.sum(lut[h_idx, m_idx, k_codes.astype(jnp.int32)], axis=-1)  # (S, H)

    if length is not None:
        mask = jnp.arange(S)[:, None] < length
        logits = jnp.where(mask, logits, -jnp.inf)

    w = jax.nn.softmax(logits / jnp.sqrt(d).astype(logits.dtype), axis=0)  # (S, H)

    # Compressed-domain value aggregation: scatter weights into (H, M, K).
    onehot = jax.nn.one_hot(v_codes.astype(jnp.int32), K, dtype=w.dtype)  # (S,H,M,K)
    hist = jnp.einsum("sh,shmk->hmk", w, onehot)                           # (H, M, K)
    out_sub = jnp.einsum("hmk,hmks->hms", hist, v_books)                   # (H, M, d_sub)
    return out_sub.reshape(H, d)
