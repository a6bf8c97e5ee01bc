"""Public entry points for the kernels package.

Each op dispatches between:
  impl="pallas"  — the Pallas TPU kernel (``interpret=True`` automatically on
                   CPU so the kernel body is validated in this container);
  impl="xla"     — the pure-jnp oracle from ``ref.py`` (always available,
                   and what the distributed paths use inside pjit);
  impl="onehot"  — XLA one-hot matmul formulation (the MXU-shaped algorithm
                   without Pallas, useful to A/B the adaptation itself).

All wrappers handle padding to kernel block multiples.

Block/chunk parameters default to ``None`` and are resolved through the
autotuner registry (``repro.kernels.tune.best_config``): the cached winner
for (device kind, kernel, shape bucket) when ``python -m repro.tune`` has
run on this machine, the registered hand-pinned defaults otherwise. An
explicit integer argument always wins (tests pin exact block shapes).
Small-dim rounding goes through ``tune.align`` / ``tune.clamp_chunk`` —
the ONE home of those heuristics.

The three top-L ops accept ``lut_dtype`` / ``overfetch`` for the opt-in
reduced-precision stage 1 (``lut_quant.py``): the scan runs on quantized
(f16/i8) tables selecting an over-fetched pool of ``overfetch * topl``
candidates, survivors are re-scored with the exact f32 chain (op-for-op
the exact path's composition), and the exact lexicographic top-L of the
pool is returned. ``lut_dtype='float32', overfetch=1`` — the default —
routes down the literally unchanged bit-exact path.

The Pallas kernels compile with Mosaic on a TPU and run in interpret mode
everywhere else. Nothing can switch a TPU run to interpret mode: a timing
or a parity check taken on the chip is always of the compiled kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import lut_quant, ref, tune
from repro.kernels.adc_scan import (adc_scan_pallas, adc_scan_batch_pallas,
                                    DEFAULT_BLOCK_N, DEFAULT_BLOCK_Q)
from repro.kernels.dispatch_topl import (adc_dispatch_topl_pallas,
                                         adc_dispatch_topl_stream_xla,
                                         DispatchPlan,
                                         DEFAULT_DISPATCH_CHUNK)
from repro.kernels.gather_topl import (adc_gather_topl_pallas,
                                       adc_gather_topl_stream_xla,
                                       DEFAULT_CHUNK_W,
                                       DEFAULT_GATHER_BLOCK_Q,
                                       DEFAULT_GATHER_BLOCK_W)
from repro.kernels.rerank_dist import (rerank_gather_dist_pallas,
                                       rerank_gather_dist_chunked_xla,
                                       DEFAULT_RERANK_BLOCK_L,
                                       DEFAULT_RERANK_BLOCK_Q,
                                       DEFAULT_RERANK_CHUNK_L)
from repro.kernels.topl_scan import (adc_scan_topl_pallas,
                                     adc_scan_topl_stream_xla,
                                     DEFAULT_CHUNK_N, DEFAULT_TOPL_BLOCK_N,
                                     DEFAULT_TOPL_BLOCK_Q)
from repro.kernels.unq_encode import unq_encode_pallas, DEFAULT_BLOCK_B

_IMAX = jnp.iinfo(jnp.int32).max


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Pallas interpret mode exactly when no TPU backs the kernels."""
    return not _on_tpu()


def _pad_to(x: jax.Array, multiple: int, axis: int = 0):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def _onehot_scan(codes: jax.Array, luts: jax.Array) -> jax.Array:
    """The MXU-shaped formulation: per codebook, a one-hot contraction
    over K (one non-zero term, so an exact copy of the table entry at
    full f32 passes), chained over M left to right like
    ``ref.adc_scan_ref`` — the same bits as the oracle, whatever N."""
    onehot = jax.nn.one_hot(codes.astype(jnp.int32), luts.shape[-1],
                            dtype=luts.dtype)          # (N, M, K)
    acc = None
    for m in range(luts.shape[1]):
        part = jnp.einsum("nk,qk->qn", onehot[:, m], luts[:, m],
                          precision=jax.lax.Precision.HIGHEST)
        acc = part if acc is None else acc + part
    return acc


def adc_scan(codes: jax.Array, lut: jax.Array, *, impl: str = "pallas",
             block_n: int | None = None) -> jax.Array:
    """scores[n] = sum_m lut[m, codes[n, m]].  codes (N, M), lut (M, K) -> (N,)."""
    if impl == "xla":
        return ref.adc_scan_ref(codes, lut)
    if impl == "onehot":
        return _onehot_scan(codes, lut[None])[0]
    if impl == "pallas":
        cfg = tune.best_config("adc_scan", "pallas", n=codes.shape[0])
        bn = cfg["block_n"] if block_n is None else block_n
        padded, n = _pad_to(codes, bn, axis=0)
        out = adc_scan_pallas(padded, lut.astype(jnp.float32),
                              block_n=bn, interpret=_interpret())
        return out[:n]
    raise ValueError(f"unknown impl: {impl!r}")


def adc_scan_batch(codes: jax.Array, luts: jax.Array, *, impl: str = "pallas",
                   block_n: int | None = None,
                   block_q: int | None = None) -> jax.Array:
    """Multi-query scan: scores[q, n] = sum_m luts[q, m, codes[n, m]].

    codes (N, M), luts (Q, M, K) -> (Q, N). The pallas impl streams each
    code block once for all Q queries (Q-fold HBM amortization vs the
    per-query ``adc_scan``); xla/onehot are the oracles.
    """
    if impl == "xla":
        return ref.adc_scan_batch_ref(codes, luts)
    if impl == "onehot":
        return _onehot_scan(codes, luts)
    if impl == "pallas":
        q = luts.shape[0]
        cfg = tune.best_config("adc_scan_batch", "pallas",
                               n=codes.shape[0], q=q)
        bn = cfg["block_n"] if block_n is None else block_n
        # shrink the query block for small batches (8 = f32 sublane tile)
        bq = tune.align(q, cap=cfg["block_q"] if block_q is None else block_q)
        padded_codes, n = _pad_to(codes, bn, axis=0)
        padded_luts, _ = _pad_to(luts.astype(jnp.float32), bq, axis=0)
        out = adc_scan_batch_pallas(padded_codes, padded_luts,
                                    block_n=bn, block_q=bq,
                                    interpret=_interpret())
        return out[:q, :n]
    raise ValueError(f"unknown impl: {impl!r}")


def _scan_topl_run(codes, luts, scale, bias, qbias, *, topl: int, impl: str,
                   block_n, block_q, chunk_n):
    """One streaming scan+top-L pass at the given table precision (the
    shared engine behind the exact path and the quantized pool scan)."""
    n = codes.shape[0]
    q = luts.shape[0]
    if impl == "xla":
        cfg = tune.best_config("adc_scan_topl", "xla", n=n, q=q, topl=topl)
        cn = cfg["chunk_n"] if chunk_n is None else chunk_n
        return adc_scan_topl_stream_xla(
            codes, luts, bias, qbias, scale, topl=topl, n_valid=n,
            chunk_n=tune.clamp_chunk(n, cap=cn, floor=topl))
    if impl == "pallas":
        cfg = tune.best_config("adc_scan_topl", "pallas", n=n, q=q, topl=topl)
        bn = cfg["block_n"] if block_n is None else block_n
        bq = tune.align(q, cap=cfg["block_q"] if block_q is None else block_q)
        padded_codes, _ = _pad_to(codes, bn, axis=0)
        padded_luts, _ = _pad_to(luts, bq, axis=0)
        padded_bias, _ = _pad_to(bias.astype(jnp.float32), bn, axis=0)
        padded_qbias = None
        if qbias is not None:
            padded_qbias, _ = _pad_to(qbias.astype(jnp.float32), bq, axis=0)
            padded_qbias, _ = _pad_to(padded_qbias, bn, axis=1)
        padded_scale = None
        if scale is not None:
            padded_scale, _ = _pad_to(scale, bq, axis=0)
        scores, idx = adc_scan_topl_pallas(
            padded_codes, padded_luts, padded_bias, padded_qbias,
            padded_scale, topl=topl, n_valid=n, block_n=bn, block_q=bq,
            interpret=_interpret())
        return scores[:q], idx[:q]
    raise ValueError(
        f"unknown impl for adc_scan_topl: {impl!r} (streaming top-L has "
        "'pallas' and 'xla' paths; 'onehot' materializes the score matrix "
        "and is routed through the MaterializedTopL generator instead)")


@functools.partial(jax.jit, static_argnames=("topl",))
def _rescore_flat(codes, luts, bias, qbias, pool_g, topl: int):
    """Exact f32 re-score of a flat-scan candidate pool: the exact path's
    op-for-op score composition (left-to-right chain + bias + qbias) at
    the pool's rows, then the exact lexicographic top-L. Jitted: the
    pool is small (Q, L') but the ~15 eager op dispatches otherwise cost
    more than the compiled work on CPU."""
    n, num_books = codes.shape
    luts_f = luts.astype(jnp.float32)
    rows = jnp.minimum(pool_g, n - 1)
    c = jnp.take(codes, rows, axis=0).astype(jnp.int32)       # (Q, P, M)
    picked = jnp.take_along_axis(
        luts_f[:, None, :, :], c[..., None], axis=3)[..., 0]  # (Q, P, M)
    s = picked[..., 0]
    for m in range(1, num_books):                             # adc_scan_ref
        s = s + picked[..., m]                                # association
    s = s + jnp.take(bias, rows)
    if qbias is not None:
        s = s + jnp.take_along_axis(qbias, rows, axis=1)
    # scan-pad rows (gid >= n, incl. the _IMAX heap pad) can never surface
    s = jnp.where(pool_g >= n, jnp.inf, s)
    return lut_quant.exact_topl(s, pool_g, topl)


def adc_scan_topl(codes: jax.Array, luts: jax.Array, *, topl: int,
                  bias: jax.Array | None = None,
                  qbias: jax.Array | None = None, impl: str = "pallas",
                  block_n: int | None = None,
                  block_q: int | None = None,
                  chunk_n: int | None = None,
                  lut_dtype: str = "float32", overfetch: int = 1):
    """Streaming stage 1: per-query top-L over the compressed database
    WITHOUT materializing the (Q, N) score matrix.

    codes (N, M), luts (Q, M, K), optional bias (N,) ->
    ((Q, L), (Q, L) int32) with L = min(topl, N), sorted by
    (score asc, index asc) — bit-identical to ``lax.top_k`` over the full
    matrix (``ref.adc_scan_topl_ref``), tie resolution included.

      impl="pallas"  the fused scan+top-L kernel: a running (block_q, L)
                     heap in VMEM while code blocks stream from HBM.
      impl="xla"     chunked ``lax.scan`` + incremental top-L merge; the
                     always-available fallback with the same O(Q*L) peak.

    Both paths mask the internal N-padding rows to +inf so a pad entry can
    never surface as a candidate. ``bias`` carries per-point terms that do
    not fit the LUT decomposition (RVQ's stored ||decode(code)||^2);
    ``qbias`` is the optional (Q, N) per-(query, point) bias stream — the
    lowering target of the filtered-search API (+inf drops one point for
    one query) — consumed in tiles/chunks by both paths.

    ``lut_dtype`` in {'float16', 'int8'} switches the scan to quantized
    tables selecting an over-fetched pool of ``overfetch * topl``
    candidates, exactly re-scored in f32 before the final top-L (see
    ``lut_quant``); the default ('float32', overfetch 1) is the bit-exact
    path above, unchanged.
    """
    n = codes.shape[0]
    topl = min(topl, n)
    if bias is None:
        bias = jnp.zeros((n,), jnp.float32)
    lut_quant.check_lut_dtype(lut_dtype)
    if lut_dtype != "float32" or overfetch != 1:
        pool_l = lut_quant.pool_width(topl, overfetch, n)
        qluts, scale = lut_quant.quantize_luts(luts, lut_dtype)
        _, pool_g = _scan_topl_run(
            codes, qluts, scale, bias, qbias, topl=pool_l, impl=impl,
            block_n=block_n, block_q=block_q, chunk_n=chunk_n)
        return _rescore_flat(codes, luts, bias, qbias, pool_g, topl)
    return _scan_topl_run(
        codes, luts.astype(jnp.float32), None, bias, qbias, topl=topl,
        impl=impl, block_n=block_n, block_q=block_q, chunk_n=chunk_n)


def _gather_topl_run(codes, rows, gids, luts, scale, rowbias, *, topl: int,
                     impl: str, block_w, block_q, chunk_w):
    """One gathered scan+top-L pass at the given table precision."""
    q, w = rows.shape
    if impl == "xla":
        cfg = tune.best_config("adc_gather_topl", "xla", w=w, q=q, topl=topl)
        cw = cfg["chunk_w"] if chunk_w is None else chunk_w
        return adc_gather_topl_stream_xla(
            codes, rows, gids, rowbias.astype(jnp.float32), luts, scale,
            topl=topl, chunk_w=tune.clamp_chunk(w, cap=cw, floor=topl))
    if impl == "pallas":
        cfg = tune.best_config("adc_gather_topl", "pallas",
                               w=w, q=q, topl=topl)
        bq = tune.align(q, cap=cfg["block_q"] if block_q is None else block_q)
        # whole 128-lane vregs: the in-kernel merge rolls along lanes
        bw = tune.align(w, cap=cfg["block_w"] if block_w is None else block_w,
                        multiple=128)
        gathered = jnp.take(codes, rows, axis=0)           # (Q, W, M) u8
        gathered, _ = _pad_to(gathered, bq, axis=0)
        gathered, _ = _pad_to(gathered, bw, axis=1)
        padded_gids = jnp.pad(
            gids, ((0, gathered.shape[0] - q), (0, gathered.shape[1] - w)),
            constant_values=_IMAX)
        padded_bias = jnp.pad(
            rowbias.astype(jnp.float32),
            ((0, gathered.shape[0] - q), (0, gathered.shape[1] - w)))
        padded_luts, _ = _pad_to(luts, bq, axis=0)
        padded_scale = None
        if scale is not None:
            padded_scale, _ = _pad_to(scale, bq, axis=0)
        scores, idx = adc_gather_topl_pallas(
            gathered, padded_gids, padded_bias, padded_luts, padded_scale,
            topl=topl, block_w=bw, block_q=bq, interpret=_interpret())
        return scores[:q], idx[:q]
    raise ValueError(
        f"unknown impl for adc_gather_topl: {impl!r} (the gathered top-L "
        "has 'pallas' and 'xla' paths; 'onehot' routes through the "
        "materialized generator)")


@functools.partial(jax.jit, static_argnames=("topl",))
def _rescore_gather(codes, rows, gids, luts, rowbias, pool_g, topl: int):
    """Exact f32 re-score of a gathered-scan pool: pool gids map back to
    their slots via the ascending-gids plan contract (searchsorted), the
    exact chain + rowbias composition is reproduced op-for-op, +inf
    entries take the canonical _IMAX gid (gathered-path semantics)."""
    q, w = rows.shape
    num_books = luts.shape[1]
    luts_f = luts.astype(jnp.float32)
    slot = jax.vmap(jnp.searchsorted)(gids, pool_g)           # (Q, P)
    slot = jnp.minimum(slot, w - 1).astype(jnp.int32)
    hit = jnp.take_along_axis(gids, slot, axis=1) == pool_g
    rows_p = jnp.take_along_axis(rows, slot, axis=1)
    c = jnp.take(codes, rows_p, axis=0).astype(jnp.int32)     # (Q, P, M)
    picked = jnp.take_along_axis(
        luts_f[:, None, :, :], c[..., None], axis=3)[..., 0]
    s = picked[..., 0]
    for m in range(1, num_books):                             # adc_scan_ref
        s = s + picked[..., m]                                # association
    s = s + jnp.take_along_axis(rowbias.astype(jnp.float32), slot, axis=1)
    s = jnp.where(hit & (pool_g != _IMAX), s, jnp.inf)
    pool_g = jnp.where(jnp.isposinf(s), _IMAX, pool_g)
    return lut_quant.exact_topl(s, pool_g, topl)


def adc_gather_topl(codes: jax.Array, rows: jax.Array, gids: jax.Array,
                    luts: jax.Array, *, topl: int,
                    rowbias: jax.Array | None = None, impl: str = "pallas",
                    block_w: int | None = None,
                    block_q: int | None = None,
                    chunk_w: int | None = None,
                    lut_dtype: str = "float32", overfetch: int = 1):
    """Gathered stage 1 (IVF probing): per-query top-L over per-query slot
    lists instead of the whole database.

    codes (N, M) code buffer, rows (Q, W) buffer rows to score per query,
    gids (Q, W) the global id behind each slot (``_IMAX`` marks ragged
    pads), luts (Q, M, K), optional rowbias (Q, W) additive per-slot
    stream -> ((Q, L), (Q, L) int32) with L = min(topl, W), sorted by
    (score asc, global id asc).

    ``rowbias`` is the single additive slot stream every per-slot term
    composes onto HOST-SIDE before the kernel runs: gathered per-point
    biases (RVQ norms, residual-IVF cross terms ``2<centroid, decode>``),
    the residual correction's per-(query, cell) term
    ``||centroid||^2 - 2<q, centroid>`` gathered at each slot's cell, and
    the lowered filter mask (+inf drops a slot). Keeping the composition
    outside the kernel fixes one addition order, which is what makes all
    paths bit-identical for any mix of streams.

    CONTRACT: gids must be ascending within each query row (pads last) —
    IVF plan builders sort their probe lists by global id, which is what
    makes every path bit-identical to ``ref.adc_gather_topl_ref`` AND to
    flat search at nprobe == nlist (see gather_topl.py). The quantized
    path leans on the same contract to map pool gids back to slots for
    the exact re-score.

      impl="pallas"  the fused kernel: gathered uint8 code tiles stream
                     HBM->VMEM against a VMEM-resident (block_q, L) heap.
      impl="xla"     chunked ``lax.scan`` gathering O(Q*chunk_w) slots at
                     a time; the always-available fallback.

    (The materialized 'onehot' formulation routes through
    ``MaterializedTopL.gather_topl`` instead, scoring the full buffer.)

    ``lut_dtype`` / ``overfetch``: the reduced-precision pool scan + exact
    re-score, as in ``adc_scan_topl``.
    """
    q, w = rows.shape
    topl = min(topl, w)
    if rowbias is None:
        rowbias = jnp.zeros((q, w), jnp.float32)
    lut_quant.check_lut_dtype(lut_dtype)
    if lut_dtype != "float32" or overfetch != 1:
        pool_l = lut_quant.pool_width(topl, overfetch, w)
        qluts, scale = lut_quant.quantize_luts(luts, lut_dtype)
        _, pool_g = _gather_topl_run(
            codes, rows, gids, qluts, scale, rowbias, topl=pool_l,
            impl=impl, block_w=block_w, block_q=block_q, chunk_w=chunk_w)
        return _rescore_gather(codes, rows, gids, luts, rowbias, pool_g,
                               topl)
    return _gather_topl_run(
        codes, rows, gids, luts.astype(jnp.float32), None, rowbias,
        topl=topl, impl=impl, block_w=block_w, block_q=block_q,
        chunk_w=chunk_w)


def _dispatch_topl_run(codes, gids_rows, rowbias, luts, scale, cellterm,
                       plan, qkeep, *, topl: int, impl: str, chunk: int):
    """One dispatch scan+top-L pass at the given table precision."""
    n = codes.shape[0]
    padded_codes, _ = _pad_to(codes, chunk, axis=0)
    n_pad = padded_codes.shape[0] - n
    gids_p = jnp.pad(gids_rows, (0, n_pad), constant_values=_IMAX)
    rowb_p = jnp.pad(rowbias.astype(jnp.float32), (0, n_pad))
    qkeep_p = None
    if qkeep is not None:
        qkeep_p = jnp.pad(qkeep.astype(jnp.float32), ((0, 0), (0, n_pad)))
    if impl == "xla":
        scores, ids = adc_dispatch_topl_stream_xla(
            padded_codes, gids_p, rowb_p, luts, cellterm, plan, qkeep_p,
            scale, topl=topl, chunk=chunk)
    elif impl == "pallas":
        luts_p, _ = _pad_to(luts, 8, axis=0)
        scale_p = None
        if scale is not None:
            scale_p, _ = _pad_to(scale, 8, axis=0)
        if qkeep_p is not None:
            qkeep_p, _ = _pad_to(qkeep_p, 8, axis=0)
        scores, ids = adc_dispatch_topl_pallas(
            padded_codes, gids_p, rowb_p, luts_p, cellterm, plan, qkeep_p,
            scale_p, topl=topl, chunk=chunk, interpret=_interpret())
    else:
        raise ValueError(
            f"unknown impl for adc_dispatch_topl: {impl!r} (the dispatch "
            "face has 'pallas' and 'xla' paths; backends without the "
            "dispatch_topl capability use the padded gathered path)")
    # rows the router never routed (bucket padding past the active cells)
    # hold whatever the kernel left there — mask them to the canonical
    # (+inf, _IMAX) empty pool so partials are deterministic end to end
    routed = jnp.any(plan.qidx >= 0, axis=1)[:, None, None]
    scores = jnp.where(routed, scores, jnp.inf)
    ids = jnp.where(routed, ids, _IMAX)
    return scores, ids


@functools.partial(jax.jit, static_argnames=("topl",))
def _rescore_dispatch(codes, rowbias, luts, cellterm, plan, qkeep, pos,
                      part_g, topl: int):
    """Exact f32 re-score of per-cell dispatch pools: pool gids map to
    buffer rows via ``pos`` (the index's global id -> row inverse), the
    exact ``chain + (rowbias + cellterm)`` composition and mask order are
    reproduced op-for-op, +inf entries take the canonical _IMAX gid."""
    num_books = codes.shape[1]
    num_q = luts.shape[0]
    luts_f = luts.astype(jnp.float32)
    valid = part_g != _IMAX
    safe_g = jnp.clip(part_g, 0, pos.shape[0] - 1)
    rows_p = jnp.take(pos, safe_g)                        # (E+1, cap, P)
    c = jnp.take(codes, rows_p, axis=0).astype(jnp.int32)
    safe_q = jnp.clip(plan.qidx, 0, num_q - 1)            # (E+1, cap)
    lut_e = jnp.take(luts_f, safe_q, axis=0)              # (E+1, cap, M, K)
    picked = jnp.take_along_axis(
        lut_e[:, :, None, :, :], c[..., None], axis=4)[..., 0]
    s = picked[..., 0]
    for m in range(1, num_books):                         # adc_scan_ref
        s = s + picked[..., m]                            # association
    s = s + (jnp.take(rowbias.astype(jnp.float32), rows_p)
             + cellterm[:, :, None])
    if qkeep is not None:
        keep = qkeep[safe_q[..., None], rows_p]           # (E+1, cap, P)
        s = jnp.where(keep > 0.5, s, jnp.inf)
    s = jnp.where(valid, s, jnp.inf)
    s = jnp.where((plan.qidx >= 0)[:, :, None], s, jnp.inf)
    part_g = jnp.where(jnp.isposinf(s), _IMAX, part_g)
    return lut_quant.exact_topl(s, part_g, topl)


def adc_dispatch_topl(codes: jax.Array, gids_rows: jax.Array,
                      rowbias: jax.Array | None, luts: jax.Array,
                      cellterm: jax.Array, plan: DispatchPlan, *, topl: int,
                      qkeep: jax.Array | None = None, impl: str = "pallas",
                      chunk: int | None = None,
                      pos: jax.Array | None = None,
                      lut_dtype: str = "float32", overfetch: int = 1):
    """Cell-batched dispatch stage 1 (MoE-routed IVF probing): each routed
    cell's contiguous code range is scored ONCE for the dense batch of
    queries probing it, against a per-cell VMEM top-L heap.

    codes (N, M) the cell-grouped buffer, gids_rows (N,) buffer row ->
    global id, rowbias None | (N,) per-row additive stream (per-point
    bias with any (N,) filter already folded to +inf), luts (Q, M, K),
    cellterm (E+1, cap) per-(routed cell, slot) additive term, plan the
    ``DispatchPlan`` from ``repro.index.dispatch``, qkeep None | (Q, N)
    0/1 keep stream in buffer-row column order.

    ``chunk`` must be the tile width the plan was built with
    (``Routing.chunk``); ``None`` resolves the same shared registry entry
    the router uses, so router and kernel agree by construction.

    Returns per-cell partial pools ((E+1, cap, L) f32, (E+1, cap, L) i32)
    with L = min(topl, N), each slot sorted by (score asc, global id
    asc); rows the router never filled are masked to (+inf, _IMAX), so
    partials are fully deterministic. ``index.dispatch.combine_pools``
    scatters them back to per-query pools — bit-identical to the padded
    gathered path, tie semantics included.

      impl="pallas"  fused kernel: scalar-prefetched tile plan drives the
                     HBM code stream, heaps stay VMEM-resident per cell.
      impl="xla"     chunked ``lax.scan`` over the same tile plan; the
                     always-available fallback.

    ``lut_dtype`` / ``overfetch``: the reduced-precision pool scan + exact
    re-score (as in ``adc_scan_topl``) — requires ``pos``, the (n_ids,)
    global id -> buffer row inverse, to locate pool survivors' codes.
    """
    n = codes.shape[0]
    topl = min(topl, n)
    if rowbias is None:
        rowbias = jnp.zeros((n,), jnp.float32)
    if chunk is None:
        chunk = tune.best_config("adc_dispatch_topl",
                                 n=n, q=luts.shape[0])["chunk"]
    lut_quant.check_lut_dtype(lut_dtype)
    if lut_dtype != "float32" or overfetch != 1:
        if pos is None:
            raise ValueError(
                "quantized adc_dispatch_topl needs pos (global id -> "
                "buffer row) to re-score pool survivors exactly")
        pool_l = lut_quant.pool_width(topl, overfetch, n)
        qluts, scale = lut_quant.quantize_luts(luts, lut_dtype)
        _, part_g = _dispatch_topl_run(
            codes, gids_rows, rowbias, qluts, scale, cellterm, plan, qkeep,
            topl=pool_l, impl=impl, chunk=chunk)
        return _rescore_dispatch(codes, rowbias, luts, cellterm, plan,
                                 qkeep, pos, part_g, topl)
    return _dispatch_topl_run(
        codes, gids_rows, rowbias, luts.astype(jnp.float32), None, cellterm,
        plan, qkeep, topl=topl, impl=impl, chunk=chunk)


def rerank_gather_dist(cand_codes: jax.Array, queries: jax.Array,
                       table: jax.Array, *, impl: str = "pallas",
                       block_l: int | None = None,
                       block_q: int | None = None,
                       chunk_l: int | None = None) -> jax.Array:
    """Streaming stage 2 for table-decodable quantizers: exact d1
    reconstruction distances over per-query candidate lists WITHOUT
    materializing the (Q, L, D) reconstruction tensor.

    cand_codes (Q, L, M) integer candidate codes, queries (Q, D) f32,
    table (M, K, D) f32 with ``recon = sum_m table[m, code_m]``
    (``ref.decode_with_table``) -> d1 (Q, L) f32, bit-identical to the
    materialized oracle ``ref.rerank_gather_dist_ref``.

      impl="pallas"  the fused gather-decode-distance kernel: code tiles
                     stream HBM->VMEM, sub-codewords gathered from the
                     VMEM-resident table, ||q - recon||^2 reduced per
                     (query, candidate) tile.
      impl="xla"     chunked ``lax.scan`` over L; the always-available
                     fallback with O(Q * chunk_l * D) peak.
    """
    q, l, _ = cand_codes.shape
    d = queries.shape[1]
    if impl == "xla":
        cfg = tune.best_config("rerank_gather_dist", "xla", l=l, q=q, d=d)
        cl = cfg["chunk_l"] if chunk_l is None else chunk_l
        return rerank_gather_dist_chunked_xla(
            cand_codes, queries.astype(jnp.float32),
            table.astype(jnp.float32), chunk_l=cl)
    if impl == "pallas":
        cfg = tune.best_config("rerank_gather_dist", "pallas", l=l, q=q, d=d)
        bq = tune.align(q, cap=cfg["block_q"] if block_q is None else block_q)
        bl = tune.align(l, cap=cfg["block_l"] if block_l is None else block_l)
        padded_codes, _ = _pad_to(cand_codes, bq, axis=0)
        padded_codes, _ = _pad_to(padded_codes, bl, axis=1)
        padded_queries, _ = _pad_to(queries.astype(jnp.float32), bq, axis=0)
        out = rerank_gather_dist_pallas(
            padded_codes, padded_queries, table.astype(jnp.float32),
            block_l=bl, block_q=bq, interpret=_interpret())
        return out[:q, :l]
    raise ValueError(
        f"unknown impl for rerank_gather_dist: {impl!r} (the streaming "
        "stage 2 has 'pallas' and 'xla' paths; backends without the "
        "streaming capabilities use the materialized vmap reranker)")


def unq_encode(heads: jax.Array, codebooks: jax.Array, *, impl: str = "pallas",
               block_b: int | None = None) -> jax.Array:
    """codes[b, m] = argmax_k <heads[b,m], codebooks[m,k]>.

    heads (B, M, d_c), codebooks (M, K, d_c) -> (B, M) int32.
    """
    if impl == "xla":
        return ref.unq_encode_ref(heads, codebooks)
    if impl == "pallas":
        cfg = tune.best_config("unq_encode", "pallas", b=heads.shape[0])
        bb = cfg["block_b"] if block_b is None else block_b
        padded, b = _pad_to(heads, bb, axis=0)
        out = unq_encode_pallas(padded, codebooks, block_b=bb,
                                interpret=_interpret())
        return out[:b]
    raise ValueError(f"unknown impl: {impl!r}")


def kv_adc_attention(q, k_codes, v_codes, k_books, v_books, length=None, *,
                     impl: str = "xla"):
    """Compressed-KV decode attention (see ref.kv_adc_attention_ref)."""
    if impl == "xla":
        return ref.kv_adc_attention_ref(q, k_codes, v_codes, k_books, v_books,
                                        length)
    raise ValueError(f"unknown impl: {impl!r}")
