"""Pluggable stage-1 candidate generation (the streaming engine's index
face).

``Index.search`` and ``ShardedIndex`` delegate stage 1 — d2 scores over
the compressed database plus per-query top-L — to a ``CandidateGenerator``
resolved through the scan-backend registry, instead of hardcoding one
"full (Q, N) matrix + lax.top_k" implementation:

  * ``StreamingTopL``     backends with the ``streaming_topl`` capability
                          (pallas: fused scan+top-L kernel; xla: chunked
                          scan + incremental merge). Peak memory O(Q*L);
                          the (Q, N) score matrix is never materialized.
  * ``MaterializedTopL``  the classic full-matrix scan for backends
                          without a streaming path (onehot), kept as the
                          A/B reference.

Both produce bit-identical (score, index) results — the streaming paths
reproduce ``lax.top_k`` tie semantics exactly — so generator selection is
purely a memory/performance decision, never a quality one.

Two bias streams flow through every path:

  * ``bias``  (N,)   per-point terms (RVQ's ||decode(code)||^2);
  * ``qbias`` (Q, N) per-(query, point) terms — the lowering target of
    the filtered-search API (``filter_mask`` becomes 0 / +inf).

``gather_topl`` is the IVF face of the same engines: each query scores a
per-query slot list (a padded ragged concatenation of inverted lists)
instead of the whole database. Streaming backends ride
``ops.adc_gather_topl`` (fused kernel / chunked gather-scan); the
materialized path scores the full buffer with its own formulation and
gathers the slots — which keeps IVF-at-full-probe bit-identical to flat
search PER BACKEND, reassociated onehot reductions included.

``dispatch_topl`` is the cell-batched face of the same IVF stage 1
(backends with the ``dispatch_topl`` capability): instead of per-query
slot lists, the device router (``repro.index.dispatch``) batches the
queries probing each cell and ``ops.adc_dispatch_topl`` streams every
probed cell's contiguous code range exactly once — same scores, same tie
semantics, no host-side plan. ``supports_dispatch`` is the capability
gate ``IVFIndex.search`` resolves its default against.
"""
from __future__ import annotations

import abc
import functools
import threading

import jax
import jax.numpy as jnp

from repro.index.backend import backend_supports, resolve_scan_backend
from repro.kernels import ops

_IMAX = jnp.iinfo(jnp.int32).max


class Stage1Meter:
    """Process-wide count of the query blocks the fused stage-1 kernel
    launched (grid rows: each sweeps every code once). ``repro.serve``
    metrics read deltas of ``count`` since their ``reset()``, as they
    read ``dispatch.OVERFLOWS``. Launches traced inside an outer jit
    (the device-placed sharded path) are not counted."""

    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0

    def record(self, blocks: int) -> None:
        with self._lock:
            self.count += blocks


#: process-wide stage-1 meter — ``StreamingTopL.topl`` records here
STAGE1 = Stage1Meter()


class CandidateGenerator(abc.ABC):
    """Stage 1 strategy: codes + per-query LUTs -> top-L candidates."""

    #: whether this generator allocates the full (Q, N) score matrix
    materializes_scores: bool

    def __init__(self, impl: str):
        self.impl = impl                # concrete kernels.ops impl string

    @abc.abstractmethod
    def topl(self, codes, luts, bias, *, topl: int, qbias=None,
             lut_dtype: str = "float32", overfetch: int = 1):
        """codes (N, M), luts (Q, M, K), bias None | (N,), qbias
        None | (Q, N) -> (scores, indices), each (Q, min(topl, N)),
        sorted closest-first with ties broken toward the smaller
        database index. ``lut_dtype``/``overfetch`` select the
        reduced-precision pool scan + exact re-score (streaming engines
        only — gate on the backend's ``quantized_lut`` capability)."""

    @abc.abstractmethod
    def gather_topl(self, codes, rows, gids, luts, rowbias, *, topl: int,
                    lut_dtype: str = "float32", overfetch: int = 1):
        """Gathered (IVF) stage 1: codes (N, M) buffer, rows/gids (Q, W)
        per-query slot plan (gids ascending per row, ``_IMAX`` pads),
        rowbias None | (Q, W) -> (scores, global ids), each
        (Q, min(topl, W)), sorted by (score asc, gid asc); +inf entries
        carry the canonical ``_IMAX`` id."""

    def dispatch_topl(self, codes, gids_rows, rowbias, luts, cellterm,
                      plan, *, topl: int, qkeep=None, chunk=None, pos=None,
                      lut_dtype: str = "float32", overfetch: int = 1):
        """Cell-batched (MoE-routed) IVF stage 1: codes (N, M)
        cell-grouped buffer, gids_rows (N,) row -> global id, rowbias
        None | (N,) per-row bias, luts (Q, M, K), cellterm (E+1, cap)
        per-(routed cell, slot) bias, plan a
        ``repro.index.dispatch.DispatchPlan``, qkeep None | (Q, N) keep
        stream -> per-cell partial pools ((E+1, cap, L) scores / global
        ids) for ``dispatch.combine_pools``. Only backends declaring the
        ``dispatch_topl`` capability implement it. ``chunk`` must be the
        tile width the plan was routed with (``Routing.chunk``; None
        re-resolves the shared tuner entry); ``pos`` is the (n_ids,)
        global id -> buffer row inverse the quantized re-score needs."""
        raise NotImplementedError(
            f"{type(self).__name__} has no cell-batched dispatch face; "
            "gate callers on supports_dispatch(backend)")

    def query_block(self, n: int, q: int, *, topl: int,
                    lut_dtype: str = "float32",
                    overfetch: int = 1) -> int | None:
        """Queries the fused scan kernel scores per block for a ``topl``
        call of Q queries over N codes; None when no fused kernel runs."""
        return None

    def __repr__(self):
        return f"{type(self).__name__}(impl={self.impl!r})"


@functools.partial(jax.jit, static_argnames=("topl", "impl"))
def _materialized_topl(codes, luts, bias, qbias, *, topl: int, impl: str):
    scores = ops.adc_scan_batch(codes, luts, impl=impl)    # (Q, N)
    if bias is not None:
        scores = scores + bias[None, :]
    if qbias is not None:
        scores = scores + qbias
    neg, idx = jax.lax.top_k(-scores, topl)
    return -neg, idx


@functools.partial(jax.jit, static_argnames=("topl", "impl"))
def _materialized_gather_topl(codes, rows, gids, luts, rowbias, *,
                              topl: int, impl: str):
    """Full-buffer scan (this backend's own formulation — identical bits
    to its flat scan) + slot gather + top-L. The (Q, N) matrix exists, as
    it does on every materialized path."""
    scores = ops.adc_scan_batch(codes, luts, impl=impl)    # (Q, N)
    picked = jnp.take_along_axis(scores, rows, axis=1)     # (Q, W)
    if rowbias is not None:
        picked = picked + rowbias
    picked = jnp.where(gids == _IMAX, jnp.inf, picked)
    gids = jnp.where(jnp.isposinf(picked), _IMAX, gids)
    neg, pos = jax.lax.top_k(-picked, topl)
    return -neg, jnp.take_along_axis(gids, pos, axis=1)


class MaterializedTopL(CandidateGenerator):
    """Full (Q, N) score matrix + ``lax.top_k`` (the pre-streaming stage 1;
    reference semantics, O(Q*N) peak memory)."""

    materializes_scores = True

    def _check_exact(self, lut_dtype: str, overfetch: int):
        if lut_dtype != "float32" or overfetch != 1:
            raise ValueError(
                f"{type(self).__name__} ({self.impl!r}) has no quantized-"
                "LUT path — its formulation IS the materialized f32 "
                "matrix; gate callers on the 'quantized_lut' capability")

    def topl(self, codes, luts, bias, *, topl: int, qbias=None,
             lut_dtype: str = "float32", overfetch: int = 1):
        self._check_exact(lut_dtype, overfetch)
        return _materialized_topl(codes, luts, bias, qbias,
                                  topl=min(topl, codes.shape[0]),
                                  impl=self.impl)

    def gather_topl(self, codes, rows, gids, luts, rowbias, *, topl: int,
                    lut_dtype: str = "float32", overfetch: int = 1):
        self._check_exact(lut_dtype, overfetch)
        return _materialized_gather_topl(
            codes, rows, gids, luts, rowbias,
            topl=min(topl, rows.shape[1]), impl=self.impl)


class StreamingTopL(CandidateGenerator):
    """Streaming scan+top-L (``ops.adc_scan_topl``): O(Q*L) peak memory,
    bit-identical to ``MaterializedTopL`` including tie resolution."""

    materializes_scores = False

    def query_block(self, n: int, q: int, *, topl: int,
                    lut_dtype: str = "float32",
                    overfetch: int = 1) -> int | None:
        if self.impl != "pallas":
            return None
        return ops.scan_topl_query_block(n, q, topl=topl,
                                         lut_dtype=lut_dtype,
                                         overfetch=overfetch)

    def topl(self, codes, luts, bias, *, topl: int, qbias=None,
             lut_dtype: str = "float32", overfetch: int = 1):
        q = luts.shape[0]
        bq = self.query_block(codes.shape[0], q, topl=topl,
                              lut_dtype=lut_dtype, overfetch=overfetch)
        if bq is not None:
            STAGE1.record(-(-q // bq))
        return ops.adc_scan_topl(codes, luts, topl=topl, bias=bias,
                                 qbias=qbias, impl=self.impl,
                                 lut_dtype=lut_dtype, overfetch=overfetch)

    def gather_topl(self, codes, rows, gids, luts, rowbias, *, topl: int,
                    lut_dtype: str = "float32", overfetch: int = 1):
        return ops.adc_gather_topl(codes, rows, gids, luts, topl=topl,
                                   rowbias=rowbias, impl=self.impl,
                                   lut_dtype=lut_dtype, overfetch=overfetch)

    def dispatch_topl(self, codes, gids_rows, rowbias, luts, cellterm,
                      plan, *, topl: int, qkeep=None, chunk=None, pos=None,
                      lut_dtype: str = "float32", overfetch: int = 1):
        return ops.adc_dispatch_topl(codes, gids_rows, rowbias, luts,
                                     cellterm, plan, topl=topl,
                                     qkeep=qkeep, impl=self.impl,
                                     chunk=chunk, pos=pos,
                                     lut_dtype=lut_dtype,
                                     overfetch=overfetch)


def candidate_generator_for(backend: str | None = "auto") -> CandidateGenerator:
    """Resolve an index's backend request to a stage-1 generator.

    The backend name resolves through the scan registry; backends that
    declare the ``streaming_topl`` capability get the streaming engine,
    everything else the materialized fallback. Within the streaming
    engine, ``fused_topl`` selects the kernel flavor: backends declaring
    it run the single fused scan+top-L kernel (the ``pallas`` dispatch
    target), the rest the chunked ``lax.scan`` composition (``xla``).
    """
    impl = resolve_scan_backend(backend)
    if backend_supports(impl, "streaming_topl"):
        return StreamingTopL(
            "pallas" if backend_supports(impl, "fused_topl") else "xla")
    return MaterializedTopL(impl)


def supports_dispatch(backend: str | None = "auto") -> bool:
    """True when the resolved backend has the cell-batched dispatch face
    (``dispatch_topl`` capability) — what ``IVFIndex.search`` keys its
    dispatch-vs-padded default on."""
    return backend_supports(resolve_scan_backend(backend), "dispatch_topl")


def merge_topl(scores, ids, topl: int):
    """Exact lexicographic (score asc, id asc) top-L over an UNSORTED
    candidate pool (Q, P) — the cross-shard merge for IVF pools, whose
    per-shard global-id ranges interleave (cell-grouped shards), so the
    positional tie-break of a plain ``lax.top_k`` would be wrong.

    Two stable argsorts: ascending id first, then stable-by-score — among
    equal scores the id order survives, which is exactly the flat-search
    tie-break. +inf entries are canonicalized to id ``_IMAX`` first.
    """
    ids = jnp.where(jnp.isposinf(scores), _IMAX, ids)
    order1 = jnp.argsort(ids, axis=1, stable=True)
    s = jnp.take_along_axis(scores, order1, axis=1)
    g = jnp.take_along_axis(ids, order1, axis=1)
    order2 = jnp.argsort(s, axis=1, stable=True)
    topl = min(topl, scores.shape[1])
    return (jnp.take_along_axis(s, order2, axis=1)[:, :topl],
            jnp.take_along_axis(g, order2, axis=1)[:, :topl])
