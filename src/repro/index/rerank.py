"""Pluggable stage-2 reranking (the streaming engine's index face).

``Index._rerank_distances`` — exact reconstruction distances d1 (paper
Eq. 7) over each query's stage-1 candidate list — delegates to a
``Reranker`` resolved through the scan-backend registry, mirroring how
stage 1 resolves a ``CandidateGenerator``:

  * ``TableRerank``  table-decodable quantizers (PQ / OPQ / RVQ:
                     ``recon = sum_m table[m, code_m]``) on streaming
                     backends. Backends declaring ``fused_rerank`` run
                     the fused gather-decode-distance Pallas kernel;
                     the rest the chunked ``lax.scan`` fallback. Peak
                     reconstruction memory O(Q * block * D) — the
                     (Q, L, D) tensor is never materialized.
  * ``DedupRerank``  decoder quantizers (UNQ's neural decoder) on
                     streaming backends: cross-query candidate dedup.
                     Candidate pools overlap heavily across queries, so
                     the (Q*L) pool is flattened, each UNIQUE code row is
                     decoded once in fixed-size batches, and distances
                     are gathered back per (query, candidate) in chunks —
                     decoder FLOPs and activation memory are bounded by
                     the decode chunk, and the held reconstruction shrinks
                     from (Q*L, D) to (U, D), U = #unique <= min(Q*L, N).
  * ``VmapRerank``   the classic per-query gather + decode + reduce vmap,
                     materializing (Q, L, D). Kept as the A/B oracle and
                     used by backends without streaming capabilities
                     (onehot).
  * ``ResidualRerank`` wraps any of the three for residual IVF indexes
                     (IVFADC): candidates reconstruct as
                     ``centroid + decode(code)`` — an extra centroid face
                     on the decode table for the table engine, centroid
                     adds on the deduped unique rows for decoder
                     quantizers.

All paths produce bit-identical d1 (and therefore identical final
(distance, index) rankings) — verified by tests/test_rerank.py and
tests/test_residual.py — so reranker selection is purely a
memory/performance decision, never a quality one.

``exhaustive_rerank_topk`` is the ``use_d2=False`` ablation re-shaped the
same way: a ``lax.scan`` over database chunks, each decoded ONCE for all
queries (the decode is query-independent), merged into a running (Q, k)
heap with the same lexicographic tie semantics as the stage-1 streaming
engine — the (Q, N, D) reconstruction of the old path never exists.
"""
from __future__ import annotations

import abc
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.index.backend import backend_supports, resolve_scan_backend
from repro.index.base import DECODE_CHUNK
from repro.kernels import ops, ref

_IMAX = jnp.iinfo(jnp.int32).max

#: L-chunk for the gathered-distance scan (shared with the table path)
DEDUP_DIST_CHUNK = ops.DEFAULT_RERANK_CHUNK_L


class RerankMeter:
    """Process-wide work counters of the dedup reranker: candidate slots
    (Q * L entries of the stage-1 pools), the unique rows among them, and
    the rows actually decoded once the unique rows are padded to the
    compile ladder. ``repro.serve`` metrics read deltas of ``snapshot()``
    since their ``reset()``, as they read ``dispatch.OVERFLOWS``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = (0, 0, 0)

    def record(self, slots: int, unique: int, decoded: int) -> None:
        with self._lock:
            a, b, c = self._counts
            self._counts = (a + slots, b + unique, c + decoded)

    def snapshot(self) -> tuple[int, int, int]:
        """(slots, unique rows, decoded rows) since process start."""
        return self._counts


#: process-wide rerank meter — ``DedupRerank.distances`` records here
RERANK = RerankMeter()


class Reranker(abc.ABC):
    """Stage-2 strategy: queries + candidate ids -> exact d1 distances."""

    #: whether this reranker materializes the (Q, L, D) reconstruction
    materializes_recon: bool

    @abc.abstractmethod
    def distances(self, index, queries, cand) -> jax.Array:
        """queries (Q, D), cand (Q, L) int32 rows of ``index.codes`` ->
        d1 (Q, L) f32 with d1[q, l] = ||queries[q] - recon(cand[q, l])||^2."""

    def __repr__(self):
        return f"{type(self).__name__}()"


class VmapRerank(Reranker):
    """Per-query ``codes[c_idx]`` gather + decode + reduce under ``vmap``
    (the pre-streaming stage 2; reference semantics, O(Q*L*D) peak)."""

    materializes_recon = True

    def distances(self, index, queries, cand):
        return index._rerank_distances_vmap(queries, cand)


class TableRerank(Reranker):
    """Streaming stage 2 for table-decodable quantizers
    (``ops.rerank_gather_dist``): candidate codes are gathered as uint8
    (L*M bytes per query, ~100x smaller than the float reconstruction)
    and the decode+distance runs tile-by-tile — fused Pallas kernel or
    chunked xla, bit-identical to ``VmapRerank``."""

    materializes_recon = False

    def __init__(self, impl: str):
        self.impl = impl                # concrete kernels.ops impl string

    def distances(self, index, queries, cand):
        cand_codes = jnp.take(index.codes, cand, axis=0)     # (Q, L, M) u8
        return ops.rerank_gather_dist(
            cand_codes, jnp.asarray(queries, jnp.float32),
            index._decode_table(), impl=self.impl)

    def __repr__(self):
        return f"TableRerank(impl={self.impl!r})"


@jax.jit
def _sq_dist(recon, queries):
    return ref.sq_dist(recon, queries)


@functools.partial(jax.jit, static_argnames=("chunk_l",))
def _gathered_dist_chunked(recon_u, queries, inv, *, chunk_l: int):
    """d[q, l] = ||queries[q] - recon_u[inv[q, l]]||^2 via a ``lax.scan``
    over (Q, chunk_l) column chunks — peak gathered-reconstruction memory
    O(Q * chunk_l * D) instead of O(Q * L * D)."""
    q, l = inv.shape
    pad = (-l) % chunk_l
    inv_c = jnp.moveaxis(
        jnp.pad(inv, ((0, 0), (0, pad))).reshape(q, -1, chunk_l), 1, 0)

    def step(_, idx):
        recon = recon_u[idx]                                 # (Q, c, D)
        return None, ref.sq_dist(recon, queries[:, None, :])

    _, ds = jax.lax.scan(step, None, inv_c)                  # (nc, Q, c)
    return jnp.moveaxis(ds, 0, 1).reshape(q, -1)[:, :l]


class DedupRerank(Reranker):
    """Cross-query candidate dedup for decoder quantizers (UNQ).

    Stage-1 pools overlap heavily across queries (popular database points
    appear in many top-L lists), so decoding ``codes[cand]`` per query
    repeats the expensive neural decode for every duplicate. This path
    runs host-side dedup on the concrete candidate matrix (search is
    eager), decodes each unique code row ONCE (``Index.decode_rows``), and
    gathers the decoded rows back per (query, candidate) in chunks.

    Memory: decoder activations are bounded by ``base.DECODE_CHUNK`` rows
    and the gathered distance tiles by ``dist_chunk``; the held
    reconstruction is the deduped (U, D) matrix, U = #unique <=
    min(Q*L, ntotal) — the savings over the vmap path's (Q, L, D) scale
    exactly with the pool overlap (worst case, fully disjoint pools, they
    are the same size).

    Exactness: every row is decoded by the same compiled program as in
    ``VmapRerank`` (``Index.decode_rows``) and reduced in the same
    ``ref.sq_dist`` order, so d1 matches it bit-for-bit.

    ``add_centroid=True`` is the residual-IVF variant (resolved through
    ``ResidualRerank``): dedup runs over unique BUFFER ROWS — a row pins
    both its code and its coarse cell — and each unique reconstruction
    gains its row's centroid, so d1 is computed against
    ``decode(code) + centroid`` exactly.
    """

    materializes_recon = False

    def __init__(self, dist_chunk: int = DEDUP_DIST_CHUNK,
                 add_centroid: bool = False):
        self.dist_chunk = dist_chunk
        self.add_centroid = add_centroid

    def distances(self, index, queries, cand):
        cand = jnp.asarray(cand)
        q, l = cand.shape
        # the host waits here for stage 1 to finish on the device
        with TraceAnnotation("rerank.unique"):
            uniq, inv = np.unique(np.asarray(cand), return_inverse=True)
        # pad the unique rows to a ladder (whole decode chunks, eight
        # steps an octave) so pools of similar size share compiled shapes
        u = uniq.size
        step = max(DECODE_CHUNK, 1 << max(0, (u - 1).bit_length() - 3))
        padded = -(-u // step) * step
        RERANK.record(q * l, u, padded)
        rows_u = jnp.asarray(np.pad(uniq, (0, padded - u)), jnp.int32)
        recon_u = index.decode_rows(jnp.take(index.codes, rows_u, axis=0))
        if self.add_centroid:
            recon_u = recon_u + jnp.take(
                index.coarse, jnp.take(index._cells_dev, rows_u), axis=0)
        return _gathered_dist_chunked(
            recon_u, jnp.asarray(queries, jnp.float32),
            jnp.asarray(inv.reshape(q, l), jnp.int32),
            chunk_l=self.dist_chunk)


class ResidualRerank(Reranker):
    """Stage 2 for residual IVF indexes (IVFADC): every candidate's
    implied reconstruction is ``centroid + decode(code)``, so d1 must be
    computed against it — the wrapped reranker's ``||q - decode(code)||^2``
    would rank residual decodes as if they were points.

    Wraps whichever reranker the backend would resolve for the wrapped
    quantizer and reroutes it:

      * ``TableRerank`` — candidate code rows are EXTENDED with their
        coarse cell id and scored against the index's residual decode
        table (``IVFIndex._residual_table``: the inner table plus one
        centroid face), so the UNCHANGED fused/chunked table engine
        reconstructs ``decode(code) + centroid`` bit-exactly — the
        centroid face is simply the last chained add;
      * ``DedupRerank`` — cross-query dedup over unique buffer rows with
        ``add_centroid=True`` (a row pins code AND cell);
      * ``VmapRerank`` — the materialized per-query oracle with the
        centroid added to each gathered reconstruction (the A/B ground
        truth of the two above, used by the onehot backend).

    All three produce bit-identical d1 (``decode`` is shared and the
    centroid add is a single exact fp add per row), extending the
    engine's "reranker selection is never a quality decision" contract
    to residual indexes.
    """

    def __init__(self, inner: Reranker):
        self.inner = inner
        self.materializes_recon = inner.materializes_recon
        if isinstance(inner, DedupRerank):
            # a residual wrap ALWAYS adds centroids — enforced here so the
            # natural composition ResidualRerank(DedupRerank()) cannot
            # silently rank against bare residual decodes
            inner.add_centroid = True

    def distances(self, index, queries, cand):
        if isinstance(self.inner, TableRerank) and index.nlist <= 256:
            # this route only resolves when nlist <= K <= 256 (uint8
            # codes), so the cell column fits uint8 too — the extended
            # tensor keeps the table engine's uint8 streaming footprint
            # (a direct construction with nlist > 256 falls through to
            # the materialized residual oracle instead of wrapping)
            cand_codes = jnp.take(index.codes, cand, axis=0)  # (Q, L, M)
            cand_cells = jnp.take(index._cells_dev,
                                  cand)[..., None].astype(cand_codes.dtype)
            codes_ext = jnp.concatenate([cand_codes, cand_cells], axis=-1)
            return ops.rerank_gather_dist(
                codes_ext, jnp.asarray(queries, jnp.float32),
                index._residual_table(), impl=self.inner.impl)
        if isinstance(self.inner, DedupRerank):
            return self.inner.distances(index, queries, cand)
        return self._vmap_residual(index, queries, cand)

    @staticmethod
    def _vmap_residual(index, queries, cand):
        """Materialized residual oracle: every candidate row gathered,
        decoded (``Index.decode_rows``) and given its centroid, the
        (Q, L, D) reconstruction built, d1 reduced by ``ref.sq_dist``."""
        cand = jnp.asarray(cand)
        rows = cand.reshape(-1)
        recon = index.decode_rows(jnp.take(index.codes, rows, axis=0)) \
            + jnp.take(index.coarse, jnp.take(index._cells_dev, rows),
                       axis=0)
        return _sq_dist(recon.reshape(cand.shape + (index.dim,)),
                        jnp.asarray(queries, jnp.float32)[:, None, :])

    def __repr__(self):
        return f"ResidualRerank({self.inner!r})"


def reranker_for(index) -> Reranker:
    """Resolve an index's backend request to a stage-2 reranker.

    Streaming-capable backends (``streaming_topl``) get the streaming
    engine — the fused kernel where the backend declares ``fused_rerank``
    and the index is table-decodable, the chunked xla path otherwise for
    tables, cross-query dedup for decoder quantizers. Backends without a
    streaming path (onehot) keep the materialized vmap reference.
    Residual IVF indexes get their resolved reranker wrapped in
    ``ResidualRerank`` so candidates reconstruct as centroid + decode.
    One residual-specific override: the extended-table route pads every
    decode-table face to max(K, nlist), so when ``nlist > K`` (large IVF
    over small codebooks) it would inflate the resident table and the
    per-face contraction work — those indexes rerank through the dedup
    route instead (bit-identical d1, per the engine contract).
    """
    residual = bool(getattr(index, "residual", False))
    impl = resolve_scan_backend(index.backend)
    table = index._decode_table()
    if not backend_supports(impl, "streaming_topl"):
        inner: Reranker = VmapRerank()
    elif table is not None and not (residual and
                                    index.nlist > table.shape[1]):
        inner = TableRerank(
            "pallas" if backend_supports(impl, "fused_rerank") else "xla")
    else:
        inner = DedupRerank()
    return ResidualRerank(inner) if residual else inner


# ---------------------------------------------------------------------------
# use_d2=False: chunked exhaustive rerank over the whole database
# ---------------------------------------------------------------------------

def exhaustive_topk(reconstruct_fn, payload, queries, *, k: int,
                    chunk_n: int = 2048):
    """Exact-d1 top-k over ALL codes without a (Q, N, D) reconstruction:
    a ``lax.scan`` over chunk_n-row payload chunks, each decoded ONCE for
    every query, carrying a (Q, k) heap merged with ``lax.top_k``.

    ``payload`` is whatever ``reconstruct_fn`` needs per point: the
    (N, M) code matrix for plain quantizers, or any pytree of N-leading
    arrays — residual IVF threads ``(codes, cells)`` so each chunk can
    reconstruct ``decode(code) + centroid``. The scan chunks every leaf
    along the leading axis together.

    Tie semantics are exactly ``lax.top_k`` over the full (Q, N) d1
    matrix: the carry is sorted by (distance, index) and every chunk
    entry has a larger global index than every carried entry, so top_k's
    positional tie-break IS the ascending-index tie-break.

    Trace-time function: callers jit it (with ``reconstruct_fn`` closed
    over) so the decode+distance fuse per chunk.
    """
    n = jax.tree_util.tree_leaves(payload)[0].shape[0]
    q = queries.shape[0]
    k = min(k, n)
    pad = (-n) % chunk_n

    def chunked(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, chunk_n) + a.shape[1:])

    payload_c = jax.tree_util.tree_map(chunked, payload)
    num_chunks = (n + pad) // chunk_n
    starts = (jnp.arange(num_chunks) * chunk_n).astype(jnp.int32)

    def step(carry, inp):
        vals, idx = carry                                    # (Q, k) x2
        chunk, start = inp
        recon = reconstruct_fn(chunk)                        # (c, D), once
        d = ref.sq_dist(recon[None, :, :], queries[:, None, :])  # (Q, c)
        gids = start + jnp.arange(chunk_n, dtype=jnp.int32)
        d = jnp.where(gids[None, :] < n, d, jnp.inf)
        cand_s = jnp.concatenate([vals, d], axis=1)
        cand_g = jnp.concatenate(
            [idx, jnp.broadcast_to(gids[None, :], (q, chunk_n))], axis=1)
        neg, pos = jax.lax.top_k(-cand_s, k)
        return (-neg, jnp.take_along_axis(cand_g, pos, axis=1)), None

    init = (jnp.full((q, k), jnp.inf, jnp.float32),
            jnp.full((q, k), _IMAX, jnp.int32))
    (vals, idx), _ = jax.lax.scan(step, init, (payload_c, starts))
    return vals, idx
