"""IVF coarse partitioning in front of the streaming scan (the paper's
billion-scale regime: DEEP1B-class corpora are never scanned linearly).

``IVFIndex`` wraps any trained ``Index`` quantizer behind the same
train/add/search/save/load surface and prepends a k-means coarse
quantizer with ``nlist`` cells:

  * ``train`` runs an ORDERED pipeline (``core.training.TrainStage``):
    the coarse k-means fits FIRST, then the wrapped quantizer — in
    residual mode on ``x - centroid(x)`` instead of ``x``;
  * ``add`` encodes as usual, assigns each vector to its nearest
    centroid, and keeps the codes in ONE contiguous cell-grouped buffer
    with CSR offsets (``_offsets[c]:_offsets[c+1]`` is cell c's inverted
    list) — no per-cell Python lists, so the probed cells of a whole
    query batch concatenate into a single padded (Q, W) ragged plan;
  * ``search`` ranks centroids per query, takes the top ``nprobe``
    cells, and feeds the stage-1 engine through one of two faces:

      - **dispatch** (backends with the ``dispatch_topl`` capability,
        the default there): the MoE-style device router
        (``repro.index.dispatch``) turns the (Q, nprobe) probe matrix +
        CSR offsets into dense per-cell query batches ON DEVICE — no
        host numpy, no padded-plan transfer — ``ops.adc_dispatch_topl``
        streams each probed cell's contiguous code range exactly once
        for all co-probing queries, and ``dispatch.combine_pools``
        scatter-merges the per-cell partial top-Ls back to per-query
        pools. A ``dispatch_capacity`` factor bounds the per-cell batch;
        overflow falls back LOUDLY to the padded path (never silent
        candidate drops).
      - **padded** (the retained oracle/control, and the fallback):
        builds the ragged plan (slot -> buffer row + global id + cell,
        sorted by global id, pads marked ``_IMAX``) host-side from the
        CSR offsets and hands it to the gathered face
        (``CandidateGenerator.gather_topl`` -> ``ops.adc_gather_topl``).

    Fused Pallas kernel, chunked xla, or the materialized control —
    all faces bit-identical, tie semantics included.

Exactness: a slot's score is computed with the same per-point math as the
flat scan (same left-to-right codebook chain / one-hot contraction on the
same code row), the plan lists every point exactly once at
``nprobe == nlist`` (cells partition the database), and every path breaks
score ties toward the smaller GLOBAL id — so full-probe IVF search is
bit-identical to flat search, scores and indices, on every backend. The
same plan carries the per-point bias stream (RVQ norms) and the lowered
``filter_mask`` (+inf drops a slot), so filtered IVF search composes for
free.

Residual encoding (IVFADC, ``residual=True`` / the ``Residual`` factory
token): vectors are encoded as ``x - centroid(x)``, so codebook capacity
is spent on the much-lower-variance residual distribution. Every point's
implied reconstruction becomes ``centroid + decode(code)`` and the d2
scan needs a distance correction; for table-decodable quantizers it is
EXACT and rides the existing bias streams, with no kernel changes::

    ||q - (c + d)||^2 = ||q - d||^2          the uncorrected LUT scan
                      + 2<c, d>              per-ROW cross term: computed
                                             at add time from the per-cell
                                             cross-LUT (2 * coarse @ table)
                                             and folded into the per-point
                                             ``bias`` stream
                      + ||c||^2 - 2<q, c>    per-(query, cell) term: the
                                             coarse-distance matrix already
                                             computed for probing, gathered
                                             per plan slot into the
                                             ``rowbias`` stream

Decoder quantizers (UNQ) have no exact LUT decomposition; their stage-1
scores stay a proxy (LUTs built from the query residualized against its
top-1 probed centroid, so the encoder sees residual-scale inputs) and
stage 2 reranks with the exact ``centroid + decode`` reconstruction
through ``rerank.ResidualRerank``. Plain (non-residual) indexes take
exactly the pre-residual code paths — bitwise unchanged.

Stage 2 translates candidate global ids to buffer rows through the stored
permutation and rides the streaming rerank engine (fused table kernel /
cross-query dedup) exactly like a flat index; residual indexes resolve a
``ResidualRerank`` wrapper that reconstructs ``centroid + decode(code)``
(see ``repro.index.rerank``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import annotate_function

from repro.core.baselines import kmeans
from repro.index import base
from repro.index.candidates import candidate_generator_for, supports_dispatch

_IMAX = np.iinfo(np.int32).max

#: "use the index's own dispatch_capacity" sentinel for the per-call
#: override (None is meaningful: it means lossless routing)
_INDEX_CAPACITY = object()


#: rows assigned and encoded per step of ``IVFIndex.add``
ADD_BLOCK = 1 << 17


def _cat(parts):
    """Concatenate per-block arrays (None stays None, one block as is)."""
    if parts[0] is None:
        return None
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _plan_width(w: int) -> int:
    """Pad the ragged plan width to a small ladder so repeated searches
    with similar probe sizes reuse one compiled scan: multiples of 8 up to
    128, of 128 up to 1024, then eight steps an octave (at most an eighth
    of the width is padding)."""
    if w <= 8:
        return 8
    if w <= 128:
        return -(-w // 8) * 8
    if w <= 1024:
        return -(-w // 128) * 128
    step = 1 << (w - 1).bit_length() - 4
    return -(-w // step) * step


class IVFIndex(base.Index):
    """Inverted-file index over any wrapped quantizer (see module doc)."""

    kind = "ivf"

    def __init__(self, dim: int, *, inner: base.Index, nlist: int,
                 nprobe: int = 8, rerank: int = 0, backend: str = "auto",
                 residual: bool = False,
                 dispatch_capacity: float | None = None):
        super().__init__(dim, rerank=rerank, backend=backend)
        if nlist < 1:
            raise ValueError(f"nlist must be >= 1, got {nlist}")
        if inner.ntotal:
            raise ValueError("wrap an EMPTY quantizer index; add vectors "
                             "through the IVFIndex so they are partitioned")
        self.inner = inner
        self.nlist = nlist
        self.nprobe = nprobe
        self.residual = bool(residual)
        #: MoE capacity factor for the dispatch face: None = lossless
        #: (capacity covers the true max per-cell batch); a float bounds
        #: slots per cell at ~factor * Q * nprobe / E, with capacity
        #: overflow falling back loudly to the padded plan
        self.dispatch_capacity = dispatch_capacity
        self.coarse: jax.Array | None = None     # (nlist, dim) centroids
        # cell-grouped buffer state (parallel to self._codes / self._bias)
        self._ids_np: np.ndarray | None = None   # (N,) buffer row -> gid
        self._cells_np: np.ndarray | None = None  # (N,) buffer row -> cell
        self._cells_dev: jax.Array | None = None  # device copy of the above
        self._offsets: np.ndarray | None = None  # (nlist + 1,) CSR
        self._offsets_dev: jax.Array | None = None  # device CSR (router)
        self._ids_dev: jax.Array | None = None   # device row -> gid
        self._pos_dev: jax.Array | None = None   # (N,) gid -> buffer row
        self._plan_cache: dict = {}              # padded-plan memo
        # residual-mode caches (dropped by _invalidate_caches)
        self._crosslut = None                    # (nlist, M, K) cross-LUT
        self._res_table = None                   # (M+1, K', D) stage-2 table

    # -- delegated quantizer primitives ------------------------------------

    @property
    def is_trained(self) -> bool:
        return self.inner.is_trained and self.coarse is not None

    def _train_stages(self):
        """The ordered IVF pipeline: coarse k-means MUST finish before the
        wrapped quantizer trains — in residual mode the coarse stage
        transforms the training vectors into residuals for it."""
        from repro.core.training import TrainStage
        return [TrainStage("coarse", self._fit_coarse),
                TrainStage(self.inner.kind, self._fit_inner)]

    def _fit_coarse(self, xs, *, coarse_iters: int = 10,
                    coarse_seed: int = 0, **_):
        """Fit the k-means coarse partition; in residual mode return
        ``x - centroid(x)`` for the downstream quantizer stage."""
        xs = jnp.asarray(xs)        # the coarse fit runs on device anyway
        self.coarse = kmeans(jax.random.PRNGKey(coarse_seed), xs,
                             self.nlist, iters=coarse_iters)
        if not self.residual:
            return None
        cells = jnp.argmin(self._coarse_dists(xs), axis=1)
        return xs - jnp.take(self.coarse, cells, axis=0)

    def _fit_inner(self, xs, **kw):
        """Fit the wrapped quantizer (on residuals when residual mode is
        on). The coarse stage's own keyword parameters — read off its
        signature, so the two can never drift — are filtered out;
        everything else passes through (UNQ treats every leftover kwarg
        as a TrainConfig field, so leaking one would raise)."""
        import inspect
        coarse_params = {
            name for name, p in
            inspect.signature(self._fit_coarse).parameters.items()
            if p.kind is p.KEYWORD_ONLY}
        inner_kw = {k: v for k, v in kw.items() if k not in coarse_params}
        self.inner.train(xs, **inner_kw)

    def _encode(self, xs) -> jax.Array:
        self.inner.backend = self.backend       # keep encode impl in sync
        return self.inner._encode(xs)

    def _build_luts(self, queries) -> jax.Array:
        return self.inner._build_luts(queries)

    def _reconstruct(self, codes) -> jax.Array:
        return self.inner._reconstruct(codes)

    def _build_decode_table(self):
        return self.inner._build_decode_table()

    def _encode_bias(self, codes):
        return self.inner._encode_bias(codes)

    def _invalidate_caches(self) -> None:
        super()._invalidate_caches()
        self.inner._invalidate_caches()
        self._assign_fn = None
        self._crosslut = None
        self._res_table = None
        self._plan_cache = {}

    # -- residual machinery --------------------------------------------------

    @property
    def _exact_residual(self) -> bool:
        """True when residual mode can apply the EXACT stage-1 distance
        correction: the wrapped quantizer is table-decodable, so
        ``||q - (c + d)||^2`` decomposes onto the existing bias streams
        (see module doc). Decoder quantizers (UNQ) stay a proxy."""
        return self.residual and self.inner._decode_table() is not None

    def _crosstable(self) -> jax.Array:
        """(nlist, M, K) per-cell cross-LUT for the residual correction:
        ``crosslut[c, m, k] = 2 * <coarse[c], table[m, k]>``, so the
        per-row cross term ``2<c, decode(code)>`` is an M-term chained
        LUT sum over the row's own code — the same access pattern as the
        d2 scan itself."""
        if self._crosslut is None:
            with jax.ensure_compile_time_eval():
                table = self.inner._decode_table().astype(jnp.float32)
                self._crosslut = 2.0 * jnp.einsum(
                    "mkd,cd->cmk", table, self.coarse.astype(jnp.float32))
        return self._crosslut

    def _cross_bias(self, codes, cells) -> jax.Array:
        """Per-row residual cross term ``2<centroid(row), decode(code)>``
        (n,) f32, accumulated left-to-right over M like ``adc_scan_ref``
        so every path shares one association."""
        lut = self._crosstable()                           # (C, M, K)
        m_idx = jnp.arange(lut.shape[1])[None, :]          # (1, M)
        g = lut[jnp.asarray(cells)[:, None], m_idx,
                codes.astype(jnp.int32)]                   # (n, M)
        acc = g[:, 0]
        for m in range(1, lut.shape[1]):
            acc = acc + g[:, m]
        return acc

    def _residual_table(self) -> jax.Array:
        """(M+1, K', D) stage-2 decode table with the coarse centroids
        appended as an extra face (K' = max(K, nlist), zero-padded).
        Extending each candidate's code row with its cell id makes the
        UNCHANGED table rerank engine reconstruct
        ``decode(code) + centroid`` exactly: the centroid face is the
        last chained add, bit-identical to adding the centroid to
        ``ref.decode_with_table`` output.

        The inner-face padding is only free when ``nlist <= K`` —
        ``reranker_for`` routes ``nlist > K`` residual indexes through
        the dedup reranker instead, so in practice K' == max(K, nlist)
        never inflates the resident table on the path that uses it."""
        if self._res_table is None:
            with jax.ensure_compile_time_eval():
                table = self.inner._decode_table().astype(jnp.float32)
                m, k, d = table.shape
                kk = max(k, self.nlist)
                faces = jnp.zeros((m + 1, kk, d), jnp.float32)
                faces = faces.at[:m, :k, :].set(table)
                faces = faces.at[m, :self.nlist, :].set(
                    self.coarse.astype(jnp.float32))
                self._res_table = faces
        return self._res_table

    def reconstruct_rows(self, rows) -> jax.Array:
        """(n,) buffer rows -> (n, dim) implied reconstructions:
        ``decode(code)`` plus, in residual mode, the row's coarse
        centroid — the materialized oracle the residual search paths are
        validated against."""
        rows = jnp.asarray(rows, jnp.int32)
        recon = self._reconstruct(jnp.take(self._codes, rows, axis=0))
        if self.residual:
            cells = jnp.take(self._cells_dev, rows)
            recon = recon + jnp.take(self.coarse, cells, axis=0)
        return recon

    # -- cell-grouped database ---------------------------------------------

    def _coarse_dists(self, xs):
        """(n, dim) -> (n, nlist) squared distances up to a per-row
        constant (||x||^2 dropped: rankings are all we use — and the
        dropped term is per-QUERY, so the same matrix doubles as the
        residual correction's per-(query, cell) bias)."""
        if getattr(self, "_assign_fn", None) is None:
            self._assign_fn = jax.jit(
                lambda x, c: jnp.sum(c * c, axis=1)[None, :]
                - 2.0 * jnp.dot(x, c.T, precision=jax.lax.Precision.HIGHEST))
        return base.per_query(lambda x: self._assign_fn(x, self.coarse),
                              jnp.asarray(xs))

    def _probe_with_dists(self, queries, nprobe: int):
        """Clamped per-query top-``nprobe`` probe PLUS the coarse-distance
        matrix it was ranked by — the single implementation behind
        ``probe_cells``, ``search`` and the sharded IVF stage 1 (the
        matrix doubles as the residual correction's per-(query, cell)
        bias, so callers never recompute it). The probe stays a DEVICE
        array: the dispatch face routes it without a host round-trip;
        the padded plan builder converts at its own edge."""
        cd = self._coarse_dists(jnp.asarray(queries))
        nprobe = max(1, min(int(nprobe), self.nlist))
        _, cells = jax.lax.top_k(-cd, nprobe)
        return cells, cd

    def probe_cells(self, queries, nprobe: int) -> np.ndarray:
        """Per-query top-``nprobe`` coarse cells, (Q, nprobe) int32
        (closest centroid first)."""
        return np.asarray(self._probe_with_dists(queries, nprobe)[0])

    def _resolve_nprobe(self, nprobe, num_queries: int):
        """Normalize a ``search`` nprobe request to (probe width,
        per-query probe lengths).

        ``None`` -> the index default; an int -> that width (lengths
        ``None``); a (Q,) int vector — the serving fan-in, where each
        coalesced request carries its own probe budget — probes at the
        MAX width and returns the clipped lengths so each query's excess
        probe slots are masked out of its plan/pool. Because
        ``lax.top_k`` prefixes are exact, query i's first ``nprobe_i``
        probed cells at width P are exactly its solo top-``nprobe_i`` —
        the per-query results stay bit-identical to searching alone. A
        uniform vector collapses to its scalar (no masking needed)."""
        if nprobe is None:
            return max(1, min(int(self.nprobe), self.nlist)), None
        if np.ndim(nprobe) == 0:
            return max(1, min(int(nprobe), self.nlist)), None
        lens = np.asarray(nprobe)
        if lens.ndim != 1 or lens.shape[0] != num_queries:
            raise ValueError(
                f"per-query nprobe must be a ({num_queries},) int vector, "
                f"got shape {lens.shape}")
        lens = np.clip(lens.astype(np.int32), 1, self.nlist)
        width = int(lens.max())
        if int(lens.min()) == width:
            return width, None
        return width, lens

    def _stage1_luts(self, queries, probe: np.ndarray) -> jax.Array:
        """Per-query stage-1 score tables. Residual DECODER quantizers
        (no decode table, so no exact correction) residualize the query
        against its top-1 probed centroid first, keeping the encoder on
        residual-scale inputs; every other configuration scores raw
        queries (residual table quantizers correct through the bias
        streams instead)."""
        if self.residual and self.inner._decode_table() is None:
            anchor = jnp.take(self.coarse, jnp.asarray(probe[:, 0]), axis=0)
            return self._build_luts(queries - anchor)
        return self._build_luts(queries)

    def reset(self) -> None:
        super().reset()
        self._ids_np = None
        self._cells_np = None
        self._cells_dev = None
        self._offsets = None
        self._offsets_dev = None
        self._ids_dev = None
        self._pos_dev = None
        self._plan_cache = {}

    def with_codes(self, codes, bias=None):
        raise NotImplementedError(
            "IVFIndex code buffers are cell-grouped with id/offset side "
            "state; use add()/reset() instead of with_codes views")

    def subset(self, n: int):
        raise NotImplementedError(
            "nested-subset views are not defined for cell-grouped IVF "
            "buffers; build a flat index for subset scaling studies")

    def add(self, xs) -> "IVFIndex":
        """Encode, assign to coarse cells, and regroup the contiguous
        buffer (stable by cell) so every inverted list stays one CSR
        slice. Global ids are assignment order, exactly like a flat
        ``add`` — searches return them, not buffer positions.

        Residual mode encodes ``x - centroid(x)`` (assignment happens
        first) and, for table-decodable quantizers, folds the per-row
        cross term ``2<c, decode(code)>`` into the per-point bias stream
        alongside any quantizer-native bias (RVQ norms)."""
        if not self.is_trained:
            raise RuntimeError(f"{type(self).__name__}.add before train()")
        xs = jnp.asarray(xs)
        n = xs.shape[0]
        # assign + encode ADD_BLOCK rows at a time: the (rows, nlist)
        # coarse distances and the quantizer's (rows, M, K) encode
        # distances stay bounded however large the add
        parts = [self._assign_encode(xs[s:s + ADD_BLOCK])
                 for s in range(0, max(n, 1), ADD_BLOCK)]
        cells_dev, codes, bias = (_cat(list(v)) for v in zip(*parts))
        cells = np.asarray(cells_dev, np.int32)
        old_n = self.ntotal
        ids = np.arange(old_n, old_n + n, dtype=np.int32)
        if self._codes is not None:
            codes = jnp.concatenate([self._codes, codes], axis=0)
            if bias is not None:
                bias = jnp.concatenate([self._bias, bias], axis=0)
            cells = np.concatenate([self._cells_np, cells])
            ids = np.concatenate([self._ids_np, ids])
        order = np.argsort(cells, kind="stable")
        order_dev = jnp.asarray(order, jnp.int32)
        self._codes = jnp.take(codes, order_dev, axis=0)
        self._bias = None if bias is None else jnp.take(bias, order_dev)
        self._cells_np = cells[order]
        self._cells_dev = jnp.asarray(self._cells_np)
        self._ids_np = ids[order]
        counts = np.bincount(self._cells_np, minlength=self.nlist)
        self._offsets = np.concatenate(
            [[0], np.cumsum(counts)]).astype(np.int64)
        self._offsets_dev = jnp.asarray(self._offsets, jnp.int32)
        self._ids_dev = jnp.asarray(self._ids_np)
        pos = np.empty(self.ntotal, np.int32)
        pos[self._ids_np] = np.arange(self.ntotal, dtype=np.int32)
        self._pos_dev = jnp.asarray(pos)
        self._plan_cache = {}
        return self

    def _assign_encode(self, xs):
        """One block of ``add``: (cells (n,) i32, codes (n, M), bias (n,)
        f32 or None) for the rows of xs."""
        n = xs.shape[0]
        cells = jnp.argmin(self._coarse_dists(xs), axis=1).astype(jnp.int32)
        enc_in = xs - jnp.take(self.coarse, cells, axis=0) \
            if self.residual else xs
        bucket = self._encode_bucket(n)
        xp = jnp.pad(enc_in, ((0, bucket - n), (0, 0))) if bucket != n \
            else enc_in
        codes = self._encode(xp)[:n]
        bias = self._encode_bias(codes)
        if self._exact_residual:
            cross = self._cross_bias(codes, cells)
            bias = cross if bias is None else bias + cross
        return cells, codes, bias

    # -- probing -------------------------------------------------------------

    @functools.partial(annotate_function, name="ivf.probe_plan")
    def _probe_plan(self, probe: np.ndarray, cell_range=None,
                    row_offset: int = 0, probe_lens=None):
        """Concatenate the CSR inverted lists of each query's probed cells
        into one padded ragged plan.

        probe (Q, P) int32 cell ids; ``cell_range=(lo, hi)`` restricts to
        a shard's owned cells (rows shifted by ``row_offset`` so they
        index the shard-local buffer slice); ``probe_lens`` (Q,) int32
        keeps only each query's first ``probe_lens[q]`` probe columns —
        the per-query nprobe fan-in (``_resolve_nprobe``), masked exactly
        like unowned cells so a query's plan is identical to probing at
        its own width alone.

        Returns (rows, gids, cells): np.int32 (Q, W) each — buffer rows
        to score, the global id behind each slot, and the slot's coarse
        cell (the residual correction's bias key), SORTED ascending by
        gid per query (pads last, gid = _IMAX, row = 0, cell = 0) — the
        plan contract of ``ops.adc_gather_topl``.

        Plans are memoized on the (probe bytes, shape, cell_range,
        row_offset, probe_lens bytes) fingerprint — repeated query
        batches (bench loops, the retained oracle path next to dispatch)
        stop rebuilding identical numpy plans. The cache dies with any
        buffer mutation (add / load / reset).
        """
        probe = np.asarray(probe, np.int32)
        key = (probe.tobytes(), probe.shape, cell_range, row_offset,
               None if probe_lens is None else probe_lens.tobytes())
        hit = self._plan_cache.get(key)
        if hit is not None:
            return hit
        off = self._offsets
        lens = (off[1:] - off[:-1]).astype(np.int64)
        q = probe.shape[0]
        cell_lens = lens[probe]                       # (Q, P)
        if cell_range is not None:
            owned = (probe >= cell_range[0]) & (probe < cell_range[1])
            cell_lens = np.where(owned, cell_lens, 0)
        if probe_lens is not None:
            within = np.arange(probe.shape[1])[None, :] < \
                np.asarray(probe_lens)[:, None]
            cell_lens = np.where(within, cell_lens, 0)
        starts = off[probe]                           # (Q, P)
        totals = cell_lens.sum(axis=1)                # (Q,)
        w = _plan_width(int(max(totals.max(initial=0), 1)))
        rows = np.zeros((q, w), np.int32)
        gids = np.full((q, w), _IMAX, np.int32)
        cells = np.zeros((q, w), np.int32)
        # flat ragged expansion of every (query, cell) list in one shot:
        # slot -> buffer row via the classic repeat/cumsum trick
        counts = cell_lens.ravel()
        total = int(counts.sum())
        if total:
            grp_starts = np.repeat(starts.ravel(), counts)
            within = np.arange(total, dtype=np.int64) - np.repeat(
                np.cumsum(counts) - counts, counts)
            flat_rows = (grp_starts + within).astype(np.int64)
            qidx = np.repeat(np.arange(q), totals)
            col = np.arange(total, dtype=np.int64) - np.repeat(
                np.cumsum(totals) - totals, totals)
            flat_gids = self._ids_np[flat_rows]
            # ONE flat stable sort by (query, gid) replaces the old padded
            # per-row argsort: lexsort's primary key (qidx, already
            # nondecreasing) confines the permutation to each query's own
            # span, so scattering through (qidx, col) lands each query's
            # slots gid-ascending — identical plans, ~W/avg-fill less sort
            # work and no (Q, W) take_along_axis passes
            perm = np.lexsort((flat_gids, qidx))
            sorted_rows = flat_rows[perm]
            rows[qidx, col] = (sorted_rows - row_offset).astype(np.int32)
            gids[qidx, col] = flat_gids[perm]
            cells[qidx, col] = self._cells_np[sorted_rows]
        plan = (rows, gids, cells)
        if len(self._plan_cache) >= 8:          # tiny FIFO: bench/serve
            self._plan_cache.pop(next(iter(self._plan_cache)))  # loops only
        self._plan_cache[key] = plan
        return plan

    def _plan_rowbias(self, rows, gids, shard_bias, filter_mask,
                      num_queries: int, slot_cells=None, cell_bias=None):
        """The per-slot additive stream for a plan: the gathered per-point
        bias (RVQ norms, residual cross terms — from the buffer/shard the
        rows index), plus the residual correction's per-(query, cell)
        term (``cell_bias`` (Q, nlist) gathered at each slot's cell),
        with the lowered filter mask applied last (+inf = filtered out,
        keyed by GLOBAL id). Returns (Q, W) f32 or None when there is
        nothing to add."""
        if shard_bias is None and filter_mask is None and cell_bias is None:
            return None
        rowbias = jnp.take(shard_bias, rows) if shard_bias is not None \
            else jnp.zeros(rows.shape, jnp.float32)
        if cell_bias is not None:
            rowbias = rowbias + jnp.take_along_axis(
                jnp.asarray(cell_bias), jnp.asarray(slot_cells), axis=1)
        if filter_mask is not None:
            mask = jnp.asarray(filter_mask, bool)
            safe = jnp.where(gids == _IMAX, 0, gids)
            if mask.ndim == 1:
                if mask.shape != (self.ntotal,):
                    raise ValueError(
                        f"filter_mask shape {mask.shape} != "
                        f"({self.ntotal},)")
                keep = jnp.take(mask, safe)
            else:
                if mask.shape != (num_queries, self.ntotal):
                    raise ValueError(
                        f"filter_mask shape {mask.shape} != "
                        f"({num_queries}, {self.ntotal})")
                keep = jnp.take_along_axis(mask, safe, axis=1)
            rowbias = jnp.where(keep, rowbias, jnp.inf)
        return rowbias

    # -- dispatch (cell-batched) stage 1 -------------------------------------

    def _dispatch_streams(self, routing, num_queries: int, filter_mask,
                          cell_bias, row_range=None):
        """The dispatch face's bias streams for one routed (sub)buffer:
        (ids, rowbias, qkeep, cellterm).

        ids (n,) row -> global id for the ``row_range`` slice (the whole
        buffer by default; a shard's rows under the sharded face);
        rowbias (n,) the per-point stream with any (N,) filter folded to
        +inf (keyed by GLOBAL id, like ``_plan_rowbias``); qkeep (Q, n)
        0/1 stream for per-(query, point) filters; cellterm (E+1, cap)
        the residual correction's per-(query, cell) term gathered at
        each routed slot. Composition order matches ``_plan_rowbias``
        exactly — score + (rowbias + cellterm), keep-mask applied last —
        which is what keeps dispatch bit-identical to the padded path.
        """
        lo, hi = row_range if row_range is not None else (0, self.ntotal)
        ids = self._ids_dev[lo:hi]
        rowbias = None if self._bias is None else self._bias[lo:hi]
        qkeep = None
        if filter_mask is not None:
            mask = jnp.asarray(filter_mask, bool)
            if mask.ndim == 1:
                if mask.shape != (self.ntotal,):
                    raise ValueError(
                        f"filter_mask shape {mask.shape} != "
                        f"({self.ntotal},)")
                keep = jnp.take(mask, ids)
                base = rowbias if rowbias is not None \
                    else jnp.zeros(ids.shape, jnp.float32)
                rowbias = jnp.where(keep, base, jnp.inf)
            else:
                if mask.shape != (num_queries, self.ntotal):
                    raise ValueError(
                        f"filter_mask shape {mask.shape} != "
                        f"({num_queries}, {self.ntotal})")
                qkeep = jnp.take(mask, ids, axis=1).astype(jnp.float32)
        qidx = routing.plan.qidx
        if cell_bias is not None:
            safe_q = jnp.clip(qidx, 0, num_queries - 1)
            safe_c = jnp.clip(routing.cell_of, 0, self.nlist - 1)
            cellterm = jnp.where(
                qidx >= 0, jnp.asarray(cell_bias)[safe_q, safe_c[:, None]],
                0.0).astype(jnp.float32)
        else:
            cellterm = jnp.zeros(qidx.shape, jnp.float32)
        return ids, rowbias, qkeep, cellterm

    def _dispatch_pool(self, queries, probe, cd, filter_mask, topl: int,
                       lut_dtype: str = "float32", overfetch: int = 1,
                       probe_lens=None, capacity=_INDEX_CAPACITY):
        """Stage 1 through the cell-batched dispatch face: route the
        probe on device, stream every probed cell once, scatter-merge the
        per-cell partials. Returns the (d2, global ids) pool —
        bit-identical to the padded gathered plan — or None when the
        capacity factor overflows (the caller's padded fallback: dropped
        probes could hide true top-L candidates; the overflow is counted
        and rate-limit-warned through ``dispatch.OVERFLOWS``).

        ``probe_lens`` (Q,) masks each query's probe columns past its own
        nprobe out of the scatter-merge (``comb_e = -1`` is the router's
        dropped-pair sentinel, so the excess cells never enter that
        query's pool) — the dispatch half of the per-query nprobe
        fan-in. ``capacity`` overrides the index's ``dispatch_capacity``
        for this call (the serving load-shed knob)."""
        from repro.index import dispatch as dsp
        if capacity is _INDEX_CAPACITY:
            capacity = self.dispatch_capacity
        routing, stats = dsp.build_dispatch(
            probe, self._offsets_dev, capacity_factor=capacity)
        if routing is None:
            dsp.OVERFLOWS.record(
                f"IVF dispatch capacity overflow: the busiest probed cell "
                f"batches {stats[1]} queries, over the "
                f"dispatch_capacity={capacity} budget for "
                f"{stats[0]} routed cells; falling back to the padded "
                "gathered plan for this batch")
            return None
        q = queries.shape[0]
        cell_bias = cd if self._exact_residual else None
        _, rowbias, qkeep, cellterm = self._dispatch_streams(
            routing, q, filter_mask, cell_bias)
        luts = self._stage1_luts(queries, probe)
        gen = candidate_generator_for(self.backend)
        part_s, part_g = gen.dispatch_topl(
            self._codes, self._ids_dev, rowbias, luts, cellterm,
            routing.plan, topl=topl, qkeep=qkeep, chunk=routing.chunk,
            pos=self._pos_dev, lut_dtype=lut_dtype, overfetch=overfetch)
        comb_e = routing.comb_e
        if probe_lens is not None:
            within = jnp.arange(probe.shape[1])[None, :] < \
                jnp.asarray(probe_lens)[:, None]
            comb_e = jnp.where(within, comb_e, -1)
        return dsp.combine_pools(part_s, part_g, comb_e,
                                 routing.comb_slot, topl=topl)

    # -- search --------------------------------------------------------------

    def search(self, queries, k: int, *, nprobe=None,
               use_rerank: bool | None = None, use_d2: bool = True,
               filter_mask=None, use_dispatch: bool | None = None,
               dispatch_capacity=_INDEX_CAPACITY,
               lut_dtype: str = "float32", overfetch: int = 1):
        """Probed two-stage search (same contract as ``Index.search`` plus
        ``nprobe``). Slots the probe misses simply never enter the pool;
        when the probed pool holds fewer than k points the tail is
        reported as (distance=+inf, index=-1).

        ``nprobe`` may be a scalar or a (Q,) int vector — one probe width
        per query, the serving fan-in for coalesced requests with
        different accuracy budgets. Per-query widths probe at the batch
        max and mask each query's excess cells out of its pool, so row i
        is bit-identical to searching that query alone with nprobe[i].

        ``use_dispatch`` pins stage 1 to the cell-batched dispatch face
        (True) or the padded gathered plan (False); the default resolves
        per backend via the ``dispatch_topl`` capability. Both faces are
        bit-identical — the knob is a perf/control choice, never a
        quality one. ``dispatch_capacity`` overrides the index's own
        capacity factor for this call (None = lossless routing): the
        load-shed knob a serving loop can tighten under pressure without
        mutating the shared index.

        ``lut_dtype``/``overfetch`` opt stage 1 into the reduced-precision
        pool scan + exact f32 re-score (``Index.search`` docstring) on
        either face; backends without the ``quantized_lut`` capability
        reject the request."""
        if self.ntotal == 0:
            raise RuntimeError("search on an empty index (call add first)")
        self._check_quantized_request(lut_dtype, overfetch)
        queries = jnp.asarray(queries)
        if use_rerank is None:
            use_rerank = self.rerank > 0
        if use_rerank and self.rerank <= 0:
            raise ValueError(
                f"{type(self).__name__} has no rerank budget (rerank=0); "
                "set index.rerank or pass use_rerank=False")
        if not use_d2:
            if filter_mask is not None:
                raise ValueError(
                    "filter_mask is not supported with use_d2=False")
            return self._exhaustive_rerank_topk(queries, k)
        if use_dispatch is None:
            use_dispatch = supports_dispatch(self.backend)
        elif use_dispatch and not supports_dispatch(self.backend):
            raise ValueError(
                f"use_dispatch=True but backend {self.backend!r} does not "
                "declare the dispatch_topl capability; use the padded "
                "path (use_dispatch=False) or an xla/pallas backend")
        nprobe_w, probe_lens = self._resolve_nprobe(nprobe, queries.shape[0])
        probe, cd = self._probe_with_dists(queries, nprobe_w)
        if use_dispatch:
            pool = self._dispatch_pool(
                queries, probe, cd, filter_mask,
                topl=self.rerank if use_rerank else k,
                lut_dtype=lut_dtype, overfetch=overfetch,
                probe_lens=probe_lens, capacity=dispatch_capacity)
            if pool is not None:
                return self._finish_pool(queries, pool[0], pool[1], k,
                                         use_rerank=use_rerank)
        rows_np, gids_np, cells_np = self._probe_plan(
            probe, probe_lens=probe_lens)
        rows = jnp.asarray(rows_np)
        gids = jnp.asarray(gids_np)
        exact = self._exact_residual
        rowbias = self._plan_rowbias(
            rows, gids, self._bias, filter_mask, queries.shape[0],
            slot_cells=cells_np if exact else None,
            cell_bias=cd if exact else None)
        luts = self._stage1_luts(queries, probe)
        topl = min(self.rerank if use_rerank else k, rows.shape[1])
        gen = candidate_generator_for(self.backend)
        d2, ids = gen.gather_topl(self._codes, rows, gids, luts, rowbias,
                                  topl=topl, lut_dtype=lut_dtype,
                                  overfetch=overfetch)
        return self._finish_pool(queries, d2, ids, k,
                                 use_rerank=use_rerank)

    def _finish_pool(self, queries, d2, ids, k: int, *, use_rerank: bool):
        """Shared tail over a gathered candidate pool (also used by
        ShardedIndex on the merged per-shard pools): optional stage-2
        rerank through the streaming engine, +inf pads reported as -1,
        and the result brought to EXACTLY the flat-search width
        min(k, ntotal) — padded with the documented (+inf, -1) tail when
        the probed pool is narrower, truncated when a pool face over-
        allocated (the dispatch scatter-merge can be P * L wide; every
        global id enters a pool at most once, so columns past ntotal are
        always pads)."""
        if not use_rerank:
            kk = min(k, d2.shape[1])
            d = d2[:, :kk]
            i = jnp.where(jnp.isposinf(d), -1, ids[:, :kk])
        else:
            valid = jnp.isfinite(d2)
            rows_cand = jnp.take(self._pos_dev, jnp.where(valid, ids, 0))
            d1 = self._rerank_distances(queries, rows_cand)
            d1 = jnp.where(valid, d1, jnp.inf)
            kk = min(k, d1.shape[1])
            neg, order = jax.lax.top_k(-d1, kk)
            d = -neg
            i = jnp.take_along_axis(ids, order, axis=1)
            i = jnp.where(jnp.isposinf(d), -1, i)
        width = min(k, self.ntotal)
        if d.shape[1] < width:
            pad = width - d.shape[1]
            d = jnp.pad(d, ((0, 0), (0, pad)), constant_values=jnp.inf)
            i = jnp.pad(i, ((0, 0), (0, pad)), constant_values=-1)
        elif d.shape[1] > width:
            d, i = d[:, :width], i[:, :width]
        return d, i

    def _exhaustive_rerank_topk(self, queries, k: int):
        """``use_d2=False`` over the ADD-ORDER view of the buffer, so tie
        resolution matches a flat index over the same vectors. Residual
        mode reconstructs ``decode(code) + centroid`` per chunk (the
        cells ride the scan payload alongside the codes)."""
        from repro.index.rerank import exhaustive_topk
        codes_add = jnp.take(self._codes, self._pos_dev, axis=0)
        if not self.residual:
            if self._exhaustive_fn is None:
                self._exhaustive_fn = jax.jit(
                    functools.partial(exhaustive_topk, self._reconstruct),
                    static_argnames=("k",))
            return self._exhaustive_fn(codes_add, queries,
                                       k=min(k, self.ntotal))
        cells_add = jnp.take(self._cells_dev, self._pos_dev)
        if self._exhaustive_fn is None:
            def recon(payload):
                codes, cells = payload
                return self._reconstruct(codes) + jnp.take(
                    self.coarse, cells, axis=0)

            self._exhaustive_fn = jax.jit(
                functools.partial(exhaustive_topk, recon),
                static_argnames=("k",))
        return self._exhaustive_fn((codes_add, cells_add), queries,
                                   k=min(k, self.ntotal))

    # -- persistence ---------------------------------------------------------

    def _tree(self):
        m = self._codes.shape[1] if self._codes is not None else \
            self.inner._tree()["codes"].shape[1]
        return {
            "inner": self.inner._tree(),
            "coarse": self.coarse,
            "codes": self._codes if self._codes is not None
            else jnp.zeros((0, m), jnp.uint8),
            "ids": jnp.asarray(self._ids_np, jnp.int32)
            if self._ids_np is not None else jnp.zeros((0,), jnp.int32),
            "cells": jnp.asarray(self._cells_np, jnp.int32)
            if self._cells_np is not None else jnp.zeros((0,), jnp.int32),
            "norms": self._bias if self._bias is not None
            else jnp.zeros((0,), jnp.float32),
        }

    def _metadata(self) -> dict:
        return {"dim": self.dim, "nlist": self.nlist, "nprobe": self.nprobe,
                "rerank": self.rerank, "backend": self.backend,
                "ntotal": self.ntotal, "residual": self.residual,
                "dispatch_capacity": self.dispatch_capacity,
                "has_bias": self._bias is not None,
                "inner_kind": self.inner.kind,
                "inner_meta": self.inner._metadata()}

    @classmethod
    def _empty_from_metadata(cls, meta: dict) -> "IVFIndex":
        inner = base._KINDS[meta["inner_kind"]]._empty_from_metadata(
            meta["inner_meta"])
        inner._codes = None                      # codes live on the wrapper
        index = cls(meta["dim"], inner=inner, nlist=meta["nlist"],
                    nprobe=meta["nprobe"], rerank=meta["rerank"],
                    backend=meta["backend"],
                    residual=meta.get("residual", False),
                    dispatch_capacity=meta.get("dispatch_capacity"))
        n = meta["ntotal"]
        m = inner._tree()["codes"].shape[1]
        index.coarse = jnp.zeros((meta["nlist"], meta["dim"]), jnp.float32)
        index._codes = jnp.zeros((n, m), jnp.uint8)
        index._ids_np = np.zeros(n, np.int32)
        index._cells_np = np.zeros(n, np.int32)
        if meta["has_bias"]:
            index._bias = jnp.zeros((n,), jnp.float32)
        return index

    def _set_tree(self, tree) -> None:
        self.inner._set_tree(tree["inner"])
        self.inner._codes = None
        self.coarse = tree["coarse"]
        n = int(tree["codes"].shape[0])
        self._codes = tree["codes"] if n else None
        self._bias = tree["norms"] if tree["norms"].shape[0] else None
        if n:
            self._ids_np = np.asarray(tree["ids"])
            self._cells_np = np.asarray(tree["cells"])
            self._cells_dev = jnp.asarray(self._cells_np)
            counts = np.bincount(self._cells_np, minlength=self.nlist)
            self._offsets = np.concatenate(
                [[0], np.cumsum(counts)]).astype(np.int64)
            self._offsets_dev = jnp.asarray(self._offsets, jnp.int32)
            self._ids_dev = jnp.asarray(self._ids_np)
            pos = np.empty(n, np.int32)
            pos[self._ids_np] = np.arange(n, dtype=np.int32)
            self._pos_dev = jnp.asarray(pos)
            self._plan_cache = {}
        else:
            self.reset()
        self._invalidate_caches()

    def __repr__(self):
        return (f"IVFIndex({self.inner!r}, nlist={self.nlist}, "
                f"nprobe={self.nprobe}, residual={self.residual}, "
                f"ntotal={self.ntotal}, rerank={self.rerank}, "
                f"backend={self.backend!r})")
