"""The ``Index`` protocol: a FAISS-style object that owns a compressed
database you can query (paper §3.3 generalized over quantizers).

Lifecycle::

    index = index_factory("UNQ8x256,Rerank500", dim=96)
    index.train(train_vectors)        # fit the quantizer
    index.add(base_vectors)           # compress + append to the database
    D, I = index.search(queries, k)   # two-stage compressed-domain search
    index.save(path); index = Index.load(path)

Every implementation reduces to four primitives (train / encode / LUT
build / reconstruct); the two-stage search itself — batched multi-query
ADC scan (d2, Eq. 8), top-L candidates, decoder rerank (d1, Eq. 7) — is
implemented ONCE here and shared by UNQ and every shallow baseline, which
is what makes paper-style method comparisons a single loop.

Stage 1 is delegated to a ``CandidateGenerator`` resolved through the
scan-backend registry (``repro.index.candidates``): backends declaring the
``streaming_topl`` capability run the streaming scan+top-L engine — the
(Q, N) score matrix is never materialized — and the rest fall back to the
classic full-matrix scan. Every Index subclass gets the right path with no
per-class branching, and per-point score biases flow through either.

Stage 2 is delegated the same way to a ``Reranker``
(``repro.index.rerank``): table-decodable quantizers stream through the
fused gather-decode-distance kernel (``fused_rerank`` capability) or its
chunked fallback, decoder quantizers (UNQ) go through cross-query
candidate dedup, and the ``use_d2=False`` exhaustive-rerank ablation
chunks over the database — the (Q, L, D) / (Q, N, D) reconstruction
tensors of the classic paths never exist, and every path is bit-identical
to the materialized vmap oracle kept as the A/B reference.
"""
from __future__ import annotations

import abc
import functools
import json
import pathlib
from typing import Any

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.checkpoint.manager import load_pytree, save_pytree
from repro.index.backend import backend_supports, resolve_scan_backend
from repro.index.candidates import candidate_generator_for
from repro.kernels import tune

# kind -> Index subclass, populated by __init_subclass__
_KINDS: dict[str, type["Index"]] = {}


#: rows per call of the jitted stage-2 decoder (``Index.decode_rows``)
DECODE_CHUNK = 512


def per_query(fn, *rows) -> jax.Array:
    """``fn(*rows)`` for the per-query dots (score tables, coarse
    distances) over row-aligned arrays, computed on rows padded to whole
    8-row tiles and sliced back.

    This is part of the search contract, not a rounding fix: a query's
    results must not depend on the batch it rides in, and the TPU
    compiler lowers a dot with fewer rows than one f32 sublane tile (8)
    as a different program, with another accumulation order, from one
    with whole tiles. Served batches are whole tiles already (query
    buckets are multiples of 8), so padding the rest gives a query
    searched alone the lowering it gets inside a batch."""
    n = rows[0].shape[0]
    pad = (-n) % 8
    if pad == 0:
        return fn(*rows)
    return fn(*[jnp.pad(r, ((0, pad),) + ((0, 0),) * (r.ndim - 1))
                for r in rows])[:n]


class Index(abc.ABC):
    """Abstract compressed-database index (see module docstring)."""

    kind: str = "abstract"

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        if cls.kind != "abstract":
            _KINDS[cls.kind] = cls

    #: encode-batch ladder: ``add`` pads inputs up to the next bucket so
    #: differently-sized chunks reuse one encoder compilation (the ladder
    #: then continues in 8192-row multiples)
    ENCODE_BUCKETS = (256, 1024, 4096, 8192)

    def __init__(self, dim: int, *, rerank: int = 0, backend: str = "auto"):
        self.dim = dim
        self.rerank = rerank          # L: stage-2 candidates (0 = ADC only)
        self.backend = backend        # scan backend name or "auto"
        self._codes: jax.Array | None = None     # (N, M) uint8
        self._bias: jax.Array | None = None      # (N,) f32 or None
        self._decode_fn = None                   # cached jitted chunk decode
        self._exhaustive_fn = None               # cached jitted use_d2=False
        self._table_cache = None                 # cached decode table

    # -- database state ----------------------------------------------------

    @property
    def ntotal(self) -> int:
        return 0 if self._codes is None else int(self._codes.shape[0])

    @property
    def codes(self) -> jax.Array | None:
        """The compressed database, (ntotal, M) uint8."""
        return self._codes

    def result_width(self, k: int) -> int:
        """Number of result columns ``search(queries, k)`` returns:
        ``min(k, ntotal)``. The serving fan-in slices a coalesced
        k_max-wide batch back to each request's own width with this, so
        a request's rows are bit-identical to searching it alone — the
        exact sorted top-k is prefix-stable (its first j columns never
        depend on how many more were asked for)."""
        return min(k, self.ntotal)

    @property
    def bias(self) -> jax.Array | None:
        """Per-point additive d2 score term, (ntotal,) f32, or None.

        Additive quantizers (RVQ) store ||decode(code)||^2 here — the
        standard extra-4-bytes trick. Public so wrappers (``ShardedIndex``,
        custom shard stores) never reach into private attributes."""
        return self._bias

    @property
    @abc.abstractmethod
    def is_trained(self) -> bool:
        ...

    def reset(self) -> None:
        """Drop the database (the trained quantizer is kept)."""
        self._codes = None
        self._bias = None

    def with_codes(self, codes, bias=None) -> "Index":
        """A shallow view over the same trained quantizer with a different
        code matrix (shard construction, external code stores)."""
        import copy
        clone = copy.copy(self)
        clone._codes = None if codes is None else jnp.asarray(codes)
        clone._bias = bias
        return clone

    def subset(self, n: int) -> "Index":
        """View over the first ``n`` database entries (nested-subset
        scaling studies, paper Tables 3/4)."""
        return self.with_codes(
            self._codes[:n],
            None if self._bias is None else self._bias[:n])

    # -- quantizer primitives (implementation-specific) --------------------

    def train(self, xs, **kw) -> "Index":
        """Fit the index on (n, dim) training vectors. Returns self.

        Training is an ORDERED pipeline of ``TrainStage``s
        (``core.training.run_train_pipeline``): plain quantizers declare
        the single ``_fit_quantizer`` stage, composite indexes sequence
        theirs — ``IVFIndex`` fits its coarse k-means first and, in
        residual mode, hands ``x - centroid(x)`` to the wrapped
        quantizer's stage. Keyword arguments are shared across the whole
        pipeline; each stage picks the ones it declares and ignores the
        rest (so ``train(xs, coarse_iters=5, iters=10)`` configures both
        IVF stages in one call).

        ``xs`` is handed to the first stage as given — each stage
        coerces to the array type it needs (UNQ trains host-side from
        numpy; the shallow quantizers convert to jnp themselves), so a
        large numpy training set is not round-tripped through the
        device before training starts.
        """
        from repro.core.training import run_train_pipeline
        run_train_pipeline(self._train_stages(), xs, kw)
        self._invalidate_caches()
        return self

    def _train_stages(self):
        """The ordered ``TrainStage`` list ``train`` runs. Default: the
        single quantizer-fitting stage."""
        from repro.core.training import TrainStage
        return [TrainStage(self.kind, self._fit_quantizer)]

    def _fit_quantizer(self, xs, **kw) -> jax.Array | None:
        """Fit this index's own quantizer (the default single pipeline
        stage). Return None, or transformed vectors for later stages."""
        raise NotImplementedError(
            f"{type(self).__name__} must implement _fit_quantizer or "
            "override _train_stages")

    @abc.abstractmethod
    def _encode(self, xs) -> jax.Array:
        """(n, dim) -> (n, M) uint8 codes."""

    @abc.abstractmethod
    def _build_luts(self, queries) -> jax.Array:
        """(Q, dim) -> (Q, M, K) float32 additive score tables (lower=closer
        after summation, up to a per-query constant)."""

    @abc.abstractmethod
    def _reconstruct(self, codes) -> jax.Array:
        """(n, M) codes -> (n, dim) reconstructions for stage-2 rerank."""

    def _encode_bias(self, codes) -> jax.Array | None:
        """Per-point additive score term for new codes (None for most)."""
        return None

    def _build_decode_table(self) -> jax.Array | None:
        """(M, K, D) f32 additive decode table with ``recon = sum_m
        table[m, code_m]`` (``ref.decode_with_table`` semantics), or None
        when reconstruction needs a learned decoder (UNQ) — the stage-2
        engine then uses cross-query dedup instead of the fused kernel."""
        return None

    def _decode_table(self) -> jax.Array | None:
        """Cached ``_build_decode_table`` (dropped by _invalidate_caches).

        Built under ``ensure_compile_time_eval`` so a first call from
        inside a jit trace (``_reconstruct`` is traced by the vmap oracle
        and the chunked decoders) still caches a concrete table instead
        of leaking a tracer."""
        if self._table_cache is None:
            with jax.ensure_compile_time_eval():
                self._table_cache = self._build_decode_table()
        return self._table_cache

    # -- add / search ------------------------------------------------------

    @classmethod
    def _encode_bucket(cls, n: int) -> int:
        """Smallest encode-batch bucket >= n (see ENCODE_BUCKETS)."""
        for b in cls.ENCODE_BUCKETS:
            if n <= b:
                return b
        step = cls.ENCODE_BUCKETS[-1]
        return -(-n // step) * step

    def add(self, xs) -> "Index":
        """Compress (n, dim) vectors and append them to the database.

        Inputs are zero-padded up to the next ``ENCODE_BUCKETS`` size
        before encoding (pad rows sliced off after), so adding
        differently-sized chunks hits one compiled encoder instead of
        re-jitting per (n, dim) shape. Encoders are row-stable, so the
        codes are identical to encoding unpadded.
        """
        if not self.is_trained:
            raise RuntimeError(f"{type(self).__name__}.add before train()")
        xs = jnp.asarray(xs)
        n = xs.shape[0]
        bucket = self._encode_bucket(n)
        if bucket != n:
            xs = jnp.pad(xs, ((0, bucket - n), (0, 0)))
        codes = self._encode(xs)[:n]
        bias = self._encode_bias(codes)
        if self._codes is None:
            self._codes, self._bias = codes, bias
        else:
            self._codes = jnp.concatenate([self._codes, codes], axis=0)
            if bias is not None:
                self._bias = jnp.concatenate([self._bias, bias], axis=0)
        return self

    def _lower_filter(self, filter_mask, num_queries: int):
        """Lower a boolean keep-mask to the two stage-1 bias streams.

        filter_mask: None | (ntotal,) | (Q, ntotal) bool (True = keep).
        Returns (bias, qbias): the per-point (N,) stream — the index's own
        bias with filtered points forced to +inf for a shared mask — and
        the per-(query, point) (Q, N) stream for per-query masks. Uses
        ``where`` rather than addition so kept points' scores are
        bit-identical to an index built over only the kept points.
        """
        if filter_mask is None:
            return self._bias, None
        mask = jnp.asarray(filter_mask, bool)
        if mask.ndim == 1:
            if mask.shape != (self.ntotal,):
                raise ValueError(
                    f"filter_mask shape {mask.shape} != ({self.ntotal},)")
            base_bias = self._bias if self._bias is not None \
                else jnp.zeros((self.ntotal,), jnp.float32)
            return jnp.where(mask, base_bias, jnp.inf), None
        if mask.shape != (num_queries, self.ntotal):
            raise ValueError(
                f"filter_mask shape {mask.shape} != "
                f"({num_queries}, {self.ntotal})")
        return self._bias, jnp.where(mask, 0.0, jnp.inf).astype(jnp.float32)

    def _check_quantized_request(self, lut_dtype: str, overfetch: int):
        """Gate a ``lut_dtype``/``overfetch`` request on the resolved
        backend's ``quantized_lut`` capability (loud, not silent f32)."""
        if lut_dtype == "float32" and overfetch == 1:
            return
        impl = resolve_scan_backend(self.backend)
        if not backend_supports(impl, "quantized_lut"):
            raise ValueError(
                f"backend {impl!r} does not declare the 'quantized_lut' "
                f"capability; lut_dtype={lut_dtype!r} / "
                f"overfetch={overfetch} need a streaming backend")

    def search(self, queries, k: int, *, use_rerank: bool | None = None,
               use_d2: bool = True, filter_mask=None,
               lut_dtype: str = "float32", overfetch: int = 1):
        """Two-stage search: (Q, dim) queries -> (distances, indices), each
        (Q, k), sorted closest-first.

        ``use_rerank=None`` reranks iff the index has a rerank budget;
        ``use_rerank=False`` returns raw d2 ranking ("No reranking"
        ablation); ``use_d2=False`` reranks the ENTIRE database with exact
        reconstruction distances ("Exhaustive reranking" ablation),
        chunked over N — the (Q, N, D) reconstruction never exists.

        ``filter_mask`` — (ntotal,) or (Q, ntotal) bool, True = eligible —
        is the public filtered-search API: it lowers to a ±inf additive
        bias stream that rides every stage-1 path (fused kernel included),
        so a filtered point can never enter the candidate pool. Results
        over the kept points are bit-identical to searching an index that
        only contains them; when fewer than k points survive, the tail is
        reported as (distance=+inf, index=-1).

        ``lut_dtype`` in {'float16', 'int8'} (with ``overfetch`` >= 1)
        opts stage 1 into the reduced-precision fast path: the scan
        selects ``overfetch * L`` candidates under quantized tables and
        re-scores the pool with the exact f32 chain before the final
        top-L (``repro.kernels.lut_quant``). Only backends with the
        ``quantized_lut`` capability accept it; the default is the
        bit-exact f32 path, unchanged.
        """
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
                    "filter_mask is not supported with use_d2=False "
                    "(the exhaustive-rerank ablation scans every point)")
            return self._exhaustive_rerank_topk(queries, k)
        topl = min(self.rerank if use_rerank else k, self.ntotal)
        with TraceAnnotation("index.lut_build"):
            luts = self._build_luts(queries)
        gen = candidate_generator_for(self.backend)
        bias, qbias = self._lower_filter(filter_mask, queries.shape[0])
        bq = gen.query_block(self._codes.shape[0], queries.shape[0],
                             topl=topl, lut_dtype=lut_dtype,
                             overfetch=overfetch)
        with TraceAnnotation("index.stage1",
                             **({} if bq is None else {"block_q": bq})):
            d2, cand = gen.topl(self._codes, luts, bias, topl=topl,
                                qbias=qbias, lut_dtype=lut_dtype,
                                overfetch=overfetch)
        if not use_rerank:
            d, i = d2[:, :k], cand[:, :k]
            if filter_mask is not None:
                i = jnp.where(jnp.isposinf(d), -1, i)
            return d, i
        valid = jnp.isfinite(d2) if filter_mask is not None else None
        with TraceAnnotation("index.rerank"):
            return self._rerank_topk(queries, cand, k, valid=valid)

    def _rerank_topk(self, queries, cand, k: int, *, valid=None):
        """Shared stage-2 tail: d1 rerank of the candidate pool + final
        top-k. Also used by ShardedIndex on the merged pool.

        ``valid`` (Q, L) bool marks pool entries that are real candidates
        (filtered search can underfill the pool): invalid slots are
        clamped to row 0 for the gather, forced to d1=+inf so they can
        never outrank a real candidate, and reported as index -1."""
        if valid is not None:
            cand = jnp.where(valid, cand, 0)
        d1 = self._rerank_distances(queries, cand)         # (Q, L)
        if valid is not None:
            d1 = jnp.where(valid, d1, jnp.inf)
        kk = min(k, d1.shape[1])
        neg, order = jax.lax.top_k(-d1, kk)
        d = -neg
        i = jnp.take_along_axis(cand, order, axis=1)
        if valid is not None:
            i = jnp.where(jnp.isposinf(d), -1, i)
        return d, i

    def _rerank_distances(self, queries, cand) -> jax.Array:
        """Stage 2: exact reconstruction distances d1 = ||q - recon||^2
        over each query's candidate list. queries (Q, D), cand (Q, L).

        Delegates to the ``Reranker`` resolved through the scan-backend
        registry (``repro.index.rerank``): fused/chunked table decode,
        cross-query dedup, or the materialized vmap oracle — all
        bit-identical, chosen purely on memory/perf grounds.
        """
        from repro.index.rerank import reranker_for
        return reranker_for(self).distances(self, queries, cand)

    def _rerank_distances_vmap(self, queries, cand) -> jax.Array:
        """The materialized stage-2 oracle: every candidate's code row
        gathered and decoded (``decode_rows``), the (Q, L, D)
        reconstruction built, d1 reduced by ``ref.sq_dist``. Ground truth
        for every streaming reranker, and the path backends without
        streaming capabilities use."""
        from repro.index.rerank import _sq_dist
        cand = jnp.asarray(cand)
        recon = self.decode_rows(jnp.take(self._codes, cand.reshape(-1),
                                          axis=0))
        return _sq_dist(recon.reshape(cand.shape + (self.dim,)),
                        jnp.asarray(queries, jnp.float32)[:, None, :])

    def decode_rows(self, codes) -> jax.Array:
        """(n, M) codes -> (n, D) reconstructions, decoded
        ``DECODE_CHUNK`` rows per call of one jitted program (cached;
        dropped by ``_invalidate_caches``). Every stage-2 decode goes
        through here, so a row's reconstruction is the same bits whatever
        else is decoded with it: a compiler picks its matmul tiling per
        shape, and a neural decoder run at two batch sizes need not agree
        bit for bit."""
        if self._decode_fn is None:
            self._decode_fn = jax.jit(self._reconstruct)
        n = codes.shape[0]
        if n == 0:
            return jnp.zeros((0, self.dim), jnp.float32)
        with TraceAnnotation("rerank.decode"):
            padded = jnp.pad(codes, ((0, (-n) % DECODE_CHUNK), (0, 0)))
            return jnp.concatenate(
                [self._decode_fn(padded[s:s + DECODE_CHUNK])
                 for s in range(0, padded.shape[0], DECODE_CHUNK)])[:n]

    def _exhaustive_rerank_topk(self, queries, k: int):
        """``use_d2=False``: exact-d1 top-k over ALL codes, chunked over N
        (``rerank.exhaustive_topk``) — each chunk decoded once for every
        query, merged into a running (Q, k) heap with ``lax.top_k`` tie
        semantics."""
        from repro.index.rerank import exhaustive_topk
        if self._exhaustive_fn is None:
            self._exhaustive_fn = jax.jit(
                functools.partial(exhaustive_topk, self._reconstruct),
                static_argnames=("k",))
        return self._exhaustive_fn(self._codes, queries, k=min(k, self.ntotal))

    def _invalidate_caches(self) -> None:
        """Drop compiled closures over quantizer params (after train/load)."""
        self._decode_fn = None
        self._exhaustive_fn = None
        self._table_cache = None

    # -- persistence (checkpoint/manager: atomic, self-describing) ---------

    @abc.abstractmethod
    def _tree(self) -> Any:
        """Pytree of everything save/load roundtrips (params + codes)."""

    @abc.abstractmethod
    def _metadata(self) -> dict:
        """JSON-serializable config sufficient to rebuild ``_tree`` shapes."""

    @classmethod
    @abc.abstractmethod
    def _empty_from_metadata(cls, meta: dict) -> "Index":
        """Rebuild an index whose ``_tree`` has the saved structure/shapes
        (leaf values are placeholders until ``_set_tree``)."""

    @abc.abstractmethod
    def _set_tree(self, tree: Any) -> None:
        """Install a restored ``_tree``."""

    def save(self, path) -> None:
        """Atomic save to a checkpoint directory (manager.save_pytree).

        For backends with the ``tuned`` capability the manifest also
        records the active autotuner fingerprint (schema version, device
        kind, tuned bucket count) — provenance for any timing attached to
        the checkpoint; ``load`` ignores it.
        """
        metadata = {"index_kind": self.kind,
                    "index_meta": self._metadata()}
        if backend_supports(resolve_scan_backend(self.backend), "tuned"):
            metadata["tuning"] = tune.cache_fingerprint()
        save_pytree(pathlib.Path(path), self._tree(), metadata=metadata)

    @staticmethod
    def load(path) -> "Index":
        """Load any saved index, dispatching on the manifest's kind tag."""
        path = pathlib.Path(path)
        with open(path / "manifest.json") as f:
            manifest = json.load(f)
        meta = manifest["metadata"]
        kind = meta.get("index_kind")
        if kind not in _KINDS:
            raise ValueError(
                f"{path} is not a saved index (kind={kind!r}; "
                f"known: {sorted(_KINDS)})")
        index = _KINDS[kind]._empty_from_metadata(meta["index_meta"])
        tree, _ = load_pytree(path, index._tree())
        index._set_tree(tree)
        return index

    def __repr__(self):
        return (f"{type(self).__name__}(dim={self.dim}, "
                f"ntotal={self.ntotal}, rerank={self.rerank}, "
                f"backend={self.backend!r}, trained={self.is_trained})")
