"""ShardedIndex: distributed stage 1 over code shards.

Subsumes the old ``core.search.search_sharded`` free function and the
host-side shard driver in ``examples/serve_search.py``: each shard scans
its own code block with the (replicated) LUTs through the streaming
scan+top-L engine, the per-shard pools merge into a global candidate pool,
and stage 2 reranks the merged pool once — the pattern that scales the
paper's billion-vector experiments across a pod.

Placement modes:

  * ``device`` — the real thing: code (and bias) shards live RESIDENT on
    devices under ``shard_map`` (``repro.parallel.search``), shard s on
    device s (placed once, then reused), per-device fused scan+top-L,
    all-gather of the (L, 2) candidate tuples, one rerank on the merged
    pool. Selected by
    ``placement="auto"`` whenever more than one device is visible.
  * ``host`` — logical shards (host-side views over one code matrix),
    scanned sequentially. The single-device fallback, and what
    ``from_shards`` uses for externally-supplied shard stores.

Wrapping an ``IVFIndex`` shards BY COARSE CELL: the cell-grouped buffer is
cut at cell boundaries (balanced by row count), so every inverted list
lives wholly on one shard and a probed cell touches exactly one shard —
shards none of the batch's probed cells map to are skipped outright in
host mode, and in device mode each device's ragged probe plan covers only
the cells it owns. Cross-shard pools merge with an explicit lexicographic
(score, global-id) top-L (``candidates.merge_topl``) because cell-grouped
shards interleave global ids.

``filter_mask`` threads through every mode: host shards see per-shard
slices of the lowered ±inf bias streams, device shards stream their slice
of the (Q, N) mask tiles, and IVF shards fold the mask into the probe
plan's slot bias.

All modes are bit-identical to the equivalent flat ``Index.search`` — the
per-shard top-L keeps everything the global top-L can contain, and merges
preserve ``lax.top_k``'s smaller-index tie-break.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.index import base
from repro.index.backend import backend_supports, resolve_scan_backend
from repro.index.candidates import candidate_generator_for, merge_topl
from repro.index.ivf import IVFIndex

_IMAX = np.iinfo(np.int32).max


class ShardedIndex:
    """Wraps a trained Index, presenting the same train/add/search surface
    with stage 1 executed per-shard and merged."""

    def __init__(self, inner: base.Index, num_shards: int = 8, *,
                 placement: str = "auto"):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if placement not in ("auto", "host", "device"):
            raise ValueError(
                f"placement must be auto|host|device, got {placement!r}")
        self.inner = inner
        self.num_shards = num_shards
        self.placement = placement
        # device placement: (codes, bias) it was cut from -> PlacedShards
        self._placed = None
        # explicit shard mode (from_shards): pre-split code blocks
        self._shards = None
        self._offsets = None
        self._biases = None

    @classmethod
    def from_shards(cls, inner: base.Index, shards, offsets,
                    biases=None) -> "ShardedIndex":
        """Wrap pre-split code shards (arbitrary offsets). Only stage-1
        candidate generation is available in this mode unless the shards
        are a contiguous split of the inner index's codes.

        ``biases``: per-shard (n_s,) score-bias arrays for additive
        quantizers (RVQ stores ||decode(code)||^2). Required whenever the
        inner index carries a bias — dropping it silently would corrupt
        the stage-1 ranking.
        """
        if isinstance(inner, IVFIndex):
            raise ValueError(
                "from_shards does not support IVF indexes — their shards "
                "are derived from the cell grouping; wrap the IVFIndex "
                "directly in ShardedIndex instead")
        index = cls(inner, num_shards=len(shards), placement="host")
        index._shards = [jnp.asarray(s) for s in shards]
        index._offsets = list(offsets)
        if biases is None and inner.bias is not None:
            raise ValueError(
                f"{type(inner).__name__} scores carry a per-point bias; "
                "pass the matching per-shard `biases` to from_shards")
        if biases is not None:
            biases = [jnp.asarray(b) for b in biases]
            if [int(b.shape[0]) for b in biases] != \
                    [int(s.shape[0]) for s in index._shards]:
                raise ValueError("biases/shards length mismatch")
        index._biases = biases
        return index

    # -- delegated surface -------------------------------------------------

    @property
    def dim(self) -> int:
        return self.inner.dim

    @property
    def ntotal(self) -> int:
        if self._shards is not None:
            return int(sum(s.shape[0] for s in self._shards))
        return self.inner.ntotal

    @property
    def is_trained(self) -> bool:
        return self.inner.is_trained

    def result_width(self, k: int) -> int:
        """See ``Index.result_width`` (against this wrapper's ntotal)."""
        return min(k, self.ntotal)

    def train(self, xs, **kw) -> "ShardedIndex":
        self.inner.train(xs, **kw)
        return self

    def add(self, xs) -> "ShardedIndex":
        if self._shards is not None:
            raise RuntimeError("add() is not supported in from_shards mode")
        self.inner.add(xs)
        return self

    @property
    def resolved_placement(self) -> str:
        """The stage-1 placement searches will actually use. Device-resident
        iff requested, or auto with a real mesh AND a streaming-capable
        backend (explicit from_shards stores are host-side by
        construction; the materialized onehot path stays host-logical)."""
        if self._shards is not None:
            return "host"
        if self.placement == "auto":
            streaming = backend_supports(
                resolve_scan_backend(self.inner.backend), "streaming_topl")
            return "device" if streaming and len(jax.devices()) > 1 \
                else "host"
        return self.placement

    def _shard_views(self):
        """[(codes, offset, bias)] — explicit shards, or a contiguous
        equal split of the inner code matrix (tail rides the last shard)."""
        if self._shards is not None:
            biases = self._biases or [None] * len(self._shards)
            return list(zip(self._shards, self._offsets, biases))
        codes, bias = self.inner.codes, self.inner.bias
        n = codes.shape[0]
        per = max(n // self.num_shards, 1)
        views = []
        for i in range(self.num_shards):
            lo = i * per
            hi = n if i == self.num_shards - 1 else min((i + 1) * per, n)
            if lo >= hi:
                break
            views.append((codes[lo:hi], lo,
                          None if bias is None else bias[lo:hi]))
        return views

    def _devices(self):
        """Device placement runs shard s on device s."""
        devices = jax.devices()[:self.num_shards]
        if len(devices) < self.num_shards:
            raise ValueError(
                f"placement='device' with num_shards={self.num_shards} "
                f"needs that many devices; {len(devices)} visible")
        return devices

    def _placed_shards(self, bias):
        """The flat database resident as one shard per device, placed on
        first use and reused while the inner codes and the stage-1 bias
        stream are the same arrays (a shared filter mask lowers to a new
        bias stream, and is placed for that call only)."""
        from repro.parallel.search import place_shards
        codes = self.inner.codes
        if self._placed is not None and self._placed[0] is codes \
                and self._placed[1] is bias:
            return self._placed[2]
        placed = place_shards(codes, bias, self._devices())
        if bias is self.inner.bias:
            self._placed = (codes, bias, placed)
        return placed

    def _ivf_cell_bounds(self) -> list[int]:
        """Cell boundaries of the by-cell sharding: ``num_shards + 1``
        monotone cell ids cutting the cell-grouped buffer into row-balanced
        contiguous cell ranges (a cell never straddles two shards)."""
        off = self.inner._offsets
        n = int(off[-1])
        bounds = [0]
        for s in range(1, self.num_shards):
            target = round(s * n / self.num_shards)
            c = int(np.searchsorted(off, target, side="left"))
            bounds.append(min(max(c, bounds[-1]), self.inner.nlist))
        bounds.append(self.inner.nlist)
        return bounds

    # -- search ------------------------------------------------------------

    def stage1_candidates(self, queries, topl: int | None = None, *,
                          filter_mask=None, nprobe=None,
                          use_dispatch: bool | None = None):
        """Distributed stage 1: per-shard top-L merged into the global
        candidate pool. Returns (d2 scores, global indices), each
        (Q, min(topl, pool width)), closest-first. ``nprobe`` and
        ``use_dispatch`` only apply to IVF inners (probe width defaults
        to the index's own; a (Q,) per-query nprobe vector works in host
        placement only; the device placement rides the cell-batched
        dispatch face whenever the backend declares ``dispatch_topl``,
        pinnable either way for A/B runs)."""
        if topl is None:
            topl = self.inner.rerank
        queries = jnp.asarray(queries)
        if isinstance(self.inner, IVFIndex):
            return self._ivf_stage1(queries, topl, filter_mask, nprobe,
                                    use_dispatch)
        if use_dispatch:
            raise ValueError("use_dispatch applies to IVF inners only")
        luts = self.inner._build_luts(queries)
        impl = resolve_scan_backend(self.inner.backend)
        bias, qbias = self.inner._lower_filter(filter_mask,
                                               queries.shape[0])

        if self.resolved_placement == "device":
            if not backend_supports(impl, "streaming_topl"):
                raise ValueError(
                    f"placement='device' needs a streaming_topl-capable "
                    f"scan backend, and {impl!r} does not declare it; use "
                    "placement='host' or a streaming backend (xla/pallas)")
            from repro.parallel.search import device_stage1_topl
            placed = self._placed_shards(bias)
            return device_stage1_topl(placed, luts, qbias=qbias, topl=topl,
                                      impl=impl)

        gen = candidate_generator_for(self.inner.backend)
        all_scores, all_idx = [], []
        for shard, off, shard_bias in self._shard_views():
            if filter_mask is not None:
                hi = off + shard.shape[0]
                # bias is None for per-query masks on bias-less indexes
                shard_bias = None if bias is None else bias[off:hi]
                shard_qbias = None if qbias is None else qbias[:, off:hi]
            else:
                shard_qbias = None
            s, i = gen.topl(shard, luts, shard_bias,
                            topl=min(topl, shard.shape[0]),
                            qbias=shard_qbias)
            all_scores.append(s)
            # +inf slots (filtered-out pads) keep the _IMAX sentinel: adding
            # the shard offset would wrap int32 into garbage "global" ids
            all_idx.append(jnp.where(jnp.isposinf(s), _IMAX, i + off))
        scores = jnp.concatenate(all_scores, axis=1)     # (Q, n_shards*L)
        idx = jnp.concatenate(all_idx, axis=1)
        neg, order = jax.lax.top_k(-scores, min(topl, scores.shape[1]))
        return -neg, jnp.take_along_axis(idx, order, axis=1)

    def _ivf_stage1(self, queries, topl: int, filter_mask,
                    nprobe, use_dispatch: bool | None = None):
        """By-cell sharded IVF stage 1: each shard owns a contiguous cell
        range; only shards owning a probed cell are scanned (host mode
        skips the rest outright, device mode gives them empty plans); the
        per-shard gathered pools merge lexicographically by
        (score, global id).

        Device placement rides the cell-batched dispatch face by default
        on ``dispatch_topl``-capable backends — per-shard routing over
        clip-restricted CSR offsets, no host plan — with the gathered
        padded-plan face retained as the pinnable control
        (``use_dispatch=False``)."""
        ivf = self.inner
        q = queries.shape[0]
        nprobe_w, probe_lens = ivf._resolve_nprobe(nprobe, q)
        if probe_lens is not None and self.resolved_placement == "device":
            raise ValueError(
                "per-query nprobe vectors are host-plan only; device "
                "placement builds one shard_map plan per batch — use "
                "placement='host' or a uniform nprobe")
        probe, cd = ivf._probe_with_dists(queries, nprobe_w)
        luts = ivf._stage1_luts(queries, probe)
        cell_bias = cd if ivf._exact_residual else None
        bounds = self._ivf_cell_bounds()
        off = ivf._offsets

        if self.resolved_placement == "device":
            impl = resolve_scan_backend(ivf.backend)
            if not backend_supports(impl, "streaming_topl"):
                raise ValueError(
                    "placement='device' needs a streaming_topl-capable "
                    f"scan backend, and {impl!r} does not declare it")
            if use_dispatch is None:
                use_dispatch = backend_supports(impl, "dispatch_topl")
            elif use_dispatch and not backend_supports(impl,
                                                       "dispatch_topl"):
                raise ValueError(
                    f"use_dispatch=True but backend {impl!r} does not "
                    "declare the dispatch_topl capability")
            if use_dispatch:
                from repro.index.dispatch import build_shard_dispatch
                from repro.parallel.search import device_dispatch_topl
                routings = build_shard_dispatch(probe, off, bounds)
                shards = []
                for s, routing in enumerate(routings):
                    row_lo = int(off[bounds[s]])
                    row_hi = int(off[bounds[s + 1]])
                    ids, rowbias, qkeep, cellterm = ivf._dispatch_streams(
                        routing, q, filter_mask, cell_bias,
                        row_range=(row_lo, row_hi))
                    shards.append((row_lo, row_hi, routing, ids, rowbias,
                                   qkeep, cellterm))
                return device_dispatch_topl(ivf.codes, shards, luts,
                                            topl=topl, impl=impl,
                                            devices=self._devices())
            from repro.parallel.search import device_gather_topl
            plans = []
            for s in range(self.num_shards):
                c_lo, c_hi = bounds[s], bounds[s + 1]
                row_lo, row_hi = int(off[c_lo]), int(off[c_hi])
                rows, gids, cells = ivf._probe_plan(
                    probe, cell_range=(c_lo, c_hi), row_offset=row_lo)
                plans.append((row_lo, row_hi, rows, gids, cells))
            rowbias_fn = lambda rows, gids, cells, sb: ivf._plan_rowbias(  # noqa: E731
                rows, gids, sb, filter_mask, q,
                slot_cells=cells if cell_bias is not None else None,
                cell_bias=cell_bias)
            return device_gather_topl(ivf.codes, ivf.bias, plans, luts,
                                      rowbias_fn, topl=topl, impl=impl,
                                      devices=self._devices())

        gen = candidate_generator_for(ivf.backend)
        pool_s, pool_i = [], []
        for s in range(self.num_shards):
            c_lo, c_hi = bounds[s], bounds[s + 1]
            row_lo, row_hi = int(off[c_lo]), int(off[c_hi])
            if row_hi == row_lo:
                continue
            rows_np, gids_np, cells_np = ivf._probe_plan(
                probe, cell_range=(c_lo, c_hi), row_offset=row_lo,
                probe_lens=probe_lens)
            if (gids_np == _IMAX).all():
                continue                      # no query probes this shard
            rows = jnp.asarray(rows_np)
            gids = jnp.asarray(gids_np)
            shard_bias = None if ivf.bias is None \
                else ivf.bias[row_lo:row_hi]
            rowbias = ivf._plan_rowbias(
                rows, gids, shard_bias, filter_mask, q,
                slot_cells=cells_np if cell_bias is not None else None,
                cell_bias=cell_bias)
            s_s, s_i = gen.gather_topl(ivf.codes[row_lo:row_hi], rows,
                                       gids, luts, rowbias,
                                       topl=min(topl, rows.shape[1]))
            pool_s.append(s_s)
            pool_i.append(s_i)
        if not pool_s:                        # every probed cell was empty
            return (jnp.full((q, 1), jnp.inf, jnp.float32),
                    jnp.full((q, 1), _IMAX, jnp.int32))
        return merge_topl(jnp.concatenate(pool_s, axis=1),
                          jnp.concatenate(pool_i, axis=1), topl)

    def search(self, queries, k: int, *, use_rerank: bool | None = None,
               filter_mask=None, nprobe=None,
               use_dispatch: bool | None = None):
        """Full two-stage sharded search: merged stage-1 candidates, then
        ONE stage-2 rerank over the merged pool through the streaming
        rerank engine (``Index._rerank_topk`` resolves a ``Reranker`` per
        backend — fused table kernel or cross-query dedup; the merged
        pool's cross-query overlap is exactly what dedup exploits). Same
        (distances, indices) contract as ``Index.search``, including the
        ``filter_mask`` semantics."""
        queries = jnp.asarray(queries)
        if use_rerank is None:
            use_rerank = self.inner.rerank > 0
        topl = self.inner.rerank if use_rerank else k
        d2, cand = self.stage1_candidates(queries, topl=max(topl, k),
                                          filter_mask=filter_mask,
                                          nprobe=nprobe,
                                          use_dispatch=use_dispatch)
        if isinstance(self.inner, IVFIndex):
            return self.inner._finish_pool(queries, d2, cand, k,
                                           use_rerank=use_rerank)
        if not use_rerank:
            d, i = d2[:, :k], cand[:, :k]
            if filter_mask is not None:
                i = jnp.where(jnp.isposinf(d), -1, i)
            return d, i
        if self._shards is not None and not self._is_contiguous_view():
            raise RuntimeError(
                "stage-2 rerank in from_shards mode needs the shards to be "
                "a contiguous split of the inner index's code matrix "
                "(global candidate ids must index inner.codes)")
        # rerank AFTER the merge (host-side): bit-parity with flat search
        # requires reranking exactly the global top-L pool — a per-shard
        # local rerank would rank a superset and can disagree on top-k
        valid = jnp.isfinite(d2) if filter_mask is not None else None
        return self.inner._rerank_topk(queries, cand, k, valid=valid)

    def _is_contiguous_view(self) -> bool:
        """True iff the explicit shards tile inner.codes front to back, so
        shard-local index + offset is a valid row of inner.codes."""
        if self.inner.ntotal != self.ntotal:
            return False
        expect = 0
        for s, off in zip(self._shards, self._offsets):
            if off != expect:
                return False
            expect += int(s.shape[0])
        return True

    def __repr__(self):
        return (f"ShardedIndex({self.inner!r}, num_shards={self.num_shards}, "
                f"placement={self.resolved_placement!r})")
