"""The serving engine: queue + scheduler + double-buffered dispatch.

``ServeEngine`` owns one index (flat ``Index``, ``IVFIndex``, or
``ShardedIndex``) and a single worker thread running the dispatch loop.
The loop is double-buffered around JAX's async dispatch: batch t's
device scan is launched (non-blocking), then the HOST work for batch
t+1 — queue drain, coalescing, probe-plan/routing construction inside
``index.search`` — proceeds while t runs; only then does the worker
block on t's result to fan it out. Steady state therefore keeps the
device busy whenever two batches are in flight. Completion of t never
waits on t+1's coalescing window: the worker fans t out eagerly when
its result is already ready, and otherwise arms the scheduler's linger
interrupt so the wait for t+1's followers is cut the moment t finishes
— which also keeps the observed service times (the scheduler's
deadline-reserve EWMA) honest instead of folding linger into them.

Tracing: the worker loop is tiled by ``jax.profiler.TraceAnnotation``
spans (``serve.wait``, ``serve.linger``, ``serve.coalesce``,
``serve.execute``, ``serve.result_wait``, ``serve.fanout``; the index
nests its own inside ``serve.execute``); a batch's spans carry its
number as ``batch=<seq>``. While no profiler records, a span costs about
a microsecond. ``docs/SERVING.md`` lists them.

Bit-parity contract: every result delivered through ``submit`` /
``search_requests`` is bitwise-equal to calling ``index.search`` on
that request alone (ties included). ``batching`` documents why each
padding step preserves this; ``tests/test_serve.py`` enforces it.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import itertools
import threading
import time
from typing import Any

import numpy as np
from jax.profiler import TraceAnnotation

from repro.index.base import Index
from repro.index.ivf import IVFIndex, _INDEX_CAPACITY
from repro.index.sharded import ShardedIndex
from repro.serve import batching
from repro.serve.batching import Batch, Request, coalesce, split_results
from repro.serve.metrics import ServeMetrics
from repro.serve.queue import RequestQueue
from repro.serve.scheduler import Scheduler


@dataclasses.dataclass
class ServeConfig:
    """Engine policy knobs (shape/compile policy + search defaults)."""
    max_batch_queries: int = 128         # queue drain budget per batch
    linger_ms: float = 2.0               # coalescing window
    deadline_slack_ms: float = 1.0       # reserve under each deadline
    default_k: int = 10
    default_deadline_ms: float | None = None
    pow2_k: bool = True                  # bucket k_max to pow2 per batch
    query_buckets: tuple = batching.QUERY_BUCKETS
    use_rerank: bool | None = None       # None = index default
    use_dispatch: bool | None = None     # IVF face pin (None = capability)
    dispatch_capacity: Any = _INDEX_CAPACITY   # load-shed override
    lut_dtype: str = "float32"
    overfetch: int = 1


class ServeEngine:
    """Async serving facade over one trained, populated index."""

    def __init__(self, index, config: ServeConfig | None = None):
        self.index = index
        self.config = config or ServeConfig()
        if self.config.max_batch_queries > self.config.query_buckets[-1]:
            raise ValueError(
                f"max_batch_queries={self.config.max_batch_queries} "
                f"exceeds the largest query bucket "
                f"{self.config.query_buckets[-1]}")
        self._ivf = self._resolve_ivf(index)
        if not isinstance(index, (Index, IVFIndex, ShardedIndex)):
            raise TypeError(f"unsupported index type {type(index).__name__}")
        if isinstance(index, ShardedIndex) and (
                self.config.lut_dtype != "float32"
                or self.config.overfetch != 1
                or self.config.dispatch_capacity is not _INDEX_CAPACITY):
            raise ValueError(
                "ShardedIndex serving does not thread lut_dtype/overfetch/"
                "dispatch_capacity; keep those at their defaults")
        self.queue = RequestQueue()
        self.scheduler = Scheduler(
            self.queue, max_batch_queries=self.config.max_batch_queries,
            linger_ms=self.config.linger_ms,
            deadline_slack_ms=self.config.deadline_slack_ms)
        self.metrics = ServeMetrics()
        self._batch_seq = itertools.count()
        self._worker: threading.Thread | None = None
        self._worker_lock = threading.Lock()

    @staticmethod
    def _resolve_ivf(index):
        """The IVFIndex whose nprobe semantics apply, or None (flat)."""
        if isinstance(index, IVFIndex):
            return index
        if isinstance(index, ShardedIndex) and \
                isinstance(index.inner, IVFIndex):
            return index.inner
        return None

    # -- request intake ----------------------------------------------------

    def submit(self, queries, *, k: int | None = None, nprobe=None,
               filter_mask=None,
               deadline_ms: float | None = None) -> concurrent.futures.Future:
        """Enqueue one request; returns a Future resolving to this
        request's own (distances, indices) numpy pair. Starts the worker
        on first use. ``deadline_ms`` defaults from the config (None =
        best-effort)."""
        request = self._make_request(queries, k=k, nprobe=nprobe,
                                     filter_mask=filter_mask,
                                     deadline_ms=deadline_ms)
        request.future = concurrent.futures.Future()
        self._ensure_worker()
        self.queue.submit(request)
        return request.future

    def _make_request(self, queries, *, k, nprobe, filter_mask,
                      deadline_ms) -> Request:
        queries = np.asarray(queries, dtype=np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        if queries.ndim != 2 or queries.shape[1] != self.index.dim:
            raise ValueError(
                f"queries must be (q, {self.index.dim}), got "
                f"{queries.shape}")
        q = queries.shape[0]
        if q < 1 or q > self.config.max_batch_queries:
            raise ValueError(
                f"request width {q} outside [1, "
                f"{self.config.max_batch_queries}] (max_batch_queries)")
        if nprobe is not None:
            if self._ivf is None:
                raise ValueError("nprobe applies to IVF-backed indexes only")
            if np.ndim(nprobe) not in (0, 1) or (
                    np.ndim(nprobe) == 1 and len(nprobe) != q):
                raise ValueError(
                    f"nprobe must be a scalar or a ({q},) vector")
        if filter_mask is not None:
            filter_mask = np.asarray(filter_mask, dtype=bool)
            if filter_mask.shape != (q, self.index.ntotal):
                raise ValueError(
                    f"filter_mask must be ({q}, {self.index.ntotal}), "
                    f"got {filter_mask.shape}")
        if deadline_ms is None:
            deadline_ms = self.config.default_deadline_ms
        return Request(queries=queries,
                       k=self.config.default_k if k is None else int(k),
                       nprobe=nprobe, filter_mask=filter_mask,
                       deadline_ms=deadline_ms)

    # -- synchronous parity surface ----------------------------------------

    def search_requests(self, requests) -> list:
        """Coalesce + execute + fan-in one request group synchronously —
        the deterministic surface the parity suite and the smoke check
        drive (no queue/timing in the loop, same batch math as the
        worker). ``requests`` are dicts of ``submit`` kwargs or
        ``Request`` objects; returns one (distances, indices) numpy pair
        per request, in order."""
        reqs = [r if isinstance(r, Request) else self._make_request(
                    r.get("queries"), k=r.get("k"), nprobe=r.get("nprobe"),
                    filter_mask=r.get("filter_mask"),
                    deadline_ms=r.get("deadline_ms"))
                for r in requests]
        total = sum(r.num_queries for r in reqs)
        if total > self.config.max_batch_queries:
            raise ValueError(
                f"group of {total} queries exceeds max_batch_queries="
                f"{self.config.max_batch_queries}; split the group")
        batch = self._coalesce(reqs)
        d, i = self._execute(batch)
        return split_results(batch, np.asarray(d), np.asarray(i),
                             self.index.ntotal)

    # -- batch construction / execution ------------------------------------

    def _coalesce(self, requests) -> Batch:
        seq = next(self._batch_seq)
        with TraceAnnotation("serve.coalesce", batch=seq):
            return coalesce(
                requests, ntotal=self.index.ntotal,
                default_nprobe=None if self._ivf is None
                else self._ivf.nprobe,
                pow2_k=self.config.pow2_k,
                buckets=self.config.query_buckets)._replace(seq=seq)

    def _execute(self, batch: Batch):
        """Launch the batched search; returns DEVICE arrays (JAX async
        dispatch pending) so the worker can overlap the next batch's
        host work before blocking on them."""
        t_launch = time.perf_counter()
        for r in batch.requests:
            r.t_launch = t_launch
        cfg = self.config
        kw = dict(use_rerank=cfg.use_rerank, filter_mask=batch.filter_mask)
        if isinstance(self.index, IVFIndex):
            kw.update(nprobe=batch.nprobe, use_dispatch=cfg.use_dispatch,
                      dispatch_capacity=cfg.dispatch_capacity,
                      lut_dtype=cfg.lut_dtype, overfetch=cfg.overfetch)
        elif isinstance(self.index, ShardedIndex):
            kw.update(nprobe=batch.nprobe, use_dispatch=cfg.use_dispatch)
        else:
            kw.update(lut_dtype=cfg.lut_dtype, overfetch=cfg.overfetch)
        with TraceAnnotation("serve.execute", batch=batch.seq):
            return self.index.search(batch.queries, batch.k_eff, **kw)

    # -- warmup ------------------------------------------------------------

    def warmup(self, buckets=None, ks=None, *, masks: bool = False,
               nprobe_vectors: bool = False) -> dict:
        """Compile every (query bucket, k bucket) the serving loop will
        hit, through the SAME coalesce+execute path, before any timed
        traffic: the cold-compile cost lands here, in its own metric
        line, instead of inside the first requests' p95. Returns
        {label: ms} (also recorded on ``self.metrics``).

        The base pass covers maskless, default-nprobe traffic only — a
        ``filter_mask`` adds a (Q, ntotal) operand and (on the dispatch
        face) a per-query nprobe vector adds a probe-lengths operand, so
        those variants trace DIFFERENT programs. Traffic carrying them
        must opt in here (``masks=True`` warms an all-True-mask batch
        per bucket, ``nprobe_vectors=True`` a non-uniform probe vector
        at the default width; IVF-backed only) or its first request per
        bucket pays the jit inside the timed path. IVF probe-PLAN widths
        remain data-dependent either way — the ladder pins the shapes it
        can (see docs/SERVING.md for the exact coverage)."""
        cfg = self.config
        if buckets is None:
            buckets = [b for b in cfg.query_buckets
                       if b <= cfg.max_batch_queries]
        if ks is None:
            ks = [cfg.default_k]
        if nprobe_vectors and self._ivf is None:
            raise ValueError(
                "nprobe_vectors warmup applies to IVF-backed indexes only")
        timings = {}
        for b in buckets:
            for k in ks:
                variants = [("", None, None)]
                if masks:
                    variants.append(
                        ("_masked", None,
                         np.ones((b, self.index.ntotal), dtype=bool)))
                if nprobe_vectors:
                    # non-uniform on purpose: a uniform vector collapses
                    # to its scalar and would trace the base program
                    dflt = max(1, min(self._ivf.nprobe, self._ivf.nlist))
                    lens = np.full(b, dflt, dtype=np.int32)
                    if self._ivf.nlist > 1 and b > 1:
                        lens[0] = dflt - 1 if dflt > 1 else dflt + 1
                    variants.append(("_vnprobe", lens, None))
                for suffix, nprobe, mask in variants:
                    req = self._make_request(
                        np.zeros((b, self.index.dim), np.float32),
                        k=k, nprobe=nprobe, filter_mask=mask,
                        deadline_ms=None)
                    t0 = time.perf_counter()
                    batch = self._coalesce([req])
                    d, i = self._execute(batch)
                    np.asarray(d), np.asarray(i)    # block for compile+run
                    ms = (time.perf_counter() - t0) * 1e3
                    kb = batching.k_bucket(k) if cfg.pow2_k else k
                    label = f"q{b}_k{kb}{suffix}"
                    timings[label] = ms
                    self.metrics.record_cold_compile(label, ms)
        return timings

    # -- worker loop -------------------------------------------------------

    def _ensure_worker(self) -> None:
        with self._worker_lock:
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._run_worker, name="repro-serve-worker",
                    daemon=True)
                self._worker.start()

    @staticmethod
    def _pending_ready(pending) -> bool:
        """True when batch t's result no longer needs a device wait, so
        completing it now costs (almost) nothing. Plain numpy results
        (no ``is_ready``) are host-resident by definition; if readiness
        cannot be probed, answer False and keep the blocking order."""
        _, d, i, _ = pending
        try:
            return all(getattr(a, "is_ready", lambda: True)()
                       for a in (d, i))
        except Exception:        # noqa: BLE001 — probe only, never fatal
            return False

    def _run_worker(self) -> None:
        pending = None        # (batch, device distances, device indices, t0)
        while True:
            # an already-finished batch t fans out BEFORE the next
            # linger window opens: waiting for t+1's followers must
            # never delay results that are sitting ready
            if pending is not None and self._pending_ready(pending):
                self._complete(*pending)
                pending = None
            # host work for t+1 overlaps the device scan of t: only
            # block for fresh items when nothing is in flight, and let
            # t's completion interrupt the linger the moment t is ready
            interrupt = None if pending is None else \
                (lambda p=pending: self._pending_ready(p))
            items = self.scheduler.next_items(block=pending is None,
                                              interrupt=interrupt)
            nxt = None
            if items:
                try:
                    batch = self._coalesce(items)
                    t0 = time.perf_counter()
                    d, i = self._execute(batch)
                    nxt = (batch, d, i, t0)
                except Exception as exc:     # noqa: BLE001 — fan the
                    for r in items:          # failure out per-request
                        if r.future is not None:
                            r.future.set_exception(exc)
            if pending is not None:
                self._complete(*pending)
            pending = nxt
            if pending is None and not items and self.queue.drained():
                return

    def _complete(self, batch: Batch, d, i, t0: float) -> None:
        """Block on the device result, fan out, account. The service
        sample fed to the scheduler spans launch -> result ready; the
        worker's eager-completion/linger-interrupt discipline keeps the
        gap between "device done" and this call at poll granularity, so
        the EWMA tracks service, not linger."""
        try:
            with TraceAnnotation("serve.result_wait", batch=batch.seq):
                d_np, i_np = np.asarray(d), np.asarray(i)
        except Exception as exc:             # noqa: BLE001
            for r in batch.requests:
                if r.future is not None:
                    r.future.set_exception(exc)
            return
        t_done = time.perf_counter()
        with TraceAnnotation("serve.fanout", batch=batch.seq):
            self.scheduler.observe_service((t_done - t0) * 1e3)
            self.metrics.record_batch(batch)
            parts = split_results(batch, d_np, i_np, self.index.ntotal)
            for r, part in zip(batch.requests, parts):
                self.metrics.record_request(r, t_done)
                if r.future is not None:
                    r.future.set_result(part)

    def close(self, drain: bool = True) -> None:
        """Stop intake; with ``drain`` (default) the worker finishes
        every pending request before the thread exits."""
        self.queue.close()
        worker = self._worker
        if worker is not None and worker.is_alive():
            if drain:
                worker.join()
            else:
                worker.join(timeout=0.1)

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=True)
