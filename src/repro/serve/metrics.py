"""Serving observability: latency percentiles, queue wait, deadline
accounting, batching efficiency, cold-compile ledger, the dispatch-
overflow counter (the load-shed events `index.dispatch.OVERFLOWS`
rate-limits out of the warning stream — here they stay exactly
countable), the dedup reranker's work (`index.rerank.RERANK`), the
fused stage-1 kernel's query blocks (`index.candidates.STAGE1`) and the
Python collector's pauses (`GC_PAUSES`, also traced as `host.gc` spans).
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from repro.index import candidates as _candidates
from repro.index import dispatch as _dispatch
from repro.index import rerank as _rerank


class GcPauseMeter:
    """Pauses of Python's cyclic collector, counted and timed by
    generation through a ``gc.callbacks`` hook, each also a ``host.gc``
    span (``generation=<g>``) on the thread that collects.

    The hook takes no lock: it runs inside a collection, which can start
    on a thread that already holds any lock of the program. Collections
    never overlap, so one open span at a time suffices."""

    def __init__(self):
        self.pauses = [0, 0, 0]
        self.ms = [0.0, 0.0, 0.0]
        self._open = None

    def install(self) -> None:
        """Hook the collector (once per process)."""
        if self._hook not in gc.callbacks:
            gc.callbacks.append(self._hook)

    def _hook(self, phase: str, info: dict) -> None:
        if phase == "start":
            span = TraceAnnotation("host.gc", generation=info["generation"])
            span.__enter__()
            self._open = (time.perf_counter(), span)
        elif self._open is not None:
            t0, span = self._open
            self._open = None
            span.__exit__(None, None, None)
            gen = info["generation"]
            self.pauses[gen] += 1
            self.ms[gen] += (time.perf_counter() - t0) * 1e3

    def totals(self) -> tuple[int, float]:
        """(pauses, ms) over every generation since process start."""
        return sum(self.pauses), sum(self.ms)


#: process-wide collector meter, installed by the first ``ServeMetrics``
GC_PAUSES = GcPauseMeter()


def latency_percentiles(latencies_ms) -> dict:
    """p50/p95/p99 over a latency sample (ms). Empty sample -> NaNs, so
    a dry run still emits well-formed rows."""
    if len(latencies_ms) == 0:
        return {"p50_ms": float("nan"), "p95_ms": float("nan"),
                "p99_ms": float("nan")}
    lat = np.asarray(latencies_ms, dtype=np.float64)
    p50, p95, p99 = np.percentile(lat, [50, 95, 99])
    return {"p50_ms": float(p50), "p95_ms": float(p95),
            "p99_ms": float(p99)}


class ServeMetrics:
    """Accumulates per-request and per-batch accounting for one engine.

    ``dispatch_overflows`` reads the process-wide ``OVERFLOWS`` meter as
    a delta from this object's last ``reset()``, so concurrent direct
    index use outside the engine window doesn't pollute the count (two
    engines serving simultaneously would share it — overflow is a
    property of the shared index, not of one queue). The rerank,
    stage-1 and collector meters are read the same way, the collector's
    up to the last completed batch: pauses after it (a caller's own
    clean-up, say) delayed no request."""

    def __init__(self):
        self._lock = threading.Lock()
        GC_PAUSES.install()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.latencies_ms: list[float] = []
            # (batch seq, launch - submit ms) per request
            self.queue_waits: list[tuple[int, float]] = []
            self.deadline_misses = 0
            self.deadline_total = 0
            self.batches = 0
            self.padded_queries = 0
            self.real_queries = 0
            self.cold_compile_ms: dict[str, float] = {}
            self._overflow_base = _dispatch.OVERFLOWS.count
            self._rerank_base = _rerank.RERANK.snapshot()
            self._stage1_base = _candidates.STAGE1.count
            self._gc_base = self._gc_last = GC_PAUSES.totals()

    @property
    def dispatch_overflows(self) -> int:
        return _dispatch.OVERFLOWS.count - self._overflow_base

    def record_batch(self, batch) -> None:
        with self._lock:
            self.batches += 1
            self.padded_queries += batch.num_pad
            self.real_queries += batch.num_real
            self.queue_waits.extend(
                (batch.seq, (r.t_launch - r.t_submit) * 1e3)
                for r in batch.requests if r.t_launch is not None)
            self._gc_last = GC_PAUSES.totals()

    def record_request(self, request, t_done: float) -> None:
        with self._lock:
            self.latencies_ms.append((t_done - request.t_submit) * 1e3)
            if request.t_deadline is not None:
                self.deadline_total += 1
                if t_done > request.t_deadline:
                    self.deadline_misses += 1

    def record_cold_compile(self, label: str, ms: float) -> None:
        with self._lock:
            self.cold_compile_ms[label] = ms

    def summary(self) -> dict:
        """One flat dict: the BENCH_serve.json row shape."""
        with self._lock:
            lat = list(self.latencies_ms)
            waits = latency_percentiles([ms for _, ms in self.queue_waits])
            out = {
                "requests": len(lat),
                **latency_percentiles(lat),
                "queue_wait_p50_ms": waits["p50_ms"],
                "queue_wait_p95_ms": waits["p95_ms"],
                "deadline_misses": self.deadline_misses,
                "deadline_total": self.deadline_total,
                "deadline_miss_rate": (
                    self.deadline_misses / self.deadline_total
                    if self.deadline_total else 0.0),
                "batches": self.batches,
                "padded_queries": self.padded_queries,
                "real_queries": self.real_queries,
                "cold_compile_ms": dict(self.cold_compile_ms),
            }
        out["dispatch_overflows"] = self.dispatch_overflows
        return out | self._meter_deltas()

    def _meter_deltas(self) -> dict:
        slots, unique, decoded = (
            now - base for now, base in zip(_rerank.RERANK.snapshot(),
                                            self._rerank_base))
        pauses, ms = (last - base for last, base in zip(self._gc_last,
                                                        self._gc_base))
        return {"rerank_slots": slots, "rerank_unique_rows": unique,
                "rerank_decoded_rows": decoded,
                "stage1_query_blocks":
                    _candidates.STAGE1.count - self._stage1_base,
                "gc_pauses": pauses, "gc_pause_ms": ms}
