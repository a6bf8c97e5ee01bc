"""Deadline-aware dynamic batching policy.

The scheduler decides WHEN a batch is cut, trading latency for batch
width: after the first request of a group arrives it lingers up to
``linger_ms`` for followers to coalesce — but never past the point
where the group's earliest deadline could no longer absorb a service
time (tracked as an EWMA of observed batch service, padded by
``deadline_slack_ms``). A request with a tight deadline therefore cuts
its batch almost immediately; best-effort traffic coalesces up to the
full linger window.
"""
from __future__ import annotations

import time
from typing import Callable

from jax.profiler import TraceAnnotation

from repro.serve.queue import RequestQueue

#: linger-wait slice (s) when an ``interrupt`` probe is armed: the
#: engine's "batch t finished on device" signal is checked at this
#: granularity, bounding how long a ready result can sit behind an
#: open linger window.
_INTERRUPT_POLL_S = 5e-4


class Scheduler:
    def __init__(self, queue: RequestQueue, *, max_batch_queries: int,
                 linger_ms: float = 2.0, deadline_slack_ms: float = 0.0):
        self.queue = queue
        self.max_batch_queries = max_batch_queries
        self.linger_ms = linger_ms
        self.deadline_slack_ms = deadline_slack_ms
        self._service_ewma_ms = 0.0

    @property
    def service_estimate_ms(self) -> float:
        return self._service_ewma_ms

    def observe_service(self, ms: float) -> None:
        """Fold one observed batch service time into the EWMA the linger
        cut uses as its deadline-slack estimate."""
        if self._service_ewma_ms == 0.0:
            self._service_ewma_ms = ms
        else:
            self._service_ewma_ms += 0.25 * (ms - self._service_ewma_ms)

    def _linger_budget_s(self, items) -> float:
        """Seconds the group can still afford to wait for followers."""
        budget = self.linger_ms / 1e3
        now = time.perf_counter()
        reserve = (self._service_ewma_ms + self.deadline_slack_ms) / 1e3
        for r in items:
            if r.t_deadline is not None:
                budget = min(budget, r.t_deadline - now - reserve)
        return max(budget, 0.0)

    def next_items(self, *, block: bool = True,
                   interrupt: Callable[[], bool] | None = None):
        """The next request group to coalesce (empty list = nothing
        pending; with ``block=True`` an empty list means the queue is
        closed and drained). Takes the EDF head, then lingers within the
        group's deadline budget to fill toward ``max_batch_queries``.

        Refills are budget-STRICT: a request wider than the remaining
        budget is left queued (it leads the next batch) rather than
        popped past ``max_batch_queries`` — an overfull group would pick
        an un-warmed bucket or, at the top rung, fail the whole group in
        ``coalesce``. An EDF head the budget refuses also ends the
        linger: later arrivals may not legally jump that head.

        ``interrupt`` (optional, engine-armed) is polled during the
        linger wait; when it returns True the group is cut immediately —
        the engine uses it to stop a linger for batch t+1 from delaying
        fan-out of batch t once t's device result is ready."""
        with TraceAnnotation("serve.wait"):
            items = self.queue.take(self.max_batch_queries, block=block)
        if not items:
            return items
        with TraceAnnotation("serve.linger"):
            return self._linger(items, interrupt)

    def _linger(self, items, interrupt):
        """Refill ``items`` within the group's linger budget (the loop
        ``next_items`` documents)."""
        used = sum(r.num_queries for r in items)
        cutoff = time.perf_counter() + self._linger_budget_s(items)
        while used < self.max_batch_queries:
            remaining = cutoff - time.perf_counter()
            if remaining <= 0:
                break
            if interrupt is not None:
                if interrupt():
                    break
                remaining = min(remaining, _INTERRUPT_POLL_S)
            more = self.queue.take(self.max_batch_queries - used,
                                   block=True, timeout=remaining,
                                   strict_budget=True)
            if more:
                items.extend(more)
                used += sum(r.num_queries for r in more)
                cutoff = min(cutoff, time.perf_counter()
                             + self._linger_budget_s(more))
            elif interrupt is None or len(self.queue):
                break    # full wait elapsed, or an oversize head refused
        return items
