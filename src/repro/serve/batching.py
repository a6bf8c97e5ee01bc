"""Request coalescing onto the pow2 shape-bucket ladder.

One jit compile per (query bucket, k bucket) pair, the same
bucket-by-size idiom as ``ENCODE_BUCKETS`` on the encode path: the
scheduler hands a group of requests here, ``coalesce`` pads them up to
the next ``QUERY_BUCKETS`` rung, and ``split_results`` slices each
request's rows back out of the batched result.

Every padding decision below is parity-preserving by construction:

* pad QUERIES are zero vectors whose rows are simply discarded at
  fan-in (and fully masked whenever a filter-mask stream exists, so
  they cannot even cost scan work on the masked path);
* per-request ``k`` batches at the pow2-bucketed max and slices each
  request back to its own ``min(k_r, ntotal)`` prefix — the exact
  sorted top-k is prefix-stable, so the first j columns never depend
  on how many more were computed;
* per-request ``nprobe`` coalesces into a (Q,) vector that
  ``IVFIndex.search`` masks per query (probe at the batch max, excess
  cells never enter that query's pool);
* maskless requests riding a batch that carries masks get all-True
  rows — an all-True row lowers to a zero bias, which can only turn
  -0.0 scores into +0.0, invisible to ranking and to ``array_equal``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import numpy as np

#: Q-padding ladder for coalesced batches (pow2, like ENCODE_BUCKETS):
#: each rung is one jit specialization of the batched search.
QUERY_BUCKETS = (8, 16, 32, 64, 128)


def query_bucket(num_queries: int,
                 buckets: tuple[int, ...] = QUERY_BUCKETS) -> int:
    """Smallest ladder rung holding ``num_queries`` rows."""
    for b in buckets:
        if num_queries <= b:
            return b
    raise ValueError(
        f"batch of {num_queries} queries exceeds the largest query "
        f"bucket {buckets[-1]}; lower max_batch_queries or extend "
        "QUERY_BUCKETS")


def k_bucket(k: int) -> int:
    """Next power of two >= k: batching heterogeneous-k requests at a
    bucketed k_max keeps the compile count per query bucket at
    O(log k_max) instead of one per distinct k."""
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    return 1 << (k - 1).bit_length()


@dataclasses.dataclass
class Request:
    """One search request: its own query block plus per-request knobs.

    ``deadline_ms`` is a latency budget relative to submission; the
    queue stamps ``t_submit`` and ``seq`` (FIFO tie-break) on submit and
    derives the absolute ``t_deadline``; the engine stamps ``t_launch``
    (same ``perf_counter`` clock) when the request's batch is launched,
    so ``t_launch - t_submit`` is its queue wait. ``future`` resolves to
    this request's own ``(distances, indices)`` numpy pair."""
    queries: np.ndarray                      # (q, dim) float32
    k: int
    nprobe: Any = None                       # None | int | (q,) int vector
    filter_mask: np.ndarray | None = None    # (q, ntotal) bool
    deadline_ms: float | None = None
    # stamped by RequestQueue.submit
    t_submit: float = 0.0
    t_deadline: float | None = None
    seq: int = -1
    # stamped by ServeEngine._execute
    t_launch: float | None = None
    future: Any = None

    @property
    def num_queries(self) -> int:
        return int(self.queries.shape[0])


class Batch(NamedTuple):
    """A coalesced, bucket-padded group of requests ready to execute."""
    requests: tuple                          # the member Requests, in order
    spans: tuple                             # per-request (lo, hi) row spans
    queries: np.ndarray                      # (bucket, dim), pad rows zero
    bucket: int                              # the QUERY_BUCKETS rung used
    k_eff: int                               # batched k (pow2 of max k_r)
    nprobe: Any                              # None | int | (bucket,) vector
    filter_mask: np.ndarray | None           # None | (bucket, ntotal) bool
    deadline: float | None                   # earliest absolute deadline
    seq: int = -1                            # engine's batch number

    @property
    def num_real(self) -> int:
        return int(self.spans[-1][1]) if self.spans else 0

    @property
    def num_pad(self) -> int:
        return self.bucket - self.num_real


def coalesce(requests, *, ntotal: int, default_nprobe: int | None = None,
             pow2_k: bool = True,
             buckets: tuple[int, ...] = QUERY_BUCKETS) -> Batch:
    """Stack a request group into one padded ``Batch``.

    ``default_nprobe`` fills nprobe-less requests when any member pins
    its own width (pass the index's ``nprobe``); with no member pinning
    one, the batch nprobe stays None and the index default applies
    uniformly. ``ntotal`` sizes the combined filter mask."""
    if not requests:
        raise ValueError("coalesce needs at least one request")
    spans, lo = [], 0
    for r in requests:
        spans.append((lo, lo + r.num_queries))
        lo += r.num_queries
    bucket = query_bucket(lo, buckets)
    dim = requests[0].queries.shape[1]
    queries = np.zeros((bucket, dim), dtype=np.float32)
    for r, (a, b) in zip(requests, spans):
        queries[a:b] = r.queries

    k_max = max(r.k for r in requests)
    k_eff = k_bucket(k_max) if pow2_k else k_max

    nprobe = None
    if any(r.nprobe is not None for r in requests):
        if default_nprobe is None:
            raise ValueError(
                "a request pins nprobe but no default_nprobe was given "
                "for the nprobe-less requests (pass the index's nprobe)")
        lens = np.ones(bucket, dtype=np.int32)   # pad rows: cheapest probe
        for r, (a, b) in zip(requests, spans):
            lens[a:b] = default_nprobe if r.nprobe is None else r.nprobe
        if lo == bucket and int(lens.min()) == int(lens.max()):
            nprobe = int(lens[0])
        else:
            nprobe = lens

    filter_mask = None
    if any(r.filter_mask is not None for r in requests):
        # pad rows all-False only BECAUSE a mask stream already exists:
        # on maskless batches the pads just compute-and-discard, which
        # beats shipping a (bucket, ntotal) mask to mask them out.
        filter_mask = np.zeros((bucket, ntotal), dtype=bool)
        for r, (a, b) in zip(requests, spans):
            filter_mask[a:b] = True if r.filter_mask is None \
                else r.filter_mask

    deadlines = [r.t_deadline for r in requests if r.t_deadline is not None]
    return Batch(requests=tuple(requests), spans=tuple(spans),
                 queries=queries, bucket=bucket, k_eff=k_eff,
                 nprobe=nprobe, filter_mask=filter_mask,
                 deadline=min(deadlines) if deadlines else None)


def split_results(batch: Batch, distances: np.ndarray, indices: np.ndarray,
                  ntotal: int):
    """Fan the batched (bucket, W) result back into per-request views.

    Operates on NUMPY arrays on purpose: the engine converts the device
    result to host memory once per batch, and per-request slicing here
    is plain strided views — slicing per-span on device arrays would
    compile one kernel per distinct span shape, breaking the
    one-compile-per-bucket guarantee."""
    out = []
    for r, (a, b) in zip(batch.requests, batch.spans):
        w = min(r.k, ntotal)
        out.append((distances[a:b, :w], indices[a:b, :w]))
    return out
