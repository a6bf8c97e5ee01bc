"""The one load generator: a traffic mix is a data file that it reads.

A mix file (``chipbench/traffic/<mix>.json``) holds parameters only; a
new shape of traffic is a new file, not new code. Its keys:

* ``loop``: ``"closed"`` (``clients`` callers, each waiting for its
  answer before it sends again) or ``"open"`` (requests sent at due
  times whatever the answers do);
* ``arrivals`` (open loop): ``"poisson"`` (exponential gaps) or
  ``"even"`` (equal gaps), at ``rate_per_s`` requests a second;
  ``phases`` (optional), a list of ``[seconds, factor]`` repeated
  through the window, multiplies that rate phase by phase: bursts;
* ``queries_per_request`` and ``k``: a number, or a choice
  ``{"values": [...], "weights": [...]}`` drawn per request;
* ``options`` (optional): further arguments of every request to the
  engine's ``submit`` (``nprobe``, ``deadline_ms``), each a value or a
  choice as above;
* ``filter`` (optional): ``{"categories": C, "share": p}``: every corpus
  row belongs to one of C categories (``category_of``), and a share p of
  the requests keeps only the rows of one category, drawn per request;
* ``popularity`` (optional): ``"uniform"`` (the default) or
  ``{"zipf": s}``, how often each query of the pool is asked;
* ``pool``: how many distinct queries the seed draws for the mix;
* ``serve`` (optional): the serving engine's policy for this traffic
  (``ServeConfig`` fields, such as ``max_batch_queries``).

Every seed gets the same work: an open loop's arrivals are one fixed
realisation of the arrival process (the quantiles of its unit gaps in
one fixed shuffled order, mapped through the rate's phases), and the
sequence of request shapes and options is fixed too, so a window always
holds the same requests at the same times. The seed picks which queries
each request carries and which category a filter keeps. (With the order
of the gaps drawn from the seed, the p95 of a 30 s window at 0.8 of the
knee moved by about 20 % from seed to seed: it followed where the
largest bursts fell, not the system.)

Requests are timed from when they were due: for an open loop, the due
time on the schedule, so a stalled sender is charged to the requests it
delays; for a closed loop, the moment the client sent it.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

WAIT_AFTER_CLOSE_S = 60.0   # how long answers are awaited past the window
ARRIVALS_SEED = 0x9A95      # the one order of an open loop's gaps
SHAPES_SEED = 0x5A9E5       # the one sequence of request shapes


def category_of(ids, categories: int):
    """The category of each corpus row id: a 32-bit integer hash of the
    id, modulo ``categories``. Written for numpy and jax.numpy alike, so
    the program's filter masks and the reference's agree."""
    x = ids.astype(np.uint32)
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x % np.uint32(categories)).astype(np.int32)


@dataclasses.dataclass
class Record:
    """One request of a window."""
    rows: np.ndarray          # query-pool rows the request carries
    due: float                # perf_counter when it was due
    k: int = 10
    options: dict = dataclasses.field(default_factory=dict)
    category: int = -1        # the category a filter keeps; -1: none
    sent: float = 0.0
    done: float = float("nan")
    result: tuple | None = None
    error: str | None = None


def _choice(spec, rng):
    """A value of ``spec``: the value itself, or one drawn from a
    ``{"values", "weights"}`` choice."""
    if not isinstance(spec, dict):
        return spec
    values = spec["values"]
    w = np.asarray(spec.get("weights", [1] * len(values)), np.float64)
    return values[int(rng.choice(len(values), p=w / w.sum()))]


def _values(spec) -> list:
    return list(spec["values"]) if isinstance(spec, dict) else [spec]


def mean_queries(mix: dict) -> float:
    """The mean number of queries a request of the mix carries."""
    spec = mix["queries_per_request"]
    if not isinstance(spec, dict):
        return float(spec)
    w = np.asarray(spec.get("weights", [1] * len(spec["values"])),
                   np.float64)
    return float(np.dot(w / w.sum(), spec["values"]))


class Traffic:
    """One mix under one seed; ``stream`` picks an independent sequence of
    requests of the same seed."""

    def __init__(self, mix: dict, seed: int, stream: int = 0):
        self.mix = mix
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"loop must be open or closed, got {self.loop!r}")
        self.pool = int(mix["pool"])
        self.sizes = sorted(int(q) for q in
                            _values(mix["queries_per_request"]))
        self.ks = sorted(int(k) for k in _values(mix["k"]))
        self.filter = mix.get("filter")
        self.rng = np.random.default_rng([seed, 0x7AF1C, stream])
        self.shapes = np.random.default_rng([SHAPES_SEED, stream])
        self.weights = self._popularity(seed)
        self.masks: dict[int, np.ndarray] = {}

    @property
    def k(self) -> int:
        """The largest k of the mix."""
        return self.ks[-1]

    def _popularity(self, seed: int):
        pop = self.mix.get("popularity", "uniform")
        if pop == "uniform":
            return None
        s = float(pop["zipf"])
        ranks = np.random.default_rng([seed, 0x21BF]).permutation(self.pool)
        w = 1.0 / (ranks + 1.0) ** s
        return w / w.sum()

    def request(self, due: float = 0.0) -> Record:
        """The next request: its shape and options from the fixed
        sequence, its queries and filter from the seed."""
        q = int(_choice(self.mix["queries_per_request"], self.shapes))
        k = int(_choice(self.mix["k"], self.shapes))
        options = {name: _choice(spec, self.shapes)
                   for name, spec in self.mix.get("options", {}).items()}
        category = -1
        if self.filter is not None and \
                self.shapes.random() < float(self.filter["share"]):
            category = int(self.rng.integers(self.filter["categories"]))
        rows = self.rng.choice(self.pool, size=q, replace=False,
                               p=self.weights)
        return Record(rows=rows, due=due, k=k, options=options,
                      category=category)

    def mask(self, category: int, ntotal: int) -> np.ndarray:
        """The (ntotal,) filter mask of ``category``, made once."""
        if category not in self.masks:
            ids = np.arange(ntotal, dtype=np.uint32)
            self.masks[category] = category_of(
                ids, int(self.filter["categories"])) == category
        return self.masks[category]

    def offsets(self, seconds: float) -> np.ndarray:
        """Open loop: the due times of a window's requests, in seconds
        from its start."""
        arrivals = self.mix.get("arrivals")
        if arrivals not in ("poisson", "even"):
            raise ValueError(f"unknown arrivals {arrivals!r}")
        rate = float(self.mix["rate_per_s"])
        phases = np.asarray(self.mix.get("phases", [[seconds, 1.0]]),
                            np.float64).reshape(-1, 2)
        if np.any(phases <= 0):
            raise ValueError("phases need positive lengths and factors")
        # the expected number of arrivals by each phase edge, over the window
        reps = int(np.ceil(seconds / phases[:, 0].sum())) + 1
        edges = np.concatenate([[0.0], np.cumsum(np.tile(phases[:, 0],
                                                         reps))])
        rates = rate * np.tile(phases[:, 1], reps)
        mass = np.concatenate([[0.0], np.cumsum(np.diff(edges) * rates)])
        n = max(1, int(round(float(np.interp(seconds, edges, mass)))))
        if arrivals == "poisson":
            quantiles = (np.arange(n) + 0.5) / n
            order = np.random.default_rng(ARRIVALS_SEED).permutation(n)
            unit = -np.log1p(-quantiles[order])
        else:
            unit = np.ones(n)
        at = np.cumsum(unit)
        at -= at[0]
        return np.interp(at, mass, edges)

    # -- driving an engine ---------------------------------------------------

    def run(self, submit, pool: np.ndarray, seconds: float,
            ntotal: int = 0) -> list[Record]:
        """Drive ``submit(queries, k, **options) -> Future`` for one
        window; returns every request of the window once each has
        answered or the wait past the close has run out. ``ntotal`` is
        the corpus size, for a mix with filters."""
        send = self._sender(submit, pool, ntotal)
        if self.loop == "open":
            return self._open(send, seconds)
        return self._closed(send, seconds)

    def _sender(self, submit, pool, ntotal):
        tracker = _Tracker()

        def send(rec: Record) -> None:
            kw = dict(rec.options)
            if rec.category >= 0:
                kw["filter_mask"] = np.broadcast_to(
                    self.mask(rec.category, ntotal), (len(rec.rows), ntotal))
            rec.sent = time.perf_counter()
            tracker.watch(rec, submit(pool[rec.rows], rec.k, **kw))

        send.tracker = tracker
        return send

    def _open(self, send, seconds):
        start = time.perf_counter()
        for off in self.offsets(seconds):
            rec = self.request(start + float(off))
            wait = rec.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            send(rec)
        send.tracker.wait(time.perf_counter() + WAIT_AFTER_CLOSE_S)
        return send.tracker.records

    def _closed(self, send, seconds):
        close = time.perf_counter() + seconds
        tracker = send.tracker

        def one():
            send(self.request(time.perf_counter()))

        for _ in range(int(self.mix["clients"])):
            one()
        # each client sends its next request once its answer is in
        while True:
            answered = tracker.answered(until=close)
            if time.perf_counter() >= close:
                break
            for _ in range(answered):
                one()
        tracker.wait(close + WAIT_AFTER_CLOSE_S)
        return tracker.records


class _Tracker:
    """The records of a window, completed by their futures' callbacks."""

    def __init__(self):
        self.lock = threading.Condition()
        self.records: list[Record] = []
        self.pending = 0
        self.fresh = 0              # answers not yet seen by ``answered``

    def watch(self, record: Record, future) -> None:
        with self.lock:
            self.records.append(record)
            self.pending += 1

        def done(f):
            record.done = time.perf_counter()
            if f.exception() is not None:
                record.error = repr(f.exception())
            else:
                record.result = f.result()
            with self.lock:
                self.pending -= 1
                self.fresh += 1
                self.lock.notify_all()

        future.add_done_callback(done)

    def answered(self, until: float) -> int:
        """Wait for answers (at most until ``until``); how many came."""
        with self.lock:
            while not self.fresh and time.perf_counter() < until:
                self.lock.wait(timeout=min(0.05, max(
                    0.0, until - time.perf_counter())))
            fresh, self.fresh = self.fresh, 0
            return fresh

    def wait(self, deadline: float) -> None:
        """Until every watched request has answered, or the deadline."""
        with self.lock:
            while self.pending and time.perf_counter() < deadline:
                self.lock.wait(timeout=min(0.05, max(
                    0.0, deadline - time.perf_counter())))
