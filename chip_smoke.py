#!/usr/bin/env python3
"""On-chip smoke of the UNQ search path, through the entry points a user
calls: ``index_factory`` -> ``train`` -> ``add`` -> ``Index.search`` ->
``ServeEngine``, at the paper's widths and a Deep10M-scale corpus.

    python chip_smoke.py              # one chip: flat UNQ + residual IVF
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

One chip. A Deep-like 96-d corpus of 10^7 vectors, a 10^5-vector train
set and a query set are drawn from ``--seed`` by the generator of
``data.descriptors.make_synthetic_dataset`` (``_mixture`` + ``_deep_like``),
the base 2^17 vectors at a time on host threads, so the float base never
exists whole. Two indexes are trained and filled from the same corpus:

* ``UNQ8x256,Rerank500`` — encoder/decoder 2x1024, d_c=256, K=256,
  8 B/vector (``configs/unq_paper.DEEP_8B``): fused scan+top-L stage 1
  (``adc_scan_topl``), dedup decoder rerank;
* ``IVF4096,Residual,PQ8x256,Rerank500`` — the gathered and the
  cell-batched dispatch stage-1 faces (``adc_gather_topl``,
  ``adc_dispatch_topl``).

Each index serves a few dozen requests (1-16 queries, k=100) through
``ServeEngine`` with the backend left at ``auto``. The run fails when:
the backend does not resolve to ``pallas``; a kernel would run in
interpret mode; a served result differs bit-wise from ``Index.search``
of that request alone; for a query sample the stage-1 top-L differs
from the ``kernels/ref.py`` oracle on the same tables and codes (run on
the chip) or from a numpy witness on the host; the final top-k differs
from the exact d1 rerank oracle (the materialized decode) over those
candidates; or the two IVF faces disagree.

Four chips (``--chips 4``). A UNQ index at the same widths over 4 x
10^7 codes, weights and codes drawn from ``--seed`` (not trained or
encoded), wrapped in ``ShardedIndex(num_shards=4, placement="device")``
— 10^7 codes resident on each chip — must match the flat search of the
same index on one chip bit-wise, for Deep-like queries.

Prints phase timings (wall seconds, with the compile seconds inside each
phase apart), recall@1/10/100 against exact float kNN for the query
sample, the device's ``peak_bytes_in_use`` and the stage-1 kernel's
``memory_analysis()`` temp bytes, then as its last line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import collections
import concurrent.futures
import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

K = 100
RERANK = 500
UNQ_SPEC = f"UNQ8x256,Rerank{RERANK}"
IVF_SPEC = f"IVF4096,Residual,PQ8x256,Rerank{RERANK}"
SAMPLE = 8                # queries per oracle check (one query block)
N_BASE = 10_000_000       # corpus vectors per chip (Deep10M scale)
N_TRAIN = 100_000
N_QUERY = 512
EPOCHS = 1                # UNQ epochs: N_TRAIN / 256 = 390 steps
REQUESTS = 32             # served requests per index
PART = 1 << 17            # corpus vectors per generator call
CHUNK = 1 << 20           # corpus vectors added per step (every add
                          # grows the code buffers, a few compiles each)
DIM, LATENT, CENTERS = 96, 24, 512   # make_synthetic_dataset's deep mixture
WORKERS = max(1, min(16, (os.cpu_count() or 2) - 2))
_IMAX = np.iinfo(np.int32).max
_HIGHEST = jax.lax.Precision.HIGHEST


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int) -> list:
    """The chip path or nothing: no CPU fallback."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU visible (platform "
                 f"{devices[0].platform!r}); this check runs only on a "
                 "TPU")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but {len(devices)} TPU "
                 "device(s) visible")
    return devices


class Run:
    """Phase clock, compile clock and the list of failed checks."""

    def __init__(self):
        self.failures: list[str] = []
        self.phases: list[tuple[str, float, float, int]] = []
        self._compile_s = 0.0
        self._compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile_s += duration
            self._compiles += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, n0, t0 = self._compile_s, self._compiles, time.perf_counter()
        try:
            yield
        finally:
            wall = time.perf_counter() - t0
            self.phases.append((name, wall, self._compile_s - c0,
                                self._compiles - n0))
            log(f"phase {name}: wall_s={wall!r} compile_s="
                f"{self._compile_s - c0!r} compiles={self._compiles - n0}")

    def check(self, ok: bool, what: str, detail=None) -> None:
        log(f"check {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failures.append(what)
            if detail is not None:
                log(f"  {detail()}")

    def fail(self, what: str) -> None:
        log(f"FAIL {what}")
        self.failures.append(what)


def same(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and np.array_equal(a, b)


def diff(got_s, got_i, want_s, want_i) -> str:
    """Where two sorted (scores, ids) results part: the counts that tell
    a tie order from a difference in the scores themselves."""
    gs, gi, ws, wi = map(np.asarray, (got_s, got_i, want_s, want_i))
    if gs.shape != ws.shape:
        return f"shapes {gs.shape} vs {ws.shape}"
    fin = np.isfinite(gs) & np.isfinite(ws)
    ties = int(np.sum(np.diff(ws, axis=1) == 0))
    return (f"ids differ at {int(np.sum(gi != wi))}, scores at "
            f"{int(np.sum(gs != ws))} of {gs.size} (max |diff| "
            f"{float(np.max(np.abs(np.where(fin, gs - ws, 0))))!r}); "
            f"same id sets per row: "
            f"{all(set(a) == set(b) for a, b in zip(gi, wi))}; "
            f"tied neighbours in the reference: {ties}")


def host_topl(codes, lut, topl: int):
    """Stage-1 top-L of one query on the host: the left-to-right f32
    chain of ``ref.adc_scan_ref``, then a (score, id) lexsort."""
    codes, lut = np.asarray(codes, np.int64), np.asarray(lut, np.float32)
    s = lut[0][codes[:, 0]]
    for m in range(1, lut.shape[0]):
        s = s + lut[m][codes[:, m]]
    order = np.lexsort((np.arange(len(s)), s))[:topl]
    return s[order], order.astype(np.int32)


def host_gather_topl(codes, rows, gids, luts, rowbias, topl: int):
    """Gathered stage-1 top-L on the host: per slot the left-to-right f32
    chain of ``ref.adc_gather_topl_ref`` plus the slot bias, pad and
    +inf slots canonicalized, then a (score, gid) lexsort per query."""
    codes, rows, gids = map(np.asarray, (codes, rows, gids))
    luts = np.asarray(luts, np.float32)
    rowbias = np.asarray(rowbias, np.float32)
    out_s, out_g = [], []
    for j in range(rows.shape[0]):
        c = codes[rows[j]].astype(np.int64)                    # (W, M)
        s = luts[j, 0][c[:, 0]]
        for m in range(1, luts.shape[1]):
            s = s + luts[j, m][c[:, m]]
        s = np.where(gids[j] == _IMAX, np.float32(np.inf), s + rowbias[j])
        g = np.where(np.isposinf(s), _IMAX, gids[j])
        order = np.lexsort((g, s))[:topl]
        out_s.append(s[order])
        out_g.append(g[order].astype(np.int32))
    return np.stack(out_s), np.stack(out_g)


def explain_slots(got_s, got_i, want_s, codes, rows, gids, luts, rowbias,
                  limit: int = 4) -> str:
    """For the first entries where two gathered top-L results part: both
    scores, the slot's per-m table entries and bias, and the f32 chain
    recomputed on the host (all as float32 hex)."""
    got_s, got_i, want_s = map(np.asarray, (got_s, got_i, want_s))
    codes, rows, gids = map(np.asarray, (codes, rows, gids))
    luts = np.asarray(luts, np.float32)
    rowbias = np.asarray(rowbias, np.float32)
    lines = []
    for j, col in list(zip(*np.nonzero(got_s != want_s)))[:limit]:
        slot = int(np.flatnonzero(gids[j] == got_i[j, col])[0])
        code = codes[rows[j, slot]].astype(np.int64)
        parts = np.array([luts[j, m, code[m]] for m in range(len(code))],
                         np.float32)
        chain = parts[0]
        for p in parts[1:]:
            chain = np.float32(chain + p)
        lines.append(
            f"q{j} col {col} gid {int(got_i[j, col])}: got "
            f"{float(got_s[j, col]).hex()} want {float(want_s[j, col]).hex()}"
            f" host {float(np.float32(chain + rowbias[j, slot])).hex()}; "
            f"parts {[float(p).hex() for p in parts]} bias "
            f"{float(rowbias[j, slot]).hex()}")
    return "\n  ".join(lines)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

class Corpus:
    """Deep-like descriptors from ``--seed`` through the generator of
    ``make_synthetic_dataset`` (same latent mixture for the same seed).
    Train and query sets come off ``default_rng(seed)`` in turn; base
    part p (PART vectors) off its own child seed, so parts are drawn on
    worker threads and the base never exists whole on the host."""

    def __init__(self, seed: int):
        from repro.data.descriptors import _mixture
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.mix = _mixture(self.rng, DIM, CENTERS, LATENT)

    def draw(self, n: int) -> np.ndarray:
        from repro.data.descriptors import _deep_like
        return _deep_like(self.rng, n, DIM, LATENT, *self.mix)

    def part(self, p: int, n: int) -> np.ndarray:
        from repro.data.descriptors import _deep_like
        rng = np.random.default_rng(
            np.random.SeedSequence(self.seed, spawn_key=(p,)))
        return _deep_like(rng, n, DIM, LATENT, *self.mix)

    def chunks(self, n_base: int, chunk: int):
        """Yield (offset, (rows, D) f32) over n_base base vectors, chunk
        rows at a time, drawn by WORKERS threads a bounded window ahead."""
        parts = iter([(p, min(PART, n_base - lo))
                      for p, lo in enumerate(range(0, n_base, PART))])
        with concurrent.futures.ThreadPoolExecutor(WORKERS) as pool:
            pending = collections.deque()

            def refill():
                for p, n in parts:
                    pending.append(pool.submit(self.part, p, n))
                    if len(pending) >= 2 * WORKERS:
                        return

            refill()
            buf, offset = [], 0
            while pending:
                buf.append(pending.popleft().result())
                refill()
                if sum(len(b) for b in buf) >= chunk or not pending:
                    x = np.concatenate(buf)
                    buf = []
                    yield offset, x
                    offset += len(x)


class ExactKnn:
    """Running exact float top-k of a query sample over streamed base
    chunks (full f32 passes; +||q||^2 dropped, ranking-invariant)."""

    def __init__(self, queries, k: int):
        self.q = jnp.asarray(queries)
        self.k = k
        self.d = jnp.full((self.q.shape[0], k), jnp.inf, jnp.float32)
        self.i = jnp.zeros((self.q.shape[0], k), jnp.int32)

    @staticmethod
    @jax.jit
    def _merge(q, x, offset, d, i):
        dx = jnp.sum(x * x, axis=1)[None, :] - 2.0 * jnp.dot(
            q, x.T, precision=_HIGHEST)
        nd, ni = jax.lax.top_k(-dx, d.shape[1])
        cd = jnp.concatenate([d, -nd], axis=1)
        ci = jnp.concatenate([i, ni.astype(jnp.int32) + offset], axis=1)
        neg, order = jax.lax.top_k(-cd, d.shape[1])
        return -neg, jnp.take_along_axis(ci, order, axis=1)

    def update(self, x, offset: int) -> None:
        self.d, self.i = self._merge(self.q, x, jnp.int32(offset),
                                     self.d, self.i)


def fill(indexes, corpus, n_base: int, chunk: int, knn):
    """Add the corpus to every index chunk by chunk; each chunk is moved
    to the device once and dropped after use. Returns per-index add
    seconds."""
    spent = {name: 0.0 for name in indexes}
    for lo, x_host in corpus.chunks(n_base, chunk):
        x = jnp.asarray(x_host)
        for name, index in indexes.items():
            t0 = time.perf_counter()
            index.add(x)
            index.codes.block_until_ready()
            spent[name] += time.perf_counter() - t0
        if knn is not None:
            knn.update(x, lo)
    return spent


#: request sizes: both ends of 1-16, bucket edges and odd sizes, few
#: enough that solo searches compile a handful of programs
REQUEST_SIZES = (1, 3, 8, 13, 16)


def make_requests(rng, n_requests: int, n_queries: int):
    """Query row ids per request."""
    sizes = rng.choice(REQUEST_SIZES, size=n_requests)
    return [rng.choice(n_queries, size=int(s), replace=False)
            for s in sizes]


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def check_backend(run, index, label: str) -> None:
    from repro.index import resolve_scan_backend
    from repro.kernels import ops
    impl = resolve_scan_backend(index.backend)
    run.check(index.backend == "auto" and impl == "pallas",
              f"{label}: backend auto resolves to pallas (got {impl!r})")
    run.check(not ops._interpret(), f"{label}: Pallas kernels compiled, "
              "not interpreted")


def oracle_topk(d1, cand, k: int):
    neg, order = jax.lax.top_k(-d1, k)
    return -neg, jnp.take_along_axis(cand, order, axis=1)


def check_flat(run, index, queries) -> None:
    """Stage 1 of the fused kernel == ref oracle; final top-k == the
    exact d1 oracle over the same candidates."""
    from repro.index import VmapRerank, candidate_generator_for
    from repro.kernels import ref
    q = jnp.asarray(queries)
    luts = index._build_luts(q)
    gen = candidate_generator_for(index.backend)
    got_s, got_i = gen.topl(index.codes, luts, index.bias, topl=RERANK)
    # one query per oracle call: its (N, M) gathered f32 table entries
    # take N x 128 x 4 bytes in the chip's tiled layout (5 GB at 10^7)
    oracle = jax.jit(ref.adc_scan_topl_ref, static_argnums=3)
    want = [oracle(index.codes, luts[j:j + 1], index.bias, RERANK)
            for j in range(q.shape[0])]
    want_s = jnp.concatenate([s for s, _ in want])
    want_i = jnp.concatenate([i for _, i in want])
    run.check(same(got_s, want_s) and same(got_i, want_i),
              "flat stage-1 top-L == ref.adc_scan_topl_ref on chip",
              lambda: diff(got_s, got_i, want_s, want_i))
    h_s, h_i = host_topl(index.codes, luts[0], RERANK)
    run.check(same(got_s[0], h_s) and same(got_i[0], h_i),
              "flat stage-1 top-L == host f32 chain + lexsort (query 0)",
              lambda: diff(got_s[:1], got_i[:1], h_s[None], h_i[None]))
    d, i = index.search(q, K)
    d_o, i_o = oracle_topk(VmapRerank().distances(index, q, got_i),
                           got_i, K)
    run.check(same(d, d_o) and same(i, i_o),
              "flat final top-k == exact d1 rerank oracle",
              lambda: diff(d, i, d_o, i_o))


def check_ivf(run, ivf, queries) -> None:
    """Gathered face == ref oracle == host witness on the same plan;
    dispatch face == gathered face; final top-k == exact residual d1
    oracle."""
    from repro.index import ResidualRerank
    from repro.kernels import ops, ref
    q = jnp.asarray(queries)
    probe, cd = ivf._probe_with_dists(q, ivf.nprobe)
    rows, gids, cells = ivf._probe_plan(np.asarray(probe))
    rows, gids = jnp.asarray(rows), jnp.asarray(gids)
    rowbias = ivf._plan_rowbias(rows, gids, ivf.bias, None, q.shape[0],
                                slot_cells=cells, cell_bias=cd)
    luts = ivf._stage1_luts(q, probe)
    topl = min(RERANK, rows.shape[1])
    got_s, got_i = ops.adc_gather_topl(ivf.codes, rows, gids, luts,
                                       topl=topl, rowbias=rowbias,
                                       impl="pallas")
    want_s, want_i = jax.jit(ref.adc_gather_topl_ref, static_argnums=5)(
        ivf.codes, rows, gids, luts, rowbias, topl)
    h_s, h_i = host_gather_topl(ivf.codes, rows, gids, luts, rowbias, topl)
    plan = (ivf.codes, rows, gids, luts, rowbias)
    run.check(same(got_s, want_s) and same(got_i, want_i),
              "IVF gathered stage-1 top-L == ref.adc_gather_topl_ref "
              "on chip", lambda: diff(got_s, got_i, want_s, want_i) + "\n  "
              + explain_slots(got_s, got_i, want_s, *plan))
    run.check(same(got_s, h_s) and same(got_i, h_i),
              "IVF gathered stage-1 top-L == host f32 chain + bias + "
              "lexsort", lambda: diff(got_s, got_i, h_s, h_i) + "\n  "
              + explain_slots(got_s, got_i, h_s, *plan))
    run.check(same(want_s, h_s) and same(want_i, h_i),
              "ref.adc_gather_topl_ref on chip == host witness",
              lambda: diff(want_s, want_i, h_s, h_i) + "\n  "
              + explain_slots(want_s, want_i, h_s, *plan))
    pool = ivf._dispatch_pool(q, probe, cd, None, topl=RERANK)
    run.check(pool is not None and same(pool[0][:, :topl], got_s)
              and same(pool[1][:, :topl], got_i),
              "IVF dispatch-face pool == gathered-face pool")
    d_g, i_g = ivf.search(q, K, use_dispatch=False)
    d_d, i_d = ivf.search(q, K, use_dispatch=True)
    run.check(same(d_g, d_d) and same(i_g, i_d),
              "IVF search: dispatch face == gathered face")
    valid = jnp.isfinite(got_s)
    rows_c = jnp.take(ivf._pos_dev, jnp.where(valid, got_i, 0))
    d1 = jnp.where(valid, ResidualRerank._vmap_residual(ivf, q, rows_c),
                   jnp.inf)
    d_o, i_o = oracle_topk(d1, got_i, K)
    i_o = jnp.where(jnp.isposinf(d_o), -1, i_o)
    run.check(same(d_g, d_o) and same(i_g, i_o),
              "IVF final top-k == exact residual d1 rerank oracle",
              lambda: diff(d_g, i_g, d_o, i_o))


def serve(run, index, label: str, queries, requests, knn_i):
    """Serve every request through ServeEngine, wait on every future,
    compare each with searching it alone; returns recall of the served
    results against exact kNN."""
    from repro.core.search import recall_at_k
    from repro.serve import ServeConfig, ServeEngine
    engine = ServeEngine(index, ServeConfig(default_k=K))
    served = [None] * len(requests)
    with run.phase(f"{label}.serve"):
        futures = [engine.submit(queries[r], k=K) for r in requests]
        for j, f in enumerate(futures):
            served[j] = f.result(timeout=900)
        engine.close()
    summary = engine.metrics.summary()
    log(f"{label}.serve metrics: requests={summary['requests']} "
        f"batches={summary['batches']} p50_ms={summary['p50_ms']!r} "
        f"p95_ms={summary['p95_ms']!r} (host clock; single pass, not a "
        "benchmark)")
    with run.phase(f"{label}.solo"):
        drifted = []
        for r, (d, i) in zip(requests, served):
            d_s, i_s = index.search(jnp.asarray(queries[r]), K)
            if not (same(d, d_s) and same(i, i_s)):
                drifted.append(f"Q={len(r)}: {diff(d, i, d_s, i_s)}")
    run.check(not drifted, f"{label}: {len(requests)} served requests "
              f"bit-identical to solo Index.search ({len(drifted)} "
              "drifted)", lambda: "\n  ".join(drifted))
    ids = np.concatenate([i for _, i in served])
    rows = np.concatenate(requests)
    rec = recall_at_k(jnp.asarray(ids), jnp.asarray(knn_i[rows, 0]))
    log(f"{label} recall vs exact float kNN over {len(rows)} served "
        f"queries: " + " ".join(f"{k}={v!r}" for k, v in rec.items()))


def stage1_temp_bytes(index, q: int) -> int:
    """Compiled stage-1 kernel at this index's shapes: temp bytes."""
    from repro.kernels import tune
    from repro.kernels.topl_scan import adc_scan_topl_pallas
    n, m = index.codes.shape
    cfg = tune.best_config("adc_scan_topl", "pallas", n=n, q=q,
                           topl=RERANK)
    bn, bq = cfg["block_n"], tune.align(q, cap=cfg["block_q"])
    sds = jax.ShapeDtypeStruct
    compiled = adc_scan_topl_pallas.lower(
        sds((-(-n // bn) * bn, m), jnp.uint8),
        sds((-(-q // bq) * bq, m, 256), jnp.float32),
        sds((-(-n // bn) * bn,), jnp.float32),
        topl=RERANK, n_valid=n, block_n=bn, block_q=bq).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def one_chip(run, seed: int) -> None:
    from repro.configs.unq_paper import DEEP_8B
    from repro.index import index_factory

    corpus = Corpus(seed)
    with run.phase("data"):
        train = corpus.draw(N_TRAIN)
        queries = corpus.draw(N_QUERY)
    unq = index_factory(UNQ_SPEC, dim=96)
    ivf = index_factory(IVF_SPEC, dim=96)
    run.check(unq.cfg == DEEP_8B,
              f"{UNQ_SPEC} has the paper's DEEP_8B widths")
    check_backend(run, unq, "UNQ")
    check_backend(run, ivf, "IVF")
    with run.phase("unq.train"):
        unq.train(train, epochs=EPOCHS, log_every=10 ** 9)
        jax.block_until_ready(unq.params)
    log(f"unq.train steps={EPOCHS * (N_TRAIN // 256)}")
    with run.phase("ivf.train"):
        ivf.train(train)
        jax.block_until_ready(ivf.coarse)
    knn = ExactKnn(queries, K)
    with run.phase("add"):
        spent = fill({"unq": unq, "ivf": ivf}, corpus, N_BASE, CHUNK, knn)
    log("add seconds per index: " + " ".join(
        f"{k}={v!r}" for k, v in spent.items()))
    log(f"ntotal unq={unq.ntotal} ivf={ivf.ntotal}; codes on "
        f"{unq.codes.sharding.device_set}")
    knn_i = np.asarray(knn.i)

    rng = np.random.default_rng(seed)
    requests = make_requests(rng, REQUESTS, N_QUERY)
    with run.phase("unq.check"):
        check_flat(run, unq, queries[:SAMPLE])
    serve(run, unq, "unq", queries, requests, knn_i)
    with run.phase("ivf.check"):
        check_ivf(run, ivf, queries[:SAMPLE])
    serve(run, ivf, "ivf", queries, requests, knn_i)
    log(f"stage-1 adc_scan_topl temp_size_in_bytes at N={unq.ntotal}, "
        f"Q=128: {stage1_temp_bytes(unq, 128)}")


def four_chips(run, seed: int) -> None:
    from repro.configs.unq_paper import DEEP_8B
    from repro.core import unq as unq_model
    from repro.index import ShardedIndex, UNQIndex

    n_base = 4 * N_BASE
    with run.phase("data"):
        queries = Corpus(seed).draw(N_QUERY)
        # the paper's widths with weights and the 4 x 10^7 codes drawn
        # from the seed: the sharded == flat contract is over the code
        # database, whatever encoded it (encoding 4 x 10^7 vectors would
        # take most of the run)
        params, state = unq_model.init(jax.random.PRNGKey(seed), DEEP_8B)
        codes = jax.random.randint(
            jax.random.PRNGKey(seed + 1), (n_base, DEEP_8B.num_codebooks),
            0, DEEP_8B.codebook_size).astype(jnp.uint8)
        codes.block_until_ready()
    unq = UNQIndex.from_trained(params, state, DEEP_8B, codes=codes,
                                rerank=RERANK)
    check_backend(run, unq, "UNQ")
    sharded = ShardedIndex(unq, num_shards=4, placement="device")
    run.check(sharded.resolved_placement == "device",
              "ShardedIndex placement resolves to device")
    rng = np.random.default_rng(seed)
    with run.phase("sharded.place"):
        placed = sharded._placed_shards(unq.bias)
        placed.codes.block_until_ready()
    shards = sorted((s.device.id, s.data.shape[0])
                    for s in placed.codes.addressable_shards)
    log(f"code shards (device id, rows): {shards}")
    run.check(len({d for d, _ in shards}) == 4
              and all(r == -(-n_base // 4) for _, r in shards),
              "each of the 4 shards resident on its own device")
    for size in (1, 8, 16, 64):
        q = jnp.asarray(queries[rng.choice(N_QUERY, size,
                                           replace=False)])
        with run.phase(f"sharded.search.q{size}"):
            d_s, i_s = sharded.search(q, K)
            np.asarray(d_s)
        with run.phase(f"flat.search.q{size}"):
            d_f, i_f = unq.search(q, K)
            np.asarray(d_f)
        run.check(same(d_s, d_f) and same(i_s, i_f),
                  f"sharded (4 chips) == flat (1 chip) search, Q={size}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus, queries and requests")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    from repro.utils.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {devices}")
    run = Run()
    try:
        (four_chips if args.chips == 4 else one_chip)(run, args.seed)
    except Exception:                 # noqa: BLE001 — report, exit non-zero
        run.fail(f"run aborted:\n{traceback.format_exc()}")

    stats = devices[0].memory_stats() or {}
    log(f"device 0 peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    log("phases (name, wall_s, compile_s, compiles): "
        + json.dumps(run.phases))
    if run.failures:
        log(f"chip_smoke: {len(run.failures)} check(s) failed")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
