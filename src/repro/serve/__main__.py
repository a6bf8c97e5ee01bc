"""CLI driver: `python -m repro.serve [--smoke]`.

Default mode runs a small demo trace against a quick index and prints
the metrics summary. ``--smoke`` is the CI gate: a fixed, seeded
arrival trace over flat and IVF indexes asserting (a) batched results
are bitwise-equal to each request searched alone, (b) zero deadline
misses at quick scale under a generous budget, (c) warm-up recorded
cold-compile lines so the timed trace never pays a jit. Non-zero exit
on any drift.

Both modes pin ``Scan(xla)``: they are CPU gates of the serving logic
(batching, parity, deadlines) at toy sizes. The on-chip path — the
``auto`` backend resolving to the compiled Pallas kernels — is driven by
``chip_smoke.py`` at the repository root.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.index import index_factory
from repro.serve import ServeConfig, ServeEngine
from repro.utils.compile_cache import enable_compile_cache

_DIM = 32


def _build(spec: str, n_base: int = 4000, n_train: int = 1500):
    rng = np.random.default_rng(0)
    train = rng.normal(size=(n_train, _DIM)).astype(np.float32)
    base = rng.normal(size=(n_base, _DIM)).astype(np.float32)
    ix = index_factory(spec, _DIM)
    ix.train(train, iters=4)
    ix.add(base)
    return ix


def _trace(rng, ntotal: int, n_requests: int, ivf: bool):
    """A deterministic heterogeneous request mix: widths, ks, per-request
    nprobe (IVF), and sparse filter masks."""
    reqs = []
    for t in range(n_requests):
        q = int(rng.integers(1, 5))
        r = {"queries": rng.normal(size=(q, _DIM)).astype(np.float32),
             "k": int(rng.integers(1, 20))}
        if ivf and t % 3 == 1:
            r["nprobe"] = int(rng.integers(1, 8))
        if t % 4 == 2:
            r["filter_mask"] = rng.random((q, ntotal)) > 0.5
        reqs.append(r)
    return reqs


def _solo(index, r):
    kw = {}
    if r.get("nprobe") is not None:
        kw["nprobe"] = r["nprobe"]
    if r.get("filter_mask") is not None:
        kw["filter_mask"] = r["filter_mask"]
    d, i = index.search(r["queries"], r["k"], **kw)
    return np.asarray(d), np.asarray(i)


def _check_parity(index, engine, requests, label: str) -> int:
    bad = 0
    for group_lo in range(0, len(requests), 8):
        group = requests[group_lo:group_lo + 8]
        got = engine.search_requests(group)
        for r, (d, i) in zip(group, got):
            d_ref, i_ref = _solo(index, r)
            if not (np.array_equal(d, d_ref) and np.array_equal(i, i_ref)):
                bad += 1
                print(f"PARITY DRIFT [{label}] request k={r['k']} "
                      f"q={r['queries'].shape[0]}", file=sys.stderr)
    return bad


def smoke() -> int:
    failures = 0
    for spec, ivf in (("PQ4x16,Rerank32,Scan(xla)", False),
                      ("PQ4x16,IVF32,NProbe4,Rerank32,Scan(xla)", True)):
        index = _build(spec)
        engine = ServeEngine(index, ServeConfig(
            max_batch_queries=32, linger_ms=1.0, default_k=10))
        # masks=True: the trace carries filter_mask requests, whose
        # (Q, ntotal) operand traces a different program per bucket
        cold = engine.warmup(buckets=(8, 16, 32), ks=(16,), masks=True)
        print(f"[{spec}] cold-compile ms: "
              + ", ".join(f"{k}={v:.1f}" for k, v in cold.items()))
        rng = np.random.default_rng(7)
        requests = _trace(rng, index.ntotal, 24, ivf)
        failures += _check_parity(index, engine, requests, spec)

        # async trace under a generous deadline: zero misses expected
        futures = [engine.submit(**r, deadline_ms=10_000.0)
                   for r in _trace(rng, index.ntotal, 16, ivf)]
        for f in futures:
            f.result(timeout=60)
        engine.close()
        s = engine.metrics.summary()
        print(f"[{spec}] requests={s['requests']} p50={s['p50_ms']:.2f}ms "
              f"p95={s['p95_ms']:.2f}ms misses={s['deadline_misses']} "
              f"batches={s['batches']} overflows={s['dispatch_overflows']}")
        if s["deadline_misses"] != 0:
            print(f"SMOKE FAIL [{spec}]: {s['deadline_misses']} deadline "
                  "misses under a 10s budget", file=sys.stderr)
            failures += 1
    print("serve smoke:", "FAIL" if failures else "OK")
    return 1 if failures else 0


def demo(n_requests: int, rate_hz: float) -> int:
    index = _build("PQ4x16,IVF32,NProbe4,Rerank32,Scan(xla)")
    engine = ServeEngine(index, ServeConfig(max_batch_queries=32,
                                            default_deadline_ms=50.0))
    engine.warmup(buckets=(8, 16, 32))
    rng = np.random.default_rng(1)
    futures = []
    for r in _trace(rng, index.ntotal, n_requests, ivf=True):
        futures.append(engine.submit(**r))
        time.sleep(1.0 / rate_hz)
    for f in futures:
        f.result(timeout=60)
    engine.close()
    for key, val in engine.metrics.summary().items():
        print(f"  {key}: {val}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.serve",
                                 description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="deterministic CI gate: parity + zero-miss")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="demo arrival rate (req/s)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    return smoke() if args.smoke else demo(args.requests, args.rate)


if __name__ == "__main__":
    sys.exit(main())
