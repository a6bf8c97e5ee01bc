"""Benchmark harness: one module per paper table + roofline readout.

    PYTHONPATH=src python -m benchmarks.run [--scale quick|default|full]
        [--only recall,scale,ablation,timings,roofline,stage1,stage2,ivf,
               serve]
    PYTHONPATH=src python -m benchmarks.run --smoke [--specs PQ8x64,...]

Prints ``name,us_per_call,derived`` CSV rows. ``--smoke`` is the CI path:
it exercises ``Index.search`` on ALL registered scan backends (xla /
onehot / pallas-interpret) over tiny factory-built indexes — flat AND
IVF-wrapped at full probe — and EXITS NON-ZERO if any backend disagrees
with the xla oracle (every mismatch is still reported before exiting, so
one run surfaces all drift). Under the candidate-generator resolution
this covers both stage-1 engines and their gathered (IVF) faces: xla and
pallas route through the streaming scan+top-L / gathered scan (bit-exact
pair), onehot through the materialized full-matrix scan — and all three
stage-2 rerankers: xla/pallas resolve the streaming rerank engine
(chunked/fused table decode for PQ, cross-query dedup for UNQ), onehot
the materialized vmap reranker. ``--only stage1`` / ``--only stage2`` /
``--only ivf`` / ``--only serve`` write ``BENCH_stage1.json`` /
``BENCH_stage2.json`` / ``BENCH_ivf.json`` / ``BENCH_serve.json``
(throughput + peak-memory / recall / serving-latency trajectories).

Failures in the ``--only``/full bench loop are reported per bench and
the process exits non-zero at the end if any bench failed — CI can no
longer green-light a broken harness.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

#: smoke specs: name -> (factory string, train kwargs). IVF at
#: nprobe == nlist so backend parity is exact, not probe-dependent; the
#: Residual spec additionally exercises the IVFADC correction streams
#: (per-row cross bias + per-(query, cell) bias) and the extended-table
#: residual reranker on every backend.
SMOKE_SPECS = {
    "PQ8x64,Rerank64": dict(iters=4),
    "IVF32,NProbe32,PQ8x64,Rerank64": dict(iters=4),
    "IVF32,NProbe32,Residual,PQ8x64,Rerank64": dict(iters=4),
    "UNQ8x64,Rerank64": dict(epochs=2, log_every=1000),
}


def smoke(specs=None) -> list[str]:
    """Tiny end-to-end pass over the unified index API, per scan backend.

    Returns the list of parity-failure descriptions (empty = all green);
    every backend is checked even after a failure so one run reports all
    drift. ``REPRO_SMOKE_FORCE_FAIL=1`` injects a synthetic failure — the
    hook the exit-code regression test uses.
    """
    import numpy as np
    import jax.numpy as jnp

    from benchmarks import common
    from repro.index import available_scan_backends, index_factory

    ds = common.dataset("deep", "quick")
    queries = jnp.asarray(ds.queries[:64])
    failures: list[str] = []

    for spec, train_kw in (SMOKE_SPECS if specs is None else
                           {s: SMOKE_SPECS[s] for s in specs}).items():
        spec_failures_before = len(failures)
        index = index_factory(spec, dim=ds.dim)
        index.train(ds.train, **train_kw)
        index.add(ds.base)
        want = None
        for backend in sorted(available_scan_backends()):
            index.backend = backend
            _, got = index.search(queries, 10)           # warmup/compile
            t0 = time.time()
            _, got = index.search(queries, 10)
            got.block_until_ready()
            us = (time.time() - t0) * 1e6 / queries.shape[0]
            if backend == "xla":
                want = np.asarray(got)
            common.emit(f"smoke/{spec}/search[{backend}]", us,
                        f"ntotal={index.ntotal}")
        for backend in available_scan_backends():
            index.backend = backend
            _, got = index.search(queries, 10)
            got = np.asarray(got)
            if backend in ("xla", "pallas"):
                if not np.array_equal(got, want):   # bit-exact scan pair
                    failures.append(
                        f"{spec}: backend {backend!r} disagrees with xla")
            else:   # reassociated reductions may swap exact d2 ties
                overlap = np.mean([len(set(a) & set(b)) / len(a)
                                   for a, b in zip(got, want)])
                if overlap < 0.99:
                    failures.append(
                        f"{spec}: backend {backend!r} overlap "
                        f"{overlap:.3f}")
        if len(failures) > spec_failures_before:
            for f in failures[spec_failures_before:]:
                print(f"# SMOKE-FAIL {f}")
        else:
            print(f"# smoke {spec}: all backends agree with xla")
    if os.environ.get("REPRO_SMOKE_FORCE_FAIL", "") not in ("", "0"):
        failures.append("forced failure (REPRO_SMOKE_FORCE_FAIL)")
    return failures


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick",
                    choices=["quick", "default", "full"])
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benches")
    ap.add_argument("--smoke", action="store_true",
                    help="CI path: Index.search on every scan backend; "
                         "exits non-zero on any parity failure")
    ap.add_argument("--specs", default=None,
                    help="semicolon-separated subset of smoke specs "
                         f"(known: {list(SMOKE_SPECS)})")
    args = ap.parse_args(argv)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    if args.smoke:
        failures = smoke(args.specs.split(";") if args.specs else None)
        if failures:
            print(f"# smoke: {len(failures)} parity failure(s)")
            sys.exit(1)
        return

    from benchmarks import (bench_ablation, bench_ivf, bench_recall,
                            bench_roofline, bench_scale, bench_serve,
                            bench_stage1, bench_stage2, bench_timings)

    benches = {
        "timings": lambda: bench_timings.run(args.scale),
        "recall": lambda: bench_recall.run(args.scale),
        "scale": lambda: bench_scale.run(args.scale),
        "ablation": lambda: bench_ablation.run(args.scale),
        "roofline": lambda: bench_roofline.run(),
        "stage1": lambda: bench_stage1.run(args.scale),
        "stage2": lambda: bench_stage2.run(args.scale),
        "ivf": lambda: bench_ivf.run(args.scale),
        "serve": lambda: bench_serve.run(args.scale),
    }
    selected = (args.only.split(",") if args.only else list(benches))

    failed = []
    for name in selected:
        t0 = time.time()
        try:
            benches[name]()
            print(f"# {name}: done in {time.time() - t0:.1f}s")
        except Exception as e:  # noqa: BLE001 — keep the harness running
            failed.append(name)
            print(f"# {name}: FAILED {type(e).__name__}: {e}")
            traceback.print_exc()
    if failed:
        print(f"# benches failed: {','.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
