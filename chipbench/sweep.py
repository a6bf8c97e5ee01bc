"""Find the highest open-loop rate one deployment sustains, on the chip.

    python3 chipbench/sweep.py --config <config> --mix <mix> --seed <n> \\
        --rates 20,30,40 --seconds 15

Builds the configuration's deployment once, warms every fill of every
query bucket the engine can form for the mix, primes it with the mix at the highest rate, then serves one
open-loop window of ``--seconds`` at each rate (the mix's
``rate_per_s`` replaced) and prints a JSON line per rate: p50 and p95
from due time, requests answered, and the backlog trend, the median
latency of the window's last third over that of its first third. A rate
is sustained when every request is answered and the trend stays near 1;
a cell's mix then runs at about four fifths of the highest sustained
rate (``chipbench/README.md``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    import importlib

    import numpy as np

    from chipbench import data, harness, loadgen

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    config = harness.load_json(harness.HERE / "configs"
                               / f"{args.config}.json")
    mix = harness.load_json(harness.HERE / "traffic" / f"{args.mix}.json")
    harness.require_chips(config["chips"])
    harness.enable_compile_cache(ROOT)
    clock = harness.CompileClock()
    family = importlib.import_module(f"chipbench.deploy.{config['family']}")
    index, serve_kw = family.build(config, args.seed)
    pool = np.asarray(data.DeepLike(args.seed, config["data"]).queries(
        mix["pool"]))
    traffic = loadgen.Traffic(mix, args.seed)
    engine = harness.serve_engine(index, serve_kw, traffic)
    warmed = harness.warm(engine, traffic, pool)

    def submit(q, k, **kw):
        return engine.submit(q, k=k, **kw)

    rates = [float(r) for r in args.rates.split(",")]
    # primed at the highest rate, whose batches are the widest
    prime = loadgen.Traffic(dict(mix, rate_per_s=max(rates)), args.seed,
                            stream=1)
    passes = harness.prime(prime, submit, pool, clock, index.ntotal)
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "warm_batches": warmed, "prime_passes": passes,
                      "programs": clock.count, "cache_hits": clock.hits}),
          flush=True)
    for rate in rates:
        before, names = clock.count, len(clock.names)
        engine.metrics.reset()
        records = loadgen.Traffic(dict(mix, rate_per_s=rate),
                                  args.seed).run(submit, pool, args.seconds,
                                                 index.ntotal)
        lat = [(r.done - r.due) * 1e3 for r in records
               if r.result is not None]
        third = max(1, len(lat) // 3)
        stats = engine.metrics.summary()
        print(json.dumps({
            "rate_per_s": rate, "requests": len(records),
            "batches": stats.get("batches"),
            "real_queries": stats.get("real_queries"),
            "padded_queries": stats.get("padded_queries"),
            "answered": len(lat),
            "p50_ms": harness.percentile(lat, 50) if lat else None,
            "p95_ms": harness.percentile(lat, 95) if lat else None,
            "trend": (float(np.median(lat[-third:]))
                      / float(np.median(lat[:third]))) if lat else None,
            "late_p95_ms": harness.percentile(
                [(r.sent - r.due) * 1e3 for r in records], 95),
            "programs": clock.count - before,
            "made": clock.names[names:][:10]}), flush=True)
    engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
