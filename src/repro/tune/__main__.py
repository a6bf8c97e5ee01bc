"""``python -m repro.tune`` — run the kernel block-config sweep.

Modes:

  (default)     sweep the full bucket ladder (``FULL_BUCKETS``) and fold
                the winners into the cache (``TUNE_CACHE.json`` at the
                repo root, or ``--out`` / ``REPRO_TUNE_CACHE``);
  --quick       the ci.sh smoke: one bucket per kernel at the quick-scale
                bench shapes, then three self-checks —
                  roundtrip     save -> reload reproduces the document,
                  determinism   an immediate re-sweep (winners seeded as
                                incumbents behind the hysteresis margin)
                                reproduces the same configs,
                  schema drift  a cache with a foreign schema_version
                                MUST raise ``TuneCacheError``;
                any failed self-check exits non-zero;
  --validate    load + schema-check an existing cache, print the
                fingerprint, exit non-zero on drift.

The sweep never runs Pallas impls in interpret mode (winners measured
there would poison the cache for the real device) — those entries are
skipped with a visible reason.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile

from repro.kernels import tune
from repro.tune import FULL_BUCKETS, QUICK_BUCKETS, run_sweep
from repro.utils.compile_cache import enable_compile_cache


def _check_roundtrip(doc: dict, path: pathlib.Path) -> list[str]:
    reloaded = tune.load_cache(path, refresh=True)
    if reloaded != doc:
        return [f"roundtrip: reloaded cache differs from swept document "
                f"({path})"]
    return []


def _check_determinism(doc: dict, *, repeats: int) -> list[str]:
    """Re-sweep with the winners as incumbents: hysteresis must keep
    every config stable on the same machine."""
    before = json.loads(json.dumps(doc))    # deep copy
    after = run_sweep(QUICK_BUCKETS, repeats=repeats, doc=doc,
                      log=lambda *_: None)
    errors = []
    for dk, kernels in before.get("entries", {}).items():
        for key, buckets in kernels.items():
            for bkey, entry in buckets.items():
                got = after["entries"][dk][key][bkey]["config"]
                if got != entry["config"]:
                    errors.append(
                        f"determinism: {key}[{bkey}] flipped "
                        f"{entry['config']} -> {got}")
    return errors


def _check_schema_drift() -> list[str]:
    """A cache written by a different build MUST fail loudly."""
    drifted = {"schema_version": tune.SCHEMA_VERSION + 1, "entries": {}}
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(drifted, f)
        p = pathlib.Path(f.name)
    try:
        tune.load_cache(p, refresh=True)
        return ["schema drift: foreign schema_version was ACCEPTED "
                "(load_cache must raise TuneCacheError)"]
    except tune.TuneCacheError:
        return []
    finally:
        p.unlink(missing_ok=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.tune",
        description="kernel block-config sweep: time candidate ladders "
                    "per shape bucket, persist winners for "
                    "tune.best_config")
    parser.add_argument("--quick", action="store_true",
                        help="one bucket per kernel + self-checks "
                             "(the ci.sh smoke)")
    parser.add_argument("--validate", action="store_true",
                        help="schema-check an existing cache and exit")
    parser.add_argument("--out", default=None,
                        help="cache path (default: repo TUNE_CACHE.json "
                             "or $REPRO_TUNE_CACHE)")
    parser.add_argument("--repeats", type=int, default=None,
                        help="timing repeats per candidate "
                             "(min-of-repeats; default 5)")
    args = parser.parse_args(argv)
    enable_compile_cache()
    path = pathlib.Path(args.out) if args.out else tune.cache_path()

    if args.validate:
        try:
            doc = tune.load_cache(path, refresh=True)
        except tune.TuneCacheError as e:
            print(f"INVALID {path}: {e}")
            return 1
        print(f"ok {path}")
        n = sum(len(b) for k in doc.get("entries", {}).values()
                for b in k.values())
        print(f"  schema_version: {doc.get('schema_version')}")
        print(f"  tuned buckets (all devices): {n}")
        return 0

    repeats = args.repeats or 5
    buckets = QUICK_BUCKETS if args.quick else FULL_BUCKETS
    try:
        doc = tune.load_cache(path, refresh=True)
    except tune.TuneCacheError as e:
        print(f"existing cache invalid, starting fresh: {e}")
        doc = None

    print(f"== sweep ({'quick' if args.quick else 'full'}, "
          f"repeats={repeats}, device={tune.device_kind()}) ==")
    doc = run_sweep(buckets, repeats=repeats, doc=doc)
    tune.save_cache(doc, path)
    print(f"saved {path}")

    if not args.quick:
        return 0

    print("== self-checks ==")
    errors = []
    errors += _check_roundtrip(doc, path)
    errors += _check_determinism(doc, repeats=repeats)
    errors += _check_schema_drift()
    # determinism may legitimately re-time entries; persist the final doc
    tune.save_cache(doc, path)
    for name in ("roundtrip", "determinism", "schema drift"):
        status = ("FAIL" if any(e.startswith(name.split()[0]) for e in errors)
                  else "ok")
        print(f"  {status:4s} {name}")
    for e in errors:
        print(f"  {e}")
    if errors:
        print(f"quick sweep: {len(errors)} self-check failure(s)")
        return 1
    mine = doc.get("entries", {}).get(tune.device_kind(), {})
    print(f"  tuned buckets for {tune.device_kind()}: "
          f"{sum(len(b) for b in mine.values())}")
    print("quick sweep: all self-checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
