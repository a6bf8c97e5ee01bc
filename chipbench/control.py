"""The control of a cell's comparison, run on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3

The control is the cell's plain reference computed one precision below
the float32 its configuration states (``precision.py``: three bfloat16
passes, what ``Precision.HIGH`` gives on a TPU), put in the program's
place: for each seed it answers as many of the mix's queries as a run
of ``run_seconds`` compares, and the reference at float32 judges those
answers as it judges a run's. Prints one JSON line per seed with the
numbers a run compares beside the cell's limits; the control has to
fail at least one of them. The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def queries_compared(manifest: dict, config: dict, mix: dict) -> int:
    """How many queries a run of ``run_seconds`` compares."""
    from chipbench import loadgen
    if mix["loop"] == "open":
        served = mix["rate_per_s"] * manifest["run_seconds"]
        served *= loadgen.mean_queries(mix)
    else:
        served = config["check_queries"]
    return int(min(config["check_queries"], served))


def control_readings(manifest, cell, config, mix, seed: int) -> dict:
    import importlib

    import numpy as np

    from chipbench import data, loadgen

    family = importlib.import_module(f"chipbench.deploy.{config['family']}")
    traffic = loadgen.Traffic(mix, seed)
    pool = np.asarray(data.DeepLike(seed, config["data"]).queries(
        traffic.pool))
    n = queries_compared(manifest, config, mix)
    reqs, total = [], 0
    while total < n:
        reqs.append(traffic.request())
        total += len(reqs[-1].rows)
    n_categories = (mix.get("filter") or {}).get("categories", 0)
    control = family.Reference(config, seed, precision="high")
    ref = family.Reference(config, seed)
    gaps, wrong, spent = [], 0, [0.0, 0.0]
    for k in sorted({r.k for r in reqs}):
        group = [r for r in reqs if r.k == k]
        q = pool[np.concatenate([r.rows for r in group])]
        cats = np.concatenate([np.full(len(r.rows), r.category)
                               for r in group])
        t0 = time.perf_counter()
        d, i = control.search(q, k, cats, n_categories)
        t1 = time.perf_counter()
        verdict = ref.judge(q, d, i, cats, n_categories)
        spent[0] += t1 - t0
        spent[1] += time.perf_counter() - t1
        gaps.append(verdict["gap"])
        wrong += int(np.sum(verdict["wrong"]))
    gap = np.concatenate(gaps)
    limits = config["limits"]
    return {"workload": cell["name"], "seed": seed, "queries": total,
            "control_s": spent[0], "judge_s": spent[1],
            "d1_gap": float(np.max(gap)),
            "d1_gap_median": float(np.median(gap)),
            "wrong_ids": wrong, "limits": limits,
            "fails": bool(np.max(gap) > limits["d1_gap"]
                          or wrong > limits["wrong_ids"])}


def main(argv=None) -> int:
    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.find_cell(manifest, args.workload)
    config = harness.load_json(harness.HERE / "configs"
                               / f"{cell['config']}.json")
    mix = harness.load_json(harness.HERE / "traffic"
                            / f"{cell['traffic']}.json")
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_readings(manifest, cell, config, mix,
                                          seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
