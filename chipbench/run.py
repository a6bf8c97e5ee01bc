"""The benchmark's command: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Builds the cell's deployment from the seed on the chip, warms and primes
it with the cell's own traffic, serves that traffic for ``--seconds``,
checks a seeded sample of the answers against the plain reference, and
prints one JSON line as the last line of standard output. ``--trace 1``
records the window with the profiler and reports the per-layer metrics
instead of the end-to-end ones. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result. See
``chipbench/README.md``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    from chipbench import harness
    sys.exit(harness.main(parse(), T_START))
