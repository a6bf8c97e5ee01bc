"""Exit-code regression for the CI smoke harness: ``benchmarks.run
--smoke`` must FAIL the process when a backend-parity check fails, not
just print the mismatch (a green CI over drifting backends is the worst
failure mode a parity harness can have).

Both directions run as real subprocesses — the exit code IS the contract
— restricted to the fast PQ spec via ``--specs`` so the regression does
not retrain the UNQ smoke model. The failing direction uses the
documented ``REPRO_SMOKE_FORCE_FAIL`` hook, which injects a synthetic
parity failure after the normal checks run.
"""
import os
import pathlib
import subprocess
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
_SPEC = "PQ8x64,Rerank64"


def _run_smoke(extra_env):
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("REPRO_SMOKE_FORCE_FAIL", None)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke",
         "--specs", _SPEC],
        env=env, cwd=_ROOT, capture_output=True, text=True, timeout=540)


def test_smoke_green_path_exits_zero():
    r = _run_smoke({})
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert f"smoke {_SPEC}: all backends agree" in r.stdout


def test_smoke_parity_failure_exits_nonzero():
    r = _run_smoke({"REPRO_SMOKE_FORCE_FAIL": "1"})
    assert r.returncode != 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "parity failure" in r.stdout
