"""Static-analysis gate: per-rule good/bad fixtures, the negative
HLO-contract test (a materialized (Q, N) scan must be REJECTED), the
compile-count discipline, and the ``python -m repro.analysis.check`` CLI
(including the seeded-violations inversion CI relies on)."""
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import contracts
from repro.analysis.compilecount import count_compiles
from repro.analysis.lint import ALL_RULES, LintTree, run_lint

FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "lint"
REPO = pathlib.Path(__file__).resolve().parents[1]


def _tree(which: str) -> LintTree:
    return LintTree(src=FIXTURES / which / "src",
                    tests=FIXTURES / which / "tests")


# ---------------------------------------------------------------------------
# lint rules vs fixtures
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule", ALL_RULES)
def test_each_rule_passes_good_and_flags_bad(rule):
    """Every rule must stay silent on its known-good fixture and fire on
    its known-bad one — a rule that cannot flag its own bad fixture is a
    vacuous gate."""
    assert run_lint(_tree("good"), rules=(rule,)) == []
    bad = run_lint(_tree("bad"), rules=(rule,))
    assert bad, f"rule {rule} missed its seeded bad fixture"
    assert all(f.rule == rule for f in bad)


def test_recompile_hazard_catches_scan_bodies_and_all_three_hazards():
    """float() / .item() / np.* must each be flagged, including inside a
    ``lax.scan`` body that has no jit decorator of its own."""
    msgs = [f.message for f in run_lint(_tree("bad"),
                                        rules=("recompile-hazard",))]
    assert any("float(" in m for m in msgs)
    assert any(".item()" in m for m in msgs)
    assert any("np.log" in m for m in msgs)
    assert any("'body'" in m for m in msgs)         # the scan body


def test_pragma_suppresses_findings(tmp_path):
    """``# lint: allow(<rule>)`` on the offending line silences exactly
    that rule."""
    src = tmp_path / "src"
    (src / "index").mkdir(parents=True)
    (tmp_path / "tests").mkdir()
    (src / "index" / "hot.py").write_text(
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.log(x)  # lint: allow(recompile-hazard)\n"
        "def g(x):\n"
        "    return jax.device_get(x)\n")
    tree = LintTree(src=src, tests=tmp_path / "tests")
    findings = run_lint(tree)
    assert [f.rule for f in findings] == ["host-sync"]


def test_repo_tree_is_lint_clean():
    """The live tree must satisfy its own rules (this is the CI gate)."""
    assert run_lint() == []


# ---------------------------------------------------------------------------
# HLO contracts
# ---------------------------------------------------------------------------

def test_negative_contract_rejects_materialized_qn():
    """The detector itself: point the streaming contract's forbid clause
    at the materialized build — the verifier MUST reject it."""
    control = contracts.REGISTRY["stage1.materialized.control"]
    seeded = dataclasses.replace(
        contracts.REGISTRY["stage1.stream.xla"],
        path_id="test.seeded-materialized",
        build=control.build, buckets=control.buckets, max_temp=None)
    res = contracts.verify(seeded)
    kinds = {v.kind for v in res.violations}
    assert "materialization" in kinds, res


def test_require_clause_fails_on_streaming_build():
    """A control contract pointed at a genuinely streaming build must
    report the missing (Q, N) buffer instead of passing vacuously."""
    stream = contracts.REGISTRY["stage1.stream.xla"]
    seeded = dataclasses.replace(
        contracts.REGISTRY["stage1.materialized.control"],
        path_id="test.vacuous-control",
        build=stream.build, buckets=stream.buckets)
    res = contracts.verify(seeded)
    assert any(v.kind == "missing-shape" for v in res.violations), res


def test_forbidden_host_transfer_ops_detected():
    """An outfeed in the compiled module must trip the forbidden-op
    clause (host transfer inside an engine path)."""

    def build(p):
        def f(x):
            jax.debug.print("x0={v}", v=x[0, 0])   # lowers via outfeed/
            return x * 2                           # custom host callback

        x = jax.ShapeDtypeStruct((p["Q"], p["N"]), jnp.float32)
        return jax.jit(f).lower(x).compile()

    c = contracts.Contract(
        path_id="test.host-transfer", description="", build=build,
        buckets=({"Q": 4, "N": 8},),
        forbidden_ops=contracts.HOST_TRANSFER_OPS + ("custom-call",))
    res = contracts.verify(c)
    assert any(v.kind == "forbidden-op" for v in res.violations), res


def test_sharded_contract_declares_collectives():
    c = contracts.REGISTRY["sharded.stage1.device"]
    assert c.collectives == frozenset({"all-gather"})
    res = contracts.check_contract("sharded.stage1.device")
    if len(jax.devices()) < 2:
        assert res.skipped and "devices" in res.reason
    else:
        assert res.ok


# ---------------------------------------------------------------------------
# compile-count discipline
# ---------------------------------------------------------------------------

def test_compile_counter_sees_fresh_compiles_and_cache_hits():
    with count_compiles() as log:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(17, dtype=jnp.float32))
    assert log.count >= 1

    f = jax.jit(lambda x: x - 2)
    x = jnp.arange(19, dtype=jnp.float32)
    f(x)
    with count_compiles() as log:
        f(x)                                   # identical shapes: cache hit
    assert log.count == 0


# ---------------------------------------------------------------------------
# the CLI
# ---------------------------------------------------------------------------

def _run_cli(*args):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.check", *args],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=570)


def test_cli_lint_section_exits_zero():
    proc = _run_cli("--only", "lint")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "== lint ==" in proc.stdout


def test_cli_seeded_violations_exits_nonzero_with_all_findings():
    """The CI inversion: on the seeded-violation fixtures the checker
    must exit non-zero AND report every seeded defect class first."""
    proc = _run_cli("--seeded-violations")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    for marker in ("kernel-oracle", "capability-consumed",
                   "recompile-hazard", "host-sync", "materialization"):
        assert marker in proc.stdout, f"missing {marker}:\n{proc.stdout}"


def test_cli_list_names_contracts_and_rules():
    proc = _run_cli("--list")
    assert proc.returncode == 0
    assert "stage1.stream.xla" in proc.stdout
    assert "recompile-hazard" in proc.stdout
