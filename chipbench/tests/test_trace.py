"""The trace reduction, on a small trace recorded on a v5e chip (the
served flat UNQ index, a few batches) and kept as a fixture, and on
hand-made intervals (CPU only)."""
import pathlib
import re

import numpy as np
import pytest

from chipbench import harness, trace
from chipbench.kernels import adc_scan_topl

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_v5e_unq.json"


def brute_union(intervals) -> int:
    """Covered nanoseconds by marking every covered nanosecond."""
    if not intervals:
        return 0
    lo = min(s for s, _ in intervals)
    hi = max(s + d for s, d in intervals)
    cover = np.zeros(hi - lo, bool)
    for s, d in intervals:
        cover[s - lo:s + d - lo] = True
    return int(cover.sum())


def sweep_union(intervals) -> float:
    """Covered length by a vectorised sweep: segments start where an
    interval begins after every earlier one has ended."""
    iv = np.array(sorted(intervals), np.float64)
    starts, ends = iv[:, 0], iv[:, 0] + iv[:, 1]
    reach = np.maximum.accumulate(ends)
    new = np.concatenate([[True], starts[1:] > reach[:-1]])
    first = np.flatnonzero(new)
    last_reach = np.maximum.reduceat(ends, first)
    return float(np.sum(last_reach - starts[first]))


@pytest.mark.parametrize("intervals", [
    [], [(0, 10)], [(0, 10), (5, 10)], [(0, 10), (10, 5)],
    [(20, 5), (0, 3), (1, 1), (2, 30)], [(0, 4), (10, 4), (12, 1)]])
def test_union_of_intervals(intervals):
    assert trace.union_ns(intervals) == brute_union(intervals)
    if intervals:
        assert sweep_union(intervals) == brute_union(intervals)


def test_busy_idle_and_gaps_on_hand_made_events():
    ev = trace.Events({0: [["a", 0, 10], ["b", 5, 10], ["a", 30, 10]]},
                      {0: [["jit_x(1)", 0, 40]]},
                      [["t", "host_work", 14, 20], ["t", "outer", 0, 100]])
    assert ev.busy_ns(0) == 25
    assert ev.busy_seconds(1) == 25e-9
    assert ev.idle_gaps(0) == [(15, 15)]
    assert ev.op_seconds("^a$") == 20e-9
    assert ev.op_seconds("^zzz") is None
    assert ev.module_seconds(r"^jit_x\(") == 40e-9
    bd = ev.breakdown()
    assert bd["device_ops"][0] == ["a", 20e-9]
    assert bd["idle_gaps"] == [["host_work", 15e-9]]


@pytest.fixture(scope="module")
def recorded():
    return trace.Events.from_json(FIXTURE)


def test_recorded_trace_reduction(recorded):
    ops = recorded.ops[0]
    assert sorted(recorded.ops) == [0]
    busy = recorded.busy_ns(0)
    assert busy == pytest.approx(sweep_union([(s, d) for _, s, d in ops]),
                                 abs=1)
    start = min(s for _, s, _ in ops)
    stop = max(s + d for _, s, d in ops)
    gaps = sum(length for _, length in recorded.idle_gaps(0))
    assert busy + gaps == pytest.approx(stop - start, abs=1)
    stage1 = recorded.op_seconds(adc_scan_topl.OP)
    assert stage1 is not None and 0 < stage1 * 1e9 <= busy
    rerank = recorded.module_seconds(
        harness.load_json(harness.HERE / "configs/unq8-deep10m.json")
        ["rerank_programs"])
    assert rerank is not None and rerank < stage1
    bd = recorded.breakdown()
    assert 1 <= len(bd["device_ops"]) <= 10
    assert len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0].startswith("%adc_scan_topl_pallas")
    assert all(v > 0 for _, v in bd["device_ops"] + bd["idle_gaps"])


def test_readers_on_the_recorded_trace(recorded):
    class Ctx:
        events = recorded
        cell = {"chips": 1}
        config = harness.load_json(harness.HERE
                                   / "configs/unq8-deep10m.json")
        peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        serve = {"batches": sum(1 for name, _, _ in recorded.ops[0]
                                if re.search(adc_scan_topl.OP, name)),
                 "real_queries": 128}
        window_s = 1e-9 * (max(s + d for _, s, d in recorded.ops[0])
                           - min(s for _, s, _ in recorded.ops[0]))

        def served_queries(self):
            return 128

    roof = harness.reader("adc_scan_topl_roofline.bulk")(Ctx())
    assert 0 < roof < 100
    idle = harness.reader("device_idle.bulk")(Ctx())
    assert 0 <= idle < 100
    assert harness.reader("rerank_ms_per_query.bulk")(Ctx()) > 0
