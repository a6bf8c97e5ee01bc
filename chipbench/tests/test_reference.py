"""The plain references against the program's search at a tiny size, the
control that has to fail the comparison, and whole runs of the harness
with the timed path broken underneath (CPU only: the chip check is
skipped, everything after it runs)."""
import json
import pathlib
import time

import numpy as np
import pytest

from chipbench import data, harness, loadgen
from chipbench.deploy import unq

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2 ** 33 + 4242
UNQ_CFG = dict(json.loads((ROOT / "chipbench/configs/unq8-deep10m.json")
                          .read_text()),
               spec="UNQ8x256,Rerank64", rerank=64, n_per_chip=6000,
               check_queries=48)
FAMILIES = {"unq": (unq, UNQ_CFG)}


@pytest.fixture(scope="module")
def queries():
    return np.asarray(data.DeepLike(SEED, UNQ_CFG["data"]).queries(48))


@pytest.fixture(scope="module")
def built():
    """family -> (the program's index, the reference), built once."""
    out = {}
    for name, (mod, cfg) in FAMILIES.items():
        index, serve_kw = mod.build(cfg, SEED)
        out[name] = (index, serve_kw, mod.Reference(cfg, SEED))
    return out


def program_answer(index, serve_kw, q, k):
    kw = {"use_dispatch": serve_kw["use_dispatch"]} if serve_kw else {}
    d, i = index.search(q, k, **kw)
    return np.asarray(d), np.asarray(i)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_reference_agrees_with_the_program(family, built, queries):
    index, serve_kw, ref = built[family]
    d, i = program_answer(index, serve_kw, queries, 10)
    verdict = ref.judge(queries, d, i)
    limits = FAMILIES[family][1]["limits"]
    assert np.max(verdict["gap"]) <= limits["d1_gap"]
    assert np.sum(verdict["wrong"]) == 0
    own_d, own_i = ref.search(queries, 10)
    assert np.mean(own_i == i) > 0.95
    np.testing.assert_allclose(own_d, d, rtol=1e-5)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_control_below_float32_reads_far_above_the_program(family, built,
                                                           queries):
    """The control (the reference one precision below float32, in the
    program's place) against the program, judged alike. On the chip, at
    the cells' sizes, the control reads several times over the limit
    (PERF.md); the CPU's three-pass product is more exact than the
    TPU's, so here it is held to reading five times the program's gap."""
    mod, cfg = FAMILIES[family]
    index, serve_kw, ref = built[family]
    control = mod.Reference(cfg, SEED, precision="high")
    gap_control = np.max(ref.judge(queries, *control.search(queries, 10))
                         ["gap"])
    gap_program = np.max(ref.judge(
        queries, *program_answer(index, serve_kw, queries, 10))["gap"])
    assert gap_control > 5 * gap_program


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_altered_answers_fail(family, built, queries):
    index, serve_kw, ref = built[family]
    d, i = program_answer(index, serve_kw, queries, 10)
    n = FAMILIES[family][1]["n_per_chip"]
    moved = i.copy()
    moved[3, 4] = (moved[3, 4] + 1) % n
    assert np.sum(ref.judge(queries, d, moved)["wrong"]) >= 1
    swapped = i.copy()
    swapped[[0, 1]] = swapped[[1, 0]]
    assert np.sum(ref.judge(queries, d, swapped)["wrong"]) >= 1
    far = d * np.float32(1.001)
    assert np.max(ref.judge(queries, far, i)["gap"]) > \
        FAMILIES[family][1]["limits"]["d1_gap"]


# -- whole runs on the CPU, with the timed path broken -----------------------

def manifest_for(config_name):
    return {"workloads": [{"name": "cell", "config": config_name,
                           "traffic": "mix", "chips": 1, "why": "test"}],
            "end_to_end": [
                {"name": "qps", "unit": "queries/s", "better": "higher",
                 "bound": 0.05, "source": "host_clock"},
                {"name": "setup_s", "unit": "s", "better": "lower",
                 "bound": 0.25, "source": "host_clock"}],
            "per_layer": []}


MIX = {"loop": "closed", "clients": 2, "queries_per_request": 8, "k": 10,
       "pool": 64, "serve": {"max_batch_queries": 16}}


def alter_ids(monkeypatch, cls):
    search = cls.search

    def broken(self, queries, k, **kw):
        d, i = search(self, queries, k, **kw)
        return d, i.at[:, 0].set((i[:, 0] + 1) % self.ntotal)

    monkeypatch.setattr(cls, "search", broken)


def swap_fan_in(monkeypatch, _cls):
    from repro.serve import engine
    split = engine.split_results

    def broken(batch, d, i, ntotal):
        parts = split(batch, d, i, ntotal)
        return parts[1:] + parts[:1] if len(parts) > 1 else \
            [(p[0], (p[1] + 1) % ntotal) for p in parts]

    monkeypatch.setattr(engine, "split_results", broken)


def stretch_distances(monkeypatch, cls):
    search = cls.search

    def broken(self, queries, k, **kw):
        d, i = search(self, queries, k, **kw)
        return d * 1.0001, i

    monkeypatch.setattr(cls, "search", broken)


FAULTS = {"none": None, "answer_altered": alter_ids,
          "fan_in_swapped": swap_fan_in,
          "distance_altered": stretch_distances}


@pytest.mark.parametrize("family,fault", [
    ("unq", "none"), ("unq", "answer_altered"), ("unq", "fan_in_swapped"),
    ("unq", "distance_altered")])
def test_whole_run_sees_a_broken_timed_path(family, fault, monkeypatch):
    from repro.index.base import Index
    monkeypatch.setattr(harness, "PRIME_SECONDS", 0.3)
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch, Index)
    _, cfg = FAMILIES[family]
    result = harness.run_cell(
        "cell", SEED, 1.0, False, time.perf_counter(),
        manifest=manifest_for(family), config=cfg, mix=MIX, on_chip=False)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is (fault == "none"), result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"qps", "setup_s"}


# -- filtered requests and mixed shapes ----------------------------------------

CATEGORIES = 4


def test_filtered_answers_agree_with_the_reference(built, queries):
    """A request that keeps one category: the program's answer under the
    category's mask is right by the reference told the same filter, and
    an answer served without the filter is wrong by it."""
    index, serve_kw, ref = built["unq"]
    traffic = loadgen.Traffic({"loop": "closed", "pool": 1,
                               "queries_per_request": 1, "k": 10,
                               "filter": {"categories": CATEGORIES,
                                          "share": 1.0}}, SEED)
    cats = np.arange(len(queries)) % CATEGORIES
    d = np.zeros((len(queries), 10), np.float32)
    i = np.zeros((len(queries), 10), np.int32)
    for c in range(CATEGORIES):
        rows = cats == c
        dc, ic = index.search(queries[rows], 10,
                              filter_mask=traffic.mask(c, index.ntotal))
        d[rows], i[rows] = np.asarray(dc), np.asarray(ic)
    assert np.all(loadgen.category_of(i, CATEGORIES) == cats[:, None])
    verdict = ref.judge(queries, d, i, categories=cats,
                        n_categories=CATEGORIES)
    assert np.max(verdict["gap"]) <= UNQ_CFG["limits"]["d1_gap"]
    assert np.sum(verdict["wrong"]) == 0
    own_d, own_i = ref.search(queries, 10, cats, CATEGORIES)
    assert np.mean(own_i == i) > 0.95
    plain_d, plain_i = program_answer(index, serve_kw, queries, 10)
    assert np.sum(ref.judge(queries, plain_d, plain_i, categories=cats,
                            n_categories=CATEGORIES)["wrong"]) >= 1


def test_reference_refuses_an_option_it_cannot_judge(built, queries):
    _, _, ref = built["unq"]
    d = np.zeros((2, 10), np.float32)
    i = np.tile(np.arange(10, dtype=np.int32), (2, 1))
    with pytest.raises(ValueError, match="nprobe"):
        ref.judge(queries[:2], d, i, options=[{"nprobe": 8}] * 2)


OPEN_MIX = {"loop": "open", "arrivals": "poisson", "rate_per_s": 30.0,
            "phases": [[0.2, 2.0], [0.2, 0.5]],
            "queries_per_request": {"values": [1, 3], "weights": [3, 1]},
            "k": {"values": [5, 10]}, "options": {"deadline_ms": 5000.0},
            "filter": {"categories": CATEGORIES, "share": 0.5},
            "pool": 64, "serve": {"max_batch_queries": 8}}


@pytest.mark.parametrize("fault", ["none", "answer_altered"])
def test_whole_open_loop_run_with_filters_and_mixed_shapes(fault,
                                                           monkeypatch):
    from repro.index.base import Index
    monkeypatch.setattr(harness, "PRIME_SECONDS", 0.3)
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch, Index)
    manifest = manifest_for("unq")
    manifest["end_to_end"][0] = {"name": "p50_ms", "unit": "ms",
                                 "better": "lower", "bound": 0.05,
                                 "source": "host_clock"}
    result = harness.run_cell(
        "cell", SEED, 1.0, False, time.perf_counter(), manifest=manifest,
        config=dict(UNQ_CFG, check_queries=24), mix=OPEN_MIX,
        on_chip=False)
    sent = len(loadgen.Traffic(OPEN_MIX, SEED).offsets(1.0))
    assert result["attempted"] == sent == 42 and result["failed"] == 0
    assert result["correct"] is (fault == "none"), result["checks"]
    assert set(result["metrics"]) == {"p50_ms", "setup_s"}
