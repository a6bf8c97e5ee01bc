"""The load generator: each traffic mix is deterministic in its seed and
gives every seed the same work (CPU only)."""
import concurrent.futures
import json
import pathlib

import numpy as np
import pytest

from chipbench import loadgen

MIXES = sorted((pathlib.Path(__file__).resolve().parents[1]
                / "traffic").glob("*.json"))
BIG_SEED = 2 ** 33 + 977


def answered(queries, k, **_):
    f = concurrent.futures.Future()
    f.set_result((np.zeros((len(queries), k), np.float32),
                  np.zeros((len(queries), k), np.int32)))
    return f


def draw(mix, seed, n=50):
    t = loadgen.Traffic(mix, seed)
    rows = [t.request().rows for _ in range(n)]
    due = t.offsets(4.0) if mix["loop"] == "open" else None
    return rows, due


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_mix_is_deterministic_in_the_seed(path):
    mix = json.loads(path.read_text())
    rows_a, due_a = draw(mix, BIG_SEED)
    rows_b, due_b = draw(mix, BIG_SEED)
    rows_c, due_c = draw(mix, BIG_SEED + 1)
    assert all(np.array_equal(a, b) for a, b in zip(rows_a, rows_b))
    assert not all(np.array_equal(a, c) for a, c in zip(rows_a, rows_c))
    assert all(len(r) == mix["queries_per_request"]
               and r.max() < mix["pool"] for r in rows_a)
    if due_a is not None:
        # every seed: the same arrivals; the seed picks the queries
        assert np.array_equal(due_a, due_b)
        assert np.array_equal(due_a, due_c)
        assert len(due_a) == round(4.0 * mix["rate_per_s"])
        assert due_a[0] == 0 and np.all(np.diff(due_a) > 0)
        assert abs(due_a[-1] / (len(due_a) - 1) * mix["rate_per_s"]
                   - 1) < 0.1


def test_open_loop_sends_on_schedule():
    mix = {"loop": "open", "arrivals": "poisson", "rate_per_s": 200.0,
           "queries_per_request": 1, "k": 10, "pool": 100}
    pool = np.zeros((100, 4), np.float32)
    recs = loadgen.Traffic(mix, 3).run(answered, pool, 0.5)
    assert len(recs) == 100
    dues = np.array([r.due for r in recs])
    assert np.all(np.diff(dues) >= 0)
    assert all(r.result is not None and r.done >= r.sent >= r.due
               for r in recs)


def test_closed_loop_keeps_its_clients_busy():
    mix = {"loop": "closed", "clients": 3, "queries_per_request": 2,
           "k": 5, "pool": 50}
    pool = np.zeros((50, 4), np.float32)
    recs = loadgen.Traffic(mix, 3).run(answered, pool, 0.05)
    assert len(recs) > 3
    assert all(r.result[1].shape == (2, 5) for r in recs)


def open_mix(**kw):
    return dict({"loop": "open", "arrivals": "poisson", "rate_per_s": 50.0,
                 "queries_per_request": 1, "k": 10, "pool": 100}, **kw)


def test_poisson_schedule_is_the_fixed_realisation():
    """One rate, no phases: the quantiles of the exponential gaps in the
    one fixed order, at the rate (the schedule every seed has had)."""
    due = loadgen.Traffic(open_mix(), 1).offsets(2.0)
    n = 100
    q = (np.arange(n) + 0.5) / n
    order = np.random.default_rng(loadgen.ARRIVALS_SEED).permutation(n)
    gaps = -np.log1p(-q[order]) / 50.0
    np.testing.assert_allclose(due, np.cumsum(gaps) - gaps[0], rtol=1e-12)


def test_even_arrivals_are_evenly_spaced():
    due = loadgen.Traffic(open_mix(arrivals="even"), 1).offsets(1.0)
    assert len(due) == 50
    np.testing.assert_allclose(np.diff(due), 1 / 50.0)


def test_phases_make_bursts():
    """0.5 s at four times the rate, then 0.5 s at a quarter, repeated:
    most arrivals fall in the fast phases, and the expected count is
    the integral of the rate."""
    mix = open_mix(arrivals="even", phases=[[0.5, 4.0], [0.5, 0.25]])
    due = loadgen.Traffic(mix, 1).offsets(2.0)
    assert len(due) == round(2 * (0.5 * 200 + 0.5 * 12.5))
    fast = np.sum((due % 1.0) < 0.5)
    assert fast == 200 and len(due) - fast == 12
    with pytest.raises(ValueError):
        loadgen.Traffic(open_mix(phases=[[1.0, 0.0]]), 1).offsets(1.0)


def test_shapes_and_options_are_the_same_for_every_seed():
    mix = open_mix(queries_per_request={"values": [1, 4], "weights": [3, 1]},
                   k={"values": [10, 100]},
                   options={"nprobe": {"values": [4, 8, 32]},
                            "deadline_ms": 50.0},
                   filter={"categories": 8, "share": 0.25})
    ta, tb = loadgen.Traffic(mix, 5), loadgen.Traffic(mix, 6)
    a = [ta.request() for _ in range(400)]
    b = [tb.request() for _ in range(400)]
    assert [(len(r.rows), r.k, r.options, r.category >= 0) for r in a] == \
        [(len(r.rows), r.k, r.options, r.category >= 0) for r in b]
    assert any(not np.array_equal(x.rows, y.rows) for x, y in zip(a, b))
    assert {len(r.rows) for r in a} == {1, 4}
    assert {r.k for r in a} == {10, 100}
    assert {r.options["nprobe"] for r in a} == {4, 8, 32}
    assert all(r.options["deadline_ms"] == 50.0 for r in a)
    share = np.mean([r.category >= 0 for r in a])
    assert 0.15 < share < 0.35
    assert {r.category for r in a if r.category >= 0} == set(range(8))
    assert abs(loadgen.mean_queries(mix) - 1.75) < 1e-12


def test_zipf_popularity_asks_hot_queries_more():
    mix = open_mix(popularity={"zipf": 1.2})
    t = loadgen.Traffic(mix, 9)
    rows = np.concatenate([t.request().rows for _ in range(2000)])
    counts = np.bincount(rows, minlength=100)
    assert counts.max() > 10 * np.median(counts)
    hot = np.argmax(counts)
    other = loadgen.Traffic(mix, 10)
    rows2 = np.concatenate([other.request().rows for _ in range(2000)])
    assert np.argmax(np.bincount(rows2, minlength=100)) != hot or \
        not np.array_equal(rows, rows2)


def test_categories_split_the_corpus_and_masks_match():
    ids = np.arange(100000, dtype=np.uint32)
    cats = loadgen.category_of(ids, 16)
    counts = np.bincount(cats, minlength=16)
    assert counts.min() > 0.9 * 100000 / 16
    import jax.numpy as jnp
    np.testing.assert_array_equal(
        np.asarray(loadgen.category_of(jnp.asarray(ids[:5000], jnp.int32),
                                       16)), cats[:5000])
    t = loadgen.Traffic(open_mix(filter={"categories": 16, "share": 1.0}),
                        1)
    np.testing.assert_array_equal(t.mask(3, 100000), cats == 3)


def test_requests_reach_submit_with_their_options_and_filter():
    mix = open_mix(rate_per_s=400.0, k={"values": [5, 7]},
                   options={"deadline_ms": 9.0},
                   filter={"categories": 4, "share": 0.5})
    seen = []

    def submit(queries, k, **kw):
        seen.append((len(queries), k, kw))
        return answered(queries, k)

    pool = np.zeros((100, 4), np.float32)
    recs = loadgen.Traffic(mix, 3).run(submit, pool, 0.1, ntotal=64)
    assert len(recs) == len(seen) == 40
    for rec, (q, k, kw) in zip(recs, seen):
        assert q == len(rec.rows) and k == rec.k
        assert kw["deadline_ms"] == 9.0
        if rec.category >= 0:
            mask = kw["filter_mask"]
            assert mask.shape == (q, 64)
            assert np.array_equal(mask[0], loadgen.category_of(
                np.arange(64), 4) == rec.category)
        else:
            assert "filter_mask" not in kw


def test_fills_reach_every_batch_the_engine_can_form():
    from chipbench import harness
    got = harness.fills([1], 32)
    assert sorted(got) == list(range(1, 33))
    assert all(sum(parts) == t for t, parts in got.items())
    bulk = harness.fills([16], 128)
    assert sorted(bulk) == list(range(16, 129, 16))
    mixed = harness.fills([3, 5], 12)
    assert sorted(mixed) == [3, 5, 6, 8, 9, 10, 11, 12]
    assert all(sum(parts) == t and set(parts) <= {3, 5}
               for t, parts in mixed.items())


def test_peak_memory_counts_the_reserved_program_temps():
    """On a v5e the stage-1 program's temp shows in the runtime's
    reserved region, not in peak_bytes_in_use (memory_stats read on the
    chip after a 10^7-code search)."""
    from chipbench import harness
    stats = {"bytes_in_use": 132702208, "peak_bytes_in_use": 280828416,
             "bytes_reserved": 2560196608,
             "peak_bytes_reserved": 2560196608}
    assert harness.peak_bytes(stats) == 280828416 + 2560196608
    assert harness.peak_bytes({"peak_bytes_in_use": 7}) == 7
    assert harness.peak_bytes({}) == 0
