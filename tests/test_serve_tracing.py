"""The serve path's spans and counters: the IVF probe-plan span, the
queue-wait and rerank counters read as deltas across ``reset()``, and
the collector-pause meter (CPU only)."""
import gc
import math
import pathlib

import jax
import numpy as np
import pytest

from repro.core import unq
from repro.index import UNQIndex
from repro.index.base import DECODE_CHUNK
from repro.serve import ServeConfig, ServeEngine

RERANK = 32


def host_spans(directory, name: str) -> list[dict]:
    """Every host event named ``name`` in the trace under ``directory``,
    as {start, dur, stats}."""
    path = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    return [{"start": e.start_ns, "dur": e.duration_ns,
             "stats": dict(e.stats)}
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name == name]


@pytest.fixture(scope="module")
def engine():
    """A tiny flat UNQ engine (untrained weights, random codes), warmed."""
    cfg = unq.UNQConfig(dim=16, num_codebooks=4, codebook_size=16,
                        code_dim=8, hidden_dim=32)
    params, state = unq.init(jax.random.PRNGKey(0), cfg)
    codes = np.random.default_rng(0).integers(0, 16, (3000, 4), np.uint8)
    index = UNQIndex.from_trained(params, state, cfg, codes=codes,
                                  rerank=RERANK)
    eng = ServeEngine(index, ServeConfig(max_batch_queries=8))
    eng.warmup(buckets=(8,))
    yield eng
    eng.close()


def serve(engine, n: int, seed: int) -> None:
    rng = np.random.default_rng(seed)
    futures = [engine.submit(rng.standard_normal((1, 16), np.float32), k=5)
               for _ in range(n)]
    for f in futures:
        f.result(timeout=60)


def test_probe_plan_span(trained_index_factory, tiny_dataset, tmp_path):
    ivf = trained_index_factory("IVF16,PQ4x32,Rerank50", iters=4)
    with jax.profiler.trace(str(tmp_path)):
        ivf.search(np.asarray(tiny_dataset.queries[:4]), 5,
                   use_dispatch=False)
    assert host_spans(tmp_path, "ivf.probe_plan")


def test_queue_wait_and_rerank_deltas_across_reset(engine):
    serve(engine, 5, seed=1)
    before = engine.metrics.summary()
    assert before["queue_wait_p50_ms"] >= 0
    assert before["queue_wait_p95_ms"] >= before["queue_wait_p50_ms"]
    assert before["rerank_slots"] > 0
    engine.metrics.reset()
    empty = engine.metrics.summary()
    assert math.isnan(empty["queue_wait_p50_ms"])
    assert (empty["rerank_slots"], empty["rerank_unique_rows"],
            empty["rerank_decoded_rows"]) == (0, 0, 0)
    serve(engine, 3, seed=2)
    s = engine.metrics.summary()
    waits = engine.metrics.queue_waits
    assert len(waits) == 3 and all(ms >= 0 for _, ms in waits)
    assert len({seq for seq, _ in waits}) == s["batches"]
    # every batch is one 8-query bucket: each row holds RERANK candidates
    assert s["rerank_slots"] == 8 * RERANK * s["batches"]
    assert 0 < s["rerank_unique_rows"] <= s["rerank_slots"]
    assert s["rerank_decoded_rows"] >= s["rerank_unique_rows"]
    assert s["rerank_decoded_rows"] % DECODE_CHUNK == 0


def test_forced_collection_counts_as_a_pause(engine, tmp_path):
    engine.metrics.reset()
    with jax.profiler.trace(str(tmp_path)):
        gc.collect()
    assert engine.metrics.summary()["gc_pauses"] == 0   # no batch yet
    serve(engine, 1, seed=3)
    s = engine.metrics.summary()
    assert s["gc_pauses"] >= 1 and s["gc_pause_ms"] > 0
    spans = host_spans(tmp_path, "host.gc")
    assert any(span["stats"].get("generation") == 2 for span in spans)
    gc.collect()                        # after the last batch: not counted
    assert engine.metrics.summary()["gc_pause_ms"] == s["gc_pause_ms"]
