"""The program's host spans as the benchmark reads them: a traced tiny
flat UNQ engine reduced by ``Events.from_xplane``, the readers of the
span and counter metrics on hand-made events, and a trace with spans
recorded on a v5e chip (CPU only)."""
import collections
import gc
import math
import pathlib

import jax
import numpy as np
import pytest

from chipbench import harness, trace
from chipbench.metrics import _spans

# Six batches of a traced run of unq8-deep10m.poisson (3 s window) on one
# v5e: the Python threads' host events and the chip's ops and programs
# that lie wholly between the first batch's serve.coalesce and the last
# one's serve.fanout, saved with Events.to_json.
FIXTURE = pathlib.Path(__file__).parent / "fixtures" \
    / "trace_v5e_unq_spans.json"
WORKER_SPANS = ("serve.wait", "serve.linger", "serve.coalesce",
                "serve.execute", "index.lut_build", "index.stage1",
                "index.rerank", "rerank.unique", "rerank.decode",
                "serve.result_wait", "serve.fanout", "host.gc")
BATCH_SPANS = ("serve.coalesce", "serve.execute", "serve.result_wait",
               "serve.fanout")
INSIDE_EXECUTE = ("index.lut_build", "index.stage1", "index.rerank",
                  "rerank.unique", "rerank.decode")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(Events, the xplane's host events with their stats, the engine's
    ServeMetrics summary) of a tiny flat UNQ engine serving a few
    requests under the benchmark's tracer."""
    from repro.core import unq
    from repro.index import UNQIndex
    from repro.serve import ServeConfig, ServeEngine

    cfg = unq.UNQConfig(dim=16, num_codebooks=4, codebook_size=16,
                        code_dim=8, hidden_dim=32)
    params, state = unq.init(jax.random.PRNGKey(0), cfg)
    codes = np.random.default_rng(0).integers(0, 16, (3000, 4), np.uint8)
    index = UNQIndex.from_trained(params, state, cfg, codes=codes,
                                  rerank=32)
    engine = ServeEngine(index, ServeConfig(max_batch_queries=8))
    engine.warmup(buckets=(8,))
    engine.metrics.reset()
    rng = np.random.default_rng(1)
    tracer = trace.Tracer(tmp_path_factory.mktemp("trace"))
    tracer.start()
    for _ in range(3):
        futures = [engine.submit(rng.standard_normal((1, 16), np.float32),
                                 k=5) for _ in range(4)]
        for f in futures:
            f.result(timeout=60)
    gc.collect()
    events = tracer.stop()
    engine.close()
    path = sorted(tracer.directory.rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    host = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    return events, host, engine.metrics.summary()


def test_every_span_is_in_the_trace(traced):
    events, _, _ = traced
    names = {name for _, name, _, _ in events.host}
    assert set(WORKER_SPANS) <= names


def test_a_batch_shares_its_number(traced):
    _, host, summary = traced
    seqs = collections.defaultdict(set)
    for name, _, _, stats in host:
        if name in BATCH_SPANS:
            seqs[stats["batch"]].add(name)
    assert len(seqs) >= summary["batches"] >= 1
    assert all(names == set(BATCH_SPANS) for names in seqs.values())
    # each execute span covers the index spans of its own batch only
    execute = sorted((s, s + d, st["batch"]) for name, s, d, st in host
                     if name == "serve.execute")
    for name, s, d, _ in host:
        if name in INSIDE_EXECUTE:
            owners = [b for lo, hi, b in execute if lo <= s and s + d <= hi]
            assert len(owners) == 1, name


def test_the_readers_on_the_traced_engine(traced):
    events, _, summary = traced
    ctx = make_ctx(events, summary, served=12)
    launch = harness.reader("launch_host_ms.bulk")(ctx)
    lut = harness.reader("lut_build_host_ms.bulk")(ctx)
    assert launch > 0 and 0 < lut < launch * summary["batches"]
    assert harness.reader("rerank_rows_per_query.bulk")(ctx) \
        == summary["rerank_decoded_rows"] / 12
    assert harness.reader("queue_wait_ms.poisson")(ctx) >= 0
    assert harness.reader("gc_pause_ms.poisson")(ctx) \
        == summary["gc_pause_ms"] >= 0


def make_ctx(events, serve: dict, served: int):
    class Ctx:
        cell = {"chips": 1}

        def served_queries(self):
            return served
    ctx = Ctx()
    ctx.events, ctx.serve = events, serve
    return ctx


HAND_MADE = trace.Events(
    {0: [["op", 0, 10]]}, {},
    [["python3", "serve.execute", 0, 4_000_000],
     ["python3", "serve.execute#batch=1#", 10, 8_000_000],
     ["python3", "serve.execute", 20, 2_000_000],
     ["python3", "index.lut_build", 30, 1_500_000],
     ["python3", "index.lut_build#batch=2#", 40, 500_000],
     ["python3", "serve.executed", 50, 9_000_000],
     ["python3", "DevicePut", 60, 7_000_000]])
COUNTERS = {"batches": 4, "queue_wait_p50_ms": 12.5,
            "rerank_decoded_rows": 1024, "gc_pause_ms": 3.25}


@pytest.mark.parametrize("metric, value", [
    ("launch_host_ms.bulk", 4.0),
    ("launch_host_ms.poisson", 4.0),
    ("lut_build_host_ms.bulk", 0.5),
    ("lut_build_host_ms.poisson", 0.5),
    ("queue_wait_ms.poisson", 12.5),
    ("rerank_rows_per_query.bulk", 128.0),
    ("rerank_rows_per_query.poisson", 128.0),
    ("gc_pause_ms.poisson", 3.25)])
def test_readers_on_hand_made_events(metric, value):
    ctx = make_ctx(HAND_MADE, COUNTERS, served=8)
    assert harness.reader(metric)(ctx) == pytest.approx(value)


@pytest.mark.parametrize("metric", [
    "launch_host_ms.bulk", "lut_build_host_ms.poisson",
    "queue_wait_ms.poisson", "rerank_rows_per_query.bulk",
    "gc_pause_ms.poisson"])
@pytest.mark.parametrize("events, serve, served", [
    (None, {}, 0),
    (trace.Events({}, {}, []), {}, 8),
    (trace.Events({}, {}, []), {"queue_wait_p50_ms": math.nan,
                                "batches": 0}, 0)])
def test_readers_read_nothing_from_nothing(metric, events, serve, served):
    assert harness.reader(metric)(make_ctx(events, serve, served)) is None


def test_span_names_drop_trace_me_metadata():
    ctx = make_ctx(HAND_MADE, {}, 0)
    assert sorted(_spans.durations_ns(ctx, "serve.execute")) \
        == [2_000_000, 4_000_000, 8_000_000]
    assert _spans.durations_ns(ctx, "serve") == []


@pytest.fixture(scope="module")
def recorded():
    return trace.Events.from_json(FIXTURE)


def test_recorded_spans_name_the_idle_gaps(recorded):
    names = {name for _, name, _, _ in recorded.host}
    assert set(WORKER_SPANS) - {"host.gc"} <= names
    gaps = recorded.breakdown(top=len(recorded.host) + 1)["idle_gaps"]
    idle = sum(v for _, v in gaps)
    unnamed = sum(v for n, v in gaps if n == "(no host event)")
    assert unnamed < 0.1 * idle
    top = max(gaps, key=lambda g: g[1])[0]
    assert top in WORKER_SPANS


def test_readers_on_the_recorded_spans(recorded):
    batches = sum(1 for _, name, _, _ in recorded.host
                  if name == "serve.fanout")
    ctx = make_ctx(recorded, {"batches": batches}, served=batches)
    launch = harness.reader("launch_host_ms.poisson")(ctx)
    lut = harness.reader("lut_build_host_ms.poisson")(ctx)
    assert launch > 0 and 0 < lut < launch
