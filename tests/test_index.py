"""Unified ``repro.index`` API: factory parsing, protocol interchange,
save/load, batched-scan parity, sharded merge, stage-1 oracle
equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.search import recall_at_k
from repro.index import (Index, IVFIndex, OPQIndex, PQIndex, RVQIndex,
                         ShardedIndex, UNQIndex, index_factory,
                         resolve_scan_backend)
from repro.index.unq_index import build_luts, encode_database
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# factory-string parsing
# ---------------------------------------------------------------------------

def test_factory_parses_quantizers_and_modifiers():
    idx = index_factory("UNQ8x256,Rerank500", dim=96)
    assert isinstance(idx, UNQIndex)
    assert idx.cfg.num_codebooks == 8 and idx.cfg.codebook_size == 256
    assert idx.rerank == 500 and idx.dim == 96

    idx = index_factory("PQ4", dim=96)
    assert isinstance(idx, PQIndex)
    assert idx.num_books == 4 and idx.book_size == 256
    assert idx.rerank == 0          # classic ADC-only IndexPQ behavior

    idx = index_factory("OPQ8x64,Rerank100,Scan(onehot)", dim=96)
    assert isinstance(idx, OPQIndex)
    assert idx.book_size == 64 and idx.rerank == 100
    assert idx.backend == "onehot"

    idx = index_factory("RVQ4x32", dim=96)
    assert isinstance(idx, RVQIndex)

    idx = index_factory("IVF256,NProbe16,UNQ8x256", dim=96)
    assert isinstance(idx, IVFIndex) and isinstance(idx.inner, UNQIndex)
    assert idx.nlist == 256 and idx.nprobe == 16
    assert idx.rerank == 500        # inherits UNQ's paper default

    idx = index_factory("IVF64,PQ4,Rerank80,Scan(onehot)", dim=96)
    assert isinstance(idx, IVFIndex) and isinstance(idx.inner, PQIndex)
    assert idx.nprobe == 8 and idx.rerank == 80
    assert idx.backend == "onehot" and idx.inner.backend == "onehot"


@pytest.mark.parametrize("bad", ["", "Rerank500", "UNQ8x256,PQ4",
                                 "LSH16", "UNQ8x256,Foo",
                                 "IVF64", "NProbe8,PQ4"])
def test_factory_rejects_malformed_specs(bad):
    with pytest.raises(ValueError):
        index_factory(bad, dim=96)


def test_scan_backend_resolution():
    assert resolve_scan_backend("xla") == "xla"
    assert resolve_scan_backend("pallas") == "pallas"
    # auto never picks pallas off-TPU, and never picks the A/B-only onehot
    assert resolve_scan_backend("auto") == (
        "pallas" if jax.default_backend() == "tpu" else "xla")
    with pytest.raises(ValueError):
        resolve_scan_backend("cuda")


# ---------------------------------------------------------------------------
# batched multi-query scan vs per-query oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,k,q", [(1000, 8, 256, 3), (257, 16, 256, 33),
                                     (2048, 4, 64, 1)])
def test_adc_scan_batch_matches_per_query_oracle(n, m, k, q):
    rng = np.random.default_rng(n + q)
    codes = jnp.asarray(rng.integers(0, k, (n, m)), jnp.uint8)
    luts = jnp.asarray(rng.normal(size=(q, m, k)), jnp.float32)
    want = jnp.stack([ops.adc_scan(codes, luts[i], impl="xla")
                      for i in range(q)])
    for impl in ("xla", "pallas"):
        got = ops.adc_scan_batch(codes, luts, impl=impl)
        assert got.shape == (q, n)
        # acceptance: interpret-mode kernel is bit-for-bit vs the oracle
        # (both accumulate the M partial sums left-to-right)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=impl)
    # the one-hot einsum reassociates the reduction; close, not bit-equal
    got = ops.adc_scan_batch(codes, luts, impl="onehot")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_adc_scan_batch_ref_is_vmap_of_single():
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, 64, (100, 8)), jnp.uint8)
    luts = jnp.asarray(rng.normal(size=(5, 8, 64)), jnp.float32)
    got = ref.adc_scan_batch_ref(codes, luts)
    want = jax.vmap(ref.adc_scan_ref, in_axes=(None, 0))(codes, luts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# protocol interchangeability: one loop over heterogeneous indexes
# ---------------------------------------------------------------------------

def _small_pq_family(tiny_dataset):
    return [
        index_factory("PQ4x32,Rerank50", dim=tiny_dataset.dim),
        index_factory("OPQ4x32,Rerank50", dim=tiny_dataset.dim),
        index_factory("RVQ2x32,Rerank50", dim=tiny_dataset.dim),
    ]


def test_protocol_interchangeability(tiny_dataset):
    """UNQ and every shallow baseline run the identical loop (what makes
    paper-table comparisons one loop instead of per-method scripts)."""
    queries = jnp.asarray(tiny_dataset.queries[:30])
    gt = jnp.asarray(tiny_dataset.gt_nn[:30])
    n = tiny_dataset.base.shape[0]
    for index in _small_pq_family(tiny_dataset):
        assert not index.is_trained
        index.train(tiny_dataset.train, iters=4)
        index.add(tiny_dataset.base)
        assert index.is_trained and index.ntotal == n
        distances, idx = index.search(queries, 20)
        assert distances.shape == idx.shape == (30, 20)
        # distances sorted ascending (closest first)
        d = np.asarray(distances)
        assert (np.diff(d, axis=1) >= -1e-5).all()
        rec = recall_at_k(idx, gt, ks=(10,))
        assert rec["recall@10"] > 10 * (10 / n), (type(index).__name__, rec)


def test_train_before_add_is_an_error():
    idx = index_factory("PQ4x32", dim=96)
    with pytest.raises(RuntimeError):
        idx.add(np.zeros((10, 96), np.float32))


def test_forced_rerank_without_budget_is_an_error(tiny_dataset,
                                                  trained_index_factory):
    idx = trained_index_factory("PQ4x32,Rerank50", iters=4)
    idx.rerank = 0                    # classic ADC-only IndexPQ behavior
    with pytest.raises(ValueError, match="rerank budget"):
        idx.search(jnp.asarray(tiny_dataset.queries[:5]), 10,
                   use_rerank=True)


# ---------------------------------------------------------------------------
# save / load roundtrip (checkpoint/manager-backed)
# ---------------------------------------------------------------------------

#: every registered index_factory shape (quantizer family x IVF wrapping),
#: with the train kwargs the session cache uses — the save/load roundtrip
#: below runs over ALL of them
REGISTRY_SPECS = [
    ("PQ4x32,Rerank50", dict(iters=4)),
    ("OPQ4x32,Rerank50", dict(iters=4)),
    ("RVQ2x32,Rerank50", dict(iters=4)),
    ("UNQ8x64,Rerank60", dict(epochs=2, log_every=1000)),
    ("IVF8,PQ4x32,Rerank50", dict(iters=4)),
    ("IVF8,NProbe3,RVQ2x32,Rerank50", dict(iters=4)),
    ("IVF8,UNQ8x64,Rerank60", dict(epochs=2, log_every=1000)),
    ("IVF8,Residual,PQ4x32,Rerank50", dict(iters=4)),
    ("IVF8,NProbe3,Residual,RVQ2x32,Rerank50", dict(iters=4)),
]


@pytest.mark.parametrize("spec,train_kw",
                         REGISTRY_SPECS, ids=[s for s, _ in REGISTRY_SPECS])
def test_save_load_roundtrip_registry(trained_index_factory, tiny_dataset,
                                      spec, train_kw, tmp_path):
    """Acceptance satellite: EVERY factory spec — the new IVF prefixes
    included — roundtrips through save/load with bitwise-equal search
    results (distances and indices), and IVF wrappers keep their coarse
    state (nlist/nprobe/cell grouping)."""
    index = trained_index_factory(spec, **train_kw)
    queries = jnp.asarray(tiny_dataset.queries[:10])
    want_d, want_i = index.search(queries, 15)
    index.save(tmp_path / "ckpt")
    loaded = Index.load(tmp_path / "ckpt")
    assert type(loaded) is type(index)
    assert loaded.ntotal == index.ntotal
    assert loaded.rerank == index.rerank
    got_d, got_i = loaded.search(queries, 15)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    if isinstance(index, IVFIndex):
        assert isinstance(loaded, IVFIndex)
        assert (loaded.nlist, loaded.nprobe) == (index.nlist, index.nprobe)
        assert type(loaded.inner) is type(index.inner)
        np.testing.assert_array_equal(loaded._ids_np, index._ids_np)
        np.testing.assert_array_equal(loaded._offsets, index._offsets)
        # a partial probe exercises the restored CSR/coarse state
        want = index.search(queries, 10, nprobe=2)
        got = loaded.search(queries, 10, nprobe=2)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))


def test_save_load_roundtrip_unq(tiny_unq, tiny_dataset, tmp_path):
    cfg, params, state, _ = tiny_unq
    index = UNQIndex.from_trained(params, state, cfg, rerank=60)
    index.add(tiny_dataset.base)
    queries = jnp.asarray(tiny_dataset.queries[:10])
    _, want = index.search(queries, 15)
    index.save(tmp_path / "unq")
    loaded = Index.load(tmp_path / "unq")
    assert isinstance(loaded, UNQIndex) and loaded.cfg == cfg
    _, got = loaded.search(queries, 15)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_load_rejects_non_index_checkpoint(tmp_path):
    from repro.checkpoint.manager import save_pytree
    save_pytree(tmp_path / "ckpt", {"w": jnp.zeros((2,))}, metadata={})
    with pytest.raises(ValueError):
        Index.load(tmp_path / "ckpt")


# ---------------------------------------------------------------------------
# acceptance: factory index == hand-rolled two-stage pipeline on same
# params/codes (the oracle the deleted core.search shims used to provide)
# ---------------------------------------------------------------------------

def test_unq_index_matches_manual_two_stage_pipeline(tiny_unq, tiny_dataset):
    from repro.core import unq

    cfg, params, state, _ = tiny_unq
    base = jnp.asarray(tiny_dataset.base)
    queries = jnp.asarray(tiny_dataset.queries[:40])
    codes = encode_database(params, state, cfg, base)

    index = index_factory(
        f"UNQ{cfg.num_codebooks}x{cfg.codebook_size},Rerank100",
        dim=cfg.dim)
    index.cfg = cfg                      # tiny test cfg (small code_dim)
    index.params, index.state = params, state
    index.add(base)
    np.testing.assert_array_equal(np.asarray(index.codes), np.asarray(codes))

    # stage 1 oracle: materialized d2 matrix + top_k; stage 2: exact d1
    luts = build_luts(params, state, cfg, queries)
    scores = ref.adc_scan_batch_ref(codes, luts)
    neg, cand = jax.lax.top_k(-scores, 100)

    def rerank(cand_row, q_row):
        recon = unq.decode_codes(params, state, cfg, codes[cand_row])
        d1 = ref.sq_dist(recon, q_row[None, :])
        neg1, order = jax.lax.top_k(-d1, 30)
        return cand_row[order]

    want = jnp.stack([rerank(cand[i], queries[i])
                      for i in range(queries.shape[0])])
    _, got = index.search(queries, 30)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # no-rerank ablation returns the raw d2 ranking
    _, got_nr = index.search(queries, 30, use_rerank=False)
    np.testing.assert_array_equal(np.asarray(got_nr), np.asarray(cand[:, :30]))


# ---------------------------------------------------------------------------
# ShardedIndex: merge correctness
# ---------------------------------------------------------------------------

def test_sharded_index_merge_matches_flat_search(tiny_unq, tiny_dataset):
    cfg, params, state, _ = tiny_unq
    index = UNQIndex.from_trained(params, state, cfg, rerank=80)
    index.add(tiny_dataset.base)
    queries = jnp.asarray(tiny_dataset.queries[:25])

    _, flat = index.search(queries, 30)
    for num_shards in (1, 4, 7):       # 7: uneven split, tail shard
        sharded = ShardedIndex(index, num_shards=num_shards)
        assert sharded.ntotal == index.ntotal
        _, got = sharded.search(queries, 30)
        # same candidate pool (rerank >= per-shard L keeps sets identical
        # up to d2 ties at the pool boundary)
        for i in range(queries.shape[0]):
            a = set(np.asarray(flat[i]).tolist())
            b = set(np.asarray(got[i]).tolist())
            assert len(a & b) / len(a) > 0.95, (num_shards, i)


def test_sharded_stage1_matches_flat_oracle(tiny_unq, tiny_dataset):
    """from_shards candidate merge == lax.top_k over the full d2 matrix,
    bit-exact (score AND index, ties included)."""
    cfg, params, state, _ = tiny_unq
    base = jnp.asarray(tiny_dataset.base)
    codes = encode_database(params, state, cfg, base)
    queries = jnp.asarray(tiny_dataset.queries[:20])
    n = codes.shape[0]
    shards = [codes[: n // 3], codes[n // 3: 2 * n // 3],
              codes[2 * n // 3:]]
    offsets = [0, n // 3, 2 * n // 3]

    luts = build_luts(params, state, cfg, queries)
    want_s, want_i = ref.adc_scan_topl_ref(codes, luts, None, 50)

    inner = UNQIndex.from_trained(params, state, cfg, rerank=50)
    sharded = ShardedIndex.from_shards(inner, shards, offsets)
    got_s, got_i = sharded.stage1_candidates(queries, topl=50)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


def test_sharded_rvq_carries_score_bias(tiny_dataset,
                                        trained_index_factory):
    """Additive quantizers carry a per-point bias (||decode||^2); sharded
    stage 1 must slice it per shard, and from_shards must refuse to drop
    it silently."""
    index = trained_index_factory("RVQ2x32,Rerank60", iters=4)
    queries = jnp.asarray(tiny_dataset.queries[:15])
    _, flat = index.search(queries, 20)

    sharded = ShardedIndex(index, num_shards=3)
    _, got = sharded.search(queries, 20)
    for i in range(queries.shape[0]):
        a = set(np.asarray(flat[i]).tolist())
        b = set(np.asarray(got[i]).tolist())
        assert len(a & b) / len(a) > 0.95, i

    n = index.ntotal
    shards = [index.codes[: n // 2], index.codes[n // 2:]]
    with pytest.raises(ValueError, match="bias"):
        ShardedIndex.from_shards(index, shards, [0, n // 2])
    biased = ShardedIndex.from_shards(
        index, shards, [0, n // 2],
        biases=[index.bias[: n // 2], index.bias[n // 2:]])
    _, got2 = biased.stage1_candidates(queries, topl=60)
    _, want2 = ShardedIndex(index, num_shards=2).stage1_candidates(
        queries, topl=60)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))


def test_sharded_pq_backend_pinning(tiny_dataset, trained_index_factory):
    """Sharded search honors the scan-backend registry per inner index."""
    index = trained_index_factory("PQ4x32,Rerank50", iters=4)
    index.backend = "onehot"          # as Scan(onehot) would pin it
    index.rerank = 40
    queries = jnp.asarray(tiny_dataset.queries[:10])
    _, want = index.search(queries, 10)
    index.backend = "xla"
    sharded = ShardedIndex(index, num_shards=3)
    _, got = sharded.search(queries, 10)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# subset views
# ---------------------------------------------------------------------------

def test_subset_view_restricts_results(tiny_dataset,
                                       trained_index_factory):
    index = trained_index_factory("PQ4x32,Rerank50", iters=4)
    half = index.subset(index.ntotal // 2)
    assert half.ntotal == index.ntotal // 2
    _, got = half.search(jnp.asarray(tiny_dataset.queries[:10]), 10)
    assert int(np.asarray(got).max()) < half.ntotal
    # the view shares the quantizer: full index unchanged
    assert index.ntotal == tiny_dataset.base.shape[0]


# ---------------------------------------------------------------------------
# per-query math and stage 2 do not depend on the batch a query rides in
# ---------------------------------------------------------------------------

def test_per_query_runs_whole_8_row_tiles():
    """``base.per_query`` hands ``fn`` whole 8-row tiles and slices the
    result back: a one-query search and a served batch (query buckets are
    multiples of 8) run the same row tiling of the table/coarse dots."""
    from repro.index.base import per_query
    seen = []

    def fn(a, b):
        seen.append((a.shape[0], b.shape[0]))
        return a + b

    x = jnp.arange(6, dtype=jnp.float32).reshape(3, 2)
    out = per_query(fn, x, 2 * x)
    assert seen == [(8, 8)]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(3 * x))
    seen.clear()
    per_query(fn, jnp.ones((16, 2)), jnp.ones((16, 2)))
    assert seen == [(16, 16)]


@pytest.mark.parametrize("spec", ["UNQ", "IVF8,Residual,PQ4x32,Rerank50"])
def test_single_query_search_matches_batched_bucket(spec, tiny_unq,
                                                    tiny_dataset,
                                                    trained_index_factory):
    """Each query searched alone (Q=1) returns the bits it gets inside a
    16-query batch: stage-1 tables, stage 2 decode (``decode_rows``) and
    the d1 fold are batch-independent by construction."""
    if spec == "UNQ":
        cfg, params, state, _ = tiny_unq
        index = UNQIndex.from_trained(params, state, cfg, rerank=60)
        index.add(tiny_dataset.base)
    else:
        index = trained_index_factory(spec, iters=4)
    queries = jnp.asarray(tiny_dataset.queries[:16])
    d_b, i_b = index.search(queries, 10)
    for j in (0, 5, 15):
        d_1, i_1 = index.search(queries[j:j + 1], 10)
        np.testing.assert_array_equal(np.asarray(d_1), np.asarray(d_b[j:j + 1]))
        np.testing.assert_array_equal(np.asarray(i_1), np.asarray(i_b[j:j + 1]))
