"""Quantized-LUT fast path (``kernels/lut_quant.py`` + the ``lut_dtype``
/ ``overfetch`` threading through ops -> candidates -> Index.search):

  * quantization scheme invariants (pow2 int8 scales -> exact dequant);
  * pool parity: both impls select bit-identically to the ``*_q_ref``
    oracles for every face and dtype;
  * full-pool identity: with the pool covering the population, the
    quantized path is BITWISE the exact path (scan order, re-score
    composition and tie handling all collapse to the exact semantics);
  * the recall floor the module docstring advertises: quantized pool +
    exact re-score keeps recall@L >= 0.999 at overfetch=2;
  * loud rejection everywhere a quantized request cannot be exact-ified
    (materialized generator, onehot backend, dispatch without pos).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.index import UNQIndex
from repro.index.candidates import MaterializedTopL
from repro.kernels import lut_quant, ops, ref

_IMAX = np.iinfo(np.int32).max


def _recall(got_ids, want_ids):
    got, want = np.asarray(got_ids), np.asarray(want_ids)
    return np.mean([len(set(got[q]) & set(want[q])) / want.shape[1]
                    for q in range(want.shape[0])])


# ---------------------------------------------------------------------------
# quantization scheme
# ---------------------------------------------------------------------------

def test_quantize_luts_shapes_and_pow2_scales():
    rng = np.random.default_rng(0)
    luts = jnp.asarray(rng.standard_normal((5, 8, 64)).astype(np.float32))
    f16, scale16 = lut_quant.quantize_luts(luts, "float16")
    assert f16.dtype == jnp.float16 and scale16 is None
    q8, scale = lut_quant.quantize_luts(luts, "int8")
    assert q8.dtype == jnp.int8 and scale.shape == (5, 8)
    assert int(jnp.max(jnp.abs(q8.astype(jnp.int32)))) <= 127
    # scales are powers of two: mantissa exactly 0.5 -> f32(q8) * scale
    # is exact, which is what makes the i8 chain FMA-contraction-immune
    m, _ = np.frexp(np.asarray(scale))
    np.testing.assert_array_equal(m, np.full_like(m, 0.5))
    # f32 passthrough + unknown dtype rejection
    same, none = lut_quant.quantize_luts(luts, "float32")
    assert same is luts and none is None
    with pytest.raises(ValueError, match="lut_dtype"):
        lut_quant.check_lut_dtype("bf16")


def test_pool_width_semantics():
    assert lut_quant.pool_width(10, 2, 1000) == 20
    assert lut_quant.pool_width(10, 200, 64) == 64      # clamped to pop.
    assert lut_quant.pool_width(10, 1, 1000) == 10
    with pytest.raises(ValueError, match="overfetch"):
        lut_quant.pool_width(10, 0, 1000)


def test_exact_topl_tie_contract():
    s = jnp.asarray([[2.0, 1.0, 1.0, 3.0]])
    g = jnp.asarray([[7, 9, 4, 1]], dtype=jnp.int32)
    ts, tg = lut_quant.exact_topl(s, g, 3)
    np.testing.assert_array_equal(np.asarray(ts), [[1.0, 1.0, 2.0]])
    np.testing.assert_array_equal(np.asarray(tg), [[4, 9, 7]])


# ---------------------------------------------------------------------------
# pool parity vs the *_q_ref oracles (both impls, both dtypes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_q,q", [(8, 5), (16, 37), (24, 21),
                                       (32, 29), (128, 125)])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("lut_dtype", ["float16", "int8"])
def test_flat_pool_matches_q_ref(scan_case, impl, lut_dtype, block_q, q):
    rng = np.random.default_rng(3)
    n, L = 700, 33
    codes, luts = scan_case(rng, n, m=8, k=32, q=q, tie_heavy=True)
    bias = jnp.asarray(rng.integers(0, 3, (n,)), jnp.float32)
    qluts, scale = lut_quant.quantize_luts(luts, lut_dtype)
    want = ref.adc_scan_topl_q_ref(codes, qluts, scale, bias, L)
    got = ops._scan_topl_run(codes, qluts, scale, bias, None, topl=L,
                             impl=impl, block_n=128, block_q=block_q,
                             chunk_n=96)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("lut_dtype", ["float16", "int8"])
def test_gather_pool_matches_q_ref(scan_case, impl, lut_dtype):
    rng = np.random.default_rng(4)
    n, q, w_max, L = 600, 4, 200, 25
    codes, luts = scan_case(rng, n, m=4, k=32, q=q, tie_heavy=True)
    rows = np.zeros((q, w_max), np.int32)
    gids = np.full((q, w_max), _IMAX, np.int32)
    for qi in range(q):
        w = rng.integers(L, w_max)
        sel = np.sort(rng.choice(n, size=w, replace=False)).astype(np.int32)
        rows[qi, :w], gids[qi, :w] = sel, sel
    rows, gids = jnp.asarray(rows), jnp.asarray(gids)
    rowbias = jnp.asarray(rng.integers(0, 2, (q, w_max)), jnp.float32)
    qluts, scale = lut_quant.quantize_luts(luts, lut_dtype)
    want = ref.adc_gather_topl_q_ref(codes, rows, gids, qluts, scale,
                                     rowbias, L)
    got = ops._gather_topl_run(codes, rows, gids, qluts, scale, rowbias,
                               topl=L, impl=impl, block_w=64, block_q=8,
                               chunk_w=48)
    for w_, g_ in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))


# ---------------------------------------------------------------------------
# full-pool identity + the recall floor (flat + gathered)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("lut_dtype", ["float16", "int8"])
def test_full_pool_is_bitwise_exact_path(scan_case, impl, lut_dtype):
    """With overfetch covering the whole population the pool is every
    candidate, so the exact re-score + lexicographic top-L must reproduce
    the exact path BIT FOR BIT — scores, ids, ties, +inf filters."""
    rng = np.random.default_rng(5)
    n, q, L = 500, 6, 29
    codes, luts = scan_case(rng, n, m=8, k=16, q=q, tie_heavy=True)
    bias = jnp.asarray(rng.integers(0, 2, (n,)), jnp.float32)
    qbias = jnp.where(jnp.asarray(rng.random((q, n))) < 0.05,
                      jnp.inf, 0.0).astype(jnp.float32)
    want = ops.adc_scan_topl(codes, luts, topl=L, bias=bias, qbias=qbias,
                             impl=impl)
    got = ops.adc_scan_topl(codes, luts, topl=L, bias=bias, qbias=qbias,
                            impl=impl, lut_dtype=lut_dtype, overfetch=n)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@settings(max_examples=10, deadline=None)
@given(
    lut_dtype=st.sampled_from(["float16", "int8"]),
    impl=st.sampled_from(["xla", "pallas"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_quantized_recall_floor(scan_case, lut_dtype, impl, seed):
    """The advertised contract: quantized pool selection + exact re-score
    keeps recall@L >= 0.999 at overfetch=2 (the lut_quant module doc and
    the bench rows both cite this bound)."""
    rng = np.random.default_rng(seed)
    n, L = 2048, 64
    q = int(rng.integers(3, 9))
    codes, luts = scan_case(rng, n, m=8, k=32, q=q,
                            tie_heavy=bool(rng.integers(0, 2)))
    _, want_i = ops.adc_scan_topl(codes, luts, topl=L, impl=impl)
    _, got_i = ops.adc_scan_topl(codes, luts, topl=L, impl=impl,
                                 lut_dtype=lut_dtype, overfetch=2)
    assert _recall(got_i, want_i) >= 0.999, (impl, lut_dtype)


@pytest.mark.parametrize("lut_dtype", ["float16", "int8"])
def test_overfetch_alone_is_bitwise_noop(scan_case, lut_dtype):
    """overfetch > 1 with lut_dtype='float32' (and the quantized modes at
    overfetch=1) still go through pool+re-score — but with f32 tables the
    pool order IS the exact order, so results stay bitwise identical."""
    rng = np.random.default_rng(6)
    codes, luts = scan_case(rng, 400, m=4, k=16, q=4, tie_heavy=True)
    want = ops.adc_scan_topl(codes, luts, topl=21, impl="xla")
    overfetched = ops.adc_scan_topl(codes, luts, topl=21, impl="xla",
                                    overfetch=3)
    for w, g in zip(want, overfetched):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# dispatch face: full-pool identity through pool combine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("lut_dtype", ["float16", "int8"])
def test_dispatch_quantized_full_pool_and_pos_requirement(scan_case, impl,
                                                          lut_dtype):
    from repro.index.dispatch import build_dispatch
    rng = np.random.default_rng(7)
    nlist, P, q, topl = 10, 4, 8, 17
    sizes = rng.integers(10, 120, size=nlist)
    offsets = np.zeros(nlist + 1, np.int64)
    offsets[1:] = np.cumsum(sizes)
    n = int(offsets[-1])
    codes, luts = scan_case(rng, n, m=8, k=16, q=q, tie_heavy=True)
    gids = np.sort(rng.choice(3 * n, size=n, replace=False)).astype(np.int32)
    pos = np.zeros(int(gids.max()) + 1, np.int32)
    pos[gids] = np.arange(n, dtype=np.int32)
    probe = np.stack([rng.choice(nlist, size=P, replace=False)
                      for _ in range(q)]).astype(np.int32)
    routing, _ = build_dispatch(probe, offsets, chunk=64)
    cap = routing.plan.qidx.shape[1]
    cellterm = jnp.asarray(rng.integers(0, 2, (routing.cell_of.shape[0],
                                               cap)), jnp.float32)
    rowbias = jnp.asarray(rng.integers(0, 2, (n,)), jnp.float32)

    want = ops.adc_dispatch_topl(codes, jnp.asarray(gids), rowbias, luts,
                                 cellterm, routing.plan, topl=topl,
                                 impl=impl, chunk=routing.chunk)
    got = ops.adc_dispatch_topl(codes, jnp.asarray(gids), rowbias, luts,
                                cellterm, routing.plan, topl=topl,
                                impl=impl, chunk=routing.chunk,
                                pos=jnp.asarray(pos), lut_dtype=lut_dtype,
                                overfetch=n)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    # a quantized dispatch without the gid->row inverse cannot re-score
    with pytest.raises(ValueError, match="pos"):
        ops.adc_dispatch_topl(codes, jnp.asarray(gids), rowbias, luts,
                              cellterm, routing.plan, topl=topl, impl=impl,
                              chunk=routing.chunk, lut_dtype=lut_dtype,
                              overfetch=2)


# ---------------------------------------------------------------------------
# index surface: capability gate + end-to-end quantized search
# ---------------------------------------------------------------------------

def test_materialized_generator_rejects_quantized_requests(scan_case):
    rng = np.random.default_rng(8)
    codes, luts = scan_case(rng, 100, m=4, k=16, q=2, tie_heavy=False)
    gen = MaterializedTopL("onehot")
    with pytest.raises(ValueError, match="quantized"):
        gen.topl(codes, luts, None, topl=5, lut_dtype="float16")


def test_index_backend_gate_and_end_to_end_quantized_search(tiny_unq,
                                                            tiny_dataset):
    cfg, params, state, _ = tiny_unq
    queries = jnp.asarray(tiny_dataset.queries)[:32]
    index = UNQIndex.from_trained(params, state, cfg, rerank=0,
                                  backend="xla").add(tiny_dataset.base)
    _, want = index.search(queries, 32)
    # huge overfetch -> pool covers the base -> bitwise-identical ranking
    _, full = index.search(queries, 32, lut_dtype="float16",
                           overfetch=tiny_dataset.base.shape[0])
    np.testing.assert_array_equal(np.asarray(full), np.asarray(want))
    # the advertised operating point
    _, got = index.search(queries, 32, lut_dtype="float16", overfetch=2)
    assert _recall(got, want) >= 0.999
    # f32 default stays the untouched exact path
    _, dflt = index.search(queries, 32)
    np.testing.assert_array_equal(np.asarray(dflt), np.asarray(want))

    index.backend = "onehot"
    with pytest.raises(ValueError, match="quantized_lut"):
        index.search(queries, 8, lut_dtype="int8", overfetch=2)
