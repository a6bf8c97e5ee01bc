"""Streaming stage-1 engine: fused scan+top-L kernel vs chunked xla
fallback vs materialized oracle — exact (score, index) parity including
tie resolution — plus the HLO peak-memory guarantee and candidate
generator resolution."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.analysis.contracts import assert_contract
from repro.index import (MaterializedTopL, StreamingTopL,
                         backend_capabilities, backend_supports,
                         candidate_generator_for)
from repro.kernels import ops, ref


# tie-heavy case construction lives in conftest (``scan_case``): integer
# tables make d2 collisions ubiquitous, so parity tests exercise tie
# RESOLUTION, not just score math

#: (query block, queries) of the fused kernel: one 8-row merge slab, one
#: 16-row slab, three 8-row slabs (24 is no multiple of 16), then blocks
#: of 2 and 8 slabs of 16. No Q is a multiple of the slab height, and
#: Q = 37 over 16-query blocks spans three blocks.
QUERY_BLOCKS = [(8, 5), (16, 37), (24, 21), (32, 29), (128, 125)]


@pytest.mark.parametrize("block_q,q", QUERY_BLOCKS)
@pytest.mark.parametrize("tie_heavy", [False, True])
@pytest.mark.parametrize("n,L", [(1000, 37),     # N % block_n != 0
                                 (257, 300),     # L > N (clamped to N)
                                 (2048, 64),     # exact block multiple
                                 (1, 1)])        # degenerate
def test_topl_all_backends_bit_exact(scan_case, n, L, tie_heavy, block_q, q):
    rng = np.random.default_rng(n + L)
    codes, luts = scan_case(rng, n, m=8, k=64, q=q, tie_heavy=tie_heavy)
    want_s, want_i = ref.adc_scan_topl_ref(codes, luts, None, L)
    assert want_s.shape == (q, min(L, n))
    for impl in ("xla", "pallas"):
        got_s, got_i = ops.adc_scan_topl(codes, luts, topl=L, impl=impl,
                                         block_n=256, block_q=block_q,
                                         chunk_n=192)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s),
                                      err_msg=impl)
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i),
                                      err_msg=impl)


def test_topl_bias_flows_through_fused_path(scan_case):
    """Per-point biases (RVQ's ||decode||^2) must flow through both
    streaming paths, not just the materialized one."""
    rng = np.random.default_rng(0)
    codes, luts = scan_case(rng, 700, m=4, k=32, q=3, tie_heavy=True)
    bias = jnp.asarray(rng.integers(0, 3, (700,)), jnp.float32)
    want_s, want_i = ref.adc_scan_topl_ref(codes, luts, bias, 50)
    for impl in ("xla", "pallas"):
        got_s, got_i = ops.adc_scan_topl(codes, luts, topl=50, bias=bias,
                                         impl=impl, block_n=128, chunk_n=96)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s),
                                      err_msg=impl)
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i),
                                      err_msg=impl)


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(1, 400),
    L=st.integers(1, 80),
    block_n=st.sampled_from([64, 128, 256]),
    seed=st.integers(0, 2**31 - 1),
)
def test_topl_property_parity(scan_case, n, L, block_n, seed):
    """Property: for random shapes/blockings — N not a multiple of the
    block, L > N, tie-heavy tables — the fused kernel (interpret mode),
    the chunked xla fallback, and lax.top_k over the full matrix agree
    bit-for-bit in (score, index)."""
    rng = np.random.default_rng(seed)
    q = int(rng.integers(1, 7))
    codes, luts = scan_case(rng, n, m=4, k=16, q=q,
                            tie_heavy=bool(rng.integers(0, 2)))
    bias = (jnp.asarray(rng.integers(-1, 2, (n,)), jnp.float32)
            if rng.integers(0, 2) else None)
    want_s, want_i = ref.adc_scan_topl_ref(codes, luts, bias, L)
    for impl in ("xla", "pallas"):
        got_s, got_i = ops.adc_scan_topl(
            codes, luts, topl=L, bias=bias, impl=impl,
            block_n=block_n, block_q=8, chunk_n=max(1, block_n // 2))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s),
                                      err_msg=f"{impl} scores")
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i),
                                      err_msg=f"{impl} idx")


def test_streaming_stage1_contracts():
    """The acceptance guarantee — no (Q, N) score matrix, temp memory
    below the matrix footprint — now declared ONCE in the contract
    registry (repro.analysis.contracts) and merely invoked here. The
    materialized control proves the detector would actually see the
    forbidden buffer."""
    assert_contract("stage1.stream.xla")
    assert_contract("stage1.fused.pallas")
    assert_contract("stage1.materialized.control")


def test_gathered_stage1_contracts():
    """IVF face of the same guarantee: the gathered (probing) paths never
    hold a (Q, W) slot-score batch or the (Q, N) matrix."""
    assert_contract("stage1.gathered.xla")
    assert_contract("stage1.gathered.pallas")


def test_backend_capability_matrix_and_generator_resolution():
    assert backend_supports("xla", "streaming_topl")
    assert backend_supports("pallas", "streaming_topl")
    assert backend_supports("pallas", "fused_topl")
    assert not backend_supports("onehot", "streaming_topl")
    assert backend_capabilities("onehot") == frozenset()
    with pytest.raises(ValueError):
        backend_capabilities("cuda")

    assert isinstance(candidate_generator_for("xla"), StreamingTopL)
    assert isinstance(candidate_generator_for("pallas"), StreamingTopL)
    assert isinstance(candidate_generator_for("onehot"), MaterializedTopL)
    auto = candidate_generator_for("auto")
    assert isinstance(auto, StreamingTopL)        # xla on CPU, pallas on TPU
    assert not auto.materializes_scores


@pytest.mark.parametrize("block_q,q", QUERY_BLOCKS)
def test_qbias_stream_flows_through_every_path(scan_case, block_q, q):
    """The per-(query, point) bias stream (the lowered filter mask) is
    bit-exact across the materialized oracle, the chunked xla path and
    the fused kernel — ±inf entries included."""
    rng = np.random.default_rng(7)
    n = 900
    codes, luts = scan_case(rng, n, m=4, k=32, q=q, tie_heavy=True)
    bias = jnp.asarray(rng.integers(0, 3, (n,)), jnp.float32)
    qbias = jnp.where(jnp.asarray(rng.integers(0, 3, (q, n))) == 0,
                      jnp.inf, 0.0)
    scores = ref.adc_scan_batch_ref(codes, luts) + bias[None, :] + qbias
    neg, idx = jax.lax.top_k(-scores, 60)
    want_s, want_i = -neg, idx
    for impl in ("xla", "pallas"):
        got_s, got_i = ops.adc_scan_topl(codes, luts, topl=60, bias=bias,
                                         qbias=qbias, impl=impl,
                                         block_n=256, block_q=block_q,
                                         chunk_n=192)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s),
                                      err_msg=impl)
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i),
                                      err_msg=impl)


def test_generators_bit_identical_on_index_data(tiny_dataset,
                                                trained_index_factory):
    """End-to-end generator interchange on a real trained index (RVQ so the
    per-point bias is exercised): streaming == materialized bit-for-bit."""
    index = trained_index_factory("RVQ2x32,Rerank60", iters=4)
    luts = index._build_luts(jnp.asarray(tiny_dataset.queries[:25]))
    m_s, m_i = MaterializedTopL("xla").topl(index.codes, luts, index.bias,
                                            topl=60)
    for impl in ("xla", "pallas"):
        s_s, s_i = StreamingTopL(impl).topl(index.codes, luts, index.bias,
                                            topl=60)
        np.testing.assert_array_equal(np.asarray(s_s), np.asarray(m_s),
                                      err_msg=impl)
        np.testing.assert_array_equal(np.asarray(s_i), np.asarray(m_i),
                                      err_msg=impl)


def test_index_bias_is_public(trained_index_factory):
    """Satellite: wrappers read ``Index.bias``, never ``_bias`` (custom
    subclasses only need the public surface)."""
    pq = trained_index_factory("PQ4x32,Rerank50", iters=4)
    assert pq.bias is None
    rvq = trained_index_factory("RVQ2x32,Rerank60", iters=4)
    assert rvq.bias is not None and rvq.bias.shape == (rvq.ntotal,)
