import copy

import jax
import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose — unit tests/benches must see the real
# single CPU device; only launch/dryrun.py forces 512 host devices, and the
# multi-device tests spawn subprocesses with their own flags.

jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop a module's compiled programs when it ends. XLA's CPU backend
    keeps memory mappings for every executable it holds, and one test
    process that runs several kernel-heavy modules (interpret-mode Pallas
    kernels compile to large programs) would otherwise pass the kernel's
    per-process map limit (``vm.max_map_count``) and die in a compile."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def tiny_dataset():
    from repro.data.descriptors import make_synthetic_dataset
    return make_synthetic_dataset("deep", n_train=1500, n_base=4000,
                                  n_query=150, n_centers=64, seed=0)


@pytest.fixture(scope="session")
def tiny_unq(tiny_dataset):
    """A small UNQ model trained for a couple of epochs (shared by search /
    integration tests; quality asserted loosely, mechanics strictly)."""
    import jax.numpy as jnp
    from repro.core import unq, training

    cfg = unq.UNQConfig(dim=96, num_codebooks=8, codebook_size=64,
                        code_dim=32, hidden_dim=96)
    tcfg = training.TrainConfig(epochs=20, batch_size=256, lr=5e-3,
                                log_every=10)
    params, state, history = training.train_unq(tiny_dataset, cfg, tcfg)
    return cfg, params, state, history


# ---------------------------------------------------------------------------
# shared trained indexes: training a quantizer is the dominant cost of the
# index-level suites, and most tests only need SOME trained index — one
# session-scoped cache hands out cheap shallow clones (mutating a clone's
# backend / codes never touches the master or other tests)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="session")
def trained_index_factory(tiny_dataset):
    """``get(spec, **train_kw) -> trained+added Index`` with one training
    run per distinct (spec, train_kw) for the whole session.

    Returned objects are ``copy.copy`` clones of the cached master: all
    heavy state (model params, code buffers) is shared immutably, while
    attribute mutation (``index.backend = ...``) stays local to the
    clone. Tests that need to exercise training itself should keep
    building indexes from scratch instead.
    """
    from repro.index import index_factory

    cache = {}

    def get(spec: str, **train_kw):
        key = (spec, tuple(sorted(train_kw.items())))
        if key not in cache:
            index = index_factory(spec, dim=tiny_dataset.dim)
            index.train(tiny_dataset.train, **train_kw)
            index.add(tiny_dataset.base)
            cache[key] = index
        return copy.copy(cache[key])

    return get


# ---------------------------------------------------------------------------
# shared synthetic-case builders (deduplicated from test_topl / test_rerank /
# test_ivf): tie-heavy integer tables make score/distance collisions
# ubiquitous, so downstream parity checks exercise tie RESOLUTION, not just
# the score math
# ---------------------------------------------------------------------------

@pytest.fixture(scope="session")
def scan_case():
    """(rng, n, m, k, q, tie_heavy) -> (codes (N, M) u8, luts (Q, M, K))."""
    import jax.numpy as jnp

    def make(rng, n, m, k, q, tie_heavy):
        codes = jnp.asarray(rng.integers(0, k, (n, m)), jnp.uint8)
        if tie_heavy:
            luts = jnp.asarray(rng.integers(-2, 3, (q, m, k)), jnp.float32)
        else:
            luts = jnp.asarray(rng.normal(size=(q, m, k)), jnp.float32)
        return codes, luts

    return make


@pytest.fixture(scope="session")
def rerank_case():
    """(rng, q, l, m, k, d, tie_heavy) -> (cand codes (Q, L, M) u8,
    queries (Q, D), decode table (M, K, D))."""
    import jax.numpy as jnp

    def make(rng, q, l, m, k, d, tie_heavy):
        cand = jnp.asarray(rng.integers(0, k, (q, l, m)), jnp.uint8)
        queries = jnp.asarray(rng.normal(size=(q, d)), jnp.float32)
        if tie_heavy:
            table = jnp.asarray(rng.integers(-2, 3, (m, k, d)), jnp.float32)
            queries = jnp.round(queries)
        else:
            table = jnp.asarray(rng.normal(size=(m, k, d)), jnp.float32)
        return cand, queries, table

    return make


@pytest.fixture(scope="session")
def ivf_flat_pair(trained_index_factory):
    """(ivf_spec_tail, train_kw) -> (IVFIndex, flat Index) over the SAME
    data with identically-trained quantizers (same seed/iters), the
    standing setup of the IVF==flat parity properties."""

    def make(quant: str, nlist: int, rerank: int = 50, **train_kw):
        flat = trained_index_factory(f"{quant},Rerank{rerank}", **train_kw)
        ivf = trained_index_factory(
            f"IVF{nlist},{quant},Rerank{rerank}", **train_kw)
        return ivf, flat

    return make
