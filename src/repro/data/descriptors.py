"""Descriptor datasets + exact ground-truth nearest neighbors.

The paper evaluates on Deep1M/Deep1B (96-d CNN descriptors) and
BigANN1M/1B (128-d SIFT). Those datasets are not available offline, so the
pipeline provides statistically similar synthetic stand-ins:

  * ``deep``-style: L2-normalized activations of a random deep feature map
    (a random MLP applied to latent gaussians — correlated, low intrinsic
    dimension, unit norm, like the Deep1B descriptors of [3]).
  * ``sift``-style: non-negative, heavy-tailed histogram features with
    block-sparse structure, like SIFT.

Both are generated from a clustered latent mixture so nearest-neighbor
structure is non-trivial (pure i.i.d. gaussians make ANN meaninglessly hard
and flat). Everything is deterministic in the seed.

Exact k-NN (used for triplet sampling and for recall ground truth) is a
chunked brute-force scan in JAX — the same computation FAISS does on GPU in
the paper's setup.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class DescriptorDataset:
    train: np.ndarray     # (n_train, D) learning set
    base: np.ndarray      # (n_base, D)  database to compress
    queries: np.ndarray   # (n_query, D) held-out queries
    gt_nn: np.ndarray     # (n_query,)   true NN of each query in `base`
    name: str = "synthetic"

    @property
    def dim(self) -> int:
        return self.train.shape[1]


# Calibrated so 8-byte quantizer distortion is a realistic 20-40% of the
# data variance (real Deep1M/SIFT behave this way): the latent mixture
# overlaps heavily (sigma 0.9 vs unit center spread) and a full-dimensional
# "texture" component is added in descriptor space — real descriptors carry
# high-entropy content that 64 bits cannot capture, which is exactly what a
# tight synthetic manifold lacks (RVQ was near-lossless without it).
_NOISE_SIGMA = 0.9
_TEXTURE_SIGMA = 0.55     # relative to the unit-norm descriptor


def _deep_like(rng: np.random.Generator, n: int, dim: int, latent: int,
               centers: np.ndarray, w1: np.ndarray, w2: np.ndarray) -> np.ndarray:
    z = centers[rng.integers(0, len(centers), n)] + rng.normal(
        0, _NOISE_SIGMA, (n, latent))
    h = np.maximum(z @ w1, 0.0)
    x = h @ w2
    x = x / (np.linalg.norm(x, axis=1, keepdims=True) + 1e-9)
    x = x + rng.normal(0, _TEXTURE_SIGMA / np.sqrt(dim), (n, dim))
    x = x / (np.linalg.norm(x, axis=1, keepdims=True) + 1e-9)
    return x.astype(np.float32)


def _sift_like(rng: np.random.Generator, n: int, dim: int, latent: int,
               centers: np.ndarray, w1: np.ndarray, w2: np.ndarray) -> np.ndarray:
    z = centers[rng.integers(0, len(centers), n)] + rng.normal(
        0, _NOISE_SIGMA, (n, latent))
    h = np.maximum(z @ w1, 0.0)
    x = np.abs(h @ w2)
    scale = np.mean(x)
    x = np.abs(x + rng.normal(0, _TEXTURE_SIGMA * scale, (n, dim)))
    # heavy-tailed histogram-ish counts, clipped like root-SIFT pipelines
    x = np.minimum(x ** 1.5 * 25.0, 255.0)
    return x.astype(np.float32)


def _mixture(rng: np.random.Generator, dim: int, n_centers: int,
             latent: int):
    """The latent mixture: centers and the random two-layer feature map."""
    centers = rng.normal(0, 1.0, (n_centers, latent))
    w1 = rng.normal(0, 1.0 / np.sqrt(latent), (latent, 4 * latent))
    w2 = rng.normal(0, 1.0 / np.sqrt(4 * latent), (4 * latent, dim))
    return centers, w1, w2


def make_synthetic_dataset(kind: str = "deep", *, dim: int | None = None,
                           n_train: int = 20_000, n_base: int = 50_000,
                           n_query: int = 1_000, n_centers: int = 512,
                           latent: int = 24, seed: int = 0,
                           compute_gt: bool = True) -> DescriptorDataset:
    """Build a Deep1M/BigANN1M-like synthetic dataset (sizes configurable —
    the paper's 500k-train/1M-base protocol is the default in benchmarks,
    scaled down for CPU in tests)."""
    if dim is None:
        dim = 96 if kind == "deep" else 128
    rng = np.random.default_rng(seed)
    centers, w1, w2 = _mixture(rng, dim, n_centers, latent)
    gen = _deep_like if kind == "deep" else _sift_like
    train = gen(rng, n_train, dim, latent, centers, w1, w2)
    base = gen(rng, n_base, dim, latent, centers, w1, w2)
    queries = gen(rng, n_query, dim, latent, centers, w1, w2)
    gt = exact_knn(queries, base, k=1)[:, 0] if compute_gt else np.zeros(
        (n_query,), np.int64)
    return DescriptorDataset(train, base, queries, gt,
                             name=f"{kind}{n_base // 1000}k")


def exact_knn(queries: np.ndarray, base: np.ndarray, k: int,
              batch: int = 256) -> np.ndarray:
    """Exact top-k neighbors by L2, chunked over queries: (Q, k) indices."""
    base_j = jnp.asarray(base)
    base_sq = jnp.sum(base_j * base_j, axis=1)

    @jax.jit
    def _knn(qb):
        d = (jnp.sum(qb * qb, axis=1)[:, None]
             - 2.0 * jnp.dot(qb, base_j.T, precision=jax.lax.Precision.HIGHEST)
             + base_sq[None, :])
        _, idx = jax.lax.top_k(-d, k)
        return idx

    outs = []
    for s in range(0, queries.shape[0], batch):
        outs.append(np.asarray(_knn(jnp.asarray(queries[s:s + batch]))))
    return np.concatenate(outs, axis=0)


def sample_triplets(rng: np.random.Generator, train: np.ndarray,
                    neighbors: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-epoch positive/negative sampling (paper §3.4).

    neighbors: (n, >=200) each row = indices of the true NNs of train[i]
    (excluding i itself). Positives ~ top-3 NNs; negatives ~ ranks 100..200.
    Returns (pos_idx, neg_idx), each (n,).
    """
    n = train.shape[0]
    pos = neighbors[np.arange(n), rng.integers(0, 3, n)]
    hi = min(200, neighbors.shape[1])
    lo = min(100, hi - 1)
    neg = neighbors[np.arange(n), rng.integers(lo, hi, n)]
    return pos, neg


def epoch_neighbors(train: np.ndarray, k: int = 201, batch: int = 256) -> np.ndarray:
    """Top-k true NNs of every training point within the train set,
    excluding the point itself (column 0 of exact_knn is the point)."""
    nn = exact_knn(train, train, k=k, batch=batch)
    return nn[:, 1:]
