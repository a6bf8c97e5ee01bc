"""Seeded data of the benchmark, drawn on the device.

Deep-like 96-d descriptors follow the latent-mixture generator of the
program's ``data.descriptors`` (``_mixture`` + ``_deep_like``): a
random two-layer feature map of clustered latent Gaussians, L2
normalised, plus a full-dimensional texture term, normalised again. It
is rewritten here in ``jax.random`` so that a 10^7-vector corpus is
drawn on the chip chunk by chunk, and so that the yardstick does not
change when the program's generator does.

Every stream of random numbers is keyed by the run's seed and a fixed
tag, so one seed always gives the same weights, codes, corpus and
queries, whatever else a run draws.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NOISE_SIGMA = 0.9          # latent spread around each mixture centre
TEXTURE_SIGMA = 0.55       # texture term, relative to the unit norm
_HIGHEST = jax.lax.Precision.HIGHEST

# stream tags: one per kind of data drawn from a seed
MIXTURE, QUERIES, CORPUS, SAMPLE, WEIGHTS, CODES, FIT = range(7)


def seed_key(seed: int, tag: int) -> jax.Array:
    """The key of stream ``tag`` for ``seed``. Seeds may exceed 32 bits:
    ``jax.random.key`` keeps only the low word without 64-bit mode, so
    the high word is folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, tag)


@functools.partial(jax.jit, static_argnames=("dim", "centers", "latent"))
def mixture(key, *, dim: int, centers: int, latent: int):
    """The latent mixture: centres and the random two-layer feature map."""
    kc, k1, k2 = jax.random.split(key, 3)
    c = jax.random.normal(kc, (centers, latent))
    w1 = jax.random.normal(k1, (latent, 4 * latent)) / jnp.sqrt(latent)
    w2 = jax.random.normal(k2, (4 * latent, dim)) / jnp.sqrt(4 * latent)
    return c, w1, w2


def _normalise(x):
    return x / (jnp.linalg.norm(x, axis=1, keepdims=True) + 1e-9)


@functools.partial(jax.jit, static_argnames=("n",))
def deep_like(key, mix, *, n: int) -> jax.Array:
    """(n, dim) float32 Deep-like descriptors of unit norm."""
    centres, w1, w2 = mix
    kc, kz, kt = jax.random.split(key, 3)
    z = centres[jax.random.randint(kc, (n,), 0, centres.shape[0])] \
        + NOISE_SIGMA * jax.random.normal(kz, (n, centres.shape[1]))
    x = jnp.dot(jax.nn.relu(jnp.dot(z, w1, precision=_HIGHEST)), w2,
                precision=_HIGHEST)
    x = _normalise(x)
    dim = w2.shape[1]
    x = x + (TEXTURE_SIGMA / jnp.sqrt(dim)) * jax.random.normal(
        kt, (n, dim))
    return _normalise(x).astype(jnp.float32)


class DeepLike:
    """One seed's Deep-like data: the query pool and the corpus chunks.

    The corpus is cut in fixed chunks of ``chunk`` rows, each drawn
    from its own key, so the corpus is the same bits whichever code
    asks for it and in what order."""

    def __init__(self, seed: int, data: dict, chunk: int = 1 << 21):
        self.seed = seed
        self.chunk = chunk
        self.mix = mixture(seed_key(seed, MIXTURE), dim=data["dim"],
                           centers=data["centers"], latent=data["latent"])

    def queries(self, n: int) -> jax.Array:
        return deep_like(seed_key(self.seed, QUERIES), self.mix, n=n)

    def sample(self, n: int) -> jax.Array:
        """A training sample, drawn apart from the corpus."""
        return deep_like(seed_key(self.seed, SAMPLE), self.mix, n=n)

    def corpus_chunks(self, n_total: int):
        """Yield (offset, (rows, dim) array) over the n_total corpus rows."""
        base = seed_key(self.seed, CORPUS)
        for i, lo in enumerate(range(0, n_total, self.chunk)):
            rows = min(self.chunk, n_total - lo)
            yield lo, deep_like(jax.random.fold_in(base, i), self.mix,
                                n=rows)
