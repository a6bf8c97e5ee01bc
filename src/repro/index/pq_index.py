"""Shallow MCQ baselines behind the same Index protocol as UNQ: PQ, OPQ
and RVQ (the additive-family stand-in for LSQ). Sharing the protocol —
and the exact same batched ADC scan kernel — is what turns the paper's
Table 1-4 method comparisons into one loop over indexes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import baselines as bl
from repro.index import base
from repro.kernels import ref


class PQIndex(base.Index):
    """Product Quantization (Jegou et al. 2011). ADC-only by default
    (``rerank=0`` matches classic IndexPQ); give a rerank budget to re-rank
    the top-L with reconstruction distances."""

    kind = "pq"

    def __init__(self, dim: int, *, num_books: int = 8, book_size: int = 256,
                 rerank: int = 0, backend: str = "auto"):
        super().__init__(dim, rerank=rerank, backend=backend)
        assert dim % num_books == 0, (dim, num_books)
        self.num_books = num_books
        self.book_size = book_size
        self.model: bl.PQModel | None = None

    @property
    def is_trained(self) -> bool:
        return self.model is not None

    def _fit_quantizer(self, xs, *, iters: int = 25, seed: int = 0, **_):
        self.model = bl.train_pq(jax.random.PRNGKey(seed), jnp.asarray(xs),
                                 self.num_books, self.book_size, iters=iters)

    def _encode(self, xs) -> jax.Array:
        return self.model.encode(xs)

    def _build_luts(self, queries) -> jax.Array:
        # per-subspace squared-L2 tables; summed over m this is the exact
        # compressed-domain distance (no per-query constant needed)
        return base.per_query(jax.vmap(self.model.lut),
                              jnp.asarray(queries))

    def _build_decode_table(self) -> jax.Array:
        # each sub-codebook embedded into its D-slice (zero elsewhere);
        # OPQ folds the inverse rotation into the table, so the additive
        # sum IS decode() in the original space
        m, k, d_sub = self.model.codebooks.shape
        table = jnp.zeros((m, k, self.dim), jnp.float32)
        for i in range(m):
            table = table.at[i, :, i * d_sub:(i + 1) * d_sub].set(
                self.model.codebooks[i])
        if self.model.rotation is not None:
            table = jnp.dot(table, self.model.rotation.T,
                            precision=jax.lax.Precision.HIGHEST)
        return table

    def _reconstruct(self, codes) -> jax.Array:
        # table decode (not model.decode): the one association every
        # stage-2 path shares, making fused/chunked/vmap bit-identical
        return ref.decode_with_table(codes, self._decode_table())

    # -- persistence -------------------------------------------------------

    def _tree(self):
        codes = self._codes if self._codes is not None else \
            jnp.zeros((0, self.num_books), jnp.uint8)
        tree = {"codebooks": self.model.codebooks, "codes": codes}
        if self.model.rotation is not None:
            tree["rotation"] = self.model.rotation
        return tree

    def _metadata(self) -> dict:
        return {"dim": self.dim, "num_books": self.num_books,
                "book_size": self.book_size, "rerank": self.rerank,
                "backend": self.backend, "ntotal": self.ntotal,
                "has_rotation": self.model.rotation is not None}

    @classmethod
    def _empty_from_metadata(cls, meta: dict):
        index = cls(meta["dim"], num_books=meta["num_books"],
                    book_size=meta["book_size"], rerank=meta["rerank"],
                    backend=meta["backend"])
        d_sub = meta["dim"] // meta["num_books"]
        rot = jnp.eye(meta["dim"]) if meta["has_rotation"] else None
        index.model = bl.PQModel(
            jnp.zeros((meta["num_books"], meta["book_size"], d_sub),
                      jnp.float32), rotation=rot)
        index._codes = jnp.zeros((meta["ntotal"], meta["num_books"]),
                                 jnp.uint8)
        return index

    def _set_tree(self, tree) -> None:
        self.model.codebooks = tree["codebooks"]
        if "rotation" in tree:
            self.model.rotation = tree["rotation"]
        self._codes = tree["codes"] if tree["codes"].shape[0] else None
        self._invalidate_caches()


class OPQIndex(PQIndex):
    """Optimized PQ (Ge et al. 2013): learned rotation + PQ."""

    kind = "opq"

    def _fit_quantizer(self, xs, *, outer_iters: int = 8,
                       kmeans_iters: int = 10, seed: int = 0, **_):
        self.model = bl.train_opq(jax.random.PRNGKey(seed), jnp.asarray(xs),
                                  self.num_books, self.book_size,
                                  outer_iters=outer_iters,
                                  kmeans_iters=kmeans_iters)


class RVQIndex(base.Index):
    """Residual Vector Quantization (additive family). ADC for additive
    codes needs ||decode(i)||^2 alongside the inner-product LUTs —
    ``||q - x~||^2 = ||x~||^2 - 2<q, x~> + const(q)`` — carried here as the
    per-point score bias (the standard extra-4-bytes trick)."""

    kind = "rvq"

    def __init__(self, dim: int, *, num_books: int = 8, book_size: int = 256,
                 rerank: int = 0, backend: str = "auto"):
        super().__init__(dim, rerank=rerank, backend=backend)
        self.num_books = num_books
        self.book_size = book_size
        self.model: bl.RVQModel | None = None

    @property
    def is_trained(self) -> bool:
        return self.model is not None

    def _fit_quantizer(self, xs, *, iters: int = 20, seed: int = 0, **_):
        self.model = bl.train_rvq(jax.random.PRNGKey(seed), jnp.asarray(xs),
                                  self.num_books, self.book_size, iters=iters)

    def _encode(self, xs) -> jax.Array:
        return self.model.encode(jnp.asarray(xs))

    def _encode_bias(self, codes) -> jax.Array:
        recon = self.model.decode(codes)
        return jnp.sum(recon * recon, axis=-1)

    def _build_luts(self, queries) -> jax.Array:
        # scaling by -2 inside the table keeps scan scores bit-identical to
        # ``norms - 2 * adc_scan(codes, lut_ip)`` (x2 is exact in fp)
        return -2.0 * base.per_query(jax.vmap(self.model.lut_ip),
                                     jnp.asarray(queries))

    def _build_decode_table(self) -> jax.Array:
        # additive codebooks are already full-dimensional
        return self.model.codebooks.astype(jnp.float32)

    def _reconstruct(self, codes) -> jax.Array:
        # table decode (chained adds) rather than model.decode's axis
        # reduction: the association every stage-2 path shares
        return ref.decode_with_table(codes, self._decode_table())

    # -- persistence -------------------------------------------------------

    def _tree(self):
        codes = self._codes if self._codes is not None else \
            jnp.zeros((0, self.num_books), jnp.uint8)
        bias = self._bias if self._bias is not None else \
            jnp.zeros((0,), jnp.float32)
        return {"codebooks": self.model.codebooks, "codes": codes,
                "norms": bias}

    def _metadata(self) -> dict:
        return {"dim": self.dim, "num_books": self.num_books,
                "book_size": self.book_size, "rerank": self.rerank,
                "backend": self.backend, "ntotal": self.ntotal}

    @classmethod
    def _empty_from_metadata(cls, meta: dict) -> "RVQIndex":
        index = cls(meta["dim"], num_books=meta["num_books"],
                    book_size=meta["book_size"], rerank=meta["rerank"],
                    backend=meta["backend"])
        index.model = bl.RVQModel(jnp.zeros(
            (meta["num_books"], meta["book_size"], meta["dim"]), jnp.float32))
        index._codes = jnp.zeros((meta["ntotal"], meta["num_books"]),
                                 jnp.uint8)
        return index

    def _set_tree(self, tree) -> None:
        self.model.codebooks = tree["codebooks"]
        self._codes = tree["codes"] if tree["codes"].shape[0] else None
        self._bias = tree["norms"] if tree["norms"].shape[0] else None
        self._invalidate_caches()
