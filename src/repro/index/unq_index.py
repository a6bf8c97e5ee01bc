"""UNQ-backed Index: the paper's neural quantizer behind the FAISS-style
surface (train = §3.4 objective, add = one feed-forward encode pass,
search = d2 LUT scan + d1 decoder rerank)."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import unq
from repro.index import base
from repro.index.backend import encode_impl_for, resolve_scan_backend
from repro.kernels import ops


@functools.partial(jax.jit, static_argnames=("cfg", "impl"))
def _encode_batch(params, state, cfg: unq.UNQConfig, xb, *, impl: str):
    """One feed-forward pass: (B, D) -> (B, M) uint8 (the paper's headline
    encoding speed — no iterative optimization, unlike AQ/LSQ)."""
    heads, _ = unq.encode_heads(params, state, cfg, xb, train=False)
    return ops.unq_encode(heads, params["codebooks"],
                          impl=impl).astype(jnp.uint8)


def encode_database(params, state, cfg: unq.UNQConfig, base_x, *,
                    batch_size: int = 8192, impl: str = "xla") -> jax.Array:
    """Compress a base set: (N, D) -> uint8 codes (N, M), batched."""
    n = base_x.shape[0]
    outs = []
    for s in range(0, n, batch_size):
        outs.append(_encode_batch(params, state, cfg, base_x[s:s + batch_size],
                                  impl=impl))
    return jnp.concatenate(outs, axis=0)


def build_luts(params, state, cfg: unq.UNQConfig, queries) -> jax.Array:
    """(Q, D) queries -> (Q, M, K) tables of -<net(q)_m, c_mk> (d2, Eq. 8)."""
    heads, _ = unq.encode_heads(params, state, cfg, queries, train=False)
    return -unq.head_logits(params, heads)


class UNQIndex(base.Index):
    """Unsupervised Neural Quantization index (Morozov & Babenko 2019)."""

    kind = "unq"

    def __init__(self, dim: int, *, num_codebooks: int = 8,
                 codebook_size: int = 256, rerank: int = 500,
                 backend: str = "auto", cfg: unq.UNQConfig | None = None):
        super().__init__(dim, rerank=rerank, backend=backend)
        self.cfg = cfg if cfg is not None else unq.UNQConfig(
            dim=dim, num_codebooks=num_codebooks,
            codebook_size=codebook_size)
        assert self.cfg.dim == dim
        self.params = None
        self.state = None
        self.history: list[dict] = []

    @classmethod
    def from_trained(cls, params, state, cfg: unq.UNQConfig, *, codes=None,
                     rerank: int = 500, backend: str = "auto") -> "UNQIndex":
        """Wrap an already-trained UNQ model (and optionally its codes)."""
        index = cls(cfg.dim, rerank=rerank, backend=backend, cfg=cfg)
        index.params, index.state = params, state
        if codes is not None:
            index._codes = jnp.asarray(codes)
        return index

    @property
    def is_trained(self) -> bool:
        return self.params is not None

    def _fit_quantizer(self, xs, *, train_cfg=None, callback=None,
                       **overrides):
        """Fit UNQ on (n, dim) vectors (paper §3.4: QHAdam + One-Cycle,
        L = L1 + alpha*L2 + beta*CV^2). ``overrides`` are TrainConfig
        fields (epochs=..., lr=..., alpha=...)."""
        from repro.core import training
        from repro.data import descriptors as ddata

        xs = np.asarray(xs, np.float32)
        tcfg = train_cfg if train_cfg is not None else \
            training.TrainConfig(**overrides)
        ds = ddata.DescriptorDataset(
            train=xs, base=xs[:0], queries=xs[:0],
            gt_nn=np.zeros((0,), np.int64), name="index-train")
        self.params, self.state, self.history = training.train_unq(
            ds, self.cfg, tcfg, callback=callback)

    def _encode(self, xs) -> jax.Array:
        impl = encode_impl_for(resolve_scan_backend(self.backend))
        return encode_database(self.params, self.state, self.cfg, xs,
                               impl=impl)

    def _build_luts(self, queries) -> jax.Array:
        return base.per_query(
            lambda q: build_luts(self.params, self.state, self.cfg, q),
            jnp.asarray(queries))

    def _build_decode_table(self) -> None:
        # the MLP decoder is not an additive code table, so the stage-2
        # engine resolves to the cross-query dedup reranker (each unique
        # candidate decoded once) instead of the fused table kernel
        return None

    def _reconstruct(self, codes) -> jax.Array:
        return unq.decode_codes(self.params, self.state, self.cfg, codes)

    # -- persistence -------------------------------------------------------

    def _tree(self):
        codes = self._codes if self._codes is not None else \
            jnp.zeros((0, self.cfg.num_codebooks), jnp.uint8)
        return {"params": self.params, "state": self.state, "codes": codes}

    def _metadata(self) -> dict:
        cfg = {k: v for k, v in dataclasses.asdict(self.cfg).items()
               if k != "dtype"}   # dtype is not JSON; f32 is the only one used
        return {"cfg": cfg, "rerank": self.rerank, "backend": self.backend,
                "ntotal": self.ntotal}

    @classmethod
    def _empty_from_metadata(cls, meta: dict) -> "UNQIndex":
        cfg = unq.UNQConfig(**meta["cfg"])
        index = cls(cfg.dim, rerank=meta["rerank"], backend=meta["backend"],
                    cfg=cfg)
        index.params, index.state = unq.init(jax.random.PRNGKey(0), cfg)
        index._codes = jnp.zeros((meta["ntotal"], cfg.num_codebooks),
                                 jnp.uint8)
        return index

    def _set_tree(self, tree) -> None:
        self.params, self.state = tree["params"], tree["state"]
        self._codes = tree["codes"] if tree["codes"].shape[0] else None
        self._invalidate_caches()
