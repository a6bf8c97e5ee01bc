"""KV-cache compression with multi-codebook quantization — the paper's
technique integrated into the LM zoo (DESIGN.md §4).

A decode-attention logit against a compressed key IS the paper's d2 (Eq. 8):

    q . k_s  ~=  sum_m <q_m, cK_{m, i_{s,m}}>

so scoring a 500k-token cache costs M table adds per cached token (plus one
M*K LUT build per query), and the value aggregation folds softmax weights
into a per-codeword histogram before a single (M*K, d) matmul — O(S*M)
scatter-adds instead of O(S*d) MACs, exactly the paper's compressed-domain
scan transplanted into attention.

Storage per cached token per kv-head: 2*M bytes (keys+values) instead of
2*dh*2 bytes bf16 — 32x smaller at M=8, dh=128. This is what makes the
gemma3 long_500k bonus cell fit (see EXPERIMENTS.md §Dry-run).

Codebooks are per-(layer-group, kv-head, subspace) and are calibrated with
k-means on sampled K/V vectors (``calibrate_kvq``) — the PQ member of the
paper's MCQ family; the UNQ nonlinear encoder/decoder can be swapped in for
the codebook-learning step without changing this scoring path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.parallel import hints


def _dims(cfg: ModelConfig):
    m = cfg.kvq_books
    dh = cfg.dh
    assert dh % m == 0, (dh, m)
    return m, cfg.kvq_book_size, dh // m


def init_kvq_cache(cfg: ModelConfig, ng: int, batch: int, s: int):
    """Compressed cache for one (global-attention) sub-layer slot.

    Codebooks ride along in the cache pytree (they are per-layer serving
    constants, calibrated offline; random-init here stands in for the
    dry-run and is overwritten by ``calibrate_kvq`` in serving)."""
    m, k, d_sub = _dims(cfg)
    hkv = cfg.num_kv_heads
    key = jax.random.PRNGKey(0)
    books = jax.random.normal(key, (ng, hkv, m, k, d_sub)) * 0.02
    return {
        "k_codes": jnp.zeros((ng, batch, s, hkv, m), jnp.uint8),
        "v_codes": jnp.zeros((ng, batch, s, hkv, m), jnp.uint8),
        "k_books": books.astype(jnp.float32),
        "v_books": books.astype(jnp.float32),
    }


def quantize_vectors(x, books):
    """PQ-encode: x (..., dh), books (M, K, d_sub) -> codes (..., M) uint8.

    Nearest codeword per subspace by L2 (reconstruction-optimal for ADC)."""
    m, k, d_sub = books.shape
    xs = x.reshape(*x.shape[:-1], m, d_sub)
    d = (jnp.sum(xs * xs, axis=-1)[..., None]
         - 2.0 * jnp.einsum("...ms,mks->...mk", xs, books)
         + jnp.sum(books * books, axis=-1))
    return jnp.argmin(d, axis=-1).astype(jnp.uint8)


def dequantize_codes(codes, books):
    """codes (..., M) -> (..., dh)."""
    m, k, d_sub = books.shape
    m_idx = jnp.arange(m)
    cw = books[m_idx, codes.astype(jnp.int32)]       # (..., M, d_sub)
    return cw.reshape(*codes.shape[:-1], m * d_sub)


def calibrate_kvq(key, samples, m: int, book_size: int, iters: int = 15):
    """k-means codebooks from sampled cache vectors: (N, dh) -> (M, K, d_sub)."""
    from repro.core.baselines import kmeans
    n, dh = samples.shape
    d_sub = dh // m
    xs = samples.reshape(n, m, d_sub)
    keys = jax.random.split(key, m)
    return jnp.stack([kmeans(keys[i], xs[:, i, :], book_size, iters)
                      for i in range(m)])


def decode_attention_kvq_sharded(cfg: ModelConfig, cache, q, k_new, v_new,
                                 pos, mesh, seq_axes):
    """Explicit shard_map schedule for single-stream long-context decode
    (§Perf iteration 7): each shard ADC-scans its local slice of the code
    cache, the softmax reduces via (pmax, psum), and value aggregation
    psums per-shard partial histograms — the same shard/merge pattern as
    the paper's distributed billion-scale search. No sequence gather.
    """
    from jax.sharding import PartitionSpec as P

    m, kk, d_sub = _dims(cfg)
    b, h, dh = q.shape
    hkv = cfg.num_kv_heads
    rep = h // hkv
    s = cache["k_codes"].shape[1]
    axes = tuple(a for a in (seq_axes if isinstance(seq_axes, (tuple, list))
                             else (seq_axes,)) if a)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    s_loc = s // n_shards

    def body(k_codes, v_codes, k_books, v_books, q_, k_new_, v_new_, pos_):
        # shard offset along the flattened seq axes
        idx = jnp.zeros((), jnp.int32)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        off = idx * s_loc

        kc_new = quantize_vectors_per_head(k_new_, k_books)
        vc_new = quantize_vectors_per_head(v_new_, v_books)
        # write only on the owning shard
        local_pos = jnp.clip(pos_ - off, 0, s_loc - 1)
        own = (pos_ >= off) & (pos_ < off + s_loc)
        k_upd = jax.lax.dynamic_update_slice_in_dim(
            k_codes, kc_new[:, None], local_pos, axis=1)
        v_upd = jax.lax.dynamic_update_slice_in_dim(
            v_codes, vc_new[:, None], local_pos, axis=1)
        k_codes = jnp.where(own, k_upd, k_codes)
        v_codes = jnp.where(own, v_upd, v_codes)

        qg = q_.reshape(b, hkv, rep, m, d_sub)
        lut = jnp.einsum("bhrms,hmks->bhrmk", qg.astype(jnp.float32),
                         k_books)

        codes = k_codes.astype(jnp.int32)                    # (B,S_loc,Hkv,M)

        def scan_one(lut_bhr, codes_bh):                     # (M,K), (S,M)
            mi = jnp.arange(m)[None, :]
            return jnp.sum(lut_bhr[mi, codes_bh], axis=1)    # (S_loc,)

        logits = jax.vmap(jax.vmap(jax.vmap(
            scan_one, in_axes=(0, None)), in_axes=(0, 1)), in_axes=(0, 0))(
            lut, codes) / jnp.sqrt(dh)                       # (B,Hkv,rep,S_loc)
        gpos = off + jnp.arange(s_loc)
        logits = jnp.where((gpos <= pos_)[None, None, None, :], logits,
                           -jnp.inf)
        # global softmax via pmax/psum
        mx = logits.max(-1, keepdims=True)
        for a in axes:
            mx = jax.lax.pmax(mx, a)
        p = jnp.exp(logits - mx)
        denom = p.sum(-1, keepdims=True)
        for a in axes:
            denom = jax.lax.psum(denom, a)
        w = p / jnp.maximum(denom, 1e-30)

        onehot = jax.nn.one_hot(v_codes.astype(jnp.int32), kk,
                                dtype=jnp.float32)           # (B,S,Hkv,M,K)
        hist = jnp.einsum("bhrs,bshmk->bhrmk", w, onehot)
        for a in axes:
            hist = jax.lax.psum(hist, a)
        out = jnp.einsum("bhrmk,hmks->bhrms", hist, v_books)
        return out.reshape(b, h, dh).astype(q_.dtype), k_codes, v_codes

    seq_spec = seq_axes if not isinstance(seq_axes, (tuple, list)) else \
        tuple(seq_axes)
    codes_spec = P(None, seq_spec, None, None)
    from repro.parallel import hints as _hints
    with _hints.disabled():
        out, k_codes, v_codes = jax.shard_map(
            body, mesh=mesh,
            in_specs=(codes_spec, codes_spec, P(), P(), P(), P(), P(), P()),
            out_specs=(P(), codes_spec, codes_spec),
            check_vma=False,
        )(cache["k_codes"], cache["v_codes"], cache["k_books"],
          cache["v_books"], q, k_new, v_new, pos)
    return out, {**cache, "k_codes": k_codes, "v_codes": v_codes}


def decode_attention_kvq(cfg: ModelConfig, cache, q, k_new, v_new, pos):
    """One decode step against the compressed cache (single layer).

    cache: {"k_codes"/"v_codes" (B, S, Hkv, M), "k_books"/"v_books"
            (Hkv, M, K, d_sub)}  — the per-layer slice (scan strips ng).
    q:     (B, H, dh) current query;  k_new/v_new: (B, Hkv, dh).
    Returns (attention output (B, H, dh), updated cache).

    Routes to the explicit shard_map schedule for single-stream
    long-context serving (batch unsharded, sequence spread over the mesh).
    """
    mesh = hints.current_mesh()
    rules = hints.current_rules()
    if mesh is not None and rules is not None and rules.get("batch") is None:
        seq_axes = rules.get("kv_seq")
        if seq_axes:
            n = 1
            for a in (seq_axes if isinstance(seq_axes, (tuple, list))
                      else (seq_axes,)):
                n *= mesh.shape[a]
            if cache["k_codes"].shape[1] % n == 0:
                return decode_attention_kvq_sharded(
                    cfg, cache, q, k_new, v_new, pos, mesh, seq_axes)
    m, kk, d_sub = _dims(cfg)
    b, h, dh = q.shape
    hkv = cfg.num_kv_heads
    rep = h // hkv
    s = cache["k_codes"].shape[1]

    # --- encode the new K/V token and write its codes at `pos` ---
    k_codes_new = quantize_vectors_per_head(k_new, cache["k_books"])  # (B,Hkv,M)
    v_codes_new = quantize_vectors_per_head(v_new, cache["v_books"])
    k_codes = jax.lax.dynamic_update_slice_in_dim(
        cache["k_codes"], k_codes_new[:, None], pos, axis=1)
    v_codes = jax.lax.dynamic_update_slice_in_dim(
        cache["v_codes"], v_codes_new[:, None], pos, axis=1)

    # --- LUT build: O(H*M*K*d_sub), independent of S ---
    qg = q.reshape(b, hkv, rep, m, d_sub)
    lut = jnp.einsum("bhrms,hmks->bhrmk", qg.astype(jnp.float32),
                     cache["k_books"])                       # (B,Hkv,rep,M,K)

    # --- ADC scan over the cache: gather-sum, O(S*M) per head ---
    # logits[b,h,r,s] = sum_m lut[b,h,r,m, k_codes[b,s,h,m]]
    codes = k_codes.astype(jnp.int32)                        # (B,S,Hkv,M)

    def scan_one(lut_bhr, codes_bh):                         # (M,K), (S,M)
        m_idx = jnp.arange(m)[None, :]
        return jnp.sum(lut_bhr[m_idx, codes_bh], axis=1)     # (S,)

    logits = jax.vmap(  # over B
        jax.vmap(       # over Hkv
            jax.vmap(scan_one, in_axes=(0, None)),           # over rep
            in_axes=(0, 1)),
        in_axes=(0, 0))(lut, codes)                          # (B,Hkv,rep,S)
    logits = hints.hint(logits, "batch", None, None, "kv_seq")
    logits = logits / jnp.sqrt(dh)
    valid = (jnp.arange(s) <= pos)[None, None, None, :]
    w = jax.nn.softmax(jnp.where(valid, logits, -jnp.inf), axis=-1)
    w = hints.hint(w, "batch", None, None, "kv_seq")

    # --- compressed-domain value aggregation: weight histogram + matmul ---
    # One-hot einsum (not scatter-add): under pjit the contraction over the
    # SHARDED sequence axis stays local per shard and reduces with one tiny
    # (B,Hkv,rep,M,K) all-reduce; the scatter formulation forced GSPMD to
    # all-gather the full-length softmax weights (§Perf iteration 7).
    onehot = jax.nn.one_hot(v_codes.astype(jnp.int32), kk,
                            dtype=jnp.float32)               # (B,S,Hkv,M,K)
    onehot = hints.hint(onehot, "batch", "kv_seq", None, None, None)
    hist = jnp.einsum("bhrs,bshmk->bhrmk", w, onehot)        # (B,Hkv,rep,M,K)
    out = jnp.einsum("bhrmk,hmks->bhrms", hist, cache["v_books"])
    out = out.reshape(b, h, dh).astype(q.dtype)

    new_cache = {**cache, "k_codes": k_codes, "v_codes": v_codes}
    return out, new_cache


def quantize_vectors_per_head(x, books):
    """x (B, Hkv, dh), books (Hkv, M, K, d_sub) -> (B, Hkv, M) uint8."""
    return jax.vmap(quantize_vectors, in_axes=(1, 0), out_axes=1)(x, books)
