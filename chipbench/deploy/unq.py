"""Flat UNQ deployment, and its plain reference.

The program under test is ``index_factory(spec)`` holding weights and
codes that the benchmark draws from the seed: the weights of the UNQ
encoder, codebooks and decoder at the configuration's widths (the
paper's §3.2 model), and codes drawn uniformly, ``M`` bytes a vector.
With ``shards`` > 1 the index is wrapped in
``ShardedIndex(placement="device")``, one shard a chip.

The plain reference (``Reference``) is the same search written out in
``jax.numpy`` from the same seed, sharing nothing with the program: the
score tables ``-<net(q)_m, c_mk>`` (Eq. 8), the ADC scan of every code
and its top-L, then the decoder's reconstruction of each candidate and
the exact distance ``||q - g(code)||^2`` (Eq. 7) for the top-k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data, precision as prec
from chipbench.loadgen import category_of

BN_EPS = 1e-5              # BatchNorm epsilon of the UNQ MLP blocks
CALIBRATION_ROWS = 4096    # rows that set each BatchNorm's running stats
SCAN_CHUNK = 1 << 16       # reference scan rows per step
CHECK_BLOCK = 32           # queries per reference block

#: Ambiguity bands of the comparison, relative (see ``Reference.judge``):
#: a float32 computation of the same quantity in another order differs
#: by about 1e-7 of these scales, the next precision below float32 by
#: about 1e-5.
STAGE1_BAND = 1e-5         # of sum_m max_k |lut[m, k]|
D1_BAND = 1e-5             # of the k-th served distance

#: request arguments that do not change a flat index's exact answer
ANSWER_NEUTRAL = frozenset({"deadline_ms"})


def _widths(cfg: dict):
    return (cfg["dim"], cfg["num_codebooks"], cfg["codebook_size"],
            cfg["code_dim"], cfg["hidden_dim"], cfg["num_hidden_layers"])


def _mlp(m, x, precision: str):
    """Linear -> BatchNorm (running stats) -> ReLU blocks, then a linear
    head: the encoder net(x) and the decoder g of the paper."""
    for layer in m["layers"]:
        x = prec.dot(x, layer["w"], precision) + layer["b"]
        x = (x - layer["mean"]) * jax.lax.rsqrt(layer["var"] + BN_EPS)
        x = jax.nn.relu(x * layer["scale"] + layer["bias"])
    return prec.dot(x, m["head_w"], precision) + m["head_b"]


def _draw_mlp(key, probe, hidden: int, n_hidden: int, d_out: int,
              out_std: float):
    """He-initialised blocks whose BatchNorm running statistics are those
    of ``probe`` passed through the net, as training leaves them."""
    keys = jax.random.split(key, 4 * n_hidden + 2)
    x, layers = probe, []
    for i in range(n_hidden):
        kw, kb, ks, kbb = keys[4 * i:4 * i + 4]
        d = x.shape[1]
        w = jax.random.normal(kw, (d, hidden)) * jnp.sqrt(2.0 / d)
        b = 0.01 * jax.random.normal(kb, (hidden,))
        pre = prec.dot(x, w, "highest") + b
        layer = {"w": w, "b": b, "mean": jnp.mean(pre, axis=0),
                 "var": jnp.var(pre, axis=0),
                 "scale": jax.random.uniform(ks, (hidden,), minval=0.75,
                                             maxval=1.25),
                 "bias": 0.1 * jax.random.normal(kbb, (hidden,))}
        layers.append(layer)
        x = jax.nn.relu(
            (pre - layer["mean"]) * jax.lax.rsqrt(layer["var"] + BN_EPS)
            * layer["scale"] + layer["bias"])
    return {"layers": layers,
            "head_w": out_std * jax.random.normal(keys[-2],
                                                  (hidden, d_out)),
            "head_b": 0.01 * jax.random.normal(keys[-1], (d_out,))}


@functools.partial(jax.jit, static_argnames=("widths",))
def draw_weights(key, widths):
    """The UNQ weights of one seed, by role, in one call on the device."""
    dim, m, k, dc, hidden, n_hidden = widths
    kq, ke, kcb, kc, kd = jax.random.split(key, 5)
    codebooks = jax.random.normal(kcb, (m, k, dc)) / jnp.sqrt(dc)
    q = jax.random.normal(kq, (CALIBRATION_ROWS, dim))
    q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
    enc = _draw_mlp(ke, q, hidden, n_hidden, m * dc,
                    out_std=1.0 / np.sqrt(hidden))
    codes = jax.random.randint(kc, (CALIBRATION_ROWS, m), 0, k)
    z = _codeword_sum(codebooks, codes)
    # decoder outputs of about unit norm, the scale of the descriptors
    dec = _draw_mlp(kd, z, hidden, n_hidden, dim,
                    out_std=float(np.sqrt(2.0 / (hidden * dim))))
    return {"enc": enc, "dec": dec, "codebooks": codebooks,
            "log_tau": jnp.zeros((m,), jnp.float32)}


def _codeword_sum(codebooks, codes):
    """sum_m c_{m, code_m}: the decoder's input (paper §3.2)."""
    z = codebooks[0][codes[:, 0]]
    for j in range(1, codebooks.shape[0]):
        z = z + codebooks[j][codes[:, j]]
    return z


@functools.partial(jax.jit, static_argnames=("n", "m"))
def draw_codes(key, *, n: int, m: int):
    """(n, m) uint8 codes, uniform over K = 256."""
    return jax.random.bits(key, (n, m), jnp.uint8)


def _program_tree(w):
    """The weights in the program's parameter layout (``core.unq``)."""
    def mlp(part):
        params = {"layers": [{"w": l["w"], "b": l["b"]}
                             for l in part["layers"]],
                  "bn": [{"scale": l["scale"], "bias": l["bias"]}
                         for l in part["layers"]],
                  "head": {"w": part["head_w"], "b": part["head_b"]}}
        state = {"bn": [{"mean": l["mean"], "var": l["var"]}
                        for l in part["layers"]]}
        return params, state

    enc_p, enc_s = mlp(w["enc"])
    dec_p, dec_s = mlp(w["dec"])
    return ({"encoder": enc_p, "decoder": dec_p,
             "codebooks": w["codebooks"], "log_tau": w["log_tau"]},
            {"encoder": enc_s, "decoder": dec_s})


def build(cfg: dict, seed: int):
    """The program's index for one seed, and the ServeConfig fields the
    configuration fixes."""
    from repro.index import ShardedIndex, UNQIndex, index_factory

    shell = index_factory(cfg["spec"], dim=cfg["dim"])
    got = (shell.cfg.dim, shell.cfg.num_codebooks, shell.cfg.codebook_size,
           shell.cfg.code_dim, shell.cfg.hidden_dim,
           shell.cfg.num_hidden_layers)
    if got != _widths(cfg) or shell.rerank != cfg["rerank"]:
        raise ValueError(f"{cfg['spec']} builds widths {got}, rerank "
                         f"{shell.rerank}; the configuration states "
                         f"{_widths(cfg)}, rerank {cfg['rerank']}")
    params, state = _program_tree(
        draw_weights(data.seed_key(seed, data.WEIGHTS), _widths(cfg)))
    n = cfg["n_per_chip"] * cfg["shards"]
    codes = draw_codes(data.seed_key(seed, data.CODES), n=n,
                       m=cfg["num_codebooks"])
    index = UNQIndex.from_trained(params, state, shell.cfg, codes=codes,
                                  rerank=shell.rerank, backend=shell.backend)
    if cfg["shards"] > 1:
        index = ShardedIndex(index, num_shards=cfg["shards"],
                             placement="device")
    return index, {}


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision",))
def _luts(w, queries, *, precision: str):
    """(B, dim) -> (B, M, K) tables -<net(q)_m, c_mk>."""
    m, _, dc = w["codebooks"].shape
    heads = _mlp(w["enc"], queries, precision).reshape(-1, m, dc)
    return -prec.einsum("bmd,mkd->bmk", heads, w["codebooks"], precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def _d1(w, queries, codes, *, precision: str):
    """||q - g(code)||^2 for queries (B, dim) and codes (B, C, M)."""
    b, c, m = codes.shape
    z = _codeword_sum(w["codebooks"], codes.reshape(b * c, m).astype(
        jnp.int32))
    recon = _mlp(w["dec"], z, precision).reshape(b, c, -1)
    return jnp.sum(jnp.square(queries[:, None, :] - recon), axis=-1)


@functools.partial(jax.jit, static_argnames=("topl", "n", "precision",
                                             "categories"))
def _stage1(codes, luts, cats, *, topl: int, n: int, precision: str,
            categories: int):
    """Top-``topl`` (score, id) of sum_m lut[m, code_m] over the first n
    rows of ``codes`` (padded to whole SCAN_CHUNKs), ascending. A query
    whose ``cats`` entry is c >= 0 sees only the rows of category c (of
    ``categories``, ``loadgen.category_of``)."""
    b, m, k = luts.shape
    iota = jnp.arange(k, dtype=jnp.int32)[None, :]

    def step(i, carry):
        vals, ids = carry
        rows = jax.lax.dynamic_slice_in_dim(
            codes, i * SCAN_CHUNK, SCAN_CHUNK).astype(jnp.int32)
        acc = jnp.zeros((b, SCAN_CHUNK), jnp.float32)
        for j in range(m):
            onehot = (rows[:, j:j + 1] == iota).astype(jnp.float32)
            acc = acc + prec.einsum("bk,nk->bn", luts[:, j, :], onehot,
                                    precision)
        gid = i * SCAN_CHUNK + jnp.arange(SCAN_CHUNK, dtype=jnp.int32)
        keep = gid[None, :] < n
        if categories:
            keep = keep & ((cats[:, None] < 0) | (
                category_of(gid, categories)[None, :] == cats[:, None]))
        acc = jnp.where(keep, acc, jnp.inf)
        allv = jnp.concatenate([vals, acc], axis=1)
        allg = jnp.concatenate(
            [ids, jnp.broadcast_to(gid[None, :], (b, SCAN_CHUNK))], axis=1)
        neg, pos = jax.lax.top_k(-allv, topl)
        return -neg, jnp.take_along_axis(allg, pos, axis=1)

    init = (jnp.full((b, topl), jnp.inf, jnp.float32),
            jnp.full((b, topl), -1, jnp.int32))
    return jax.lax.fori_loop(0, codes.shape[0] // SCAN_CHUNK, step, init)


@jax.jit
def _scores_of(codes, luts, ids):
    """sum_m lut[m, code_m] of the rows ``ids`` (B, C)."""
    c = jnp.take(codes, ids, axis=0).astype(jnp.int32)       # (B, C, M)
    parts = jnp.take_along_axis(
        luts[:, None, :, :], c[..., None], axis=3)[..., 0]   # (B, C, M)
    acc = parts[..., 0]
    for j in range(1, parts.shape[-1]):
        acc = acc + parts[..., j]
    return acc


class Reference:
    """The search of one seed's deployment, recomputed plainly."""

    def __init__(self, cfg: dict, seed: int, precision: str = "highest"):
        self.precision = precision
        self.topl = cfg["rerank"]
        self.n = cfg["n_per_chip"] * cfg["shards"]
        self.w = draw_weights(data.seed_key(seed, data.WEIGHTS),
                              _widths(cfg))
        codes = draw_codes(data.seed_key(seed, data.CODES), n=self.n,
                           m=cfg["num_codebooks"])
        pad = (-self.n) % SCAN_CHUNK
        self.codes = jnp.pad(codes, ((0, pad), (0, 0)))

    def _pool(self, q, cats, categories: int):
        luts = _luts(self.w, q, precision=self.precision)
        vals, cand = _stage1(self.codes, luts, jnp.asarray(cats, jnp.int32),
                             topl=self.topl, n=self.n,
                             precision=self.precision, categories=categories)
        return luts, vals, cand

    @staticmethod
    def _filters(num: int, categories, n_categories: int, options):
        """Per query: the category its filter keeps (-1: none)."""
        for opts in options or ():
            unknown = set(opts) - ANSWER_NEUTRAL
            if unknown:
                raise ValueError(f"the flat UNQ reference cannot judge "
                                 f"requests with {sorted(unknown)}")
        if categories is None or not n_categories:
            return np.full(num, -1, np.int32), 0
        return np.asarray(categories, np.int32), int(n_categories)

    def search(self, queries, k: int, categories=None, n_categories=0):
        """The reference's own top-k (distances, ids) of each query: the
        control of the comparison when run below float32."""
        cats, ncat = self._filters(queries.shape[0], categories,
                                   n_categories, None)
        out_d, out_i = [], []
        for lo in range(0, queries.shape[0], CHECK_BLOCK):
            q = jnp.asarray(queries[lo:lo + CHECK_BLOCK])
            _, _, cand = self._pool(q, cats[lo:lo + CHECK_BLOCK], ncat)
            d1 = _d1(self.w, q, jnp.take(self.codes, cand, axis=0),
                     precision=self.precision)
            neg, pos = jax.lax.top_k(-d1, k)
            out_d.append(np.asarray(-neg))
            out_i.append(np.asarray(jnp.take_along_axis(cand, pos, axis=1)))
        return np.concatenate(out_d), np.concatenate(out_i)

    def judge(self, queries, served_d, served_i, categories=None,
              n_categories=0, options=None):
        """Per query ``gap`` (d1_gap) and ``wrong`` (wrong_ids) of a
        served answer. ``categories`` (per query, -1 for none) and
        ``n_categories`` give the filter each query was served under;
        ``options`` (per query, the request's further arguments) may hold
        only arguments that leave the exact answer as it is.

        A served id is wrong when it is no id, repeats in its row, lies
        outside the query's filter, or is
        not among the reference's top-L by stage-1 score (beyond the
        STAGE1_BAND of the L-th score); a reference candidate whose
        stage-1 score lies below that band is wrong to have missed when
        its distance is under the k-th served one (beyond D1_BAND of it);
        an out-of-order row counts once. ``d1_gap`` is the largest
        |served distance - reference distance| / reference distance over
        the row's right ids."""
        cats, ncat = self._filters(queries.shape[0], categories,
                                   n_categories, options)
        gaps, wrong = [], []
        for lo in range(0, queries.shape[0], CHECK_BLOCK):
            q = jnp.asarray(queries[lo:lo + CHECK_BLOCK])
            sd = np.asarray(served_d[lo:lo + CHECK_BLOCK], np.float32)
            si = np.asarray(served_i[lo:lo + CHECK_BLOCK])
            cat = cats[lo:lo + CHECK_BLOCK]
            luts, vals, cand = self._pool(q, cat, ncat)
            ok = (si >= 0) & (si < self.n)
            if ncat:
                ok &= (cat[:, None] < 0) | (category_of(
                    np.where(ok, si, 0), ncat) == cat[:, None])
            si_safe = jnp.asarray(np.where(ok, si, 0), jnp.int32)
            band = STAGE1_BAND * jnp.sum(jnp.max(jnp.abs(luts), axis=2),
                                         axis=1)
            t = vals[:, -1]
            legit = ok & np.asarray(
                _scores_of(self.codes, luts, si_safe)
                <= (t + band)[:, None])
            d1_s = np.asarray(_d1(self.w, q, jnp.take(self.codes, si_safe,
                                                      axis=0),
                                  precision=self.precision))
            d1_c = np.asarray(_d1(self.w, q, jnp.take(self.codes, cand,
                                                      axis=0),
                                  precision=self.precision))
            sure = np.asarray(vals < (t - band)[:, None])
            cand_np = np.asarray(cand)
            served = (cand_np[:, :, None] == si[:, None, :]).any(axis=2)
            kth = sd[:, -1:]
            missed = sure & ~served & (d1_c < kth * (1 - D1_BAND))
            srt = np.sort(si, axis=1)
            dup = np.sum(srt[:, 1:] == srt[:, :-1], axis=1)
            unsorted = np.any(np.diff(sd, axis=1) < 0, axis=1)
            wrong.append(np.sum(~legit, axis=1) + np.sum(missed, axis=1)
                         + dup + unsorted)
            rel = np.abs(sd.astype(np.float64) - d1_s) / np.maximum(
                d1_s, np.finfo(np.float32).tiny)
            gaps.append(np.max(np.where(legit, rel, 0.0), axis=1))
        return {"gap": np.concatenate(gaps), "wrong": np.concatenate(wrong)}
