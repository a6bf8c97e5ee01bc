"""Sweep driver for the kernel block-config autotuner.

``repro.kernels.tune`` owns the registry (tunable kernels, parameter
ladders, shape buckets) and the persistent winner cache that
``ops.py`` resolves every block parameter through. This package is the
part that actually RUNS: for each registered (kernel, impl) and each
shape bucket it builds representative device-resident inputs once,
times the hand-pinned default and every candidate ladder point
(min-of-repeats wall time around a ``block_until_ready`` boundary),
and records the winner.

Sweep discipline (what makes cached winners trustworthy):

  * the DEFAULT config is always timed first and is the initial
    incumbent, so a recorded winner is never slower than the
    hand-pinned fallback beyond timing noise;
  * a challenger must beat the incumbent by ``tune.HYSTERESIS`` to
    replace it — re-sweeping on the same machine reproduces the same
    winners (the determinism assertion ``--quick`` enforces);
  * an existing cache entry is re-timed as the incumbent before the
    grid, so re-sweeps refine rather than thrash;
  * Pallas impls are skipped when the kernels would run in interpret
    mode (off-TPU default): interpret wall time says nothing about the
    compiled kernel, and a winner measured there would poison the
    cache for the real device.

``python -m repro.tune`` is the CLI (see ``__main__``): ``--quick``
sweeps one bucket per kernel and is the ci.sh smoke, the default mode
sweeps the full bucket ladder, ``--validate`` checks an existing cache
against the schema.
"""
from __future__ import annotations

import itertools
import math
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, tune

_M, _K = 8, 256           # codebook geometry shared by every builder
_D = 64                   # rerank reconstruction dim
_TOPL = 128               # dispatch sweeps (no topl dim in its bucket key)
_SEED = 0

#: one bucket per kernel — the ci.sh smoke ladder, aligned with the
#: quick-scale bench shapes so bench rows exercise the swept bucket
QUICK_BUCKETS = {
    "adc_scan_topl.pallas": ({"n": 65536, "q": 32, "topl": 128},),
    "adc_scan_topl.xla": ({"n": 65536, "q": 32, "topl": 128},),
    "adc_gather_topl.pallas": ({"w": 8192, "q": 32, "topl": 128},),
    "adc_gather_topl.xla": ({"w": 8192, "q": 32, "topl": 128},),
    "adc_dispatch_topl": ({"n": 65536, "q": 32},),
    "rerank_gather_dist.pallas": ({"l": 1024, "q": 32, "d": _D},),
    "rerank_gather_dist.xla": ({"l": 1024, "q": 32, "d": _D},),
}

#: the full ladder: quick's buckets plus one size step up per kernel
FULL_BUCKETS = {
    key: buckets + extra for key, buckets, extra in (
        ("adc_scan_topl.pallas", QUICK_BUCKETS["adc_scan_topl.pallas"],
         ({"n": 262144, "q": 32, "topl": 128},)),
        ("adc_scan_topl.xla", QUICK_BUCKETS["adc_scan_topl.xla"],
         ({"n": 262144, "q": 32, "topl": 128},)),
        ("adc_gather_topl.pallas", QUICK_BUCKETS["adc_gather_topl.pallas"],
         ({"w": 32768, "q": 32, "topl": 128},)),
        ("adc_gather_topl.xla", QUICK_BUCKETS["adc_gather_topl.xla"],
         ({"w": 32768, "q": 32, "topl": 128},)),
        ("adc_dispatch_topl", QUICK_BUCKETS["adc_dispatch_topl"],
         ({"n": 262144, "q": 32},)),
        ("rerank_gather_dist.pallas",
         QUICK_BUCKETS["rerank_gather_dist.pallas"],
         ({"l": 4096, "q": 32, "d": _D},)),
        ("rerank_gather_dist.xla", QUICK_BUCKETS["rerank_gather_dist.xla"],
         ({"l": 4096, "q": 32, "d": _D},)),
    )
}


# ---------------------------------------------------------------------------
# per-kernel input builders + runner factories
# ---------------------------------------------------------------------------

def _build_scan(dims):
    rng = np.random.default_rng(_SEED)
    n, q = dims["n"], dims["q"]
    return {
        "codes": jnp.asarray(rng.integers(0, _K, (n, _M), dtype=np.uint8)),
        "luts": jnp.asarray(
            rng.standard_normal((q, _M, _K), dtype=np.float32)),
        "bias": jnp.asarray(rng.standard_normal((n,), dtype=np.float32)),
    }


def _make_scan(impl):
    def make(inputs, dims, config):
        def fn():
            jax.block_until_ready(ops.adc_scan_topl(
                inputs["codes"], inputs["luts"], topl=dims["topl"],
                bias=inputs["bias"], impl=impl,
                block_n=config.get("block_n"),
                block_q=config.get("block_q"),
                chunk_n=config.get("chunk_n")))
        return fn
    return make


def _build_gather(dims):
    rng = np.random.default_rng(_SEED)
    w, q = dims["w"], dims["q"]
    nbuf = 2 * w
    return {
        "codes": jnp.asarray(rng.integers(0, _K, (nbuf, _M),
                                          dtype=np.uint8)),
        "rows": jnp.asarray(rng.integers(0, nbuf, (q, w), dtype=np.int32)),
        # ascending within each row — the gathered-path plan contract
        "gids": jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32), (q, w)),
        "luts": jnp.asarray(
            rng.standard_normal((q, _M, _K), dtype=np.float32)),
    }


def _make_gather(impl):
    def make(inputs, dims, config):
        def fn():
            jax.block_until_ready(ops.adc_gather_topl(
                inputs["codes"], inputs["rows"], inputs["gids"],
                inputs["luts"], topl=dims["topl"], impl=impl,
                block_w=config.get("block_w"),
                block_q=config.get("block_q"),
                chunk_w=config.get("chunk_w")))
        return fn
    return make


def _build_dispatch(dims):
    rng = np.random.default_rng(_SEED)
    n, q = dims["n"], dims["q"]
    nlist, nprobe = 64, 8
    assert n % nlist == 0
    offsets = np.arange(nlist + 1, dtype=np.int32) * (n // nlist)
    probe = np.sort(np.stack([
        rng.choice(nlist, size=nprobe, replace=False)
        for _ in range(q)]).astype(np.int32), axis=1)
    return {
        "codes": jnp.asarray(rng.integers(0, _K, (n, _M), dtype=np.uint8)),
        "gids_rows": jnp.arange(n, dtype=jnp.int32),
        "luts": jnp.asarray(
            rng.standard_normal((q, _M, _K), dtype=np.float32)),
        "probe": probe,
        "offsets": offsets,
    }


def _make_dispatch(impl):
    def make(inputs, dims, config):
        # the plan bakes the tile width in, so routing is rebuilt per
        # candidate — host-side, outside the timed region
        from repro.index.dispatch import build_dispatch
        routing, _ = build_dispatch(inputs["probe"], inputs["offsets"],
                                    chunk=config["chunk"])
        cellterm = jnp.zeros(routing.plan.qidx.shape, jnp.float32)

        def fn():
            jax.block_until_ready(ops.adc_dispatch_topl(
                inputs["codes"], inputs["gids_rows"], None, inputs["luts"],
                cellterm, routing.plan, topl=_TOPL, impl=impl,
                chunk=routing.chunk))
        return fn
    return make


def _build_rerank(dims):
    rng = np.random.default_rng(_SEED)
    l, q, d = dims["l"], dims["q"], dims["d"]
    return {
        "cand_codes": jnp.asarray(
            rng.integers(0, _K, (q, l, _M), dtype=np.int32)),
        "queries": jnp.asarray(
            rng.standard_normal((q, d), dtype=np.float32)),
        "table": jnp.asarray(
            rng.standard_normal((_M, _K, d), dtype=np.float32)),
    }


def _make_rerank(impl):
    def make(inputs, dims, config):
        def fn():
            jax.block_until_ready(ops.rerank_gather_dist(
                inputs["cand_codes"], inputs["queries"], inputs["table"],
                impl=impl,
                block_l=config.get("block_l"),
                block_q=config.get("block_q"),
                chunk_l=config.get("chunk_l")))
        return fn
    return make


def _dispatch_impl() -> str:
    """The impl the dispatch sweep times: the compiled Pallas kernel on
    TPU, the xla stream everywhere interpret mode would apply."""
    return "pallas" if ops._on_tpu() else "xla"


#: registry key -> (input builder, runner factory); the runner factory
#: returns ``make(inputs, dims, config) -> zero-arg timed callable``
RUNNERS = {
    "adc_scan_topl.pallas": (_build_scan, _make_scan("pallas")),
    "adc_scan_topl.xla": (_build_scan, _make_scan("xla")),
    "adc_gather_topl.pallas": (_build_gather, _make_gather("pallas")),
    "adc_gather_topl.xla": (_build_gather, _make_gather("xla")),
    "adc_dispatch_topl": (_build_dispatch, None),   # impl picked at run time
    "rerank_gather_dist.pallas": (_build_rerank, _make_rerank("pallas")),
    "rerank_gather_dist.xla": (_build_rerank, _make_rerank("xla")),
}


# ---------------------------------------------------------------------------
# timing + the sweep proper
# ---------------------------------------------------------------------------

def _time_round_robin(fns: list, repeats: int) -> list[float]:
    """Interleaved min-of-rounds wall times in microseconds: one untimed
    call per fn absorbs compilation, then ``repeats`` rounds visit every
    fn, SHUFFLED each round under a fixed seed. Interleaving is what
    makes winners reproducible on the same machine — ambient drift (CPU
    frequency, cache pressure, VM steal) hits all candidates equally
    instead of biasing whichever one happened to run during a quiet
    window. The shuffle matters too: a fixed cyclic order gives every
    candidate a FIXED predecessor (warm or cold caches), and inserting
    the cached incumbent into the list — as a re-sweep does — would
    shift every candidate's predecessor, enough to flip near-tied
    configs between a sweep and its determinism re-check."""
    for fn in fns:
        fn()
    best = [math.inf] * len(fns)
    order = list(range(len(fns)))
    shuffle = random.Random(0x5eed).shuffle
    for _ in range(max(repeats, 1)):
        shuffle(order)
        for i in order:
            t0 = time.perf_counter()
            fns[i]()
            best[i] = min(best[i], time.perf_counter() - t0)
    return [b * 1e6 for b in best]


def _skip(key: str) -> str | None:
    """Reason this registry key cannot be meaningfully swept here."""
    if key.endswith(".pallas") and ops._interpret():
        return "pallas interpret mode — compiled timings unavailable"
    return None


def sweep_bucket(key: str, dims: dict, *, repeats: int,
                 incumbent: dict | None = None, log=print) -> dict:
    """Sweep one (kernel, shape bucket): returns the cache entry
    ``{"config", "us", "default_us"}`` with the winner config covering
    every registered parameter.

    All configs (default, cached incumbent, ladder candidates) are timed
    round-robin in ONE interleaved pass; the incumbent then only needs to
    be merely fastest to stay (it already cleared the hysteresis bar when
    first cached), while a challenger must beat the incumbent (or, fresh,
    the default) by the ``tune.HYSTERESIS`` margin to replace it. The bar
    is fixed at the baseline — among challengers that clear it the plain
    argmin wins, so the candidate ladder's ORDER never decides: a bar
    re-anchored at each successive winner would make a config sitting
    right at ``HYSTERESIS x`` its neighbor a fresh-sweep coin flip that
    the determinism self-check then catches as an incumbent flip."""
    spec = tune.KERNELS[key]
    build, make = RUNNERS[key]
    if make is None:
        make = _make_dispatch(_dispatch_impl())
    inputs = build(dims)

    default_cfg = dict(spec.params)
    incumbent_cfg = {**default_cfg, **incumbent} if incumbent else None
    if incumbent_cfg == default_cfg:
        incumbent_cfg = None
    configs = [default_cfg] + ([incumbent_cfg] if incumbent_cfg else [])
    names = sorted(spec.candidates)
    for values in itertools.product(*(spec.candidates[n] for n in names)):
        cfg = {**default_cfg, **dict(zip(names, values))}
        if cfg not in configs:
            configs.append(cfg)

    times = _time_round_robin(
        [make(inputs, dims, cfg) for cfg in configs], repeats)
    default_us = times[0]
    best_cfg, best_us = default_cfg, default_us
    log(f"    default {default_cfg} -> {default_us:.1f}us")
    if incumbent_cfg:
        us = times[1]
        log(f"    cached  {incumbent_cfg} -> {us:.1f}us")
        if us < best_us:
            best_cfg, best_us = incumbent_cfg, us
    bar = best_us * tune.HYSTERESIS
    for cfg, us in zip(configs, times):
        if cfg in (default_cfg, incumbent_cfg):
            continue
        if us < bar and us < best_us:
            log(f"    winner  {cfg} -> {us:.1f}us")
            best_cfg, best_us = cfg, us
    return {"config": best_cfg, "us": round(best_us, 1),
            "default_us": round(default_us, 1)}


def run_sweep(buckets: dict, *, repeats: int, doc: dict | None = None,
              log=print) -> dict:
    """Sweep every (key, bucket) in ``buckets`` and fold the winners into
    a cache document (existing entries become incumbents). Returns the
    updated document; the caller saves it."""
    if doc is None:
        doc = {"schema_version": tune.SCHEMA_VERSION, "entries": {}}
    dk = tune.device_kind()
    mine = doc.setdefault("entries", {}).setdefault(dk, {})
    for key, bucket_list in buckets.items():
        reason = _skip(key)
        if reason:
            log(f"  SKIP {key}: {reason}")
            continue
        spec = tune.KERNELS[key]
        if not spec.candidates:
            log(f"  SKIP {key}: defaults-only registration (no ladder)")
            continue
        for dims in bucket_list:
            bkey = tune.bucket_key(spec, dims)
            log(f"  {key} [{bkey}]")
            cached = mine.get(key, {}).get(bkey)
            entry = sweep_bucket(
                key, dims, repeats=repeats,
                incumbent=cached["config"] if cached else None, log=log)
            mine.setdefault(key, {})[bkey] = entry
    tune.validate(doc)
    return doc
