"""Stage-2 engine benchmark: streaming rerank (fused table kernel /
chunked xla / cross-query dedup) vs the materialized vmap baseline —
throughput AND peak-memory trajectory at the acceptance shape
Q=32, L=500.

Writes ``BENCH_stage2.json`` (repo root by default) with, per path:

  * ``us_per_call`` — one full d1 rerank of the (Q, L) candidate pool,
  * ``interpret`` — True when the Pallas kernel ran in interpret mode
    (off-TPU): a correctness datapoint excluded from the ``headline``,
  * ``peak_recon_bytes`` — the analytic reconstruction footprint
    (Q*L*D*4 for the vmap baseline, chunk-bounded for streaming paths),
  * ``temp_bytes`` — the compiler's measured temp allocation for the
    jitted rerank fn (None when unavailable or multi-jit),
  * section ``dedup`` additionally records ``unique_ratio`` — how many
    decoder calls cross-query dedup saved on the overlapping pool,
  * ``tuner_bucket`` — the autotuner shape bucket the row's block params
    resolved in (compare longitudinal rows only within one bucket).

Two sections mirror the two engine families:

  * ``table``   — PQ-shaped additive decode table (M=8, K=256, D=96):
                  vmap vs chunked xla (tuner-resolved AND
                  ``chunked/xla[default]`` with the tuner disabled — the
                  ``tuned_vs_default`` block compares them) vs fused
                  Pallas.
  * ``decoder`` — UNQ's MLP decoder on a hot-set candidate pool
                  (pools overlap across queries as they do after a real
                  stage 1): vmap vs cross-query dedup.

A third section, ``gathered_quantized``, benches the gathered candidate
scan (``adc_gather_topl`` — the kernel that scores stage-2-shaped
per-query slot lists) f32 vs fp16 vs int8 LUTs at ``overfetch=2``,
recording recall@L of each quantized row against the exact f32 ids.

Run via ``python -m benchmarks.run --only stage2`` (ci.sh records the
json on every PR alongside the stage-1 trajectory).
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.index.base import DECODE_CHUNK
from repro.kernels import ops, ref, tune
from repro.kernels.rerank_dist import rerank_gather_dist_chunked_xla

_SIZES = {"quick": (60_000, 32, 500), "default": (200_000, 32, 500),
          "full": (1_000_000, 32, 500)}
_M, _K, _D = 8, 256, 96
_HOT_FRACTION = 8          # decoder pool drawn from a hot set of Q*L/8 ids
_OVERFETCH = 2


def _temp_bytes(fn, *avals):
    try:
        compiled = jax.jit(fn).lower(*avals).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)
    except Exception:
        return None


def _bench_table(results, codes, queries, cand):
    q, topl = cand.shape
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(_M, _K, _D)), jnp.float32)
    cand_codes = jnp.take(codes, cand, axis=0)

    bucket = tune.bucket_key(tune.KERNELS["rerank_gather_dist.xla"],
                             {"l": topl, "q": q, "d": _D})
    # the chunk the tuner resolves for this shape (winner or default)
    chunk_l = tune.best_config("rerank_gather_dist", "xla",
                               l=topl, q=q, d=_D)["chunk_l"]
    default_l = tune.KERNELS["rerank_gather_dist.xla"].params["chunk_l"]

    vmap_fn = jax.jit(jax.vmap(
        lambda qr, ci: ref.sq_dist(
            ref.decode_with_table(codes[ci], table), qr[None, :]),
        in_axes=(0, 0)))
    interp = ops._interpret()

    def chunked_xla(**kw):
        return ops.rerank_gather_dist(cand_codes, queries, table,
                                      impl="xla", **kw)
    paths = {
        "vmap/xla": (lambda: vmap_fn(queries, cand),
                     q * topl * _D * 4, False),
        "chunked/xla": (chunked_xla, q * chunk_l * _D * 4, False),
        # same rerank, tuner disabled: the hand-pinned baseline
        "chunked/xla[default]": (
            common.with_defaults(chunked_xla),
            q * default_l * _D * 4, False),
        # interpret mode off-TPU: correctness path, not a perf claim
        "fused/pallas": (
            lambda: ops.rerank_gather_dist(cand_codes, queries, table,
                                           impl="pallas"),
            ops.DEFAULT_RERANK_BLOCK_Q * ops.DEFAULT_RERANK_BLOCK_L * _D * 4,
            interp),
    }
    temp = {
        "vmap/xla": _temp_bytes(
            vmap_fn,
            jax.ShapeDtypeStruct(queries.shape, jnp.float32),
            jax.ShapeDtypeStruct(cand.shape, jnp.int32)),
        "chunked/xla": _temp_bytes(
            lambda c, qs, t: rerank_gather_dist_chunked_xla(
                c, qs, t, chunk_l=chunk_l),
            jax.ShapeDtypeStruct(cand_codes.shape, jnp.uint8),
            jax.ShapeDtypeStruct(queries.shape, jnp.float32),
            jax.ShapeDtypeStruct(table.shape, jnp.float32)),
    }
    # the interpret-mode row is not a comparison row and its ~50ms body
    # would both slow the rotation and trash caches mid-round: time it
    # alone, and give the three comparison rows a longer rotation
    timed = common.timed_group(
        {name: fn for name, (fn, *_r) in paths.items()
         if name != "fused/pallas"}, repeats=10)
    timed["fused/pallas"] = (None, common.timed(paths["fused/pallas"][0])[1])
    for name, (fn, recon_bytes, interpret) in paths.items():
        _, us = timed[name]
        results["table"][name] = {
            "us_per_call": round(us, 1), "interpret": bool(interpret),
            "peak_recon_bytes": recon_bytes,
            "temp_bytes": temp.get(name),
            "tuner_bucket": bucket}
        common.emit(f"stage2/table/{name}", us,
                    f"recon-mem={recon_bytes / 1e6:.2f}MB"
                    + (" [interpret]" if interpret else ""))
    results["tuned_vs_default"] = {
        "path": "table/chunked/xla", "tuner_bucket": bucket,
        # when the sweep kept the default at this bucket both rows run the
        # SAME config and |speedup - 1| is pure timing noise
        "identical_config": chunk_l == default_l,
        "tuned_us": results["table"]["chunked/xla"]["us_per_call"],
        "default_us": results["table"]["chunked/xla[default]"]
        ["us_per_call"],
        "speedup": round(
            results["table"]["chunked/xla[default]"]["us_per_call"]
            / results["table"]["chunked/xla"]["us_per_call"], 3)}


def _bench_decoder(results, n, queries, cand):
    from repro.core import unq
    from repro.index import DedupRerank, UNQIndex, VmapRerank

    q, topl = cand.shape
    rng = np.random.default_rng(2)
    cfg = unq.UNQConfig(dim=_D, num_codebooks=_M, codebook_size=_K)
    params, state = unq.init(jax.random.PRNGKey(0), cfg)
    index = UNQIndex.from_trained(params, state, cfg, rerank=topl)
    index._codes = jnp.asarray(rng.integers(0, _K, (n, _M)), jnp.uint8)

    n_unique = int(np.unique(np.asarray(cand)).size)
    vm, dd = VmapRerank(), DedupRerank()
    u_pad = -(-n_unique // DECODE_CHUNK) * DECODE_CHUNK
    paths = {
        "vmap/decoder": (lambda: vm.distances(index, queries, cand),
                         q * topl * _D * 4),
        # held deduped (U, D) reconstruction + gathered distance tiles
        "dedup/decoder": (
            lambda: dd.distances(index, queries, cand),
            (u_pad + q * dd.dist_chunk) * _D * 4),
    }
    for name, (fn, recon_bytes) in paths.items():
        _, us = common.timed(fn, repeats=3)
        results["decoder"][name] = {
            "us_per_call": round(us, 1), "interpret": False,
            "peak_recon_bytes": recon_bytes, "temp_bytes": None}
        common.emit(f"stage2/decoder/{name}", us,
                    f"recon-mem={recon_bytes / 1e6:.2f}MB")
    results["decoder"]["dedup/decoder"]["unique_ratio"] = round(
        q * topl / max(n_unique, 1), 2)


def _bench_gathered_quantized(results, codes, n, q, topl):
    """f32 vs fp16 vs int8 LUTs over the gathered candidate scan at the
    stage-2 pool shape: (Q, W=topl) unique ascending slot lists, scan
    top-L = topl // 5, quantized rows over-fetched and exactly
    re-scored (recall@L measured against the exact f32 ids)."""
    rng = np.random.default_rng(3)
    luts = jnp.asarray(rng.normal(size=(q, _M, _K)), jnp.float32)
    gids_np = np.stack([np.sort(rng.choice(n, size=topl, replace=False))
                        for _ in range(q)]).astype(np.int32)
    gids = jnp.asarray(gids_np)
    rows = gids                    # flat world: row index == global id
    topl_s = max(topl // 5, 1)

    def gather(**kw):
        return ops.adc_gather_topl(codes, rows, gids, luts, topl=topl_s,
                                   impl="xla", **kw)

    exact_ids = np.asarray(gather()[1])
    spec = tune.KERNELS["adc_gather_topl.xla"]
    pool = min(topl_s * _OVERFETCH, topl)
    pool_bucket = tune.bucket_key(spec, {"w": topl, "q": q, "topl": pool})
    rows_cfg = {
        "f32": (gather, tune.bucket_key(
            spec, {"w": topl, "q": q, "topl": topl_s})),
        "f16": (lambda: gather(lut_dtype="float16", overfetch=_OVERFETCH),
                pool_bucket),
        "i8": (lambda: gather(lut_dtype="int8", overfetch=_OVERFETCH),
               pool_bucket),
        # matched-pipeline control: the f32 BRIDGE path (same L' pool,
        # re-score, exact select) — only the table dtype differs from
        # the quantized rows
        "f32@pool": (
            lambda: gather(lut_dtype="float32", overfetch=_OVERFETCH),
            pool_bucket),
    }
    timed = common.timed_group(
        {name: fn for name, (fn, _b) in rows_cfg.items()}, repeats=10)
    f32_us = matched_us = None
    for name, (fn, bucket) in rows_cfg.items():
        out, us = timed[name]
        row = {"us_per_call": round(us, 1), "interpret": False,
               "tuner_bucket": bucket}
        extra = ""
        if name == "f32":
            f32_us = us
        elif name == "f32@pool":
            matched_us = us
        else:
            got = np.asarray(out[1])
            hits = sum(np.intersect1d(g, e).size
                       for g, e in zip(got, exact_ids))
            row["overfetch"] = _OVERFETCH
            row["recall@L"] = round(hits / exact_ids.size, 5)
            row["speedup_vs_f32"] = round(f32_us / us, 3)
            extra = f" R@L={row['recall@L']:.4f} overfetch={_OVERFETCH}"
        results["gathered_quantized"][name] = row
        common.emit(f"stage2/gathered/{name}", us,
                    f"topl={topl_s} W={topl}" + extra)
    for name in ("f16", "i8"):
        results["gathered_quantized"][name]["speedup_vs_f32_matched"] = \
            round(matched_us
                  / results["gathered_quantized"][name]["us_per_call"], 3)


def run(scale: str = "quick", out_path: str | None = None) -> dict:
    n, q, topl = _SIZES.get(scale, _SIZES["quick"])
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, _K, (n, _M)), jnp.uint8)
    queries = jnp.asarray(rng.normal(size=(q, _D)), jnp.float32)
    # hot-set pool: stage-1 candidates overlap heavily across queries
    hot = rng.integers(0, n, max(q * topl // _HOT_FRACTION, 1))
    cand = jnp.asarray(hot[rng.integers(0, hot.size, (q, topl))], jnp.int32)

    results = {"n": n, "q": q, "topl": topl, "dim": _D,
               "backend": jax.default_backend(),
               "tuning": tune.cache_fingerprint(),
               "table": {}, "decoder": {}, "gathered_quantized": {}}
    _bench_table(results, codes, queries, cand)
    _bench_decoder(results, n, queries, cand)
    _bench_gathered_quantized(results, codes, n, q, topl)

    headline = {f"{sec}/{name}": p["us_per_call"]
                for sec in ("table", "decoder")
                for name, p in results[sec].items()
                if not p["interpret"] and "[" not in name}
    results["headline"] = {
        "us_per_call": headline,
        "best_table": min((k for k in headline if k.startswith("table/")),
                          key=headline.get),
        "best_decoder": min((k for k in headline if k.startswith("decoder/")),
                            key=headline.get)}

    if out_path is None:
        out_path = pathlib.Path(__file__).resolve().parent.parent \
            / "BENCH_stage2.json"
    pathlib.Path(out_path).write_text(json.dumps(results, indent=2) + "\n")
    print(f"# stage2: wrote {out_path}")
    return results


if __name__ == "__main__":
    run()
