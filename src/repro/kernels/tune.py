"""Block-config autotuner registry + persistent winner cache.

Every block/chunk parameter a kernel wrapper in ``ops.py`` accepts was
historically a hand-pinned constant, tuned once on one CPU and wrong
everywhere else. This module makes that tuning durable:

  * ``KERNELS`` — the registry of tunable kernels: per (kernel, impl)
    the block parameters, their hand-pinned defaults (the zero-cache
    fallback), the shape dimensions that key a tuning bucket, and the
    candidate ladders the sweep driver (``repro.tune``) explores;
  * ``best_config(kernel, impl, **dims)`` — the lookup ``ops.py``
    resolves EVERY block parameter through: pow2-bucket the shape dims,
    consult the versioned JSON cache for this device kind, fall back to
    the registered defaults when no winner is cached (or tuning is
    disabled via ``REPRO_TUNE_DISABLE=1``);
  * ``align`` / ``clamp_chunk`` — the ONE home of the block-rounding
    heuristics that used to be copy-pasted ad hoc across ``ops.py``;
  * cache I/O with schema validation: ``load_cache`` raises
    ``TuneCacheError`` on any drift (wrong version, unknown kernel,
    unknown parameter, non-integer config), so a stale cache fails
    loudly instead of silently mis-tuning.

Cache document shape (``TUNE_CACHE.json`` at the repo root, or the path
in ``REPRO_TUNE_CACHE``)::

    {"schema_version": 1,
     "entries": {"<device kind>": {"<kernel>.<impl>": {
         "n=65536,q=32,topl=128": {
             "config": {"chunk_n": 8192},
             "us": 101.2, "default_us": 130.4}}}}}

Winners are keyed by (device kind, kernel.impl, shape bucket); a bucket
key is the pow2 ceiling of each registered dim, so any runtime shape
resolves to the bucket the sweep actually timed. The sweep driver only
ever REPLACES the default when a candidate beats the incumbent by a
hysteresis margin, so tuner-resolved configs are never slower than the
hand-pinned defaults (up to timing noise below the margin).

This module is import-light on purpose (no ``ops`` import): ``ops.py``
imports it, the sweep driver imports both.
"""
from __future__ import annotations

import json
import os
import pathlib
import threading
from typing import NamedTuple

from repro.kernels.adc_scan import DEFAULT_BLOCK_N, DEFAULT_BLOCK_Q
from repro.kernels.dispatch_topl import DEFAULT_DISPATCH_CHUNK
from repro.kernels.gather_topl import (DEFAULT_CHUNK_W,
                                       DEFAULT_GATHER_BLOCK_Q,
                                       DEFAULT_GATHER_BLOCK_W)
from repro.kernels.rerank_dist import (DEFAULT_RERANK_BLOCK_L,
                                       DEFAULT_RERANK_BLOCK_Q,
                                       DEFAULT_RERANK_CHUNK_L)
from repro.kernels.topl_scan import (DEFAULT_CHUNK_N, DEFAULT_TOPL_BLOCK_N,
                                     DEFAULT_TOPL_BLOCK_Q)
from repro.kernels.unq_encode import DEFAULT_BLOCK_B

SCHEMA_VERSION = 1
CACHE_ENV = "REPRO_TUNE_CACHE"
DISABLE_ENV = "REPRO_TUNE_DISABLE"


class TuneCacheError(ValueError):
    """The tune cache on disk does not match this build's schema."""


class KernelSpec(NamedTuple):
    """One tunable (kernel, impl) entry: parameter defaults (the
    zero-cache fallback), the shape dims that key a bucket, and the
    candidate ladder per parameter (empty = registered for resolution
    but not swept)."""
    params: dict
    dims: tuple
    candidates: dict


#: every (kernel, impl) whose block parameters ``ops.py`` resolves.
#: The four engine kernels carry sweep ladders; the auxiliary kernels
#: are registered defaults-only so EVERY block parameter still resolves
#: through ``best_config`` (and picks up cached winners if a future
#: sweep adds ladders).
KERNELS = {
    # block_q is a cap: ``ops`` shrinks it to the padded batch, so by
    # default a served bucket (8 to 128 queries) scores in ONE query
    # block and every code block's one-hot is built and pushed into the
    # MXU once for the whole bucket; the merge folds the block in
    # slabs of at most 16 rows whatever block_q is (``topl_scan``)
    "adc_scan_topl.pallas": KernelSpec(
        {"block_n": DEFAULT_TOPL_BLOCK_N, "block_q": DEFAULT_TOPL_BLOCK_Q},
        ("n", "q", "topl"),
        {"block_n": (256, 512, 1024, 2048, 4096),
         "block_q": (8, 16, 32, 64, 128)}),
    "adc_scan_topl.xla": KernelSpec(
        {"chunk_n": DEFAULT_CHUNK_N},
        ("n", "q", "topl"),
        {"chunk_n": (1024, 2048, 4096, 8192, 16384)}),
    "adc_gather_topl.pallas": KernelSpec(
        {"block_w": DEFAULT_GATHER_BLOCK_W,
         "block_q": DEFAULT_GATHER_BLOCK_Q},
        ("w", "q", "topl"),
        {"block_w": (128, 256, 512, 1024, 2048), "block_q": (8, 16)}),
    "adc_gather_topl.xla": KernelSpec(
        {"chunk_w": DEFAULT_CHUNK_W},
        ("w", "q", "topl"),
        {"chunk_w": (512, 1024, 2048, 4096, 8192)}),
    # one shared entry for both impls: the chunk is baked into the tile
    # plan by the router (index/dispatch.build_dispatch), so the router
    # and the kernel MUST resolve the same value — a single registry key
    # guarantees it. Chunks are whole 128-lane tiles: the kernel streams
    # (1, chunk) row blocks, which Mosaic tiles in 128-lane units
    "adc_dispatch_topl": KernelSpec(
        {"chunk": DEFAULT_DISPATCH_CHUNK},
        ("n", "q"),
        {"chunk": (128, 256, 512, 1024)}),
    "rerank_gather_dist.pallas": KernelSpec(
        {"block_l": DEFAULT_RERANK_BLOCK_L,
         "block_q": DEFAULT_RERANK_BLOCK_Q},
        ("l", "q", "d"),
        {"block_l": (64, 128, 256, 512), "block_q": (8, 16)}),
    "rerank_gather_dist.xla": KernelSpec(
        {"chunk_l": DEFAULT_RERANK_CHUNK_L},
        ("l", "q", "d"),
        {"chunk_l": (32, 64, 128, 256, 512)}),
    # auxiliary kernels: defaults-only registration (no sweep ladder yet)
    "adc_scan.pallas": KernelSpec(
        {"block_n": DEFAULT_BLOCK_N}, ("n",), {}),
    "adc_scan_batch.pallas": KernelSpec(
        {"block_n": DEFAULT_BLOCK_N, "block_q": DEFAULT_BLOCK_Q},
        ("n", "q"), {}),
    "unq_encode.pallas": KernelSpec(
        {"block_b": DEFAULT_BLOCK_B}, ("b",), {}),
}

#: the hysteresis margin the sweep applies: a challenger must beat the
#: running best by this factor to replace it — keeps winners stable
#: against timing noise (same machine -> same winners) and guarantees a
#: cached winner is never slower than the default beyond noise. 0.8 is
#: deliberately wide: within-pass interleaved timing noise is a few
#: percent, but candidates hovering a few percent past a narrow bar
#: flip-flop between sweeps, and a durable cache values reproducible
#: winners over the last ~10% of a marginal one.
HYSTERESIS = 0.8


# ---------------------------------------------------------------------------
# shape buckets + the shared rounding helpers (satellite: ONE home for
# the ad-hoc ``min(block, max(8, ceil...))`` heuristics ops.py carried)
# ---------------------------------------------------------------------------

def shape_bucket(value: int, floor: int = 8) -> int:
    """Pow2 ceiling of a shape dim (ENCODE_BUCKETS-style ladder)."""
    b = floor
    while b < value:
        b *= 2
    return b


def bucket_key(spec: KernelSpec, dims: dict) -> str:
    """Canonical cache key for a shape: ``"n=65536,q=32,topl=128"``."""
    missing = [d for d in spec.dims if d not in dims]
    if missing:
        raise KeyError(f"missing bucket dims {missing} (have {list(dims)})")
    return ",".join(f"{d}={shape_bucket(int(dims[d]))}" for d in spec.dims)


def align(dim: int, *, cap: int, multiple: int = 8) -> int:
    """Shrink a block request to a small dim: ``dim`` rounded up to the
    tile ``multiple`` (floor ``multiple``), capped by the requested
    block. The former ``min(block, max(8, -(-d // 8) * 8))`` pattern."""
    return min(cap, max(multiple, -(-dim // multiple) * multiple))


def clamp_chunk(dim: int, *, cap: int, floor: int) -> int:
    """Shrink a streaming chunk request for a small dim: at most the
    request, at least ``floor`` (the heap width), and no wider than
    ~dim/8 so short scans keep a few steps instead of one padded chunk.
    The former ``min(chunk, max(topl, -(-d // 8)))`` pattern."""
    return min(cap, max(floor, -(-dim // 8)))


# ---------------------------------------------------------------------------
# cache I/O + validation
# ---------------------------------------------------------------------------

_default_cache_path: pathlib.Path | None = None


def cache_path() -> pathlib.Path:
    global _default_cache_path
    env = os.environ.get(CACHE_ENV, "")
    if env:
        return pathlib.Path(env)
    if _default_cache_path is None:      # resolve() syscalls once, not
        _default_cache_path = pathlib.Path(            # per dispatch
            __file__).resolve().parents[3] / "TUNE_CACHE.json"
    return _default_cache_path


def validate(doc) -> dict:
    """Check a cache document against this build's schema; returns the
    document. Raises ``TuneCacheError`` on ANY drift."""
    if not isinstance(doc, dict):
        raise TuneCacheError(f"cache root must be an object, got "
                             f"{type(doc).__name__}")
    if doc.get("schema_version") != SCHEMA_VERSION:
        raise TuneCacheError(
            f"schema_version {doc.get('schema_version')!r} != "
            f"{SCHEMA_VERSION} — regenerate with `python -m repro.tune`")
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        raise TuneCacheError("missing/invalid 'entries' object")
    for device, kernels in entries.items():
        if not isinstance(kernels, dict):
            raise TuneCacheError(f"entries[{device!r}] must be an object")
        for key, buckets in kernels.items():
            spec = KERNELS.get(key)
            if spec is None:
                raise TuneCacheError(f"unknown kernel {key!r} in cache")
            if not isinstance(buckets, dict):
                raise TuneCacheError(f"{key!r} buckets must be an object")
            for bkey, entry in buckets.items():
                cfg = entry.get("config") if isinstance(entry, dict) else None
                if not isinstance(cfg, dict):
                    raise TuneCacheError(
                        f"{key!r}[{bkey!r}] missing 'config' object")
                for p, v in cfg.items():
                    if p not in spec.params:
                        raise TuneCacheError(
                            f"{key!r}[{bkey!r}]: unknown param {p!r}")
                    if not isinstance(v, int) or isinstance(v, bool):
                        raise TuneCacheError(
                            f"{key!r}[{bkey!r}].{p}: non-integer {v!r}")
    return doc


_cache_memo: tuple | None = None        # (path, mtime_ns, doc)


def load_cache(path: pathlib.Path | None = None, *,
               refresh: bool = False) -> dict:
    """Load + validate the winner cache (memoized on (path, mtime); a
    missing file is an empty cache, a malformed one raises
    ``TuneCacheError``)."""
    global _cache_memo
    p = pathlib.Path(path) if path is not None else cache_path()
    try:
        mtime = p.stat().st_mtime_ns
    except OSError:
        return {"schema_version": SCHEMA_VERSION, "entries": {}}
    if (not refresh and _cache_memo is not None
            and _cache_memo[0] == p and _cache_memo[1] == mtime):
        return _cache_memo[2]
    try:
        doc = json.loads(p.read_text())
    except ValueError as e:
        raise TuneCacheError(f"unparseable tune cache {p}: {e}") from e
    doc = validate(doc)
    _cache_memo = (p, mtime, doc)
    return doc


def save_cache(doc: dict, path: pathlib.Path | None = None) -> pathlib.Path:
    """Validate + atomically write the cache document."""
    global _cache_memo
    validate(doc)
    p = pathlib.Path(path) if path is not None else cache_path()
    tmp = p.with_suffix(".tmp")
    tmp.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    tmp.replace(p)
    _cache_memo = None
    return p


_device_kind_memo: str | None = None


def device_kind() -> str:
    """Cache key for the current accelerator (e.g. 'cpu',
    'TPU v4' -> 'tpu_v4'). Memoized — the device set is fixed for the
    life of the process, and this sits on the per-call resolve path."""
    global _device_kind_memo
    if _device_kind_memo is None:
        import jax
        _device_kind_memo = \
            jax.devices()[0].device_kind.lower().replace(" ", "_")
    return _device_kind_memo


# ---------------------------------------------------------------------------
# the lookup ops.py resolves every block parameter through
# ---------------------------------------------------------------------------

def registry_key(kernel: str, impl: str | None = None) -> str:
    key = kernel if impl is None else f"{kernel}.{impl}"
    if key not in KERNELS and kernel in KERNELS:
        key = kernel                    # impl-agnostic entry (dispatch)
    if key not in KERNELS:
        raise KeyError(f"unknown tunable kernel {key!r} "
                       f"(registered: {sorted(KERNELS)})")
    return key


_resolve_memo: dict = {}
#: resolution-memo capacity. Eviction is LRU one-at-a-time (dicts are
#: insertion-ordered; a hit reinserts its key at the back), so a serving
#: loop's hot buckets stay resident no matter how much one-off shape
#: churn flows past — a wholesale clear here made steady-state serving
#: repay every resolution after each overflow.
_MEMO_CAP = 4096
#: guards every _resolve_memo access: the serving worker thread and
#: direct index.search callers resolve concurrently, and the unguarded
#: pop-reinsert/evict dance could KeyError mid-eviction (iter one
#: thread, pop another). Held only for dict probes — never across the
#: cache load.
_memo_lock = threading.Lock()


def best_config(kernel: str, impl: str | None = None, **dims) -> dict:
    """Resolve the block parameters for a kernel at a runtime shape:
    the cached winner of this device's (kernel, shape-bucket) sweep, or
    the registered hand-pinned defaults when nothing is cached (or
    ``REPRO_TUNE_DISABLE=1``). Returns ``{param: value}``.

    Resolutions are memoized on (kernel, bucket, cache mtime) in a small
    LRU (capacity ``_MEMO_CAP``), so the steady-state cost is one stat +
    two dict probes — this sits on EVERY kernel dispatch, where a JSON
    reparse per call would cost ~10% of a small rerank call."""
    key = registry_key(kernel, impl)
    spec = KERNELS[key]
    if os.environ.get(DISABLE_ENV, "") not in ("", "0"):
        return dict(spec.params)
    try:
        mtime = cache_path().stat().st_mtime_ns
    except OSError:
        mtime = None
    bkey = bucket_key(spec, dims)
    memo_key = (key, bkey, mtime)
    with _memo_lock:
        hit = _resolve_memo.pop(memo_key, None)
        if hit is not None:
            _resolve_memo[memo_key] = hit   # reinsert: most recently used
    if hit is not None:
        return dict(hit)
    entry = (load_cache().get("entries", {})
             .get(device_kind(), {})
             .get(key, {})
             .get(bkey))
    out = dict(spec.params)
    if entry:
        out.update({p: entry["config"][p]
                    for p in spec.params if p in entry["config"]})
    with _memo_lock:
        while len(_resolve_memo) >= _MEMO_CAP:
            _resolve_memo.pop(next(iter(_resolve_memo)))   # evict oldest
        _resolve_memo[memo_key] = dict(out)
    return out


def cache_fingerprint() -> dict:
    """Small summary for ``Index`` save metadata: where the winners came
    from and how many buckets are tuned for this device."""
    doc = load_cache()
    mine = doc.get("entries", {}).get(device_kind(), {})
    return {"schema_version": doc.get("schema_version", SCHEMA_VERSION),
            "device_kind": device_kind(),
            "tuned_buckets": sum(len(b) for b in mine.values())}
