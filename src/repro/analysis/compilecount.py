"""Compile-count discipline: prove the bucket ladder actually buckets.

``Index.add`` pads every batch to the ``ENCODE_BUCKETS`` ladder so the
encoder compiles once per bucket instead of once per batch size, and
``Index.search`` runs fixed-shape jitted paths that must hit the trace
cache on every repeat call. Neither property is visible to a unit test
that only checks results — a silently broken ladder still returns correct
codes, just N times slower. This harness counts XLA compiles directly
(``jax_log_compiles`` emits one log record per cache-miss compilation)
and asserts the discipline:

  * a repeat ``add`` of an already-seen batch size within an already-seen
    bucket compiles nothing but unavoidable shape-varying glue (the
    ``concatenate`` growing the code buffer — ``ntotal`` changes shape
    every add by design);
  * the first batch landing in a NEW bucket compiles the encoder exactly
    then (events mentioning the bucket's padded shape appear);
  * a repeat ``search`` with identical query shape compiles NOTHING.

The harness self-checks its counter first (a fresh jitted lambda must
produce >= 1 event) so a broken logging hookup can never pass vacuously.
"""
from __future__ import annotations

import contextlib
import logging
import re

#: compile events whose trigger is an input-shape-dependent glue op, not
#: the encoder body: the code-buffer concatenate (ntotal grows every add),
#: the raw-batch pad to the bucket, and the unpad slice back out
_ADD_GLUE = ("concatenate", "_pad", "dynamic_slice", "convert_element_type")

#: the computation's name; JAX logs it bare or as ``jit(<name>)``
_NAME_RE = re.compile(r"Compiling (?:jit\()?([\w.<>\-]+)")


class CompileLog:
    """Captured compile events from one ``count_compiles()`` window."""

    def __init__(self):
        self.events: list[str] = []

    @property
    def count(self) -> int:
        return len(self.events)

    def names(self) -> list[str]:
        out = []
        for e in self.events:
            m = _NAME_RE.search(e)
            out.append(m.group(1) if m else e[:60])
        return out


class _Capture(logging.Handler):
    def __init__(self, log: CompileLog):
        super().__init__(level=logging.DEBUG)
        self.log = log

    def emit(self, record):
        msg = record.getMessage()
        if "Compiling" in msg:
            self.log.events.append(msg)


def _mute(record) -> bool:
    return False


@contextlib.contextmanager
def count_compiles():
    """Count XLA compilations triggered inside the ``with`` block.

    Pre-existing handlers on the jax logger are muted for the duration so
    enabling ``jax_log_compiles`` doesn't spray the terminal; only the
    capture handler sees the records.
    """
    import jax
    log = CompileLog()
    handler = _Capture(log)
    logger = logging.getLogger("jax")
    prev_level = logger.level
    prev = jax.config.jax_log_compiles
    muted = list(logger.handlers)
    for h in muted:
        h.addFilter(_mute)
    jax.config.update("jax_log_compiles", True)
    if logger.level > logging.WARNING:
        logger.setLevel(logging.WARNING)
    logger.addHandler(handler)
    try:
        yield log
    finally:
        logger.removeHandler(handler)
        for h in muted:
            h.removeFilter(_mute)
        logger.setLevel(prev_level)
        jax.config.update("jax_log_compiles", prev)


def _counter_sane() -> bool:
    """A fresh jitted function must register >= 1 compile event (fresh
    function object -> guaranteed trace-cache miss)."""
    import jax
    import jax.numpy as jnp
    with count_compiles() as log:
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(13, dtype=jnp.float32))
    return log.count >= 1


def encode_ladder_violations() -> list[str]:
    """Run the add/search discipline scenario; returns violation strings
    (empty = disciplined). Uses a distinctive dim so a shared process's
    earlier trace-cache entries cannot mask a missing compile."""
    import numpy as np

    from repro.index import index_factory

    violations: list[str] = []
    if not _counter_sane():
        return ["compile counter captured no event for a fresh jitted "
                "function — the jax_log_compiles hookup is broken, all "
                "discipline checks would pass vacuously"]

    dim = 21                         # distinctive: avoids cross-test caches
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((1900, dim)).astype(np.float32)
    queries = rng.standard_normal((3, dim)).astype(np.float32)

    index = index_factory("PQ3x16,Rerank10", dim=dim)
    index.train(xs[:600], iters=3)

    index.add(xs[:250])              # warm: first 256-bucket compile
    with count_compiles() as log:
        index.add(xs[250:500])       # repeat size, same bucket
    bad = [n for n in log.names()
           if not any(n.startswith(g) for g in _ADD_GLUE)]
    if bad:
        violations.append(
            "same-size add in an already-compiled bucket recompiled "
            f"non-glue computations: {bad} (bucket ladder broken?)")

    with count_compiles() as log:
        index.add(xs[500:1100])      # 600 rows -> first hit of bucket 1024
    if not any("1024" in e for e in log.events):
        violations.append(
            "first add into the 1024 bucket compiled nothing shaped by the "
            "bucket — either the ladder is bypassed or the counter missed "
            "the encoder compile")

    with count_compiles() as log:
        index.add(xs[1100:1700])     # repeat size, bucket 1024 already hot
    bad = [n for n in log.names()
           if not any(n.startswith(g) for g in _ADD_GLUE)]
    if bad:
        violations.append(
            "repeat add in the 1024 bucket recompiled non-glue "
            f"computations: {bad}")

    index.search(queries, 5)         # warm every search-path shape
    with count_compiles() as log:
        index.search(queries, 5)
    if log.count:
        violations.append(
            f"repeat search with identical shapes compiled {log.count} "
            f"computations ({log.names()[:5]}) — the search path must be "
            "fully trace-cached")
    return violations
