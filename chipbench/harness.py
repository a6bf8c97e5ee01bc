"""One run of one cell: build, warm, prime, measure, check, report.

``chipbench/run.py`` is the command; this module is what it runs, kept
importable so that the tests can drive a whole run on the CPU at a tiny
size. Everything that belongs to one configuration, traffic mix or
per-layer metric is found by name in ``BENCHMARK.json``:

* ``configs/<config>.json``: the deployment; its ``family`` names the
  module of ``deploy/`` that builds it and holds its plain reference;
* ``traffic/<mix>.json``: the mix, read by ``loadgen.Traffic``;
* ``metrics/<metric>.py`` (or ``metrics/<part before the first dot>.py``):
  the reader of a per-layer metric, ``read(ctx)`` -> number or None;
* ``kernels/<kernel>.py``: the operations and bytes of a kernel's work;
* ``peaks.json``: the chip's peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import logging
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
RUN_DIR = ROOT / ".chipbench"            # traces and run records
PRIME_SECONDS = 3.0                      # priming passes, until one
PRIME_BUDGET_S = 300.0                   # makes no program (or budget)


class NoChip(SystemExit):
    """Raised when the cell's chips are not there: no result is printed."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: dict, workload: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def reader(name: str):
    """The ``read`` function of per-layer metric ``name``."""
    for stem in (name, name.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"chipbench.metrics.{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {name!r}")


def metrics_of(manifest: dict, workload: str, trace: bool) -> list[dict]:
    """The metric entries a cell reports: its end-to-end metrics in an
    untraced run, its per-layer metrics in a traced one."""
    if not trace:
        return [m for m in manifest["end_to_end"]
                if workload in m.get("workloads", [workload])]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    out = []
    for m in manifest["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            moved = e2e[m["moves"]]
            cells = moved.get("workloads", [workload])
        if workload in cells:
            out.append(m)
    return out


class CompileClock(logging.Handler):
    """Counts the programs a process makes, through ``jax.monitoring``:
    each is compiled, or loaded from the persistent cache (a hit). It
    also keeps the name and argument shapes of each program JAX lowers
    (its debug log), so a run can say what a window made."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        import jax
        super().__init__(logging.DEBUG)
        self.count = 0
        self.seconds = 0.0
        self.hits = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)
        logger = logging.getLogger(self.LOGGER)
        logger.setLevel(logging.DEBUG)
        logger.propagate = False
        logger.addHandler(self)

    def emit(self, record) -> None:
        if str(record.msg).startswith("Compiling ") and record.args:
            self.names.append(" ".join(str(a) for a in record.args[:2])[:200])

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1


def require_chips(chips: int):
    """The cell's devices, or NoChip: the benchmark runs on a TPU only."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU (platform {devices[0].platform!r});"
                     " the benchmark runs only on a TPU")
    if len(devices) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} chips, "
                     f"{len(devices)} visible")
    return devices[:chips]


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compile cache at a fixed path of the checkout,
    keeping every program, however small or quick to compile, and never
    evicting one (a size limit set in the environment would let the
    cell's own programs push each other out)."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def peak_bytes(stats: dict) -> int:
    """The peak device memory of a chip's ``memory_stats()``: the buffers'
    peak (``peak_bytes_in_use``) plus the peak of the region the TPU
    runtime reserves for the programs' temporaries
    (``peak_bytes_reserved``), which ``peak_bytes_in_use`` leaves out. The
    stage-1 program's 2.56 GB temp shows only in the second."""
    return int(stats.get("peak_bytes_in_use", 0)) \
        + int(stats.get("peak_bytes_reserved", 0))


def percentile(values, q: float) -> float:
    """The q-th percentile (linear between order statistics)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Run:
    """State of one run, handed to the per-layer readers as ``ctx``."""

    def __init__(self, manifest, cell, config, mix, seed, seconds, trace):
        self.manifest, self.cell = manifest, cell
        self.config, self.mix = config, mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.records = []
        self.window = (0.0, 0.0)
        self.serve = {}                 # ServeMetrics summary of the window
        self.window_compiles = 0
        self.window_programs = []       # what JAX lowered in the window
        self.events = None              # trace.Events of a traced window
        self.setup = {}
        self.peaks = None
        self.devices = []
        self.peak_bytes = 0
        self.memory = {}                # memory_stats of the fullest chip

    # -- what the readers use ------------------------------------------------

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def served_queries(self) -> int:
        return sum(len(r.rows) for r in self.records if r.result is not None)

    def lateness_ms(self) -> list[float]:
        return [(r.sent - r.due) * 1e3 for r in self.records]

    def latencies_ms(self) -> list[float]:
        """Due time to answer, for every request of the window (one that
        never answered counts as infinitely late)."""
        return [(r.done - r.due) * 1e3 if r.result is not None
                else float("inf") for r in self.records]


def fills(sizes, most: int) -> dict[int, list[int]]:
    """Every number of real queries a batch of the mix can hold, up to
    ``most``, each with one list of request sizes that sums to it."""
    reach = {0: []}
    for total in range(1, most + 1):
        for q in sizes:
            if total - q in reach:
                reach[total] = reach[total - q] + [q]
                break
    return {t: parts for t, parts in reach.items() if t}


def serve_engine(index, serve_kw: dict, traffic):
    """The program's serving engine for the deployment and the mix (the
    mix's ``serve`` policy over the configuration's)."""
    from repro.serve import ServeConfig, ServeEngine
    kw = dict(serve_kw, **traffic.mix.get("serve", {}))
    return ServeEngine(index, ServeConfig(default_k=traffic.k, **kw))


def warm(engine, traffic, pool) -> int:
    """One batch of every fill the engine can form from the mix (every
    number of real queries a batch can hold, up to the engine's
    ``max_batch_queries``, so every query bucket it can use), at each k
    of the mix, with and without a filter where the mix filters, through
    the engine's coalesce-and-execute path: the rerank's pool sizes, and
    so its programs, follow the number of real queries in a batch.
    Returns the number of batches."""
    most = engine.config.max_batch_queries
    share = float(traffic.filter["share"]) if traffic.filter else 0.0
    masked = [m for m in (False, True)
              if (m and share > 0) or (not m and share < 1)]
    rows, batches = np.arange(pool.shape[0]), 0
    ntotal = engine.index.ntotal
    for fill, parts in fills(traffic.sizes, most).items():
        for k in traffic.ks:
            for m in masked:
                reqs, lo = [], 0
                for q in parts:
                    req = {"queries": pool[rows[lo:lo + q]], "k": k}
                    if m:
                        req["filter_mask"] = np.broadcast_to(
                            traffic.mask(0, ntotal), (q, ntotal))
                    reqs.append(req)
                    lo += q
                engine.search_requests(reqs)
                rows = np.roll(rows, -fill)
                batches += 1
    return batches


def prime(traffic, submit, pool, clock: CompileClock, ntotal: int) -> int:
    """Serve passes of the cell's own traffic until one makes no program
    (compiles none and loads none from the cache), or PRIME_BUDGET_S has
    passed; returns the number of passes. The rerank's pools (and an
    IVF index's plan widths) take their shapes from the data, so the
    warm-up of the query buckets alone does not make every program the
    window needs."""
    stop = time.perf_counter() + PRIME_BUDGET_S
    passes = 0
    while time.perf_counter() < stop:
        before = clock.count
        traffic.run(submit, pool, PRIME_SECONDS, ntotal)
        passes += 1
        if clock.count == before:
            break
    return passes


def build_and_serve(run: Run, clock: CompileClock, t_start: float):
    """Set-up and the measured window, recorded on ``run``; returns the
    query pool and the deployment's module (for the reference)."""
    import jax
    from chipbench import data, loadgen, trace as tracemod

    family = importlib.import_module(
        f"chipbench.deploy.{run.config['family']}")
    traffic = loadgen.Traffic(run.mix, run.seed)

    t0 = time.perf_counter()
    index, serve_kw = family.build(run.config, run.seed)
    pool = np.asarray(data.DeepLike(run.seed, run.config["data"]).queries(
        traffic.pool))
    jax.block_until_ready(index.codes)
    t1 = time.perf_counter()
    engine = serve_engine(index, serve_kw, traffic)
    warmed = warm(engine, traffic, pool)

    def submit(queries, k, **kw):
        return engine.submit(queries, k=k, **kw)

    t2 = time.perf_counter()
    # priming draws its requests apart, so the window's are the seed's
    passes = prime(loadgen.Traffic(run.mix, run.seed, stream=1), submit,
                   pool, clock, index.ntotal)
    t3 = time.perf_counter()
    run.setup = {"build_s": t1 - t0, "warm_s": t2 - t1, "prime_s": t3 - t2,
                 "warm_batches": warmed, "prime_passes": passes,
                 "programs": clock.count,
                 "cache_hits": clock.hits, "program_s": clock.seconds}
    engine.metrics.reset()
    compiles_before, names_before = clock.count, len(clock.names)
    tracer = tracemod.Tracer(RUN_DIR / "trace" / run.cell["name"]) \
        if run.trace else None
    if tracer is not None:
        tracer.start()
    run.setup["setup_s"] = time.perf_counter() - t_start
    w0 = time.perf_counter()
    records = traffic.run(submit, pool, run.seconds, index.ntotal)
    done = [r.done for r in records if r.result is not None]
    w1 = max(done) if done else time.perf_counter()
    if tracer is not None:
        run.events = tracer.stop()
    run.window = (w0, w1)
    run.records = records
    run.window_compiles = clock.count - compiles_before
    run.window_programs = clock.names[names_before:]
    run.serve = engine.metrics.summary()
    run.devices = jax.devices()[:run.cell["chips"]]
    stats = [d.memory_stats() or {} for d in run.devices]
    run.memory = max(stats, key=peak_bytes)
    run.peak_bytes = peak_bytes(run.memory)
    engine.close()
    return pool, family


def check(run: Run, pool, family) -> dict:
    """Compare a seeded sample of the window's answers with the plain
    reference; returns {name: (value, limit)}. Every answer of the window
    is due: one that never came counts under ``failed``."""
    limits = run.config["limits"]
    answered = [r for r in run.records if r.result is not None]
    checks = {"failed": (len(run.records) - len(answered), 0),
              "errors": (sum(r.error is not None for r in run.records), 0)}
    if not answered:                    # nothing to compare: not correct
        return checks
    rng = np.random.default_rng([run.seed, 0xC4EC])
    order = rng.permutation(len(answered))
    sizes = np.cumsum([len(answered[j].rows) for j in order])
    take = int(np.searchsorted(sizes, run.config["check_queries"])) + 1
    sample = [answered[j] for j in sorted(order[:take])]
    ref = family.Reference(run.config, run.seed)
    n_categories = (run.mix.get("filter") or {}).get("categories", 0)
    gap, wrong = 0.0, 0
    for k in sorted({r.k for r in sample}):
        group = [r for r in sample if r.k == k]
        verdict = ref.judge(
            pool[np.concatenate([r.rows for r in group])],
            np.concatenate([r.result[0] for r in group]),
            np.concatenate([r.result[1] for r in group]),
            categories=np.concatenate([np.full(len(r.rows), r.category)
                                       for r in group]),
            n_categories=n_categories,
            options=[r.options for r in group for _ in r.rows])
        gap = max(gap, float(np.max(verdict["gap"])))
        wrong += int(np.sum(verdict["wrong"]))
    checks["d1_gap"] = (gap, limits["d1_gap"])
    checks["wrong_ids"] = (wrong, limits["wrong_ids"])
    return checks


def is_correct(checks: dict) -> bool:
    return "d1_gap" in checks and all(v <= lim for v, lim in checks.values())


def end_to_end(run: Run) -> dict:
    """The values of every end-to-end metric this harness knows: a rate
    is all the queries of the window over all its time (from the first
    send to the last answer); latencies are of every request of the
    window, from its due time."""
    out = {"setup_s": run.setup["setup_s"],
           "peak_hbm_gb": run.peak_bytes / 1e9}
    if run.mix["loop"] == "closed":
        out["qps"] = run.served_queries() / run.window_s
    else:
        lat = run.latencies_ms()
        out["p50_ms"] = percentile(lat, 50)
        out["p95_ms"] = percentile(lat, 95)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, manifest=None, config=None, mix=None,
             on_chip: bool = True) -> dict:
    """One run; returns the result line as a dict. ``manifest``,
    ``config`` and ``mix`` replace the files of the checkout, and
    ``on_chip=False`` skips the look for a chip, the peaks and the compile
    cache: the tests drive the rest of a run so on the CPU."""
    manifest = manifest or load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(manifest, workload)
    config = config or load_json(HERE / "configs" / f"{cell['config']}.json")
    mix = mix or load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    run = Run(manifest, cell, config, mix, seed, seconds, trace)
    if on_chip:
        from chipbench import peaks
        devices = require_chips(cell["chips"])
        enable_compile_cache(ROOT)
        run.peaks = peaks.lookup(devices[0].device_kind)
    else:
        import jax
        devices = jax.devices()[:cell["chips"]]
    clock = CompileClock()
    pool, family = build_and_serve(run, clock, t_start)
    gc.collect()            # the program's state goes before the reference
    t_check = time.perf_counter()
    checks = check(run, pool, family)
    t_check = time.perf_counter() - t_check
    values = end_to_end(run)
    metrics = {}
    for m in metrics_of(manifest, cell["name"], trace):
        value = reader(m["name"])(run) if trace else values.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.peak_bytes}
    result = {"correct": is_correct(checks), "attempted": len(run.records),
              "failed": checks["failed"][0], "metrics": metrics,
              "device": device}
    if trace:
        device.update(busy_s=run.events.busy_seconds(len(devices)),
                      window_s=run.window_s)
        result["breakdown"] = run.events.breakdown()
    lat = run.lateness_ms()
    log(f"setup: {json.dumps(run.setup)}")
    log(f"window: {run.window_s!r} s, {len(run.records)} requests, "
        f"{run.window_compiles} compiles; serve counters "
        + json.dumps({k: run.serve.get(k) for k in
                      ("batches", "real_queries", "padded_queries")}))
    for name in run.window_programs[:20]:
        log(f"made in the window: {name}")
    log(f"generator lateness ms: p50 {percentile(lat, 50)!r} p95 "
        f"{percentile(lat, 95)!r} max {max(lat)!r}")
    log(f"end to end: {json.dumps(values)}")
    log(f"memory_stats: {json.dumps(run.memory)}")
    log(f"peaks: {json.dumps(run.peaks)}")
    log(f"reference check: {t_check!r} s")
    for name, (value, limit) in checks.items():
        log(f"check {name}: {value!r} (limit {limit!r})")
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def main(args, t_start: float) -> int:
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start)
    print(json.dumps(result), flush=True)
    return 0
