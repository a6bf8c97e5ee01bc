"""Capture of a profiler trace and its reduction to device time.

``Tracer`` records a window with JAX's profiler (no Python tracer: it
would slow the serving host several times over). ``Events`` keeps what
the readers need from the trace: per chip the intervals of its XLA
operations and of its programs (XLA modules), and the host's events.
Its methods are the reduction every per-layer metric shares: the union
of a chip's busy intervals, the idle share, the device time of the
operations or programs whose names match, and the breakdown printed
with a traced run.
"""
from __future__ import annotations

import collections
import json
import pathlib
import re
import shutil

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union_ns(intervals) -> int:
    """Total length covered by (start, duration) intervals."""
    total, end = 0, None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


class Events:
    """The reduced trace: ``ops[chip]`` and ``modules[chip]`` are lists of
    [name, start_ns, duration_ns]; ``host`` of [thread, name, start_ns,
    duration_ns]."""

    def __init__(self, ops: dict, modules: dict, host: list):
        self.ops = {int(k): v for k, v in ops.items()}
        self.modules = {int(k): v for k, v in modules.items()}
        self.host = host

    # -- loading -------------------------------------------------------------

    @classmethod
    def from_xplane(cls, path) -> "Events":
        import jax
        data = jax.profiler.ProfileData.from_file(str(path))
        ops, modules, host = {}, {}, []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m:
                    chip = int(m.group(1))
                    if line.name == OPS_LINE:
                        # an op's event name is its HLO text: keep the
                        # instruction name
                        ops.setdefault(chip, []).extend(
                            [e.name.split(" = ")[0], e.start_ns,
                             e.duration_ns] for e in line.events)
                    elif line.name == MODULES_LINE:
                        modules.setdefault(chip, []).extend(
                            [e.name, e.start_ns, e.duration_ns]
                            for e in line.events)
                elif plane.name.startswith("/host:"):
                    host.extend([line.name, e.name, e.start_ns,
                                 e.duration_ns] for e in line.events)
        return cls(ops, modules, host)

    def to_json(self, path) -> None:
        with open(path, "w") as f:
            json.dump({"ops": self.ops, "modules": self.modules,
                       "host": self.host}, f)

    @classmethod
    def from_json(cls, path) -> "Events":
        with open(path) as f:
            return cls(**json.load(f))

    # -- reduction -----------------------------------------------------------

    def busy_ns(self, chip: int) -> int:
        """Nanoseconds in which some operation ran on the chip."""
        return union_ns((s, d) for _, s, d in self.ops.get(chip, []))

    def busy_seconds(self, chips: int) -> float:
        """Busy time averaged over the first ``chips`` chips."""
        return sum(self.busy_ns(c) for c in range(chips)) / chips / 1e9

    def op_seconds(self, pattern: str, chips: int = 1) -> float | None:
        """Device seconds of the operations whose name matches
        ``pattern`` (a regular expression), averaged over the chips; None
        when no operation matches."""
        rx = re.compile(pattern)
        hit = [(s, d) for c in range(chips)
               for name, s, d in self.ops.get(c, []) if rx.search(name)]
        return union_ns(hit) / chips / 1e9 if hit else None

    def module_seconds(self, pattern: str, chips: int = 1) -> float | None:
        """Device seconds of the programs whose name matches ``pattern``,
        averaged over the chips; None when none matches."""
        rx = re.compile(pattern)
        hit = [(s, d) for c in range(chips)
               for name, s, d in self.modules.get(c, []) if rx.search(name)]
        return union_ns(hit) / chips / 1e9 if hit else None

    def idle_gaps(self, chip: int = 0):
        """(start_ns, length_ns) of the gaps between the chip's busy
        intervals, in order."""
        gaps, end = [], None
        for start, dur in sorted((s, d) for _, s, d in self.ops.get(chip,
                                                                   [])):
            if end is not None and start > end:
                gaps.append((end, start - end))
            end = start + dur if end is None else max(end, start + dur)
        return gaps

    def breakdown(self, top: int = 10) -> dict:
        """The chip-0 operations (by HLO instruction name) that took most
        device time, and the idle time by what the host was doing in it:
        each gap goes to the shortest event of the Python threads that
        covers its middle."""
        per_op = collections.Counter()
        for name, _, dur in self.ops.get(0, []):
            per_op[name.split(" = ")[0]] += dur
        # what the Python program was doing, where the trace shows it
        host = [h for h in self.host if "python" in h[0] and h[3] > 0] \
            or [h for h in self.host if h[3] > 0]
        starts = np.array([s for _, _, s, _ in host], np.int64)
        stops = np.array([s + d for _, _, s, d in host], np.int64)
        names = [n for _, n, _, _ in host]
        per_gap = collections.Counter()
        for start, length in self.idle_gaps(0):
            mid = start + length // 2
            cover = np.flatnonzero((starts <= mid) & (mid < stops))
            name = names[cover[np.argmin(stops[cover] - starts[cover])]] \
                if cover.size else "(no host event)"
            per_gap[name] += length
        return {"device_ops": [[n, v / 1e9]
                               for n, v in per_op.most_common(top)],
                "idle_gaps": [[n, v / 1e9]
                              for n, v in per_gap.most_common(top)]}


class Tracer:
    """One traced window, written under ``directory`` (emptied first)."""

    def __init__(self, directory: pathlib.Path):
        self.directory = pathlib.Path(directory)

    def start(self) -> None:
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        self.directory.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.directory), profiler_options=opts)

    def stop(self) -> Events:
        import jax
        jax.profiler.stop_trace()
        files = sorted(self.directory.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.directory}")
        return Events.from_xplane(files[-1])
