"""The device trace of a ``--trace 1`` run, and its reduction to numbers.

A run is traced with ``jax.profiler`` over its measured window, and the
benchmark's own host spans (``jax.profiler.TraceAnnotation``) mark the
window and the client work. ``load`` turns the ``.xplane.pb`` into flat
events, ``Reduced`` answers every question a metric reader asks. The
reduction reads only these names, kept here in one lookup:

* device operations: the ``XLA Ops`` line of each ``/device:`` plane,
  named by their HLO instruction (``%hop_kernel_call.4 = ...`` is kept as
  ``hop_kernel_call.4``); control flow (``CONTAINERS``) holds other ops and
  counts as busy but not in the table of ops;
* program executions: the ``XLA Modules`` line;
* the search program: a module whose name holds ``SEARCH_PROGRAM``;
* the hop megakernel: an op whose name starts with ``HOP_KERNEL``, the
  Pallas call's name in ``kernels/hop.py``;
* the window: the host span named ``WINDOW_SPAN``.
"""
from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
from collections import defaultdict

import numpy as np

SEARCH_PROGRAM = "_search_improvised_jit"
HOP_KERNEL = "hop_kernel_call"
CONTAINERS = ("while", "conditional", "call")
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAP_NS = 50_000
SHORT_GAPS = "between ops (< 50 us)"
NO_SPAN = "no host span over most of it"


@contextlib.contextmanager
def capture(out_dir: str):
    """Trace what runs inside the block into ``out_dir`` (emptied first)."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # host spans are the benchmark's own
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def op_name(name: str) -> str:
    """``%fusion.52 = pred[4096] fusion(...)`` -> ``fusion.52``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(out_dir: str) -> list[tuple]:
    """Flat events ``(plane, line, name, start_ns, dur_ns)`` of the trace
    in ``out_dir``, device ops under their short names."""
    import jax

    paths = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {out_dir}, found "
                           f"{len(paths)}")
    events = []
    for plane in jax.profiler.ProfileData.from_file(paths[0]).planes:
        if not (plane.name.startswith("/device:")
                or plane.name.startswith("/host:CPU")):
            continue
        dev = plane.name.startswith("/device:")
        for line in plane.lines:
            for e in line.events:
                events.append((plane.name, line.name,
                               op_name(e.name) if dev else e.name,
                               float(e.start_ns), float(e.duration_ns)))
    return events


def load_events(path: str) -> list[tuple]:
    with open(path) as f:
        return [tuple(e) for e in json.load(f)]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Reduced:
    """The numbers a trace gives, over the benchmark's window span."""

    def __init__(self, events):
        win = [e for e in events if e[2] == WINDOW_SPAN
               and e[0].startswith("/host")]
        if len(win) != 1:
            raise RuntimeError(f"expected one {WINDOW_SPAN!r} span, found "
                               f"{len(win)}")
        self.w0, self.w1 = win[0][3], win[0][3] + win[0][4]
        dev = [e for e in events if e[0].startswith("/device:")
               and e[3] < self.w1 and e[3] + e[4] > self.w0]
        self.devices = sorted({e[0] for e in dev})
        self.ops = [e for e in dev if e[1] == OPS_LINE]
        self.modules = sorted((e for e in dev if e[1] == MODULES_LINE),
                              key=lambda e: e[3])
        self.host = [e for e in events if e[0].startswith("/host")
                     and e[2] != WINDOW_SPAN]

    # -- device time -------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def busy(self, device: str):
        """Merged intervals in which an op ran on ``device``, clipped to
        the window."""
        return _union(
            (max(e[3], self.w0), min(e[3] + e[4], self.w1))
            for e in self.ops if e[0] == device)

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran anything."""
        if not self.devices:
            return 0.0
        tot = sum(e - s for d in self.devices for s, e in self.busy(d))
        return tot * 1e-9 / len(self.devices)

    def idle_share(self) -> float | None:
        if not self.ops:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def top_ops(self, n: int = 10):
        """Device seconds by op (control flow left out), most first."""
        tot = defaultdict(float)
        for e in self.ops:
            if e[2].split(".")[0] not in CONTAINERS:
                tot[e[2]] += e[4] * 1e-9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10):
        """Idle time of the first device, by what the host was doing. A gap
        of at least ``GAP_NS`` is named by the host event that overlaps it
        most (the innermost among equals), where that covers at least half
        of it, else ``NO_SPAN`` (the host ran code that writes no span);
        shorter gaps, between the ops of one program, add up under
        ``SHORT_GAPS``. Seconds of equal names add up."""
        if not self.devices:
            return []
        busy = self.busy(self.devices[0])
        edges = [self.w0] + [x for iv in busy for x in iv] + [self.w1]
        hs = np.array([h[3] for h in self.host])
        he = hs + np.array([h[4] for h in self.host])
        tot = defaultdict(float)
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            name = SHORT_GAPS
            if e - s >= GAP_NS:
                name = NO_SPAN
                if len(hs):
                    ov = np.minimum(he, e) - np.maximum(hs, s)
                    # most overlap first, then the shortest event
                    best = np.lexsort((he - hs, -ov))[0]
                    if ov[best] >= 0.5 * (e - s):
                        name = self.host[best][2]
            tot[name] += (e - s) * 1e-9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    # -- the search program and the hop kernel -----------------------------
    def search_runs(self):
        return [e for e in self.modules if SEARCH_PROGRAM in e[2]]

    def hop_ops(self):
        return [e for e in self.ops if e[2].startswith(HOP_KERNEL)]

    def flush_gaps_s(self):
        """Device gaps between one search program's end and the next
        one's start, on the same device."""
        gaps = []
        by_dev = defaultdict(list)
        for e in self.search_runs():
            by_dev[e[0]].append(e)
        for runs in by_dev.values():
            for a, b in zip(runs, runs[1:]):
                gaps.append(max(b[3] - (a[3] + a[4]), 0.0) * 1e-9)
        return gaps
