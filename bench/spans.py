"""The serving path's own host spans in a ``--trace 1`` run, matched to
its flushes and to the search programs on the device.

The program writes these spans (``jax.profiler.TraceAnnotation``), all
leaves, and none around the device's run:

* one flush, in this order: ``serve.form`` (event loop: take and plan the
  batch), ``serve.prep``, ``serve.launch``, ``serve.wait``, ``serve.fetch``,
  ``serve.map`` (worker thread), ``serve.post`` (event loop: resolve the
  batch's futures);
* the event loop's others: ``serve.admit`` (one a request) and
  ``serve.reap`` (one a pass of the deadline watcher).

The loop runs one flush at a time, and ``devtrace``'s flat events carry no
span arguments, so each ``serve.form`` opens a flush and the flush spans up
to the next one belong to it. The two hand-offs of a flush are the gaps
``asyncio.to_thread`` leaves: from ``serve.form``'s end to ``serve.prep``'s
start, and from ``serve.map``'s end to ``serve.post``'s start.
"""
from __future__ import annotations

import bisect

from devtrace import _union

FORM, PREP, LAUNCH, WAIT, FETCH, MAP, POST = (
    "serve.form", "serve.prep", "serve.launch", "serve.wait", "serve.fetch",
    "serve.map", "serve.post")
FLUSH = (FORM, PREP, LAUNCH, WAIT, FETCH, MAP, POST)
ADMIT, REAP = "serve.admit", "serve.reap"
LOOP = (FORM, POST, ADMIT, REAP)
OWN = ("serve.", "bench.")      # the program's spans and the benchmark's


def named(trace, names, in_window: bool = True):
    """Host events named in ``names``, by start; with ``in_window`` only
    those that start inside the window."""
    out = [e for e in trace.host if e[2] in names
           and (not in_window or trace.w0 <= e[3] < trace.w1)]
    return sorted(out, key=lambda e: e[3])


def per_search_run(trace, names) -> float | None:
    """Summed ms of the window's ``names`` spans per search program run, or
    None where there is no run or no such span."""
    runs = trace.search_runs()
    ev = named(trace, names)
    if not runs or not ev:
        return None
    return sum(e[4] for e in ev) * 1e-6 / len(runs)


def flushes(trace, in_window: bool = True) -> list[dict]:
    """Flushes whose seven spans each appear once, in order, as
    ``{name: (start_ns, end_ns)}``; with ``in_window`` those whose
    ``serve.form`` starts inside the window."""
    out, cur = [], None
    for e in named(trace, FLUSH, in_window=False):
        if e[2] == FORM:
            cur = {}
            out.append(cur)
        if cur is not None:
            cur.setdefault(e[2], []).append((e[3], e[3] + e[4]))
    whole = []
    for f in out:
        if tuple(f) != FLUSH or any(len(v) != 1 for v in f.values()):
            continue
        f = {k: v[0] for k, v in f.items()}
        if not in_window or trace.w0 <= f[FORM][0] < trace.w1:
            whole.append(f)
    return whole


def handoffs(f: dict):
    """The two hand-off intervals of one flush."""
    return [(f[FORM][1], f[PREP][0]), (f[MAP][1], f[POST][0])]


def fetch_of_runs(trace) -> list[tuple]:
    """(search program run, the ``serve.fetch`` that returned its results)
    for each run that has one: the first fetch that starts after the run
    started. The fetch of the run before started before this run was
    launched, and this run's own fetch starts after its wait, so a skew of
    the two clocks well under a flush cannot pair a run with another
    flush's fetch."""
    fetches = named(trace, (FETCH,), in_window=False)
    out, i = [], 0
    for run in sorted(trace.search_runs(), key=lambda e: e[3]):
        while i < len(fetches) and fetches[i][3] < run[3]:
            i += 1
        if i < len(fetches):
            out.append((run, fetches[i]))
    return out


def unspanned_gaps_ns(trace) -> list[float]:
    """For each device gap between two search programs in a row, the time
    that no program or benchmark span (on any thread) and no hand-off
    covers. JAX's and the runtime's own host events do not count: some of
    them last across whole gaps on threads that do no work for the
    flush."""
    cover = _union([(e[3], e[3] + e[4]) for e in trace.host
                   if e[2].startswith(OWN)]
                   + [iv for f in flushes(trace, in_window=False)
                      for iv in handoffs(f)])
    starts = [c[0] for c in cover]
    by_dev: dict = {}
    for e in trace.search_runs():
        by_dev.setdefault(e[0], []).append(e)
    out = []
    for runs in by_dev.values():
        for a, b in zip(runs, runs[1:]):
            s, e = a[3] + a[4], b[3]
            free = max(e - s, 0.0)
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            while i < len(cover) and cover[i][0] < e:
                free -= max(min(cover[i][1], e) - max(cover[i][0], s), 0.0)
                i += 1
            out.append(free)
    return out
