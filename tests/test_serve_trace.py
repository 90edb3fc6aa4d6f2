"""The serving path's profiler spans and counters.

A small ``AsyncServingEngine`` serves under ``jax.profiler``; the test reads
the written ``.xplane.pb`` back with ``ProfileData``: the spans
(``serve.*``), their ``flush`` arguments, and the counters that
``AsyncServingEngine.stats`` surfaces beside them.
"""
import asyncio
import glob
import os
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.core import BuildConfig, RangeGraphIndex, SearchConfig, ServeConfig
from repro.serve import AsyncServingEngine, Request, SearchExecutor

CFG = SearchConfig(ef=32, k_bucket=10)
FLUSH_SPANS = ("serve.form", "serve.prep", "serve.launch", "serve.wait",
               "serve.fetch", "serve.map", "serve.post")
MAX_BATCH = 4
N_REQ = 14          # three full flushes of 4, then one of 2 after linger


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(5)
    n, d = 256, 12
    idx = RangeGraphIndex.build(
        rng.standard_normal((n, d)).astype(np.float32),
        rng.uniform(0, 100, n),
        BuildConfig(m=8, ef_construction=32, brute_threshold=32),
    )
    reqs = []
    for _ in range(N_REQ):
        lo, hi = sorted(rng.uniform(0, 100, 2))
        reqs.append(Request(rng.standard_normal(d).astype(np.float32),
                            lo, hi, k=5))
    return idx, reqs


def serve(idx, ex, reqs, **serve_kw):
    """Submit every request at once; (answers in order, engine stats)."""
    cfg = ServeConfig(deadline_s=60.0, **serve_kw)

    async def go():
        async with AsyncServingEngine(idx, executor=ex, serve=cfg,
                                      faults=False) as eng:
            got = await asyncio.gather(*(eng.submit(r) for r in reqs))
            return got, eng.stats

    return asyncio.run(go())


def spans(out_dir):
    """``serve.*`` host events of the trace: (name, start_ns, end_ns,
    args), by start."""
    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced(index, tmp_path_factory):
    """One traced session: a fresh executor warms bucket 4 and compiles
    bucket 2 on the last flush's miss."""
    idx, reqs = index
    out_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(out_dir)
    try:
        ex = SearchExecutor(idx, CFG, max_batch=MAX_BATCH, warmup=False)
        ex.warmup(batch_buckets=(MAX_BATCH,), k_buckets=(10,))
        got, stats = serve(idx, ex, reqs)
    finally:
        jax.profiler.stop_trace()
    return ex, got, stats, spans(out_dir)


def test_each_flush_has_one_of_each_span_in_order(traced):
    ex, got, stats, ss = traced
    by_flush = defaultdict(list)
    for name, s, e, args in ss:
        if name in FLUSH_SPANS:
            assert set(args) >= {"flush"}
            by_flush[args["flush"]].append((name, s, e))
    assert stats["flushes"] == 4
    assert sorted(by_flush) == list(range(stats["flushes"]))
    for flush, seq in by_flush.items():
        assert tuple(n for n, _, _ in seq) == FLUSH_SPANS, flush
        # leaves: each phase ends before the next one starts
        for (_, _, end), (_, start, _) in zip(seq, seq[1:]):
            assert end <= start, flush
    rows = [a["rows"] for n, _, _, a in ss if n == "serve.form"]
    assert rows == [4, 4, 4, 2]
    post = [a for n, _, _, a in ss if n == "serve.post"]
    assert [(a["served"], a["late"]) for a in post] == [
        (4, 0), (4, 0), (4, 0), (2, 0)]
    launch = [a for n, _, _, a in ss if n == "serve.launch"]
    assert [(a["bucket"], a["k"]) for a in launch] == [
        (4, 10), (4, 10), (4, 10), (2, 10)]
    # one admission span per request; the reaper's passes are spanned
    assert sum(n == "serve.admit" for n, *_ in ss) == N_REQ
    assert any(n == "serve.reap" and "inflight" in a for n, _, _, a in ss)


def test_one_compile_span_per_program(traced):
    ex, got, stats, ss = traced
    comp = [a for n, _, _, a in ss if n == "serve.compile"]
    assert len(comp) == ex.stats["compiles"] == 2
    assert [(a["bucket"], a["k"], a["warmup"]) for a in comp] == [
        (MAX_BATCH, 10, 1), (2, 10, 0)]


def test_counters_match_a_direct_search(index, traced):
    idx, reqs = index
    ex, got, stats, ss = traced
    hops = rows = 0
    for s in range(0, N_REQ, MAX_BATCH):
        batch = reqs[s: s + MAX_BATCH]
        L, R = idx.ranks_of([r.lo for r in batch], [r.hi for r in batch])
        res = ex.search_ranks(np.stack([r.vector for r in batch]), L, R,
                              k=10)
        hops += int(np.sum(res.n_hops))
        rows += int(np.sum(res.n_dists))
    assert hops > 0 and rows > 0
    assert (stats["nodes_expanded"], stats["rows_gathered"]) == (hops, rows)


def test_answers_identical_with_and_without_the_profiler(index, traced):
    idx, reqs = index
    ex, got, stats, ss = traced
    want, _ = serve(idx, ex, reqs)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.ids, w.ids)
        np.testing.assert_array_equal(g.dists, w.dists)


def test_queue_wait_grows_with_linger(index, traced):
    """One request alone waits out the linger before its flush forms."""
    idx, reqs = index
    ex = traced[0]
    waits = []
    for linger in (0.0, 0.1):
        _, stats = serve(idx, ex, reqs[:1], max_wait_s=linger)
        assert stats["dispatched"] == 1
        waits.append(stats["queue_wait_s"])
    assert 0.0 <= waits[0] < waits[1]
    assert waits[1] >= 0.1
