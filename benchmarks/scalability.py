"""Paper §5.2.3 scalability: build time, memory, and query metrics vs n."""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core import BuildConfig, RangeGraphIndex, SearchConfig, recall
from repro.data.pipeline import vector_dataset
from repro import compile_cache


def run(quick=False):
    rows = []
    sizes = (2048, 4096) if quick else (2048, 8192, 16384)
    for n in sizes:
        vectors, attrs, qv = vector_dataset(n, 64, seed=7, queries=64)
        t0 = time.perf_counter()
        idx = RangeGraphIndex.build(
            vectors, attrs[:, 0], BuildConfig(m=12, ef_construction=48)
        )
        build_s = time.perf_counter() - t0
        wl = common.make_workload(idx, "mixed", n_queries=64)
        m = common.measure(
            lambda q, L, R, k: idx.search_ranks(
                q, L, R, k=k, config=SearchConfig(ef=64)),
            wl, idx,
        )
        rows.append((
            "scalability", f"n{n}", round(build_s, 2),
            round(idx.nbytes / 1e6, 1), round(m["qps"], 1),
            round(m["recall"], 4),
        ))
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    common.emit(run())
