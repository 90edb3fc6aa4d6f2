"""Paper Fig. 4 / §5.2.4: gap to Oracle (dedicated graph built from scratch
per query range). The paper finds Oracle <= 2x faster at 0.9 recall; we
measure qps at matched recall on a mixed workload with a small number of
distinct ranges (as the paper does, to keep Oracle builds feasible)."""
from __future__ import annotations

import numpy as np

from benchmarks import common
from repro.core import SearchConfig, baselines
from repro import compile_cache

EFS = (16, 48, 96)


def run(quick=False):
    rows = []
    ds = list(common.BENCH_DATASETS)[0]
    index = common.build_index(ds)
    rng = np.random.default_rng(4)
    n = index.n
    # 4 distinct ranges, 24 queries each (paper: 10 ranges x 100 queries)
    n_ranges = 2 if quick else 4
    per = 16 if quick else 24
    Ls, Rs = [], []
    for i in range(n_ranges):
        span = max(n >> rng.integers(0, 6), 64)
        lo = int(rng.integers(0, n - span))
        Ls += [lo] * per
        Rs += [lo + span - 1] * per
    wl = common.Workload(
        "oracle-mixed", np.asarray(Ls, np.int32), np.asarray(Rs, np.int32),
        common.make_workload(index, "mixed", n_queries=n_ranges * per).queries,
    )
    cache: dict = {}
    for ef in EFS[:2] if quick else EFS:
        m = common.measure(
            lambda q, L, R, k, _ef=ef: index.search_ranks(
                q, L, R, k=k, config=SearchConfig(ef=_ef)
            ), wl, index,
        )
        rows.append(("fig4", ds, "iRangeGraph", ef,
                     round(m["qps"], 1), round(m["recall"], 4)))
        m = common.measure(
            lambda q, L, R, k, _ef=ef: baselines.oracle_search(
                index, q, L, R, k=k, config=SearchConfig(ef=_ef),
                cache=cache
            ), wl, index,
        )
        rows.append(("fig4", ds, "Oracle", ef,
                     round(m["qps"], 1), round(m["recall"], 4)))
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    common.emit(run())
