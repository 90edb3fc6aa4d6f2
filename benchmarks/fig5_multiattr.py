"""Paper Fig. 5 / §5.2.5: multi-attribute conjunctive RFANN.

Compares the §4 extension modes: post-filtering, in-filtering, and
iRangeGraph+ (visit out-of-range neighbors with p = exp(-t)), plus
Pre-filtering exact. Workload: range fraction ~2^-2 on each attribute."""
from __future__ import annotations

import numpy as np

from benchmarks import common
from repro.core import SearchConfig, multiattr
from repro import compile_cache

EFS = (32, 96)


def run(quick=False):
    rows = []
    ds = list(common.BENCH_DATASETS)[0]
    index = common.build_index(ds)
    n = index.n
    rng = np.random.default_rng(5)
    attr2 = rng.uniform(0, 1.0, n).astype(np.float32)
    B = 48 if quick else 64
    wl = common.make_workload(index, "frac_2", n_queries=B)
    lo2 = rng.uniform(0, 0.5, B).astype(np.float32)
    hi2 = (lo2 + 0.25).astype(np.float32)

    gt, _ = multiattr.brute_force_multiattr(
        index, attr2, wl.queries, wl.L, wl.R, lo2, hi2, k=10
    )
    import time

    from repro.core.index import recall as recall_fn

    for mode, label in (("post", "iRangeGraph-post"),
                        ("in", "iRangeGraph-in"),
                        ("adaptive", "iRangeGraph+")):
        for ef in EFS[:2] if quick else EFS:
            multiattr.search_multiattr(  # warmup/compile
                index, attr2, wl.queries[:8], wl.L[:8], wl.R[:8],
                lo2[:8], hi2[:8], k=10, mode=mode,
                config=SearchConfig(ef=ef),
            )
            t0 = time.perf_counter()
            res = multiattr.search_multiattr(
                index, attr2, wl.queries, wl.L, wl.R, lo2, hi2,
                k=10, mode=mode, config=SearchConfig(ef=ef),
            )
            ids = np.asarray(res.ids)
            dt = time.perf_counter() - t0
            rows.append((
                "fig5", ds, label, ef, round(B / dt, 1),
                round(recall_fn(ids, gt), 4),
            ))
    # Pre-filtering exact
    t0 = time.perf_counter()
    ids, _ = multiattr.brute_force_multiattr(
        index, attr2, wl.queries, wl.L, wl.R, lo2, hi2, k=10
    )
    dt = time.perf_counter() - t0
    rows.append(("fig5", ds, "Pre-filtering", 0, round(B / dt, 1), 1.0))
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    common.emit(run())
