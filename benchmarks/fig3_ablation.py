"""Paper Fig. 3 ablation: improvised dedicated graph vs BasicSearch
(segment-decomposition search) and efficient edge selection (skip layers,
iRangeGraph) vs naive (iRangeGraph-)."""
from __future__ import annotations

from benchmarks import common
from repro.core import SearchConfig, baselines
from repro import compile_cache

EFS = (32, 96)


def run(quick=False):
    rows = []
    for ds in list(common.BENCH_DATASETS)[: 1 if quick else 2]:
        index = common.build_index(ds)
        wl = common.make_workload(index, "mixed", n_queries=64)
        for ef in EFS[:2] if quick else EFS:
            m = common.measure(
                lambda q, L, R, k, _ef=ef: index.search_ranks(
                    q, L, R, k=k, config=SearchConfig(ef=_ef)
                ), wl, index,
            )
            rows.append(("fig3", ds, "iRangeGraph", ef,
                         round(m["qps"], 1), round(m["recall"], 4)))
            m = common.measure(
                lambda q, L, R, k, _ef=ef: index.search_ranks(
                    q, L, R, k=k,
                    config=SearchConfig(ef=_ef, skip_layers=False)
                ), wl, index,
            )
            rows.append(("fig3", ds, "iRangeGraph-", ef,
                         round(m["qps"], 1), round(m["recall"], 4)))
            m = common.measure(
                lambda q, L, R, k, _ef=ef: baselines.basic_search(
                    index, q, L, R, k=k, config=SearchConfig(ef=_ef)
                ), wl, index,
            )
            rows.append(("fig3", ds, "BasicSearch", ef,
                         round(m["qps"], 1), round(m["recall"], 4)))
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    common.emit(run())
