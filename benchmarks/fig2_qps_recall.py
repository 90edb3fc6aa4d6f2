"""Paper Fig. 2: qps-recall across methods x datasets x workloads.

Sweeps the beam size (ef) for every graph-based method; Pre-filtering is the
exact scan. Emits CSV rows:
  fig2,<dataset>,<workload>,<method>,<ef>,<qps>,<recall>,<mean_dists>
"""
from __future__ import annotations

import functools

import numpy as np

from benchmarks import common
from repro.core import SearchConfig, baselines
from repro import compile_cache

EFS = (16, 48, 96)
WORKLOADS = ("frac_2", "frac_8", "mixed")


def _methods(index):
    def irange(q, L, R, k, ef):
        return index.search_ranks(q, L, R, k=k, config=SearchConfig(ef=ef))

    def pre(q, L, R, k, ef):
        return baselines.prefilter(index, q, L, R, k=k)

    return {
        "iRangeGraph": irange,
        "Pre-filtering": pre,
        "Post-filtering": functools.partial(_wrap, baselines.postfilter,
                                            index),
        "In-filtering": functools.partial(_wrap, baselines.infilter, index),
        "SuperPost": functools.partial(_wrap, baselines.super_postfilter,
                                       index),
    }


def _wrap(fn, index, q, L, R, k, ef):
    return fn(index, q, L, R, k=k, config=SearchConfig(ef=ef))


def run(quick=False, n_queries=64):
    rows = []
    datasets = list(common.BENCH_DATASETS)[:2]
    if quick:
        datasets = datasets[:1]
    for ds in datasets:
        index = common.build_index(ds)
        for wl_kind in (WORKLOADS[:2] if quick else WORKLOADS):
            wl = common.make_workload(index, wl_kind, n_queries=n_queries)
            for name, fn in _methods(index).items():
                efs = (64,) if name == "Pre-filtering" else (
                    EFS[:2] if quick else EFS
                )
                for ef in efs:
                    m = common.measure(
                        lambda q, L, R, k, _ef=ef, _fn=fn: _fn(
                            q, L, R, k, _ef
                        ),
                        wl, index,
                    )
                    rows.append((
                        "fig2", ds, wl_kind, name, ef,
                        round(m["qps"], 1), round(m["recall"], 4),
                        round(m["mean_dists"], 1),
                    ))
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    common.emit(run())
