"""Paper Table 3: indexing time — iRangeGraph's bottom-up build vs a
from-scratch flat graph (HNSW stand-in) and the paper's <=3x claim."""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core import BuildConfig, build_flat_graph, build_neighbor_table
from repro.data.pipeline import vector_dataset
from repro import compile_cache


def run(quick=False):
    rows = []
    n, dim = (4096, 64) if quick else (8192, 64)
    vectors, attrs, _ = vector_dataset(n, dim, seed=3)
    order = np.argsort(attrs[:, 0], kind="stable")
    vs = vectors[order]
    cfg = BuildConfig(m=12, ef_construction=48)

    t0 = time.perf_counter()
    build_neighbor_table(vs, cfg)
    t_full = time.perf_counter() - t0

    t0 = time.perf_counter()
    build_flat_graph(vs, cfg)  # root graph only == single-HNSW stand-in
    t_flat = time.perf_counter() - t0

    rows.append(("table3", f"n{n}", "iRangeGraph_s", round(t_full, 2)))
    rows.append(("table3", f"n{n}", "flat_graph_s", round(t_flat, 2)))
    rows.append((
        "table3", f"n{n}", "ratio_vs_single_graph",
        round(t_full / max(t_flat, 1e-9), 2),
    ))
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    common.emit(run())
