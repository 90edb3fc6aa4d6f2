"""Paper Table 2: memory footprint per method (index + raw vectors),
including the compact-storage encoding (bf16 vectors + narrow neighbor
ids) and the quantized codecs (int8 / PQ vectors + split segment-offset
neighbor ids, ``core/storage.py``, DESIGN.md §9) of the same index."""
from __future__ import annotations

import numpy as np

from benchmarks import common
from repro.core import storage as storage_mod
from repro import compile_cache


def run(quick=False):
    rows = []
    for ds in list(common.BENCH_DATASETS)[: 1 if quick else None]:
        # pin the baseline: under REPRO_STORAGE=compact the default build
        # would already be compact and compact_over_f32 would report ~1.0
        index = common.build_index(ds, storage=storage_mod.StorageConfig())
        raw = index.vectors.nbytes
        elemental = index.neighbors.nbytes
        n, layers, m = index.neighbors.shape
        rows.append(("table2", ds, "raw_vectors_mb", round(raw / 1e6, 2)))
        rows.append((
            "table2", ds, "iRangeGraph_mb",
            round((raw + elemental + index.attrs.nbytes) / 1e6, 2),
        ))
        compact = index.astype_storage(storage_mod.StorageConfig.compact())
        rows.append((
            "table2", ds, "iRangeGraph_compact_mb",
            round(compact.nbytes / 1e6, 2),
        ))
        rows.append((
            "table2", ds, "compact_over_f32",
            round(compact.nbytes / index.nbytes, 3),
        ))
        for tag, st in (("int8", storage_mod.StorageConfig.int8()),
                        ("pq", storage_mod.StorageConfig.pq())):
            qidx = index.astype_storage(st)
            rows.append((
                "table2", ds, f"iRangeGraph_{tag}_mb",
                round(qidx.nbytes / 1e6, 2),
            ))
            rows.append((
                "table2", ds, f"{tag}_over_f32",
                round(qidx.nbytes / index.nbytes, 3),
            ))
        # single flat graph (Milvus/HNSW-style baseline): one layer of edges
        rows.append((
            "table2", ds, "flat_graph_mb",
            round((raw + elemental / layers) / 1e6, 2),
        ))
        # the O(n^2) dedicated-graph strawman the paper argues against
        rows.append((
            "table2", ds, "oracle_all_ranges_gb(theoretical)",
            round(n * n * m * 4 / 2 / 1e9, 1),
        ))
        rows.append(("table2", ds, "layers", layers))
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    common.emit(run())
