"""Benchmark aggregator: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick]`` runs everything and
prints CSV rows (``table,dataset,...``). Individual modules run standalone:
``python -m benchmarks.fig2_qps_recall`` etc. The roofline module reads the
dry-run artifacts (produce them with ``python -m repro.launch.dryrun --all
--both-meshes --out artifacts/dryrun_all.jsonl``).
"""
from __future__ import annotations

import argparse
import sys
import time

from repro import compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sweeps (CI-sized)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names")
    args = ap.parse_args()

    from benchmarks import (
        common,
        fig2_qps_recall,
        fig3_ablation,
        fig4_oracle,
        fig5_multiattr,
        roofline,
        scalability,
        table2_memory,
        table3_indexing,
    )

    modules = {
        "fig2": fig2_qps_recall,
        "table2": table2_memory,
        "table3": table3_indexing,
        "fig3": fig3_ablation,
        "fig4": fig4_oracle,
        "fig5": fig5_multiattr,
        "scalability": scalability,
        "roofline": roofline,
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    print("table,col1,col2,col3,col4,col5,col6,col7,col8")
    for name, mod in modules.items():
        t0 = time.time()
        try:
            rows = mod.run(quick=args.quick)
        except Exception as e:  # noqa: BLE001 — keep the suite going
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            continue
        common.emit(rows)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    compile_cache.enable()
    main()
