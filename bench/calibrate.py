"""Readings the limits and the open loop's rate are set from (PERF.md).

    python3 bench/calibrate.py --workload <cell> --seconds <s>
        [--seeds 1,2,...] [--control-seeds 7,8,9] [--rates 400,600,...]

One process, one set-up, then windows of the cell's own traffic:

* ``--seeds``: the program as the cell runs it, one window per seed; each
  prints the numbers compared (the lower readings of their limits) and the
  end-to-end metrics;
* ``--control-seeds``: the control, the program with its own bfloat16
  vector storage switched on (``StorageConfig(vector_dtype="bfloat16")``
  over the same graph), one window per seed: the upper readings;
* ``--rates``: the open-loop sweep for the knee, one window per offered
  rate (requests per second), with the cell's other traffic unchanged.

Each window prints one JSON line. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

import numpy as np

import data
import reference
import run as bench
import traffic as traffic_mod


def window(index, executor, cell, seed, seconds, coll, **extra):
    reqs, due = bench.make_requests(cell.cfg, cell.traffic, seed, seconds,
                                    coll.sorted_attr, data.centres(cell.cfg))
    r = bench.run_window(index, executor, cell, reqs, due, seconds)
    bench.evaluate(r, coll, reqs, seed)
    lg = r.log
    ok = lg.outcome == traffic_mod.OK
    due_in = lg.t_due <= r.t_end
    lat = (lg.t_done - lg.t_due)[ok & due_in]
    thirds = np.array_split(lat, 3) if len(lat) >= 3 else [lat] * 3
    out = {
        **extra, "seed": seed,
        "checks": {k: c["value"] for k, c in r.checks.items()
                   if not k.startswith("_")},
        "qps": bench.reader("qps")(r),
        "p99_ms": bench.reader("p99_ms")(r),
        "p50_ms": float(np.median(lat) * 1e3) if len(lat) else None,
        "met_share": float(ok[due_in].mean()) if due_in.any() else None,
        "median_ms_by_third": [float(np.median(t) * 1e3) if len(t) else None
                               for t in thirds],
        "batch_mean": r.window_stats["dispatched"] / max(
            r.window_stats["flushes"], 1),
        "compiles_in_window": r.compiles_in_window,
        "lateness_ms": r.lateness_ms,
        "gc": [r.gc_pauses.count, r.gc_pauses.total_s, r.gc_pauses.max_s],
    }
    print(json.dumps(out), flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctrl = [int(s) for s in args.control_seeds.split(",") if s]
    rates = [float(s) for s in args.rates.split(",") if s]

    spec = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    cell = bench.find_cell(spec, args.workload)
    cfg_file = next(c["file"] for c in spec["configs"]
                    if c["name"] == cell.cfg["name"])
    built = bench.ensure_index(os.path.join(bench.ROOT, cfg_file),
                               cell.cfg, True)
    if built is not None:
        print(f"index: built and saved in {built:.1f} s", flush=True)
    bench.require_chip(cell.workload["chips"])
    bench.enable_compile_cache()
    from repro.core import StorageConfig

    index, _ = bench.load_index(cell.cfg)
    coll = reference.Collection(*data.collection(cell.cfg))
    warm, _ = bench.make_requests(cell.cfg, cell.traffic, 0, 1.0,
                                  coll.sorted_attr, data.centres(cell.cfg))
    executor = bench.make_executor(index, cell.cfg, cell.traffic, warm)
    for s in seeds:
        window(index, executor, cell, s, args.seconds, coll, side="program")
    for rate in rates:
        swept = copy.copy(cell)
        swept.traffic = copy.deepcopy(cell.traffic)
        swept.traffic["arrival"]["rate"] = rate
        window(index, executor, swept, 1000 + int(rate), args.seconds, coll,
               side="sweep", rate=rate)
    executor.close()
    if ctrl:
        low = index.astype_storage(StorageConfig(vector_dtype="bfloat16",
                                                 neighbor_dtype="int32"))
        executor = bench.make_executor(low, cell.cfg, cell.traffic, warm)
        for s in ctrl:
            window(low, executor, cell, s, args.seconds, coll,
                   side="control")
        executor.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
