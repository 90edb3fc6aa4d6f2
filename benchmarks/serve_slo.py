"""Poisson load-generator SLO benchmark for the async serving loop.

Drives ``serve/loop.py::AsyncServingEngine`` with open-loop Poisson
arrivals (exponential inter-arrival gaps — requests keep arriving whether
or not the server keeps up, unlike a closed benchmark loop) and records
the latency/outcome distribution per leg, emitted to
``artifacts/BENCH_serve_slo.json``:

  * ``nominal``  — target QPS at ~half the measured full-batch capacity:
    the steady-state SLO numbers (p50/p99 of served requests).
  * ``overload`` — ~4x capacity against the bounded queue: admission
    control and deadline shedding take over; the interesting numbers are
    the shed/timeout/reject rates and that p99 of what IS served stays
    bounded (that is the whole point of deadline-aware serving).
  * ``chaos``    — overload plus fault injection (``serve/faults.py``:
    latency spikes, flush errors, queue-full bursts): the soak proof that
    every request still resolves with exactly one terminal outcome.

Every leg hard-records ``offered == resolved`` (no lost or stuck
requests) and the executor's post-warmup compile count (0 — the loop
serves entirely from the AOT-warmed grid). ``benchmarks/ci_gate.py``
hard-fails on either, and soft-warns on nominal-p99 / overload-shed-rate
drift against the committed ``smoke_ref``.

Usage: ``PYTHONPATH=src python benchmarks/serve_slo.py [--smoke]
[--update-smoke-ref] [--duration 4.0] [--max-batch 32]``
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax

from common import DEFAULT_K, artifacts_dir, build_index, carry_smoke_ref, \
    make_workload, time_it, update_smoke_ref
from repro.core import SearchConfig, ServeConfig
from repro.serve import AsyncServingEngine, DeadlineExceededError, \
    FaultConfig, OverloadedError, Request, SearchExecutor, ShedError, \
    ShutdownError
from repro import compile_cache

OUTCOMES = ("ok", "rejected", "shed", "timeout", "shutdown", "failed")


def measure_capacity(executor, wl, k, iters=5) -> float:
    """Queries/sec of a warmed full-batch flush — the denominator the
    nominal/overload QPS targets scale from, so the legs stress the same
    relative load on any host."""
    B = executor.max_batch
    q, L, R = wl.queries[:B], wl.L[:B], wl.R[:B]
    t = time_it(lambda: executor.search_ranks(q, L, R, k=k), iters=iters)
    return B / t


async def run_leg(index, executor, wl, *, qps, duration_s, serve_cfg,
                  faults, k, seed):
    """One open-loop Poisson leg; returns outcome counts + percentiles."""
    eng = AsyncServingEngine(
        index, serve=serve_cfg, executor=executor, faults=faults
    )
    rng = np.random.default_rng(seed)
    nq = len(wl.queries)
    # value-space bounds for the workload's rank ranges
    lo = index.attrs[wl.L]
    hi = index.attrs[wl.R]
    arrivals = []
    t = 0.0
    while True:
        t += rng.exponential(1.0 / qps)
        if t >= duration_s:
            break
        arrivals.append(t)
    outcomes: list[tuple[str, float]] = []

    async def fire(j, delay):
        await asyncio.sleep(delay)
        i = j % nq
        t0 = time.monotonic()
        try:
            await eng.submit(Request(wl.queries[i], lo[i], hi[i], k=k))
            kind = "ok"
        except OverloadedError:
            kind = "rejected"
        except ShedError:
            kind = "shed"
        except DeadlineExceededError:
            kind = "timeout"
        except ShutdownError:
            kind = "shutdown"
        except Exception:  # noqa: BLE001 — typed flush failures
            kind = "failed"
        outcomes.append((kind, time.monotonic() - t0))

    t_start = time.monotonic()
    await asyncio.gather(*(
        asyncio.create_task(fire(j, a)) for j, a in enumerate(arrivals)
    ))
    await eng.aclose(drain=True)
    wall = time.monotonic() - t_start
    counts = Counter(kind for kind, _ in outcomes)
    ok_lat = np.array([l for kind, l in outcomes if kind == "ok"])
    offered = len(arrivals)
    out = {
        "target_qps": float(qps),
        "duration_s": float(duration_s),
        "offered": offered,
        "resolved": len(outcomes),
        "lost": offered - len(outcomes),   # ci_gate hard-fails != 0
        **{kind: int(counts.get(kind, 0)) for kind in OUTCOMES},
        "shed_rate": counts.get("shed", 0) / max(offered, 1),
        "timeout_rate": counts.get("timeout", 0) / max(offered, 1),
        "reject_rate": counts.get("rejected", 0) / max(offered, 1),
        "achieved_qps": counts.get("ok", 0) / max(wall, 1e-9),
        "p50_ms": float(np.percentile(ok_lat, 50) * 1e3) if len(ok_lat)
        else None,
        "p99_ms": float(np.percentile(ok_lat, 99) * 1e3) if len(ok_lat)
        else None,
        "engine": {kk: v for kk, v in eng.stats.items()
                   if isinstance(v, int)},
    }
    if eng.faults is not None:
        out["injected"] = dict(eng.faults.counts)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="ytaudio-like")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--duration", type=float, default=4.0)
    ap.add_argument("--deadline", type=float, default=0.25)
    ap.add_argument("--smoke", action="store_true",
                    help="short legs on a small index: a CI regression "
                         "probe for the serving loop, not a measurement")
    ap.add_argument("--update-smoke-ref", action="store_true",
                    help="with --smoke: record this run's p99/shed-rate as "
                         "the committed BENCH_serve_slo.json smoke_ref")
    args = ap.parse_args(argv)
    if args.smoke:
        args.duration = 1.0
        args.max_batch = 16

    index = build_index(args.dataset)
    cfg = SearchConfig(ef=64, k_bucket=DEFAULT_K)
    executor = SearchExecutor(index, cfg, max_batch=args.max_batch,
                              warmup=False)
    # warm exactly the grid the legs use: every batch bucket at one k
    warmed = executor.warmup(k_buckets=(DEFAULT_K,))
    wl = make_workload(index, "mixed", n_queries=256)
    cap = measure_capacity(executor, wl, DEFAULT_K)
    print(f"capacity ~{cap:.0f} qps (max_batch={args.max_batch}, "
          f"{warmed} programs warmed)")

    # size the queue off measured capacity so that at overload the back of
    # the queue waits ~2x the shed threshold: the shed path (not just
    # admission rejects) is exercised regardless of host speed
    margin = args.deadline / 5
    max_queue = max(4 * args.max_batch,
                    int(2 * cap * (args.deadline - margin)))
    serve_cfg = ServeConfig(
        deadline_s=args.deadline, max_queue=max_queue,
        backpressure="reject", max_wait_s=0.01,
        deadline_margin_s=margin,
    )
    legs = {}
    legs["nominal"] = asyncio.run(run_leg(
        index, executor, wl, qps=0.5 * cap, duration_s=args.duration,
        serve_cfg=serve_cfg, faults=False, k=DEFAULT_K, seed=1,
    ))
    legs["overload"] = asyncio.run(run_leg(
        index, executor, wl, qps=4.0 * cap, duration_s=args.duration,
        serve_cfg=serve_cfg, faults=False, k=DEFAULT_K, seed=2,
    ))
    chaos_faults = FaultConfig(
        kinds=("latency", "flush_error", "queue_full"),
        latency_s=2 * args.deadline, latency_rate=0.1,
        flush_error_rate=0.1, queue_full_rate=0.1, seed=7,
    )
    legs["chaos"] = asyncio.run(run_leg(
        index, executor, wl, qps=4.0 * cap, duration_s=args.duration,
        serve_cfg=serve_cfg, faults=chaos_faults, k=DEFAULT_K, seed=3,
    ))
    for name, leg in legs.items():
        p50 = f"{leg['p50_ms']:.1f}" if leg["p50_ms"] is not None else "-"
        p99 = f"{leg['p99_ms']:.1f}" if leg["p99_ms"] is not None else "-"
        print(
            f"{name}: target {leg['target_qps']:.0f} qps, offered "
            f"{leg['offered']}, ok {leg['ok']} (p50 {p50}ms p99 {p99}ms), "
            f"shed {leg['shed']}, timeout {leg['timeout']}, rejected "
            f"{leg['rejected']}, failed {leg['failed']}, lost {leg['lost']}"
        )

    post_warmup = executor.stats["compiles"] - executor.stats[
        "warmup_compiles"]
    print(f"executor: {executor.stats['compiles']} programs, "
          f"{post_warmup} post-warmup")
    payload = {
        "host": {
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "smoke": args.smoke,
        },
        "config": {
            "dataset": args.dataset, "max_batch": args.max_batch,
            "duration_s": args.duration, "k": DEFAULT_K,
            "deadline_s": serve_cfg.deadline_s,
            "max_queue": serve_cfg.max_queue,
            "backpressure": serve_cfg.backpressure,
            "max_wait_s": serve_cfg.max_wait_s,
            "deadline_margin_s": serve_cfg.deadline_margin_s,
        },
        "capacity_qps": float(cap),
        **legs,
        "serve": {
            "compiles": int(executor.stats["compiles"]),
            "warmup_compiles": int(executor.stats["warmup_compiles"]),
            "post_warmup_compiles": int(post_warmup),
        },
    }
    committed = os.path.join(artifacts_dir(), "BENCH_serve_slo.json")
    if args.smoke:
        out = os.path.join(artifacts_dir(), "BENCH_serve_slo_smoke.json")
        if args.update_smoke_ref:
            refs = {
                "nominal.p99_ms": legs["nominal"]["p99_ms"],
                "overload.shed_rate": legs["overload"]["shed_rate"],
            }
            if update_smoke_ref(committed, refs):
                print("updated smoke_ref in", committed)
            else:
                print("no committed record to update:", committed)
    else:
        out = committed
        payload = carry_smoke_ref(payload, committed)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print("wrote", out)


if __name__ == "__main__":
    compile_cache.enable()
    main()
