"""Per-kernel roofline: bytes moved, FLOPs, achieved fraction of host peaks.

The honesty check behind every claimed kernel speedup
(``artifacts/BENCH_hotpath.json``): for each hot-path kernel this measures
the *production dispatch path* (``kernels/ops.py``, so XLA off-TPU and the
Pallas kernels on TPU) at the benchmark shape, pairs the timing with an
analytic count of bytes moved and arithmetic ops, and reports the achieved
fraction of the roofline bound

    t_bound = max(flops / peak_flops, bytes / peak_bw)

where both peaks are *measured* on this host right before the kernel rows
(a big f32 matmul for FLOPs, an out-of-cache elementwise stream for
bandwidth) — no datasheet numbers. ``bottleneck`` says which side of the
roofline the kernel sits on at its arithmetic intensity. For the integer
kernels (edge-select, the hop's dedup/bitset phases) "flops" counts
compare/select VPU ops — the units still cancel in the fraction. A
fraction above 1.0 means the working set stayed cache-resident (the
bandwidth peak is measured out-of-cache), not a broken clock.

Emits ``artifacts/BENCH_roofline.json`` (``BENCH_roofline_smoke.json``
under ``--smoke``) plus the historical CSV rows on stdout.

``--strict`` makes every degraded outcome a non-zero exit: a kernel row
that errored, a non-finite measurement, or (with ``--with-dryrun``)
missing dry-run artifacts. The seed version of this file silently emitted
a placeholder row when artifacts were missing, so a CI perf-gate could
"pass" on an empty roofline; ``--strict`` exists so it can't. The
distributed dry-run table is still available behind ``--with-dryrun``
(reads ``artifacts/dryrun_all.jsonl`` / ``dryrun_paper.jsonl`` produced by
``python -m repro.launch.dryrun --all --both-meshes``).

Usage: ``PYTHONPATH=src python benchmarks/roofline.py [--smoke] [--strict]
[--with-dryrun] [--b 64] [--n 100000] [--d 128] [--m 16] [--iters 20]``
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

import common
from repro.core import bitset
from repro.kernels import ops
from repro import compile_cache


def _best_s(fn, *args, iters=10):
    """Min seconds per call, post-compile (min, not mean: roofline compares
    against a peak, so the least-disturbed iteration is the right sample)."""
    import time

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def measure_peaks(iters=10):
    """Measured host peaks: f32 matmul FLOP/s and out-of-cache stream GB/s."""
    k = 1024
    a = jnp.ones((k, k), jnp.float32)
    b = jnp.ones((k, k), jnp.float32)
    t = _best_s(jax.jit(lambda a, b: a @ b), a, b, iters=iters)
    peak_flops = 2.0 * k ** 3 / t
    # 128 MiB stream: far past any cache, reads + writes both count
    x = jnp.ones((32 * 1024 * 1024,), jnp.float32)
    t = _best_s(jax.jit(lambda x: x * 1.5 + 0.5), x, iters=iters)
    peak_bw = 2.0 * x.nbytes / t
    return {
        "peak_gflops": peak_flops / 1e9,
        "peak_gbps": peak_bw / 1e9,
        "ridge_intensity_flop_per_byte": peak_flops / peak_bw,
    }


def _mk_problem(B, n, d, M, seed=11):
    """One shared problem at the hotpath benchmark shape."""
    from hotpath import _elemental_table

    rng = np.random.default_rng(seed)
    W, m_out = 4, M
    logn = max(int(np.ceil(np.log2(max(n, 2)))), 1)
    return {
        "B": B, "n": n, "d": d, "M": M, "W": W, "m_out": m_out,
        "logn": logn, "K": (logn + 1) * M,
        "q": jnp.asarray(rng.standard_normal((B, d)), jnp.float32),
        "table": jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
        "nbrs": jnp.asarray(_elemental_table(rng, n, M, logn)),
        "u": jnp.asarray(rng.integers(0, n, (B, W)).astype(np.int32)),
        "L": jnp.asarray(rng.integers(0, n // 2, B * W).astype(np.int32)),
        "gids": jnp.asarray(
            rng.integers(-1, n, (B, W * m_out)).astype(np.int32)),
        "cand_ids": jnp.asarray(
            rng.integers(0, n, (B, 4 * M)).astype(np.int32)),
        "cand_dists": jnp.asarray(rng.random((B, 4 * M)), jnp.float32),
    }


def _kernel_rows(p, iters):
    """(name, run_fn, flops, bytes) per hot-path kernel.

    Byte counts assume every table access misses cache (the tables are the
    benchmark's n-row working set); flops count multiply-adds as 2 and, for
    the integer kernels, compare/select ops as 1 each — coarse by design,
    the fraction is a sanity bound, not a cycle model.
    """
    B, n, d = p["B"], p["n"], p["d"]
    W, m_out, K, logn = p["W"], p["m_out"], p["K"], p["logn"]
    F, WM = B * W, W * m_out
    C = p["cand_ids"].shape[1]
    words = bitset.num_words(n)
    q, table, nbrs = p["q"], p["table"], p["nbrs"]
    u, L, gids = p["u"], p["L"], p["gids"]
    R = L + n // 2 - 1
    vis = bitset.make(B, n)
    exp_ok = jnp.ones((B, W), bool)

    # integer op estimates shared by edge_select and the hop's select phase
    scan_ops = 12 * F * K              # validity: bounds + layer-mask tests
    dedup_ops = 4 * F * K * m_out      # m_out masked-argmin + wipe sweeps

    return [
        (
            "pairwise_dist",
            jax.jit(lambda: ops.pairwise_dist(q, table)),
            2 * B * n * d + 3 * B * n,
            4 * (B * d + n * d + B * n),
        ),
        (
            "gather_dist",
            jax.jit(lambda: ops.gather_dist(q, table, gids)),
            2 * B * WM * d + 3 * B * WM,
            4 * (B * d + B * WM * d + 2 * B * WM),
        ),
        (
            "edge_select",
            jax.jit(lambda: ops.select_edges(
                nbrs, u.reshape(F), L, R, logn=logn, m_out=m_out)),
            scan_ops + dedup_ops,
            4 * (F * K + 3 * F + F * m_out),
        ),
        (
            "hop",
            jax.jit(lambda: ops.hop(
                q, table, nbrs, u, L, R, vis, exp_ok,
                logn=logn, m_out=m_out)),
            scan_ops + dedup_ops + 2 * B * WM * d + 13 * B * WM,
            4 * (F * K + B * WM * d + 2 * B * words + B * d + 3 * B * WM),
        ),
        (
            "prune",
            jax.jit(lambda: ops.prune(
                p["cand_ids"], p["cand_dists"], table, m=p["M"])),
            B * (2 * p["M"] * C * d + 8 * p["M"] * C + 3 * C * C),
            4 * (B * C * d + 2 * B * C + B * p["M"]),
        ),
    ]


def run_kernels(p, peaks, iters):
    rows = []
    pf = peaks["peak_gflops"] * 1e9
    pb = peaks["peak_gbps"] * 1e9
    for name, fn, flops, nbytes in _kernel_rows(p, iters):
        row = {"kernel": name, "flops": int(flops), "bytes": int(nbytes),
               "intensity_flop_per_byte": flops / nbytes}
        try:
            t = _best_s(fn, iters=iters)
        except Exception as e:  # a backend that can't run this op
            row["error"] = f"{type(e).__name__}: {e}"
            rows.append(row)
            continue
        t_bound = max(flops / pf, nbytes / pb)
        row.update({
            "time_us": t * 1e6,
            "achieved_gflops": flops / t / 1e9,
            "achieved_gbps": nbytes / t / 1e9,
            "bound_us": t_bound * 1e6,
            "achieved_fraction": t_bound / t,
            "bottleneck": (
                "compute" if flops / pf >= nbytes / pb else "memory"
            ),
        })
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# legacy distributed dry-run table (--with-dryrun)
# ---------------------------------------------------------------------------

def load(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(l) for l in f if l.strip()]


def run_dryrun():
    """Rows from the 512-device dry-run artifacts; [] when absent (the
    caller decides whether that is fatal — see ``--strict``)."""
    rows = []
    art = common.artifacts_dir()
    recs = load(os.path.join(art, "dryrun_all.jsonl")) + load(
        os.path.join(art, "dryrun_paper.jsonl")
    )
    for r in recs:
        if r.get("mesh") != "16x16":
            continue
        if r.get("status") == "skipped":
            rows.append(("dryrun", r["arch"], r["shape"], "skipped",
                         r["reason"][:40], "", "", "", ""))
            continue
        if r.get("status") != "ok" or "t_compute" not in r:
            rows.append(("dryrun", r.get("arch"), r.get("shape"),
                         r.get("status"), r.get("error", "")[:40],
                         "", "", "", ""))
            continue
        rows.append((
            "dryrun", r["arch"], r["shape"], r["bottleneck"],
            f"{r['t_compute']:.3e}", f"{r['t_memory']:.3e}",
            f"{r['t_collective']:.3e}",
            f"{r.get('useful_flop_frac') or 0:.3f}",
            r.get("bytes_per_device", ""),
        ))
    return rows


def _csv_rows(rows, failures):
    """Kernel dict rows -> historical CSV tuples, collecting failures."""
    csv = []
    for r in rows:
        if "error" in r:
            failures.append(f"kernel {r['kernel']} errored: {r['error']}")
            csv.append(("roofline", r["kernel"], "error", r["error"][:60],
                        "", "", "", "", ""))
            continue
        if not math.isfinite(r["achieved_fraction"]):
            failures.append(
                f"kernel {r['kernel']} non-finite achieved_fraction")
        csv.append((
            "roofline", r["kernel"], r["bottleneck"],
            f"{r['time_us']:.1f}us", f"{r['flops']:.3e}",
            f"{r['bytes']:.3e}",
            f"{r['intensity_flop_per_byte']:.2f}",
            f"{r['achieved_gbps']:.2f}GB/s",
            f"{r['achieved_fraction']:.3f}",
        ))
    return csv


def run(quick=False):
    """Aggregator entry (``benchmarks/run.py``): kernel roofline rows, plus
    the dry-run table when its artifacts exist (placeholder row when not —
    the standalone CLI's ``--strict`` is where that becomes fatal)."""
    peaks = measure_peaks(iters=3)
    p = _mk_problem(8, 4096, 32, 8) if quick \
        else _mk_problem(64, 100_000, 128, 16)
    failures: list[str] = []
    csv = _csv_rows(run_kernels(p, peaks, 3 if quick else 10), failures)
    dr = run_dryrun()
    if dr:
        csv.extend(dr)
    else:
        csv.append(("dryrun", "no-dryrun-artifacts",
                    "run python -m repro.launch.dryrun --all first",
                    "", "", "", "", "", ""))
    return csv


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--b", type=int, default=64)
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--m", type=int, default=16)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few iters; writes the _smoke "
                         "artifact (numbers are a schema probe, not a "
                         "measurement)")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero on any errored/placeholder row "
                         "(so a perf-gate cannot pass on an empty or "
                         "broken roofline)")
    ap.add_argument("--with-dryrun", action="store_true",
                    help="append the distributed dry-run table (requires "
                         "the dryrun artifacts)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.b, args.n, args.d, args.m = 8, 4096, 32, 8
        args.iters = 3

    failures = []
    peaks = measure_peaks(iters=max(3, args.iters // 2))
    print(f"host peaks: {peaks['peak_gflops']:.1f} GFLOP/s  "
          f"{peaks['peak_gbps']:.1f} GB/s  "
          f"(ridge {peaks['ridge_intensity_flop_per_byte']:.1f} flop/B)")
    if not all(math.isfinite(v) and v > 0 for v in peaks.values()):
        failures.append(f"non-finite host peaks: {peaks}")

    p = _mk_problem(args.b, args.n, args.d, args.m)
    rows = run_kernels(p, peaks, args.iters)
    csv = _csv_rows(rows, failures)

    dryrun_rows = None
    if args.with_dryrun:
        dryrun_rows = run_dryrun()
        if not dryrun_rows:
            failures.append(
                "dry-run artifacts missing (run python -m "
                "repro.launch.dryrun --all --both-meshes first)")
            csv.append(("dryrun", "no-dryrun-artifacts",
                        "run python -m repro.launch.dryrun --all first",
                        "", "", "", "", "", ""))
        else:
            csv.extend(dryrun_rows)

    common.emit(csv)

    payload = {
        "host": {
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "smoke": args.smoke,
        },
        "config": {"B": args.b, "n": args.n, "d": args.d, "M": args.m,
                   "iters": args.iters},
        "peaks": peaks,
        "kernels": rows,
    }
    if dryrun_rows is not None:
        payload["dryrun"] = [list(r) for r in dryrun_rows]
    name = "BENCH_roofline_smoke.json" if args.smoke \
        else "BENCH_roofline.json"
    out = os.path.join(common.artifacts_dir(), name)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print("wrote", out)

    if failures:
        for msg in failures:
            print(f"roofline: {msg}", file=sys.stderr)
        if args.strict:
            print(f"roofline: FAIL ({len(failures)} degraded rows, "
                  "--strict)", file=sys.stderr)
            return 1
        print(f"roofline: {len(failures)} degraded rows (pass --strict "
              "to fail on these)")
    return 0


if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main())
