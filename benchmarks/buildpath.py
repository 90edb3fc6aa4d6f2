"""Build-path microbenchmark: fused vs legacy construction prune.

The build-side analog of ``hotpath.py``. Two measurements, emitted to
``artifacts/BENCH_build.json``:

  * ``prune_step`` — one batched RNG prune per representative level shape
    (search levels C = m + ef_construction, brute levels
    C = brute_threshold, reverse pass C = 3m), swept over chunk sizes and
    backends: the legacy eager path (XLA gather + full [C, C]
    candidate-candidate matrix + C-step scan, ``core/rng.py``) against the
    fused lazy-column one (``ops.prune`` — ``kernels/ref.py::prune`` off-TPU,
    the Pallas construction-prune kernel on TPU; pass ``--interpret`` to
    force the kernel through the interpreter, orders of magnitude slower,
    only useful as a smoke test). Backends are asserted bit-identical
    before timing; ``parity`` records it for the CI bench-gate.
  * ``build_levels`` — end-to-end ``build_neighbor_table`` per prune
    backend under the production default ``chunk=None`` (the C*d-bytes
    auto-tuner, ``core/build.py::auto_chunk``), recording nodes/sec AND the
    auto-chosen chunk per level (the ``level_times`` hook), so the
    whole-build win, its per-level breakdown, and the tuner's choices get
    the same perf record the hop side has.

Usage: ``PYTHONPATH=src python benchmarks/buildpath.py [--n 32768]
[--d 64] [--m 16] [--efc 64] [--iters 8] [--chunks 512,2048,4096]
[--no-e2e] [--interpret] [--smoke]``

``--smoke`` shrinks every shape and iteration count to a seconds-long CI
pass that still exercises both measurements (shape or parity regressions in
the build path fail loudly, numbers are meaningless).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from common import artifacts_dir, carry_smoke_ref, time_it, update_smoke_ref
from repro.core import build as build_mod
from repro.core import knobs as knobs_mod
from repro.kernels import ops
from repro import compile_cache


def _prune_case(rng, table_np, chunk, C, d):
    """Synthetic but build-shaped candidate lists: ~10% invalid slots, one
    duplicated slot per row, distances computed the way build.py does."""
    n = table_np.shape[0]
    ids = rng.integers(0, n, (chunk, C)).astype(np.int32)
    ids[:, -1] = ids[:, 0]                       # duplicates exercise dedup
    ids = np.where(rng.random((chunk, C)) < 0.1, -1, ids).astype(np.int32)
    u = rng.standard_normal((chunk, d)).astype(np.float32)
    cvec = table_np[np.maximum(ids, 0)]
    du = ((cvec - u[:, None, :]) ** 2).sum(-1).astype(np.float32)
    du = np.where(ids < 0, np.inf, du)
    return jnp.asarray(ids), jnp.asarray(du), jnp.asarray(cvec)


def bench_prune_step(n, d, m, efc, brute_threshold, chunks, iters,
                     fused_impl):
    """Per level shape x chunk size: legacy vs fused prune throughput."""
    rng = np.random.default_rng(0)
    table_np = rng.standard_normal((n, d)).astype(np.float32)
    table = jnp.asarray(table_np)
    shapes = [
        ("search", m + efc),
        ("brute", brute_threshold),
        ("reverse", 3 * m),
    ]
    rows = []
    parity = True
    for kind, C in shapes:
        for chunk in chunks:
            ids, du, cvec = _prune_case(rng, table_np, chunk, C, d)

            # the build loop hands the jnp paths its already-gathered
            # candidate vectors; the Pallas path ignores them and DMAs
            # from the table — time the calls the way the build makes them
            def step(ids, du, impl):
                return ops.prune(
                    ids, du, table, m=m, alpha=1.0, fill=True, impl=impl,
                    cand_vecs=cvec,
                )

            # backends must agree before we time them
            want = np.asarray(step(ids, du, "legacy"))
            got = np.asarray(step(ids, du, fused_impl))
            if not np.array_equal(want, got):
                parity = False

            legacy_s = time_it(step, ids, du, "legacy", iters=iters)
            fused_s = time_it(step, ids, du, fused_impl, iters=iters)
            rows.append({
                "kind": kind, "C": int(C), "m": int(m), "d": int(d),
                "chunk": int(chunk), "fused_impl": fused_impl,
                "legacy_us": legacy_s * 1e6, "fused_us": fused_s * 1e6,
                "legacy_nodes_per_s": chunk / legacy_s,
                "fused_nodes_per_s": chunk / fused_s,
                "speedup": legacy_s / fused_s,
            })
    return rows, parity


def bench_build_levels(n, d, m, efc, brute_threshold, fused_impl):
    """End-to-end build per prune backend with per-level nodes/sec.

    Runs ``chunk=None``: each level's prune chunk comes from the C*d-bytes
    auto-tuner and lands in the per-level record (``chunk`` /
    ``chunk_reverse`` keys)."""
    rng = np.random.default_rng(1)
    vectors = rng.standard_normal((n, d)).astype(np.float32)
    out = {}
    tables = {}
    for impl in ("legacy", fused_impl):
        cfg = build_mod.BuildConfig(
            m=m, ef_construction=efc, brute_threshold=brute_threshold,
            prune_impl=impl,
        )
        build_mod.build_neighbor_table(vectors, cfg)  # compile outside timing
        times: list = []
        t0 = time.perf_counter()
        tables[impl] = build_mod.build_neighbor_table(
            vectors, cfg, level_times=times
        )
        total = time.perf_counter() - t0
        out[impl] = {
            "total_s": total,
            "nodes_per_s": n / total,
            "levels": [
                {**lt, "nodes_per_s": n / max(lt["seconds"], 1e-9)}
                for lt in times
            ],
        }
    parity = bool(np.array_equal(tables["legacy"], tables[fused_impl]))
    speedup = out["legacy"]["total_s"] / out[fused_impl]["total_s"]
    return out, parity, speedup


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32_768)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--m", type=int, default=16)
    ap.add_argument("--efc", type=int, default=64)
    ap.add_argument("--brute-threshold", type=int, default=128)
    ap.add_argument("--chunks", type=str, default="512,2048,4096")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--e2e-n", type=int, default=8192)
    ap.add_argument("--no-e2e", action="store_true",
                    help="skip the end-to-end per-level build sweep")
    ap.add_argument("--interpret", action="store_true",
                    help="force the Pallas kernel through the interpreter")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few iters: a CI regression probe "
                         "for build-path shapes, not a measurement")
    ap.add_argument("--update-smoke-ref", action="store_true",
                    help="with --smoke: record this run's ratios as the "
                         "committed BENCH_build.json smoke_ref baseline "
                         "(what the CI bench-gate compares against)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.n, args.d, args.m, args.efc = 2048, 32, 8, 24
        args.brute_threshold, args.chunks = 32, "256"
        args.iters, args.e2e_n = 2, 1024

    chunks = tuple(int(c) for c in args.chunks.split(","))
    backend = jax.default_backend()
    # resolve the backend the fused side will actually use so the artifact
    # attributes the numbers correctly
    fused_impl = "pallas" if (args.interpret or backend == "tpu") else "xla"

    step_rows, step_parity = bench_prune_step(
        args.n, args.d, args.m, args.efc, args.brute_threshold, chunks,
        args.iters, fused_impl,
    )
    for r in step_rows:
        print(
            f"prune {r['kind']:7s} C={r['C']:3d} chunk={r['chunk']:5d}: "
            f"legacy {r['legacy_us']:.0f}us  fused {r['fused_us']:.0f}us  "
            f"({r['speedup']:.2f}x, {r['fused_nodes_per_s']:.0f} nodes/s)"
        )

    e2e = None
    e2e_parity = True
    e2e_speedup = None
    if not args.no_e2e:
        e2e, e2e_parity, e2e_speedup = bench_build_levels(
            args.e2e_n, args.d, args.m, args.efc, args.brute_threshold,
            fused_impl,
        )
        print(
            f"e2e build n={args.e2e_n}: legacy {e2e['legacy']['total_s']:.2f}s"
            f"  fused {e2e[fused_impl]['total_s']:.2f}s  "
            f"({e2e_speedup:.2f}x)"
        )

    best = max(r["speedup"] for r in step_rows)
    payload = {
        "host": {
            "backend": backend,
            "device": str(jax.devices()[0]),
            "kernel_interpreted": args.interpret and backend != "tpu",
            "smoke": args.smoke,
        },
        "config": {
            "n": args.n, "d": args.d, "m": args.m, "efc": args.efc,
            "brute_threshold": args.brute_threshold, "chunks": list(chunks),
            "iters": args.iters, "fused_impl": fused_impl,
        },
        # the chunk auto-tuner's picks at this run's level shapes (the e2e
        # build below runs chunk=None, so its level records carry these);
        # search levels floor at _SEARCH_CHUNK_FLOOR — report what the
        # build actually uses, not the raw budget math
        "auto_chunk": {
            "budget_mb": knobs_mod.get_int("REPRO_CHUNK_BUDGET_MB"),
            "search": build_mod.resolve_chunk(
                build_mod.BuildConfig(), args.m + args.efc, args.d,
                floor=build_mod._SEARCH_CHUNK_FLOOR),
            "brute": build_mod.resolve_chunk(
                build_mod.BuildConfig(), args.brute_threshold, args.d),
            "reverse": build_mod.resolve_chunk(
                build_mod.BuildConfig(), 3 * args.m, args.d),
        },
        "parity": bool(step_parity and e2e_parity),
        "prune_step": step_rows,
        "prune_speedup_best": best,
        "build_levels": e2e,
        "build_speedup": e2e_speedup,
    }
    if not payload["parity"]:
        print("ERROR: fused and legacy prune backends diverged", flush=True)
    # smoke numbers are meaningless; never clobber the real perf record
    committed = os.path.join(artifacts_dir(), "BENCH_build.json")
    if args.smoke:
        out = os.path.join(artifacts_dir(), "BENCH_build_smoke.json")
        if args.update_smoke_ref:
            if update_smoke_ref(committed, {"prune_speedup_best": best}):
                print("updated smoke_ref in", committed)
            else:
                print("no committed record to update:", committed)
    else:
        out = committed
        payload = carry_smoke_ref(payload, committed)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print("wrote", out)
    return 0 if payload["parity"] else 1


if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main())
