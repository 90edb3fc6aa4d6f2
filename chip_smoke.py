"""Chip smoke test: the main RFANN path, end to end, on a TPU.

    python chip_smoke.py [--seed 0] [--n 131072] [--requests 256]
    python chip_smoke.py --four-chips [--seed 0] [--n 131072]

One process, phases in order; any failure exits non-zero and prints no
result line.

  0. Refuse to run unless JAX's first device is a TPU, or when a
     ``REPRO_*_IMPL`` override would take the kernels off the Pallas path.
  1. Kernel parity: every Pallas kernel of the main path (hop and
     gather_dist on f32, bf16 and int8 tables, edge_select, prune) on a
     small random problem against its jnp oracle in ``kernels/ref.py``.
  2. Build ``RangeGraphIndex`` from ``data/pipeline.vector_dataset``:
     ``--n`` x 128 f32 vectors, uniform attribute,
     ``BuildConfig(m=16, ef_construction=64)``.
  3. Warm a ``SearchExecutor`` over its batch buckets at k=10.
  4. Serve ``--requests`` requests of the paper's mixed workload (range
     fractions 2^0..2^-9, ``benchmarks/common.py::make_workload``) through
     ``AsyncServingEngine``; every request must resolve with a result.
  5. Recall@10 of the served results on a subset against
     ``RangeGraphIndex.brute_force`` (floor 0.8), and every warmed search
     program must hold a Mosaic kernel (``tpu_custom_call``).

``--four-chips`` runs only the sharded path instead: ``build_sharded``
into 4 shards, placed with ``NamedSharding(mesh, P("data"))`` on a
``(4, 1)`` ``("data", "model")`` mesh, served by ``make_serve_jit``, and
checked for in-range ids and recall against the exact brute force over
all n objects.

The compile cache is placed by ``repro/compile_cache.py``. The last line
of stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
RECALL_FLOOR = 0.8          # examples/rfann_serving.py's floor
RECALL_SUBSET = 64
K = 10


def log(msg):
    print(msg, flush=True)


def require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX's first device is "
                 f"{dev.platform!r}")
    return jax.devices()


def refuse_impl_overrides():
    from repro.core import knobs

    forced = {name: knobs.get_str(name) for name in (
        "REPRO_IMPL", "REPRO_DIST_IMPL", "REPRO_EDGE_IMPL",
        "REPRO_PRUNE_IMPL", "REPRO_HOP_IMPL")}
    forced = {k: v for k, v in forced.items() if v}
    if forced:
        sys.exit(f"chip_smoke: kernel overrides set {forced}; unset them "
                 f"so 'auto' picks the Pallas kernels")


class CacheEvents:
    """Counts JAX's persistent-compilation-cache events."""

    def __init__(self):
        import jax

        self.counts = {"cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        for name in self.counts:
            if event == f"/jax/compilation_cache/{name}":
                self.counts[name] += 1


# ---------------------------------------------------------------------------
# phase 1: kernel parity on the chip
# ---------------------------------------------------------------------------

def kernel_parity(seed):
    """Each Pallas kernel vs its jnp oracle on one small random problem.
    Integer outputs must match exactly; distances to f32 tolerance (the
    oracle runs at full f32 matmul precision). Prune's keep decisions
    compare distances, so its problem has small-integer coordinates: every
    distance is then exact in f32 in any summation order (MXU or XLA), a
    tie is a true tie, and the keep-sets must be identical."""
    import jax
    import jax.numpy as jnp

    from repro.core import bitset
    from repro.core import storage as storage_mod
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    n, d, m, B, W = 4096, 128, 16, 16, 4
    logn = 12
    table = rng.standard_normal((n, d)).astype(np.float32)
    nbrs = jnp.asarray(rng.integers(-1, n, (n, logn + 1, m)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    u = jnp.asarray(rng.integers(-1, n, (B, W)), jnp.int32)
    L = rng.integers(0, n // 2, B)
    R = L + rng.integers(0, n // 2, B)
    Lw = jnp.asarray(np.repeat(L, W), jnp.int32)
    Rw = jnp.asarray(np.repeat(R, W), jnp.int32)
    pre = jnp.asarray(rng.integers(0, n, (B, 64)), jnp.int32)
    visited, _ = bitset.test_and_set(bitset.make(B, n), pre,
                                     jnp.ones((B, 64), bool))
    exp_ok = jnp.asarray(rng.random((B, W)) < 0.8)

    def close(got, want, what):
        got, want = np.asarray(got), np.asarray(want)
        if not np.array_equal(np.isfinite(got), np.isfinite(want)):
            raise AssertionError(f"{what}: inf pattern differs")
        fin = np.isfinite(want)
        np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4,
                                   atol=1e-3, err_msg=what)

    tables = {
        "f32": jnp.asarray(table),
        "bf16": jnp.asarray(table, jnp.bfloat16),
        "int8": storage_mod.as_device(storage_mod.encode_vectors(
            table, storage_mod.StorageConfig.int8())),
    }
    with jax.default_matmul_precision("highest"):
        for name, tbl in tables.items():
            args = (q, tbl, nbrs, u, Lw, Rw, visited, exp_ok)
            got = ops.hop(*args, logn=logn, m_out=m, impl="pallas")
            want = ops.hop(*args, logn=logn, m_out=m, impl="xla")
            for i, what in ((0, "edges"), (2, "nvalid"), (3, "visited")):
                np.testing.assert_array_equal(
                    np.asarray(got[i]), np.asarray(want[i]),
                    err_msg=f"hop[{name}] {what}")
            close(got[1], want[1], f"hop[{name}] dists")
        ids = jnp.asarray(rng.integers(-1, n, (B, 256)), jnp.int32)
        for name, tbl in tables.items():
            close(ops.gather_dist(q, tbl, ids, impl="pallas"),
                  ops.gather_dist(q, tbl, ids, impl="xla"),
                  f"gather_dist[{name}]")
        np.testing.assert_array_equal(
            np.asarray(ops.select_edges(nbrs, u.reshape(-1), Lw, Rw,
                                        logn=logn, m_out=m, impl="pallas")),
            np.asarray(ops.select_edges(nbrs, u.reshape(-1), Lw, Rw,
                                        logn=logn, m_out=m, impl="xla")),
            err_msg="edge_select")
        C, P = 80, 256
        grid = rng.integers(-8, 9, (n, d)).astype(np.float32)
        cand = rng.integers(0, n, (P, C))
        cand[rng.random((P, C)) < 0.2] = -1
        pts = rng.integers(-8, 9, (P, d)).astype(np.float32)
        du = ((grid[np.maximum(cand, 0)] - pts[:, None, :]) ** 2).sum(-1)
        du = np.where(cand < 0, np.inf, du).astype(np.float32)
        args = (jnp.asarray(cand, jnp.int32), jnp.asarray(du),
                jnp.asarray(grid))
        np.testing.assert_array_equal(
            np.asarray(ops.prune(*args, m=m, impl="pallas")),
            np.asarray(ops.prune(*args, m=m, impl="xla")), err_msg="prune")
    log("kernel parity: hop and gather_dist on f32/bf16/int8, edge_select, "
        "prune: all match their oracles")


# ---------------------------------------------------------------------------
# one chip: build -> SearchExecutor -> AsyncServingEngine
# ---------------------------------------------------------------------------

async def serve_all(index, executor, reqs):
    from repro.core import ServeConfig
    from repro.serve import AsyncServingEngine

    # deadlines far beyond any flush: this run checks outcomes, not SLOs
    policy = ServeConfig(deadline_s=600.0, max_queue=len(reqs),
                         backpressure="block", drain_timeout_s=600.0)
    engine = AsyncServingEngine(index, executor=executor, serve=policy,
                                faults=False)
    try:
        return await asyncio.gather(
            *(engine.submit(r) for r in reqs), return_exceptions=True)
    finally:
        await engine.aclose()


def single_chip(args):
    from benchmarks.common import make_workload
    from repro.core import (BuildConfig, RangeGraphIndex, SearchConfig,
                            StorageConfig, recall)
    from repro.data.pipeline import vector_dataset
    from repro.serve import Request, Result, SearchExecutor

    t0 = time.perf_counter()
    vectors, attrs, _ = vector_dataset(args.n, 128, seed=args.seed)
    log(f"data: n={args.n} d=128 in {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    index = RangeGraphIndex.build(
        vectors, attrs[:, 0], BuildConfig(m=16, ef_construction=64),
        storage=StorageConfig(), verbose=True)
    build_s = time.perf_counter() - t0
    del vectors
    log(f"build_s={build_s:.1f} layers={index.logn + 1} "
        f"index_bytes={index.nbytes}")

    executor = SearchExecutor(index, SearchConfig(), max_batch=64,
                              warmup=False)
    t0 = time.perf_counter()
    warm = executor.warmup(k_buckets=(K,))
    log(f"warmup: {warm} programs in {time.perf_counter() - t0:.1f} s")

    wl = make_workload(index, "mixed", n_queries=args.requests,
                       seed=args.seed + 1)
    reqs = [Request(wl.queries[i], float(index.attrs[wl.L[i]]),
                    float(index.attrs[wl.R[i]]), K)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    outs = asyncio.run(serve_all(index, executor, reqs))
    serve_s = time.perf_counter() - t0
    failed = [o for o in outs if not isinstance(o, Result)]
    if failed:
        raise RuntimeError(
            f"{len(failed)}/{len(outs)} requests failed; first: "
            f"{failed[0]!r}")
    post = executor.stats["compiles"] - executor.stats["warmup_compiles"]
    log(f"served {len(outs)} requests in {serve_s:.2f} s, "
        f"compiles={executor.stats['compiles']} post_warmup_compiles={post}")

    sub = slice(0, min(RECALL_SUBSET, args.requests))
    gt, _ = index.brute_force(wl.queries[sub], wl.L[sub], wl.R[sub], k=K)
    got = np.stack([o.ids for o in outs[sub]])
    rec = recall(got, index.original_ids(gt))
    log(f"recall@{K}={rec:.4f} over {got.shape[0]} requests")
    if rec < RECALL_FLOOR:
        raise AssertionError(f"recall@{K} {rec:.4f} < {RECALL_FLOOR}")
    if post:
        raise AssertionError(f"{post} compiles after warmup")
    missing = [key[1:] for key, exe in executor._cache.items()
               if "tpu_custom_call" not in exe.as_text()]
    if missing:
        raise AssertionError(f"no Mosaic kernel in the search programs of "
                             f"(batch, k) buckets {missing}")


# ---------------------------------------------------------------------------
# four chips: build_sharded -> place on a (4, 1) mesh -> make_serve_jit
# ---------------------------------------------------------------------------

def four_chips(args, devices):
    import jax
    from jax.sharding import AxisType

    from repro.core import BuildConfig, SearchConfig, StorageConfig, recall
    from repro.core import distributed as dist
    from repro.core.index import brute_force
    from repro.data.pipeline import vector_dataset

    if len(devices) != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, "
                           f"found {len(devices)}")
    B = 64
    vectors, attrs, qv = vector_dataset(args.n, 128, seed=args.seed,
                                        queries=B)
    t0 = time.perf_counter()
    sharded = dist.build_sharded(
        vectors, attrs[:, 0], 4, BuildConfig(m=16, ef_construction=64),
        storage=StorageConfig())
    log(f"build_sharded: 4 x {sharded.vectors.shape[1]} in "
        f"{time.perf_counter() - t0:.1f} s")
    mesh = jax.make_mesh((4, 1), ("data", "model"), devices=devices,
                         axis_types=(AxisType.Auto,) * 2)
    vec, nbr, bnd = sharded.place(mesh)
    # make_workload's mixed ranges: fractions 2^0..2^-9 of n
    rng = np.random.default_rng(args.seed + 1)
    spans = np.maximum(args.n >> rng.integers(0, 10, B), 8)
    L = np.array([rng.integers(0, args.n - s + 1) for s in spans], np.int32)
    R = (L + spans - 1).astype(np.int32)
    step = dist.make_serve_jit(
        mesh, logn=sharded.logn, m=sharded.m, k=K, config=SearchConfig())
    t0 = time.perf_counter()
    ids, _ = step(vec, nbr, bnd, qv, L, R)
    ids = np.asarray(ids)
    log(f"sharded serve step (compile + run) in "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    ids2 = np.asarray(step(vec, nbr, bnd, qv, L, R)[0])
    log(f"sharded serve step (warm) in {time.perf_counter() - t0:.3f} s")
    if not np.array_equal(ids, ids2):
        raise AssertionError("sharded serve step is not deterministic")
    for i in range(B):
        got = ids[i][ids[i] >= 0]
        if not ((got >= L[i]) & (got <= R[i])).all():
            raise AssertionError(f"query {i}: id outside [{L[i]}, {R[i]}]")
    order = np.argsort(attrs[:, 0], kind="stable")
    gt, _ = brute_force(vectors[order], qv, L, R, k=K)
    rec = recall(ids, gt)
    log(f"sharded recall@{K}={rec:.4f} over {B} queries, ids in range")
    if rec < RECALL_FLOOR:
        raise AssertionError(f"recall@{K} {rec:.4f} < {RECALL_FLOOR}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=1 << 17)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--four-chips", action="store_true")
    args = ap.parse_args(argv)

    devices = require_tpu()
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    from repro import compile_cache

    log(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{compile_cache.enable()}")
    refuse_impl_overrides()
    cache = CacheEvents()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args, devices)
    else:
        kernel_parity(args.seed)
        single_chip(args)
    log(f"persistent cache: {cache.counts}; total "
        f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
