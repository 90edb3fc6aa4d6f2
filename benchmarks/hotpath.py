"""Beam-search hot-path microbenchmark: fused vs reference hop pieces.

Three measurements, emitted to ``artifacts/BENCH_hotpath.json``:

  * ``expansion_step`` — one beam-search hop in isolation at the acceptance
    shape (B=64, n=100k, d=128 by default): the seed formulation (dense
    ``bool[B, n]`` visited + XLA ``[B, M, d]`` gather + einsum) against the
    fused one (packed uint32 bitset + ``ops.gather_dist``). On TPU the fused
    side runs the Pallas gather-distance kernel; off-TPU it runs the XLA
    reference distance with the packed bitset (pass ``--interpret`` to force
    the kernel through the interpreter — orders of magnitude slower, only
    useful as a smoke test).
  * ``edge_select_step`` — one batched edge improvisation for a [B*W]
    frontier at the same shape: the historical stable-argsort formulation
    against the sort-free one (equality-matrix dedup + masked argmin top-m,
    ``kernels/ref.py::select_edges`` / the Pallas edge-selection kernel on
    TPU).
  * ``hop_fused`` — one WHOLE beam-search hop, three ways: the seed
    composition (argsort edge selection + dense ``bool[B, n]`` visited +
    HBM gather/einsum distances, three separate launches), today's
    composed dispatch (``ops.hop(impl="composed")``, still three
    launches), and the fused ``ops.hop`` (one launch: the Pallas
    megakernel on TPU, the one-program jnp hop off-TPU). ``speedup`` is
    fused vs the seed composition — the same seed-vs-fused framing as
    ``expansion_step``, now over the full hop; ``launch_fusion_speedup``
    is fused vs the modern composed three-launch path and isolates the
    launch fusion alone (≈1.0 off-TPU, where both sides compile to
    near-identical XLA; the VMEM-residency win needs the real TPU).
    Composed and fused outputs are asserted identical before timing.
  * ``autotune`` — measured block-size / pipeline-depth picks for every
    Pallas kernel (``kernels/autotune.py``) on pinned probe shapes; the
    winners are installed process-wide (they feed the ``ops.py`` Pallas
    branches) and recorded here so ``ci_gate.py`` can flag pick drift.
  * ``search_sweep`` — end-to-end ``search_ranks`` qps/recall over
    ``expand_width`` in {1, 2, 4, 8} and over ``edge_impl`` backends on a
    CPU-tractable index, giving future PRs a perf trajectory.
  * ``storage_footprint`` — the compact-storage trade (``core/storage.py``):
    real ``nbytes`` of the same index under f32/int32 vs bf16/int16 storage
    (the two tables every hop reads, so the ratio is also the hop-bandwidth
    ratio), qps + recall@10 at both, and a bit-identity probe of the
    neighbor codec. ``ci_gate.py`` hard-fails when the ratio exceeds 0.55
    or the recall delta exceeds 0.01.
  * ``serve_latency`` — the executor layer (``serve/executor.py``): warmed
    small-batch flush latency with power-of-two batch buckets vs the
    historical always-pad-to-max executor, plus a mixed-workload
    compile-count probe (random k <= ef, random batch sizes, two configs).
    ``ci_gate.py`` hard-fails any post-warmup compile or a program count
    above the ``len(configs) * len(batch_buckets) * len(k_buckets)`` grid.

Usage: ``PYTHONPATH=src python benchmarks/hotpath.py [--no-sweep] [--b 64]
[--n 100000] [--d 128] [--m 16] [--iters 50] [--smoke]``

``--smoke`` shrinks every shape and iteration count to a seconds-long CI
pass that still exercises all three measurements (shape regressions in the
hot path fail loudly, numbers are meaningless).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from common import DEFAULT_K, artifacts_dir, build_index, carry_smoke_ref, \
    make_searcher, make_workload, measure, time_it, update_smoke_ref
from repro.core import SearchConfig, bitset
from repro.core import edge_select as edge_select_mod
from repro.core import storage as storage_mod
from repro.core.search import _pairdist
from repro.kernels import autotune as autotune_mod
from repro.kernels import edge_select as edge_select_k
from repro.kernels import gather_distance as gather_k
from repro.kernels import hop as hop_k
from repro.kernels import ops
from repro.kernels import prune as prune_k
from repro import compile_cache


def _elemental_table(rng, n, m, logn):
    """Synthetic but structurally valid elemental-graph table: every edge
    stays inside its layer's segment, 15% of slots are -1 padding."""
    layers = logn + 1
    base = rng.integers(0, n, (n, layers, m)).astype(np.int32)
    u_ids = np.arange(n, dtype=np.int32)[:, None, None]
    shift = (logn - np.arange(layers, dtype=np.int32))[None, :, None]
    seg_lo = (u_ids >> shift) << shift
    seg_size = (1 << shift)
    nbrs = np.minimum(seg_lo + base % seg_size, n - 1).astype(np.int32)
    nbrs[rng.random(nbrs.shape) < 0.15] = -1
    return nbrs


def bench_expansion_step(B, n, d, M, iters, dist_impl):
    """One hop: visited test+mark and neighbor distances for [B, M] ids."""
    rng = np.random.default_rng(0)
    vectors = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    nbr = jnp.asarray(rng.integers(0, n, (B, M)).astype(np.int32))

    @jax.jit
    def seed_step(visited, q, nbr):
        nvalid = nbr >= 0
        b = jnp.arange(B)[:, None]
        seen = visited[b, jnp.maximum(nbr, 0)]
        nvalid &= ~seen
        visited = visited.at[b, jnp.maximum(nbr, 0)].max(nvalid)
        nx = vectors[jnp.maximum(nbr, 0)]                  # [B, M, d] in HBM
        nd = jnp.where(nvalid, _pairdist(q, nx, "l2"), jnp.inf)
        return visited, nd

    @jax.jit
    def fused_step(bits, q, nbr):
        bits, seen = bitset.test_and_set(bits, nbr, nbr >= 0)
        nvalid = (nbr >= 0) & ~seen
        nd = ops.gather_dist(
            q, vectors, jnp.where(nvalid, nbr, -1), impl=dist_impl
        )
        return bits, nd

    dense = jnp.zeros((B, n), bool)
    bits = bitset.make(B, n)
    seed_s = time_it(seed_step, dense, q, nbr, iters=iters)
    fused_s = time_it(fused_step, bits, q, nbr, iters=iters)
    return {
        "seed_us": seed_s * 1e6,
        "fused_us": fused_s * 1e6,
        "speedup": seed_s / fused_s,
        "visited_state_bytes": {
            "dense": int(B * n),
            "bitset": int(B * bitset.num_words(n) * 4),
        },
    }


def bench_edge_select(B, n, m, iters, edge_impl):
    """One batched edge improvisation for a [B*W] frontier: the historical
    argsort formulation vs the sort-free one (the half of the hop PR 2
    fuses). Ids are bit-identical; only the formulation changes."""
    rng = np.random.default_rng(1)
    logn = max(int(np.ceil(np.log2(max(n, 2)))), 1)
    layers = logn + 1
    nbrs = _elemental_table(rng, n, m, logn)

    F = B * 4  # the flattened [B*W] frontier at the default expand_width
    us = jnp.asarray(rng.integers(0, n, F).astype(np.int32))
    L = jnp.asarray(rng.integers(0, n // 2, F).astype(np.int32))
    R = jnp.asarray((np.asarray(L) + n // 2 - 1).astype(np.int32))
    nbrs = jnp.asarray(nbrs)

    @jax.jit
    def argsort_step(us, L, R):
        return edge_select_mod.select_edges_batch(
            nbrs, us, L, R, logn=logn, m_out=m
        )

    @jax.jit
    def sortfree_step(us, L, R):
        return ops.select_edges(
            nbrs, us, L, R, logn=logn, m_out=m, impl=edge_impl
        )

    # sanity: formulations must agree before we time them
    a = np.asarray(argsort_step(us, L, R))
    b = np.asarray(sortfree_step(us, L, R))
    assert np.array_equal(a, b), "edge-selection formulations diverged"

    argsort_s = time_it(argsort_step, us, L, R, iters=iters)
    sortfree_s = time_it(sortfree_step, us, L, R, iters=iters)
    return {
        "frontier": int(F),
        "K": int(layers * m),
        "logn": int(logn),
        "argsort_us": argsort_s * 1e6,
        "sortfree_us": sortfree_s * 1e6,
        "speedup": argsort_s / sortfree_s,
        "edge_impl": edge_impl,
    }


def bench_hop_fused(B, n, d, M, iters, hop_impl):
    """One whole beam-search hop (edge improvisation + visited test-and-set
    + gather-distance), three ways — see the module docstring. Integer
    outputs of the composed and fused paths are asserted bit-identical and
    distances allclose before anything is timed."""
    rng = np.random.default_rng(3)
    W, m_out = 4, M
    logn = max(int(np.ceil(np.log2(max(n, 2)))), 1)
    layers = logn + 1
    nbrs = jnp.asarray(_elemental_table(rng, n, M, logn))
    table = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    u = jnp.asarray(rng.integers(0, n, (B, W)).astype(np.int32))
    L = jnp.asarray(rng.integers(0, n // 2, B * W).astype(np.int32))
    R = L + n // 2 - 1
    vis = bitset.make(B, n)
    dense = jnp.zeros((B, n), bool)
    exp_ok = jnp.ones((B, W), bool)

    # -- seed composition: argsort select / dense visited / HBM gather ------
    @jax.jit
    def seed_select(u):
        return edge_select_mod.select_edges_batch(
            nbrs, u.reshape(B * W), L, R, logn=logn, m_out=m_out
        ).reshape(B, W * m_out)

    @jax.jit
    def seed_visited(dense, nbr, exp_ok):
        pre = (nbr >= 0) & jnp.repeat(exp_ok, m_out, axis=1)
        b = jnp.arange(B)[:, None]
        seen = dense[b, jnp.maximum(nbr, 0)]
        nvalid = pre & ~seen
        dense = dense.at[b, jnp.maximum(nbr, 0)].max(nvalid)
        return dense, nvalid

    @jax.jit
    def seed_gdist(nbr, nvalid):
        nx = table[jnp.maximum(nbr, 0)]                   # [B, WM, d] in HBM
        return jnp.where(nvalid, _pairdist(q, nx, "l2"), jnp.inf)

    def seed_hop(u, exp_ok, dense):
        nbr = seed_select(u)
        dense, nvalid = seed_visited(dense, nbr, exp_ok)
        return nbr, seed_gdist(nbr, nvalid), nvalid, dense

    # -- modern composed dispatch, still three launches ---------------------
    @jax.jit
    def c_select(u):
        return ops.select_edges(
            nbrs, u.reshape(B * W), L, R, logn=logn, m_out=m_out
        ).reshape(B, W * m_out)

    @jax.jit
    def c_bitset(vis, nbr, exp_ok):
        pre = (nbr >= 0) & jnp.repeat(exp_ok, m_out, axis=1)
        vis, seen = bitset.test_and_set(vis, nbr, pre)
        return vis, pre & ~seen

    @jax.jit
    def c_gdist(nbr, nvalid):
        return ops.gather_dist(q, table, jnp.where(nvalid, nbr, -1))

    def composed_hop(u, exp_ok, vis):
        nbr = c_select(u)
        vis, nvalid = c_bitset(vis, nbr, exp_ok)
        return nbr, c_gdist(nbr, nvalid), nvalid, vis

    # -- fused: one launch --------------------------------------------------
    @jax.jit
    def fused_hop(u, exp_ok, vis):
        return ops.hop(q, table, nbrs, u, L, R, vis, exp_ok,
                       logn=logn, m_out=m_out, impl=hop_impl)

    # parity before timing: composed vs fused must be identical; the seed
    # composition must improvise the same edges (its newly-visited mask is
    # NOT compared — the dense formulation marks in-row duplicate ids
    # visited twice, the exactly-once defect the packed bitset fixed)
    a = seed_hop(u, exp_ok, dense)
    b = composed_hop(u, exp_ok, vis)
    c = fused_hop(u, exp_ok, vis)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0])), \
        "seed vs composed edge ids diverged"
    for x, y, what in zip(b, c, ("nbr", "ndist", "nvalid", "visited")):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype.kind == "f":
            ok = np.allclose(x, y, rtol=1e-5, atol=1e-5, equal_nan=True)
        else:
            ok = np.array_equal(x, y)
        assert ok, f"composed vs fused hop diverged on {what}"

    seed_s = time_it(seed_hop, u, exp_ok, dense, iters=iters)
    composed_s = time_it(composed_hop, u, exp_ok, vis, iters=iters)
    fused_s = time_it(fused_hop, u, exp_ok, vis, iters=iters)
    return {
        "W": int(W), "m_out": int(m_out), "K": int(layers * M),
        "logn": int(logn), "hop_impl": hop_impl,
        "seed_us": seed_s * 1e6,
        "composed_us": composed_s * 1e6,
        "fused_us": fused_s * 1e6,
        "speedup": seed_s / fused_s,
        "launch_fusion_speedup": composed_s / fused_s,
    }


def bench_autotune(iters=3, interpret=False):
    """Measure block-size / pipeline-depth picks for every Pallas kernel on
    pinned probe shapes and install the winners process-wide.

    The probe shapes are deliberately identical between full and ``--smoke``
    runs so the ``autotune.picks`` section is comparable across artifacts —
    ``ci_gate.py`` hard-fails a missing/malformed section and soft-warns on
    pick drift (timing is host-dependent). Off-TPU the kernels run under
    the interpreter, so the picks only matter for interpret-mode runs; on a
    TPU host the same probe drives the real Mosaic kernels.
    """
    B, n, d, m = 8, 4096, 32, 8
    W, m_out, C = 4, 8, 64
    rng = np.random.default_rng(7)
    logn = max(int(np.ceil(np.log2(max(n, 2)))), 1)
    nbrs = jnp.asarray(_elemental_table(rng, n, m, logn))
    table = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    u = jnp.asarray(rng.integers(0, n, (B, W)).astype(np.int32))
    L = jnp.asarray(rng.integers(0, n // 2, B * W).astype(np.int32))
    R = L + n // 2 - 1
    vis = bitset.make(B, n)
    exp_ok = jnp.ones((B, W), bool)
    gids = jnp.asarray(rng.integers(-1, n, (B, W * m_out)).astype(np.int32))
    cand_ids = jnp.asarray(rng.integers(0, n, (B, C)).astype(np.int32))
    cand_dists = jnp.asarray(rng.random((B, C)), jnp.float32)

    # codec probe: the int8 table changes the DMA row dtype and adds the
    # in-register dequant, so its tile/window optimum is tuned separately
    table_i8 = storage_mod.as_device(storage_mod.encode_vectors(
        np.asarray(table), storage_mod.StorageConfig.int8()))

    edges = storage_mod.pack_edges(nbrs)    # the kernels' row-DMA form
    runs = {
        "hop": lambda **p: hop_k.hop_kernel_call(
            q, table, edges, u, L, R, vis, exp_ok, logn=logn, m_out=m_out,
            interpret=interpret, **p),
        "gather_dist": lambda **p: gather_k.gather_distance_kernel_call(
            q, table, gids, interpret=interpret, **p),
        "gather_dist_codec": lambda **p: gather_k.gather_distance_kernel_call(
            q, table_i8, gids, interpret=interpret, **p),
        "edge_select": lambda **p: edge_select_k.edge_select_kernel_call(
            edges, u.reshape(B * W), L, R, logn=logn, m_out=m_out,
            interpret=interpret, **p),
        "prune": lambda **p: prune_k.prune_kernel_call(
            cand_ids, cand_dists, table, m=m, interpret=interpret, **p),
    }
    records = {}
    for kind, run in runs.items():
        rec = autotune_mod.autotune(kind, run, iters=iters)
        autotune_mod.set_pick(kind, rec["best"])
        records[kind] = rec
    return {
        "probe": {"B": B, "n": n, "d": d, "m": m, "W": W, "m_out": m_out,
                  "C": C, "logn": int(logn), "iters": int(iters)},
        "interpret": bool(interpret),
        "picks": autotune_mod.all_picks(),
        "records": records,
    }


def bench_search_sweep(widths=(1, 2, 4, 8), edge_impls=("argsort", "xla"),
                      dataset="wit-like", n_queries=128):
    index = build_index(dataset)
    wl = make_workload(index, "mixed", n_queries=n_queries)
    auto_edge = ops.default_impl("edge")
    rows = []
    for w in widths:
        fn = make_searcher(index, config=SearchConfig(ef=64, expand_width=w))
        r = measure(fn, wl, index, k=DEFAULT_K)
        # label the resolved backend so rows are self-describing
        rows.append({"expand_width": w, "edge_impl": auto_edge,
                     **{k: float(v) for k, v in r.items()}})
    for impl in edge_impls:
        if impl == auto_edge:
            continue  # already measured as the width-4 auto row
        fn = make_searcher(
            index, config=SearchConfig(ef=64, edge_impl=impl))
        r = measure(fn, wl, index, k=DEFAULT_K)
        rows.append({
            "expand_width": 4, "edge_impl": impl,
            **{k: float(v) for k, v in r.items()},
        })
    return rows


def bench_storage_footprint(dataset="wit-like", n_queries=64):
    """Footprint + hot-path cost of compact storage vs the f32 baseline.

    The compact index is the SAME graph re-encoded (``astype_storage``), so
    the recall delta isolates bf16 vector quantization, and neighbor ids are
    checked bit-identical across the int16/int32 codecs (the decode is a
    plain -1-preserving widening cast).
    """
    # pin the baseline storage explicitly so a REPRO_STORAGE=compact CI leg
    # still measures compact against true f32/int32
    idx32 = build_index(dataset, storage=storage_mod.StorageConfig())
    compact = storage_mod.StorageConfig.compact()
    idxc = idx32.astype_storage(compact)
    wl = make_workload(idx32, "mixed", n_queries=n_queries)
    out = {
        "dataset": dataset,
        "f32_bytes": int(idx32.nbytes),
        "compact_bytes": int(idxc.nbytes),
        "footprint_ratio": idxc.nbytes / idx32.nbytes,
        "vector_dtype": str(idxc.vectors.dtype),
        "neighbor_dtype": str(idxc.neighbors.dtype),
        "hop_tables_bytes": {
            "f32": int(idx32.vectors.nbytes + idx32.neighbors.nbytes),
            "compact": int(idxc.vectors.nbytes + idxc.neighbors.nbytes),
        },
    }
    for tag, idx in (("f32", idx32), ("compact", idxc)):
        # ground truth always comes from the f32 index: recall_delta must
        # see quantization-induced loss, not a self-consistent compact gt
        r = measure(make_searcher(idx, config=SearchConfig(ef=64)), wl,
                    idx32, k=DEFAULT_K)
        out[tag] = {k: float(v) for k, v in r.items()}
    out["recall_delta"] = out["compact"]["recall"] - out["f32"]["recall"]
    # int16/split vs int32 neighbor storage with identical vectors: ids must
    # be bit-identical end-to-end (the acceptance criterion ci_gate enforces)
    nq = min(16, len(wl.queries))
    a = idx32.search_ranks(wl.queries[:nq], wl.L[:nq], wl.R[:nq],
                           k=DEFAULT_K, config=SearchConfig(ef=64))
    for codec in ("int16", "split"):
        idxn = idx32.astype_storage(
            storage_mod.StorageConfig(neighbor_dtype=codec)
        )
        b = idxn.search_ranks(wl.queries[:nq], wl.L[:nq], wl.R[:nq],
                              k=DEFAULT_K, config=SearchConfig(ef=64))
        out[f"neighbor_codec_ids_identical_{codec}"] = bool(
            np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
        )
    out["neighbor_codec_ids_identical"] = \
        out["neighbor_codec_ids_identical_int16"]

    # --- quantized vector codecs (DESIGN.md §9): int8 + PQ, fused decode ---
    # Same graph (astype_storage), so the recall delta isolates vector
    # quantization; the rerank pass re-scores the beam's top-r against the
    # sidecar (int8 for pq profiles) inside the jitted search. nav_* counts
    # only what the hot path touches (vectors + neighbors + attrs); the
    # footprint_ratio includes the rerank sidecar. The quantized legs buy
    # their recall back with a deeper beam (ef 64 -> 128; the memory-for-
    # compute trade the codecs exist to make) — lossy navigation distances
    # swap near-ties the wider beam re-covers, and the exact-sidecar
    # rerank then fixes the final ordering. Measured wit-like deltas vs
    # f32@ef=64: int8 -0.005, pq -0.008 (both inside the 0.01 gate);
    # int8's rerank is a no-op (it re-scores the same int8 vectors), pq
    # without rerank sits at ~0.67 recall.
    codec_cfg = {
        "int8": SearchConfig(ef=128),
        "pq": SearchConfig(ef=128, rerank=128),
    }
    for tag, st in (("int8", storage_mod.StorageConfig.int8()),
                    ("pq", storage_mod.StorageConfig.pq())):
        qidx = idx32.astype_storage(st)
        nav_bytes = (storage_mod.table_nbytes(qidx.vectors)
                     + storage_mod.table_nbytes(qidx.neighbors)
                     + qidx.attrs.nbytes)
        leg = {
            "bytes": int(qidx.nbytes),
            "nav_bytes": int(nav_bytes),
            "rerank_bytes": int(storage_mod.table_nbytes(qidx.rerank)),
            "footprint_ratio": qidx.nbytes / idx32.nbytes,
            "nav_footprint_ratio": nav_bytes / idx32.nbytes,
        }
        for mode, cfg in (
            ("plain", SearchConfig(ef=64)),
            ("rerank", codec_cfg[tag]),
        ):
            r = measure(make_searcher(qidx, config=cfg), wl, idx32,
                        k=DEFAULT_K)
            leg[mode] = {k: float(v) for k, v in r.items()}
        leg["recall_delta"] = leg["rerank"]["recall"] - out["f32"]["recall"]
        out[tag] = leg
    return out


def bench_serve_latency(dataset="ytaudio-like", max_batch=64,
                        small_batches=(1, 2, 4, 8), iters=20):
    """Bucketed flushes vs always-pad-to-max on small batches, plus the
    mixed-workload compile-count probe (the ci_gate hard gate).

    Both executors are warmed, so the timings isolate the padded compute:
    the pad-to-max side runs every flush at [max_batch] rows, the bucketed
    side at the next power of two.
    """
    from repro.serve.executor import SearchExecutor

    index = build_index(dataset)
    cfg = SearchConfig(ef=64, k_bucket=DEFAULT_K)
    bucketed = SearchExecutor(index, cfg, max_batch=max_batch, warmup=False)
    padmax = SearchExecutor(index, cfg, max_batch=max_batch,
                            batch_buckets=(max_batch,), warmup=False)
    # warm only what the sweep serves (k=10 at the touched batch buckets):
    # the full-grid warmup is the compile probe below
    small_bbs = sorted({bucketed.batch_bucket(b) for b in small_batches})
    bucketed.warmup(batch_buckets=small_bbs, k_buckets=(DEFAULT_K,))
    padmax.warmup(batch_buckets=(max_batch,), k_buckets=(DEFAULT_K,))
    wl = make_workload(index, "mixed", n_queries=max_batch)
    rows = []
    for B in small_batches:
        q, L, R = wl.queries[:B], wl.L[:B], wl.R[:B]
        tb = time_it(
            lambda q=q, L=L, R=R: bucketed.search_ranks(q, L, R, k=DEFAULT_K),
            iters=iters)
        tp = time_it(
            lambda q=q, L=L, R=R: padmax.search_ranks(q, L, R, k=DEFAULT_K),
            iters=iters)
        rows.append({
            "B": int(B), "bucket": int(bucketed.batch_bucket(B)),
            "bucketed_us": tb * 1e6, "padmax_us": tp * 1e6,
            "speedup": tp / tb,
        })
    # compile-count probe: warmed executor, mixed workload, two configs —
    # zero post-warmup compiles inside the declared grid (hard-gated).
    # The probe has its own small grid (ef=32, max_batch=8) so the full
    # benchmark doesn't pay a 70-program warmup.
    pcfg = SearchConfig(ef=32, k_bucket=DEFAULT_K)
    pcfg2 = pcfg.replace(expand_width=2)
    probe = SearchExecutor(index, pcfg, max_batch=8, warmup=False)
    warm = probe.warmup(configs=(pcfg, pcfg2))
    rng = np.random.default_rng(5)
    for config in (pcfg, pcfg2):
        for _ in range(16):
            B = int(rng.integers(1, probe.max_batch + 1))
            k = int(rng.integers(1, config.ef + 1))
            probe.search_ranks(wl.queries[:B], wl.L[:B], wl.R[:B], k=k,
                               config=config)
    return {
        "dataset": dataset, "max_batch": int(max_batch),
        "batch_buckets": list(bucketed.batch_buckets),
        "k_buckets": list(cfg.k_buckets()),
        "rows": rows,
        # the one unit-free ratio the bench-gate tracks: how much the
        # smallest flush gains from bucketing
        "small_batch_speedup": rows[0]["speedup"],
        "warmup_compiles": int(warm),
        "post_warmup_compiles": int(
            probe.stats["compiles"] - probe.stats["warmup_compiles"]
        ),
        "max_programs": int(probe.program_grid(configs=(pcfg, pcfg2))),
        "compiles": int(probe.stats["compiles"]),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--b", type=int, default=64)
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--m", type=int, default=16)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--no-sweep", action="store_true",
                    help="skip the end-to-end expand_width sweep")
    ap.add_argument("--interpret", action="store_true",
                    help="force the Pallas kernel through the interpreter")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few iters: a CI regression probe "
                         "for hot-path shapes, not a measurement")
    ap.add_argument("--update-smoke-ref", action="store_true",
                    help="with --smoke: record this run's ratios as the "
                         "committed BENCH_hotpath.json smoke_ref baseline "
                         "(what the CI bench-gate compares against)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.b, args.n, args.d, args.m = 8, 4096, 32, 8
        args.iters = 3

    backend = jax.default_backend()
    # resolve the backend the fused side will actually use so the artifact
    # attributes the numbers correctly
    dist_impl = "pallas" if (args.interpret or backend == "tpu") else "xla"
    edge_impl = "pallas" if (args.interpret or backend == "tpu") else "xla"
    hop_impl = "pallas" if (args.interpret or backend == "tpu") else "xla"
    kernel_interpreted = args.interpret and backend != "tpu"

    # autotune first: the installed picks feed every later Pallas call
    at = bench_autotune(iters=1 if args.smoke else 3,
                        interpret=backend != "tpu")
    print("autotune picks: " + "  ".join(
        f"{k}={v}" for k, v in sorted(at["picks"].items())))

    step = bench_expansion_step(
        args.b, args.n, args.d, args.m, args.iters, dist_impl
    )
    print(
        f"expansion step B={args.b} n={args.n} d={args.d} M={args.m}: "
        f"seed {step['seed_us']:.1f}us  fused {step['fused_us']:.1f}us  "
        f"({step['speedup']:.2f}x)"
    )

    edge = bench_edge_select(args.b, args.n, args.m, args.iters, edge_impl)
    print(
        f"edge select F={edge['frontier']} K={edge['K']}: "
        f"argsort {edge['argsort_us']:.1f}us  "
        f"sort-free {edge['sortfree_us']:.1f}us  ({edge['speedup']:.2f}x)"
    )

    hop = bench_hop_fused(
        args.b, args.n, args.d, args.m, args.iters, hop_impl
    )
    print(
        f"hop fused B={args.b} W={hop['W']} K={hop['K']}: "
        f"seed {hop['seed_us']:.1f}us  composed {hop['composed_us']:.1f}us  "
        f"fused[{hop['hop_impl']}] {hop['fused_us']:.1f}us  "
        f"({hop['speedup']:.2f}x vs seed, "
        f"{hop['launch_fusion_speedup']:.2f}x launch fusion)"
    )

    if args.smoke:
        storage = bench_storage_footprint("ytaudio-like", n_queries=16)
        serve = bench_serve_latency(
            "ytaudio-like", max_batch=16, small_batches=(1, 4), iters=3
        )
    else:
        storage = bench_storage_footprint("wit-like", n_queries=64)
        serve = bench_serve_latency("ytaudio-like")
    for row in serve["rows"]:
        print(
            f"serve flush B={row['B']} (bucket {row['bucket']}): "
            f"bucketed {row['bucketed_us']:.0f}us  "
            f"pad-to-max {row['padmax_us']:.0f}us  ({row['speedup']:.2f}x)"
        )
    print(
        f"serve compile probe: {serve['compiles']} programs "
        f"(grid max {serve['max_programs']}, "
        f"{serve['post_warmup_compiles']} post-warmup)"
    )
    print(
        f"storage {storage['dataset']}: f32 {storage['f32_bytes']/1e6:.2f}MB"
        f" -> compact {storage['compact_bytes']/1e6:.2f}MB "
        f"(ratio {storage['footprint_ratio']:.3f}, "
        f"{storage['vector_dtype']}/{storage['neighbor_dtype']}) "
        f"recall {storage['f32']['recall']:.3f} -> "
        f"{storage['compact']['recall']:.3f} "
        f"qps {storage['f32']['qps']:.1f} -> {storage['compact']['qps']:.1f}"
    )
    for tag in ("int8", "pq"):
        leg = storage[tag]
        print(
            f"storage {tag}: ratio {leg['footprint_ratio']:.3f} "
            f"(nav {leg['nav_footprint_ratio']:.3f}) recall "
            f"{leg['plain']['recall']:.3f} -> {leg['rerank']['recall']:.3f} "
            f"rerank (delta {leg['recall_delta']:+.4f})"
        )

    sweep = None
    if not args.no_sweep:
        if args.smoke:
            sweep = bench_search_sweep(
                widths=(1, 4), edge_impls=("argsort", "xla"),
                dataset="ytaudio-like", n_queries=16,
            )
        else:
            sweep = bench_search_sweep()
        for row in sweep:
            tag = f" edge_impl={row['edge_impl']}" if "edge_impl" in row \
                else ""
            print(
                f"expand_width={row['expand_width']}{tag}: "
                f"qps={row['qps']:.1f} recall={row['recall']:.3f} "
                f"mean_dists={row['mean_dists']:.0f}"
            )

    payload = {
        "host": {
            "backend": backend,
            "device": str(jax.devices()[0]),
            "kernel_interpreted": kernel_interpreted,
            "smoke": args.smoke,
        },
        "config": {
            "B": args.b, "n": args.n, "d": args.d, "M": args.m,
            "iters": args.iters, "dist_impl": dist_impl,
            "edge_impl": edge_impl, "hop_impl": hop_impl,
        },
        "expansion_step": step,
        "edge_select_step": edge,
        "hop_fused": hop,
        "autotune": at,
        "storage_footprint": storage,
        "serve_latency": serve,
        "search_sweep": sweep,
    }
    # smoke numbers are meaningless; never clobber the real perf record
    committed = os.path.join(artifacts_dir(), "BENCH_hotpath.json")
    if args.smoke:
        out = os.path.join(artifacts_dir(), "BENCH_hotpath_smoke.json")
        if args.update_smoke_ref:
            refs = {
                "expansion_step.speedup": step["speedup"],
                "edge_select_step.speedup": edge["speedup"],
                "hop_fused.speedup": hop["speedup"],
                "serve_latency.small_batch_speedup":
                    serve["small_batch_speedup"],
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
