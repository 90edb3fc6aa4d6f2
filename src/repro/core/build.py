"""Bulk-synchronous bottom-up construction of the iRangeGraph index.

Paper §3.2.2, adapted for accelerators (DESIGN.md §2): instead of inserting
nodes one at a time, every segment-tree level is built in one batched pass.
For the segment ``[l, r]`` with children ``[l, mid]`` / ``[mid+1, r]`` and a
node ``u`` in the left child:

  * candidates inside the *own* child are copied from the child graph (an
    edge pruned in the subset is pruned in the superset — paper's first case);
  * candidates from the *sibling* child come from a beam search over the
    sibling's already-built elemental graph — this is one
    ``search_fixed_layer`` call for *all* n nodes of the level at once, each
    query carrying its own sibling-segment bounds;
  * the merged candidate set is RNG-pruned (``kernels/ops.py::prune`` — the
    fused lazy-column formulation / Pallas kernel, dispatched by
    ``cfg.prune_impl``, with ``core/rng.py`` kept as the eager oracle).

Levels whose segments are small (``<= brute_threshold``) skip the search and
take the whole segment as candidates (exact RNG up to the degree cap).

A reverse-edge pass (optional, on by default) mirrors HNSW's bidirectional
insertion: each directed edge contributes its reverse as a candidate and the
target re-prunes. This measurably improves connectivity of elemental graphs.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import knobs as knobs_mod
from repro.core import search as search_mod
from repro.core import storage as storage_mod
from repro.core.config import SearchConfig
from repro.kernels import ops

__all__ = [
    "BuildConfig", "auto_chunk", "resolve_chunk", "build_neighbor_table",
    "build_flat_graph",
]


@dataclasses.dataclass(frozen=True)
class BuildConfig:
    m: int = 16                    # max out-degree per elemental graph
    ef_construction: int = 64      # beam/candidates for sibling search (EF)
    alpha: float = 1.0             # RNG alpha (1.0 == paper's rule)
    brute_threshold: int = 128     # segments this small use exact candidates
    add_reverse: bool = True       # bidirectional pass per level
    fill_pruned: bool = True       # keepPrunedConnections
    chunk: int | None = None       # nodes per batched prune call; None = auto
    prune_impl: str = "auto"       # "auto" | "pallas" | "xla" | "legacy"


# The gathered candidate block a chunked prune re-reads once per keep sweep
# is [chunk, C, d] f32; past cache residency the lazy-column win decays
# (2.3x -> 1.8x on the dev host, BENCH_build.json chunk sweep), so the
# auto-tuner sizes the chunk against this budget. The REPRO_CHUNK_BUDGET_MB
# knob (default 16, core/knobs.py registry) overrides for hosts with
# different cache hierarchies.
_CHUNK_MIN, _CHUNK_MAX = 256, 8192
# Search levels interleave the prune with a batched sibling beam search
# (one search_fixed_layer call per chunk) whose cost amortizes with batch
# size, so their chunk never auto-tunes below this floor — the residency
# budget only governs the prune-only passes (brute levels, reverse pass).
_SEARCH_CHUNK_FLOOR = 2048


def auto_chunk(C: int, d: int, *, budget_bytes: int | None = None) -> int:
    """Per-level build chunk: the largest power of two keeping the gathered
    ``[chunk, C, d]`` f32 candidate block inside the cache budget, clamped
    to [256, 8192]. ``BuildConfig.chunk`` overrides (see resolve_chunk)."""
    if budget_bytes is None:
        budget_bytes = knobs_mod.get_int("REPRO_CHUNK_BUDGET_MB") << 20
    per_row = max(int(C) * int(d) * 4, 1)
    target = max(budget_bytes // per_row, 1)
    p = 1
    while p * 2 <= target:
        p <<= 1
    return max(_CHUNK_MIN, min(_CHUNK_MAX, p))


def resolve_chunk(cfg: BuildConfig, C: int, d: int, *,
                  floor: int | None = None) -> int:
    """The chunk a level actually uses: the explicit ``cfg.chunk`` when set,
    else :func:`auto_chunk` keyed on that level's candidate width ``C``
    (raised to ``floor`` for passes whose cost amortizes with batch size,
    e.g. the search levels' sibling beam search)."""
    if cfg.chunk is not None:
        return int(cfg.chunk)
    chunk = auto_chunk(C, d)
    return max(chunk, floor) if floor else chunk


def _level_sizes(n: int) -> tuple[int, int]:
    logn = int(math.ceil(math.log2(max(n, 2))))
    return logn, logn + 1


def _reverse_pass(
    nbrs_lay: np.ndarray, vec_j, seg_of, cfg: BuildConfig,
    chunk: int | None = None,
):
    """Add reverse edges then re-prune each node's list. numpy + fused prune.

    nbrs_lay: int32[n, m] this level's edges. vec_j: the jnp vector table
    (``ops.prune`` gathers candidate vectors from it). seg_of: int32[n]
    segment id of each node at this level (reverse edges only ever connect
    nodes of the same segment, but we keep the check for safety).
    ``chunk``: nodes per prune call; defaults to the auto-tuned chunk for
    this pass's candidate width C = 3m.
    """
    n, m = nbrs_lay.shape
    if chunk is None:
        chunk = resolve_chunk(cfg, 3 * m, vec_j.shape[1])
    # collect reverse candidates: for edge (u, v) add u to v's pool (capped)
    us = np.repeat(np.arange(n, dtype=np.int32), m)
    vs = nbrs_lay.reshape(-1)
    ok = (vs >= 0) & (seg_of[us] == seg_of[np.maximum(vs, 0)])
    us, vs = us[ok], vs[ok]
    if us.size == 0:
        return nbrs_lay
    order = np.argsort(vs, kind="stable")
    vs, us = vs[order], us[order]
    counts = np.bincount(vs, minlength=n)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int64)
    pos = np.arange(vs.size, dtype=np.int64) - starts[vs]
    rev_cap = 2 * m
    keep = pos < rev_cap
    C = m + rev_cap
    cand = np.full((n, C), -1, np.int32)
    cand[:, :m] = nbrs_lay
    cand[vs[keep], m + pos[keep]] = us[keep]
    out = np.empty((n, m), np.int32)
    for s in range(0, n, chunk):
        e = min(n, s + chunk)
        ids = jnp.asarray(cand[s:e])
        cvec = vec_j[jnp.maximum(ids, 0)]
        u_vec = vec_j[s:e]
        d = jnp.sum((cvec - u_vec[:, None, :]) ** 2, axis=-1)
        d = jnp.where(ids >= 0, d, jnp.inf)
        out[s:e] = np.asarray(
            ops.prune(
                ids, d, vec_j, m=m, alpha=cfg.alpha, fill=cfg.fill_pruned,
                impl=cfg.prune_impl, cand_vecs=cvec,
            )
        )
    return out


def build_neighbor_table(
    vectors: np.ndarray, cfg: BuildConfig | None = None, *, verbose=False,
    level_times: list | None = None,
    storage: storage_mod.StorageConfig | None = None,
) -> np.ndarray:
    """Build the packed elemental-graph table ``[n, layers, m]``.

    ``vectors`` must already be in attribute-rank order (see index.py).
    ``level_times``, if given a list, collects per-level wall-clock dicts
    (layer, segment size, kind, chunk sizes, seconds) — the
    build-throughput record ``benchmarks/buildpath.py`` emits. With
    ``cfg.chunk=None`` each level's prune chunk is auto-tuned per its
    candidate width (see :func:`auto_chunk`); chunking never changes the
    built table (chunk-invariance is tested), only throughput.

    Construction scratch is int32; with ``storage`` the finished table is
    emitted directly in the compact neighbor codec (int16 when ids fit,
    ``-1`` sentinel unchanged — see ``core/storage.py``), otherwise int32.
    """
    cfg = cfg or BuildConfig()
    vectors = np.asarray(vectors, np.float32)
    n, d = vectors.shape
    logn, layers = _level_sizes(n)
    m = cfg.m
    nbrs = np.full((n, layers, m), -1, np.int32)
    vec_j = jnp.asarray(vectors)

    ids_all = np.arange(n, dtype=np.int32)
    for lay in range(logn - 1, -1, -1):  # leaves (logn) have no edges
        size = 1 << (logn - lay)
        seg_of = ids_all >> (logn - lay)
        t0 = time.perf_counter()
        if size <= cfg.brute_threshold:
            chunk = resolve_chunk(cfg, size, d)
            edges = _build_brute_level(vec_j, n, lay, logn, size, cfg, chunk)
        else:
            chunk = resolve_chunk(cfg, m + cfg.ef_construction, d,
                                  floor=_SEARCH_CHUNK_FLOOR)
            edges = _build_search_level(
                vec_j, nbrs, n, lay, logn, size, cfg, chunk
            )
        rev_chunk = None
        if cfg.add_reverse:
            rev_chunk = resolve_chunk(cfg, 3 * m, d)
            edges = _reverse_pass(edges, vec_j, seg_of, cfg,
                                  rev_chunk)
        nbrs[:, lay, :] = edges
        if level_times is not None:
            level_times.append({
                "layer": int(lay), "seg_size": int(size),
                "kind": "brute" if size <= cfg.brute_threshold else "search",
                "chunk": int(chunk),
                "chunk_reverse": rev_chunk if rev_chunk is None
                else int(rev_chunk),
                "seconds": time.perf_counter() - t0,
            })
        if verbose:
            deg = float((edges >= 0).sum(1).mean())
            print(f"  layer {lay:2d} seg_size {size:7d} mean_deg {deg:.1f}")
    if storage is not None:
        return storage_mod.encode_neighbors(nbrs, n, storage)
    return nbrs


def _build_brute_level(vec_j, n, lay, logn, size, cfg: BuildConfig, chunk):
    """Exact candidates = whole segment. One batched prune per chunk."""
    m = cfg.m
    out = np.empty((n, m), np.int32)
    step = max(1, chunk // max(size, 1)) * size  # chunk on segment bounds
    for s in range(0, n, step):
        e = min(n, s + step)
        u = jnp.arange(s, e, dtype=jnp.int32)
        lo = (u >> (logn - lay)) << (logn - lay)
        cand = lo[:, None] + jnp.arange(size, dtype=jnp.int32)[None, :]
        valid = (cand < n) & (cand != u[:, None])
        cand = jnp.where(valid, cand, -1)
        cvec = vec_j[jnp.maximum(cand, 0)]
        uvec = vec_j[u]
        dist = jnp.sum((cvec - uvec[:, None, :]) ** 2, -1)
        dist = jnp.where(valid, dist, jnp.inf)
        out[s:e] = np.asarray(
            ops.prune(
                cand, dist, vec_j, m=m, alpha=cfg.alpha,
                fill=cfg.fill_pruned, impl=cfg.prune_impl, cand_vecs=cvec,
            )
        )
    return out


def _build_search_level(vec_j, nbrs, n, lay, logn, size, cfg: BuildConfig,
                        chunk):
    """Own-child copy + sibling beam search, then prune. Paper §3.2.2."""
    m, efc = cfg.m, cfg.ef_construction
    # the children of this level are already built; only their layer is
    # read, so only it goes to the device
    child = jnp.asarray(nbrs[:, lay + 1:lay + 2, :])    # int32[n, 1, m]
    out = np.empty((n, m), np.int32)
    half = size // 2
    search_cfg = SearchConfig(ef=efc)
    for s in range(0, n, chunk):
        e = min(n, s + chunk)
        u = jnp.arange(s, e, dtype=jnp.int32)
        lo = (u >> (logn - lay)) << (logn - lay)
        mid = lo + half - 1
        in_left = u <= mid
        sib_lo = jnp.where(in_left, mid + 1, lo)
        sib_hi = jnp.where(in_left, lo + size - 1, mid)
        res = search_mod.search_fixed_layer(
            vec_j, child, vec_j[u], sib_lo, sib_hi,
            layer=0, k=efc, config=search_cfg,
        )
        own = child[u, 0, :]                            # int32[B, m]
        cand = jnp.concatenate([own, res.ids], axis=1)  # [B, m + efc]
        valid = (cand >= 0) & (cand != u[:, None]) & (cand < n)
        cand = jnp.where(valid, cand, -1)
        cvec = vec_j[jnp.maximum(cand, 0)]
        dist = jnp.sum((cvec - vec_j[u][:, None, :]) ** 2, -1)
        dist = jnp.where(valid, dist, jnp.inf)
        out[s:e] = np.asarray(
            ops.prune(
                cand, dist, vec_j, m=m, alpha=cfg.alpha,
                fill=cfg.fill_pruned, impl=cfg.prune_impl, cand_vecs=cvec,
            )
        )
    return out


def build_flat_graph(
    vectors: np.ndarray, cfg: BuildConfig | None = None
) -> np.ndarray:
    """From-scratch single RNG graph over ``vectors`` (Oracle baseline,
    paper §5.2.4). Returns int32[n, 1, m] so it plugs into the same search
    code at layer 0. Built by the same bottom-up machinery on the slice."""
    tbl = build_neighbor_table(vectors, cfg)
    return tbl[:, :1, :]
