"""Batched greedy beam search engines (fused hot path).

The paper's query phase is greedy beam search (HNSW-style dynamic list of
size ``ef``) over a graph whose edges are improvised per query range
(Algorithm 1). On TPU the priority-queue formulation becomes a fixed-shape
lockstep loop; this module is the performance-tuned engine (DESIGN.md §3):

  * per-query state: candidate list ``(ids, dists, visited)`` of size ``ef``
    holding the best-so-far, a *packed* ``uint32[B, ceil(n/32)]`` visited
    bitset (``core/bitset.py``), an active flag;
  * each iteration expands the top ``expand_width`` unvisited candidates of
    every active query simultaneously; their edge selections run as ONE
    batched call of shape ``[B*W]``, so per-iteration fixed costs (edge
    selection, top-k merge) amortize over W expansions;
  * neighbor distances come from the fused gather-distance kernel
    (``kernels/gather_distance.py``) on TPU — no ``[B, M, d]`` HBM
    intermediate — and from the XLA gather+einsum reference elsewhere;
  * edge improvisation dispatches through ``kernels/ops.py::select_edges``
    (``edge_impl`` knob): the Pallas edge-selection kernel on TPU, the
    sort-free jnp formulation elsewhere — bit-identical ids either way;
  * termination (best unvisited worse than the worst of a full list) becomes
    a mask; finished queries coast.

``beam_search`` is generic over a ``nbr_fn`` so the same engine serves the
improvised graph, single elemental graphs (index construction, BasicSearch,
SuperPostfiltering), the root graph with post-/in-filtering, and the
multi-attribute variant. **nbr_fn contract**: it receives the *flattened*
expansion frontier ``int32[B*W]`` (row ``b*W + w`` is query b's w-th
expansion, ``-1`` for inactive slots) and must return ``int32[B*W, M]``.

Alternatively a caller may bind the *whole* hop: with ``hop_fn`` given, the
edge-selection + visited-test-and-set + gather-distance middle of the loop
body runs as one call (``kernels/ops.py::hop`` — on TPU the fused Pallas
megakernel, one launch per beam iteration with the frontier resident in
VMEM). **hop_fn contract**: ``(u int32[B, W], exp_ok bool[B, W],
visited uint32[B, words]) -> (nbr int32[B, W*M], ndist f32[B, W*M],
nvalid bool[B, W*M], visited')`` with the same semantics as the composed
path (``kernels/ref.py::hop``) — integer outputs bit-identical, distances
f32. The two-list filtered searches keep the composed body (their
range-filter hook lives between edge selection and the visited update).

Engine knobs arrive as ONE frozen ``core/config.py::SearchConfig`` (a
static arg of the jitted searches, so equal configs share one compiled
program — the contract ``serve/executor.py`` builds its compile cache on).
The historical loose kwargs (``ef=``, ``expand_width=``, ...) remain as a
deprecation shim resolved by ``config.merge``; ``k`` stays per-call.

With ``expand_width=1`` the engine is bit-identical (ids and dists) to the
reference implementation in ``core/search_ref.py``; tests enforce this.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bitset
from repro.core import config as config_mod
from repro.core import storage as storage_mod
from repro.core.config import DEFAULT_EXPAND_WIDTH, SearchConfig
from repro.kernels import ops

__all__ = [
    "SearchResult",
    "SearchConfig",
    "beam_search",
    "effective_expand_width",
    "search_improvised",
    "search_fixed_layer",
    "search_filtered",
    "neighbor_form",
]

_INF = jnp.float32(jnp.inf)


def effective_expand_width(expand_width: int, ef: int) -> int:
    """The W beam_search will actually run: clamped to the ef-sized
    candidate list. Every caller that tiles per-query state into a [B*W]
    frontier for its nbr_fn MUST use this same value."""
    w = int(expand_width)
    if w < 1:
        raise ValueError(f"expand_width must be >= 1, got {w}")
    return min(w, ef)


class SearchResult(NamedTuple):
    ids: jnp.ndarray      # int32[B, k] (-1 padded)
    dists: jnp.ndarray    # float32[B, k]
    n_hops: jnp.ndarray   # int32[B]   nodes expanded
    n_dists: jnp.ndarray  # int32[B]   distance computations


def _pairdist(q, x, metric):
    """Distance between queries q[B, d] and points x[B, M, d] -> [B, M].

    Inputs may be bf16 (the storage-dtype hillclimb); math is f32. Kept for
    benchmarks/tests; the engine itself uses ``ops.gather_dist``.
    """
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    if metric == "l2":
        # ||x||^2 - 2 x.q + ||q||^2 ; keep ||q||^2 for exactness of ordering
        xx = jnp.sum(x * x, axis=-1)
        qq = jnp.sum(q * q, axis=-1, keepdims=True)
        xq = jnp.einsum("bd,bmd->bm", q, x)
        return xx - 2.0 * xq + qq
    if metric == "ip":
        return -jnp.einsum("bd,bmd->bm", q, x)
    raise ValueError(f"unknown metric {metric!r}")


def beam_search(
    vectors: jnp.ndarray,          # f32[n, d]
    queries: jnp.ndarray,          # f32[B, d]
    entry_ids: jnp.ndarray,        # int32[B, E] (-1 for unused)
    nbr_fn: Callable,              # int32[B*W] -> int32[B*W, M]
    *,
    k: int,
    config: SearchConfig | None = None,
    ef: int | None = None,
    expand_width: int | None = None,
    max_iters: int | None = None,
    metric: str | None = None,
    result_filter_fn: Callable | None = None,
    visit_prob_fn: Callable | None = None,
    rng: jax.Array | None = None,
    dist_impl: str | None = None,
    edge_impl: str | None = None,
    hop_impl: str | None = None,
    hop_fn: Callable | None = None,
) -> SearchResult:
    """Generic batched beam search. See module docstring.

    config: the engine knobs as ONE frozen ``SearchConfig``; ``k`` stays
      per-call. The loose kwargs below are the deprecation shim (resolved
      onto the config by ``config.merge``; non-None values win).
    config.expand_width: number of unvisited candidates expanded per query
      per iteration (static). 1 reproduces the reference engine bit-for-bit.
    result_filter_fn: optional ``ids[B,K] -> bool[B,K]``; when given, the
      navigation list accepts everything but the *result* list only accepts
      ids passing the filter (multi-attribute post-filtering semantics).
    visit_prob_fn: optional ``(ids[B,K], t[B]) -> p[B,K]`` probability of
      visiting an id that fails the result filter (the paper's §4
      generalization; p=1 is post-filtering, p=0 in-filtering). Requires rng.
    config.dist_impl: "auto" | "pallas" | "xla" distance backend (see
      kernels/ops).
    config.edge_impl: edge-selection backend, same value set plus "argsort".
      The generic engine performs no edge selection itself (``nbr_fn``
      arrives pre-bound), but the knob lives in the config so every wrapper
      forwards one uniform backend set; concrete searches bind it into
      their ``nbr_fn`` via ``ops.select_edges``.
    hop_fn: optional whole-hop closure (see module docstring). Mutually
      exclusive with ``result_filter_fn`` — the two-list searches hook the
      range filter *between* edge selection and the visited update, which
      only the composed body exposes.
    """
    config = config_mod.merge(
        config, ef=ef, expand_width=expand_width, max_iters=max_iters,
        metric=metric, dist_impl=dist_impl, edge_impl=edge_impl,
        hop_impl=hop_impl,
    )
    if hop_fn is not None and result_filter_fn is not None:
        raise ValueError(
            "beam_search: hop_fn is incompatible with result_filter_fn "
            "(filtered searches need the composed hop body)"
        )
    if hop_fn is None and nbr_fn is None:
        raise ValueError("beam_search: need nbr_fn or hop_fn")
    ef = config.ef
    metric = config.metric
    dist_impl = config.dist_impl
    # vectors may be a quantized codec struct (storage.Int8Vectors /
    # storage.PQVectors); the distance kernels decode per-row in VMEM
    n = storage_mod.table_n(vectors)
    B = queries.shape[0]
    W = effective_expand_width(config.expand_width, ef)
    max_iters = config.max_iters
    if max_iters is None:
        max_iters = 4 * ef + 32

    two_lists = result_filter_fn is not None

    def gdist(ids):
        return ops.gather_dist(
            queries, vectors, ids, metric=metric, impl=dist_impl
        )

    def init_state():
        e = entry_ids
        valid = e >= 0
        dists = gdist(jnp.where(valid, e, -1))
        E = e.shape[1]
        pad = ef - E
        cand_ids = jnp.concatenate(
            [jnp.where(valid, e, -1), jnp.full((B, pad), -1, jnp.int32)], axis=1
        )
        cand_dists = jnp.concatenate([dists, jnp.full((B, pad), _INF)], axis=1)
        cand_vis = jnp.zeros((B, ef), bool)
        visited, _ = bitset.test_and_set(bitset.make(B, n), e, valid)
        if two_lists:
            ok = result_filter_fn(jnp.maximum(e, 0)) & valid
            res_ids = jnp.concatenate(
                [jnp.where(ok, e, -1), jnp.full((B, pad), -1, jnp.int32)], 1
            )
            res_dists = jnp.concatenate(
                [jnp.where(ok, dists, _INF), jnp.full((B, pad), _INF)], 1
            )
        else:
            res_ids = cand_ids
            res_dists = cand_dists
        t = jnp.zeros((B,), jnp.int32)  # consecutive out-of-range counter
        stats = (jnp.zeros((B,), jnp.int32), jnp.sum(valid, 1, dtype=jnp.int32))
        key = rng if rng is not None else jax.random.PRNGKey(0)
        return (
            cand_ids, cand_dists, cand_vis, visited,
            res_ids, res_dists, t, jnp.ones((B,), bool), stats, key,
            jnp.int32(0),
        )

    def cond(state):
        *_, active, _stats, _key, it = state
        return jnp.any(active) & (it < max_iters)

    def body(state):
        (cand_ids, cand_dists, cand_vis, visited,
         res_ids, res_dists, t, active, stats, key, it) = state
        n_hops, n_dists = stats

        unvisited = jnp.where(
            cand_vis | (cand_ids < 0), _INF, cand_dists
        )
        # top-W unvisited candidates; slot 0 is the argmin, so the classic
        # termination test reads off the first column
        neg_sel, slots = jax.lax.top_k(-unvisited, W)       # [B, W]
        sel_dists = -neg_sel
        best_dist = sel_dists[:, 0]
        worst = jnp.max(jnp.where(cand_ids >= 0, cand_dists, -_INF), axis=1)
        full = jnp.all(cand_ids >= 0, axis=1)
        progress = jnp.isfinite(best_dist) & (~full | (best_dist <= worst))
        active = active & progress

        exp_ok = active[:, None] & jnp.isfinite(sel_dists)  # [B, W]
        u = jnp.where(
            exp_ok, jnp.take_along_axis(cand_ids, slots, 1), -1
        )                                                   # [B, W]
        rows = jnp.arange(B)[:, None]
        cand_vis = cand_vis.at[rows, slots].max(exp_ok)
        n_hops = n_hops + jnp.sum(exp_ok, axis=1, dtype=jnp.int32)

        if hop_fn is not None:
            # whole hop in one call: edge selection + visited test-and-set
            # + gather-distance (on TPU one fused Pallas launch)
            nbr, ndist, nvalid, visited = hop_fn(u, exp_ok, visited)
        else:
            # ONE batched edge selection for the whole [B, W] frontier
            nbr = nbr_fn(u.reshape(B * W))                  # [B*W, M]
            M = nbr.shape[1]
            nbr = nbr.reshape(B, W * M)
            exp_rep = jnp.repeat(exp_ok, M, axis=1)         # [B, W*M]
            pre_valid = (nbr >= 0) & exp_rep

            if two_lists:
                in_rng = result_filter_fn(jnp.maximum(nbr, 0))
                if visit_prob_fn is not None:
                    key, sub = jax.random.split(key)
                    p = visit_prob_fn(jnp.maximum(nbr, 0), t)
                    coin = jax.random.uniform(sub, (B, W * M))
                    visit_out = coin < p
                else:
                    visit_out = jnp.ones((B, W * M), bool)  # post-filtering
                pre_valid &= in_rng | visit_out
                # consecutive out-of-range counter follows the expanded nodes
                u_in = result_filter_fn(jnp.maximum(u, 0)) & exp_ok
                any_exp = jnp.any(exp_ok, axis=1)
                num_out = jnp.sum(exp_ok & ~u_in, axis=1, dtype=jnp.int32)
                t = jnp.where(
                    any_exp,
                    jnp.where(jnp.any(u_in, axis=1), 0, t + num_out),
                    t,
                )

            # packed visited: one test_and_set both reads and marks, and
            # dedups the same neighbor arriving from two expansions
            visited, seen = bitset.test_and_set(visited, nbr, pre_valid)
            nvalid = pre_valid & ~seen

            # fused gather+distance: no [B, W*M, d] intermediate on TPU
            ndist = gdist(jnp.where(nvalid, nbr, -1))

        WM = nbr.shape[1]
        n_dists = n_dists + jnp.sum(nvalid, axis=1, dtype=jnp.int32)

        # merge into navigation list
        all_ids = jnp.concatenate([cand_ids, jnp.where(nvalid, nbr, -1)], 1)
        all_dists = jnp.concatenate([cand_dists, ndist], 1)
        all_vis = jnp.concatenate([cand_vis, jnp.zeros((B, WM), bool)], 1)
        _, idx = jax.lax.top_k(-all_dists, ef)
        cand_ids = jnp.take_along_axis(all_ids, idx, 1)
        cand_dists = jnp.take_along_axis(all_dists, idx, 1)
        cand_vis = jnp.take_along_axis(all_vis, idx, 1)

        if two_lists:
            rvalid = nvalid & in_rng
            r_ids = jnp.concatenate([res_ids, jnp.where(rvalid, nbr, -1)], 1)
            r_dists = jnp.concatenate(
                [res_dists, jnp.where(rvalid, ndist, _INF)], 1
            )
            _, ridx = jax.lax.top_k(-r_dists, ef)
            res_ids = jnp.take_along_axis(r_ids, ridx, 1)
            res_dists = jnp.take_along_axis(r_dists, ridx, 1)
        else:
            res_ids, res_dists = cand_ids, cand_dists

        return (cand_ids, cand_dists, cand_vis, visited,
                res_ids, res_dists, t, active, (n_hops, n_dists), key,
                it + 1)

    state = init_state()
    state = jax.lax.while_loop(cond, body, state)
    (_, _, _, _, res_ids, res_dists, _, _, stats, _, _) = state
    _, idx = jax.lax.top_k(-res_dists, k)
    out_ids = jnp.take_along_axis(res_ids, idx, 1)
    out_dists = jnp.take_along_axis(res_dists, idx, 1)
    out_ids = jnp.where(jnp.isfinite(out_dists), out_ids, -1)
    return SearchResult(out_ids, out_dists, stats[0], stats[1])


# ---------------------------------------------------------------------------
# Entry-point helpers
# ---------------------------------------------------------------------------

def range_entry_ids(L, R, n, num_entries=3):
    """Deterministic in-range entry points: midpoint + quartiles of [L, R]."""
    fracs = jnp.array([0.5, 0.25, 0.75, 0.0, 1.0][:num_entries])
    span = (R - L).astype(jnp.float32)[..., None]
    ids = L[..., None] + jnp.round(span * fracs[None, :]).astype(jnp.int32)
    ids = jnp.clip(ids, 0, n - 1)
    # dedupe within the row: later duplicates -> -1
    sortd = jnp.sort(ids, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros_like(sortd[..., :1], bool), sortd[..., 1:] == sortd[..., :-1]],
        axis=-1,
    )
    return jnp.where(dup, -1, sortd)


def tile_frontier(x, expand_width):
    """Repeat per-query values to the flattened [B*W] frontier layout."""
    return jnp.repeat(x, expand_width, axis=0)


def neighbor_form(nbrs, config: SearchConfig):
    """The neighbor table in the form ``config``'s hop reads:
    ``storage.EdgeRows`` where a Pallas kernel reads it
    (``ops.reads_edge_rows``), the dense int32 ``[n, layers, m]``
    otherwise. Either conversion is a full-table copy, so it belongs
    outside any loop over hops."""
    if ops.reads_edge_rows(config.hop_impl, config.edge_impl,
                           config.dist_impl):
        return storage_mod.pack_edges(nbrs)
    return storage_mod.decode_neighbors(nbrs)


# ---------------------------------------------------------------------------
# Concrete searches
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("logn", "m_out", "k", "config"))
def _search_improvised_jit(vectors, nbrs, queries, L, R, rerank_store=None,
                           *, logn, m_out, k, config: SearchConfig):
    """The jitted improvised-search program: ONE static ``config`` instead
    of a kwarg pile, so equal configs share a compiled program — the unit
    ``serve/executor.py`` AOT-compiles and caches.

    ``rerank_store`` is the optional exact(er) sidecar table for
    ``config.rerank > 0`` (DESIGN.md §9): the beam returns its top-``r``
    candidates, which are re-scored exactly against the sidecar (falling
    back to the navigation ``vectors`` when None — a no-op refinement for
    exact codecs) and re-cut to ``k`` — all inside this one jit, so the
    executor's compile accounting sees a single program.
    """
    # once per call and outside the loop, the table takes the form the hop
    # reads (a no-op for the executor's upload, already in that form)
    nbrs = neighbor_form(nbrs, config)
    n = storage_mod.table_n(vectors)
    expand_width = effective_expand_width(config.expand_width, config.ef)
    entries = range_entry_ids(L, jnp.minimum(R, n - 1), n)
    ok = (entries >= L[:, None]) & (entries <= R[:, None])
    entries = jnp.where(ok, entries, -1)
    Lw = tile_frontier(L, expand_width)
    Rw = tile_frontier(R, expand_width)

    # the whole hop dispatches as one unit: config.hop_impl picks the fused
    # megakernel (pallas/xla) or the composed three-op path, inside which
    # the per-op edge_impl/dist_impl knobs still apply
    def hop_fn(u, exp_ok, visited):
        return ops.hop(
            queries, vectors, nbrs, u, Lw, Rw, visited, exp_ok,
            logn=logn, m_out=m_out, skip_layers=config.skip_layers,
            metric=config.metric, impl=config.hop_impl,
            edge_impl=config.edge_impl, dist_impl=config.dist_impl,
        )

    r = max(k, min(config.rerank, config.ef)) if config.rerank else 0
    res = beam_search(
        vectors, queries, entries, None, k=r or k, config=config,
        hop_fn=hop_fn,
    )
    if not r:
        return res
    store = vectors if rerank_store is None else rerank_store
    ids = res.ids                                          # [B, r]
    x = storage_mod.decode_rows(store, jnp.maximum(ids, 0))  # [B, r, d] f32
    qf = queries.astype(jnp.float32)
    if config.metric == "ip":
        dd = -jnp.einsum("bd,brd->br", qf, x)
    else:
        dd = ((x - qf[:, None, :]) ** 2).sum(-1)
    dd = jnp.where(ids < 0, jnp.inf, dd)
    _, take = jax.lax.top_k(-dd, k)
    out_ids = jnp.take_along_axis(ids, take, 1)
    out_dists = jnp.take_along_axis(dd, take, 1)
    out_ids = jnp.where(jnp.isfinite(out_dists), out_ids, -1)
    return SearchResult(out_ids, out_dists, res.n_hops, res.n_dists)


def search_improvised(
    vectors, nbrs, queries, L, R, *, logn, m_out, k,
    config: SearchConfig | None = None, rerank_store=None, ef=None,
    skip_layers=None, metric=None, max_iters=None, expand_width=None,
    dist_impl=None, edge_impl=None, hop_impl=None,
):
    """The paper's query path: beam search on the improvised dedicated graph.

    L, R: int32[B] per-query inclusive rank ranges. ``vectors``/``nbrs`` may
    arrive in any storage codec (bf16/f16/int8/PQ vectors, int16/split ids
    or ``storage.EdgeRows``): the neighbor table takes the form the hop
    reads (:func:`neighbor_form`) once here, outside the hop loop; vectors
    stay
    encoded end-to-end (the distance kernels decode in-register, DESIGN.md
    §9). ``rerank_store`` + ``config.rerank`` enable the in-jit exact
    refinement pass over the sidecar table.

    Knobs come from ``config`` (one frozen ``SearchConfig``); the loose
    kwargs are the deprecation shim.
    """
    config = config_mod.merge(
        config, ef=ef, skip_layers=skip_layers, metric=metric,
        max_iters=max_iters, expand_width=expand_width, dist_impl=dist_impl,
        edge_impl=edge_impl, hop_impl=hop_impl,
        _warn_where="search_improvised",
    )
    return _search_improvised_jit(
        vectors, nbrs, queries, L, R, rerank_store, logn=logn, m_out=m_out,
        k=k, config=config,
    )


@functools.partial(jax.jit, static_argnames=("layer", "k", "config"))
def _search_fixed_layer_jit(vectors, nbrs, queries, seg_lo, seg_hi, *,
                            layer, k, config: SearchConfig):
    nbrs = storage_mod.decode_neighbors(nbrs)
    n = storage_mod.table_n(vectors)
    hi_real = jnp.minimum(seg_hi, n - 1)
    entries = range_entry_ids(seg_lo, hi_real, n)
    # guard: empty / padded-away segments contribute no entry points, and an
    # entry must actually lie inside its segment
    ok = (
        (seg_lo[:, None] <= hi_real[:, None])
        & (entries >= seg_lo[:, None])
        & (entries <= hi_real[:, None])
    )
    entries = jnp.where(ok, entries, -1)
    expand_width = effective_expand_width(config.expand_width, config.ef)
    low = tile_frontier(seg_lo, expand_width)
    hiw = tile_frontier(seg_hi, expand_width)

    def nbr_fn(u):
        row = nbrs[jnp.maximum(u, 0), layer, :]
        ok = (row >= 0) & (row >= low[:, None]) & (row <= hiw[:, None])
        return jnp.where(ok & (u >= 0)[:, None], row, -1)

    return beam_search(vectors, queries, entries, nbr_fn, k=k, config=config)


def search_fixed_layer(
    vectors, nbrs, queries, seg_lo, seg_hi, *, layer, k,
    config: SearchConfig | None = None, ef=None, metric=None, max_iters=None,
    expand_width=None, dist_impl=None, edge_impl=None,
):
    """Beam search on one elemental graph (segment ``[seg_lo, seg_hi]`` at
    ``layer``). Used during construction, and by BasicSearch /
    SuperPostfiltering baselines. ``config.edge_impl`` is accepted for knob
    symmetry; this search's nbr_fn is a plain row gather (no
    improvisation)."""
    config = config_mod.merge(
        config, ef=ef, metric=metric, max_iters=max_iters,
        expand_width=expand_width, dist_impl=dist_impl, edge_impl=edge_impl,
        _warn_where="search_fixed_layer",
    )
    return _search_fixed_layer_jit(
        vectors, nbrs, queries, seg_lo, seg_hi, layer=layer, k=k,
        config=config,
    )


@functools.partial(jax.jit, static_argnames=("mode", "k", "config"))
def _search_filtered_jit(vectors, nbrs, queries, L, R, rng, *, mode, k,
                         config: SearchConfig):
    nbrs = storage_mod.decode_neighbors(nbrs)
    n = storage_mod.table_n(vectors)
    mid = jnp.clip((L + R) // 2, 0, n - 1)
    entries = jnp.stack([mid, jnp.zeros_like(mid) + n // 2], axis=1)

    def filt(ids):
        return (ids >= L[:, None]) & (ids <= R[:, None])

    expand_width = effective_expand_width(config.expand_width, config.ef)
    Lw = tile_frontier(L, expand_width)
    Rw = tile_frontier(R, expand_width)

    def nbr_fn(u):
        row = nbrs[jnp.maximum(u, 0), 0, :]
        ok = (row >= 0) & (u >= 0)[:, None]
        if mode == "in":
            ok &= (row >= Lw[:, None]) & (row <= Rw[:, None])
        return jnp.where(ok, row, -1)

    return beam_search(
        vectors, queries, entries, nbr_fn, k=k, config=config,
        result_filter_fn=filt, rng=rng,
    )


def search_filtered(
    vectors, nbrs, queries, L, R, *, mode, k,
    config: SearchConfig | None = None, ef=None, metric=None, max_iters=None,
    rng=None, expand_width=None, dist_impl=None, edge_impl=None,
):
    """Post-/In-filtering baselines on the root elemental graph (layer 0).

    mode: "post" visits everything, keeps in-range results;
          "in"   only traverses in-range neighbors.
    ``config.edge_impl`` is accepted for knob symmetry (layer-0 row gather,
    no improvisation).
    """
    config = config_mod.merge(
        config, ef=ef, metric=metric, max_iters=max_iters,
        expand_width=expand_width, dist_impl=dist_impl, edge_impl=edge_impl,
        _warn_where="search_filtered",
    )
    return _search_filtered_jit(
        vectors, nbrs, queries, L, R, rng, mode=mode, k=k, config=config,
    )
