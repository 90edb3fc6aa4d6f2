"""Distributed RFANN: iRangeGraph sharded over the ``data`` mesh axis.

Sharding scheme (DESIGN.md §2): objects are split into *contiguous
attribute-rank chunks*, one per data-parallel device group. Each shard holds
its slice of vectors plus a full iRangeGraph (segment tree + elemental
graphs) built on the slice. A query range [L, R] then intersects a
contiguous run of shards; each shard improvises its dedicated graph for the
clipped local range and the per-shard top-k are merged with one all-gather
over the ``data`` axis. The ``model`` axis replicates the index and splits
the query batch (so both axes contribute to serving throughput).

This is the paper's technique made multi-pod: per-shard work is exactly the
single-machine algorithm, and the only cross-device traffic is the k-sized
merge — O(B * k) per query batch, independent of n.

``rfann_serve_step`` is the paper-system dry-run cell: it lowers under the
production mesh with vectors/neighbors sharded on the leading axis. Shards
may be ragged (``build_sharded`` pads the tail, bounds mask the padding)
and may store compact dtypes (bf16 vectors / int16 neighbor ids,
``core/storage.py``); ``shard_topk`` is the per-shard body shared by the
shard_map path and mesh-free hosts.
"""
from __future__ import annotations

import concurrent.futures
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import build as build_mod
from repro.core import config as config_mod
from repro.core import search as search_mod
from repro.core import storage as storage_mod
from repro.core.config import SearchConfig
from repro.core.index import RangeGraphIndex
from repro.kernels import ops

__all__ = [
    "ShardedRangeIndex", "build_sharded", "shard_topk", "merge_topk",
    "rfann_serve_step",
]


class ShardedRangeIndex:
    """Host-side container for the per-shard artifacts (stacked arrays)."""

    def __init__(self, vectors, neighbors, bounds, logn, m, storage=None):
        # vectors: [S, n_shard, d] in storage.vector_dtype
        # neighbors: [S, n_shard, layers, m] in the neighbor codec dtype
        # bounds:  [S, 2] global rank range per shard (inclusive; masks any
        #          padded tail rows out of every query)
        self.vectors = vectors
        self.neighbors = neighbors
        self.bounds = bounds
        self.logn = logn
        self.m = m
        # introspection only: default-derive from the arrays so the field
        # can never contradict what is actually stored
        self.storage = storage or storage_mod.StorageConfig(
            vector_dtype=str(vectors.dtype),
            neighbor_dtype=str(neighbors.dtype),
        )

    @property
    def n_shards(self):
        return self.vectors.shape[0]

    @property
    def nbytes(self) -> int:
        """Real stored footprint of the stacked per-shard tables."""
        return (self.vectors.nbytes + self.neighbors.nbytes
                + self.bounds.nbytes)

    def place(self, mesh: Mesh, config: SearchConfig | None = None):
        """Upload the shards straight to their devices: each stacked table
        goes up with ``NamedSharding(mesh, P("data"))``, so shard ``s``
        lands on the ``data``-axis device that serves it (replicated over
        ``model``) instead of whole on one device first. Neighbors go up
        in the ``storage.EdgeRows`` form where ``config``'s hop reads it
        through a Pallas kernel (``ops.reads_edge_rows``). Returns the
        ``(vectors, neighbors, bounds)`` triple ``rfann_serve_step``
        takes."""
        config = config or SearchConfig()
        put = functools.partial(
            jax.device_put, device=NamedSharding(mesh, P("data")))
        if ops.reads_edge_rows(config.hop_impl, config.edge_impl,
                               config.dist_impl):
            packed = [storage_mod.pack_edges(t) for t in self.neighbors]
            nbrs = storage_mod.EdgeRows(
                put(np.stack([p.rows for p in packed])),
                packed[0].layers, packed[0].m)
        else:
            nbrs = put(self.neighbors)
        return put(self.vectors), nbrs, put(self.bounds)


def build_sharded(
    vectors: np.ndarray, attrs: np.ndarray, n_shards: int,
    cfg: build_mod.BuildConfig | None = None,
    storage: storage_mod.StorageConfig | None = None,
) -> ShardedRangeIndex:
    """Sort globally by attribute, chunk into contiguous rank ranges, build
    one index per shard — in parallel, one shard per local device (and
    embarrassingly parallel across hosts in a real deployment).

    ``n_shards`` need not divide ``n``: shards are ``ceil(n / n_shards)``
    wide and a ragged tail is padded by repeating its last vector row, with
    ``bounds`` holding only the real rank range — the serve path clips every
    query to ``[lo, hi]``, so padded rows are never entered, traversed into,
    or returned. Every shard therefore shares one ``logn``/table shape.
    """
    cfg = cfg or build_mod.BuildConfig()
    storage = storage or storage_mod.default_config()
    if (storage.vector_dtype in ("int8", "pq")
            or storage.neighbor_dtype == "split"):
        # codec structs don't stack into the [S, ...] shard-major arrays
        # this layer shards over; quantized sharded serving is future work
        raise ValueError(
            "build_sharded does not support codec storage "
            f"(vector_dtype={storage.vector_dtype!r}, "
            f"neighbor_dtype={storage.neighbor_dtype!r}); use a plain "
            "float/compact StorageConfig"
        )
    n = vectors.shape[0]
    if not 1 <= n_shards <= n:
        raise ValueError(f"need 1 <= n_shards <= n, got S={n_shards} n={n}")
    order = np.argsort(attrs, kind="stable")
    vs = np.asarray(vectors, np.float32)[order]
    per = -(-n // n_shards)  # ceil: the last shard may be ragged
    slices, bounds = [], []
    for s in range(n_shards):
        lo = s * per
        hi = min(lo + per, n) - 1  # hi < lo marks an all-padding shard
        sl = vs[lo : hi + 1] if hi >= lo else vs[:0]
        if sl.shape[0] < per:
            fill = sl[-1] if sl.shape[0] else vs[-1]
            sl = np.concatenate(
                [sl, np.broadcast_to(fill, (per - sl.shape[0], vs.shape[1]))]
            )
        slices.append(sl)
        bounds.append((lo, hi))
    # shards are independent: on accelerators shard s builds on local
    # device s (mod the count), all devices at once; host devices share
    # the host's cores, so there the shards build one after another
    devices = jax.local_devices()

    def build(s):
        with jax.default_device(devices[s % len(devices)]):
            return build_mod.build_neighbor_table(slices[s], cfg,
                                                  storage=storage)

    workers = 1 if devices[0].platform == "cpu" else \
        min(len(devices), n_shards)
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        tables = list(pool.map(build, range(n_shards)))
    return ShardedRangeIndex(
        np.stack([storage_mod.encode_vectors(sl, storage) for sl in slices]),
        np.stack(tables), np.asarray(bounds, np.int32),
        tables[0].shape[1] - 1, cfg.m, storage,
    )


def shard_topk(
    vec, nbr, bnd, q, Lq, Rq, *,
    logn, m, k, config: SearchConfig | None = None, ef=None,
    expand_width=None, dist_impl=None, edge_impl=None,
):
    """One shard's clipped local search -> global-id top-k candidates.

    The per-shard body of ``rfann_serve_step``, factored out so the same
    code path — including the compact-storage decode and the padded-tail /
    empty-clip masking — runs under shard_map on a ``data`` mesh axis and
    plain per-shard on hosts (tests, single-process serving).

    vec [n_shard, d] (any storage dtype); nbr [n_shard, layers, m] (any
    neighbor codec) or ``storage.EdgeRows``; bnd i32[2] the shard's real
    global rank range; q
    [B, d]; Lq/Rq i32[B] global rank ranges. Engine knobs come from
    ``config`` (loose kwargs = deprecation shim). Returns (ids, dists)
    [B, k] with ids global (-1 padded) and dists inf-padded.
    """
    config = config_mod.merge(
        config, ef=ef, expand_width=expand_width, dist_impl=dist_impl,
        edge_impl=edge_impl, _warn_where="shard_topk",
    )
    # any neighbor codec passes through: the search packs it into the
    # EdgeRows form once per call; vectors stay bf16/f16 down to the kernels
    lo, hi = bnd[0], bnd[1]
    # clip the global range to this shard's rank range, local coords;
    # hi is the REAL range end, so any padded tail rows stay > Rl and are
    # never entered, traversed into, or returned
    Ll = jnp.clip(Lq - lo, 0, vec.shape[0] - 1).astype(jnp.int32)
    Rl = (jnp.minimum(Rq, hi) - lo).astype(jnp.int32)
    empty = (Rq < lo) | (Lq > hi)
    # an empty clip becomes the L > R range, which yields no entry
    # points and therefore no results
    Ll = jnp.where(empty, 1, Ll)
    Rl = jnp.where(empty, 0, Rl)
    res = search_mod.search_improvised(
        vec, nbr, q, Ll, Rl, logn=logn, m_out=m, k=k, config=config,
    )
    ids = jnp.where(
        (res.ids >= 0) & ~empty[:, None], res.ids + lo, -1
    )
    dists = jnp.where(ids >= 0, res.dists, jnp.inf)
    return ids, dists


def merge_topk(all_ids, all_d, k):
    """Merge stacked per-shard candidates [S, B, k] -> global top-k [B, k].

    The one merge both the all-gather path and host-side consumers use.
    """
    S, B = all_ids.shape[0], all_ids.shape[1]
    flat_i = jnp.moveaxis(all_ids, 0, 1).reshape(B, S * k)
    flat_d = jnp.moveaxis(all_d, 0, 1).reshape(B, S * k)
    _, take = jax.lax.top_k(-flat_d, k)
    out_i = jnp.take_along_axis(flat_i, take, 1)
    out_d = jnp.take_along_axis(flat_d, take, 1)
    return out_i, out_d


def rfann_serve_step(
    shard_vectors,    # f32/bf16[S, n_shard, d]   sharded: ("data", None, None)
    shard_neighbors,  # i32/i16[S, n_shard, layers, m] or stacked EdgeRows
                      # (``ShardedRangeIndex.place``), sharded likewise
    shard_bounds,     # i32[S, 2]
    queries,          # f32[B, d]            sharded: ("model", None)
    L, R,             # i32[B] global rank ranges
    *,
    mesh: Mesh,
    logn: int,
    m: int,
    k: int,
    config: SearchConfig | None = None,
    ef: int | None = None,
    expand_width: int | None = None,
    dist_impl: str | None = None,
    edge_impl: str | None = None,
):
    """Batched distributed RFANN query under shard_map. Engine knobs come
    from ``config`` (loose kwargs = deprecation shim)."""
    config = config_mod.merge(
        config, ef=ef, expand_width=expand_width, dist_impl=dist_impl,
        edge_impl=edge_impl, _warn_where="rfann_serve_step",
    )

    have_pod = "pod" in mesh.shape
    query_spec = P(("pod", "model")) if have_pod else P("model")

    def local(vec, nbr, bnd, q, Lq, Rq):
        # leading shard dim is mapped over the data axis (per leaf, so a
        # stacked EdgeRows / codec struct unstacks too)
        nbr = jax.tree.map(lambda a: a[0], nbr)
        ids, dists = shard_topk(
            vec[0], nbr, bnd[0], q, Lq, Rq,
            logn=logn, m=m, k=k, config=config,
        )
        # merge across the data axis: gather all shards' top-k
        all_ids = jax.lax.all_gather(ids, "data", axis=0)      # [S, B, k]
        all_d = jax.lax.all_gather(dists, "data", axis=0)
        return merge_topk(all_ids, all_d, k)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P("data"), P("data"), P("data"),
            query_spec, query_spec, query_spec,
        ),
        out_specs=(query_spec, query_spec),
        check_vma=False,
    )
    return fn(shard_vectors, shard_neighbors, shard_bounds, queries, L, R)


def make_serve_jit(mesh: Mesh, *, logn, m, k, config=None, ef=None,
                   expand_width=None, dist_impl=None, edge_impl=None):
    """jit wrapper with shardings bound — what the dry-run lowers."""
    config = config_mod.merge(
        config, ef=ef, expand_width=expand_width, dist_impl=dist_impl,
        edge_impl=edge_impl, _warn_where="make_serve_jit",
    )

    @functools.partial(jax.jit, static_argnums=())
    def step(shard_vectors, shard_neighbors, shard_bounds, queries, L, R):
        return rfann_serve_step(
            shard_vectors, shard_neighbors, shard_bounds, queries, L, R,
            mesh=mesh, logn=logn, m=m, k=k, config=config,
        )

    return step
