"""Public iRangeGraph index API.

``RangeGraphIndex.build(vectors, attrs)`` sorts objects by attribute value
(stable, so duplicates keep insertion order — paper §3.4's duplicate
discussion), builds the packed elemental-graph table, and exposes:

  * ``search(queries, ranges, ...)`` — RFANN in attribute-VALUE space;
  * ``search_ranks(queries, L, R, ...)`` — RFANN in rank space;
  * value<->rank mapping via binary search (paper §2.2);
  * serialization (msgpack + zstd, content-checksummed);
  * compact storage (``core/storage.py``): vectors in bf16/f16 and neighbor
    ids in int16 when they fit, decoded at the consumption edges —
    ``nbytes`` reports the real footprint either way.
"""
from __future__ import annotations

import dataclasses
import hashlib
import io
import math
import warnings
import zlib

import jax.numpy as jnp
import msgpack
import numpy as np

from repro import compressio

from repro.core import build as build_mod
from repro.core import config as config_mod
from repro.core import search as search_mod
from repro.core import storage as storage_mod

__all__ = [
    "IndexCorruptionError", "RangeGraphIndex", "brute_force", "recall",
]


class IndexCorruptionError(IOError):
    """A saved index failed an integrity check on load.

    ``field`` names the offending array (``"vectors"``, ``"neighbors"``,
    ...) or ``"envelope"`` for whole-file damage, so operators see *what*
    rotted instead of an opaque unpack/reshape error. Subclasses
    ``IOError`` so historical ``except IOError`` call sites keep working.
    """

    def __init__(self, field: str, message: str):
        super().__init__(f"corrupt index [{field}]: {message}")
        self.field = field


def _pack_array(a: np.ndarray) -> dict:
    data = a.tobytes()
    # per-array checksum: the envelope sha256 says "this file rotted",
    # crc32 here says *which field* — and survives partial/streamed writes
    return {"dtype": str(a.dtype), "shape": list(a.shape), "data": data,
            "crc32": zlib.crc32(data)}


def _unpack_array(d: dict, field: str) -> np.ndarray:
    data = d["data"]
    dtype = storage_mod.np_dtype(d["dtype"])
    want = int(np.prod(d["shape"], dtype=np.int64)) * dtype.itemsize
    if len(data) != want:
        raise IndexCorruptionError(
            field, f"truncated: {len(data)} bytes, expected {want} "
            f"for shape {d['shape']} {d['dtype']}"
        )
    crc = d.get("crc32")
    if crc is None:
        warnings.warn(
            f"index file predates per-array checksums ({field} unchecked); "
            "re-save to add them", stacklevel=3,
        )
    elif zlib.crc32(data) != crc:
        raise IndexCorruptionError(field, "checksum mismatch (bit flip?)")
    # frombuffer views the msgpack bytes read-only; copy so a loaded index
    # is equivalent to a built one (in-place consumers must not raise)
    a = np.frombuffer(data, dtype=dtype)
    return a.reshape(d["shape"]).copy()


@dataclasses.dataclass
class RangeGraphIndex:
    vectors: np.ndarray        # [n, d] table or codec struct, rank order
    attrs: np.ndarray          # f64[n], sorted attribute values
    perm: np.ndarray           # original index of rank i
    neighbors: np.ndarray      # [n, layers, m] or SplitNeighbors struct
    m: int
    logn: int
    build_cfg: build_mod.BuildConfig
    storage: storage_mod.StorageConfig = dataclasses.field(
        default_factory=storage_mod.StorageConfig
    )
    # higher-fidelity rerank sidecar (storage.rerank_dtype): None, an
    # [n, d] array, or Int8Vectors — feeds SearchConfig.rerank refinement
    rerank: object = None

    # -- construction -------------------------------------------------------
    @classmethod
    def build(
        cls,
        vectors: np.ndarray,
        attrs: np.ndarray,
        cfg: build_mod.BuildConfig | None = None,
        *,
        verbose: bool = False,
        prune_impl: str | None = None,
        storage: storage_mod.StorageConfig | None = None,
    ) -> "RangeGraphIndex":
        """``prune_impl`` overrides ``cfg.prune_impl`` (the construction-prune
        backend: "auto" | "pallas" | "xla" | "legacy", see kernels/ops).
        ``storage`` picks the stored dtypes (default ``REPRO_STORAGE`` env or
        f32); construction math always runs in f32."""
        cfg = cfg or build_mod.BuildConfig()
        if prune_impl is not None:
            cfg = dataclasses.replace(cfg, prune_impl=prune_impl)
        storage = storage or storage_mod.default_config()
        vectors = np.asarray(vectors, np.float32)
        attrs = np.asarray(attrs, np.float64)
        n = vectors.shape[0]
        perm = np.argsort(attrs, kind="stable").astype(np.int64)
        vectors = np.ascontiguousarray(vectors[perm])
        attrs = attrs[perm]
        nbrs = build_mod.build_neighbor_table(
            vectors, cfg, verbose=verbose, storage=storage
        )
        logn = int(math.ceil(math.log2(max(n, 2))))
        rerank = storage_mod.encode_rerank(vectors, storage)
        vectors = storage_mod.encode_vectors(vectors, storage)
        return cls(vectors, attrs, perm, nbrs, cfg.m, logn, cfg,
                   storage=storage, rerank=rerank)

    def astype_storage(
        self, storage: storage_mod.StorageConfig
    ) -> "RangeGraphIndex":
        """Re-encode the stored arrays under ``storage`` — no rebuild.

        The graph is unchanged, so neighbor ids are bit-identical across
        codecs and only vector precision changes (bf16/f16/int8/pq round
        once; going back to f32 does not restore already-rounded values).
        Re-encoding starts from the highest-fidelity source available: the
        rerank sidecar when present, else the stored vectors."""
        src = (storage_mod.decode_vectors(self.rerank)
               if self.rerank is not None
               else storage_mod.decode_vectors(self.vectors))
        return dataclasses.replace(
            self,
            vectors=storage_mod.encode_vectors(src, storage),
            neighbors=storage_mod.encode_neighbors(
                storage_mod.decode_neighbors(self.neighbors), self.n, storage
            ),
            rerank=storage_mod.encode_rerank(src, storage),
            storage=storage,
        )

    @property
    def n(self) -> int:
        return storage_mod.table_n(self.vectors)

    @property
    def dim(self) -> int:
        return storage_mod.table_dim(self.vectors)

    @property
    def nbytes(self) -> int:
        """Real stored footprint — sums codec-struct leaves (the two
        hot-path tables dominate; ``attrs`` stays f64 for rank fidelity)."""
        return (storage_mod.table_nbytes(self.vectors)
                + storage_mod.table_nbytes(self.neighbors)
                + storage_mod.table_nbytes(self.rerank)
                + self.attrs.nbytes)

    # -- range mapping -------------------------------------------------------
    def ranks_of(self, lo_val, hi_val):
        """Map inclusive attribute-value ranges to inclusive rank ranges."""
        L = np.searchsorted(self.attrs, np.asarray(lo_val), side="left")
        R = np.searchsorted(self.attrs, np.asarray(hi_val), side="right") - 1
        return L.astype(np.int32), R.astype(np.int32)

    # -- query ---------------------------------------------------------------
    def search_ranks(
        self, queries, L, R, *, k=10, config=None, ef=None, skip_layers=None,
        metric=None, expand_width=None, dist_impl=None, edge_impl=None,
    ) -> search_mod.SearchResult:
        """RFANN in rank space: per-query inclusive rank ranges [L, R].

        config: one frozen ``SearchConfig`` holding every engine knob
        (``k`` stays per-call); the loose kwargs are the deprecation shim —
        non-None values override the config. For repeated serving traffic
        prefer ``serve/executor.py::SearchExecutor`` (compile cache +
        batch/k buckets + AOT warmup) over calling this in a loop.
        """
        config = config_mod.merge(
            config, ef=ef, skip_layers=skip_layers, metric=metric,
            expand_width=expand_width, dist_impl=dist_impl,
            edge_impl=edge_impl, _warn_where="RangeGraphIndex.search_ranks",
        )
        return search_mod.search_improvised(
            storage_mod.as_device(self.vectors),
            storage_mod.as_device(self.neighbors),
            jnp.asarray(queries, jnp.float32),
            jnp.asarray(L, jnp.int32),
            jnp.asarray(R, jnp.int32),
            logn=self.logn,
            m_out=self.m,
            k=k,
            config=config,
            rerank_store=storage_mod.as_device(self.rerank),
        )

    def search(self, queries, lo_val, hi_val, **kw) -> search_mod.SearchResult:
        L, R = self.ranks_of(lo_val, hi_val)
        return self.search_ranks(queries, L, R, **kw)

    def original_ids(self, rank_ids):
        """Map rank-space result ids back to the caller's original ids."""
        rank_ids = np.asarray(rank_ids)
        out = np.where(rank_ids >= 0, self.perm[np.maximum(rank_ids, 0)], -1)
        return out

    # -- ground truth ---------------------------------------------------------
    def brute_force(self, queries, L, R, *, k=10, metric="l2"):
        """Exact in-range top-k (== the Pre-filtering strategy). numpy."""
        return brute_force(storage_mod.decode_vectors(self.vectors),
                           queries, L, R, k=k, metric=metric)

    # -- serialization ---------------------------------------------------------
    def save(self, path: str):
        """Codec structs flatten to one crc32-checked field per leaf
        (``vectors``/``vec_scales``/``vec_codebook``, ``neighbors``/
        ``neighbors_lo``, ``rerank``/``rerank_scales``) so a bit flip in a
        scale or codebook array is named on load, not just "vectors"."""
        payload = {
            "attrs": _pack_array(self.attrs),
            "perm": _pack_array(self.perm),
            "m": self.m,
            "logn": self.logn,
            "cfg": dataclasses.asdict(self.build_cfg),
            "storage": dataclasses.asdict(self.storage),
        }
        if isinstance(self.vectors, storage_mod.Int8Vectors):
            payload["vectors"] = _pack_array(self.vectors.codes)
            payload["vec_scales"] = _pack_array(self.vectors.scales)
        elif isinstance(self.vectors, storage_mod.PQVectors):
            payload["vectors"] = _pack_array(self.vectors.codes)
            payload["vec_codebook"] = _pack_array(self.vectors.codebook)
        else:
            payload["vectors"] = _pack_array(self.vectors)
        if isinstance(self.neighbors, storage_mod.SplitNeighbors):
            payload["neighbors"] = _pack_array(self.neighbors.hi)
            payload["neighbors_lo"] = _pack_array(self.neighbors.lo)
        else:
            payload["neighbors"] = _pack_array(self.neighbors)
        if isinstance(self.rerank, storage_mod.Int8Vectors):
            payload["rerank"] = _pack_array(self.rerank.codes)
            payload["rerank_scales"] = _pack_array(self.rerank.scales)
        elif self.rerank is not None:
            payload["rerank"] = _pack_array(self.rerank)
        raw = msgpack.packb(payload)
        digest = hashlib.sha256(raw).hexdigest()
        blob = msgpack.packb({"sha256": digest, "payload": raw})
        with open(path, "wb") as f:
            f.write(compressio.compress(blob, level=3))

    @classmethod
    def load(cls, path: str) -> "RangeGraphIndex":
        """Load with integrity checking: whole-file (envelope sha256) and
        per-array (crc32 + size) — any mismatch raises
        :class:`IndexCorruptionError` naming the offending field.
        Pre-checksum files (no per-array crc32) still load, with a
        warning."""
        with open(path, "rb") as f:
            blob = f.read()
        try:
            blob = compressio.decompress(blob)
            outer = msgpack.unpackb(blob)
            raw = outer["payload"]
            digest = outer["sha256"]
        except IndexCorruptionError:
            raise
        except Exception as e:  # zlib/zstd/msgpack: the file is not ours
            raise IndexCorruptionError(
                "envelope", f"unreadable file {path}: {e}"
            ) from e
        if hashlib.sha256(raw).hexdigest() != digest:
            raise IndexCorruptionError(
                "envelope", f"payload checksum mismatch loading {path}"
            )
        try:
            p = msgpack.unpackb(raw)
        except Exception as e:
            raise IndexCorruptionError(
                "envelope", f"payload unpack failed loading {path}: {e}"
            ) from e
        vectors = _unpack_array(p["vectors"], "vectors")
        if "vec_scales" in p:
            vectors = storage_mod.Int8Vectors(
                vectors, _unpack_array(p["vec_scales"], "vec_scales")
            )
        elif "vec_codebook" in p:
            vectors = storage_mod.PQVectors(
                vectors, _unpack_array(p["vec_codebook"], "vec_codebook")
            )
        neighbors = _unpack_array(p["neighbors"], "neighbors")
        if "neighbors_lo" in p:
            neighbors = storage_mod.SplitNeighbors(
                neighbors, _unpack_array(p["neighbors_lo"], "neighbors_lo")
            )
        rerank = None
        if "rerank" in p:
            rerank = _unpack_array(p["rerank"], "rerank")
            if "rerank_scales" in p:
                rerank = storage_mod.Int8Vectors(
                    rerank, _unpack_array(p["rerank_scales"], "rerank_scales")
                )
        st = p.get("storage")
        if st is None:  # pre-storage files: the stored dtypes ARE the config
            st = {"vector_dtype": str(vectors.dtype),
                  "neighbor_dtype": str(neighbors.dtype)}
        return cls(
            vectors=vectors,
            attrs=_unpack_array(p["attrs"], "attrs"),
            perm=_unpack_array(p["perm"], "perm"),
            neighbors=neighbors,
            m=p["m"],
            logn=p["logn"],
            build_cfg=build_mod.BuildConfig(**p["cfg"]),
            storage=storage_mod.StorageConfig(**st),
            rerank=rerank,
        )


def brute_force(vecs, queries, L, R, *, k=10, metric="l2"):
    """Exact in-range top-k over rank-ordered f32 ``vecs [n, d]`` for
    inclusive rank ranges ``[L, R]`` (the Pre-filtering strategy): the
    ground truth every search path is checked against. numpy."""
    q = np.asarray(queries, np.float32)
    L = np.asarray(L)
    R = np.asarray(R)
    ids = np.full((q.shape[0], k), -1, np.int64)
    dists = np.full((q.shape[0], k), np.inf, np.float32)
    for i in range(q.shape[0]):
        lo, hi = int(L[i]), int(R[i])
        if hi < lo:
            continue
        x = vecs[lo : hi + 1]
        if metric == "l2":
            d = ((x - q[i]) ** 2).sum(1)
        else:
            d = -(x @ q[i])
        kk = min(k, d.shape[0])
        part = np.argpartition(d, kk - 1)[:kk]
        part = part[np.argsort(d[part], kind="stable")]
        ids[i, :kk] = part + lo
        dists[i, :kk] = d[part]
    return ids, dists


def recall(result_ids, gt_ids) -> float:
    """Mean recall@k of result ids vs ground-truth ids (both [B, k])."""
    result_ids = np.asarray(result_ids)
    gt_ids = np.asarray(gt_ids)
    hits = 0
    total = 0
    for r, g in zip(result_ids, gt_ids):
        gset = set(int(x) for x in g if x >= 0)
        if not gset:
            continue
        hits += len(gset & set(int(x) for x in r if x >= 0))
        total += len(gset)
    return hits / max(total, 1)
