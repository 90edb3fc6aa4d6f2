"""Storage codecs: compact floats, quantized vectors, narrow neighbor ids.

The index's HBM footprint and per-hop bandwidth are the two largest arrays
every hop reads — the vector table ``[n, d]`` and the packed elemental-graph
table ``[n, logn+1, m]`` (DESIGN.md §storage, §9). This module is the ONE
place their storage dtypes are chosen, encoded, and decoded:

  * **Vectors** store as ``float32`` (default), ``bfloat16`` (the compact
    default — f32's full exponent range, so no scale bookkeeping),
    ``float16`` (for CPU hosts where bf16 arithmetic emulation is slow),
    per-vector scaled ``int8`` (:class:`Int8Vectors`: ``codes int8[n, d]`` +
    ``scales f32[n]``, symmetric max-abs quantization), or product
    quantization ``pq`` (:class:`PQVectors`: ``codes uint8[n, M]`` + a
    ``codebook f32[M, 256, d/M]`` trained by a deterministic k-means).
    Every consumer computes distances in f32: the Pallas kernels dequantize
    in VMEM registers right after the row DMA (the gather scratch holds the
    *stored* rows, so the bandwidth saving survives end-to-end — no widened
    table ever hits HBM), the jnp contracts decode through
    :func:`decode_rows` in ``kernels/ref.py``, and numpy consumers
    (``brute_force``) decode through :func:`decode_vectors`.
  * **Neighbor ids** store as ``int16`` when every id fits (``n <= 32768``),
    ``int32`` otherwise (``neighbor_dtype="auto"``), or as the ``"split"``
    codec (:class:`SplitNeighbors`): elemental-graph edges at layer ``l``
    stay inside their node's layer-``l`` segment of width ``2^(logn-l)``,
    so every layer whose segments hold ≤128 nodes stores **int8 offsets
    from the segment base** instead of absolute ids — at the bench shapes
    that is 8 of ~14 layers, and it is what pushes the whole-index ratio
    past what vector codecs alone can reach. There is ONE sentinel
    convention: ``-1`` is the absent-edge marker in *every* storage dtype
    (including the int8 offsets), so decode widens/rebases without a
    special case and ids are bit-identical across codecs.

Decode-at-the-edge: stored arrays flow as far as possible — through
``RangeGraphIndex`` storage, serialization, and into the jit boundary — and
widen exactly once per consumer: neighbor tables at the top of the jitted
searches (``core/search.py``) and in ``kernels/ops.py``; vector tables never
widen outside a kernel register file (§9's fused-decode contract).

Reranking: quantized distances can swap near-ties, so ``rerank_dtype``
declares an optional exact(er) sidecar table the jitted search re-scores its
top-``r`` candidates against (``SearchConfig.rerank``). The PQ profile pairs
a ``uint8`` navigation table with an int8 rerank sidecar; the footprint gate
accounts for both (``nav`` vs total ratio, ``benchmarks/ci_gate.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import knobs as knobs_mod

__all__ = [
    "StorageConfig",
    "default_config",
    "np_dtype",
    "resolve_neighbor_dtype",
    "encode_vectors",
    "decode_vectors",
    "encode_neighbors",
    "decode_neighbors",
    "encode_rerank",
    "decode_rows",
    "train_pq",
    "table_n",
    "table_dim",
    "table_nbytes",
    "as_device",
    "split_layer",
    "Int8Vectors",
    "PQVectors",
    "SplitNeighbors",
    "EdgeRows",
    "pack_edges",
    "NEIGHBOR_SENTINEL",
    "PQ_CENTROIDS",
]

# The one absent-edge marker, in every storage dtype.
NEIGHBOR_SENTINEL = -1

# Centroids per PQ subspace: one uint8 code book.
PQ_CENTROIDS = 256

_VECTOR_DTYPES = ("float32", "bfloat16", "float16", "int8", "pq")
_NEIGHBOR_DTYPES = ("auto", "int16", "int32", "split")
_RERANK_DTYPES = ("none", "int8", "bfloat16", "float16", "float32")

# numpy resolves "bfloat16" only after ml_dtypes registration (importing
# jax.numpy above guarantees it); keep an explicit map so unpacking a saved
# index never depends on registration order.
_NP_DTYPES = {
    "float32": np.dtype(np.float32),
    "bfloat16": np.dtype(jnp.bfloat16),
    "float16": np.dtype(np.float16),
    "int16": np.dtype(np.int16),
    "int32": np.dtype(np.int32),
    "int8": np.dtype(np.int8),
    "uint8": np.dtype(np.uint8),
}


class Int8Vectors(NamedTuple):
    """Per-vector symmetric int8 quantization: ``x ≈ codes * scales[:,None]``.

    codes:  int8[n, d], values in [-127, 127]
    scales: f32[n], ``max|x_i| / 127`` per row (1.0 for all-zero rows)

    A NamedTuple is a registered jax pytree, so the pair flows through
    ``jnp.asarray`` uploads, jit arguments, and AOT-compiled executables with
    the structure folded into the trace signature — the executor's
    zero-post-warmup-compile guarantee is untouched.
    """

    codes: Any
    scales: Any


class PQVectors(NamedTuple):
    """Product quantization: ``x[i] ≈ concat_j codebook[j, codes[i, j]]``.

    codes:    uint8[n, M] — per-subspace centroid index
    codebook: f32[M, 256, dsub] — per-subspace centroids, ``dsub = d // M``
    """

    codes: Any
    codebook: Any


class SplitNeighbors(NamedTuple):
    """Segment-offset neighbor codec (DESIGN.md §9).

    hi: int16/int32[n, split, m]          — absolute ids, layers [0, split)
    lo: int8[n, logn+1-split, m]          — offsets from the node's own
        layer-``l`` segment base ``(u >> (logn-l)) << (logn-l)``, layers
        [split, logn]; ``-1`` stays the absent-edge sentinel.

    ``split = max(0, logn - 7)``: below it segments are wider than 128 nodes
    and offsets would overflow int8.
    """

    hi: Any
    lo: Any


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class EdgeRows:
    """Row-DMA form of a neighbor table, the one the Pallas kernels read.

    rows: int32[n*R, 128] — node ``u``'s ``K = layers*m`` edges (layer
        major, as in ``[n, layers, m]``) padded with ``-1`` to ``R =
        ceil(K/128)`` whole 128-lane rows ``u*R .. u*R+R-1``.

    On a TPU an int32 ``[n, layers, m]`` or ``[n, K]`` array gets a
    node-minor default layout, so a node's edges are not contiguous and a
    kernel fed that array pays a full-table transpose on every call. Here
    one node is one contiguous ``R``-row DMA. ``layers`` and ``m`` are
    static pytree metadata (part of a jit signature, like a codec's
    structure); :func:`decode_neighbors` unpacks to ``[n, layers, m]``.
    """

    rows: Any
    layers: int
    m: int

    @property
    def R(self) -> int:
        return -(-self.layers * self.m // 128)

    def tree_flatten(self):
        return (self.rows,), (self.layers, self.m)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def pack_edges(nbrs) -> EdgeRows:
    """Neighbor table (any codec, numpy or jnp) -> :class:`EdgeRows`."""
    if isinstance(nbrs, EdgeRows):
        return nbrs
    nbrs = decode_neighbors(nbrs)
    n, layers, m = nbrs.shape
    K = layers * m
    Kp = -(-K // 128) * 128
    xp = np if isinstance(nbrs, np.ndarray) else jnp
    flat = nbrs.reshape(n, K)
    if Kp != K:
        flat = xp.pad(flat, ((0, 0), (0, Kp - K)),
                      constant_values=NEIGHBOR_SENTINEL)
    return EdgeRows(flat.reshape(n * (Kp // 128), 128), layers, m)


def split_layer(logn: int) -> int:
    """First layer whose segment offsets fit int8 (segment width <= 128)."""
    return max(0, logn - 7)


@dataclasses.dataclass(frozen=True)
class StorageConfig:
    """Storage codecs for the hot-path tables.

    vector_dtype:   "float32" | "bfloat16" | "float16" | "int8" | "pq" —
      math stays f32 everywhere; the quantized codecs decode inside the
      kernels (DESIGN.md §9).
    neighbor_dtype: "auto" | "int16" | "int32" | "split" — "auto" picks the
      narrowest width that holds every id of an ``n``-object index;
      explicit "int16" raises at encode time when ids don't fit; "split"
      stores int8 segment offsets for the narrow layers (requires a
      segment-aligned elemental-graph table, i.e. every real index).
    rerank_dtype:   "none" | "int8" | "bfloat16" | "float16" | "float32" —
      optional exact(er) sidecar the search re-scores top-``r`` candidates
      against (``SearchConfig.rerank``); "none" reranks against the stored
      navigation vectors, which is a no-op refinement for exact codecs.
    pq_m:           subspace count for "pq" (0 = auto: ``d // 4`` when d is
      divisible by 4, else ``d``).

    The default is the full-width f32/int32 baseline; :meth:`compact`,
    :meth:`int8` and :meth:`pq` opt into the codecs.
    """

    vector_dtype: str = "float32"
    neighbor_dtype: str = "int32"
    rerank_dtype: str = "none"
    pq_m: int = 0

    def __post_init__(self):
        if self.vector_dtype not in _VECTOR_DTYPES:
            raise ValueError(
                f"vector_dtype {self.vector_dtype!r} not in {_VECTOR_DTYPES}"
            )
        if self.neighbor_dtype not in _NEIGHBOR_DTYPES:
            raise ValueError(
                f"neighbor_dtype {self.neighbor_dtype!r} not in "
                f"{_NEIGHBOR_DTYPES}"
            )
        if self.rerank_dtype not in _RERANK_DTYPES:
            raise ValueError(
                f"rerank_dtype {self.rerank_dtype!r} not in {_RERANK_DTYPES}"
            )
        if self.pq_m < 0:
            raise ValueError(f"pq_m must be >= 0, got {self.pq_m}")

    @classmethod
    def compact(cls, vector_dtype: str = "bfloat16") -> "StorageConfig":
        """The halved-footprint configuration (bf16 + narrow ids)."""
        return cls(vector_dtype=vector_dtype, neighbor_dtype="auto")

    @classmethod
    def int8(cls) -> "StorageConfig":
        """Scaled-int8 vectors + split neighbor offsets (~0.33 ratio)."""
        return cls(vector_dtype="int8", neighbor_dtype="split")

    @classmethod
    def pq(cls, pq_m: int = 0) -> "StorageConfig":
        """PQ navigation vectors + split offsets + int8 rerank sidecar.

        The navigation tables alone reach ~0.27 of the f32 footprint; the
        int8 sidecar (for ``SearchConfig.rerank``) is what holds the recall
        gate, and the footprint gate accounts for it separately.
        """
        return cls(vector_dtype="pq", neighbor_dtype="split",
                   rerank_dtype="int8", pq_m=pq_m)


def default_config() -> StorageConfig:
    """StorageConfig for callers that pass ``storage=None``.

    ``REPRO_STORAGE`` overrides: "compact" (bf16 + auto-narrow ids), "f16"
    (f16 + auto-narrow ids), "int8" (scaled int8 + split offsets), "pq"
    (PQ + split offsets + int8 rerank), "f32"/unset (full precision). This
    is the hook the CI storage legs use to force every build through a
    codec (docs/KNOBS.md).
    """
    env = (knobs_mod.get_str("REPRO_STORAGE") or "").strip().lower()
    if env in ("", "f32", "float32"):
        return StorageConfig()
    if env == "compact":
        return StorageConfig.compact()
    if env in ("f16", "float16"):
        return StorageConfig.compact("float16")
    if env == "int8":
        return StorageConfig.int8()
    if env == "pq":
        return StorageConfig.pq()
    raise ValueError(
        f"REPRO_STORAGE={env!r}: expected 'compact', 'f16', 'int8', 'pq' "
        f"or 'f32'"
    )


def np_dtype(name: str) -> np.dtype:
    """Resolve a serialized dtype string, including the ml_dtypes names."""
    if name in _NP_DTYPES:
        return _NP_DTYPES[name]
    return np.dtype(name)


def resolve_neighbor_dtype(n: int, spec: str = "auto") -> np.dtype:
    """Narrowest id dtype for an ``n``-object table under ``spec``.

    For ``spec="split"`` this resolves the dtype of the *wide* (absolute-id)
    layers; the narrow layers are always int8 offsets.
    """
    fits16 = (
        n - 1 <= np.iinfo(np.int16).max  # replint: allow[R5] capacity math
    )
    if spec == "int32":
        return _NP_DTYPES["int32"]
    if spec == "int16":
        if not fits16:
            raise ValueError(
                f"neighbor_dtype=int16 cannot hold ids up to {n - 1} "
                f"(max {np.iinfo(np.int16).max})"  # replint: allow[R5] error message cites the dtype ceiling
            )
        return _NP_DTYPES["int16"]
    if spec in ("auto", "split"):
        return _NP_DTYPES["int16" if fits16 else "int32"]
    raise ValueError(f"neighbor_dtype {spec!r} not in {_NEIGHBOR_DTYPES}")


# ---------------------------------------------------------------------------
# vector codecs
# ---------------------------------------------------------------------------

def _encode_int8(vectors: np.ndarray) -> Int8Vectors:
    v = np.asarray(vectors, np.float32)
    amax = np.abs(v).max(axis=1)
    scales = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    codes = np.clip(np.rint(v / scales[:, None]), -127, 127).astype(np.int8)
    return Int8Vectors(np.ascontiguousarray(codes), scales)


def resolve_pq_m(d: int, pq_m: int = 0) -> int:
    """Subspace count: explicit (must divide d) or auto ``d // 4``."""
    if pq_m:
        if d % pq_m:
            raise ValueError(f"pq_m={pq_m} does not divide d={d}")
        return pq_m
    return d // 4 if d % 4 == 0 and d >= 4 else d


def train_pq(vectors, pq_m: int = 0, *, seed: int = 0, iters: int = 8,
             sample: int = 4096) -> PQVectors:
    """Deterministic per-subspace k-means PQ (numpy, host-side).

    Subsamples up to ``sample`` training rows per subspace, runs ``iters``
    Lloyd iterations from a seeded init (empty clusters keep their previous
    centroid), then encodes every row. Same (vectors, pq_m, seed) ->
    bit-identical codebook on every host.
    """
    v = np.asarray(vectors, np.float32)
    n, d = v.shape
    M = resolve_pq_m(d, pq_m)
    dsub = d // M
    rng = np.random.default_rng(seed)
    train_idx = (np.arange(n) if n <= sample
                 else rng.choice(n, sample, replace=False))
    codebook = np.empty((M, PQ_CENTROIDS, dsub), np.float32)
    codes = np.empty((n, M), np.uint8)
    for j in range(M):
        sub = v[:, j * dsub:(j + 1) * dsub]
        train = sub[train_idx]
        init = rng.choice(train.shape[0], PQ_CENTROIDS,
                          replace=train.shape[0] < PQ_CENTROIDS)
        cent = train[init].copy()
        for _ in range(iters):
            # [S, 256] squared distances; argmin assign; mean update
            d2 = ((train[:, None, :] - cent[None, :, :]) ** 2).sum(-1)
            assign = d2.argmin(1)
            for c in range(PQ_CENTROIDS):
                sel = assign == c
                if sel.any():
                    cent[c] = train[sel].mean(0)
        codebook[j] = cent
        d2 = ((sub[:, None, :] - cent[None, :, :]) ** 2).sum(-1)
        codes[:, j] = d2.argmin(1).astype(np.uint8)
    return PQVectors(np.ascontiguousarray(codes),
                     np.ascontiguousarray(codebook))


def encode_vectors(vectors, cfg: StorageConfig):
    """Vector table -> its storage representation (host-side, numpy).

    Returns a plain ndarray for the float codecs, :class:`Int8Vectors` /
    :class:`PQVectors` for the quantized ones.
    """
    if cfg.vector_dtype == "int8":
        return _encode_int8(vectors)
    if cfg.vector_dtype == "pq":
        return train_pq(vectors, cfg.pq_m)
    dt = np_dtype(cfg.vector_dtype)
    vectors = np.asarray(vectors)
    if vectors.dtype == dt:
        return vectors
    return np.ascontiguousarray(vectors.astype(dt))


def decode_vectors(vectors) -> np.ndarray:
    """Vector table -> f32 numpy (``brute_force``, oracle baselines).

    jnp consumers skip this: kernels decode per-row in VMEM registers
    (:func:`decode_rows` is the in-trace contract), so the stored table is
    what actually crosses HBM.
    """
    if isinstance(vectors, Int8Vectors):
        codes = np.asarray(vectors.codes, np.float32)
        return codes * np.asarray(vectors.scales, np.float32)[:, None]
    if isinstance(vectors, PQVectors):
        codes = np.asarray(vectors.codes)
        cb = np.asarray(vectors.codebook, np.float32)
        M, _, dsub = cb.shape
        out = cb[np.arange(M)[None, :], codes.astype(np.int64)]  # [n, M, dsub]
        return np.ascontiguousarray(out.reshape(codes.shape[0], M * dsub))
    vectors = np.asarray(vectors)
    if vectors.dtype == np.float32:
        return vectors
    return np.ascontiguousarray(vectors.astype(np.float32))


def decode_rows(table, ids):
    """Gather + decode rows -> f32, numpy OR inside a trace.

    ``ids`` must already be clipped non-negative (callers use
    ``maximum(ids, 0)`` and mask afterwards, the ``kernels/ref.py``
    convention). For plain arrays this is the historical widening gather;
    for the quantized codecs it is the jnp contract the Pallas kernels'
    in-VMEM decode is pinned against (bit-identical under f32 ordering,
    ``tests/test_codecs.py``).
    """
    if isinstance(table, Int8Vectors):
        x = table.codes[ids].astype(jnp.float32
                                    if not isinstance(table.codes, np.ndarray)
                                    else np.float32)
        s = table.scales[ids]
        return x * s[..., None]
    if isinstance(table, PQVectors):
        cb = table.codebook
        M, K, dsub = cb.shape
        codes = table.codes[ids]
        if isinstance(cb, np.ndarray):
            out = cb[np.arange(M), codes.astype(np.int64)]
            return out.reshape(*codes.shape[:-1], M * dsub).astype(np.float32)
        flat = cb.reshape(M * K, dsub)
        idx = codes.astype(jnp.int32) + jnp.arange(M, dtype=jnp.int32) * K
        out = jnp.take(flat, idx.reshape(-1), axis=0)
        return out.reshape(*codes.shape, dsub).reshape(
            *codes.shape[:-1], M * dsub)
    x = table[ids]
    if isinstance(x, np.ndarray):
        return x.astype(np.float32)
    return x.astype(jnp.float32)


def encode_rerank(vectors, cfg: StorageConfig):
    """f32 vector table -> the rerank sidecar, or None for "none"."""
    if cfg.rerank_dtype == "none":
        return None
    if cfg.rerank_dtype == "int8":
        return _encode_int8(vectors)
    dt = np_dtype(cfg.rerank_dtype)
    return np.ascontiguousarray(np.asarray(vectors).astype(dt))


# ---------------------------------------------------------------------------
# neighbor codecs
# ---------------------------------------------------------------------------

def _encode_split(nbrs: np.ndarray, n: int, cfg: StorageConfig
                  ) -> SplitNeighbors:
    nodes, layers, m = nbrs.shape
    logn = layers - 1
    split = split_layer(logn)
    hi = np.ascontiguousarray(
        nbrs[:, :split, :].astype(resolve_neighbor_dtype(n, "split")))
    u = np.arange(nodes, dtype=np.int64)
    shifts = logn - np.arange(split, layers)          # [nl], each <= 7
    base = (u[:, None] >> shifts[None, :]) << shifts[None, :]  # [nodes, nl]
    narrow = nbrs[:, split:, :].astype(np.int64)
    off = narrow - base[:, :, None]
    absent = narrow < 0
    width = 1 << shifts[None, :, None]                # segment width, <= 128
    bad = ~absent & ((off < 0) | (off > width - 1))
    if bad.any():
        l_bad = split + int(np.argwhere(bad)[0][1])
        raise ValueError(
            f"neighbor_dtype='split' requires segment-aligned edges: layer "
            f"{l_bad} has an edge outside its node's segment"
        )
    lo = np.where(absent, -1, off).astype(np.int8)
    return SplitNeighbors(hi, np.ascontiguousarray(lo))


def encode_neighbors(nbrs, n: int, cfg: StorageConfig):
    """Neighbor table -> its storage codec. ``-1`` stays ``-1``."""
    nbrs = np.asarray(nbrs)
    if nbrs.size and int(nbrs.max(initial=-1)) >= n:
        raise ValueError(
            f"neighbor id {int(nbrs.max())} out of range for n={n}"
        )
    if cfg.neighbor_dtype == "split":
        return _encode_split(nbrs, n, cfg)
    dt = resolve_neighbor_dtype(n, cfg.neighbor_dtype)
    if nbrs.dtype == dt:
        return nbrs
    return np.ascontiguousarray(nbrs.astype(dt))


def _decode_split(sn: SplitNeighbors):
    hi, lo = sn.hi, sn.lo
    nodes = hi.shape[0]
    layers = hi.shape[1] + lo.shape[1]
    logn = layers - 1
    split = hi.shape[1]
    xp = np if isinstance(lo, np.ndarray) else jnp
    i32 = np.int32 if xp is np else jnp.int32
    u = xp.arange(nodes, dtype=i32)
    shifts = logn - xp.arange(split, layers, dtype=i32)
    base = (u[:, None] >> shifts[None, :]) << shifts[None, :]  # [nodes, nl]
    narrow = lo.astype(i32)
    absn = xp.where(narrow < 0, -1, narrow + base[:, :, None])
    return xp.concatenate([hi.astype(i32), absn], axis=1)


def decode_neighbors(nbrs):
    """Neighbor table -> int32 at the consumption edge (numpy OR jnp).

    Because ``-1`` is the sentinel in every storage dtype, decode is a plain
    widening cast (int16/int32) or a widen+rebase (``split``: offset plus
    the closed-form segment base) — ids are bit-identical across codecs.
    Safe inside a trace; a no-op (no copy) when the table is already int32.
    """
    if isinstance(nbrs, SplitNeighbors):
        return _decode_split(nbrs)
    if isinstance(nbrs, EdgeRows):
        n = nbrs.rows.shape[0] // nbrs.R
        K = nbrs.layers * nbrs.m
        return nbrs.rows.reshape(n, nbrs.R * 128)[:, :K].reshape(
            n, nbrs.layers, nbrs.m)
    if nbrs.dtype == np.int32:
        return nbrs
    return nbrs.astype(jnp.int32 if isinstance(nbrs, jnp.ndarray)
                       else np.int32)


# ---------------------------------------------------------------------------
# table introspection — the struct-safe .shape/.nbytes/.asarray accessors
# ---------------------------------------------------------------------------

def table_n(table) -> int:
    """Row count of a (possibly codec-struct) vector or neighbor table."""
    if isinstance(table, (Int8Vectors, PQVectors)):
        return table.codes.shape[0]
    if isinstance(table, SplitNeighbors):
        return table.hi.shape[0]
    if isinstance(table, EdgeRows):
        return table.rows.shape[0] // table.R
    return table.shape[0]


def table_dim(table) -> int:
    """Decoded vector dimensionality of a (possibly codec-struct) table."""
    if isinstance(table, Int8Vectors):
        return table.codes.shape[1]
    if isinstance(table, PQVectors):
        M, _, dsub = table.codebook.shape
        return M * dsub
    return table.shape[1]


def table_nbytes(table) -> int:
    """Real stored bytes of a table — the sum over codec-struct leaves."""
    if table is None:
        return 0
    if isinstance(table, (Int8Vectors, PQVectors, SplitNeighbors)):
        return sum(int(np.asarray(leaf).nbytes) for leaf in table)
    if isinstance(table, EdgeRows):
        return int(table.rows.nbytes)
    return int(table.nbytes)


def as_device(table):
    """Upload a (possibly codec-struct) table: ``jnp.asarray`` per leaf.

    NamedTuple codecs are jax pytrees, so the returned struct feeds jit /
    AOT-compiled executables directly with its structure in the trace
    signature.
    """
    if table is None:
        return None
    if isinstance(table, (Int8Vectors, PQVectors, SplitNeighbors)):
        return type(table)(*(jnp.asarray(leaf) for leaf in table))
    if isinstance(table, EdgeRows):
        return EdgeRows(jnp.asarray(table.rows), table.layers, table.m)
    return jnp.asarray(table)
