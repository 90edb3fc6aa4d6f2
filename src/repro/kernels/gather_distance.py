"""Pallas TPU kernel: fused gather + masked distance (the beam-search hop).

DESIGN.md §3 (hot path) and §9 (codec decode). Each beam-search iteration
needs distances from ``B`` queries to the ``M`` neighbors just pulled from
the improvised graph — ids ``int32[B, M]`` with ``-1`` marking masked slots.
The XLA formulation materializes the gathered ``[B, M, d]`` tensor in HBM
before the einsum; at serving batch sizes that intermediate dominates the
hop's HBM traffic. Here the gather lands directly in VMEM: per ``(bb, bm)``
tile the kernel row-DMAs only the *valid* vector rows from the table (kept
whole in ``ANY``/HBM space, never blocked) into a VMEM scratch, overlapping
up to ``window`` copies, then emits masked ``f32[bb, bm]`` distances off one
MXU matmul — no ``[B, M, d]`` intermediate ever exists.

**Codec decode happens here, in VMEM registers** (§9): the table may be a
``storage.Int8Vectors`` (the DMA moves int8 rows; the kernel multiplies by
the pre-gathered per-row scales) or a ``storage.PQVectors`` (the DMA moves
uint8 code rows; the kernel looks the codebook — resident in VMEM — up per
subspace). The decoded f32 rows exist only in the register file /
scratch-local values; no widened table ever hits HBM, so the footprint
saving is also a hop-bandwidth saving.

Shape contract: ``q f32[B, d]``, ``table [n, d]`` float dtypes or codec
struct, ``ids int32[B, M]`` -> ``f32[B, M]``. Math matches
``kernels/ref.py::gather_dist`` (and the historical inline ``_pairdist``)
bit-for-bit in f32 under identical fusion: ``||x||^2 - 2 x.q + ||q||^2``
for l2, ``-x.q`` for ip; invalid slots return ``+inf``.

VMEM residency per program is one f32 row per slot, ``bb*bm*d_pad*4``
bytes for the gather scratch whatever the codec (default tiles 8x128:
0.5 MB at d=128; 16/8-bit tables add a ``window``-block staging ring,
``kernels/rowdma.py``) plus the query tile and, for PQ, the
``[M*256, dsub]`` codebook (128 KB at d=128, M=32).
The codec tiles are autotuned separately (``kind="gather_dist_codec"``,
``kernels/autotune.py``). CPU/CI runs use ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import storage as _storage
from repro.kernels import rowdma as _rows

__all__ = ["gather_distance_kernel_call"]


def _gather_dist_kernel(
    q_ref,       # VMEM [bb, dp]
    ids_smem,    # SMEM [bb, bm] (DMA row indices)
    ids_vmem,    # VMEM [bb, bm] (vectorized mask)
    *refs,       # table_ref (ANY), [aux_ref], o_ref, xbuf, [stage], sems
    bb, bm, metric, window, codec, blocked, dp, pq_m, pq_dsub,
):
    it = iter(refs)
    table_ref = next(it)
    aux_ref = next(it) if codec is not None else None
    o_ref, xbuf = next(it), next(it)
    stage = next(it) if blocked else None
    sems = next(it)
    total = bb * bm

    def slot_id(t):
        return ids_smem[t // bm, t % bm]

    def scale(t):                                         # int8 row scale
        return _rows.lane_pick(aux_ref[pl.ds(t // bm, 1), :], t % bm)

    # codec decode, in-register (§9): int8 rows arrive scaled, PQ rows as
    # their codes
    _rows.gather_rows(table_ref, xbuf, stage, sems, total, window, slot_id,
                      scale=scale if codec == "int8" else None)

    q = q_ref[...].astype(jnp.float32)       # [bb, dp]
    ids = ids_vmem[...]
    x = xbuf[...]                                         # [bb*bm, w] f32
    if codec == "pq":
        codes = x[:, :pq_m].astype(jnp.int32)             # [bb*bm, M]
        sub = jax.lax.broadcasted_iota(jnp.int32, (total, pq_m), 1)
        idx = codes + sub * _storage.PQ_CENTROIDS
        x = jnp.take(aux_ref[...], idx.reshape(-1), axis=0)
        x = x.reshape(total, pq_m * pq_dsub)
        pad = dp - pq_m * pq_dsub
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((total, pad), jnp.float32)], axis=1)
    # one MXU pass against every query in the tile, then keep the diagonal
    # query<->row pairing (overcompute factor bb is tiny next to the gather)
    dots = jax.lax.dot_general(
        x, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(bb, bm, bb)
    row_q = jax.lax.broadcasted_iota(jnp.int32, (bb, bm, bb), 0)
    col_q = jax.lax.broadcasted_iota(jnp.int32, (bb, bm, bb), 2)
    dot = jnp.sum(jnp.where(row_q == col_q, dots, 0.0), axis=2)  # [bb, bm]

    if metric == "ip":
        out = -dot
    else:
        xx = jnp.sum(x * x, axis=1).reshape(bb, bm)
        qq = jnp.sum(q * q, axis=1)
        out = xx - 2.0 * dot + qq[:, None]
    o_ref[...] = jnp.where(ids >= 0, out, jnp.inf)


@functools.partial(
    jax.jit,
    static_argnames=("metric", "block_b", "block_m", "window", "interpret"),
)
def gather_distance_kernel_call(
    q, table, ids, *, metric="l2", block_b=8, block_m=128, window=16,
    interpret=False,
):
    """q[B, d], table ([n, d] float / Int8Vectors / PQVectors), ids
    int32[B, M] (-1 masked) -> f32[B, M].

    Distances from query b to the decoded table[ids[b, j]]; +inf where
    ids < 0. Pads B/M to tile multiples and the stored row width to the 128
    lane width internally (zero columns are exact for both metrics). For
    ``Int8Vectors`` the per-row scales are pre-gathered outside the kernel
    (ids are known at call time) and ride in as a ``[bb, bm]`` f32 tile; for
    ``PQVectors`` the flattened codebook is a VMEM-resident input and codes
    decode in-register after the DMA.
    """
    B, d = q.shape
    M = ids.shape[1]
    bb = min(block_b, max(8, B))
    # a narrow M is one whole tile (the full padded dim): no padded slots
    bm = -(-M // 8) * 8 if M <= 128 else min(block_m, M)
    pad_to = _rows.pad_to

    qp = pad_to(pad_to(q, bb, 0), 128, 1)
    idp = pad_to(pad_to(ids, bb, 0, value=-1), bm, 1, value=-1)
    dp = qp.shape[1]
    grid = (qp.shape[0] // bb, idp.shape[1] // bm)

    codec, aux, aux_spec, pq_m, pq_dsub = None, None, None, 0, 0
    if isinstance(table, _storage.Int8Vectors):
        codec = "int8"
        tbl, blocked = _rows.row_table(table.codes)
        scales = table.scales[jnp.maximum(ids, 0)].astype(jnp.float32)
        aux = pad_to(pad_to(scales, bb, 0), bm, 1)
        aux_spec = pl.BlockSpec((bb, bm), lambda i, j: (i, j))
    elif isinstance(table, _storage.PQVectors):
        codec = "pq"
        pq_m, _, pq_dsub = table.codebook.shape
        tbl, blocked = _rows.row_table(table.codes)
        aux = table.codebook.reshape(pq_m * _storage.PQ_CENTROIDS, pq_dsub)
        aux_spec = pl.BlockSpec(aux.shape, lambda i, j: (0, 0))
    else:
        tbl, blocked = _rows.row_table(table)

    # the SMEM copy of the ids is column-tile major, [Mp/bm, Bp, bm], so
    # each (i, j) block spans its array's whole last dim
    nj = idp.shape[1] // bm
    ids_s = idp.reshape(idp.shape[0], nj, bm).transpose(1, 0, 2)
    in_specs = [
        pl.BlockSpec((bb, dp), lambda i, j: (i, 0)),
        pl.BlockSpec((None, bb, bm), lambda i, j: (j, i, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((bb, bm), lambda i, j: (i, j)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    args = [qp, ids_s, idp, tbl]
    if codec is not None:
        in_specs.append(aux_spec)
        args.append(aux)

    win = min(window, bb * bm)
    scratch_shapes = [pltpu.VMEM((bb * bm, tbl.shape[-1]), jnp.float32)]
    if blocked:
        scratch_shapes.append(
            pltpu.VMEM((win, _rows.ROWS, tbl.shape[-1]), tbl.dtype))
    scratch_shapes.append(pltpu.SemaphoreType.DMA((win,)))

    out = pl.pallas_call(
        functools.partial(
            _gather_dist_kernel, bb=bb, bm=bm, metric=metric,
            window=win, codec=codec, blocked=blocked,
            dp=dp, pq_m=pq_m, pq_dsub=pq_dsub,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], idp.shape[1]),
                                       jnp.float32),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(*args)
    return out[:B, :M]
