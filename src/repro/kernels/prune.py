"""Pallas TPU kernel: fused construction prune (the build-path hot loop).

Every level of the bottom-up build RNG-prunes each node's candidate list
(paper Def. 2.1 / §3.2.2). The legacy formulation (``core/rng.py``)
precomputes the full ``[C, C]`` candidate-candidate distance matrix in XLA —
``O(C^2 d)`` flops and a ``[B, C, C]`` HBM intermediate — before a
``C``-step sequential keep-set scan. Here the candidate vectors never touch
an XLA gather: the vector table stays un-blocked in ``ANY``/HBM space and
the kernel row-DMAs only each chunk row's ``C`` candidate vectors into a
VMEM scratch (software-pipelined like ``gather_distance.py``, ``-1`` slots
skipped by predication), then runs the keep-set recurrence *flipped*: at
most ``m`` masked-argmin sweeps each select the nearest live candidate by
``(class, du, position)`` and — only when the selection is a keep — compute
that one candidate's distance column ``cc[:, j]`` on the fly against the
chunk (one MXU pass), growing the suppressed set. Only the kept set (≤ m
rows) ever contributes columns, so the work drops to ``O(m C d)`` and the
HNSW-style ``keepPrunedConnections`` fill pass folds into the same sweep as
selection class 1 (suppressed survivors, still in distance order).

Ids match ``kernels/ref.py::prune`` (the lazy jnp contract) and
``core/rng.py::prune`` (the eager oracle) in kept ids; the keep decisions
compare f32 distances built from the same ``xx_i - 2 x_i.x_j + xx_j``
expansion, so parity holds under identical fusion.

Duplicate candidate ids are wiped lazily inside the sweeps (no
``[bb, C, C]`` matrix: Mosaic cannot lower its rank-3 bool broadcast).
VMEM residency per program: the gather scratch ``bb*C*d_pad*4`` bytes,
one f32 row per slot whatever the codec (default ``bb=8``, C=128, d=128:
0.5 MB; 16/8-bit tables add a ``window``-block staging ring) plus the
``[bb*C, C]`` row repeat that turns each sweep's one-hot selection into a
column (0.5 MB as f32 at C=128); lower ``block_b`` for very large
``C*d``.
CPU/CI runs use ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import storage as _storage
from repro.kernels import rowdma as _rows

__all__ = ["prune_kernel_call"]


def _prune_kernel(
    ids_smem,    # SMEM [bb, C] (DMA row indices)
    ids_vmem,    # VMEM [bb, C] (vectorized ids)
    du_ref,      # VMEM [bb, C] squared distances to u
    *refs,       # table_ref (ANY), [aux_ref], o_ref, xbuf, [stage], sems
    bb, C, m, alpha, fill, window, codec, blocked, pq_m, pq_dsub,
):
    it = iter(refs)
    table_ref = next(it)
    aux_ref = next(it) if codec is not None else None
    o_ref, xbuf = next(it), next(it)
    stage = next(it) if blocked else None
    sems = next(it)
    total = bb * C
    big = jnp.int32(2**30)

    def slot_id(t):
        return ids_smem[t // C, t % C]

    def scale(t):                                         # int8 row scale
        return _rows.lane_pick(aux_ref[pl.ds(t // C, 1), :], t % C)

    # codec decode, in-register (DESIGN.md §9): int8 rows arrive scaled,
    # PQ rows as their codes
    _rows.gather_rows(table_ref, xbuf, stage, sems, total, window, slot_id,
                      scale=scale if codec == "int8" else None)

    ids = ids_vmem[...]                                   # [bb, C]
    du = du_ref[...]                                      # [bb, C]
    x = xbuf[...]                                         # [bb*C, w] f32
    if codec == "pq":
        codes = x[:, :pq_m].astype(jnp.int32)
        sub = jax.lax.broadcasted_iota(jnp.int32, (total, pq_m), 1)
        idx = codes + sub * _storage.PQ_CENTROIDS
        x = jnp.take(aux_ref[...], idx.reshape(-1), axis=0)
        x = x.reshape(total, pq_m * pq_dsub)
    xx = jnp.sum(x * x, axis=1).reshape(bb, C)            # [bb, C]
    pos = jax.lax.broadcasted_iota(jnp.int32, (bb, C), 1)
    valid = (ids >= 0) & jnp.isfinite(du)

    # -- keep-set recurrence + fill, one masked-argmin sweep per slot -------
    # Duplicate ids are wiped lazily: selecting an id marks every position
    # holding it taken. Duplicates share a vector, hence du, cc and class,
    # so the (du, position)-first copy is always the one selected — the
    # oracle's stable distance sort followed by keep-first-id.
    supp = jnp.zeros((bb, C), bool)
    taken = jnp.zeros((bb, C), bool)
    outs = []
    for _ in range(m):
        avail = valid & ~taken
        keepable = avail & ~supp
        fillable = (avail & supp) if fill else jnp.zeros_like(avail)
        cls = jnp.where(keepable, 0, jnp.where(fillable, 1, 2))
        cmin = jnp.min(cls, axis=1, keepdims=True)        # [bb, 1]
        cand = (cls == cmin) & (cmin < 2)
        dmask = jnp.where(cand, du, jnp.inf)
        dmin = jnp.min(dmask, axis=1, keepdims=True)
        p = jnp.min(
            jnp.where(cand & (dmask == dmin), pos, big), axis=1,
            keepdims=True,
        )                                                 # [bb, 1]
        onehot = pos == p                                 # no hit when big
        has = cmin < 2
        out_t = jnp.max(
            jnp.where(onehot, ids, jnp.iinfo(jnp.int32).min),
            axis=1, keepdims=True,
        )
        out_t = jnp.where(has, out_t, jnp.int32(-1))
        outs.append(out_t)
        # the selected keep's cc column, computed lazily: one MXU pass of
        # the whole gathered chunk against the selected vector (overcompute
        # factor bb, the gather_distance diagonal trick)
        sel_col = _rows.to_column(jnp.where(onehot, 1.0, 0.0))  # [bb*C, 1]
        vsel = jnp.sum(
            jnp.where(sel_col > 0, x, 0.0).reshape(bb, C, -1), axis=1
        )                                                 # [bb, d]
        xx_sel = jnp.sum(jnp.where(onehot, xx, 0.0), axis=1, keepdims=True)
        dots = jax.lax.dot_general(
            x, vsel, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).reshape(bb, C, bb)
        row_q = jax.lax.broadcasted_iota(jnp.int32, (bb, C, bb), 0)
        col_q = jax.lax.broadcasted_iota(jnp.int32, (bb, C, bb), 2)
        xy = jnp.sum(jnp.where(row_q == col_q, dots, 0.0), axis=2)
        cc = jnp.maximum(xx - 2.0 * xy + xx_sel, 0.0)
        is_keep = has & (cmin == 0)
        supp |= is_keep & (alpha * cc < du)
        taken |= onehot | ((ids == out_t) & valid)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bb, m), 1)
    res = jnp.full((bb, m), -1, jnp.int32)
    for i, out_t in enumerate(outs):
        res = jnp.where(lane == i, out_t, res)
    o_ref[...] = res


@functools.partial(
    jax.jit,
    static_argnames=("m", "alpha", "fill", "block_b", "window", "interpret"),
)
def prune_kernel_call(
    cand_ids, cand_dists, table, *, m, alpha=1.0, fill=True, block_b=8,
    window=16, interpret=False,
):
    """Fused construction prune (DESIGN.md §4; oracle: ``ref.prune``).

    cand_ids int32[B, C] (-1 masked), cand_dists f32[B, C] (inf masked),
    table ([n, d] float / Int8Vectors / PQVectors) -> int32[B, m] pruned
    neighbor ids, -1 padded.

    Pads B to the ``block_b`` row-tile multiple and the stored row width to
    the 128 lane width internally (zero columns are exact for squared L2);
    the table is passed un-blocked so each candidate is one contiguous row
    DMA. Codec tables decode in VMEM registers after the DMA (DESIGN.md §9),
    exactly like the gather-distance kernel.
    """
    B, _ = cand_ids.shape
    bb = min(block_b, max(8, B))
    pad_to = _rows.pad_to
    # C to a sublane multiple: padded slots are invalid (-1 / inf), so they
    # are never selected, and the [bb*C] <-> [bb, C] reshapes stay free
    ids = pad_to(cand_ids.astype(jnp.int32), 8, 1, value=-1)
    du = pad_to(cand_dists.astype(jnp.float32), 8, 1, value=jnp.inf)
    C = ids.shape[1]

    idp = pad_to(ids, bb, 0, value=-1)
    dup_ = pad_to(du, bb, 0, value=jnp.inf)
    grid = (idp.shape[0] // bb,)

    codec, aux, aux_spec, pq_m, pq_dsub = None, None, None, 0, 0
    if isinstance(table, _storage.Int8Vectors):
        codec = "int8"
        tp, blocked = _rows.row_table(table.codes)
        scales = table.scales[jnp.maximum(ids, 0)].astype(jnp.float32)
        aux = pad_to(scales, bb, 0)
        aux_spec = pl.BlockSpec((bb, C), lambda i: (i, 0))
    elif isinstance(table, _storage.PQVectors):
        codec = "pq"
        pq_m, _, pq_dsub = table.codebook.shape
        tp, blocked = _rows.row_table(table.codes)
        aux = table.codebook.reshape(pq_m * 256, pq_dsub)
        aux_spec = pl.BlockSpec(aux.shape, lambda i: (0, 0))
    else:
        tp, blocked = _rows.row_table(table)

    in_specs = [
        pl.BlockSpec((bb, C), lambda i: (i, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((bb, C), lambda i: (i, 0)),
        pl.BlockSpec((bb, C), lambda i: (i, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    args = [idp, idp, dup_, tp]
    if codec is not None:
        in_specs.append(aux_spec)
        args.append(aux)

    win = min(window, bb * C)
    scratch_shapes = [pltpu.VMEM((bb * C, tp.shape[-1]), jnp.float32)]
    if blocked:
        scratch_shapes.append(
            pltpu.VMEM((win, _rows.ROWS, tp.shape[-1]), tp.dtype))
    scratch_shapes.append(pltpu.SemaphoreType.DMA((win,)))

    out = pl.pallas_call(
        functools.partial(
            _prune_kernel, bb=bb, C=C, m=m, alpha=alpha, fill=fill,
            window=win, codec=codec, blocked=blocked,
            pq_m=pq_m, pq_dsub=pq_dsub,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((idp.shape[0], m), jnp.int32),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(*args)
    return out[:B]
