"""Pallas TPU kernel: fused edge selection (the other half of the hop).

Each beam-search iteration improvises up to ``m_out`` out-edges per frontier
node (paper Algorithm 1). The XLA formulation gathers the full
``[F, (logn+1)*m]`` candidate-edge block into HBM before masking; at serving
batch sizes that gather plus the per-row dedup dominate the remaining hop
cost. Here the packed table ``nbrs[n, layers*m]`` stays un-blocked in
``ANY``/HBM space and the kernel row-DMAs only each frontier node's edge
block into a VMEM scratch (software-pipelined like ``gather_distance.py``,
``-1`` frontier slots skipped by predication), computes the
``segment_tree.scan_mask`` closed form in-kernel, and replaces the stable
argsort dedup with a sort-free formulation. Two dedup variants:

  * ``dedup="lazy"`` (default) — O(m_out·K): the priority-ordered
    top-``m_out`` runs as ``m_out`` masked argmin steps, and each step
    wipes *every* position holding the id it just selected, so later
    steps can only surface new ids. No ``[K, K]`` intermediate exists,
    VMEM stays flat in K. CPU measurements showed ~8.2x vs eager at
    K=288 (ROADMAP "lazy-vs-eager" decision — resolved in favor of lazy).
  * ``dedup="eager"`` — the historical **equality matrix**: a
    strictly-lower-triangular ``[K, K]`` ``id[i] == id[j]`` comparison
    marks non-first occurrences up front, then the same ``m_out`` argmin
    steps select. Kept selectable for A/B benchmarking.

Ids match ``kernels/ref.py::select_edges`` (and the historical argsort
formulation ``core/edge_select.py::select_edges_batch``) bit-for-bit in
both variants; the math is integer-exact, so parity is equality, not
tolerance.

VMEM residency: lazy keeps only the flat ``[bf, K]`` buffers. Eager's
``[bf, K, K]`` intermediates dominate (at ``bf=8``, K=288 the masks pad
to ``8*288*384`` lanes, ~3.5 MB as i32; K=400 pads to 512 lanes,
~6.5 MB). The row tile is ``bf=8`` for both, the sublane tile (a 4-row
block breaks Mosaic's (8, 128) rule). The gather scratch itself is tiny
(``bf*R*128*4`` bytes). CPU/CI runs use
``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import storage as _storage
from repro.kernels import ref as _ref
from repro.kernels import rowdma as _rows

__all__ = ["edge_select_kernel_call"]


def _edge_select_kernel(
    meta_smem,   # SMEM [bf, 4] (u, L, R, pad) — DMA row indices
    meta_vmem,   # VMEM [bf, 4] (vectorized u/L/R)
    table_ref,   # ANY  [n*Rw, 128] (packed nbrs, never blocked)
    o_ref,       # VMEM [bf, m_out]
    xbuf,        # VMEM scratch [bf, Rw, 128] gathered edge blocks
    sems,        # DMA semaphores [window]
    *, bf, K, Rw, m, logn, m_out, skip_layers, window, dedup,
):
    big = jnp.int32(2**30)

    def slot_u(t):
        return meta_smem[t, 0]

    _rows.gather(bf, window, slot_u, lambda t: [pltpu.make_async_copy(
        table_ref.at[pl.ds(slot_u(t) * Rw, Rw)], xbuf.at[t],
        sems.at[t % window])])

    us = meta_vmem[:, 0:1]                                # [bf, 1]
    L = meta_vmem[:, 1:2]
    R = meta_vmem[:, 2:3]
    flat = jnp.concatenate(
        [xbuf[:, c, :] for c in range(Rw)], axis=1)[:, :K]  # [bf, K]

    # scan-mask + in-range validity: the one shared closed form (Mosaic
    # needs the 2D broadcasted iota; everything inside is elementwise)
    lay = jax.lax.broadcasted_iota(jnp.int32, (bf, K), 1) // m
    valid = _ref.edge_scan_valid(
        flat, us, L, R, lay, logn=logn, skip_layers=skip_layers
    )

    # priority == flat position (upper layer first, then slot order)
    pos = jax.lax.broadcasted_iota(jnp.int32, (bf, K), 1)
    if dedup == "eager":
        # strictly-lower-triangular equality matrix marks non-first
        # occurrences up front (the [bf, K, K] VMEM hog)
        pos_i = jax.lax.broadcasted_iota(jnp.int32, (bf, K, K), 1)
        pos_j = jax.lax.broadcasted_iota(jnp.int32, (bf, K, K), 2)
        eq = (flat[:, :, None] == flat[:, None, :]) & valid[:, None, :]
        dup = jnp.any(eq & (pos_j < pos_i), axis=2)       # [bf, K]
        prio = jnp.where(valid & ~dup, pos, big)
    else:
        prio = jnp.where(valid, pos, big)

    # -- priority-ordered top-m_out: m_out masked argmin steps --------------
    outs = []
    for _ in range(m_out):
        pmin = jnp.min(prio, axis=1, keepdims=True)       # [bf, 1]
        sel = prio == pmin                                # one hit unless BIG
        idt = jnp.max(
            jnp.where(sel, flat, jnp.iinfo(jnp.int32).min),
            axis=1, keepdims=True,
        )
        out_t = jnp.where(pmin < big, idt, jnp.int32(-1))
        outs.append(out_t)
        if dedup == "eager":
            prio = jnp.where(sel, big, prio)
        else:
            # lazy: wipe every position holding the selected id so later
            # steps can only surface new ids — O(m_out*K), no [K, K]
            taken = (flat == out_t) & (prio < big)
            prio = jnp.where(sel | taken, big, prio)
    o_ref[...] = jnp.concatenate(outs, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("logn", "m_out", "skip_layers", "block_f", "window",
                     "dedup", "interpret"),
)
def edge_select_kernel_call(
    edges, us, L, R, *, logn, m_out, skip_layers=True, block_f=8,
    window=8, dedup="lazy", interpret=False,
):
    """Fused per-hop edge improvisation (DESIGN.md §2/§3; oracle:
    ``ref.select_edges``).

    edges the ``storage.EdgeRows`` row-DMA form of the neighbor table
    (``storage.pack_edges``; -1 sentinel), us int32[F] (-1 masked), L/R
    scalars or int32[F] -> int32[F, m_out] improvised edges, -1 padded.
    Ids are bit-identical to the oracle across codecs and backends.

    Pads F to the ``block_f`` row-tile multiple internally; each frontier
    node is one contiguous ``R``-row DMA. ``dedup`` picks "lazy" (default,
    O(m_out*K)) or "eager" (the [K, K] equality matrix, kept for A/B) —
    bit-identical ids.
    """
    if dedup not in ("lazy", "eager"):
        raise ValueError(
            f"edge_select: unknown dedup {dedup!r} "
            "(expected 'lazy' or 'eager')"
        )
    if not isinstance(edges, _storage.EdgeRows):
        raise TypeError(f"edge_select_kernel_call reads storage.EdgeRows, "
                        f"got {type(edges).__name__} (storage.pack_edges)")
    K, m = edges.layers * edges.m, edges.m
    F = us.shape[0]
    us = us.astype(jnp.int32)
    L = jnp.broadcast_to(jnp.asarray(L, jnp.int32), us.shape)
    R = jnp.broadcast_to(jnp.asarray(R, jnp.int32), us.shape)
    bf = block_f

    meta = jnp.stack(
        [us, L, R, jnp.zeros_like(us)], axis=1
    )                                                     # [F, 4]
    r = (-F) % bf
    if r:
        pad = jnp.full((r, 4), -1, jnp.int32)
        meta = jnp.concatenate([meta, pad], axis=0)
    Fp = meta.shape[0]
    grid = (Fp // bf,)

    out = pl.pallas_call(
        functools.partial(
            _edge_select_kernel, bf=bf, K=K, Rw=edges.R, m=m, logn=logn,
            m_out=m_out, skip_layers=skip_layers, window=min(window, bf),
            dedup=dedup,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bf, 4), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bf, 4), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((bf, m_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Fp, m_out), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bf, edges.R, 128), jnp.int32),
            pltpu.SemaphoreType.DMA((min(window, bf),)),
        ],
        interpret=interpret,
    )(meta, meta, edges.rows)
    return out[:F]
