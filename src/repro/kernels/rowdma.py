"""Row gathers from an un-blocked HBM table, in forms Mosaic can lower.

Every kernel in this package gathers table rows by id with DMAs (the table
stays in ``ANY``/HBM space, never blocked). On a TPU the table lives in a
tiled HBM layout, and a DMA may only move whole tiles along the row axis:

  * a 32-bit table exactly one lane tile wide (``[n, 128]``) is stored row
    after row, so one row is one contiguous DMA straight into its slot;
  * every other table (bf16 ``(8,128)(2,1)``, int8 ``(8,128)(4,1)``, or a
    32-bit table wider than 128 lanes) packs 8 rows per tile, so the DMA
    moves the 8-row block that holds the row into a staging ring of
    ``window`` blocks, and on arrival the kernel picks the row out (and
    scales it, for int8) into its f32 slot.

Either way a gathered slot costs one f32 row of VMEM, whatever the codec.
:func:`row_table` puts a table into the form its kernel DMAs from and says
which of the two it is; :func:`gather_rows` runs the row gather through
:func:`gather`, the software pipeline every kernel's DMAs go through.
:func:`to_column` turns a ``[R, C]`` tile into the ``[R*C, 1]`` column
that lines up with gathered rows (a plain reshape moves lanes to sublanes,
which Mosaic refuses).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ROWS", "pad_to", "row_table", "gather", "gather_rows",
           "lane_pick", "to_column"]

# rows per HBM tile along the row axis, for every dtype the tables use
ROWS = 8


def pad_to(a, mult, axis, value=0):
    """Pad ``axis`` of ``a`` up to a multiple of ``mult`` with ``value``."""
    r = (-a.shape[axis]) % mult
    if r == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, r)
    return jnp.pad(a, widths, constant_values=value)


def row_table(table):
    """``[n, w]`` table -> ``(tbl, blocked)``: lanes padded to 128 (zero
    columns are exact for both metrics); a table that is not one 32-bit
    lane tile wide is viewed as ``[ceil(n/8), 8, w]`` 8-row blocks (a
    free reshape: the same bytes as the tiled 2D layout)."""
    tbl = pad_to(table, 128, 1)
    if tbl.dtype.itemsize == 4 and tbl.shape[1] == 128:
        return tbl, False
    tbl = pad_to(tbl, ROWS, 0)
    return tbl.reshape(tbl.shape[0] // ROWS, ROWS, tbl.shape[1]), True


def gather(total, window, idx, copies, arrive=None):
    """Software-pipelined DMA loop over slots ``0..total-1``: keep up to
    ``window`` slots in flight; ``copies(t)`` gives slot ``t``'s DMA
    descriptors (semaphore ``t % window``), skipped when ``idx(t) < 0``.
    ``arrive(t)``, when given, runs right after slot ``t``'s copies land
    and before slot ``t + window`` reuses its semaphore (and staging).
    ``total`` is a Python int, or an int32 scalar the kernel counted
    itself (the hop's live slots, listed in SMEM)."""
    def start(t):
        @pl.when(idx(t) >= 0)
        def _():
            for c in copies(t):
                c.start()

    def wait(t):
        @pl.when(idx(t) >= 0)
        def _():
            for c in copies(t):
                c.wait()
            if arrive is not None:
                arrive(t)

    def fill(t, carry):
        @pl.when(t >= window)
        def _():
            wait(t - window)

        start(t)
        return carry

    jax.lax.fori_loop(0, total, fill, 0)

    def drain(t, carry):
        wait(t)
        return carry

    first = (max(0, total - window) if isinstance(total, int)
             else jnp.maximum(total - window, 0))
    jax.lax.fori_loop(first, total, drain, 0)


def gather_rows(table_ref, xbuf, stage, sems, total, window, idx,
                scale=None, extra=(), row=None):
    """Gather table row ``idx(t)`` into ``xbuf[row(t)]`` (f32 ``[.., w]``;
    ``row`` defaults to ``t``) for every ``t < total`` with ``idx(t) >=
    0``; other rows hold garbage the caller masks. ``stage`` is ``None``
    for a one-tile 32-bit table (the row DMAs straight into its slot), else
    the ``[window, ROWS, w]`` staging ring the row's 8-row block lands in.
    ``scale(t)`` (a ``[1, 1]`` f32, read after the copies land) multiplies
    the picked row; ``extra`` adds per-slot DMA descriptors ``e(t)`` that
    ride on the same pipeline."""
    dst_row = row if row is not None else (lambda t: t)

    def copies(t):
        i = idx(t)
        if stage is None:
            src, dst = table_ref.at[i], xbuf.at[dst_row(t)]
        else:
            src, dst = table_ref.at[i >> 3], stage.at[t % window]
        return [pltpu.make_async_copy(src, dst, sems.at[t % window]),
                *(e(t) for e in extra)]

    def arrive(t):
        blk = stage[t % window].astype(jnp.float32)       # [ROWS, w]
        sub = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
        picked = jnp.sum(jnp.where(sub == (idx(t) & (ROWS - 1)), blk, 0.0),
                         axis=0, keepdims=True)           # [1, w]
        if scale is not None:
            picked = picked * scale(t)
        xbuf[pl.ds(dst_row(t), 1), :] = picked

    gather(total, window, idx, copies, None if stage is None else arrive)


def lane_pick(row, j):
    """Lane ``j`` of a ``[1, w]`` row, as a ``[1, 1]`` array (Mosaic has
    no dynamic lane index)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == j, row, 0.0), axis=1, keepdims=True)


def to_column(a):
    """``[R, C]`` -> ``[R*C, 1]`` in row-major order, exactly: each output
    element is one input element (a masked lane sum of zeros and it).
    Needs ``C % 8 == 0`` on a TPU, so the row repeat is a free reshape."""
    R, C = a.shape
    rep = jnp.broadcast_to(a[:, None, :], (R, C, C)).reshape(R * C, C)
    lane = jax.lax.broadcasted_iota(jnp.int32, (R * C, C), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (R * C, C), 0)
    zero = jnp.zeros((), a.dtype)
    return jnp.sum(jnp.where(lane == row % C, rep, zero), axis=1,
                   keepdims=True)
