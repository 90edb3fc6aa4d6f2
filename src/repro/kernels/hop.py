"""Pallas TPU megakernel: one whole beam-search hop in a single launch.

Each beam-search iteration used to be three kernel launches — edge
selection (``kernels/edge_select.py``), the packed-bitset visited update
(``core/bitset.py``), and gather-distance (``kernels/gather_distance.py``)
— with the frontier round-tripping through HBM between them: the improvised
edges land in HBM, get re-read by the bitset scatter, and the surviving ids
get re-read again to drive the vector gather. This kernel fuses the whole
hop so the frontier never leaves VMEM:

  1. **edge gather** — per ``(bb)`` query tile the neighbor table stays
     un-blocked in ``ANY``/HBM space in its ``storage.EdgeRows`` form and
     the kernel DMAs each live frontier node's ``K = (logn+1)*m`` edges
     (``Rw`` whole 128-lane rows) into a VMEM scratch: the ``u >= 0``
     slots of the ``bb*W`` are listed in SMEM first and the
     software-pipelined loop (up to ``window`` copies in flight) runs over
     that list alone;
  2. **edge selection** — the ``segment_tree.scan_mask`` closed form
     (``ref.edge_scan_valid``) plus the *lazy* O(m_out·K) dedup: ``m_out``
     masked-argmin steps, each wiping every position holding the id it just
     selected — no ``[K, K]`` equality matrix, so VMEM stays flat in K; the
     selected ids, with each node's live span (the length of its prefix of
     selected ids, 0 where its expansion is off), then DMA to SMEM, where
     the next two phases index with them as scalars, and ``nbr`` is stored
     from them in one vector store;
  3. **visited test-and-set** — the query tile's bitset rows (int32 words)
     live in VMEM for the whole launch. Only the live slots run, one at a
     time in row-major order (node by node over its span): each reads its
     word as the aligned 128-word column block of all ``bb`` rows, tests
     the bit, and sets it only when new, so a repeat of an id in the same
     row finds its bit set — the first-occurrence rule of
     ``core/bitset.py``. (Mosaic lowers neither a general gather over the
     row nor an unsigned reduction.) Each slot writes its new id (or -1)
     to an SMEM row that comes back to VMEM once as ``nvalid``, and each
     newly visited slot is listed for phase 4;
  4. **vector gather + distance** — the listed new ids DMA their vector
     rows straight from the un-blocked table, each into the f32 VMEM row of
     its own slot (16/8-bit tables land as 8-row blocks in a staging ring
     and keep only the row, ``kernels/rowdma.py``), so the DMA window holds
     only live copies, and one MXU matmul emits masked f32 distances,
     exactly the ``gather_distance.py`` structure.

Every loop over slots is bounded by a count the kernel takes from its own
input, so a dead slot costs no iteration.

Semantics are ``kernels/ref.py::hop`` (select_edges -> bitset.test_and_set
-> gather_dist): integer outputs (edges, newly-visited mask, bitset words)
must match bit-for-bit, distances to f32 tolerance.

VMEM residency per program: the bitset tile ``bb*ceil(n/32)*4`` bytes,
in and out, double-buffered, dominates at deployment n (default bb=8 at
n = 2^20: 1 MiB each way), then the vector scratch ``bb*W*m_out`` f32
rows of ``d_pad`` lanes whatever the codec (256 KiB at bb=8, W=4,
m_out=16, d=128) and the edge scratch ``bb*W*Rw*512`` bytes. CPU/CI runs
use ``interpret=True``.
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

__all__ = ["hop_kernel_call"]

# plain Python ints so the kernel body inlines them as literals (Pallas
# rejects closure-captured traced constants)
_BIG = 2**30
_IMIN = -(2**31)


def _hop_kernel(
    meta_smem,   # SMEM [bb, 4*W] (u | L | R | exp) — scalar u and exp_ok
    col_vmem,    # VMEM [bb*W, 4] (u, L, R, exp) per frontier node
    q_ref,       # VMEM [bb, dp]
    vis_ref,     # VMEM [bb, words] int32 (query tile's bitset rows)
    nbrs_ref,    # ANY  [n*Rw, 128]  (packed edge rows, never blocked)
    table_ref,   # ANY  vector/code table in ``rowdma.row_table`` form
    *refs,       # [aux_ref], outputs, ebuf, esel, esel_s, gid_s, fr_s,
                 # new_s, xbuf, [stage], [sstage], sems, [sems2]
    bb, W, K, Rw, m, m_out, logn, skip_layers, metric, window,
    codec, blocked, dp, pq_m, pq_dsub,
):
    it = iter(refs)
    aux_ref = next(it) if codec is not None else None
    nbr_out, dist_out, nvalid_out, vis_out = (next(it) for _ in range(4))
    ebuf, esel, esel_s, gid_s, fr_s, new_s, xbuf = (
        next(it) for _ in range(7))
    stage = next(it) if blocked else None
    sstage = next(it) if codec == "int8" else None
    sems = next(it)
    sems2 = next(it) if codec == "int8" else None
    WM = W * m_out
    F = bb * W
    T = bb * WM

    # -- 1. pipelined edge-block gather (one Rw-row DMA per live node) ------
    # The frontier slots with u >= 0 are listed in SMEM first, so the DMA
    # loop runs over them alone and its window holds only live copies; an
    # unlisted slot's ebuf row is never read as an edge (u < 0 fails
    # edge_scan_valid).
    def edge_u(f):
        return meta_smem[f // W, f % W]

    def list_node(f, count):
        fr_s[count] = f
        return count + (edge_u(f) >= 0).astype(jnp.int32)

    n_nodes = jax.lax.fori_loop(0, F, list_node, jnp.int32(0))
    _rows.gather(n_nodes, window, lambda t: edge_u(fr_s[t]),
                 lambda t: [pltpu.make_async_copy(
                     nbrs_ref.at[pl.ds(edge_u(fr_s[t]) * Rw, Rw)],
                     ebuf.at[fr_s[t]], sems.at[t % window])])

    # -- 2. edge selection: scan-mask validity + lazy O(m_out*K) dedup ------
    col = col_vmem[...]
    us, L, R = col[:, 0:1], col[:, 1:2], col[:, 2:3]      # [F, 1]
    flat = jnp.concatenate(
        [ebuf[:, c, :] for c in range(Rw)], axis=1)[:, :K]  # [F, K]

    lay = jax.lax.broadcasted_iota(jnp.int32, (F, K), 1) // m
    valid = _ref.edge_scan_valid(
        flat, us, L, R, lay, logn=logn, skip_layers=skip_layers
    )

    # priority == flat position (upper layer first, then slot order); the
    # lazy dedup wipes every position holding a selected id, so later steps
    # can only surface new ids — bit-identical to the eager [K, K] matrix
    pos = jax.lax.broadcasted_iota(jnp.int32, (F, K), 1)
    prio = jnp.where(valid, pos, _BIG)
    outs = []
    for _ in range(m_out):
        pmin = jnp.min(prio, axis=1, keepdims=True)       # [F, 1]
        sel = prio == pmin
        idt = jnp.max(jnp.where(sel, flat, _IMIN), axis=1, keepdims=True)
        out_t = jnp.where(pmin < _BIG, idt, jnp.int32(-1))
        outs.append(out_t)
        taken = (flat == out_t) & (prio < _BIG)
        prio = jnp.where(sel | taken, _BIG, prio)
    sel_ids = jnp.concatenate(outs, axis=1)               # [F, m_out]
    # a node's selected ids are a prefix of its row (a step that finds
    # nothing left selects -1, and so does every later step); the number
    # of slots phase 3 visits is the length of that prefix, 0 for a node
    # whose expansion is off
    j1 = jax.lax.broadcasted_iota(jnp.int32, (F, m_out), 1) + 1
    span = jnp.max(jnp.where(sel_ids >= 0, j1, 0), axis=1, keepdims=True)
    span = jnp.where(col[:, 3:4] != 0, span, 0)           # [F, 1]
    # the selected ids and the spans go to SMEM: the visited test-and-set
    # and the vector DMAs below index with them as scalars
    esel[...] = jnp.concatenate([sel_ids, span], axis=1)  # [F, m_out+1]
    to_smem = pltpu.make_async_copy(esel, esel_s, sems.at[0])
    to_smem.start()
    # row b of nbr_out holds frontier rows b*W .. b*W+W-1 side by side
    nbr_out[...] = jnp.concatenate(
        [esel[pl.ds(w, bb, stride=W), pl.ds(0, m_out)] for w in range(W)],
        axis=1)                                           # [bb, WM]
    # gid_s[b, c], slot c of query b's row, is the slot's id where it is
    # newly visited and -1 elsewhere: set to -1 through nvalid_out, the
    # slots of the listed spans written below
    nvalid_out[...] = jnp.full((bb, WM), -1, jnp.int32)
    to_smem.wait()
    to_gid = pltpu.make_async_copy(nvalid_out, gid_s, sems.at[0])
    to_gid.start()
    to_gid.wait()

    # -- 3. visited test-and-set, bitset rows resident in VMEM --------------
    # Live slots only, one at a time, in row-major slot order (node by
    # node, each node's span in order), so the first occurrence of an id
    # within a row wins (the second finds its bit set). A slot's word is
    # read as the aligned 128-word column block of all bb rows. Each newly
    # visited slot is listed in new_s for phase 4.
    vis_out[...] = vis_ref[...]
    sub = jax.lax.broadcasted_iota(jnp.int32, (bb, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bb, 128), 1)

    def node(f, count):
        b, w = f // W, f % W

        def visit(j, count):
            idv = esel_s[f, j]
            safe = jnp.maximum(idv, 0)
            word = safe >> 5
            base = pl.multiple_of((word >> 7) * 128, 128)
            blk = vis_out[:, pl.ds(base, 128)]            # [bb, 128]
            hit = (sub == b) & (lane == (word & 127))
            bit = (jnp.sum(jnp.where(hit, blk, 0)) >> (safe & 31)) & 1
            new = (idv >= 0) & (bit == 0)
            gid_s[b, w * m_out + j] = jnp.where(new, idv, -1)
            new_s[count] = f * m_out + j

            @pl.when(new)
            def _():
                vis_out[:, pl.ds(base, 128)] = blk | jnp.where(
                    hit, jnp.int32(1) << (safe & 31), 0)

            return count + new.astype(jnp.int32)

        return jax.lax.fori_loop(0, esel_s[f, m_out], visit, count)

    n_new = jax.lax.fori_loop(0, F, node, jnp.int32(0))
    to_vmem = pltpu.make_async_copy(gid_s, nvalid_out, sems.at[0])
    to_vmem.start()
    to_vmem.wait()
    nvalid = (nvalid_out[...] >= 0).astype(jnp.int32)     # [bb, WM]
    nvalid_out[...] = nvalid

    # -- 4. pipelined vector gather for the newly-visited ids ---------------
    # over the n_new listed slots only; slot s = f*m_out + j of the list
    # is row s of xbuf (row-major [bb, WM] order), as the distances expect
    def vec_id(t):
        s_ = new_s[t]
        return esel_s[s_ // m_out, s_ % m_out]

    def scale_copy(t):
        # ids are discovered in-kernel, so the per-row scale rides a
        # parallel DMA of its 128-scale row (aux_ref is ANY [n/128, 128])
        return pltpu.make_async_copy(
            aux_ref.at[vec_id(t) >> 7], sstage.at[t % window],
            sems2.at[t % window])

    def scale(t):
        return _rows.lane_pick(sstage[pl.ds(t % window, 1), :],
                               vec_id(t) & 127)

    # codec decode, in-register (DESIGN.md §9): int8 rows arrive scaled,
    # PQ rows as their codes
    int8 = codec == "int8"
    _rows.gather_rows(table_ref, xbuf, stage, sems, n_new, window, vec_id,
                      scale=scale if int8 else None,
                      extra=(scale_copy,) if int8 else (),
                      row=lambda t: new_s[t])

    # -- distance: one MXU pass, keep the diagonal query<->row pairing ------
    q = q_ref[...].astype(jnp.float32)                    # [bb, dp]
    x = xbuf[...]                                         # [T, w] f32
    if codec == "pq":
        codes = x[:, :pq_m].astype(jnp.int32)             # [T, M]
        sub_m = jax.lax.broadcasted_iota(jnp.int32, (T, pq_m), 1)
        idx = codes + sub_m * _storage.PQ_CENTROIDS
        x = jnp.take(aux_ref[...], idx.reshape(-1), axis=0)
        x = x.reshape(T, pq_m * pq_dsub)
        pad = dp - pq_m * pq_dsub
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((T, pad), jnp.float32)], axis=1)
    dots = jax.lax.dot_general(
        x, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(bb, WM, bb)
    row_q = jax.lax.broadcasted_iota(jnp.int32, (bb, WM, bb), 0)
    col_q = jax.lax.broadcasted_iota(jnp.int32, (bb, WM, bb), 2)
    dot = jnp.sum(jnp.where(row_q == col_q, dots, 0.0), axis=2)  # [bb, WM]
    if metric == "ip":
        out = -dot
    else:
        xx = jnp.sum(x * x, axis=1).reshape(bb, WM)
        qq = jnp.sum(q * q, axis=1)
        out = xx - 2.0 * dot + qq[:, None]
    dist_out[...] = jnp.where(nvalid != 0, out, jnp.inf)


@functools.partial(
    jax.jit,
    static_argnames=("logn", "m_out", "skip_layers", "metric", "block_b",
                     "window", "interpret"),
)
def hop_kernel_call(
    q, table, edges, u, L, R, visited, exp_ok, *, logn, m_out,
    skip_layers=True, metric="l2", block_b=8, window=8, interpret=False,
):
    """One fused whole-hop launch (DESIGN.md §3). See ``kernels/ref.py::hop``
    for the semantic contract and shapes: q f32[B, d], table ([n, d] float /
    Int8Vectors / PQVectors), edges the ``storage.EdgeRows`` row-DMA form
    of the neighbor table (``storage.pack_edges``), u int32[B, W], L/R
    int32[B*W], visited uint32[B, words], exp_ok bool[B, W]. Returns
    ``(nbr, ndist, nvalid, visited')``.

    Pads B to the ``block_b`` tile multiple (at least 8 rows, the sublane
    tile), the bitset rows to whole 128-word columns and the stored row
    width to the 128 lane width (zero columns are exact for both metrics),
    and views the vector table in its row-DMA form (``rowdma.row_table``).
    Codec decode happens in-register after the DMA (DESIGN.md §9). Because
    the gathered ids are *discovered inside* the kernel, the int8 per-row
    scales cannot be pre-gathered like ``gather_distance.py`` does — they
    ride as an ``ANY [n/128, 128]`` f32 input with a parallel DMA of each
    id's 128-scale row (second semaphore array); the PQ codebook is a
    VMEM-resident input.
    """
    if not isinstance(edges, _storage.EdgeRows):
        raise TypeError(f"hop_kernel_call reads storage.EdgeRows, got "
                        f"{type(edges).__name__} (storage.pack_edges)")
    B, d = q.shape
    K, Rw = edges.layers * edges.m, edges.R
    W = u.shape[1]
    words = visited.shape[1]
    bb = min(block_b, max(8, B))
    pad_to = _rows.pad_to

    meta = jnp.concatenate(
        [
            u.astype(jnp.int32),
            L.astype(jnp.int32).reshape(B, W),
            R.astype(jnp.int32).reshape(B, W),
            exp_ok.astype(jnp.int32),
        ],
        axis=1,
    )                                                     # [B, 4W]
    meta = pad_to(meta, bb, 0, value=-1)
    Bp = meta.shape[0]
    col = meta.reshape(Bp, 4, W).transpose(0, 2, 1).reshape(Bp * W, 4)
    qp = pad_to(pad_to(q, bb, 0), 128, 1)
    vp = pad_to(pad_to(jax.lax.bitcast_convert_type(visited, jnp.int32),
                       bb, 0), 128, 1)
    wp = vp.shape[1]
    dp = qp.shape[1]
    grid = (Bp // bb,)
    WM = W * m_out
    win = max(1, min(window, bb * W))

    codec, aux, aux_spec, pq_m, pq_dsub = None, None, None, 0, 0
    if isinstance(table, _storage.Int8Vectors):
        codec = "int8"
        tp, blocked = _rows.row_table(table.codes)
        aux = pad_to(table.scales.astype(jnp.float32), 128, 0)
        aux = aux.reshape(-1, 128)
        aux_spec = pl.BlockSpec(memory_space=pl.ANY)
    elif isinstance(table, _storage.PQVectors):
        codec = "pq"
        pq_m, _, pq_dsub = table.codebook.shape
        tp, blocked = _rows.row_table(table.codes)
        aux = table.codebook.reshape(pq_m * _storage.PQ_CENTROIDS, pq_dsub)
        aux_spec = pl.BlockSpec(aux.shape, lambda i: (0, 0))
    else:
        tp, blocked = _rows.row_table(table)

    in_specs = [
        pl.BlockSpec((bb, 4 * W), lambda i: (i, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((bb * W, 4), lambda i: (i, 0)),
        pl.BlockSpec((bb, dp), lambda i: (i, 0)),
        pl.BlockSpec((bb, wp), lambda i: (i, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    args = [meta, col, qp, vp, edges.rows, tp]
    if codec is not None:
        in_specs.append(aux_spec)
        args.append(aux)

    scratch_shapes = [
        pltpu.VMEM((bb * W, Rw, 128), jnp.int32),
        pltpu.VMEM((bb * W, m_out + 1), jnp.int32),
        pltpu.SMEM((bb * W, m_out + 1), jnp.int32),
        pltpu.SMEM((bb, WM), jnp.int32),
        pltpu.SMEM((bb * W,), jnp.int32),
        pltpu.SMEM((bb * WM,), jnp.int32),
        pltpu.VMEM((bb * WM, tp.shape[-1]), jnp.float32),
    ]
    if blocked:
        scratch_shapes.append(
            pltpu.VMEM((win, _rows.ROWS, tp.shape[-1]), tp.dtype))
    if codec == "int8":
        scratch_shapes.append(pltpu.VMEM((win, 128), jnp.float32))
    scratch_shapes.append(pltpu.SemaphoreType.DMA((win,)))
    if codec == "int8":
        scratch_shapes.append(pltpu.SemaphoreType.DMA((win,)))

    nbr, dist, nvalid, vis = pl.pallas_call(
        functools.partial(
            _hop_kernel, bb=bb, W=W, K=K, Rw=Rw, m=edges.m,
            m_out=m_out, logn=logn, skip_layers=skip_layers, metric=metric,
            window=win, codec=codec, blocked=blocked, dp=dp, pq_m=pq_m,
            pq_dsub=pq_dsub,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bb, WM), lambda i: (i, 0)),
            pl.BlockSpec((bb, WM), lambda i: (i, 0)),
            pl.BlockSpec((bb, WM), lambda i: (i, 0)),
            pl.BlockSpec((bb, wp), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, WM), jnp.int32),
            jax.ShapeDtypeStruct((Bp, WM), jnp.float32),
            jax.ShapeDtypeStruct((Bp, WM), jnp.int32),
            jax.ShapeDtypeStruct((Bp, wp), jnp.int32),
        ],
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(*args)
    vis = jax.lax.bitcast_convert_type(vis[:B, :words], jnp.uint32)
    return nbr[:B], dist[:B], nvalid[:B] != 0, vis
