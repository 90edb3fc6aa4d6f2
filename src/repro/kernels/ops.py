"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the Mosaic kernels run natively; everywhere else (this CPU
container, tests) they run in ``interpret=True`` mode unless the caller asks
for the pure-XLA reference instead. ``impl`` selection:

  * "pallas"    — pallas_call, interpret on non-TPU backends
  * "xla"       — ref.py jnp implementation (what the multi-pod dry-run
                  lowers, since Mosaic cannot lower on the CPU host platform)
  * "auto"      — pallas on TPU else xla; overridable per-op via the
                  ``REPRO_DIST_IMPL`` / ``REPRO_EDGE_IMPL`` /
                  ``REPRO_PRUNE_IMPL`` / ``REPRO_FLASH_IMPL`` env vars, or
                  globally via ``REPRO_IMPL`` (the CI backend matrix)
  * "argsort"   — edge selection only: the historical stable-argsort
                  formulation (``core/edge_select.py``), kept for regression
                  benchmarking
  * "legacy"    — construction prune only: the historical eager path
                  (``core/rng.py::prune_batch``, full [C, C] matrix), kept
                  as the bit-identical oracle and benchmark baseline
  * "composed"  — whole hop only: the three-op composition (select_edges
                  -> bitset.test_and_set -> gather_dist), kept as the
                  bit-identical oracle; the per-op ``edge_impl`` /
                  ``dist_impl`` knobs apply inside it. ``hop``'s "auto"
                  resolves to "composed" off-TPU (not "xla") so the per-op
                  knobs keep meaning something; any global ``REPRO_IMPL``
                  (including "legacy") resolves it the same way — only
                  ``REPRO_HOP_IMPL`` or TPU auto engages the megakernel —
                  and explicit per-op pins force it regardless of impl.

``select_edges`` is integer-exact: all three backends return bit-identical
ids. ``prune`` backends agree bit-identically in kept ids (keep decisions
compare f32 distances built from the same expansion). ``gather_dist``
backends agree to f32 tolerance (and bit-exactly under identical fusion).
``hop`` is integer-exact in (edges, newly-visited mask, bitset words)
across all three backends; distances agree to f32 tolerance.

Pallas branches merge the autotuner's installed picks
(``kernels/autotune.py::get_pick``) underneath any explicit ``**block_kw``,
so a measured block-size/pipeline-depth choice applies process-wide while
caller overrides still win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bitset as _bitset
from repro.core import edge_select as _legacy_edge_select
from repro.core import knobs as _knobs
from repro.core import rng as _legacy_rng
from repro.core import storage as _storage
from repro.kernels import autotune as _autotune
from repro.kernels import distance as _distance
from repro.kernels import edge_select as _edge_select
from repro.kernels import flash_attention as _flash
from repro.kernels import gather_distance as _gather
from repro.kernels import hop as _hop
from repro.kernels import prune as _prune
from repro.kernels import ref as _ref

__all__ = [
    "pairwise_dist", "gather_dist", "select_edges", "prune", "hop",
    "flash_attention", "default_impl",
]


def default_impl(kind: str | None = None) -> str:
    """Backend for ``impl="auto"``: pallas on TPU, xla elsewhere.

    ``kind`` ("dist" | "edge" | ...) checks ``REPRO_<KIND>_IMPL`` first,
    then the global ``REPRO_IMPL`` — the hook the CI backend matrix uses to
    force every auto dispatch through one backend.
    """
    if kind:
        forced = _knobs.get_str(f"REPRO_{kind.upper()}_IMPL")
        if forced:
            return forced
    forced = _knobs.get_str("REPRO_IMPL")
    if forced:
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _check_impl(op, impl, allowed):
    """Reject unknown backend tokens instead of silently running Pallas —
    e.g. a global REPRO_IMPL=legacy (prune-only token) or a typo must not
    route the other ops through the interpreter on CPU."""
    if impl not in allowed:
        raise ValueError(
            f"{op}: unknown impl {impl!r} (expected one of {sorted(allowed)})"
        )


def pairwise_dist(q, x, *, metric="l2", impl="auto", **block_kw):
    if impl == "auto":
        impl = default_impl("dist")
    _check_impl("pairwise_dist", impl, {"pallas", "xla"})
    if impl == "xla":
        return _ref.pairwise_dist(q, x, metric=metric)
    return _distance.pairwise_dist_kernel_call(
        q, x, metric=metric, interpret=_interpret(), **block_kw
    )


def gather_dist(q, table, ids, *, metric="l2", impl="auto", **block_kw):
    """Fused gather + masked distance for the beam-search hop.

    "pallas" runs the Mosaic kernel (no [B, M, d] intermediate); "xla" is the
    gather+einsum reference, which is also what "auto" picks off-TPU. The
    table may be a plain float [n, d] array or a codec struct
    (``storage.Int8Vectors`` / ``storage.PQVectors``, DESIGN.md §9): both
    backends decode — XLA via ``storage.decode_rows``, Pallas in-register
    after the row DMA. Codec tables use the separately-tuned
    ``"gather_dist_codec"`` autotune pick (narrow rows shift the optimum).
    """
    if impl == "auto":
        impl = default_impl("dist")
    _check_impl("gather_dist", impl, {"pallas", "xla"})
    if impl == "xla":
        return _ref.gather_dist(q, table, ids, metric=metric)
    kind = ("gather_dist_codec"
            if isinstance(table, (_storage.Int8Vectors, _storage.PQVectors))
            else "gather_dist")
    return _gather.gather_distance_kernel_call(
        q, table, ids, metric=metric, interpret=_interpret(),
        **{**_autotune.get_pick(kind), **block_kw},
    )


def select_edges(nbrs, us, L, R, *, logn, m_out, skip_layers=True,
                 impl="auto", **block_kw):
    """Fused edge improvisation (Algorithm 1) for a flat [F] frontier.

    "pallas" runs the Mosaic kernel (row-DMA gather + sort-free dedup, no
    [F, layers*m] HBM intermediate); "xla" is the sort-free jnp formulation
    (``ref.select_edges``), also what "auto" picks off-TPU; "argsort" is the
    historical stable-argsort formulation kept as a benchmark baseline. All
    backends return bit-identical int32[F, m_out] ids.
    """
    if impl == "auto":
        impl = default_impl("edge")
    _check_impl("select_edges", impl, {"pallas", "xla", "argsort"})
    if impl == "pallas":
        # the kernel reads the EdgeRows form; a dense table packs here, a
        # full-table copy per call (loops pack once, see reads_edge_rows)
        return _edge_select.edge_select_kernel_call(
            _storage.pack_edges(nbrs), us, L, R, logn=logn, m_out=m_out,
            skip_layers=skip_layers,
            interpret=_interpret(),
            **{**_autotune.get_pick("edge_select"), **block_kw},
        )
    # compact neighbor tables (int16 ids, -1 sentinel) decode here so every
    # jnp backend sees int32; trace-time no-op for already-wide tables
    nbrs = _storage.decode_neighbors(nbrs)
    if impl == "xla":
        return _ref.select_edges(
            nbrs, us, L, R, logn=logn, m_out=m_out, skip_layers=skip_layers
        )
    return _legacy_edge_select.select_edges_batch(
        nbrs, us, L, R, logn=logn, m_out=m_out, skip_layers=skip_layers
    )


_prune_xla = functools.partial(
    jax.jit, static_argnames=("m", "fill")
)(_ref.prune)
_prune_xla_vecs = functools.partial(
    jax.jit, static_argnames=("m", "fill")
)(_ref.prune_vecs)


@functools.partial(jax.jit, static_argnames=("m", "fill"))
def _prune_legacy(cand_ids, cand_dists, table, *, m, alpha=1.0, fill=True):
    cvec = _storage.decode_rows(table, jnp.maximum(cand_ids, 0))
    return _legacy_rng.prune_batch(
        cand_ids, cand_dists, cvec, m=m, alpha=alpha, fill=fill
    )


def prune(cand_ids, cand_dists, table, *, m, alpha=1.0, fill=True,
          impl="auto", cand_vecs=None, **block_kw):
    """Fused construction prune (the per-level build hot loop).

    "pallas" runs the Mosaic kernel (row-DMA gather + lazy cc columns, no
    [B, C, d] or [B, C, C] HBM intermediates); "xla" is the lazy-column jnp
    formulation (``ref.prune``), also what "auto" picks off-TPU; "legacy" is
    the historical eager path (XLA gather + full [C, C] distance matrix +
    C-step scan, ``core/rng.py::prune_batch``), kept as the bit-identical
    oracle and benchmark baseline. All backends agree in kept ids.

    ``cand_vecs`` [B, C, d]: the already-gathered candidate vectors, when
    the caller materialized them anyway (the build loop does, to compute
    ``cand_dists``) — saves the xla/legacy paths a redundant gather. The
    Pallas path ignores it: DMA-ing rows straight from ``table`` is the
    point. Gathers are exact, so results are identical either way.

    ``table`` may be a codec struct (``storage.Int8Vectors`` /
    ``storage.PQVectors``); every backend decodes — the Pallas kernel
    in-register after the row DMA (DESIGN.md §9).
    """
    if impl == "auto":
        impl = default_impl("prune")
    _check_impl("prune", impl, {"pallas", "xla", "legacy"})
    if impl == "xla":
        if cand_vecs is not None:
            return _prune_xla_vecs(
                cand_ids, cand_dists, cand_vecs, m=m, alpha=alpha, fill=fill
            )
        return _prune_xla(
            cand_ids, cand_dists, table, m=m, alpha=alpha, fill=fill
        )
    if impl == "legacy":
        if cand_vecs is not None:
            return _legacy_rng.prune_batch(
                cand_ids, cand_dists, cand_vecs, m=m, alpha=alpha, fill=fill
            )
        return _prune_legacy(
            cand_ids, cand_dists, table, m=m, alpha=alpha, fill=fill
        )
    return _prune.prune_kernel_call(
        cand_ids, cand_dists, table, m=m, alpha=float(alpha), fill=fill,
        interpret=_interpret(),
        **{**_autotune.get_pick("prune"), **block_kw},
    )


def _resolve_hop(impl, edge_impl, dist_impl):
    """``hop``'s dispatch rule (see its docstring): the resolved
    ``(impl, edge_impl, dist_impl)``, impl one of pallas/xla/composed."""
    if impl == "auto":
        forced = _knobs.get_str("REPRO_HOP_IMPL")
        glob = _knobs.get_str("REPRO_IMPL")
        if forced:
            impl = forced
        elif glob == "legacy":
            impl = "legacy"
        elif glob:
            # the global override targets the *per-op* kernels: keep the hop
            # composed so each inner op's auto resolves to the forced
            # backend — only REPRO_HOP_IMPL (or TPU auto) engages the fused
            # megakernel, so e.g. the REPRO_IMPL=pallas CI leg still runs
            # the per-op interpreted kernels, not an interpreted whole-hop
            # inside every deadline-sensitive serving test
            impl = "composed"
        else:
            # off-TPU auto stays "composed" (not "xla") so the per-op
            # edge_impl/dist_impl knobs keep applying inside the hop
            impl = "pallas" if jax.default_backend() == "tpu" else "composed"
    if impl == "legacy":
        # global REPRO_IMPL=legacy (prune-only token) falls back to the
        # composed path rather than erroring the whole hop; the inner ops
        # would reject the token too, so their autos resolve backend-default
        impl = "composed"
        inner = "pallas" if jax.default_backend() == "tpu" else "xla"
        if edge_impl == "auto":
            edge_impl = inner
        if dist_impl == "auto":
            dist_impl = inner
    _check_impl("hop", impl, {"pallas", "xla", "composed"})
    if impl != "composed" and not (edge_impl == "auto"
                                   and dist_impl == "auto"):
        # an explicit per-op pin always wins — neither the megakernel nor
        # the jnp composition has per-op backends, so a caller that pinned
        # edge_impl/dist_impl (e.g. dist_impl="xla" for per-backend
        # bit-exactness) routes through the composed path even when
        # REPRO_HOP_IMPL forces "pallas"
        impl = "composed"
    return impl, edge_impl, dist_impl


def reads_edge_rows(hop_impl="auto", edge_impl="auto", dist_impl="auto"):
    """Whether ``hop`` with these impls reads the neighbor table through a
    Pallas kernel, i.e. in the ``storage.EdgeRows`` form. A caller that
    runs many hops over one table packs it once when this holds (the
    executor's upload, the jitted search) and keeps the dense form
    otherwise, so no per-hop or per-call repack happens on either path."""
    impl, edge_impl, _ = _resolve_hop(hop_impl, edge_impl, dist_impl)
    if impl == "composed":
        if edge_impl == "auto":
            edge_impl = default_impl("edge")
        return edge_impl == "pallas"
    return impl == "pallas"


def hop(q, table, nbrs, u, L, R, visited, exp_ok, *, logn, m_out,
        skip_layers=True, metric="l2", impl="auto", edge_impl="auto",
        dist_impl="auto", **block_kw):
    """One whole beam-search hop: edge improvisation + visited test-and-set
    + gather-distance, the full ``beam_search`` iteration body.

    "pallas" runs the fused megakernel (``kernels/hop.py``) — one launch,
    frontier resident in VMEM; "xla" is the jnp composition
    (``ref.hop``); "composed" chains the three *dispatched* ops
    (``select_edges`` -> ``bitset.test_and_set`` -> ``gather_dist``), so
    the per-op ``edge_impl`` / ``dist_impl`` knobs apply — it is the
    bit-identical oracle and the pre-fusion production path. "auto" picks
    pallas on TPU and "composed" off-TPU (keeping per-op knobs live);
    ``REPRO_HOP_IMPL`` overrides that choice. The global ``REPRO_IMPL``
    resolves "auto" to "composed" (its job is forcing the *per-op*
    kernels, which run inside the composition; "legacy" maps the same
    way) — only ``REPRO_HOP_IMPL`` or TPU auto engages the megakernel.
    An explicit non-"auto" ``edge_impl``/``dist_impl`` pin always wins:
    it routes any resolved impl through "composed", since the fused
    kernel has no per-op backends. Integer outputs
    (nbr, nvalid, visited) are bit-identical across backends; distances
    agree to f32 tolerance.

    Shapes: q f32[B, d], table ([n, d] float or a codec struct —
    ``storage.Int8Vectors`` / ``storage.PQVectors``, decoded in-register by
    the megakernel per DESIGN.md §9), nbrs [n, layers, m] in any neighbor
    codec or ``storage.EdgeRows`` (the Pallas kernels read that form; the
    jnp paths decode), u int32[B, W], L/R int32[B*W], visited
    uint32[B, words], exp_ok bool[B, W] -> (nbr i32[B, W*m_out], ndist
    f32[B, W*m_out], nvalid bool[B, W*m_out], visited' uint32[B, words]).
    """
    impl, edge_impl, dist_impl = _resolve_hop(impl, edge_impl, dist_impl)
    if impl == "composed":
        B, W = u.shape
        nbr = select_edges(
            nbrs, u.reshape(B * W), L, R, logn=logn, m_out=m_out,
            skip_layers=skip_layers, impl=edge_impl,
        ).reshape(B, W * m_out)
        pre_valid = (nbr >= 0) & jnp.repeat(exp_ok, m_out, axis=1)
        visited, seen = _bitset.test_and_set(visited, nbr, pre_valid)
        nvalid = pre_valid & ~seen
        ndist = gather_dist(
            q, table, jnp.where(nvalid, nbr, -1), metric=metric,
            impl=dist_impl,
        )
        return nbr, ndist, nvalid, visited
    if impl == "xla":
        return _ref.hop(
            q, table, _storage.decode_neighbors(nbrs), u, L, R, visited,
            exp_ok, logn=logn, m_out=m_out, skip_layers=skip_layers,
            metric=metric,
        )
    # the megakernel reads the EdgeRows form; a dense table packs here, a
    # full-table copy per call (loops pack once, see reads_edge_rows)
    return _hop.hop_kernel_call(
        q, table, _storage.pack_edges(nbrs), u, L, R, visited, exp_ok,
        logn=logn, m_out=m_out, skip_layers=skip_layers, metric=metric,
        interpret=_interpret(),
        **{**_autotune.get_pick("hop"), **block_kw},
    )


def flash_attention(
    q, k, v, *, causal=True, window=None, softcap=None, scale=None,
    q_offset=0, impl="auto", unroll=1, **block_kw,
):
    if impl == "auto":
        impl = default_impl("flash")
    _check_impl("flash_attention", impl, {"pallas", "xla"})
    if impl == "xla":
        return _ref.attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, q_offset=q_offset, unroll=unroll,
            **{k2: v2 for k2, v2 in block_kw.items() if k2 == "block_q"},
        )
    return _flash.flash_attention_kernel_call(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        q_offset=q_offset, interpret=_interpret(), **block_kw
    )
