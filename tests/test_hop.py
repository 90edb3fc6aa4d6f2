"""Whole-hop megakernel: bit-identity vs the composed three-op oracle.

The fused hop (``kernels/hop.py`` / ``ref.hop``) must reproduce the
composed select_edges -> bitset.test_and_set -> gather_dist path exactly:
integer outputs (edges, newly-visited mask, bitset words) bit-for-bit on
both the xla and pallas(interpret) backends, distances to f32 tolerance —
including compact (bf16 vectors + int16 neighbor) storage, degenerate
ranges, expand_width > 1, and bitset boundaries at n not a multiple of 32.
Plus the dispatch guards: unknown tokens raise, ``REPRO_HOP_IMPL`` wins
over ``REPRO_IMPL``, and a global ``REPRO_IMPL=legacy`` falls back to the
composed path instead of erroring.
"""
import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SearchConfig, bitset
from repro.core import storage as storage_mod
from repro.core.search import beam_search
from repro.kernels import ops
from repro.kernels import ref as _ref
from repro.kernels.edge_select import edge_select_kernel_call
from repro.kernels.hop import hop_kernel_call


def _mk(n=300, d=24, m=4, B=6, W=3, m_out=8, seed=0, full_range=False):
    """A structurally unconstrained hop problem (edges may be junk ids or
    -1; the hop must mask them identically on every backend)."""
    rng = np.random.default_rng(seed)
    logn = max(int(np.ceil(np.log2(max(n, 2)))), 1)
    layers = logn + 1
    table = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    nbrs = jnp.asarray(
        rng.integers(-1, n, size=(n, layers, m)).astype(np.int32))
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    u = jnp.asarray(rng.integers(-1, n, size=(B, W)).astype(np.int32))
    if full_range:
        L = jnp.zeros((B,), jnp.int32)
        R = jnp.full((B,), n - 1, jnp.int32)
    else:
        L = jnp.asarray(rng.integers(0, n // 2, size=(B,)).astype(np.int32))
        R = L + jnp.asarray(
            rng.integers(0, n // 2, size=(B,)).astype(np.int32))
    Lw, Rw = jnp.repeat(L, W), jnp.repeat(R, W)
    visited = bitset.make(B, n)
    pre = jnp.asarray(rng.integers(0, n, size=(B, 9)).astype(np.int32))
    visited, _ = bitset.test_and_set(visited, pre, jnp.ones((B, 9), bool))
    exp_ok = jnp.asarray(rng.integers(0, 2, size=(B, W)).astype(bool))
    return dict(args=(q, table, nbrs, u, Lw, Rw, visited, exp_ok),
                kw=dict(logn=logn, m_out=m_out))


def _assert_hop_equal(got, want, dist_tol=1e-5):
    """Integer outputs bit-identical; distances f32-close (inf-masked
    slots must agree exactly, so compare the mask first)."""
    nbr_g, nd_g, nv_g, vis_g = (np.asarray(x) for x in got)
    nbr_w, nd_w, nv_w, vis_w = (np.asarray(x) for x in want)
    np.testing.assert_array_equal(nbr_g, nbr_w)
    np.testing.assert_array_equal(nv_g, nv_w)
    np.testing.assert_array_equal(vis_g, vis_w)
    np.testing.assert_array_equal(np.isfinite(nd_g), np.isfinite(nd_w))
    fin = np.isfinite(nd_w)
    np.testing.assert_allclose(nd_g[fin], nd_w[fin],
                               rtol=dist_tol, atol=dist_tol)


# ---------------------------------------------------------------------------
# bit-identity vs the composed oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_hop_matches_composed(impl, metric):
    p = _mk()
    want = ops.hop(*p["args"], metric=metric, impl="composed",
                   edge_impl="xla", dist_impl="xla", **p["kw"])
    got = ops.hop(*p["args"], metric=metric, impl=impl, **p["kw"])
    _assert_hop_equal(got, want)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_hop_compact_storage(impl):
    """bf16 vectors + int16 neighbor ids: the neighbor codec decodes at
    the dispatch layer, the vector table stays compact into the kernel."""
    p = _mk(seed=3)
    q, table, nbrs, u, Lw, Rw, visited, exp_ok = p["args"]
    tb = table.astype(jnp.bfloat16)
    nb = jnp.asarray(storage_mod.encode_neighbors(
        np.asarray(nbrs), table.shape[0],
        storage_mod.StorageConfig(neighbor_dtype="int16")))
    assert nb.dtype == jnp.int16
    args_c = (q, tb, nb, u, Lw, Rw, visited, exp_ok)
    want = ops.hop(*args_c, impl="composed", edge_impl="xla",
                   dist_impl="xla", **p["kw"])
    got = ops.hop(*args_c, impl=impl, **p["kw"])
    # bf16 quantizes the table identically on both sides: ids stay exact
    _assert_hop_equal(got, want, dist_tol=1e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_hop_degenerate_ranges(impl):
    """L == R (single-point range) and L > R (empty range) per query."""
    p = _mk(B=4, W=2, seed=5)
    q, table, nbrs, u, _, _, visited, exp_ok = p["args"]
    n = table.shape[0]
    L = jnp.asarray([10, n - 1, 50, 40], jnp.int32)
    R = jnp.asarray([10, n - 1, 20, 39], jnp.int32)   # rows 2,3: empty
    Lw, Rw = jnp.repeat(L, 2), jnp.repeat(R, 2)
    args = (q, table, nbrs, u, Lw, Rw, visited, exp_ok)
    want = ops.hop(*args, impl="composed", edge_impl="xla",
                   dist_impl="xla", **p["kw"])
    got = ops.hop(*args, impl=impl, **p["kw"])
    _assert_hop_equal(got, want)
    # empty ranges select nothing: every edge slot of rows 2,3 is -1
    nbr = np.asarray(got[0])
    assert (nbr[2:] == -1).all()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("W", [1, 4])
def test_hop_expand_width(impl, W):
    p = _mk(B=3, W=W, seed=7)
    want = ops.hop(*p["args"], impl="composed", edge_impl="xla",
                   dist_impl="xla", **p["kw"])
    got = ops.hop(*p["args"], impl=impl, **p["kw"])
    _assert_hop_equal(got, want)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("n", [63, 65, 70])
def test_hop_bitset_saturation_at_n_boundary(impl, n):
    """n not a multiple of 32, frontier/edges clustered at n-1, visited
    nearly saturated: the packed test-and-set must not touch bits past n
    and must dedup ids already present in the (almost full) bitset."""
    p = _mk(n=n, B=4, W=2, m_out=6, seed=11, full_range=True)
    q, table, nbrs, u, Lw, Rw, visited, exp_ok = p["args"]
    # point the frontier at the top ids and pre-visit everything but the
    # last few, so most candidate edges hit already-set bits
    u = jnp.full_like(u, n - 1).at[:, 0].set(n - 2)
    all_ids = jnp.broadcast_to(jnp.arange(n - 3, dtype=jnp.int32),
                               (u.shape[0], n - 3))
    visited, _ = bitset.test_and_set(
        visited, all_ids, jnp.ones(all_ids.shape, bool))
    exp_ok = jnp.ones_like(exp_ok)
    args = (q, table, nbrs, u, Lw, Rw, visited, exp_ok)
    want = ops.hop(*args, impl="composed", edge_impl="xla",
                   dist_impl="xla", **p["kw"])
    got = ops.hop(*args, impl=impl, **p["kw"])
    _assert_hop_equal(got, want)
    # no bit at an index >= n may ever be set
    words = np.asarray(got[3])
    tail_bits = words[:, -1] >> (n % 32 if n % 32 else 32)
    if n % 32:
        assert (tail_bits == 0).all()


def test_hop_kernel_block_sizes():
    """Tile/pipeline knobs change scheduling, never results."""
    p = _mk(B=5, seed=13)
    args = list(p["args"])
    args[2] = storage_mod.pack_edges(args[2])   # the kernel's row-DMA form
    base = hop_kernel_call(*args, interpret=True, **p["kw"])
    for bb, w in ((1, 2), (2, 4), (8, 16)):
        got = hop_kernel_call(*args, block_b=bb, window=w,
                              interpret=True, **p["kw"])
        _assert_hop_equal(got, base)


# ---------------------------------------------------------------------------
# live-slot loops: the kernel visits and gathers the live slots alone
# ---------------------------------------------------------------------------

_LN, _LB, _LW, _LM = 200, 10, 2, 8      # n, B (two tiles of 8), W, m=m_out


def _live_case(case):
    """``(lists, exp_ok, pre)`` of one live-slot edge case: frontier
    slot f = b*W + w expands node 100 + f, whose every layer holds
    ``lists[f]`` (so exactly its distinct ids are selected, in order);
    ``None`` makes the slot u = -1. ``pre`` is a list of (row, id)
    visited before the hop. Unlisted slots of the batch are dead."""
    F = _LB * _LW
    fresh = itertools.cycle(range(100))     # distinct within any row

    def take(c):
        return [next(fresh) for _ in range(c)]

    exp_ok = np.ones((_LB, _LW), bool)
    pre = []
    if case == "no_live_exp":
        lists = [take(_LM) for _ in range(F)]
        exp_ok[:] = False
    elif case == "no_live_u":
        lists = [None] * F
    elif case == "all_live":            # every slot of tile 0 new
        lists = [take(_LM) for _ in range(8 * _LW)] + [None] * (F - 16)
    elif case in ("one_live", "below_window", "at_window", "above_window"):
        # live copies in tile 0 against the DMA window of 4: 1, 3, 4, 9;
        # in above_window one node has u >= 0 but selects nothing
        counts = {"one_live": [1], "below_window": [2, 1],
                  "at_window": [4], "above_window": [8, 1, 0]}[case]
        lists = [take(c) for c in counts] + [None] * (F - len(counts))
    elif case == "repeated_ids":
        # row 0: 6 and 5 come twice (only the first is new), 5 once more
        # in one node; row 1: 5 again (new in its own bitset) and 40
        # (visited before); row 2: expansion off
        lists = [[5, 6, 7, 5], [6, 9, 5], [5, 40], [41], [5, 6], [7]]
        lists += [None] * (F - len(lists))
        pre = [(1, 40), (3, 9)]
        exp_ok[2] = False
    elif case == "ids_0_and_n-1":
        # the bitset's first and last bits, and a word's edges
        lists = [[0, _LN - 1], [31, 32], [_LN - 1, 1, 0], None,
                 [_LN - 1], [0]]
        lists += [None] * (F - len(lists))
        pre = [(2, 0)]
    else:                               # "mixed": random lists
        rng = np.random.default_rng(23)
        lists = [None if rng.random() < 0.3 else
                 list(rng.integers(-1, _LN, size=_LM)) for _ in range(F)]
        exp_ok = rng.random((_LB, _LW)) < 0.7
        pre = [(int(b), int(i)) for b, i in zip(
            rng.integers(0, _LB, 40), rng.integers(0, _LN, 40))]
    return lists, exp_ok, pre


def _live_problem(case, codec, d=24):
    lists, exp_ok, pre = _live_case(case)
    rng = np.random.default_rng(29)
    logn = int(np.ceil(np.log2(_LN)))
    nbrs = np.full((_LN, logn + 1, _LM), -1, np.int32)
    u = np.full((_LB * _LW,), -1, np.int32)
    for f, ids in enumerate(lists):
        if ids is not None:
            u[f] = 100 + f
            nbrs[100 + f, :, :len(ids)] = ids
    vecs = rng.standard_normal((_LN, d)).astype(np.float32)
    cfg = {"f32": storage_mod.StorageConfig(vector_dtype="float32"),
           "bf16": storage_mod.StorageConfig(vector_dtype="bfloat16"),
           "int8": storage_mod.StorageConfig.int8(),
           "pq": storage_mod.StorageConfig.pq()}[codec]
    table = storage_mod.as_device(storage_mod.encode_vectors(vecs, cfg))
    visited = bitset.make(_LB, _LN)
    if pre:
        rows, ids = zip(*pre)
        ids_b = np.full((_LB, len(pre)), -1, np.int32)
        ids_b[list(rows), np.arange(len(pre))] = ids
        visited, _ = bitset.test_and_set(
            visited, jnp.asarray(ids_b), jnp.asarray(ids_b >= 0))
    q = jnp.asarray(rng.standard_normal((_LB, d)), jnp.float32)
    full = jnp.full((_LB * _LW,), _LN - 1, jnp.int32)
    args = (q, table, jnp.asarray(nbrs), jnp.asarray(u).reshape(_LB, _LW),
            jnp.zeros_like(full), full, visited, jnp.asarray(exp_ok))
    return args, dict(logn=logn, m_out=_LM)


@pytest.mark.parametrize("case, codec", [
    ("no_live_exp", "f32"), ("no_live_u", "f32"), ("all_live", "f32"),
    ("one_live", "f32"), ("below_window", "f32"), ("at_window", "f32"),
    ("above_window", "f32"), ("repeated_ids", "f32"),
    ("ids_0_and_n-1", "f32"), ("mixed", "f32"),
    ("below_window", "bf16"), ("above_window", "bf16"), ("mixed", "bf16"),
    ("below_window", "int8"), ("mixed", "int8"), ("mixed", "pq"),
])
def test_hop_live_slots(case, codec):
    """The megakernel runs its edge DMAs, visited test-and-set and row DMAs
    over the live slots alone; on a tile with none, with all of them, with
    fewer, as many and more live row copies than the DMA window, with ids
    repeated in and across rows and at the bitset's ends, and on the
    blocked and quantized tables, it still matches ``ref.hop``: integer
    outputs bit for bit, distances to f32 tolerance."""
    args, kw = _live_problem(case, codec)
    want = _ref.hop(*args, **kw)
    got = ops.hop(*args, impl="pallas", window=4, **kw)
    _assert_hop_equal(got, want, dist_tol=1e-5 if codec == "f32" else 1e-4)
    # the rows each case gathers, as it sets out to
    new_rows = {"no_live_exp": 0, "no_live_u": 0, "all_live": 128,
                "one_live": 1, "below_window": 3, "at_window": 4,
                "above_window": 9, "repeated_ids": 4 + 2}
    if case in new_rows:
        assert np.asarray(want[2]).sum() == new_rows[case]


# ---------------------------------------------------------------------------
# dispatch guards
# ---------------------------------------------------------------------------

def test_hop_unknown_impl_rejected():
    p = _mk(B=2, W=1, seed=1)
    with pytest.raises(ValueError, match="hop: unknown impl"):
        ops.hop(*p["args"], impl="bogus", **p["kw"])


def test_hop_global_legacy_falls_back_to_composed(monkeypatch):
    """REPRO_IMPL=legacy (the prune-only token) must not error the hop:
    it falls back to the composed path, inner autos resolving
    backend-default so they don't see the foreign token either."""
    p = _mk(B=2, W=1, seed=2)
    want = ops.hop(*p["args"], impl="composed", **p["kw"])
    monkeypatch.delenv("REPRO_HOP_IMPL", raising=False)
    monkeypatch.setenv("REPRO_IMPL", "legacy")
    got = ops.hop(*p["args"], **p["kw"])
    _assert_hop_equal(got, want)
    # explicit impl="legacy" maps the same way
    got = ops.hop(*p["args"], impl="legacy", **p["kw"])
    _assert_hop_equal(got, want)


def test_hop_env_override_precedence(monkeypatch):
    """REPRO_HOP_IMPL beats REPRO_IMPL, and bogus env tokens still raise."""
    p = _mk(B=2, W=1, seed=4)
    want = ops.hop(*p["args"], impl="composed", **p["kw"])
    monkeypatch.setenv("REPRO_IMPL", "xla")
    monkeypatch.setenv("REPRO_HOP_IMPL", "pallas")
    got = ops.hop(*p["args"], **p["kw"])
    _assert_hop_equal(got, want)
    monkeypatch.setenv("REPRO_HOP_IMPL", "bogus")
    with pytest.raises(ValueError, match="hop: unknown impl"):
        ops.hop(*p["args"], **p["kw"])


def test_hop_global_impl_keeps_hop_composed(monkeypatch):
    """REPRO_IMPL targets the per-op kernels: with it set (and no
    REPRO_HOP_IMPL) the hop's auto must stay composed, so the inner ops
    see the forced backend — e.g. the REPRO_IMPL=pallas CI leg runs the
    per-op interpreted kernels, never an interpreted whole-hop inside
    every serving test."""
    p = _mk(B=2, W=1, seed=5)
    want = ops.hop(*p["args"], impl="composed", **p["kw"])
    monkeypatch.delenv("REPRO_HOP_IMPL", raising=False)
    for glob in ("xla", "pallas"):
        monkeypatch.setenv("REPRO_IMPL", glob)
        got = ops.hop(*p["args"], **p["kw"])
        _assert_hop_equal(got, want)


def test_hop_per_op_pin_beats_forced_pallas(monkeypatch):
    """An explicit edge_impl/dist_impl pin must survive REPRO_HOP_IMPL:
    the megakernel has no per-op backends, so a pinned call routes
    through the composed path and reproduces it bit-for-bit — distances
    included (the beam-search per-backend bit-exactness tests rely on
    dist_impl="xla" holding under every env)."""
    p = _mk(B=2, W=1, seed=6)
    want = ops.hop(*p["args"], impl="composed", edge_impl="xla",
                   dist_impl="xla", **p["kw"])
    monkeypatch.setenv("REPRO_HOP_IMPL", "pallas")
    got = ops.hop(*p["args"], edge_impl="xla", dist_impl="xla", **p["kw"])
    _assert_hop_equal(got, want)
    gd, wd = np.asarray(got[1]), np.asarray(want[1])
    assert ((gd == wd) | (np.isinf(gd) & np.isinf(wd))).all()


def test_search_config_hop_impl_validated():
    assert SearchConfig(hop_impl="pallas").hop_impl == "pallas"
    with pytest.raises(ValueError, match="hop_impl"):
        SearchConfig(hop_impl="bogus")


def test_beam_search_hop_fn_excludes_result_filter():
    with pytest.raises(ValueError, match="hop_fn is incompatible"):
        beam_search(
            jnp.zeros((8, 4)), jnp.zeros((2, 4)),
            jnp.zeros((2, 2), jnp.int32), None, k=1,
            hop_fn=lambda u, e, v: None,
            result_filter_fn=lambda ids: ids >= 0,
        )


# ---------------------------------------------------------------------------
# end-to-end: the jitted improvised search is backend-invariant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hop_impl", ["xla", "pallas"])
def test_search_improvised_hop_impl_equivalent(hop_impl):
    """The whole jitted search returns identical ids/dists whether the hop
    runs composed, as the jnp fusion, or as the Pallas megakernel."""
    from repro.core import BuildConfig, RangeGraphIndex

    rng = np.random.default_rng(21)
    n, d = 128, 8
    vectors = rng.standard_normal((n, d)).astype(np.float32)
    attrs = rng.uniform(0, 100, n)
    idx = RangeGraphIndex.build(
        vectors, attrs, BuildConfig(m=4, ef_construction=16,
                                    brute_threshold=8))
    B = 3
    q = rng.standard_normal((B, d)).astype(np.float32)
    L = np.asarray([0, 20, 60], np.int32)
    R = np.asarray([n - 1, 90, 61], np.int32)
    base_cfg = SearchConfig(ef=16, expand_width=2, dist_impl="xla",
                            edge_impl="xla", hop_impl="composed")
    want = idx.search_ranks(q, L, R, k=5, config=base_cfg)
    got = idx.search_ranks(q, L, R, k=5,
                           config=base_cfg.replace(hop_impl=hop_impl))
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_allclose(
        np.asarray(got.dists), np.asarray(want.dists), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(got.n_dists), np.asarray(want.n_dists))


# ---------------------------------------------------------------------------
# edge-select lazy dedup (the standalone kernel's new default)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K_big", [False, True])
def test_edge_select_lazy_matches_eager(K_big):
    """Lazy O(m_out*K) dedup == eager [K,K] matrix, including K > 384
    where lazy keeps the full bf=8 row tile (the lifted VMEM cap)."""
    rng = np.random.default_rng(17)
    if K_big:
        n, m = 2000, 36         # logn=11, layers=12 -> K=432 > 384
    else:
        n, m = 500, 4
    logn = max(int(np.ceil(np.log2(max(n, 2)))), 1)
    layers = logn + 1
    K = layers * m
    if K_big:
        assert K > 384
    nbrs = jnp.asarray(
        rng.integers(-1, n, size=(n, layers, m)).astype(np.int32))
    F = 10
    us = jnp.asarray(rng.integers(-1, n, size=(F,)).astype(np.int32))
    L = jnp.asarray(rng.integers(0, n // 2, size=(F,)).astype(np.int32))
    R = L + 200
    kw = dict(logn=logn, m_out=8, interpret=True)
    edges = storage_mod.pack_edges(nbrs)
    lazy = edge_select_kernel_call(edges, us, L, R, dedup="lazy", **kw)
    eager = edge_select_kernel_call(edges, us, L, R, dedup="eager", **kw)
    ref = ops.select_edges(nbrs, us, L, R, logn=logn, m_out=8, impl="xla")
    np.testing.assert_array_equal(np.asarray(lazy), np.asarray(eager))
    np.testing.assert_array_equal(np.asarray(lazy), np.asarray(ref))


def test_edge_select_unknown_dedup_rejected():
    rng = np.random.default_rng(0)
    nbrs = jnp.asarray(rng.integers(-1, 16, (16, 5, 4)).astype(np.int32))
    us = jnp.asarray([0, 1], jnp.int32)
    with pytest.raises(ValueError, match="unknown dedup"):
        edge_select_kernel_call(
            storage_mod.pack_edges(nbrs), us, jnp.zeros(2, jnp.int32),
            jnp.full(2, 15, jnp.int32), logn=4, m_out=4, dedup="nope",
            interpret=True)
