"""Fused beam-search hot path: kernel parity, bitset, multi-expansion.

Covers the three legs of the fused expansion step:
  * gather-distance Pallas kernel (interpret mode) vs the jnp oracle, the
    historical inline ``_pairdist`` composition, and the tiled pairwise
    kernel;
  * packed uint32 visited bitset vs a dense bool visited map;
  * ``expand_width`` generalization: W=1 is bit-identical to the reference
    engine (``core/search_ref.py``); W>1 keeps recall on a saturating index.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import BuildConfig, RangeGraphIndex, bitset, edge_select, recall
from repro.core import search as search_mod
from repro.core import storage as storage_mod
from repro.core import search_ref
from repro.kernels import ref
from repro.kernels.distance import pairwise_dist_kernel_call
from repro.kernels.gather_distance import gather_distance_kernel_call


# ---------------------------------------------------------------------------
# gather-distance kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", ["l2", "ip"])
# the last case tiles both grid axes (B and M past one tile each)
@pytest.mark.parametrize("B,n,d,M", [(3, 64, 16, 9), (8, 128, 48, 16),
                                     (9, 256, 32, 200)])
def test_gather_distance_matches_oracle(metric, B, n, d, M):
    rng = np.random.default_rng(B * 100 + M)
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    ids = rng.integers(0, n, (B, M)).astype(np.int32)
    ids[rng.random((B, M)) < 0.3] = -1
    ids = jnp.asarray(ids)

    got = np.asarray(
        gather_distance_kernel_call(q, x, ids, metric=metric, interpret=True)
    )
    want = np.asarray(ref.gather_dist(q, x, ids, metric=metric))
    assert (np.isinf(got) == np.isinf(want)).all()
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-4)


def test_gather_distance_matches_pairdist_composition():
    """Oracle == the historical gather + _pairdist inline formulation."""
    rng = np.random.default_rng(0)
    B, n, d, M = 6, 100, 24, 11
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, n, (B, M)).astype(np.int32))
    want = search_mod._pairdist(q, x[jnp.maximum(ids, 0)], "l2")
    got = ref.gather_dist(q, x, ids)
    # all ids valid -> bit-identical math path
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gather_distance_matches_pairwise_kernel():
    """Gathering every row reproduces the tiled pairwise-distance kernel."""
    rng = np.random.default_rng(1)
    B, n, d = 4, 72, 32
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (B, n))
    got = np.asarray(
        gather_distance_kernel_call(q, x, ids, interpret=True)
    )
    want = np.asarray(
        pairwise_dist_kernel_call(
            q, x, block_q=8, block_n=16, block_k=16, interpret=True
        )
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_gather_distance_bf16_table():
    rng = np.random.default_rng(2)
    B, n, d, M = 3, 50, 16, 7
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
    ids = jnp.asarray(rng.integers(0, n, (B, M)).astype(np.int32))
    got = np.asarray(gather_distance_kernel_call(q, x, ids, interpret=True))
    want = np.asarray(ref.gather_dist(q, x, ids))
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# packed visited bitset
# ---------------------------------------------------------------------------

def _dense_test_and_set(dense, ids, valid):
    """Oracle: same contract as bitset.test_and_set on a bool[B, n] map."""
    B, K = ids.shape
    seen = np.zeros((B, K), bool)
    for b in range(B):
        for j in range(K):
            if not valid[b, j] or ids[b, j] < 0:
                continue
            v = ids[b, j]
            if dense[b, v]:
                seen[b, j] = True
            else:
                dense[b, v] = True
    return dense, seen


def test_bitset_matches_dense_bool():
    rng = np.random.default_rng(3)
    B, n, K = 7, 200, 23
    bits = bitset.make(B, n)
    dense = np.zeros((B, n), bool)
    for step in range(6):
        ids = rng.integers(-1, n, (B, K)).astype(np.int32)
        valid = rng.random((B, K)) < 0.8
        bits, seen = bitset.test_and_set(bits, jnp.asarray(ids),
                                         jnp.asarray(valid))
        dense, want_seen = _dense_test_and_set(dense, ids, valid)
        np.testing.assert_array_equal(np.asarray(seen), want_seen)
        # membership agrees on every id afterwards
        probe = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (B, n))
        np.testing.assert_array_equal(
            np.asarray(bitset.lookup(bits, probe)), dense
        )


def test_bitset_in_row_duplicates_exactly_once():
    bits = bitset.make(2, 64)
    ids = jnp.asarray([[5, 5, 9, 5], [63, 0, 63, -1]], jnp.int32)
    valid = jnp.ones((2, 4), bool)
    bits, seen = bitset.test_and_set(bits, ids, valid)
    # note: the -1 slot is *invalid*, not "seen" — callers mask by validity
    np.testing.assert_array_equal(
        np.asarray(seen),
        [[False, True, False, True], [False, False, True, False]],
    )
    # exactly the distinct ids are set
    probe = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32)[None], (2, 64))
    member = np.asarray(bitset.lookup(bits, probe))
    assert sorted(np.where(member[0])[0].tolist()) == [5, 9]
    assert sorted(np.where(member[1])[0].tolist()) == [0, 63]


def test_bitset_word_count():
    assert bitset.num_words(1) == 1
    assert bitset.num_words(32) == 1
    assert bitset.num_words(33) == 2
    assert bitset.make(4, 100).shape == (4, 4)
    assert bitset.make(4, 100).dtype == jnp.uint32


# ---------------------------------------------------------------------------
# expand_width generalization
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_index():
    rng = np.random.default_rng(7)
    n, d = 512, 16
    vectors = rng.standard_normal((n, d)).astype(np.float32)
    attrs = rng.uniform(0, 100, n)
    cfg = BuildConfig(m=8, ef_construction=32, brute_threshold=32)
    return RangeGraphIndex.build(vectors, attrs, cfg), rng


def test_expand_width1_bit_identical_to_reference(small_index):
    """Acceptance: W=1 reproduces the seed engine's ids AND dists exactly.

    The reference runs under jit like the seed's ``search_improvised`` did;
    eager evaluation changes XLA's FMA fusion and drifts by 1 ulp. The seed
    engine computes distances with the inline XLA formulation, so the pin
    holds at dist_impl="xla" (bit-exactness is per-backend; the Pallas
    kernel's parity with the oracle is covered to f32 tolerance above).
    """
    idx, rng = small_index
    n = idx.n
    B = 32
    q = rng.standard_normal((B, idx.dim)).astype(np.float32)
    L = rng.integers(0, n - 64, B).astype(np.int32)
    R = (L + rng.integers(8, 64, B)).astype(np.int32)

    got = idx.search_ranks(q, L, R, k=10, ef=48, expand_width=1,
                           dist_impl="xla", edge_impl="xla")

    @functools.partial(jax.jit, static_argnames=("ef", "k"))
    def ref_search(vec, nbrs, qj, Lj, Rj, *, ef, k):
        entries = search_mod.range_entry_ids(Lj, jnp.minimum(Rj, n - 1), n)
        ok = (entries >= Lj[:, None]) & (entries <= Rj[:, None])
        entries = jnp.where(ok, entries, -1)

        def nbr_fn(u):
            return edge_select.select_edges_batch(
                nbrs, u, Lj, Rj, logn=idx.logn, m_out=idx.m, skip_layers=True
            )

        return search_ref.beam_search_reference(
            vec, qj, entries, nbr_fn, ef=ef, k=k
        )

    # decode for the reference: under the CI storage legs the engine reads
    # codec tables (bf16 / Int8Vectors / SplitNeighbors) and expands them
    # to exactly these f32 values in its own distance path
    want = ref_search(
        jnp.asarray(storage_mod.decode_vectors(idx.vectors)),
        jnp.asarray(storage_mod.decode_neighbors(idx.neighbors)),
        jnp.asarray(q), jnp.asarray(L), jnp.asarray(R), ef=48, k=10,
    )
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    gd, wd = np.asarray(got.dists), np.asarray(want.dists)
    assert ((gd == wd) | (np.isinf(gd) & np.isinf(wd))).all()
    np.testing.assert_array_equal(
        np.asarray(got.n_hops), np.asarray(want.n_hops)
    )
    np.testing.assert_array_equal(
        np.asarray(got.n_dists), np.asarray(want.n_dists)
    )


def test_expand_width1_bit_identical_filtered(small_index):
    """Two-list (post-filtering) path: W=1 matches the reference too."""
    idx, rng = small_index
    n = idx.n
    B = 16
    q = rng.standard_normal((B, idx.dim)).astype(np.float32)
    L = rng.integers(0, n // 2, B).astype(np.int32)
    R = (L + 128).astype(np.int32)

    # both sides read the same decoded f32 tables: this test pins the
    # two-list traversal against the seed engine, not the storage codec
    vec = jnp.asarray(storage_mod.decode_vectors(idx.vectors))
    nbrs_dec = jnp.asarray(storage_mod.decode_neighbors(idx.neighbors))
    got = search_mod.search_filtered(
        vec, nbrs_dec,
        jnp.asarray(q), jnp.asarray(L), jnp.asarray(R),
        mode="post", ef=48, k=10, expand_width=1, dist_impl="xla",
    )

    @functools.partial(jax.jit, static_argnames=("ef", "k"))
    def ref_search(vec, nbrs, qj, Lj, Rj, *, ef, k):
        mid = jnp.clip((Lj + Rj) // 2, 0, n - 1)
        entries = jnp.stack([mid, jnp.zeros_like(mid) + n // 2], axis=1)

        def filt(ids):
            return (ids >= Lj[:, None]) & (ids <= Rj[:, None])

        def nbr_fn(u):
            row = nbrs[jnp.maximum(u, 0), 0, :]
            ok = (row >= 0) & (u >= 0)[:, None]
            return jnp.where(ok, row, -1)

        return search_ref.beam_search_reference(
            vec, qj, entries, nbr_fn, ef=ef, k=k, result_filter_fn=filt,
        )

    want = ref_search(
        vec, nbrs_dec,
        jnp.asarray(q), jnp.asarray(L), jnp.asarray(R), ef=48, k=10,
    )
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    gd, wd = np.asarray(got.dists), np.asarray(want.dists)
    assert ((gd == wd) | (np.isinf(gd) & np.isinf(wd))).all()


def test_expand_width_identical_recall_when_saturating(small_index):
    """On ranges the beam can fully hold, every W reaches the same recall."""
    idx, rng = small_index
    B = 24
    q = rng.standard_normal((B, idx.dim)).astype(np.float32)
    span = 48  # span < ef: search saturates the range for every W
    L = rng.integers(0, idx.n - span, B).astype(np.int32)
    R = (L + span - 1).astype(np.int32)
    gt, _ = idx.brute_force(q, L, R, k=10)
    recs = {
        w: recall(
            np.asarray(idx.search_ranks(q, L, R, k=10, ef=64,
                                        expand_width=w).ids), gt
        )
        for w in (1, 2, 4, 8)
    }
    assert recs[1] == 1.0
    assert all(r == recs[1] for r in recs.values()), recs


def test_expand_width_recall_holds_on_wide_ranges(small_index):
    """W>1 must not cost recall on ranges wider than the beam."""
    idx, rng = small_index
    B = 32
    q = rng.standard_normal((B, idx.dim)).astype(np.float32)
    span = 256
    L = rng.integers(0, idx.n - span, B).astype(np.int32)
    R = (L + span - 1).astype(np.int32)
    gt, _ = idx.brute_force(q, L, R, k=10)
    r1 = recall(np.asarray(idx.search_ranks(q, L, R, k=10, ef=64,
                                            expand_width=1).ids), gt)
    r4 = recall(np.asarray(idx.search_ranks(q, L, R, k=10, ef=64,
                                            expand_width=4).ids), gt)
    assert r4 >= r1 - 0.02, (r1, r4)
    assert r4 >= 0.85


def test_expand_width_fewer_iterations(small_index):
    """The point of W>1: same work in fewer while_loop trips (hops/W)."""
    idx, rng = small_index
    B = 16
    q = rng.standard_normal((B, idx.dim)).astype(np.float32)
    L = np.zeros(B, np.int32)
    R = np.full(B, idx.n // 2, np.int32)
    r1 = idx.search_ranks(q, L, R, k=10, ef=64, expand_width=1)
    r4 = idx.search_ranks(q, L, R, k=10, ef=64, expand_width=4)
    # hops count expanded nodes; per-iteration W=4 expands up to 4, so the
    # iteration count (hops ceil-div W) must shrink substantially
    assert np.mean(np.asarray(r4.n_hops)) / 4 < np.mean(np.asarray(r1.n_hops))
