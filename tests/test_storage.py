"""Compact storage end-to-end: codec laws, index threading, kernel parity.

The contract under test (``core/storage.py``): vectors may store bf16/f16
and neighbor ids int16 with ONE sentinel convention — ``-1`` in every
storage dtype — so the decode is a plain widening cast, ids are
bit-identical across codecs, and all distance math stays f32.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import BuildConfig, RangeGraphIndex, StorageConfig, recall
from repro.core import storage as storage_mod
from repro.kernels import ops, ref
from repro.kernels.gather_distance import gather_distance_kernel_call


# ---------------------------------------------------------------------------
# codec laws
# ---------------------------------------------------------------------------

def test_neighbor_codec_roundtrip_preserves_sentinel():
    rng = np.random.default_rng(0)
    n = 1000
    nbrs = rng.integers(0, n, (64, 5, 8)).astype(np.int32)
    nbrs[rng.random(nbrs.shape) < 0.3] = -1
    enc = storage_mod.encode_neighbors(nbrs, n, StorageConfig.compact())
    assert enc.dtype == np.int16
    dec = storage_mod.decode_neighbors(enc)
    assert dec.dtype == np.int32
    np.testing.assert_array_equal(dec, nbrs)


@pytest.mark.parametrize("codec", ["int32", "int16", "split"])
@pytest.mark.parametrize("layers,m", [(5, 8), (9, 16), (8, 16)])
def test_edge_rows_roundtrip(codec, layers, m):
    """EdgeRows (the kernels' row-DMA form) packs any neighbor codec into
    whole 128-lane rows per node, -1 padded, and unpacks bit-identically;
    (8, 16) is the K = 128 case with no padding."""
    import jax

    rng = np.random.default_rng(layers * m)
    n = 1 << (layers - 1)
    nbrs = rng.integers(0, n, (n, layers, m)).astype(np.int32)
    nbrs[rng.random(nbrs.shape) < 0.3] = -1
    if codec == "split":
        # split offsets need edges inside their node's segment
        lay = np.arange(layers)[None, :, None]
        u = np.arange(n)[:, None, None]
        lo = (u >> (layers - 1 - lay)) << (layers - 1 - lay)
        width = 1 << (layers - 1 - lay)
        nbrs = np.where(nbrs >= 0, lo + nbrs % width, -1).astype(np.int32)
    enc = storage_mod.encode_neighbors(
        nbrs, n, StorageConfig(neighbor_dtype=codec))
    packed = storage_mod.pack_edges(enc)
    R = -(-layers * m // 128)
    assert packed.rows.shape == (n * R, 128)
    assert packed.rows.dtype == np.int32
    assert storage_mod.table_n(packed) == n
    tail = packed.rows.reshape(n, R * 128)[:, layers * m:]
    assert (tail == -1).all()
    np.testing.assert_array_equal(
        storage_mod.decode_neighbors(packed), nbrs)
    dev = storage_mod.as_device(packed)
    out = jax.jit(storage_mod.decode_neighbors)(dev)
    np.testing.assert_array_equal(np.asarray(out), nbrs)


def test_neighbor_dtype_auto_boundary():
    """int16 holds ids up to 32767, so n=32768 fits and n=32769 does not."""
    assert storage_mod.resolve_neighbor_dtype(32768, "auto") == np.int16
    assert storage_mod.resolve_neighbor_dtype(32769, "auto") == np.int32
    assert storage_mod.resolve_neighbor_dtype(32769, "int32") == np.int32
    with pytest.raises(ValueError, match="cannot hold ids"):
        storage_mod.resolve_neighbor_dtype(32769, "int16")


def test_encode_neighbors_rejects_out_of_range_ids():
    nbrs = np.array([[0, 5]], np.int32)
    with pytest.raises(ValueError, match="out of range"):
        storage_mod.encode_neighbors(nbrs, 5, StorageConfig.compact())


def test_decode_neighbors_jnp_in_trace():
    import jax

    nbrs = jnp.asarray(np.array([[-1, 3, 7]], np.int16))
    out = jax.jit(storage_mod.decode_neighbors)(nbrs)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), [[-1, 3, 7]])


def test_vector_codec_dtypes_and_nbytes():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((32, 16)).astype(np.float32)
    for name in ("bfloat16", "float16"):
        enc = storage_mod.encode_vectors(x, StorageConfig.compact(name))
        assert str(enc.dtype) == name
        assert enc.nbytes == x.nbytes // 2
        dec = storage_mod.decode_vectors(enc)
        assert dec.dtype == np.float32
        # bf16/f16 round once; decode is exact on the rounded values
        np.testing.assert_array_equal(dec, np.asarray(enc, np.float32))


def test_storage_config_validation(monkeypatch):
    with pytest.raises(ValueError, match="vector_dtype"):
        StorageConfig(vector_dtype="float64")
    with pytest.raises(ValueError, match="neighbor_dtype"):
        StorageConfig(neighbor_dtype="int8")
    monkeypatch.setenv("REPRO_STORAGE", "bogus")
    with pytest.raises(ValueError, match="REPRO_STORAGE"):
        storage_mod.default_config()


def test_default_config_env(monkeypatch):
    monkeypatch.setenv("REPRO_STORAGE", "compact")
    assert storage_mod.default_config() == StorageConfig.compact()
    monkeypatch.setenv("REPRO_STORAGE", "f16")
    assert storage_mod.default_config().vector_dtype == "float16"
    monkeypatch.setenv("REPRO_STORAGE", "f32")
    assert storage_mod.default_config() == StorageConfig()


# ---------------------------------------------------------------------------
# index threading
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def built_pair():
    rng = np.random.default_rng(5)
    n, d = 512, 16
    vectors = rng.standard_normal((n, d)).astype(np.float32)
    attrs = rng.uniform(0, 100, n)
    cfg = BuildConfig(m=8, ef_construction=32, brute_threshold=32)
    # pin the baseline explicitly: the CI compact leg sets
    # REPRO_STORAGE=compact, which would otherwise move the default
    idx32 = RangeGraphIndex.build(vectors, attrs, cfg,
                                  storage=StorageConfig())
    idxc = idx32.astype_storage(StorageConfig.compact())
    return idx32, idxc, rng


def test_compact_index_footprint_halves(built_pair):
    idx32, idxc, _ = built_pair
    assert idxc.vectors.dtype == np.dtype(jnp.bfloat16)
    assert idxc.neighbors.dtype == np.int16
    assert idxc.nbytes <= 0.55 * idx32.nbytes


def test_neighbor_codec_search_ids_bit_identical(built_pair):
    """int16 vs int32 neighbor storage, identical vectors: identical ids."""
    idx32, _, rng = built_pair
    idx16 = idx32.astype_storage(StorageConfig(neighbor_dtype="int16"))
    q = rng.standard_normal((8, idx32.dim)).astype(np.float32)
    L = np.arange(8, dtype=np.int32) * 16
    R = L + 300
    a = idx32.search_ranks(q, L, R, k=5, ef=32)
    b = idx16.search_ranks(q, L, R, k=5, ef=32)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))


def test_compact_index_recall_close_to_f32(built_pair):
    idx32, idxc, rng = built_pair
    q = rng.standard_normal((32, idx32.dim)).astype(np.float32)
    L = np.zeros(32, np.int32)
    R = np.full(32, idx32.n - 1, np.int32)
    # one f32 ground truth for both: the delta must count quantization loss
    gt, _ = idx32.brute_force(q, L, R, k=10)
    r32 = recall(np.asarray(idx32.search_ranks(q, L, R, k=10, ef=64).ids),
                 gt)
    rc = recall(np.asarray(idxc.search_ranks(q, L, R, k=10, ef=64).ids), gt)
    assert abs(rc - r32) <= 0.05


def test_compact_results_in_range(built_pair):
    _, idxc, rng = built_pair
    q = rng.standard_normal((16, idxc.dim)).astype(np.float32)
    L = np.full(16, 100, np.int32)
    R = np.full(16, 300, np.int32)
    ids = np.asarray(idxc.search_ranks(q, L, R, k=10, ef=32).ids)
    got = ids[ids >= 0]
    assert ((got >= 100) & (got <= 300)).all()


def test_build_with_compact_storage_emits_compact_tables():
    rng = np.random.default_rng(9)
    vectors = rng.standard_normal((256, 8)).astype(np.float32)
    attrs = rng.uniform(0, 1, 256)
    idx = RangeGraphIndex.build(
        vectors, attrs, BuildConfig(m=4, ef_construction=16),
        storage=StorageConfig.compact(),
    )
    assert idx.neighbors.dtype == np.int16
    assert idx.vectors.dtype == np.dtype(jnp.bfloat16)
    # same build under f32 storage yields the same graph (construction math
    # is storage-independent)
    idx32 = RangeGraphIndex.build(
        vectors, attrs, BuildConfig(m=4, ef_construction=16),
        storage=StorageConfig(),
    )
    np.testing.assert_array_equal(
        storage_mod.decode_neighbors(idx.neighbors), idx32.neighbors
    )


def test_save_load_roundtrip_compact(tmp_path, built_pair):
    """Loaded index == built one: values, dtypes, writeability."""
    _, idxc, rng = built_pair
    p = str(tmp_path / "compact.rg")
    idxc.save(p)
    got = RangeGraphIndex.load(p)
    for name in ("vectors", "attrs", "perm", "neighbors"):
        a, b = getattr(idxc, name), getattr(got, name)
        assert b.dtype == a.dtype, name
        np.testing.assert_array_equal(np.asarray(b, np.float64),
                                      np.asarray(a, np.float64))
        assert b.flags.writeable, f"{name} must be writeable after load"
    assert got.storage == idxc.storage
    # a loaded index must behave like the built one, including for in-place
    # consumers (the read-only frombuffer regression)
    got.neighbors[0, 0, 0] = got.neighbors[0, 0, 0]
    q = rng.standard_normal((4, idxc.dim)).astype(np.float32)
    L = np.array([0, 8, 16, 24], np.int32)
    R = L + 200
    np.testing.assert_array_equal(
        np.asarray(idxc.search_ranks(q, L, R, k=5, ef=32).ids),
        np.asarray(got.search_ranks(q, L, R, k=5, ef=32).ids),
    )


def test_save_load_roundtrip_f32_writeable(tmp_path, built_pair):
    idx32, _, _ = built_pair
    p = str(tmp_path / "f32.rg")
    idx32.save(p)
    got = RangeGraphIndex.load(p)
    assert got.vectors.flags.writeable and got.neighbors.flags.writeable
    got.vectors[0, 0] = got.vectors[0, 0]  # must not raise
    np.testing.assert_array_equal(got.neighbors, idx32.neighbors)


# ---------------------------------------------------------------------------
# kernel-level parity: bf16 storage in, f32 math out
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_gather_dist_bf16_matches_f32_oracle(impl):
    """bf16-in/f32-math parity: both backends on a bf16 table vs the f32
    oracle evaluated on the (exactly) upcast table. The jnp path is the same
    f32 expansion, so it is bit-identical; the kernel reassociates the dot,
    so it is pinned to f32 tolerance."""
    rng = np.random.default_rng(3)
    B, n, d, M = 4, 64, 24, 9
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    xc = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
    ids = rng.integers(-1, n, (B, M)).astype(np.int32)
    ids = jnp.asarray(ids)
    want = np.asarray(ref.gather_dist(q, xc.astype(jnp.float32), ids))
    if impl == "xla":
        got = np.asarray(ref.gather_dist(q, xc, ids))
        np.testing.assert_array_equal(got, want)
    else:
        got = np.asarray(gather_distance_kernel_call(q, xc, ids,
                                                     interpret=True))
        assert (np.isinf(got) == np.isinf(want)).all()
        fin = np.isfinite(want)
        np.testing.assert_allclose(got[fin], want[fin],
                                   rtol=1e-4, atol=1e-4)


def test_prune_bf16_table_backend_parity():
    """Construction prune on a bf16 table: every backend upcasts in-register
    and must keep the same ids as the f32 table holding the same values."""
    rng = np.random.default_rng(7)
    B, C, d, n, m = 4, 12, 8, 32, 4
    table = rng.standard_normal((n, d)).astype(np.float32)
    table_bf = table.astype(jnp.bfloat16)
    table_up = np.asarray(table_bf, np.float32)  # the values all paths see
    ids = rng.integers(0, n, (B, C)).astype(np.int32)
    ids[rng.random((B, C)) < 0.2] = -1
    u = rng.standard_normal((B, d)).astype(np.float32)
    du = ((table_up[np.maximum(ids, 0)] - u[:, None, :]) ** 2).sum(-1)
    du = np.where(ids < 0, np.inf, du).astype(np.float32)
    want = np.asarray(ops.prune(
        jnp.asarray(ids), jnp.asarray(du), jnp.asarray(table_up),
        m=m, impl="xla",
    ))
    for impl in ("xla", "pallas", "legacy"):
        got = np.asarray(ops.prune(
            jnp.asarray(ids), jnp.asarray(du), jnp.asarray(table_bf),
            m=m, impl=impl,
        ))
        np.testing.assert_array_equal(got, want, err_msg=impl)


def test_select_edges_int16_table_all_backends():
    """Compact neighbor tables through every edge-selection backend."""
    rng = np.random.default_rng(4)
    n, logn, m = 64, 6, 4
    layers = logn + 1
    nbrs = rng.integers(-1, n, (n, layers, m)).astype(np.int32)
    us = jnp.asarray(rng.integers(0, n, 8).astype(np.int32))
    L = jnp.zeros(8, jnp.int32)
    R = jnp.full(8, n - 1, jnp.int32)
    want = np.asarray(ops.select_edges(
        jnp.asarray(nbrs), us, L, R, logn=logn, m_out=m, impl="xla"
    ))
    nbrs16 = jnp.asarray(nbrs.astype(np.int16))
    for impl in ("xla", "pallas", "argsort"):
        got = ops.select_edges(
            nbrs16, us, L, R, logn=logn, m_out=m, impl=impl
        )
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def test_serving_engine_compact_index(built_pair):
    from repro.serve.engine import Request, ServingEngine

    idx32, idxc, rng = built_pair
    eng = ServingEngine(idxc, ef=32, max_batch=4)
    assert eng.stats["index_bytes"] == idxc.nbytes
    assert eng.stats["index_bytes"] <= 0.55 * idx32.nbytes
    attrs_orig = np.empty(idxc.n)
    attrs_orig[idxc.perm] = idxc.attrs
    reqs = []
    for _ in range(6):
        lo, hi = sorted(rng.uniform(0, 100, 2))
        reqs.append(Request(
            vector=rng.standard_normal(idxc.dim).astype(np.float32),
            lo=lo, hi=hi, k=5,
        ))
        eng.submit(reqs[-1])
    for req, res in zip(reqs, eng.flush()):
        got = res.ids[res.ids >= 0]
        assert ((attrs_orig[got] >= req.lo)
                & (attrs_orig[got] <= req.hi)).all()
