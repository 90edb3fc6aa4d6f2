"""The main-path Pallas kernels compile for a TPU v5e at deployment size.

Every other kernel test runs the kernels in interpret mode, which cannot
see what the TPU compiler refuses: blocks off the (8, 128) tile, DMA
slices of the tiled HBM layout, vector shape casts Mosaic cannot lower,
VMEM overflow. These tests compile each kernel ahead of time for a v5e
chip that is described, not attached (nothing runs), at n = 2^20, d = 128,
B = 64, W = 4, m = 16 with the default tiles: the hop megakernel on the
f32, compact (bf16) and int8 vector tables, and the construction prune,
edge-selection and gather-distance kernels. The neighbor table goes in
as the ``storage.EdgeRows`` form the serving path uploads for every
neighbor codec.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import storage as storage_mod
from repro.kernels.edge_select import edge_select_kernel_call
from repro.kernels.gather_distance import gather_distance_kernel_call
from repro.kernels.hop import hop_kernel_call
from repro.kernels.prune import prune_kernel_call

N, D, B, W, M, LOGN = 1 << 20, 128, 64, 4, 16, 20
WORDS = N // 32
C = M + 64          # a search level's candidates: m + ef_construction


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e, with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # else libtpu logs under /tmp
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """AOT compile; the program must hold a Mosaic kernel."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _tables(s):
    return {
        "f32": _spec(s, (N, D), jnp.float32),
        "compact": _spec(s, (N, D), jnp.bfloat16),
        "int8": storage_mod.Int8Vectors(_spec(s, (N, D), jnp.int8),
                                        _spec(s, (N,), jnp.float32)),
    }


def _edges(s):
    """The neighbor table as the kernels read it: ``storage.EdgeRows``,
    which the serving path uploads once for every neighbor codec."""
    K = (LOGN + 1) * M
    return storage_mod.EdgeRows(
        _spec(s, (N * -(-K // 128), 128), jnp.int32), LOGN + 1, M)


@pytest.mark.parametrize("table", ["f32", "compact", "int8"])
def test_hop_compiles(one_chip, table):
    s = one_chip
    compiled = _compile(
        lambda q, t, e, u, L, R, v, x: hop_kernel_call(
            q, t, e, u, L, R, v, x, logn=LOGN, m_out=M),
        _spec(s, (B, D), jnp.float32), _tables(s)[table], _edges(s),
        _spec(s, (B, W), jnp.int32), _spec(s, (B * W,), jnp.int32),
        _spec(s, (B * W,), jnp.int32), _spec(s, (B, WORDS), jnp.uint32),
        _spec(s, (B, W), jnp.bool_),
    )
    # visited rows (128 KiB per query) stream through VMEM blocks; what
    # lands in HBM temp is only the small padded/bitcast operands
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_prune_compiles(one_chip):
    s = one_chip
    _compile(
        lambda i, d, t: prune_kernel_call(i, d, t, m=M),
        _spec(s, (2048, C), jnp.int32), _spec(s, (2048, C), jnp.float32),
        _tables(s)["f32"],
    )


def test_edge_select_compiles(one_chip):
    s = one_chip
    F = B * W
    _compile(
        lambda e, u, L, R: edge_select_kernel_call(
            e, u, L, R, logn=LOGN, m_out=M),
        _edges(s), _spec(s, (F,), jnp.int32),
        _spec(s, (F,), jnp.int32), _spec(s, (F,), jnp.int32),
    )


def test_gather_dist_compiles(one_chip):
    s = one_chip
    _compile(
        gather_distance_kernel_call,
        _spec(s, (B, D), jnp.float32), _tables(s)["f32"],
        _spec(s, (B, W * M), jnp.int32),
    )


@pytest.mark.parametrize("table", ["compact", "int8"])
def test_gather_dist_codec_compiles(one_chip, table):
    """16/8-bit rows DMA as 8-row blocks and are picked on arrival: the
    widest autotune tile fits VMEM, one f32 row per slot."""
    s = one_chip
    _compile(
        lambda q, t, i: gather_distance_kernel_call(
            q, t, i, block_b=16, block_m=256),
        _spec(s, (B, D), jnp.float32), _tables(s)[table],
        _spec(s, (B, 512), jnp.int32),
    )


@pytest.mark.parametrize("table", ["compact", "int8"])
def test_prune_codec_compiles(one_chip, table):
    s = one_chip
    _compile(
        lambda i, d, t: prune_kernel_call(i, d, t, m=M, block_b=16),
        _spec(s, (2048, C), jnp.int32), _spec(s, (2048, C), jnp.float32),
        _tables(s)[table],
    )
