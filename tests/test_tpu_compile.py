"""The decode kernels of the cold-start path compile for a TPU v5e.

Each test compiles one Pallas kernel, at the tile the main path really
launches, for a chip that is described and not attached (the TPU
compiler ships with jaxlib): what Mosaic refuses here — an op it cannot
lower, a block past the chip's fast memory, a tile it cannot align —
interpret mode on the CPU never sees. Nothing runs, so these say
nothing about results or speed; the CPU tests own correctness.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.layout import CHUNK_SIZE
from repro.kernels.aes.bitslice_pallas import encrypt_planes_pallas
from repro.kernels.fused.fusedp import (
    fused_lanes_pallas,
    keystream_pallas,
    walk_pallas,
)
from repro.kernels.sha256.ops import _bucket_blocks, _bucket_lanes
from repro.kernels.sha256.sha256p import LANE_BLOCK, sha256_lanes_pallas

ROUNDS = 14                                     # AES-256
MAXB = _bucket_blocks((CHUNK_SIZE + 9 + 63) // 64)   # one padded chunk
LANES = _bucket_lanes(1)                        # a one-chunk decode tile


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache off here
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# a 1 MiB tile of 4 KiB chunks: 256 lanes, two lane tiles of the walk
MULTI_MAXB = _bucket_blocks((4096 + 9 + 63) // 64)
MULTI_LANES = _bucket_lanes((1 << 20) // 4096)
TILES = {"512k_chunk": (MAXB, LANES), "multi_lane": (MULTI_MAXB, MULTI_LANES)}


def _fused_text(one_chip, maxb: int, lanes: int) -> str:
    return _compiled_text(
        lambda b, nb, rk: fused_lanes_pallas(b, nb, rk, rounds=ROUNDS),
        one_chip, (lanes, 16 * maxb), (1, lanes),
        (lanes, ROUNDS + 1, 16, 8))


def test_fused_kernel_compiles_at_512k_chunk_tile(one_chip):
    text = _fused_text(one_chip, MAXB, LANES)
    assert "tpu_custom_call" in text
    assert "aes_bitsliced" in text and "fused_verify_decrypt" in text


def test_fused_kernel_compiles_at_multi_lane_tile(one_chip):
    assert MULTI_LANES > LANE_BLOCK
    text = _fused_text(one_chip, MULTI_MAXB, MULTI_LANES)
    assert "tpu_custom_call" in text
    assert "aes_bitsliced" in text and "fused_verify_decrypt" in text


@pytest.mark.parametrize("tile", TILES)
def test_keystream_kernel_compiles(one_chip, tile):
    maxb, lanes = TILES[tile]
    text = _compiled_text(
        lambda nb, rk: keystream_pallas(nb, rk, maxb=maxb, rounds=ROUNDS),
        one_chip, (1, lanes), (lanes, ROUNDS + 1, 16, 8))
    assert "tpu_custom_call" in text and "aes_bitsliced" in text


@pytest.mark.parametrize("tile", TILES)
def test_walk_kernel_compiles(one_chip, tile):
    maxb, lanes = TILES[tile]
    text = _compiled_text(
        walk_pallas, one_chip, (16, maxb, lanes), (1, lanes),
        (lanes, 16 * maxb), (lanes, 16 * maxb))
    assert "tpu_custom_call" in text and "fused_verify_decrypt" in text


def test_sha256_kernel_compiles_at_512k_chunk_tile(one_chip):
    text = _compiled_text(sha256_lanes_pallas, one_chip,
                          (16, MAXB, LANES), (1, LANES))
    assert "tpu_custom_call" in text


def test_bitsliced_aes_kernel_compiles_at_publish_tile(one_chip):
    # one 512 KiB chunk's keystream: 32768 AES blocks = 1024 plane words
    words = CHUNK_SIZE // 16 // 32
    text = _compiled_text(
        lambda p, rk: encrypt_planes_pallas(p, rk, rounds=ROUNDS),
        one_chip, (8, 16, words), (ROUNDS + 1, 8, 16, words))
    assert "tpu_custom_call" in text
