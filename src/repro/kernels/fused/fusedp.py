"""Fused verify+decrypt: a lane-dense keystream pass, then one SHA walk.

A decode tile is N chunks, one per lane of a zeroed, SHA-padded byte
buffer ``buf`` (lanes, maxb*16) of native int32 words (``sha256.ops.
pack_messages``). The device program has two phases, both in one jit:

1. ``aes_bitsliced`` (``keystream_pallas``): the zero-IV AES-CTR
   keystream of every lane, computed apart from the SHA chain. The
   bitsliced circuit (``bitslice.encrypt_planes_body``, the AES
   kernel's body) runs over (16, tile) planes whose lane WORDS are
   counter groups of one chunk: bit ``k`` of lane word ``g`` is AES
   block ``32*g + k``, so the planes are full whatever the tile's chunk
   count. The grid is (chunks, word tiles); a step reads its chunk's
   (rounds+1, 16, 8) key planes through the index map, makes its counter
   planes from iota (no counter bytes cross to the device), and skips
   itself when its blocks lie past the chunk's end. Each step unpacks
   its planes into rows ``4*k + w4`` (native word ``w4`` of block
   ``32*g + k``), so one XLA transpose of a (128, words) slab per chunk
   gives the keystream in ``buf``'s own layout (``native_keystream``).
2. ``fused_verify_decrypt`` (``walk_pallas``): the serial SHA-256 walk.
   The grid is (lane tiles, block steps) of ``sha256p.STEP_BLOCKS``
   blocks as in ``sha256_lanes``, over the word-major (16, maxb, lanes)
   schedule words, the digest state resident in the digest block across
   steps; each step folds its blocks through ``sha256p.sha_fold`` and
   XORs the step's keystream into the ciphertext words of ``buf``.
   Plaintext leaves in ``buf``'s layout; past each chunk's length it is
   garbage that the host never reads.

A Pallas route (the TPU's) and a pure-jnp jit (the XLA route: the
keystream of every word at once, then the fold and the XOR) share
every traced helper, so kernel == jit == two-pass oracles by
construction. Tamper detection stays per chunk: the host adapter
(``ops``) compares digests before releasing any plaintext.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.aes.bitslice import _permute_rows, encrypt_planes_body
from repro.kernels.sha256.ops import words_from_bytes
from repro.kernels.sha256.sha256p import (
    LANE_BLOCK,
    sha_fold,
    sha_init,
    tile_shape,
)

KS_TILE = 128              # keystream lane words per step: 4096 AES blocks

_SHL = jax.lax.shift_left
_SRL = jax.lax.shift_right_logical
# bit e < 5 of counter m0 + k over the 32 lanes k of a word (m0 % 32 == 0)
_LOW_PATTERNS = [sum(((k >> e) & 1) << k for k in range(32)) - (1 << 32)
                 for e in range(5)]
# plane rows p = 4*w4 + q reordered to 4*q + w4: byte q of every native
# word w4 of a block in one 4-row slab
_QW_ROWS = [4 * (r % 4) + r // 4 for r in range(16)]


def _counter_planes(m0, shape: tuple) -> list:
    """8 counter bit planes shaped `shape` (positions on axis 0): plane
    ``i`` row ``p`` is bit ``8*(15-p) + i`` of the zero-IV CTR counter
    ``m0 + k`` in bit ``k`` of the word. `m0` (multiples of 32, below
    2**31) broadcasts against `shape`."""
    e0 = 8 * (15 - jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    planes = []
    for i in range(8):
        e = e0 + i
        word = jnp.where(e < 31, -(_SRL(m0, jnp.minimum(e, 31)) & 1), 0)
        for b, pat in enumerate(_LOW_PATTERNS):
            word = jnp.where(e == b, pat, word)
        planes.append(word)
    return planes


def keystream_rows(g, rk_at, rounds: int):
    """AES-CTR keystream of the counter groups `g` (int32 lane words,
    any shape ``lanes``): (128,) + lanes int32, row ``4*k + w4`` the
    native word ``w4`` (stream bytes ``4*w4 .. 4*w4+3``, first in the
    low byte) of AES block ``32*g + k``. ``rk_at(r)`` gives round
    ``r``'s key planes as 8 arrays (plane ``i``) that broadcast against
    (16,) + lanes."""
    shape = (16,) + g.shape
    planes = encrypt_planes_body(_counter_planes(32 * g[None], shape),
                                 rk_at, rounds)
    planes = [_permute_rows(x, _QW_ROWS, jnp) for x in planes]
    q8 = 8 * (jax.lax.broadcasted_iota(
        jnp.int32, (16,) + (1,) * g.ndim, 0) // 4)
    rows = []
    for k in range(32):
        v = None
        for i, x in enumerate(planes):
            b = _SHL(_SRL(x, k) & 1, q8 + i)
            v = b if v is None else v | b
        rows.append(v[0:4] | v[4:8] | v[8:12] | v[12:16])
    return jnp.concatenate(rows, axis=0)


def native_keystream(ks):
    """(N, 128, words) keystream rows -> (N, words*128) in the layout of
    the byte buffer: native word ``128*g + r`` is row ``r`` of word
    ``g``."""
    n, _, words = ks.shape
    return ks.transpose(0, 2, 1).reshape(n, words * 128)


def _ks_tile(maxb: int, tile: int) -> tuple:
    """(keystream lane words per lane, words per step) for a buffer of
    ``maxb`` message blocks: ``maxb/8`` words (32 AES blocks each) in
    one step, or rounded up to whole steps of `tile` words."""
    words = maxb // 8
    if words <= tile:
        return words, words
    return -(-words // tile) * tile, tile


def _keystream_kernel(nb_ref, rk_ref, out_ref, *, rounds: int, tile: int):
    c, j = pl.program_id(0), pl.program_id(1)

    @pl.when(8 * tile * j < nb_ref[c])        # steps past the chunk skip
    def _():
        def rk_at(r):
            k = rk_ref[r]
            return [k[:, i:i + 1] for i in range(8)]

        g = tile * j + jax.lax.broadcasted_iota(jnp.int32, (tile,), 0)
        out_ref[...] = keystream_rows(g, rk_at, rounds)


@functools.partial(jax.jit, static_argnames=("maxb", "rounds", "interpret",
                                             "tile"))
def keystream_pallas(nblocks, rk, *, maxb: int, rounds: int,
                     interpret: bool = False, tile: int = KS_TILE):
    """Pallas launch of the keystream pass: nblocks (1, N) int32 message
    blocks per lane (0 for an empty lane, whose steps all skip), rk (N,
    rounds+1, 16, 8) int32 per-chunk 0/-1 key planes -> (N, 128, words)
    int32 keystream rows (``keystream_rows``), words covering ``maxb``
    message blocks; rows of skipped steps are left unwritten."""
    n = nblocks.shape[1]
    words, tile = _ks_tile(maxb, tile)
    return pl.pallas_call(
        functools.partial(_keystream_kernel, rounds=rounds, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, words // tile),
            in_specs=[pl.BlockSpec((None, rounds + 1, 16, 8),
                                   lambda c, j, nb: (c, 0, 0, 0))],
            out_specs=pl.BlockSpec((None, 128, tile),
                                   lambda c, j, nb: (c, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, 128, words), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="aes_bitsliced",
    )(nblocks.reshape(n), rk)


def _walk_kernel(words_ref, nb_ref, ct_ref, ks_ref, dig_ref, out_ref, *,
                 bt: int):
    k = pl.program_id(1)
    nb = nb_ref[...]

    @pl.when(k == 0)
    def _():
        for i, h in enumerate(sha_init(nb)):
            dig_ref[i:i + 1, :] = h

    state = tuple(dig_ref[i:i + 1, :] for i in range(8))
    state = sha_fold(
        lambda j: [words_ref[t, pl.ds(j, 1), :] for t in range(16)],
        nb, state, k * bt, bt)
    for i in range(8):
        dig_ref[i:i + 1, :] = state[i]
    out_ref[...] = ct_ref[...] ^ ks_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def walk_pallas(words, nblocks, ct, ks, *, interpret: bool = False,
                block: int = LANE_BLOCK):
    """Pallas launch of the SHA walk + XOR: words (16, maxb, N) int32
    schedule words, nblocks (1, N), ct (N, maxb*16) the same bytes as
    native int32 words, ks (N, >= maxb*16) keystream in ct's layout ->
    (digests (8, N) int32, plaintext (N, maxb*16) int32 in ct's layout).
    Steps and lane tiles as ``sha256p.tile_shape``."""
    _, maxb, n = words.shape
    bt, blk = tile_shape(maxb, n, block)
    row = pl.BlockSpec((blk, 16 * bt), lambda i, k: (i, k))
    return pl.pallas_call(
        functools.partial(_walk_kernel, bt=bt),
        grid=(n // blk, maxb // bt),
        in_specs=[
            pl.BlockSpec((16, bt, blk), lambda i, k: (0, k, i)),
            pl.BlockSpec((1, blk), lambda i, k: (0, i)),
            row,
            row,
        ],
        out_specs=(pl.BlockSpec((8, blk), lambda i, k: (0, i)), row),
        out_shape=(
            jax.ShapeDtypeStruct((8, n), jnp.int32),
            jax.ShapeDtypeStruct(ct.shape, jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="fused_verify_decrypt",
    )(words, nblocks, ct, ks)


@functools.partial(jax.jit, static_argnames=("rounds", "interpret", "block"))
def fused_lanes_pallas(buf, nblocks, rk, *, rounds: int,
                       interpret: bool = False, block: int = LANE_BLOCK):
    """The Pallas route: buf (N, maxb*16) int32 native words of the
    SHA-padded chunks, nblocks (1, N) int32, rk (N, rounds+1, 16, 8)
    int32 -> (digests (8, N) int32, plaintext (N, maxb*16) int32 in
    buf's layout): the keystream pass, its transpose to buf's layout,
    then the walk."""
    words = words_from_bytes(buf)
    ks = keystream_pallas(nblocks, rk, maxb=words.shape[1], rounds=rounds,
                          interpret=interpret)
    return walk_pallas(words, nblocks, buf, native_keystream(ks),
                       interpret=interpret, block=block)


@functools.partial(jax.jit, static_argnames=("rounds",))
def fused_lanes_jit(buf, nblocks, rk, *, rounds: int):
    """The same two phases as ONE XLA program over the whole tile (the
    off-TPU route): the keystream of every lane word of every lane at
    once, the SHA fold over all blocks, then the XOR."""
    n, width = buf.shape
    words = words_from_bytes(buf)

    def rk_at(r):
        k = jax.lax.dynamic_index_in_dim(rk, r, 1, keepdims=False)
        return [k[:, :, i].T[:, :, None] for i in range(8)]

    g = jax.lax.broadcasted_iota(jnp.int32, (n, width // 128), 1)
    ks = keystream_rows(g, rk_at, rounds).transpose(1, 0, 2)
    state = sha_fold(
        lambda j: list(jax.lax.dynamic_slice_in_dim(words, j, 1, 1)),
        nblocks, sha_init(nblocks), 0, words.shape[1])
    return jnp.concatenate(state, axis=0), buf ^ native_keystream(ks)
