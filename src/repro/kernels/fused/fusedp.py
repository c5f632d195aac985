"""Fused verify+decrypt: one tiled pass over each ciphertext tile.

The two-pass decode (``sha256_many_pallas`` then the bitsliced
keystream) streams every ciphertext byte through the device twice —
once as SHA schedule words, once as AES state planes — and pays two
host round-trips. This module runs BOTH in one pass over one layout:

* the tile arrives exactly like the SHA kernel's input — padded
  schedule words, word-major (16, maxb, lanes) int32, one chunk per
  lane — and the lockstep compression (``sha256p.sha_fold``) folds it
  to per-lane digests;
* the grid is (lane tiles, block steps), like the SHA kernel's: a step
  is ``STEP`` = 8 message blocks = 32 AES blocks, so a 512 KiB chunk is
  1032 steps of a (16, 8, lanes) tile, and the digest state stays
  resident in the digest output block across steps;
* each step's AES-CTR keystream comes from the bitsliced circuit
  (``bitslice.encrypt_planes_body``, the AES kernel's body) over (16,
  lanes) planes with lane = chunk: the 32 bits of a plane word are the
  step's 32 counter blocks of that ONE chunk. So the per-chunk round
  keys are plain 0/-1 words, the counter planes are (16, 1) columns of
  constants and of the step's first block ``m0``, and bit ``k`` of a
  plane word — AES block ``m0 + k`` = word ``4*(k % 4) + w4`` of the
  step's message block ``k // 4`` — unpacks with static shifts: no lane
  shuffle anywhere;
* the keystream words XOR into the ciphertext words in-register:
  plaintext comes back in the same layout the digests were computed
  from. One device visit per ciphertext byte.

A Pallas kernel (the TPU route) and a pure-jnp jit (the XLA route, all
steps at once along an extra group axis) share every traced helper, so
kernel == jit == two-pass oracles by construction. Tamper detection
stays per-chunk: the host adapter (``ops``) compares digests before
releasing any plaintext.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.aes.bitslice import encrypt_planes_body
from repro.kernels.sha256.sha256p import (
    LANE_BLOCK,
    sha_fold,
    sha_init,
    tile_shape,
)

STEP = 8                   # message blocks per step: 32 AES blocks

_SHL = jax.lax.shift_left
_SRL = jax.lax.shift_right_logical
# bit e < 5 of counter m0 + k over the 32 lanes k of a word (m0 % 32 == 0)
_LOW_PATTERNS = [sum(((k >> e) & 1) << k for k in range(32)) - (1 << 32)
                 for e in range(5)]


def _counter_planes(m0, shape: tuple) -> list:
    """8 counter bit planes shaped `shape` (positions on axis 0): plane
    ``i`` row ``p`` is bit ``8*(15-p) + i`` of the zero-IV CTR counter
    ``m0 + k`` in bit ``k`` of the word. `m0` (a multiple of 32, below
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


def keystream_words(m0, shape: tuple, rk_at, rounds: int) -> list:
    """AES-CTR keystream of 32 consecutive counter blocks from `m0` for
    every lane, as 4 arrays ``words[w4]`` of shape (32, 1) + shape[1:]:
    ``words[w4][k]`` is big-endian schedule word ``w4`` of AES block
    ``m0 + k``. ``rk_at(r)`` gives round ``r``'s (8, 16, ...) 0/-1 key
    planes."""
    planes = encrypt_planes_body(_counter_planes(m0, shape), rk_at, rounds)
    kk = jax.lax.broadcasted_iota(jnp.int32, (32,) + (1,) * len(shape), 0)
    byt = None
    for i, pl_i in enumerate(planes):
        bit = _SHL(_SRL(pl_i[None], kk) & 1, i)
        byt = bit if byt is None else byt | bit       # (32, 16, ...)
    words = []
    for w4 in range(4):
        word = None
        for j in range(4):
            p = 4 * w4 + j
            b = _SHL(byt[:, p:p + 1], 24 - 8 * j)
            word = b if word is None else word | b
        words.append(word)
    return words


def _fused_kernel(words_ref, nb_ref, rk_ref, dig_ref, out_ref, *,
                  rounds: int):
    k = pl.program_id(1)
    nb = nb_ref[...]

    @pl.when(k == 0)
    def _():
        for i, h in enumerate(sha_init(nb)):
            dig_ref[i:i + 1, :] = h

    state = tuple(dig_ref[i:i + 1, :] for i in range(8))
    state = sha_fold(
        lambda j: [words_ref[t, pl.ds(j, 1), :] for t in range(16)],
        nb, state, k * STEP, STEP)
    for i in range(8):
        dig_ref[i:i + 1, :] = state[i]
    ks = keystream_words(k * (4 * STEP), (16, 1), lambda r: rk_ref[r],
                         rounds)
    for b in range(STEP):
        for t in range(16):
            out_ref[t, b:b + 1, :] = (words_ref[t, b:b + 1, :]
                                      ^ ks[t % 4][4 * b + t // 4])


@functools.partial(jax.jit, static_argnames=("rounds", "interpret", "block"))
def fused_lanes_pallas(words, nblocks, rk_planes, *, rounds: int,
                       interpret: bool = False, block: int = LANE_BLOCK):
    """Pallas launch: words (16, maxb, N) int32, nblocks (1, N) int32,
    rk_planes (rounds+1, 8, 16, N) int32 per-chunk 0/-1 key planes ->
    (digests (8, N) int32, plaintext words (16, maxb, N) int32). ``maxb``
    is a multiple of ``STEP``; lanes tile as ``sha256p.tile_shape``."""
    _, maxb, n = words.shape
    _, blk = tile_shape(maxb, n, block)
    return pl.pallas_call(
        functools.partial(_fused_kernel, rounds=rounds),
        grid=(n // blk, maxb // STEP),
        in_specs=[
            pl.BlockSpec((16, STEP, blk), lambda i, k: (0, k, i)),
            pl.BlockSpec((1, blk), lambda i, k: (0, i)),
            pl.BlockSpec((rounds + 1, 8, 16, blk), lambda i, k: (0, 0, 0, i)),
        ],
        out_specs=(
            pl.BlockSpec((8, blk), lambda i, k: (0, i)),
            pl.BlockSpec((16, STEP, blk), lambda i, k: (0, k, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((8, n), jnp.int32),
            jax.ShapeDtypeStruct((16, maxb, n), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="fused_verify_decrypt",
    )(words, nblocks, rk_planes)


@functools.partial(jax.jit, static_argnames=("rounds",))
def fused_lanes_jit(words, nblocks, rk_planes, *, rounds: int):
    """The same fused pass as ONE XLA program over the whole tile: the
    SHA fold walks all blocks, and the keystream of every step is one
    plane pipeline along an extra group axis. The off-TPU route."""
    _, maxb, n = words.shape
    groups = maxb // STEP
    state = sha_fold(
        lambda j: list(jax.lax.dynamic_slice_in_dim(words, j, 1, 1)),
        nblocks, sha_init(nblocks), 0, maxb)
    m0 = 4 * STEP * jax.lax.broadcasted_iota(jnp.int32, (1, groups, 1), 1)
    ks = keystream_words(m0, (16, groups, 1),
                         lambda r: rk_planes[r][:, :, None, :], rounds)
    plain = []
    for t in range(16):
        q, w4 = t // 4, t % 4
        kt = ks[w4][q::4, 0]                  # (STEP, groups, n): block b
        plain.append(words[t] ^ kt.transpose(1, 0, 2).reshape(maxb, n))
    return jnp.concatenate(state, axis=0), jnp.stack(plain)
