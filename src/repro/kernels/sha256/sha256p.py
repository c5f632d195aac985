"""Pallas TPU kernel: lockstep SHA-256 over N message lanes.

The decode stage verifies every fetched ciphertext's SHA-256 before any
keystream is generated (paper §3.1). ``crypto.sha256v.sha256_many_np``
is the vectorized lockstep reference: all N messages' compression
functions advance together as (N,)-shaped uint32 lanes. This kernel is
that exact round structure — pure 32-bit rotate/xor/add, the shape the
VPU natively executes — with the lanes on the TPU vector axis:

* input is the padded message schedule, word-major: (16, maxb, N) int32
  — word ``t`` of message block ``b`` of lane ``c`` at ``[t, b, c]`` —
  so one block's 16 words are 16 single-row loads of (1, lanes);
* the grid is (lane tiles, block steps): the second axis walks the
  message blocks ``STEP_BLOCKS`` at a time, sequentially, and the digest
  state lives in the output block, which stays resident in VMEM across
  that axis. A 512 KiB chunk is 129 steps of a (16, 64, lanes) tile, not
  one 8256-block tile;
* inside a step a ``fori_loop`` walks the blocks, reading each block's
  words from the ref (Mosaic lowers a dynamic row load; it does not
  lower a dynamic slice of a loaded value); each block's 64 rounds run
  as 16-round groups (``sha_compress``);
* per-lane message lengths are handled exactly like the reference:
  lanes whose final padded block has been absorbed FREEZE via a masked
  state update (``nblocks > b``), so one launch hashes mixed-length
  batches;
* all arithmetic is int32 (TPU-native; uint32 adds wrap identically in
  two's complement) — adapters ``.view()`` at the boundary.

Off-TPU the adapters run the same kernel under the Pallas interpreter.
Oracle-tested against hashlib across padding boundaries in
``tests/test_bitslice_kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.crypto.sha256v import _H0, _K

_K32 = [int(k) for k in _K.view(np.int32)]
_H032 = [int(h) for h in _H0.view(np.int32)]

LANE_BLOCK = 128           # message lanes per grid step
STEP_BLOCKS = 64           # message blocks per sequential grid step


def _rotr(x, n: int):
    return jax.lax.shift_right_logical(x, n) | jax.lax.shift_left(x, 32 - n)


def _shr(x, n: int):
    return jax.lax.shift_right_logical(x, n)


def sha_init(like) -> tuple:
    """The 8 initial digest lanes, each shaped like `like`."""
    return tuple(jnp.full(like.shape, h, jnp.int32) for h in _H032)


def _rounds(w: list, state: tuple, k_at) -> tuple:
    """16 compression rounds over schedule words ``w``; ``k_at(i)`` is
    the round constant of the i-th of them."""
    a, bb, c, d, e, f, g, h = state
    for i in range(16):
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k_at(i) + w[i]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & bb) ^ (a & c) ^ (bb & c)
        a, bb, c, d, e, f, g, h = t1 + s0 + maj, a, bb, c, d + t1, e, f, g
    return a, bb, c, d, e, f, g, h


def _next_schedule(w: list) -> list:
    """The next 16 message-schedule words from the previous 16."""
    w = list(w)
    for t in range(16, 32):
        s0 = _rotr(w[t - 15], 7) ^ _rotr(w[t - 15], 18) ^ _shr(w[t - 15], 3)
        s1 = _rotr(w[t - 2], 17) ^ _rotr(w[t - 2], 19) ^ _shr(w[t - 2], 10)
        w.append(w[t - 16] + s0 + w[t - 7] + s1)
    return w[16:]


def sha_compress(w: list, state: tuple) -> tuple:
    """One compression: 16 schedule words (lane arrays) folded into the
    8 state lanes; returns the updated (added) state.

    The first 48 rounds run as a 3-trip loop of 16 rounds plus the next
    16 schedule words, the last 16 unrolled after it. Unrolled in one
    piece, XLA:CPU copies the whole 64-round graph into one fusion per
    state word and compiles each for minutes — the route every CPU test
    takes; the loop bounds each fusion to one 16-round group."""

    def group(g, carry):
        w, st = carry

        def k_at(i):
            k = jnp.int32(_K32[32 + i])
            for gg in (1, 0):
                k = jnp.where(g == gg, jnp.int32(_K32[16 * gg + i]), k)
            return k

        return tuple(_next_schedule(w)), _rounds(w, st, k_at)

    w, st = jax.lax.fori_loop(0, 3, group, (tuple(w), state))
    st = _rounds(w, st, lambda i: jnp.int32(_K32[48 + i]))
    return tuple(s + n for s, n in zip(state, st))


def sha_fold(load, nb, state: tuple, b0, nblocks: int) -> tuple:
    """Fold `nblocks` message blocks into `state`: ``load(j)`` returns
    the 16 schedule words of local block ``j`` (global block ``b0 + j``)
    as lane arrays; ``nb`` holds each lane's block count, and lanes
    past their final block keep their state. Plain traceable function:
    the SHA kernel, the fused kernel (``kernels.fused``) and its XLA
    route share these exact rounds through their own ``load``."""

    def body(j, st):
        new = sha_compress(load(j), st)
        active = nb > b0 + j                  # frozen lanes keep state
        return tuple(jnp.where(active, n, s) for s, n in zip(st, new))

    return jax.lax.fori_loop(0, nblocks, body, state)


def tile_shape(maxb: int, n: int, block: int = LANE_BLOCK) -> tuple:
    """(block steps, lanes per tile) for a (16, maxb, n) word tensor:
    a step is ``STEP_BLOCKS`` blocks (or all of a shorter message) and a
    lane tile is ``block`` lanes (or all of a narrower batch)."""
    bt = min(STEP_BLOCKS, maxb)
    assert maxb % bt == 0 and bt % 8 == 0, maxb
    blk = min(block, n)
    while n % blk:
        blk //= 2
    return bt, blk


def _sha_kernel(words_ref, nb_ref, out_ref, *, bt: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        for i, h in enumerate(sha_init(nb_ref[...])):
            out_ref[i:i + 1, :] = h

    state = tuple(out_ref[i:i + 1, :] for i in range(8))
    state = sha_fold(
        lambda j: [words_ref[t, pl.ds(j, 1), :] for t in range(16)],
        nb_ref[...], state, k * bt, bt)
    for i in range(8):
        out_ref[i:i + 1, :] = state[i]


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def sha256_lanes_pallas(words: jax.Array, nblocks: jax.Array, *,
                        interpret: bool = False,
                        block: int = LANE_BLOCK) -> jax.Array:
    """words: (16, maxb, N) int32 big-endian schedule words (zero past
    each lane's final block); nblocks: (1, N) int32 blocks per lane.
    Returns (8, N) int32 digest words. ``maxb`` must be a multiple of 8
    and, above ``STEP_BLOCKS``, of ``STEP_BLOCKS``; N must split into
    power-of-two lane tiles (callers bucket; see ``ops``)."""
    _, maxb, n = words.shape
    bt, blk = tile_shape(maxb, n, block)
    return pl.pallas_call(
        functools.partial(_sha_kernel, bt=bt),
        grid=(n // blk, maxb // bt),
        in_specs=[
            pl.BlockSpec((16, bt, blk), lambda i, k: (0, k, i)),
            pl.BlockSpec((1, blk), lambda i, k: (0, i)),
        ],
        out_specs=pl.BlockSpec((8, blk), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sha256_lanes",
    )(words, nblocks)
