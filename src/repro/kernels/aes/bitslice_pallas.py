"""Pallas TPU kernel: bitsliced AES over bit planes — no byte gathers.

The kernel body is exactly ``bitslice.aes_rounds`` (the Boyar–Peralta
S-box circuit + plane-shuffle ShiftRows/MixColumns + per-block round-key
XORs), tiled over the packed lane-word axis: each grid step pulls an
(8, 16, blk) plane tile plus its (R+1, 8, 16, blk) round-key tile into
VMEM and runs all R rounds on the VPU — ~115 AND/XOR gates per SubBytes,
zero gathers, zero MXU. 32 AES blocks ride in every uint32 lane word, so
one (8, 16, 256) tile advances 8192 blocks (128 KiB of keystream) per
grid step.

Planes are int32 in/out (the TPU-native word type; the uint32 bit
patterns pass through bitwise ops unchanged) — adapters in ``ops.py``
``.view()`` between the two. Off-TPU the adapters run the same kernel
under the Pallas interpreter, which is how the CPU tests drive it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.aes.bitslice import encrypt_planes_body

BLOCK_WORDS = 256          # lane words per tile = 8192 AES blocks


def _aes_bs_kernel(x_ref, rk_ref, o_ref, *, rounds):
    # each round's key planes are read from the ref inside the round
    # loop: Mosaic lowers a dynamic ref index, not a dynamic slice of a
    # loaded value
    out = encrypt_planes_body([x_ref[i] for i in range(8)],
                              lambda r: rk_ref[r], rounds)
    for i in range(8):
        o_ref[i] = out[i]


@functools.partial(jax.jit,
                   static_argnames=("rounds", "interpret", "block"))
def encrypt_planes_pallas(planes: jax.Array, rk_planes: jax.Array, *,
                          rounds: int, interpret: bool = False,
                          block: int = BLOCK_WORDS) -> jax.Array:
    """planes: (8, 16, W) int32 bit planes; rk_planes: (rounds+1, 8, 16,
    W) int32. Returns encrypted (8, 16, W) int32. W must divide into
    power-of-two tiles (callers bucket W; see ``ops.encrypt_many_bitsliced``)."""
    w = planes.shape[-1]
    blk = min(block, w)
    while w % blk:
        blk //= 2
    grid = (w // blk,)
    return pl.pallas_call(
        functools.partial(_aes_bs_kernel, rounds=rounds),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8, 16, blk), lambda i: (0, 0, i)),
            pl.BlockSpec((rounds + 1, 8, 16, blk), lambda i: (0, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec((8, 16, blk), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((8, 16, w), jnp.int32),
        interpret=interpret,
        name="aes_bitsliced",
    )(planes, rk_planes)
