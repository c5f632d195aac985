"""Bitsliced AES (encrypt direction) — the gather-free formulation.

The T-table AES in ``core.crypto.aes`` / ``kernels.aes.aesjax`` is a
256-entry uint32 gather per state byte, which the TPU VPU cannot do
efficiently (the same constraint that shaped ``kernels/gf256`` around
packed xtime chains). This module removes the gathers entirely:

* the batch of AES blocks is TRANSPOSED into 8 bit planes — plane ``i``
  holds bit ``i`` of every state byte, with 32 blocks packed per uint32
  lane word, so a (N, 16)-byte batch becomes an (8, 16, N/32) uint32
  tensor;
* SubBytes is the Boyar–Peralta boolean circuit for the AES S-box
  (~115 AND/XOR/XNOR gates) evaluated once over whole planes — every
  lane of every byte position advances through the same gate at once;
* ShiftRows is a static shuffle of the 16 byte positions;
* MixColumns is the xtime plane-relabeling (bit ``i`` of ``2x`` is bit
  ``i-1`` of ``x``, plus the 0x1B reduction XORs) — no multiplies;
* AddRoundKey XORs bit-transposed per-block round keys, so N chunks
  with N different convergent keys still run in one pass.

Everything here is the pure-jnp REFERENCE for the Pallas kernel in
``bitslice_pallas.py``: the round-function helpers are shape-agnostic in
the trailing lane axis and are imported by the kernel body unchanged, so
kernel == reference by construction and both are oracle-tested against
``_SBOX`` / ``encrypt_blocks`` in ``tests/test_bitslice_kernels.py``.

Layout: planes[i, p, w] is bit ``i`` of state byte position ``p`` of
blocks ``32w .. 32w+31`` (bit ``k`` of the lane word = block ``32w+k``).
Byte position ``p = 4c + r`` follows the FIPS-197 column-major state
(s[r][c] = input byte 4c+r), so ``reshape(4, 4)`` on the p axis yields
[column, row].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# ------------------------------------------------- bit-plane transposes

def pack_planes(bytes_mp: np.ndarray) -> np.ndarray:
    """(M, P) uint8 bytes -> (8, P, M/32) uint32 bit planes (P = 16 for
    AES state blocks; round keys pack all rounds' bytes in one pass).
    M must be a multiple of 32 (callers pad; see ``ops.pad_blocks``)."""
    m, p = bytes_mp.shape
    assert m % 32 == 0, m
    bits = np.unpackbits(bytes_mp.reshape(m, p, 1), axis=2,
                         bitorder="little")              # (M, P, 8)
    # pack along the block axis FIRST (8x smaller than transposing the
    # expanded bit tensor), then shuffle the packed bytes into words
    packed = np.packbits(bits.reshape(m // 32, 32, p, 8), axis=1,
                         bitorder="little")              # (W, 4, P, 8)
    lanes = np.ascontiguousarray(packed.transpose(3, 2, 0, 1))
    return lanes.view(np.uint32)[..., 0]                 # (8, P, W)


def unpack_planes(planes: np.ndarray, nblocks: int) -> np.ndarray:
    """(8, 16, W) uint32 bit planes -> (nblocks, 16) uint8 blocks."""
    planes = np.ascontiguousarray(np.asarray(planes, dtype=np.uint32))
    w = planes.shape[-1]
    # inverse shuffle of pack_planes: words -> (W, 4, P, 8) bytes,
    # expand the packed block axis LAST (keeps the transpose 8x smaller)
    packed = np.ascontiguousarray(
        planes.view(np.uint8).reshape(8, 16, w, 4).transpose(2, 3, 1, 0))
    bits = np.unpackbits(packed, axis=1, bitorder="little")  # (W, 32, 16, 8)
    return np.packbits(bits.reshape(w * 32, 16, 8), axis=2,
                       bitorder="little")[..., 0][:nblocks]


def pack_round_keys(rks: np.ndarray) -> np.ndarray:
    """(M, R+1, 4) uint32 per-block round-key columns -> bit planes
    (R+1, 8, 16, M/32) uint32. Column word byte order matches the state:
    byte j (from the MSB) of word c lands at position p = 4c + j."""
    m, nr, _ = rks.shape
    b = np.empty((m, nr, 4, 4), np.uint8)
    for j in range(4):
        b[..., j] = (rks >> np.uint32(24 - 8 * j)).astype(np.uint8)
    planes = pack_planes(b.reshape(m, nr * 16))          # (8, nr*16, W)
    return np.ascontiguousarray(
        planes.reshape(8, nr, 16, -1).transpose(1, 0, 2, 3))


# -------------------------------------- on-device (xp-generic) transposes
#
# Same layouts as the numpy versions above, expressed as shifts/ORs so
# they trace under jit and the whole tile goes device-resident ONCE —
# the host-side ``np.unpackbits`` pack was ~20ms (state) / ~140ms (round
# keys) per 256 KiB tile, all of it VPU-shaped work. ``xp=np`` runs the
# identical code eagerly (the property-test oracle).

def pack_planes_xp(bytes_mp, xp=jnp):
    """(M, P) uint8/any-int bytes -> (8, P, M/32) uint32 bit planes —
    the device-side twin of ``pack_planes`` (byte values above 255 are
    taken mod 256 via the low 8 bit extractions)."""
    m, p = bytes_mp.shape
    x = bytes_mp.astype(xp.uint32).reshape(m // 32, 32, p)       # (W,32,P)
    bit_i = xp.arange(8, dtype=xp.uint32)[:, None, None, None]
    bits = (x[None] >> bit_i) & xp.uint32(1)                     # (8,W,32,P)
    lane_k = xp.arange(32, dtype=xp.uint32)[None, None, :, None]
    # disjoint bit positions, so the sum is an OR
    words = (bits << lane_k).sum(axis=2, dtype=xp.uint32)        # (8,W,P)
    return words.transpose(0, 2, 1)                              # (8,P,W)


def unpack_planes_xp(planes, xp=jnp):
    """(8, P, W) uint32 bit planes -> (32*W, P) uint8 bytes — the
    device-side twin of ``unpack_planes`` (callers slice off padding)."""
    _, p, w = planes.shape
    lane_k = xp.arange(32, dtype=xp.uint32)[None, None, None, :]
    bits = (planes[..., None] >> lane_k) & xp.uint32(1)      # (8,P,W,32)
    bit_i = xp.arange(8, dtype=xp.uint32)[:, None, None, None]
    acc = (bits << bit_i).sum(axis=0, dtype=xp.uint32)       # (P,W,32)
    return acc.transpose(1, 2, 0).reshape(w * 32, p).astype(xp.uint8)


def pack_round_keys_xp(rks, xp=jnp):
    """(M, R+1, 4) uint32 round-key columns -> (R+1, 8, 16, M/32) uint32
    bit planes — the device-side twin of ``pack_round_keys``."""
    m, nr, _ = rks.shape
    sh = xp.uint32(24) - xp.uint32(8) * xp.arange(4, dtype=xp.uint32)
    b = (rks[..., None] >> sh) & xp.uint32(0xFF)             # (M,nr,4,4)
    planes = pack_planes_xp(b.reshape(m, nr * 16), xp)       # (8,nr*16,W)
    return planes.reshape(8, nr, 16, -1).transpose(1, 0, 2, 3)


# ------------------------------------------------------- round function
#
# Helpers take/return a LIST of 8 plane arrays shaped (16, L) — bit
# index i = significance (planes[0] is the LSB plane). The array
# namespace ``xp`` is jnp inside jit/Pallas traces and numpy for the
# zero-compile eager host fallback — the gate/shuffle structure is the
# SAME objects either way, so kernel == fallback by construction.

def sub_bytes(b: list) -> list:
    """AES S-box over bit planes: the Boyar–Peralta circuit (BP'11),
    ~115 two-input gates, no table lookups. The published circuit's
    x0..x7 inputs / s0..s7 outputs are MSB-first; ``b`` is LSB-first."""
    x7, x6, x5, x4, x3, x2, x1, x0 = b      # x0 = MSB = b[7]
    # top linear transform (23 XORs)
    y14 = x3 ^ x5
    y13 = x0 ^ x6
    y9 = x0 ^ x3
    y8 = x0 ^ x5
    t0 = x1 ^ x2
    y1 = t0 ^ x7
    y4 = y1 ^ x3
    y12 = y13 ^ y14
    y2 = y1 ^ x0
    y5 = y1 ^ x6
    y3 = y5 ^ y8
    t1 = x4 ^ y12
    y15 = t1 ^ x5
    y20 = t1 ^ x1
    y6 = y15 ^ x7
    y10 = y15 ^ t0
    y11 = y20 ^ y9
    y7 = x7 ^ y11
    y17 = y10 ^ y11
    y19 = y10 ^ y8
    y16 = t0 ^ y11
    y21 = y13 ^ y16
    y18 = x0 ^ y16
    # shared nonlinear middle (GF(2^4) tower inversion)
    t2 = y12 & y15
    t3 = y3 & y6
    t4 = t3 ^ t2
    t5 = y4 & x7
    t6 = t5 ^ t2
    t7 = y13 & y16
    t8 = y5 & y1
    t9 = t8 ^ t7
    t10 = y2 & y7
    t11 = t10 ^ t7
    t12 = y9 & y11
    t13 = y14 & y17
    t14 = t13 ^ t12
    t15 = y8 & y10
    t16 = t15 ^ t12
    t17 = t4 ^ t14
    t18 = t6 ^ t16
    t19 = t9 ^ t14
    t20 = t11 ^ t16
    t21 = t17 ^ y20
    t22 = t18 ^ y19
    t23 = t19 ^ y21
    t24 = t20 ^ y18
    t25 = t21 ^ t22
    t26 = t21 & t23
    t27 = t24 ^ t26
    t28 = t25 & t27
    t29 = t28 ^ t22
    t30 = t23 ^ t24
    t31 = t22 ^ t26
    t32 = t31 & t30
    t33 = t32 ^ t24
    t34 = t23 ^ t33
    t35 = t27 ^ t33
    t36 = t24 & t35
    t37 = t36 ^ t34
    t38 = t27 ^ t36
    t39 = t29 & t38
    t40 = t25 ^ t39
    t41 = t40 ^ t37
    t42 = t29 ^ t33
    t43 = t29 ^ t40
    t44 = t33 ^ t37
    t45 = t42 ^ t41
    z0 = t44 & y15
    z1 = t37 & y6
    z2 = t33 & x7
    z3 = t43 & y16
    z4 = t40 & y1
    z5 = t29 & y7
    z6 = t42 & y11
    z7 = t45 & y17
    z8 = t41 & y10
    z9 = t44 & y12
    z10 = t37 & y3
    z11 = t33 & y4
    z12 = t43 & y13
    z13 = t40 & y5
    z14 = t29 & y2
    z15 = t42 & y9
    z16 = t45 & y14
    z17 = t41 & y8
    # bottom linear transform (+ the 0x63 affine constant as XNORs)
    t46 = z15 ^ z16
    t47 = z10 ^ z11
    t48 = z5 ^ z13
    t49 = z9 ^ z10
    t50 = z2 ^ z12
    t51 = z2 ^ z5
    t52 = z7 ^ z8
    t53 = z0 ^ z3
    t54 = z6 ^ z7
    t55 = z16 ^ z17
    t56 = z12 ^ t48
    t57 = t50 ^ t53
    t58 = z4 ^ t46
    t59 = z3 ^ t54
    t60 = t46 ^ t57
    t61 = z14 ^ t57
    t62 = t52 ^ t58
    t63 = t49 ^ t58
    t64 = z4 ^ t59
    t65 = t61 ^ t62
    t66 = z1 ^ t63
    s0 = t59 ^ t63
    s6 = ~(t56 ^ t62)
    s7 = ~(t48 ^ t60)
    t67 = t64 ^ t65
    s3 = t53 ^ t66
    s4 = t51 ^ t66
    s5 = t47 ^ t65
    s1 = ~(t64 ^ s3)
    s2 = ~(t55 ^ t67)
    return [s7, s6, s5, s4, s3, s2, s1, s0]    # back to LSB-first


# Static permutations of the 16 byte-position rows (p = 4c + r):
# ShiftRows moves row r left by r columns; MixColumns reads rows r+k of
# the same column. Applied as row slices + one concatenate — a sublane
# shuffle Mosaic lowers (it rejects the empty slice a roll by 0 makes).
_SHIFT_ROWS = [4 * ((p // 4 + p % 4) % 4) + p % 4 for p in range(16)]
_MIX_ROWS = [[4 * (p // 4) + (p % 4 + k) % 4 for p in range(16)]
             for k in (1, 2, 3)]


def _permute_rows(a, perm, xp):
    return xp.concatenate([a[i:i + 1] for i in perm], axis=0)


def shift_rows(a, xp=jnp):
    """One plane (16, L) through ShiftRows: a static shuffle of the 16
    byte positions (row r left-rotates by r columns)."""
    return _permute_rows(a, _SHIFT_ROWS, xp)


def xtime_bits(v: list) -> list:
    """GF(2^8)·x over bit lists: a plane relabeling plus the 0x1B
    reduction XORs (bits 0, 1, 3, 4) — zero gathers, 3 XORs."""
    return [v[7], v[0] ^ v[7], v[1], v[2] ^ v[7], v[3] ^ v[7],
            v[4], v[5], v[6]]


def mix_columns(b: list, xp=jnp) -> list:
    """8 planes (16, L) through MixColumns:
    s'_r = xt(s_r ^ s_r+1) ^ s_r+1 ^ s_r+2 ^ s_r+3 (indices mod 4)."""
    s1, s2, s3 = ([_permute_rows(x, perm, xp) for x in b]
                  for perm in _MIX_ROWS)
    xt = xtime_bits([b[i] ^ s1[i] for i in range(8)])
    return [xt[i] ^ s1[i] ^ s2[i] ^ s3[i] for i in range(8)]


def add_round_key(b: list, rk) -> list:
    """rk: (8, 16, L) planes of this round's per-block keys."""
    return [x ^ rk[i] for i, x in enumerate(b)]


def middle_round(b: list, rk, xp=jnp) -> list:
    """One full middle round: SubBytes, ShiftRows, MixColumns, ARK."""
    b = sub_bytes(b)
    b = [shift_rows(x, xp) for x in b]
    b = mix_columns(b, xp)
    return add_round_key(b, rk)


def final_round(b: list, rk, xp=jnp) -> list:
    """The last round: SubBytes + ShiftRows + ARK (no MixColumns)."""
    b = sub_bytes(b)
    b = [shift_rows(x, xp) for x in b]
    return add_round_key(b, rk)


def aes_rounds(b: list, rk_planes, rounds: int, xp=jnp) -> list:
    """The full AES encrypt pipeline over bit planes, statically
    unrolled. ``rk_planes`` is (rounds+1, 8, 16, L); static ``rounds``
    (10 = AES-128, 14 = AES-256). With ``xp=np`` this runs eagerly in
    numpy — the zero-compile CPU fallback."""
    b = add_round_key(b, rk_planes[0])
    for r in range(1, rounds):
        b = middle_round(b, rk_planes[r], xp)
    return final_round(b, rk_planes[rounds], xp)


# --------------------------------------------------- reference APIs

def broadcast_pad(blocks_u8: np.ndarray, round_keys: np.ndarray,
                  target: int) -> tuple:
    """Shared batch prep for the plane pipelines: broadcast a single
    (R+1, 4) key schedule per block, then edge-repeat-pad both arrays to
    ``target`` blocks (padded lanes run a well-defined, discarded
    block). One implementation so the reference and the Pallas adapter
    cannot drift."""
    n = blocks_u8.shape[0]
    if round_keys.ndim == 2:
        round_keys = np.broadcast_to(round_keys, (n,) + round_keys.shape)
    pad = target - n
    if pad:
        blocks_u8 = np.concatenate(
            [blocks_u8, np.repeat(blocks_u8[-1:], pad, axis=0)])
        round_keys = np.concatenate(
            [round_keys, np.repeat(round_keys[-1:], pad, axis=0)])
    return blocks_u8, round_keys


def encrypt_planes_body(planes, rk_at, rounds: int) -> list:
    """Traceable plane pipeline: 8 (16, W) planes, ``rk_at(r)`` -> round
    ``r``'s (8, 16, W) key planes -> 8 encrypted planes. The middle
    rounds run under a ``fori_loop`` so the compiler sees ONE round body
    (~370 ops), not rounds-many; ``rk_at`` reads each round's keys
    inside the loop (a ref index in a Pallas kernel, an array index in
    a jit), so kernel and reference share the exact same trace."""
    x = tuple(add_round_key(list(planes), rk_at(0)))

    def body(r, x):
        return tuple(middle_round(list(x), rk_at(r)))

    x = jax.lax.fori_loop(1, rounds, body, x)
    return final_round(list(x), rk_at(rounds))


@functools.partial(jax.jit, static_argnames=("rounds",))
def encrypt_planes(planes, rk_planes, rounds: int):
    """jit'd plane-level reference over ``encrypt_planes_body``."""
    return jnp.stack(encrypt_planes_body(
        [planes[i] for i in range(8)], lambda r: rk_planes[r], rounds))


def encrypt_blocks_bitsliced(blocks_u8: np.ndarray,
                             round_keys: np.ndarray, *,
                             engine: str = "np") -> np.ndarray:
    """Drop-in for ``core.crypto.aes.encrypt_blocks`` through the
    bitsliced pipeline: (N, 16) uint8 blocks, (N, R+1, 4) or (R+1, 4)
    uint32 round keys -> (N, 16) uint8. Pads N to a lane-word multiple
    internally. ``engine="np"`` runs the planes eagerly in numpy (no
    compile — the CPU fallback), ``"jnp"`` through the jit'd reference.
    The oracle surface for the Pallas kernel."""
    n = blocks_u8.shape[0]
    if n == 0:
        return np.empty((0, 16), np.uint8)
    blocks_u8, round_keys = broadcast_pad(blocks_u8, round_keys,
                                          n + (-n) % 32)
    rounds = round_keys.shape[1] - 1
    planes = pack_planes(blocks_u8)
    rk_planes = pack_round_keys(np.ascontiguousarray(round_keys))
    if engine == "np":
        out = np.stack(aes_rounds([planes[i] for i in range(8)],
                                  rk_planes, rounds, xp=np))
    else:
        out = encrypt_planes(planes, rk_planes, rounds)
    return unpack_planes(np.asarray(out), n)


def sbox_bytes_bitsliced(x_u8: np.ndarray) -> np.ndarray:
    """Evaluate the S-box circuit on a flat byte array (oracle test
    surface: must equal ``_SBOX[x]`` for every byte value)."""
    x = np.asarray(x_u8, np.uint8).reshape(-1)
    bits = [jnp.asarray((x >> i) & 1, jnp.uint32) for i in range(8)]
    out = sub_bytes(bits)
    acc = np.zeros(x.shape, np.uint8)
    for i in range(8):
        acc |= ((np.asarray(out[i]) & 1) << i).astype(np.uint8)
    return acc
