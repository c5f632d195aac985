"""numpy-facing adapters for the jax batched-AES passes.

``encrypt_many_jax`` (the XLA T-table gather pass) and
``encrypt_many_bitsliced`` (the gather-free Pallas bit-plane kernel)
are drop-ins for the ``encrypt_many`` hook of ``aes.ctr_keystream_many``
(and so of ``convergent.decrypt_chunks`` / the ``core.decode`` backend
registry): same (blocks, per-block round keys) -> blocks contract as
the numpy core, byte-identical output. Batch sizes are padded up to
power-of-two buckets so jit compiles once per bucket, not once per
distinct chunk count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import COUNTERS, D2H_BYTES, H2D_BYTES, span
from repro.kernels import pallas_interpret
from repro.kernels.aes import aesjax, bitslice

_MIN_BUCKET = 256
_MIN_WORDS = 8          # bitsliced lane-word bucket floor (256 blocks)


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


def encrypt_many_jax(blocks_u8: np.ndarray, rks: np.ndarray) -> np.ndarray:
    """(N, 16) uint8 AES blocks + (N, rounds+1, 4) uint32 per-block round
    keys -> (N, 16) uint8, through one jit'd T-table pass."""
    n = blocks_u8.shape[0]
    pad = _bucket(n) - n
    if pad:
        # edge-repeat so padded lanes run a well-defined (discarded) block
        blocks_u8 = np.concatenate(
            [blocks_u8, np.repeat(blocks_u8[-1:], pad, axis=0)])
        rks = np.concatenate([rks, np.repeat(rks[-1:], pad, axis=0)])
    cols = aesjax.pack_cols(blocks_u8)
    out = aesjax.unpack_cols(aesjax.encrypt_blocks_cols(cols, rks))
    return np.asarray(out)[:n]


def ctr_keystream_many_jax(keys: list, nbytes: list,
                           ivs: list | None = None) -> list:
    """``aes.ctr_keystream_many`` behind the same interface, with the
    block pass on the jax backend."""
    from repro.core.crypto import aes
    return aes.ctr_keystream_many(keys, nbytes, ivs,
                                  encrypt_many=encrypt_many_jax)


@functools.partial(jax.jit, static_argnames=("rounds", "interpret"))
def _encrypt_device(blocks_u8, rk_chunks, idx, *, rounds, interpret):
    """Device-resident bitsliced pipeline: uint8 blocks go on-device
    ONCE, then pack / round-key transpose / circuit / unpack all trace
    under one jit. Round keys arrive one schedule per CHUNK
    ((C, R+1, 4) uint32) plus a per-block chunk index — the per-block
    expansion is a device gather, not a host ``np.repeat``."""
    per_block = jnp.take(rk_chunks, idx, axis=0)         # (M, R+1, 4)
    planes = jax.lax.bitcast_convert_type(
        bitslice.pack_planes_xp(blocks_u8, jnp), jnp.int32)
    rkp = jax.lax.bitcast_convert_type(
        bitslice.pack_round_keys_xp(per_block, jnp), jnp.int32)
    from repro.kernels.aes.bitslice_pallas import encrypt_planes_pallas
    out = encrypt_planes_pallas(planes, rkp, rounds=rounds,
                                interpret=interpret)
    return bitslice.unpack_planes_xp(
        jax.lax.bitcast_convert_type(out, jnp.uint32), jnp)


def encrypt_many_bitsliced(blocks_u8: np.ndarray, rks: np.ndarray, *,
                           counts: np.ndarray | None = None,
                           interpret: bool | None = None) -> np.ndarray:
    """(N, 16) uint8 blocks -> (N, 16) uint8 through the gather-free
    bitsliced Pallas kernel, with the bit-plane pack/unpack transposes
    ON DEVICE (host marshalling is two index builds, not bit twiddling).

    Round keys come in two shapes:
    * legacy: ``rks`` is (N, rounds+1, 4) per-block or (rounds+1, 4)
      shared (the ``encrypt_many`` hook contract);
    * run-length (``counts`` given): ``rks`` is (C, rounds+1, 4) — ONE
      schedule per chunk — and ``counts[c]`` blocks use schedule ``c``
      (``sum(counts) == N``). ``ctr_keystream_many`` selects this path
      via the ``per_chunk_rks`` attribute, skipping its host-side
      ``np.repeat`` of 60-word schedules per block.

    Lane-word and chunk counts are bucketed to powers of two, both from
    256, so the jit compiles O(log batch) times and a batch of up to 256
    per-block schedules compiles once per key size. ``interpret=None``
    compiles on TPU and interprets elsewhere."""
    n = blocks_u8.shape[0]
    if n == 0:
        return np.empty((0, 16), np.uint8)
    interpret = pallas_interpret("aes", interpret)
    with span("repro.kernel.pack"):
        blocks_u8, rks, idx = _pack_bitsliced(blocks_u8, rks, counts)
    h2d = blocks_u8.nbytes + rks.nbytes + idx.nbytes
    with span("repro.kernel.dispatch", h2d_bytes=h2d):
        out = _encrypt_device(blocks_u8, rks, idx, rounds=rks.shape[1] - 1,
                              interpret=interpret)
    COUNTERS.add(H2D_BYTES, h2d)
    with span("repro.kernel.readback", d2h_bytes=out.nbytes):
        host = np.asarray(out)
    COUNTERS.add(D2H_BYTES, out.nbytes)
    return host[:n]


encrypt_many_bitsliced.per_chunk_rks = True


def _pack_bitsliced(blocks_u8: np.ndarray, rks: np.ndarray,
                    counts: np.ndarray | None) -> tuple:
    """(blocks, per-chunk schedules, per-block chunk index), padded to
    ``encrypt_many_bitsliced``'s buckets."""
    n = blocks_u8.shape[0]
    if counts is None:
        if rks.ndim == 2:
            rks = rks[None]
            counts = np.array([n], np.int64)
        else:                      # per-block schedules: chunk == block
            counts = np.ones(n, np.int64)
    rks = np.ascontiguousarray(np.asarray(rks, np.uint32))
    idx = np.repeat(np.arange(len(counts), dtype=np.int32),
                    np.asarray(counts))
    assert idx.shape[0] == n, (idx.shape, n)
    words = _MIN_WORDS
    while words * 32 < n:
        words <<= 1
    pad = words * 32 - n
    if pad:                        # padded lanes rerun the last block
        blocks_u8 = np.concatenate(
            [blocks_u8, np.repeat(blocks_u8[-1:], pad, axis=0)])
        idx = np.concatenate([idx, np.full(pad, idx[-1], np.int32)])
    c = rks.shape[0]
    cb = _MIN_WORDS * 32
    while cb < c:
        cb <<= 1
    if cb > c:
        rks = np.concatenate([rks, np.repeat(rks[-1:], cb - c, axis=0)])
    return blocks_u8, rks, idx


def ctr_keystream_many_bitsliced(keys: list, nbytes: list,
                                 ivs: list | None = None) -> list:
    """``aes.ctr_keystream_many`` with the block pass on the bitsliced
    Pallas kernel — N differently-keyed CTR streams, zero gathers."""
    from repro.core.crypto import aes
    return aes.ctr_keystream_many(keys, nbytes, ivs,
                                  encrypt_many=encrypt_many_bitsliced)
