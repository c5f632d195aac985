"""Host adapters for the lockstep SHA-256 Pallas kernel.

``sha256_many_pallas`` is a drop-in for the ``sha_many`` hook of
``convergent.decrypt_chunks`` (and so of the ``bitsliced`` decode
backend): list of byte strings in, list of 32-byte digests out,
byte-identical to hashlib. The host's part is one memcpy per message
into a zeroed (lanes, maxb*64) byte buffer plus its SHA padding
(``pack_messages``); the big-endian word swap and the transpose to the
kernel's word-major layout run on the device (``words_from_bytes``).
Batch dimensions are bucketed (lanes to powers of two, message blocks
to coarse steps) so the kernel retraces O(log) times, not per shape.
Each call is four ``repro.kernel.*`` spans (pack, dispatch, readback,
split) and adds its copies to the ``xfer.*`` counters.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from repro.core.telemetry import COUNTERS, D2H_BYTES, H2D_BYTES, span
from repro.kernels import pallas_interpret
from repro.kernels.sha256.sha256p import STEP_BLOCKS, sha256_lanes_pallas

_MIN_LANES = 8
_MIN_BLOCKS = 8


def _bucket_lanes(n: int) -> int:
    b = _MIN_LANES
    while b < n:
        b <<= 1
    return b


def _bucket_blocks(b: int) -> int:
    """Coarse maxb buckets: powers of two from 8 up to ``STEP_BLOCKS``,
    then multiples of ``STEP_BLOCKS`` (chunk batches are usually
    same-length, so this compiles once for the common tile shape; the
    kernel walks blocks one ``STEP_BLOCKS`` step at a time)."""
    if b > STEP_BLOCKS:
        return -(-b // STEP_BLOCKS) * STEP_BLOCKS
    p = _MIN_BLOCKS
    while p < b:
        p <<= 1
    return p


def pack_messages(datas: list) -> tuple:
    """SHA-pad N byte strings into one zeroed (lanes, maxb*64) uint8
    buffer, one message per row. Returns (buffer viewed as native int32
    words, (1, lanes) int32 block counts)."""
    n = len(datas)
    nbl = [(len(d) + 9 + 63) // 64 for d in datas]
    maxb = _bucket_blocks(max(nbl))
    lanes = _bucket_lanes(n)
    buf = np.zeros((lanes, maxb * 64), np.uint8)
    for i, (d, nb) in enumerate(zip(datas, nbl)):
        ln = len(d)
        buf[i, :ln] = np.frombuffer(d, np.uint8)
        buf[i, ln] = 0x80
        buf[i, nb * 64 - 8:nb * 64] = np.frombuffer(
            (8 * ln).to_bytes(8, "big"), np.uint8)
    nb_arr = np.zeros((1, lanes), np.int32)
    nb_arr[0, :n] = nbl
    return buf.view(np.int32), nb_arr


def bswap32(x):
    """Byte-reverse every int32 word (big-endian <-> native order)."""
    srl = jax.lax.shift_right_logical
    return ((x << 24) | ((x & 0xFF00) << 8)
            | (srl(x, 8) & 0xFF00) | srl(x, 24))


def words_from_bytes(buf):
    """(lanes, maxb*16) native int32 view of message bytes -> (16, maxb,
    lanes) big-endian schedule words, the kernels' word-major layout."""
    lanes = buf.shape[0]
    return bswap32(buf).reshape(lanes, -1, 16).transpose(2, 1, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sha_device(buf, nb, *, interpret: bool):
    return sha256_lanes_pallas(words_from_bytes(buf), nb,
                               interpret=interpret)


def digests_to_bytes(dig, n: int) -> list:
    """(8, lanes) int32 digest words -> N 32-byte digests."""
    d = np.asarray(dig).view(np.uint32).T[:n].astype(">u4")
    return [d[i].tobytes() for i in range(n)]


def sha256_many_pallas(datas: list, *, interpret: bool | None = None) -> list:
    """Digests of N byte strings through the Pallas lockstep kernel.
    ``interpret=None`` compiles on TPU and interprets elsewhere."""
    n = len(datas)
    if n == 0:
        return []
    interpret = pallas_interpret("sha256", interpret)
    with span("repro.kernel.pack"):
        buf, nb = pack_messages(datas)
    h2d = buf.nbytes + nb.nbytes
    with span("repro.kernel.dispatch", h2d_bytes=h2d):
        dig = _sha_device(buf, nb, interpret=interpret)
    COUNTERS.add(H2D_BYTES, h2d)
    with span("repro.kernel.readback", d2h_bytes=dig.nbytes):
        dig_host = np.asarray(dig)
    COUNTERS.add(D2H_BYTES, dig.nbytes)
    with span("repro.kernel.split"):
        return digests_to_bytes(dig_host, n)
