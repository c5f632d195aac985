"""Host adapter for the fused verify+decrypt pass.

``fused_verify_decrypt`` is the ``fused`` hook of the decode-backend
registry (``core.decode``): list of ciphertexts + per-chunk AES keys
in, (digests, plaintexts) out — digests byte-identical to hashlib,
plaintexts byte-identical to the serial CTR oracle. The caller
(``convergent.decrypt_chunks``) compares digests against the expected
chunk names BEFORE releasing any plaintext, so per-chunk tamper
detection and the eviction/retry semantics are unchanged.

The pass has two phases: ``submit`` packs and dispatches a tile and
returns a ``FusedTile`` at once, ``FusedTile.result()`` waits for the
kernel and splits its output into per-chunk bytes. A caller that submits
the next tile before it asks for the previous one's result keeps the
device busy while the host marshals (``core.decode``'s tile loop does);
``fused_verify_decrypt`` is ``submit(...).result()``, and the hook
offers ``submit`` as its attribute.

Marshalling is the SHA kernel's (``sha256.ops.pack_messages``): one
padded byte row per chunk. The round keys travel as one schedule of
0/-1 bit planes per chunk (``round_key_planes``); no counter or
per-block key bytes cross. ``_fused_device`` is one jit, one dispatch,
of two Pallas kernels on the TPU (``fusedp``): ``aes_bitsliced``, the
lane-dense keystream pass, then ``fused_verify_decrypt``, the SHA walk
that XORs that keystream into the ciphertext words; off the TPU the
same two phases as one XLA program. Plaintext comes back in the byte
buffer's own layout. Each tile is four ``repro.kernel.*`` spans (pack
and dispatch in ``submit``, readback and split in ``result``), one
``kernel.route.fused.*`` launch, and adds its copies to the ``xfer.*``
counters.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from repro.core.crypto.aes import expand_key
from repro.core.telemetry import COUNTERS, D2H_BYTES, H2D_BYTES, span
from repro.kernels import on_tpu, pallas_interpret, record_route
from repro.kernels.fused.fusedp import fused_lanes_jit, fused_lanes_pallas
from repro.kernels.sha256.ops import digests_to_bytes, pack_messages


def round_key_planes(keys: list, lanes: int) -> np.ndarray:
    """AES key schedules of N chunk keys -> (lanes, rounds+1, 16, 8)
    int32: ``[c, r, p, i]`` is -(bit i of round ``r``'s key byte p) of
    chunk ``c`` (lanes past N are zero): one schedule per chunk, the
    keystream pass picks each step's by its index map."""
    expanded = {k: expand_key(k) for k in set(keys)}
    rks = np.stack([expanded[k] for k in keys])           # (N, R+1, 4)
    n, nr, _ = rks.shape
    kb = rks.astype(">u4").view(np.uint8).reshape(n, nr, 16)   # byte p
    bits = np.unpackbits(kb[..., None], axis=-1, bitorder="little")
    planes = np.zeros((lanes, nr, 16, 8), np.int32)
    planes[:n] = -bits.astype(np.int32)
    return planes


@functools.partial(jax.jit, static_argnames=("rounds", "pallas", "interpret"))
def _fused_device(buf, nb, rk_planes, *, rounds: int, pallas: bool,
                  interpret: bool):
    if pallas:
        return fused_lanes_pallas(buf, nb, rk_planes, rounds=rounds,
                                  interpret=interpret)
    return fused_lanes_jit(buf, nb, rk_planes, rounds=rounds)


class FusedTile:
    """One submitted fused pass. The copies of its digests and plaintext
    back to the host start as soon as the kernel ends; ``result()``
    waits for them and returns (digests, plaintexts) as bytes."""

    __slots__ = ("_lens", "_dig", "_plain")

    def __init__(self, lens: list, dig=None, plain=None):
        self._lens, self._dig, self._plain = lens, dig, plain

    def result(self) -> tuple:
        if not self._lens:
            return [], []
        d2h = self._dig.nbytes + self._plain.nbytes
        with span("repro.kernel.readback", d2h_bytes=d2h):
            dig = np.asarray(self._dig)
            plain = np.asarray(self._plain).view(np.uint8)
        COUNTERS.add(D2H_BYTES, d2h)
        with span("repro.kernel.split"):
            return (digests_to_bytes(dig, len(self._lens)),
                    [plain[i, :n].tobytes() for i, n in enumerate(self._lens)])


def submit(cts: list, keys: list, *, interpret: bool | None = None,
           pallas: bool | None = None) -> FusedTile:
    """Pack N ciphertext chunks and their AES keys and launch one fused
    device pass over them; returns without waiting for it (see
    ``fused_verify_decrypt`` for what ``result()`` then gives)."""
    if not cts:
        return FusedTile([])
    if pallas is None:
        pallas = on_tpu()
    if pallas:
        interpret = pallas_interpret("fused", interpret)
    else:
        interpret = False
        record_route("fused", "xla-jit")
    with span("repro.kernel.pack"):
        buf, nb = pack_messages(cts)
        rk = round_key_planes(keys, buf.shape[0])
    h2d = buf.nbytes + nb.nbytes + rk.nbytes
    with span("repro.kernel.dispatch", h2d_bytes=h2d):
        dig, plain = _fused_device(buf, nb, rk, rounds=rk.shape[1] - 1,
                                   pallas=pallas, interpret=interpret)
        dig.copy_to_host_async()
        plain.copy_to_host_async()
    COUNTERS.add(H2D_BYTES, h2d)
    return FusedTile([len(ct) for ct in cts], dig, plain)


def fused_verify_decrypt(cts: list, keys: list, *,
                         interpret: bool | None = None,
                         pallas: bool | None = None) -> tuple:
    """One fused device pass over N ciphertext chunks: returns
    (digests, plaintexts) — digests[i] == sha256(cts[i]).digest() and
    plaintexts[i] == AES-CTR(keys[i], zero IV) ^ cts[i], both as bytes.
    ``pallas=None`` routes through the Pallas kernel on TPU and the
    whole-tile XLA jit elsewhere; ``interpret`` only applies to the
    Pallas route."""
    return submit(cts, keys, interpret=interpret, pallas=pallas).result()


fused_verify_decrypt.submit = submit
