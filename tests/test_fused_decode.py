"""The fused verify+decrypt backend (``bitsliced-fused``): one tiled
pass produces SHA-256 digests AND CTR plaintexts together. Coverage the
per-backend contract tests don't reach: mixed lengths crossing every
SHA padding boundary through BOTH lowering routes (XLA jit and the
Pallas kernel in interpret mode), tamper-mid-tile aggregation across
multiple tiles identical to the two-pass bitsliced backend, fused
``decrypt_chunks`` bad-position parity with the default path, and a
streamed restore that hits a poisoned L1 ciphertext — IntegrityError,
eviction, then a clean retry (the fused pass must not weaken the
release-nothing-on-mismatch contract)."""
import hashlib

import numpy as np
import pytest

from repro.core.crypto import aes, convergent
from repro.core.decode import BatchDecoder
from repro.kernels.fused import fused_verify_decrypt
from repro.kernels.fused.fusedp import KS_TILE

RNG = np.random.default_rng(77)

# every SHA-256 padding boundary (55/56/64) plus multi-block and
# AES-block-straddling lengths, in ONE mixed batch
BOUNDARY_LENS = [0, 1, 15, 16, 17, 54, 55, 56, 57, 63, 64, 65,
                 100, 119, 120, 121, 127, 128, 129, 4096]


def _batch(lens):
    cts = [RNG.integers(0, 256, L, dtype=np.uint8).tobytes() for L in lens]
    keys = [RNG.integers(0, 256, 32, dtype=np.uint8).tobytes() for _ in lens]
    return cts, keys


# ------------------------------------------------ the fused pass itself

CHUNK = 512 << 10
# (lengths, key bytes, lanes that share the first lane's key)
CASES = {
    "boundary": (BOUNDARY_LENS, 32, ()),
    "one_chunk": ([CHUNK], 32, ()),
    "two_512k_chunks": ([CHUNK, CHUNK], 32, ()),
    # a partial last AES block, lengths off 16 and 64, exactly 64 blocks
    "mixed": ([64 * 64, 64 * 64 - 9, 1000, 4095, 33, 64 * 64 + 1], 32, ()),
    "shared_key": ([4096, 4096, 700, 4096], 32, (1, 3)),
    "aes128": ([4096, 1000, 55, 64 * 64], 16, ()),
}


def _keyed_batch(case):
    lens, key_bytes, shared = CASES[case]
    cts = [RNG.integers(0, 256, L, dtype=np.uint8).tobytes() for L in lens]
    keys = [RNG.integers(0, 256, key_bytes, dtype=np.uint8).tobytes()
            for _ in lens]
    for i in shared:
        keys[i] = keys[0]
    return cts, keys


@pytest.mark.parametrize("route, case", [
    pytest.param(route, case,
                 id=route if case == "boundary" else f"{route}-{case}")
    for case in CASES for route in ("jit", "pallas")])
def test_fused_boundary_lengths_match_oracles(route, case):
    """digest == hashlib and plaintext == serial CTR, byte for byte,
    through both lowering routes of the fused pass: every padding
    boundary in one tile; one chunk; two 512 KiB chunks in one tile;
    mixed lengths; a key in every lane and two lanes sharing one; both
    AES key sizes."""
    cts, keys = _keyed_batch(case)
    kw = ({"pallas": False} if route == "jit"
          else {"pallas": True, "interpret": True})
    digests, plains = fused_verify_decrypt(cts, keys, **kw)
    for ct, k, d, p in zip(cts, keys, digests, plains):
        assert d == hashlib.sha256(ct).digest(), (route, len(ct))
        assert p == aes.ctr_decrypt(ct, k), (route, len(ct))
    assert fused_verify_decrypt([], []) == ([], [])


def test_fused_h2d_bytes_do_not_grow_with_blocks():
    """Beside the message buffer, a tile sends the same bytes whatever
    its chunks' lengths: one key schedule and one block count per lane.
    Per-block or per-word key planes would grow with the block count."""
    from repro.core.telemetry import COUNTERS, H2D_BYTES
    from repro.kernels.sha256.ops import pack_messages

    extra = []
    for n in (4096, 64 << 10):
        cts, keys = _batch([n, n])
        buf, _ = pack_messages(cts)
        before = COUNTERS.snapshot().get(H2D_BYTES, 0)
        digests, _ = fused_verify_decrypt(cts, keys, pallas=False)
        assert digests == [hashlib.sha256(ct).digest() for ct in cts]
        extra.append(COUNTERS.snapshot()[H2D_BYTES] - before - buf.nbytes)
    lanes, rounds = buf.shape[0], 14
    assert extra[0] == extra[1] == lanes * 4 * (1 + (rounds + 1) * 16 * 8)


@pytest.mark.parametrize("route", ["jit", "pallas"])
def test_fused_submit_then_result_equals_one_call(route):
    """Two tiles submitted before either result is read: each
    ``submit(...).result()`` equals the one-call
    ``fused_verify_decrypt``, which equals hashlib and the serial CTR
    oracle."""
    from repro.kernels.fused.ops import submit

    assert fused_verify_decrypt.submit is submit
    kw = ({"pallas": False} if route == "jit"
          else {"pallas": True, "interpret": True})
    tiles = [_batch([4096, 55, 1000]), _batch([64, 0, 4096])]
    handles = [submit(cts, keys, **kw) for cts, keys in tiles]
    for (cts, keys), handle in zip(tiles, handles):
        got = handle.result()
        assert got == fused_verify_decrypt(cts, keys, **kw)
        assert got == ([hashlib.sha256(ct).digest() for ct in cts],
                       [aes.ctr_decrypt(ct, k) for ct, k in zip(cts, keys)])
    assert submit([], [], **kw).result() == ([], [])


@pytest.mark.parametrize("ks_tile", [KS_TILE, 8])
def test_fused_kernel_multi_lane_tile_multi_step(ks_tile):
    """The Pallas kernels over a (2 lane tiles) x (2 block steps) walk:
    each lane tile restarts its digest state, carries it across steps,
    and XORs its lanes' keystream, long and short lanes in both. With a
    keystream tile of 8 words the keystream grid takes two word steps,
    and the lanes that end in the first skip the second."""
    from repro.kernels.fused.fusedp import (
        keystream_pallas,
        native_keystream,
        walk_pallas,
    )
    from repro.kernels.fused.ops import round_key_planes
    from repro.kernels.sha256.ops import (
        digests_to_bytes,
        pack_messages,
        words_from_bytes,
    )
    from repro.kernels.sha256.sha256p import STEP_BLOCKS
    blocks = 64 * STEP_BLOCKS
    cts, keys = _batch([1000, 0, 55, blocks - 9, 1, 200, 513, 64,
                        7, 900, 119, blocks + 100, 2, 600, 56, 1013])
    buf, nb = pack_messages(cts)
    maxb = buf.shape[1] // 16
    assert buf.shape[0] == 16 and maxb == 2 * STEP_BLOCKS
    rk = round_key_planes(keys, buf.shape[0])
    ks = keystream_pallas(nb, rk, maxb=maxb, rounds=14, interpret=True,
                          tile=ks_tile)
    assert ks.shape == (16, 128, maxb // 8)
    dig, plain = walk_pallas(words_from_bytes(buf), nb, buf,
                             native_keystream(ks), interpret=True, block=8)
    plain = np.asarray(plain).view(np.uint8)
    assert digests_to_bytes(dig, len(cts)) == [
        hashlib.sha256(ct).digest() for ct in cts]
    for i, (ct, k) in enumerate(zip(cts, keys)):
        assert plain[i, :len(ct)].tobytes() == aes.ctr_decrypt(ct, k), i


def test_fused_decrypt_chunks_matches_two_pass_and_bad_positions():
    """``decrypt_chunks(fused=...)`` returns the same plaintexts as the
    default two-pass path, and on tamper raises IntegrityError with the
    same batch positions — the relaxed internal ordering must not change
    what callers observe."""
    chunks = [RNG.integers(0, 256, L, dtype=np.uint8).tobytes()
              for L in (4096, 63, 1, 4096, 100)]
    encs = [convergent.encrypt_chunk(c, b"salt" * 4) for c in chunks]
    cts = [e.ciphertext for e in encs]
    keys = [e.key for e in encs]
    shas = [e.sha256 for e in encs]
    want = convergent.decrypt_chunks(cts, keys, shas)
    got = convergent.decrypt_chunks(cts, keys, shas,
                                    fused=fused_verify_decrypt)
    assert got == want == chunks
    # tamper positions 1 and 3 — mid-chunk, not just the first byte
    bad_cts = list(cts)
    for i in (1, 3):
        mid = len(bad_cts[i]) // 2
        bad_cts[i] = (bad_cts[i][:mid] + bytes([bad_cts[i][mid] ^ 0x40])
                      + bad_cts[i][mid + 1:])
    with pytest.raises(convergent.IntegrityError) as e_fused:
        convergent.decrypt_chunks(bad_cts, keys, shas,
                                  fused=fused_verify_decrypt)
    with pytest.raises(convergent.IntegrityError) as e_two:
        convergent.decrypt_chunks(bad_cts, keys, shas)
    assert e_fused.value.bad_positions == e_two.value.bad_positions == [1, 3]


# --------------------------------------------- multi-tile aggregation

class _Ref:
    def __init__(self, e, i):
        self.name, self.key, self.sha256 = f"c{i}", e.key, e.sha256


def test_fused_tamper_mid_tile_aggregates_across_tiles():
    """With 1-chunk tiles the bad chunks land in DIFFERENT tiles; the
    final IntegrityError must name every one (sorted), identically to
    the two-pass bitsliced backend on the same tampered batch, and good
    batches must be byte-identical between the two backends."""
    chunks = [RNG.integers(0, 256, 4096, dtype=np.uint8).tobytes()
              for _ in range(6)]
    encs = [convergent.encrypt_chunk(c, b"salt" * 4) for c in chunks]
    refs = [_Ref(e, i) for i, e in enumerate(encs)]
    cts = {r.name: e.ciphertext for r, e in zip(refs, encs)}
    fused_dec = BatchDecoder("bitsliced-fused", max_batch_bytes=4096)
    twopass_dec = BatchDecoder("bitsliced", max_batch_bytes=4096)
    want = {f"c{i}": c for i, c in enumerate(chunks)}
    assert fused_dec.decrypt_batch(refs, cts) == want
    assert twopass_dec.decrypt_batch(refs, cts) == want
    # flip a byte mid-chunk (mid-tile) in chunks 1 and 4
    bad = dict(cts)
    for i in (1, 4):
        n = f"c{i}"
        bad[n] = bad[n][:2048] + bytes([bad[n][2048] ^ 0x01]) + bad[n][2049:]
    with pytest.raises(convergent.IntegrityError) as ef:
        fused_dec.decrypt_batch(refs, bad)
    with pytest.raises(convergent.IntegrityError) as et:
        twopass_dec.decrypt_batch(refs, bad)
    assert ef.value.bad_positions == et.value.bad_positions == ["c1", "c4"]


# ------------------------------------- streamed restore + L1 recovery

def test_fused_streamed_restore_poisoned_l1_evicts_and_recovers(tmp_path):
    """A corrupted ciphertext planted in the shared L1 must fail the
    fused verify, be evicted, and a retry (now reading origin) must
    restore byte-identically — the §3.1 integrity loop end-to-end
    through the fused backend."""
    from repro.core.gc import GenerationalGC
    from repro.core.loader import create_image
    from repro.core.manifest import ZERO_CHUNK
    from repro.core.service import ImageService, ReadPolicy, ServiceConfig
    from repro.core.store import ChunkStore

    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    rng = np.random.default_rng(5)
    tree = {"w": rng.standard_normal((8 * 1024,)).astype(np.float32)}
    key = b"F" * 32
    blob, _ = create_image(tree, tenant="fz", tenant_key=key, store=store,
                           root=gc.active, chunk_size=4096)
    # pinned tile: no autotune sweep of the CPU routes (~30 s)
    svc = ImageService(store, ServiceConfig(
        l1_bytes=8 << 20, l2_nodes=0, fetch_concurrency=0, max_coldstarts=0,
        decode_backend="bitsliced-fused", max_batch_bytes=256 << 10))
    h = svc.open(blob, key)
    oracle = h.restore_tree(policy=ReadPolicy(mode="serial"))
    victim = next(c for c in h.reader.m.chunks if c.name != ZERO_CHUNK)
    svc.l1.put(victim.name, b"\xee" * 4096)      # poisoned cached copy
    policy = ReadPolicy(mode="streamed", decode_backend="bitsliced-fused")
    with pytest.raises(convergent.IntegrityError, match=victim.name):
        h.restore_tree(policy=policy)
    assert svc.l1.peek(victim.name) is None      # poison evicted
    flat = h.restore_tree(policy=policy)         # retry reads origin
    assert np.array_equal(flat["w"], oracle["w"])
    assert np.array_equal(flat["w"], tree["w"])
    svc.close()
