"""The gather-free Pallas decode kernels and the decode-backend
registry: bitsliced AES (Boyar–Peralta S-box circuit over bit planes)
against the ``_SBOX``/T-table oracles across block counts and round
keys, the lockstep SHA-256 kernel against hashlib across message
lengths including every padding boundary, tamper-detection and full
restore byte-identity through EVERY registered decode backend, and the
registry's alias/auto resolution."""
import hashlib

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.crypto import aes, convergent
from repro.core.decode import (
    BatchDecoder,
    get_backend,
    known_backend_names,
    registered_backends,
    resolve_backend_name,
)
from repro.kernels.aes import bitslice, encrypt_many_bitsliced
from repro.kernels.aes.bitslice_pallas import encrypt_planes_pallas
from repro.kernels.sha256 import sha256_many_pallas

RNG = np.random.default_rng(123)

# every backend the registry knows, plus the serial oracle: the tamper
# and restore identity tests iterate THIS list, so a newly registered
# backend is automatically held to the same contract
ALL_BACKENDS = sorted(registered_backends()) + ["serial"]


# ------------------------------------------------------ bitsliced AES

def test_sbox_circuit_matches_table_all_bytes():
    got = bitslice.sbox_bytes_bitsliced(np.arange(256, dtype=np.uint8))
    assert np.array_equal(got, aes._SBOX)


def test_plane_transpose_roundtrip():
    blocks = RNG.integers(0, 256, (96, 16), dtype=np.uint8)
    planes = bitslice.pack_planes(blocks)
    assert planes.shape == (8, 16, 3) and planes.dtype == np.uint32
    assert np.array_equal(bitslice.unpack_planes(planes, 96), blocks)


@settings(max_examples=12)
@given(st.integers(min_value=1, max_value=200),
       st.sampled_from([16, 32]),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_bitsliced_aes_matches_ttable_oracle(nblocks, keylen, seed):
    """Property: per-block-keyed bitsliced AES == the serial T-table
    pass for arbitrary block counts, AES-128 and AES-256 schedules."""
    rng = np.random.default_rng(seed)
    blocks = rng.integers(0, 256, (nblocks, 16), dtype=np.uint8)
    rks = np.stack([
        aes.expand_key(rng.integers(0, 256, keylen, dtype=np.uint8).tobytes())
        for _ in range(nblocks)])
    want = aes.encrypt_blocks(blocks, rks)
    got_np = bitslice.encrypt_blocks_bitsliced(blocks, rks, engine="np")
    got_pl = encrypt_many_bitsliced(blocks, rks, interpret=True)
    assert np.array_equal(got_np, want)
    assert np.array_equal(got_pl, want)


def test_bitsliced_pallas_kernel_matches_plane_reference():
    """The tiled kernel and the jit'd plane reference agree at a
    multi-tile shape (grid > 1 exercises the BlockSpec indexing)."""
    n = 64 * 32                       # W = 64 words
    blocks = RNG.integers(0, 256, (n, 16), dtype=np.uint8)
    rks = np.repeat(aes.expand_key(b"q" * 32)[None], n, axis=0)
    planes = bitslice.pack_planes(blocks)
    rkp = bitslice.pack_round_keys(rks)
    out = encrypt_planes_pallas(planes.view(np.int32), rkp.view(np.int32),
                                rounds=14, interpret=True, block=16)
    ref = bitslice.encrypt_planes(planes, rkp, 14)
    assert np.array_equal(np.asarray(out).view(np.uint32), np.asarray(ref))


def test_ctr_keystream_many_bitsliced_matches_serial():
    from repro.kernels.aes import ctr_keystream_many_bitsliced
    keys = [RNG.integers(0, 256, 32, dtype=np.uint8).tobytes()
            for _ in range(5)]
    lens = [0, 1, 15, 4096, 333]
    ivs = [RNG.integers(0, 256, 16, dtype=np.uint8).tobytes()
           for _ in range(5)]
    got = ctr_keystream_many_bitsliced(keys, lens, ivs)
    for k, L, iv, g in zip(keys, lens, ivs, got):
        want = aes.ctr_keystream(k, iv, (L + 15) // 16).reshape(-1)[:L]
        assert np.array_equal(g, want)


# --------------------------------------------------- lockstep SHA-256

def test_sha256_pallas_padding_boundaries():
    """Every interesting length around the 55/56/64-byte padding
    boundaries, in ONE mixed-length batch (masked lane freezing)."""
    lens = [0, 1, 54, 55, 56, 57, 63, 64, 65, 119, 120, 127, 128, 129]
    datas = [RNG.integers(0, 256, L, dtype=np.uint8).tobytes() for L in lens]
    got = sha256_many_pallas(datas, interpret=True)
    for d, g in zip(datas, got):
        assert g == hashlib.sha256(d).digest(), len(d)
    assert sha256_many_pallas([]) == []


@settings(max_examples=10)
@given(st.lists(st.integers(min_value=0, max_value=300),
                min_size=1, max_size=40),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_sha256_pallas_matches_hashlib(lens, seed):
    rng = np.random.default_rng(seed)
    datas = [rng.integers(0, 256, L, dtype=np.uint8).tobytes() for L in lens]
    got = sha256_many_pallas(datas, interpret=True)
    assert got == [hashlib.sha256(d).digest() for d in datas]


def test_sha256_pallas_kernel_multi_lane_tile_multi_step():
    """A (2 lane tiles) x (2 block steps) grid: each lane tile restarts
    the digest state at its first step and carries it across the second,
    with long and short lanes in both tiles."""
    from repro.kernels.sha256.ops import (
        digests_to_bytes,
        pack_messages,
        words_from_bytes,
    )
    from repro.kernels.sha256.sha256p import STEP_BLOCKS, sha256_lanes_pallas
    lens = [4200, 0, 55, 64 * STEP_BLOCKS, 1, 200, 3000, 64,
            7, 4100, 119, 64 * STEP_BLOCKS - 9, 2, 1000, 56, 4500]
    datas = [RNG.integers(0, 256, L, dtype=np.uint8).tobytes() for L in lens]
    buf, nb = pack_messages(datas)
    assert buf.shape[0] == 16 and buf.shape[1] // 16 == 2 * STEP_BLOCKS
    dig = sha256_lanes_pallas(words_from_bytes(buf), nb, interpret=True,
                              block=8)
    assert digests_to_bytes(dig, len(datas)) == [
        hashlib.sha256(d).digest() for d in datas]


# ------------------------------------------------------ the registry

def test_registry_names_aliases_auto():
    assert {"python", "xla", "bitsliced"} <= set(registered_backends())
    assert resolve_backend_name("numpy") == "python"
    assert resolve_backend_name("jax") == "xla"
    assert resolve_backend_name("serial") == "serial"
    assert resolve_backend_name("auto") in registered_backends()
    assert set(known_backend_names()) >= {
        "python", "numpy", "xla", "jax", "bitsliced", "serial", "auto"}
    with pytest.raises(ValueError, match="unknown decode backend"):
        resolve_backend_name("bogus")
    with pytest.raises(ValueError):
        BatchDecoder("bogus")


def test_backend_objects_carry_kernel_pairs():
    """A backend is ONE object: kernel pair + tile shape + threading."""
    py = get_backend("python")
    assert py.encrypt_many is None and py.sha_many is None  # numpy+hashlib
    bs = get_backend("bitsliced")
    assert bs.encrypt_many is encrypt_many_bitsliced
    assert bs.threads == 1            # the kernel owns its parallelism
    assert BatchDecoder("bitsliced").threads == 1
    assert BatchDecoder("jax").threads == 1
    # the as-given (alias) name survives into telemetry; auto resolves
    assert BatchDecoder("numpy").backend == "numpy"
    assert BatchDecoder("auto").backend in registered_backends()


def _enc_batch(n=5, lens=(4096, 1, 100, 4096, 63)):
    chunks = [RNG.integers(0, 256, L, dtype=np.uint8).tobytes()
              for L in lens[:n]]
    chunks[2] = b"\x00" * len(chunks[2])
    encs = [convergent.encrypt_chunk(c, b"salt" * 4) for c in chunks]
    return chunks, encs


class _Ref:
    def __init__(self, e, i):
        self.name, self.key, self.sha256 = f"c{i}", e.key, e.sha256


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_every_registry_backend_decodes_and_names_tampered_chunk(backend):
    """The acceptance contract per backend: byte-identity on good
    batches, and a tampered ciphertext raises ``IntegrityError`` naming
    exactly the offending chunk (verify-then-decrypt preserved)."""
    chunks, encs = _enc_batch()
    refs = [_Ref(e, i) for i, e in enumerate(encs)]
    cts = {r.name: e.ciphertext for r, e in zip(refs, encs)}
    want = {f"c{i}": c for i, c in enumerate(chunks)}
    dec = BatchDecoder(backend)
    assert dec.decrypt_batch(refs, cts) == want, backend
    bad = dict(cts)
    bad["c3"] = b"\xff" + bad["c3"][1:]
    with pytest.raises(convergent.IntegrityError, match="c3"):
        dec.decrypt_batch(refs, bad)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_full_restore_byte_identity_per_backend(tmp_path, backend):
    """End-to-end reachability: ``ReadPolicy.decode_backend`` selects
    each registered backend for a full streamed restore through an
    ``ImageService``, byte-identical to the serial oracle."""
    from repro.core.gc import GenerationalGC
    from repro.core.loader import create_image
    from repro.core.service import ImageService, ReadPolicy, ServiceConfig
    from repro.core.store import ChunkStore

    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    rng = np.random.default_rng(9)
    tree = {"w": rng.standard_normal((8 * 1024,)).astype(np.float32),
            "b": rng.standard_normal((256,)).astype(np.float32)}
    key = b"B" * 32
    blob, _ = create_image(tree, tenant="bs", tenant_key=key, store=store,
                           root=gc.active, chunk_size=4096)
    # the tile is pinned: the autotune sweep (tested with fake backends in
    # test_decode_stage.py) would time the Pallas interpreter for ~30 s
    svc = ImageService(store, ServiceConfig(l1_bytes=8 << 20, l2_nodes=0,
                                            fetch_concurrency=0,
                                            max_coldstarts=0,
                                            max_batch_bytes=256 << 10))
    oracle = svc.open(blob, key).restore_tree(
        policy=ReadPolicy(mode="serial"))
    h = svc.open(blob, key)
    mode = "serial" if backend == "serial" else "streamed"
    flat = h.restore_tree(policy=ReadPolicy(mode=mode,
                                            decode_backend=backend))
    for n in tree:
        assert np.array_equal(flat[n], oracle[n]), (backend, n)
        assert np.array_equal(flat[n], np.asarray(tree[n])), (backend, n)
    if backend != "serial":
        # aliases share ONE decoder (named by whoever built it first:
        # the service default "numpy" aliases "python"), so compare the
        # canonical resolution, not the literal string
        assert resolve_backend_name(
            h.reader.last_batch["decode_backend"]) == backend
    svc.close()
