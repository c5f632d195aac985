"""Plain references that decide ``correct``. They import nothing of the
program and take nothing it made, apart from the answers under test.

* ``restore_readings``: a restored parameter tree, read back from the
  device, against the benchmark's own seed tree, byte for byte.
* ``publish_readings``: a published image against a from-scratch
  implementation of its format: canonical layout (leaves in sorted path
  order, each at a chunk-aligned offset, zero-padded), the salt
  ``SHA256("repro-salt|<epoch>|<root>|")[:16]``, convergent keys
  ``SHA256(salt || chunk)``, AES-256-CTR with a zero counter block,
  ciphertext names ``SHA256(ciphertext)``, and a manifest whose key table
  is sealed with AES-GCM under the tenant key (msgpack body as AAD).
  It uses ``hashlib`` and ``cryptography`` only.
* ``publish``: the same format written, by this implementation: how the
  cold-start cells lay their image into origin, and the publish cells'
  control.
"""
from __future__ import annotations

import hashlib
import os

import msgpack
import numpy as np
from cryptography.hazmat.primitives.ciphers import Cipher, algorithms, modes
from cryptography.hazmat.primitives.ciphers.aead import AESGCM

ZERO_NAME = "__zero__"


def _raw(a) -> np.ndarray:
    return np.ascontiguousarray(a).reshape(-1).view(np.uint8)


def leaf_mismatch(got, want: np.ndarray) -> int:
    """Bytes by which one restored leaf differs from the reference leaf;
    every byte when dtype or shape differ."""
    got = np.asarray(got)
    if got.dtype != want.dtype or got.shape != want.shape:
        return int(want.nbytes)
    return int(np.count_nonzero(_raw(got) != _raw(want)))


def restore_readings(restored: dict, want: dict, platforms: dict,
                     device_platform: str = "tpu") -> dict:
    """`restored` and `want` map paths to leaves; `platforms` maps each
    restored path to the platform its buffer sits on, which has to be
    `device_platform`. Reads each restored leaf back one at a time, so
    the host holds one extra leaf at most."""
    differing = sum(int(a.nbytes) for p, a in want.items()
                    if p not in restored)
    for path, got in restored.items():
        differing += (leaf_mismatch(got, want[path]) if path in want
                      else int(np.asarray(got).nbytes))
    off_device = sum(1 for p in want
                     if platforms.get(p) != device_platform)
    return {"bytes_differing": differing, "leaves_not_in_hbm": off_device}


def make_salt(epoch: int, root: str, placement: str = "") -> bytes:
    return hashlib.sha256(b"repro-salt|%d|%s|%s" % (
        epoch, root.encode(), placement.encode())).digest()[:16]


def ctr_encrypt(key: bytes, data: bytes) -> bytes:
    enc = Cipher(algorithms.AES(key), modes.CTR(b"\x00" * 16)).encryptor()
    return enc.update(data) + enc.finalize()


def expected_layout(tree: dict, chunk_size: int) -> list:
    """[[path, offset, nbytes, dtype, shape]] of `tree` ({path: array})."""
    table, off = [], 0
    for path in sorted(tree):
        a = np.asarray(tree[path])
        table.append([path, off, int(a.nbytes), str(a.dtype), list(a.shape)])
        off += -(-int(a.nbytes) // chunk_size) * chunk_size
    return table


def image_chunks(tree: dict, chunk_size: int):
    """Yield (index, plaintext chunk) of the flattened image; the chunk
    is None where it is all zeros (the format stores no such chunk)."""
    idx = 0
    for path in sorted(tree):
        raw = _raw(np.asarray(tree[path]))
        for lo in range(0, raw.nbytes, chunk_size):
            win = raw[lo:lo + chunk_size]
            pt = (win.tobytes() + b"\x00" * (chunk_size - win.nbytes)
                  if win.any() else None)
            yield idx, pt
            idx += 1


def open_manifest(blob: bytes, tenant_key: bytes) -> tuple:
    """(public body, key table); raises when the seal does not verify."""
    outer = msgpack.unpackb(blob, raw=False)
    body = outer["body"]
    keys = AESGCM(tenant_key).decrypt(outer["nonce"],
                                      outer["key_ct"] + outer["tag"], body)
    return msgpack.unpackb(body, raw=False), keys


def publish_readings(blob: bytes, tree: dict, *, tenant_key: bytes,
                     root: str, epoch: int, chunk_size: int,
                     get_chunk) -> dict:
    """Readings of one published image. `tree` is {path: array} as it
    was published, `get_chunk(name) -> bytes` reads the origin store.

    ``chunks_wrong`` counts chunk indices whose manifest name, digest or
    key, or whose stored ciphertext, differs from the reference's, plus
    chunks missing from or extra in the manifest; ``manifest_wrong``
    counts wrong image-level fields (seal, layout, salt, sizes)."""
    try:
        body, keys = open_manifest(blob, tenant_key)
    except Exception:                 # any unreadable manifest is wrong
        n = sum(1 for _ in image_chunks(tree, chunk_size))
        return {"chunks_wrong": n, "manifest_wrong": 1}
    table = expected_layout(tree, chunk_size)
    size = (table[-1][1] + -(-table[-1][2] // chunk_size) * chunk_size
            if table else chunk_size)
    salt = make_salt(epoch, root)
    manifest_wrong = sum([
        [list(r) for r in body.get("layout", [])] != table,
        body.get("salt") != salt,
        body.get("chunk_size") != chunk_size,
        body.get("image_size") != size,
        body.get("root_id") != root,
    ])
    refs = {int(c[0]): (pos, c[1], c[2])
            for pos, c in enumerate(body.get("chunks", []))}
    wrong = 0
    seen = 0
    for idx, pt in image_chunks(tree, chunk_size):
        seen += 1
        ref = refs.pop(idx, None)
        if ref is None:
            wrong += 1
            continue
        pos, name, digest = ref
        if pt is None:
            wrong += name != ZERO_NAME
            continue
        key = hashlib.sha256(salt + pt).digest()
        ct = ctr_encrypt(key, pt)
        want = hashlib.sha256(ct).digest()
        ok = (name == want.hex() and digest == want
              and keys[32 * pos:32 * pos + 32] == key)
        if ok:
            try:
                ok = get_chunk(name) == ct
            except OSError:
                ok = False
        wrong += not ok
    wrong += len(refs)                 # chunks the image does not have
    return {"chunks_wrong": wrong, "manifest_wrong": manifest_wrong}


def publish(tree: dict, *, tenant_key: bytes, root: str, epoch: int,
            chunk_size: int, put_chunk, image_id: str = "bench",
            tenant: str = "bench") -> bytes:
    """Publish `tree` ({path: array}) in the image format: each chunk's
    ciphertext goes to ``put_chunk(name, ciphertext)``; returns the
    sealed manifest."""
    salt = make_salt(epoch, root)
    chunks, keys, size = [], [], 0
    for idx, pt in image_chunks(tree, chunk_size):
        size += chunk_size
        if pt is None:
            chunks.append([idx, ZERO_NAME, b""])
            keys.append(b"\x00" * 32)
            continue
        key = hashlib.sha256(salt + pt).digest()
        ct = ctr_encrypt(key, pt)
        digest = hashlib.sha256(ct).digest()
        put_chunk(digest.hex(), ct)
        chunks.append([idx, digest.hex(), digest])
        keys.append(key)
    body = msgpack.packb({
        "image_id": image_id, "tenant": tenant, "root_id": root,
        "salt": salt, "chunk_size": chunk_size,
        "image_size": size or chunk_size,
        "layout": expected_layout(tree, chunk_size),
        "chunks": chunks}, use_bin_type=True)
    nonce = os.urandom(12)
    sealed = AESGCM(tenant_key).encrypt(nonce, b"".join(keys), body)
    return msgpack.packb({"body": body, "nonce": nonce,
                          "key_ct": sealed[:-16], "tag": sealed[-16:]},
                         use_bin_type=True)
