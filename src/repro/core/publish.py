"""Batched publish pipeline — the WRITE path (paper §3.1–3.2).

``core.loader.create_image`` is the serial oracle: one chunk at a time
through chunk → zero-elide → convergent-encrypt → PUT-if-absent, every
stage on the caller thread. This module is the production path: the same
stages as a *batched, overlapped* pipeline producing byte-identical
manifests and chunks:

* **chunk** — ``layout.StreamingImageWriter`` streams chunk-aligned
  windows one tensor at a time (peak extra memory: one chunk, not one
  image), accumulated into stage batches;
* **zero-elide** — all-zero chunks become ``ZERO_CHUNK`` refs without
  touching crypto (§3.2);
* **key derivation** — ONE batched SHA pass per stage batch
  (``convergent.derive_keys``, through the decode-backend registry's
  ``sha_many`` hook — the ``forward=`` direction of ``core.decode``);
* **dedup probe** — a process-wide ``NameIndex`` (convergent key →
  ciphertext name: one key ↔ one plaintext ↔ one name under a fixed
  salt) resolves previously-seen chunks to their names WITHOUT
  encrypting, and one batched ``store.has_chunks`` probe per stage
  batch confirms presence — dedup'd chunks skip encryption bytes
  entirely (the paper's ~80% fully-deduped uploads cost key hashes and
  one HEAD round, not AES);
* **encrypt** — misses go through ``BatchDecoder.encrypt_batch_timed``
  (vectorized AES-CTR keystreams + batched ciphertext naming, tiled on
  the GIL-releasing pool);
* **upload** — bounded-parallel ``put_if_absent`` (a ``BlockingLimiter``
  caps in-flight uploads AND queued ciphertext memory) with
  single-flight per (root, name) across concurrent publishers
  (``UploadFlights``) on top of the store's atomic link-into-place
  claim. Encryption of stage batch N+1 overlaps the uploads of batch N.

Publishing maintains the GC's ``RefcountIndex`` (chunk refcounts per
root, the §3.4 collection input) when one is attached, and warms the
L1 / peer tiers with the freshly-uploaded ciphertexts so the first
cold-start of a just-published checkpoint hits locally.

Each publish is one ``request_scope`` with a ``repro.publish`` span; its
stages are ``repro.publish.chunk``, ``.derive_keys``, ``.probe``,
``.encrypt``, ``.upload`` (one per upload group, on the upload threads)
and ``.seal``, each span carrying the publish's ``request``.

``GenerationalGC.migrate`` reuses the same machinery via
``copy_chunks`` (batched presence probe + bounded-parallel
single-flighted copies).
"""
from __future__ import annotations

import hashlib
import os
import threading
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from repro.core.concurrency import BlockingLimiter, LazyPool
from repro.core.crypto import convergent
from repro.core.decode import BatchDecoder
from repro.core.layout import (
    CHUNK_SIZE,
    StreamingImageWriter,
    build_layout,
    canonical_paths,
)
from repro.core.manifest import ZERO_CHUNK, ChunkRef, Manifest, seal
from repro.core.telemetry import COUNTERS, bind_request, request_scope, span

DEFAULT_UPLOAD_PARALLELISM = 8
# stage batches this large keep every vectorized pass amortized even
# when the decoder's tile is small; the decoder re-tiles internally
MIN_STAGE_BYTES = 1 << 20


@dataclass
class CreateStats:
    """Per-image creation statistics (the Fig 5 data). Produced by both
    the serial ``loader.create_image`` oracle and ``PublishPipeline``."""

    image_id: str
    total_chunks: int
    zero_chunks: int
    unique_chunks: int          # newly uploaded (not previously in store)
    dedup_chunks: int           # present already (cross/self dedup)
    bytes_total: int
    bytes_uploaded: int

    @property
    def unique_fraction(self) -> float:
        nz = self.total_chunks - self.zero_chunks
        return self.unique_chunks / max(1, nz)


def image_id_for(tree_or_bytes) -> str:
    if isinstance(tree_or_bytes, bytes):
        return hashlib.sha256(tree_or_bytes).hexdigest()[:32]
    items = canonical_paths(tree_or_bytes)
    h = hashlib.sha256()
    for name, leaf in items:
        arr = np.asarray(leaf)
        h.update(name.encode())
        h.update(np.ascontiguousarray(arr).view(np.uint8).tobytes())
    return h.hexdigest()[:32]


class NameIndex:
    """Convergent key → ciphertext name, process-wide and salt-safe.

    The convergent key is SHA256(salt ‖ plaintext), so a key uniquely
    determines the plaintext AND the salt — the mapping to the
    ciphertext name is global (no per-root scoping needed; roots only
    gate *presence*, which ``has_chunks`` probes separately). This is
    what lets successive training checkpoints publish their unchanged
    tensors without encrypting a single byte of them.

    With a ``path``, the index persists to a sidecar file: loaded at
    construction, saved atomically (temp + ``os.replace``) by
    ``save()`` — ``PublishPipeline.publish`` calls it after each
    publish — so skip-encryption dedup survives process restarts. The
    sidecar is a pure cache: a corrupt or missing file only costs
    re-encryption (never correctness), so load errors start empty
    instead of failing."""

    def __init__(self, cap: int = 1 << 20, path=None):
        self.cap = cap
        self.path = Path(path) if path is not None else None
        self._map: dict[bytes, str] = {}
        self._lock = threading.Lock()
        if self.path is not None:
            self._load()

    def _load(self):
        try:
            raw = self.path.read_text()
        except OSError:
            return
        loaded: dict[bytes, str] = {}
        try:
            for line in raw.splitlines():
                k, _, name = line.partition(" ")
                if k and name:
                    loaded[bytes.fromhex(k)] = name
        except ValueError:
            COUNTERS.inc("publish.name_index_load_errors")
            return
        self._map.update(loaded)
        COUNTERS.add("publish.name_index_loaded", len(loaded))

    def save(self):
        """Atomic sidecar write (no-op without a path). Concurrent
        publishers may race saves; each writes a consistent snapshot
        and ``os.replace`` keeps the file whole either way."""
        if self.path is None:
            return
        with self._lock:
            items = list(self._map.items())
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_name(
            self.path.name + ".tmp-%d" % threading.get_ident())
        with open(tmp, "w") as f:
            f.write("".join(f"{k.hex()} {v}\n" for k, v in items))
        os.replace(tmp, self.path)
        COUNTERS.inc("publish.name_index_saves")

    def get_many(self, keys: list) -> list:
        with self._lock:
            return [self._map.get(k) for k in keys]

    def put_many(self, pairs) -> None:
        with self._lock:
            for k, name in pairs:
                self._map[k] = name
            if self.cap and len(self._map) > self.cap:
                # drop the oldest half (insertion order); a publish-side
                # index miss only costs re-encryption, never correctness
                drop = len(self._map) - self.cap // 2
                for k in list(self._map)[:drop]:
                    del self._map[k]
                COUNTERS.inc("publish.name_index_trims")

    def __len__(self) -> int:
        with self._lock:
            return len(self._map)


class _Flight:
    __slots__ = ("event", "error")

    def __init__(self):
        self.event = threading.Event()
        self.error: BaseException | None = None


class UploadFlights:
    """Single-flight per (root, name) across concurrent publishers: of N
    racing uploads of one chunk, one performs the PUT; the rest wait on
    its flight and report dedup. The store's atomic ``put_if_absent`` is
    the correctness backstop — this table removes the duplicated upload
    *work* (bytes on the wire), not just the double-count."""

    def __init__(self):
        self._flights: dict[tuple, _Flight] = {}
        self._lock = threading.Lock()

    def begin(self, root: str, name: str) -> tuple:
        """(leader?, flight)."""
        with self._lock:
            flight = self._flights.get((root, name))
            if flight is None:
                flight = _Flight()
                self._flights[(root, name)] = flight
                return True, flight
            return False, flight

    def finish(self, root: str, name: str, flight: _Flight,
               error: BaseException | None = None) -> None:
        flight.error = error
        with self._lock:
            self._flights.pop((root, name), None)
        flight.event.set()


class PublishPipeline:
    """The batched write path over one ``ChunkStore`` (module doc).

    One pipeline per process (``ImageService`` owns one) — concurrent
    ``publish`` calls share the name index, the upload flight table and
    the bounded upload pool, so concurrent publishers single-flight
    their common chunks. All methods are thread-safe."""

    def __init__(self, store, *, backend: str = "python",
                 tile_bytes: int | str | None = None,
                 upload_parallelism: int = DEFAULT_UPLOAD_PARALLELISM,
                 l1=None, peer=None, refcounts=None,
                 name_index: NameIndex | None = None,
                 name_index_path=None,
                 flights: UploadFlights | None = None, counters=None,
                 retry=None):
        self.store = store
        self.decoder = BatchDecoder(backend, max_batch_bytes=tile_bytes)
        self.upload_parallelism = max(1, int(upload_parallelism))
        self.l1 = l1
        self.peer = peer
        self.refcounts = refcounts
        # `retry`: a ``core.retry.RetryPolicy`` wrapped around every
        # origin PUT (transient upload failures back off and re-PUT;
        # put_if_absent makes the re-PUT idempotent). None = single
        # attempt, exactly the old behavior.
        self.retry = retry
        self.names = name_index if name_index is not None \
            else NameIndex(path=name_index_path)
        self.flights = flights if flights is not None else UploadFlights()
        self.counters = counters if counters is not None else COUNTERS
        self._pool = LazyPool()
        self._limiter = BlockingLimiter(self.upload_parallelism)
        self.stage_bytes = max(MIN_STAGE_BYTES, self.decoder.max_batch_bytes)

    # ------------------------------------------------------------- publish
    def publish(self, tree, *, tenant: str, tenant_key: bytes, root: str,
                salt_epoch: int = 0, image_id: str | None = None,
                chunk_size: int = CHUNK_SIZE) -> tuple:
        """Flatten, chunk, encrypt, upload — batched and overlapped.
        Returns (sealed manifest blob, CreateStats), byte-identical to
        the serial ``loader.create_image`` (same manifest, same chunks,
        same stats semantics)."""
        t0 = time.perf_counter()
        lay = build_layout(tree, chunk_size)
        with request_scope(), span("repro.publish",
                                   image_bytes=lay.image_size):
            blob, stats = self._publish(lay, tree, tenant, tenant_key, root,
                                        salt_epoch, image_id, chunk_size)
        self.counters.inc("publish.images_published")
        self.counters.add("publish.wall_s", time.perf_counter() - t0)
        return blob, stats

    def _publish(self, lay, tree, tenant, tenant_key, root, salt_epoch,
                 image_id, chunk_size) -> tuple:
        items = canonical_paths(tree)
        salt = convergent.make_salt(salt_epoch, root)
        image_id = image_id or image_id_for(tree)
        refs: dict[int, ChunkRef] = {}
        futures: list = []
        zero = probe_dedup = 0
        chunks = iter(StreamingImageWriter(lay).chunks(items))
        while True:
            batch: list = []
            batch_bytes = 0
            with span("repro.publish.chunk") as s:
                for idx, chunk in chunks:
                    # C-speed zero scan (same predicate as the oracle's
                    # np.any, without per-chunk numpy dispatch)
                    if chunk.count(0) == len(chunk):
                        refs[idx] = ChunkRef(idx, ZERO_CHUNK)
                        zero += 1
                        continue
                    batch.append((idx, chunk))
                    batch_bytes += len(chunk)
                    if batch_bytes >= self.stage_bytes:
                        break
                s.set_metadata(chunks=len(batch), bytes=batch_bytes)
            if not batch:
                break
            probe_dedup += self._publish_batch(batch, salt, root, refs,
                                               futures)
        unique = uploaded = upload_dedup = 0
        for f in futures:
            nnew, ndup, nbytes = f.result()
            unique += nnew
            upload_dedup += ndup
            uploaded += nbytes
        with span("repro.publish.seal"):
            ordered = [refs[i] for i in sorted(refs)]
            m = Manifest(image_id=image_id, tenant=tenant, root_id=root,
                         salt=salt, chunk_size=chunk_size,
                         image_size=lay.image_size,
                         layout_table=lay.to_table(), chunks=ordered)
            blob = seal(m, tenant_key)
            self.store.put_manifest(root, image_id, blob)
            if self.refcounts is not None:
                self.refcounts.add_image(
                    root, image_id,
                    [c.name for c in ordered if c.name != ZERO_CHUNK])
            self.names.save()   # persist skip-encryption dedup (no-op
                                # without a sidecar path)
        stats = CreateStats(image_id, len(ordered), zero, unique,
                            probe_dedup + upload_dedup, lay.image_size,
                            uploaded)
        return blob, stats

    def _publish_batch(self, batch: list, salt: bytes, root: str,
                       refs: dict, futures: list) -> int:
        """One stage batch: batched key derivation → name-index + store
        presence probe (dedup'd chunks resolved WITHOUT encryption) →
        batched encrypt of the misses → bounded-parallel upload submits.
        Returns the probe-dedup count; upload futures are appended to
        `futures` (drained by ``publish`` after the last batch, so
        encryption of the next batch overlaps these uploads)."""
        idxs = [i for i, _ in batch]
        pts = [c for _, c in batch]
        with span("repro.publish.derive_keys", chunks=len(pts),
                  bytes=sum(map(len, pts))):
            keys = self.decoder.derive_keys_batch(pts, salt)
        with span("repro.publish.probe", chunks=len(pts)):
            names = self.names.get_many(keys)
            known = [p for p, n in enumerate(names) if n is not None]
            present: set = set()
            if known:
                present = self.store.has_chunks(root,
                                                [names[p] for p in known])
        skipped = 0
        skipped_bytes = 0
        to_encrypt: list[int] = []
        for p, (idx, name) in enumerate(zip(idxs, names)):
            if name is not None and name in present:
                refs[idx] = ChunkRef(idx, name, keys[p],
                                     bytes.fromhex(name))
                skipped += 1
                skipped_bytes += len(pts[p])
            else:
                to_encrypt.append(p)
        if skipped:
            self.counters.add("publish.encrypt_skipped_chunks", skipped)
            self.counters.add("publish.encrypt_skipped_bytes", skipped_bytes)
        if not to_encrypt:
            return skipped
        with span("repro.publish.encrypt", chunks=len(to_encrypt),
                  bytes=sum(len(pts[p]) for p in to_encrypt)):
            encs, _wall = self.decoder.encrypt_batch_timed(
                [pts[p] for p in to_encrypt], salt,
                keys=[keys[p] for p in to_encrypt])
        self.names.put_many((e.key, e.name) for e in encs)
        for p, enc in zip(to_encrypt, encs):
            refs[idxs[p]] = ChunkRef(idxs[p], enc.name, enc.key, enc.sha256)
        # upload in GROUPS (~2 per lane): per-chunk future/limiter churn
        # would dominate small-chunk images; within a group the puts run
        # serially on one worker, groups run bounded-parallel. Intra-
        # batch duplicate names fall out naturally — the second put is a
        # store-level dedup (or a single-flight follow across groups).
        items = [(e.name, e.ciphertext) for e in encs]
        gsz = max(1, -(-len(items) // (2 * self.upload_parallelism)))
        for g in range(0, len(items), gsz):
            self._submit_upload(root, items[g:g + gsz], futures)
        self.counters.inc("publish.stage_batches")
        return skipped

    # ------------------------------------------------------------- uploads
    def _submit_upload(self, root: str, items: list, futures: list) -> None:
        """Bounded-parallel group submit: the limiter is acquired HERE
        (caller thread) and released by the worker, capping in-flight
        upload groups and queued ciphertext memory at
        ``upload_parallelism`` groups."""
        self._limiter.acquire()
        try:
            fut = self._pool.get(self.upload_parallelism).submit(
                bind_request(self._upload_group), root, items)
        except BaseException:
            self._limiter.release()
            raise
        futures.append(fut)

    def _upload_group(self, root: str, items: list) -> tuple:
        """(new_chunks, dedup_chunks, uploaded_bytes) for a group of
        single-flighted PUT-if-absent uploads."""
        new = dup = nbytes = 0
        try:
            with span("repro.publish.upload", chunks=len(items),
                      bytes=sum(len(ct) for _, ct in items)):
                for name, ct in items:
                    if self._upload_one(root, name, ct):
                        new += 1
                        nbytes += len(ct)
                    else:
                        dup += 1
            return new, dup, nbytes
        finally:
            self._limiter.release()

    def _upload_one(self, root: str, name: str, ct: bytes) -> bool:
        """One single-flighted PUT-if-absent; True if newly uploaded."""
        leader, flight = self.flights.begin(root, name)
        if not leader:
            flight.event.wait()
            if flight.error is None:
                self.counters.inc("publish.upload_singleflight_dedup")
                return False
            # leader failed: take over with our own attempt
        err = None
        try:
            if self.retry is None:
                was_new = self.store.put_if_absent(root, name, ct)
            else:
                was_new = self.retry.call(
                    lambda: self.store.put_if_absent(root, name, ct),
                    counters=self.counters)
        except BaseException as e:
            err = e
            raise
        finally:
            if leader:
                self.flights.finish(root, name, flight, err)
        if was_new:
            self.counters.inc("publish.chunks_uploaded")
            if self.l1 is not None:
                self.l1.put(name, ct)                # warm the local tier
            if self.peer is not None:
                try:
                    self.peer.put_chunk(name, ct, source="publish")
                except TypeError:                # older put_chunk signature
                    self.peer.put_chunk(name, ct)
        return was_new

    # ---------------------------------------------------------- migration
    def copy_chunks(self, from_root: str, to_root: str, names,
                    parallelism: int | None = None) -> int:
        """Copy `names` from `from_root` into `to_root` — the batched GC
        migration path: ONE batched presence probe on the destination,
        then bounded-parallel single-flighted GET+PUT copies. Returns
        the number of chunks actually copied."""
        want = [n for n in dict.fromkeys(names) if n != ZERO_CHUNK]
        if not want:
            return 0
        present = self.store.has_chunks(to_root, want)
        missing = [n for n in want if n not in present]
        if not missing:
            return 0
        par = parallelism or self.upload_parallelism

        def copy_one(name: str) -> int:
            leader, flight = self.flights.begin(to_root, name)
            if not leader:
                flight.event.wait()
                if flight.error is None:
                    return 0
            err = None
            try:
                data = self.store.get_chunk(from_root, name)
                return 1 if self.store.put_if_absent(to_root, name, data) \
                    else 0
            except BaseException as e:
                err = e
                raise
            finally:
                if leader:
                    self.flights.finish(to_root, name, flight, err)

        copied = sum(self._pool.get(par).map(copy_one, missing))
        self.counters.add("publish.migrated_chunk_copies", copied)
        return copied

    def close(self):
        """Drain the upload pool (idempotent); in-flight PUTs finish."""
        self._pool.shutdown()
        self.decoder.close()
