"""Demand-paged block device over the chunk store + page-granular COW
overlay (paper §2.1), with the restore data path split into two explicit
stages (paper §2.2/§3.1: cold-start latency is set by how much of the
fetch AND post-fetch pipeline stays dense, not by per-chunk cost):

  stage F — fetch-I/O only (``fetch_ciphertexts``): L1 probe ->
    single-flight claim -> batched L2 stripe fetch -> parallel,
    limiter-bounded origin fetch. Nothing is decrypted here; the stage
    produces a ``FetchedBatch`` of ciphertexts.
  stage D — decode (``repro.core.decode.BatchDecoder``): ONE batched
    SHA verify + ONE batched AES-CTR keystream pass over the whole
    fetched set (``convergent.decrypt_chunks`` /
    ``aes.ctr_keystream_many``), instead of a per-chunk decrypt loop.

This inverts the PR 1 control flow: instead of each worker *pulling* one
chunk through every tier (with decrypt squeezed onto the caller thread,
GIL-bound), chunks are *pushed* through staged batches — all I/O in
flight together, then one dense vectorized decode.

Three read APIs:

* Serial (``fetch_chunk`` / ``read``): one chunk at a time, per-chunk
  ``decrypt_chunk``; each access records its end-to-end simulated
  latency in ``read_lat``. This is the oracle path — the staged batch
  path is tested byte-identical against it.
* Batched (``fetch_chunks`` / ``read_many``): callers hand over every
  byte range they will need; the reader coalesces them into a
  deduplicated chunk set and runs stage F then stage D. Origin fetches
  are bounded by the optional ``concurrency`` (``BlockingLimiter``).
  Concurrent requests for the same chunk *name* — a cache-miss stampede
  across threads or readers sharing this instance — are single-flighted:
  one origin fetch, every waiter shares the ciphertext. Per-chunk tier
  latencies still land in ``read_lat`` (the Fig 11 modes); the batch's
  pipelined wall-clock model plus the fetch/decode wall split land in
  ``batch_lat`` and ``last_batch``.
* Staged (``fetch_ciphertexts`` + a ``BatchDecoder``): for callers that
  want to overlap their own work between the stages or pick a decode
  backend per call.
* Streamed (``fetch_chunks(..., streamed=True)`` — the default restore
  path via ``loader``): stage F runs on a producer thread and *streams*
  each resolved ciphertext (L1 hits immediately, then L2
  reconstructions and origin flights the moment they land) into a
  ``BoundedQueue``; ``BatchDecoder.decrypt_stream`` consumes on the
  caller thread, tiling and decoding while fetch is still in flight.
  Decode wall-clock hides behind the deepest miss instead of starting
  after it; the queue bound gives backpressure so memory stays flat.

Streaming contract (stage F side): with a ``sink`` queue, every distinct
non-zero chunk name is pushed exactly once — by its L1 probe hit, its
single-flight leader resolution, or its followed flight. A flight's
event is always set BEFORE its own push, and an origin wave resolves
every landed fetch (and submits replacements) before pushing any of
them — so a resolved chunk's stampeding waiters on other readers never
wait on sink backpressure. Backpressure still throttles the producer
(that is its job): names this producer has claimed but not yet resolved
can be delayed transitively by a saturated sink. A cancelled sink drops
pushes silently (the producer still warms every cache tier); a fetch
failure poisons the sink after the failing flight is poisoned. On an
``IntegrityError`` from either decode mode the offending names are
evicted from L1 AND L2, so a retry refetches from origin instead of
replaying the tampered ciphertext from cache.

``origin_delay_s`` optionally injects a *real* sleep per origin fetch so
benchmarks can demonstrate the serial-vs-pipelined wall-clock gap; it
defaults to 0 and never affects correctness.

``CowBlockDevice`` adds the write path: writes land in an encrypted
overlay at page granularity with a bitmap; base chunks stay immutable so
every cache tier can share them across tenants/replicas. Reads assemble
dirty pages from the overlay and fetch all clean spans through one
``read_many`` batch; a large unaligned write batches all of its
read-modify-write base-page faults through one ``read_many`` too.
"""
from __future__ import annotations

import contextlib
import functools
import heapq
import inspect
import itertools
import threading
import time
from concurrent.futures import FIRST_COMPLETED, wait

import numpy as np

from repro.core.concurrency import BoundedQueue, LazyPool
from repro.core.crypto import aes, convergent
from repro.core.decode import BatchDecoder
from repro.core.layout import ranges_to_chunks
from repro.core.manifest import ZERO_CHUNK, Manifest
from repro.core.retry import BreakerOpenError, is_retryable
from repro.core.telemetry import (
    COUNTERS,
    LatencyRecorder,
    bind_request,
    span,
)

PAGE = 4096
ORIGIN_LAT_S = 36e-3          # paper: S3 origin median 36ms (simulated)
L1_PROBE_S = 2e-6
DEFAULT_PARALLELISM = 8
DEFAULT_QUEUE_DEPTH = 32      # streamed hand-off queue bound (chunks)


def _pinned(fn):
    """Hold the reader's GC root pin for the duration of a public read
    entry point (no-op without a registry; nested calls just bump the
    count). See ``TieredReader._pin``."""
    @functools.wraps(fn)
    def wrapped(self, *args, **kwargs):
        with self._pin():
            return fn(self, *args, **kwargs)
    return wrapped


def pipelined_latency(lats, lanes: int) -> float:
    """Wall-clock of running `lats` on `lanes` parallel workers, jobs
    assigned to the least-loaded lane in submission order (exactly what a
    thread pool does to identical-priority work)."""
    lats = list(lats)
    if not lats:
        return 0.0
    lanes = max(1, min(int(lanes), len(lats)))
    heap = [0.0] * lanes
    for lat in lats:
        heapq.heapreplace(heap, heap[0] + lat)
    return max(heap)


class _Flight:
    """In-flight fetch for one chunk name (single-flight)."""

    __slots__ = ("event", "ciphertext", "sim_lat", "error")

    def __init__(self):
        self.event = threading.Event()
        self.ciphertext = None
        self.sim_lat = 0.0
        self.error = None


class FlightTable:
    """Shared single-flight registry: (root, chunk name) -> in-flight
    fetch.

    Chunk names are content addresses, so one table can serve MANY
    readers — an ``ImageService`` passes one table to every reader it
    builds, making a stampede on the same chunk from different images
    (or different tenants: convergent encryption gives them the same
    names) cost ONE origin fetch process-wide, not one per reader.
    Keys include the reader's root: origin fetches are root-addressed,
    and a leader's root-specific failure (e.g. an expired root mid-GC)
    must not poison a follower reading the same name from a live root."""

    __slots__ = ("lock", "flights")

    def __init__(self):
        self.lock = threading.Lock()
        self.flights: dict[tuple, _Flight] = {}


class FetchedBatch:
    """Output of the fetch-I/O stage (stage F), input to the decode
    stage (stage D): ciphertexts + per-name simulated latencies, with
    the index bookkeeping the decode stage needs to fan plaintexts back
    out to chunk indices."""

    __slots__ = ("by_name", "ciphertexts", "lats", "zero_indices",
                 "l1_lat", "l1_hits", "sink")

    def __init__(self, sink: BoundedQueue | None = None):
        self.by_name: dict[str, list[int]] = {}     # name -> chunk indices
        self.ciphertexts: dict[str, bytes] = {}
        self.lats: dict[str, float] = {}            # simulated fetch lat
        self.zero_indices: list[int] = []
        self.l1_lat = 0.0
        self.l1_hits = 0
        # streaming hand-off: each resolved (name, ciphertext) is pushed
        # the moment it lands; None = staged mode (terminal dict only)
        self.sink = sink


class TieredReader:
    def __init__(self, manifest: Manifest, store, root: str | None = None,
                 l1=None, l2=None, concurrency=None,
                 origin_delay_s: float = 0.0, decoder: BatchDecoder | None = None,
                 counters=None, flights: FlightTable | None = None,
                 peer=None, pins=None, retry=None, breaker=None):
        self.m = manifest
        self.store = store
        self.root = root or manifest.root_id
        self.l1 = l1
        self.l2 = l2
        # optional peer tier (``repro.core.cache.peer.PeerClient``): the
        # worker-to-worker provisioning mesh, probed between L1 and L2.
        # Probe order: L1 -> peer -> L2 -> origin.
        self.peer = peer
        self.concurrency = concurrency
        self.origin_delay_s = origin_delay_s
        self.decoder = decoder if decoder is not None else BatchDecoder()
        # `counters`: a Counters-compatible sink (e.g. a per-tenant
        # ScopedCounters from ImageService) — the multi-tenant read path
        # attributes this reader's fetch activity without forking the
        # global totals
        self.counters = counters if counters is not None else COUNTERS
        # `pins`: a ``gc.RootPinRegistry`` — every public read entry
        # point pins ``self.root`` for its duration, so a concurrent GC
        # generation roll cannot delete/sweep the root mid-restore
        # (epoch/pin protocol, §3.4)
        self.pins = pins
        self.read_lat = LatencyRecorder("e2e.read")
        self.batch_lat = LatencyRecorder("e2e.read_batch")
        self.last_batch: dict = {}
        self._refs = {c.index: c for c in manifest.chunks}
        # single-flight state; a shared FlightTable (service-wide) dedups
        # stampedes ACROSS readers, the private default within one
        table = flights if flights is not None else FlightTable()
        self._flights = table.flights
        self._flight_lock = table.lock
        # long-lived fetch pool, grown on demand: spawning a pool per
        # batch would put thread start/join on the demand-paging hot path
        self._fetch_pool = LazyPool()
        # can the L2 feed the stream per-chunk (get_chunks(on_ready=...))?
        # can it hedge straggler stripes (get_chunks(hedge=...))?
        l2_get = getattr(l2, "get_chunks", None)
        l2_params = inspect.signature(l2_get).parameters if l2_get else {}
        self._l2_streams = "on_ready" in l2_params
        self._l2_hedges = "hedge" in l2_params
        # origin resilience (``core.retry``): `retry` is a RetryPolicy
        # wrapped around every origin GET (and around integrity-failure
        # evict+refetch rounds); `breaker` is a service-wide
        # CircuitBreaker gating origin probes (open = reads prefer
        # peer/L2 and back off; half-open = bounded probes). Both None
        # by default — the no-knobs path is byte-for-byte the old one.
        self.retry = retry
        self.breaker = breaker
        store_get = getattr(store, "get_chunk", None)
        self._store_deadlines = store_get is not None and \
            "deadline_s" in inspect.signature(store_get).parameters

    def _pin(self):
        """Pin this reader's root for the duration of a read (no-op
        without a registry). Re-entrant by construction: pins are
        counted, so a public method calling another public method just
        nests."""
        if self.pins is None:
            return contextlib.nullcontext()
        return self.pins.pin(self.root)

    # ------------------------------------------------------------- chunks
    def _origin_get(self, name: str) -> bytes:
        """ONE origin chunk GET with the resilience ladder applied:
        breaker gate (open = shed; half-open = bounded probes), bounded
        limiter, per-attempt deadline (forwarded to deadline-capable
        stores), and — when a ``RetryPolicy`` is wired — backoff retries
        of transient failures. Breaker accounting only sees *retryable*
        outcomes: a ``FileNotFoundError`` is a bug, not origin weather,
        and must not open the breaker. One ``repro.fetch.origin`` span
        per call (its retries included)."""
        def attempt() -> bytes:
            br = self.breaker
            if br is not None and not br.allow():
                raise BreakerOpenError(br.retry_after_s())
            limiter = self.concurrency if self.concurrency is not None \
                else contextlib.nullcontext()
            kw = {}
            if self._store_deadlines and self.retry is not None and \
                    self.retry.attempt_timeout_s is not None:
                kw["deadline_s"] = self.retry.attempt_timeout_s
            try:
                with limiter:
                    if self.origin_delay_s > 0:
                        time.sleep(self.origin_delay_s)
                    ct = self.store.get_chunk(self.root, name, **kw)
            except BreakerOpenError:
                raise
            except Exception as e:
                if br is not None and is_retryable(e):
                    br.record_failure()
                raise
            if br is not None:
                br.record_success()
            return ct

        with span("repro.fetch.origin") as s:
            ct = attempt() if self.retry is None else \
                self.retry.call(attempt, counters=self.counters)
            s.set_metadata(bytes=len(ct))
        return ct

    def _integrity_attempts(self) -> int:
        """Total decode attempts per read: 1 (today's behavior) plus the
        retry policy's evict+refetch budget for integrity failures."""
        if self.retry is None:
            return 1
        return 1 + max(0, int(self.retry.integrity_refetches))

    def _fetch_cipher(self, ref) -> tuple[bytes, float]:
        """(ciphertext, simulated latency) of `ref` via L2 -> origin,
        single-flighted by chunk name. L1 is probed by callers."""
        with self._flight_lock:
            flight = self._flights.get((self.root, ref.name))
            if flight is None:
                flight = _Flight()
                self._flights[(self.root, ref.name)] = flight
                leader = True
            else:
                leader = False
        if not leader:
            flight.event.wait()
            self.counters.inc("read.singleflight_dedup")
            if flight.error is not None:
                raise flight.error
            return flight.ciphertext, flight.sim_lat
        try:
            lat = 0.0
            ct = None
            src = None
            # leader double-check: a previous flight for this name may have
            # backfilled L1 after this caller's probe missed (stampede race)
            if self.l1 is not None:
                peek = getattr(self.l1, "peek", self.l1.get)
                ct = peek(ref.name)
                if ct is not None:
                    lat += L1_PROBE_S
            if ct is None and self.peer is not None:
                # peer probe: a directory hit or joined provisioning
                # flight transfers worker-to-worker; a miss leaves this
                # worker leading the mesh flight — the publish/abandon
                # below settles the lease either way
                with span("repro.fetch.peer"):
                    plat, ct = self.peer.get_chunk(ref.name,
                                                   self.m.chunk_size)
                lat += plat
                if ct is not None:
                    self.counters.inc("read.peer_hits")
                    if self.l1 is not None:
                        self.l1.put(ref.name, ct)
            if ct is None and self.l2 is not None:
                with span("repro.fetch.l2"):
                    l2lat, l2ct = self.l2.get_chunk(ref.name,
                                                    self.m.chunk_size)
                lat += l2lat
                if l2ct is not None:
                    ct, src = l2ct, "l2"
                    if self.l1 is not None:
                        self.l1.put(ref.name, ct)
            if ct is None:
                ct = self._origin_get(ref.name)
                lat += ORIGIN_LAT_S
                src = "origin"
                self.counters.inc("read.origin_fetches")
                if self.l2 is not None:
                    self.l2.put_chunk(ref.name, ct)
                if self.l1 is not None:
                    self.l1.put(ref.name, ct)
            if src is not None and self.peer is not None:
                # resolve the mesh flight (joiners receive through the
                # tree) and register per the mesh's registration policy
                self.peer.put_chunk(ref.name, ct, source=src)
            flight.ciphertext = ct
            flight.sim_lat = lat
            return ct, lat
        except Exception as e:          # propagate to waiters too
            flight.error = e
            if self.peer is not None:
                # release a mesh lease we may hold: promotes a joiner to
                # leader instead of stranding the whole tree (no-op when
                # another worker leads)
                self.peer.abandon(ref.name)
            raise
        finally:
            with self._flight_lock:
                self._flights.pop((self.root, ref.name), None)
            flight.event.set()

    @_pinned
    def fetch_chunk(self, index: int) -> bytes:
        """Plaintext of chunk `index`, via the cache hierarchy (serial).

        On an integrity failure the bad name is evicted from EVERY tier
        — including the peer mesh directory, so later joiners don't
        re-fetch a poisoned holder copy — and, with a retry policy
        wired, refetched fresh from origin (bounded rounds) instead of
        failing the read."""
        ref = self._refs[index]
        cs = self.m.chunk_size
        if ref.name == ZERO_CHUNK:
            self.counters.inc("read.zero_chunks")
            return b"\x00" * cs
        attempts = self._integrity_attempts()
        for round_ in range(attempts):
            lat = 0.0
            ct = None
            if self.l1 is not None:
                ct = self.l1.get(ref.name)
                lat += L1_PROBE_S
                if ct is not None:
                    self.counters.inc("read.l1_hits")
            if ct is None:
                ct, fetch_lat = self._fetch_cipher(ref)
                lat += fetch_lat
            try:
                plain = convergent.decrypt_chunk(ct, ref.key, ref.sha256)
            except convergent.IntegrityError:
                self._invalidate_name(ref.name)
                if round_ == attempts - 1:
                    raise
                self.counters.inc("retry.integrity_refetches")
                continue
            self.read_lat.record(lat)
            return plain

    # ------------------------------------------------- stage F: fetch I/O
    @_pinned
    def fetch_ciphertexts(self, indices,
                          parallelism: int = DEFAULT_PARALLELISM,
                          sink: BoundedQueue | None = None,
                          l2_hedge: bool | None = None) -> FetchedBatch:
        """Fetch-I/O-only stage: pull every distinct chunk name of
        `indices` into memory as CIPHERTEXT, nothing decrypted.

        Staged push through the tiers: L1 probed serially (a hit costs
        ~2us); misses claim single-flight leadership; led names go
        through one batched L2 fetch (stripe requests threaded per node
        inside the cache) and the rest through a `parallelism`-wide
        origin pool bounded by `self.concurrency`. Names led by another
        thread (stampede) are waited on last, so their fetch overlaps
        this call's own I/O.

        With a `sink` (streamed mode) every resolved ``(name,
        ciphertext)`` is additionally pushed into the bounded queue as
        it lands — L1 hits first, then L2 reconstructions and origin
        flights in arrival order — so a downstream ``decrypt_stream``
        decodes while this stage is still fetching. ``sink.put`` blocks
        when the queue is full (backpressure); see the module docstring
        for the full streaming contract.

        ``l2_hedge`` overrides the L2's hedged-GET default for this
        batch (None = inherit the cache's ``hedge_quantile`` setting);
        it is forwarded only when the L2 supports it."""
        fb = FetchedBatch(sink)
        for i in sorted(set(int(i) for i in indices)):
            ref = self._refs[i]
            if ref.name == ZERO_CHUNK:
                self.counters.inc("read.zero_chunks")
                fb.zero_indices.append(i)
            else:
                fb.by_name.setdefault(ref.name, []).append(i)
        miss = list(fb.by_name)
        if self.l1 is not None:
            probe, miss, hits = miss, [], []
            with span("repro.fetch.l1", chunks=len(probe)) as s:
                for name in probe:
                    ct = self.l1.get(name)
                    fb.l1_lat += L1_PROBE_S
                    if ct is None:
                        miss.append(name)
                        continue
                    fb.ciphertexts[name] = ct
                    fb.lats[name] = L1_PROBE_S
                    fb.l1_hits += 1
                    self.counters.inc("read.l1_hits")
                    self.read_lat.record(L1_PROBE_S)
                    hits.append((name, ct))
                s.set_metadata(bytes=sum(len(ct) for _, ct in hits))
            if fb.sink is not None:         # pushed outside the lookup span
                for hit in hits:
                    fb.sink.put(hit)
        if not miss:
            return fb
        lead, follow = [], {}
        with self._flight_lock:
            for name in miss:
                flight = self._flights.get((self.root, name))
                if flight is None:
                    flight = _Flight()
                    self._flights[(self.root, name)] = flight
                    lead.append((name, flight))
                else:
                    follow[name] = flight
        if lead:
            self._fetch_leaders(lead, parallelism, fb, l2_hedge=l2_hedge)
        for name, flight in follow.items():
            flight.event.wait()
            self.counters.inc("read.singleflight_dedup")
            if flight.error is not None:
                raise flight.error
            fb.ciphertexts[name] = flight.ciphertext
            fb.lats[name] = flight.sim_lat
            self.read_lat.record(flight.sim_lat)
            if fb.sink is not None:
                fb.sink.put((name, flight.ciphertext))
        return fb

    def _resolve_flight(self, name: str, flight: _Flight, ct: bytes,
                        lat: float, fb: FetchedBatch, push: bool = True):
        flight.ciphertext = ct
        flight.sim_lat = lat
        with self._flight_lock:
            self._flights.pop((self.root, name), None)
        flight.event.set()
        fb.ciphertexts[name] = ct
        fb.lats[name] = lat
        self.read_lat.record(lat)
        # push AFTER event.set(): a flight's own waiters never wait on
        # sink backpressure. Callers that resolve several names per wave
        # pass push=False and push after the whole wave resolves.
        if push and fb.sink is not None:
            fb.sink.put((name, ct))

    def _poison_flight(self, name: str, flight: _Flight, error: Exception):
        flight.error = error
        with self._flight_lock:
            self._flights.pop((self.root, name), None)
        flight.event.set()
        if self.peer is not None:
            # release any mesh lease we hold for this name: a joiner is
            # promoted to leader instead of the whole provisioning tree
            # stranding on our failure (no-op when another worker leads)
            self.peer.abandon(name)

    def _fetch_leaders(self, lead: list, parallelism: int, fb: FetchedBatch,
                       l2_hedge: bool | None = None):
        """Push the names this call leads through the tier stages as
        batches: L1 double-check -> peer probe -> one batched L2 fetch
        -> parallel origin pool. Each name's flight resolves the moment
        its ciphertext lands, so stampeding waiters never wait on the
        whole batch.

        The peer probe is non-blocking for in-flight mesh names: direct
        holder hits resolve inline, names another WORKER is already
        provisioning are joined on peer pool threads (futures), and
        only peer-led misses continue to L2/origin now. Joined futures
        are drained AFTER this call's own fall-through — two workers
        each leading a chunk the other joined must both keep making
        progress — and joins that come back empty (promoted to leader,
        peer death, deadline) take a second fall-through pass."""
        unresolved = dict(lead)
        try:
            pending: list[str] = []
            for name, flight in lead:
                ct = None
                # leader double-check: a previous flight for this name may
                # have backfilled L1 after this caller's probe missed
                if self.l1 is not None:
                    peek = getattr(self.l1, "peek", self.l1.get)
                    ct = peek(name)
                if ct is not None:
                    self._resolve_flight(name, unresolved.pop(name), ct,
                                         L1_PROBE_S, fb)
                else:
                    pending.append(name)
            peer_futs: dict = {}
            if pending and self.peer is not None:
                def peer_ready(name, lat, ct):
                    # runs inline for direct hits, on a peer pool thread
                    # for joined flights — pop defensively: the error
                    # path may have already poisoned this name
                    flight = unresolved.pop(name, None)
                    if flight is None:
                        return
                    self.counters.inc("read.peer_hits")
                    if self.l1 is not None:
                        self.l1.put(name, ct)
                    self._resolve_flight(name, flight, ct, lat, fb)
                with span("repro.fetch.peer", chunks=len(pending)):
                    pending, peer_futs = self.peer.probe_chunks(
                        pending, self.m.chunk_size, peer_ready)
            if pending:
                self._fall_through(pending, parallelism, fb, unresolved,
                                   l2_hedge)
            if peer_futs:
                retry = [name for name, fut in peer_futs.items()
                         if fut.result()[1] is None and name in unresolved]
                if retry:
                    self.counters.add("read.peer_fallthroughs", len(retry))
                    self._fall_through(retry, parallelism, fb, unresolved,
                                       l2_hedge)
        except BaseException as e:          # propagate to waiters too;
            # BaseException: a KeyboardInterrupt here must still resolve
            # every claimed flight or stampeding waiters hang forever
            # (the serial path gets this from its try/finally)
            for name in list(unresolved):
                flight = unresolved.pop(name, None)
                if flight is None:
                    continue        # a peer pool thread resolved it
                self._poison_flight(name, flight, e)
            raise

    def _fall_through(self, pending: list, parallelism: int,
                      fb: FetchedBatch, unresolved: dict,
                      l2_hedge: bool | None = None):
        """Lower-tier stages for `pending` led names: one batched L2
        fetch, then the parallel origin pool. Every acquired ciphertext
        is published to the peer mesh (resolving any provisioning
        flight this worker leads)."""
        l2_lat: dict[str, float] = {}
        if pending and self.l2 is not None:
            cs = self.m.chunk_size
            streamed_hits: set[str] = set()
            l2_kw = {}
            if self._l2_hedges and l2_hedge is not None:
                l2_kw["hedge"] = l2_hedge
            if self._l2_streams and fb.sink is not None:
                # streamed mode: each chunk resolves (and feeds the
                # sink) the moment its k-th stripe reconstructs,
                # instead of after the whole L2 wave returns
                def on_ready(name, lat, ct):
                    streamed_hits.add(name)
                    if self.l1 is not None:
                        self.l1.put(name, ct)
                    if self.peer is not None:
                        self.peer.put_chunk(name, ct, source="l2")
                    self._resolve_flight(name, unresolved.pop(name),
                                         ct, lat, fb)
                l2_kw["on_ready"] = on_ready
            with span("repro.fetch.l2", chunks=len(pending)):
                if hasattr(self.l2, "get_chunks"):
                    res = self.l2.get_chunks(pending, cs, **l2_kw)
                else:
                    res = {n: self.l2.get_chunk(n, cs) for n in pending}
            still = []
            for name in pending:
                if name in streamed_hits:
                    continue
                lat, ct = res[name]
                if ct is not None:
                    if self.l1 is not None:
                        self.l1.put(name, ct)
                    if self.peer is not None:
                        self.peer.put_chunk(name, ct, source="l2")
                    self._resolve_flight(name, unresolved.pop(name),
                                         ct, lat, fb)
                else:
                    l2_lat[name] = lat
                    still.append(name)
            pending = still
        if pending:
            self._origin_stage(pending, parallelism, l2_lat,
                               unresolved, fb)

    def _origin_stage(self, pending: list, parallelism: int, l2_lat: dict,
                      unresolved: dict, fb: FetchedBatch):
        """Parallel origin fetch of `pending` names. Errors stay
        per-name: a failed fetch poisons only ITS flight (exactly like
        the serial ``_fetch_cipher``), in-flight siblings still resolve
        for their waiters, and only never-started names inherit the
        first error. Raises the first error after the stage drains."""
        @bind_request                   # runs on the fetch pool's threads
        def fetch_origin(name: str):
            ct = self._origin_get(name)
            self.counters.inc("read.origin_fetches")
            if self.l2 is not None:
                self.l2.put_chunk(name, ct)
            if self.l1 is not None:
                self.l1.put(name, ct)
            if self.peer is not None:
                self.peer.put_chunk(name, ct, source="origin")
            return ct, l2_lat.get(name, 0.0) + ORIGIN_LAT_S

        first_err = None
        workers = max(1, min(int(parallelism), len(pending)))
        name_iter = iter(pending)
        if workers == 1:
            for name in name_iter:
                try:
                    ct, lat = fetch_origin(name)
                except BaseException as e:
                    self._poison_flight(name, unresolved.pop(name), e)
                    first_err = e
                    break
                self._resolve_flight(name, unresolved.pop(name), ct, lat, fb)
        else:
            # bounded submission: at most `workers` tasks in flight. The
            # pool may be wider than this call's parallelism (it is
            # shared across batches); submitting everything and gating
            # with a semaphore would park surplus worker threads on the
            # gate and starve concurrent batches.
            pool = self._fetch_pool.get(workers)
            fut_name = {pool.submit(fetch_origin, n): n
                        for n in itertools.islice(name_iter, workers)}
            while fut_name:
                done, _ = wait(fut_name, return_when=FIRST_COMPLETED)
                pushes = []
                for fut in done:
                    name = fut_name.pop(fut)
                    try:
                        ct, lat = fut.result()
                    except BaseException as e:
                        self._poison_flight(name, unresolved.pop(name), e)
                        if first_err is None:
                            first_err = e     # stop submitting new names
                        continue
                    # resolve the whole wave (and submit replacements)
                    # BEFORE any sink push: a backpressure stall must
                    # not delay flights whose bytes already landed
                    self._resolve_flight(name, unresolved.pop(name),
                                         ct, lat, fb, push=False)
                    pushes.append((name, ct))
                    if first_err is None:
                        nxt = next(name_iter, None)
                        if nxt is not None:
                            fut_name[pool.submit(fetch_origin, nxt)] = nxt
                if fb.sink is not None:
                    for name, ct in pushes:
                        fb.sink.put((name, ct))
        if first_err is not None:
            for name in name_iter:            # never-started names
                self._poison_flight(name, unresolved.pop(name), first_err)
            raise first_err

    # ------------------------------------------------- stage F + stage D
    def _invalidate_name(self, name: str):
        """Evict one tamper-flagged chunk name from every cache tier:
        the L1 entry, the L2 stripes, AND the peer mesh (directory entry
        plus every holder's serving copy — so later joiners don't
        re-fetch the poisoned copy peer-to-peer)."""
        for tier in (self.l1, self.l2, self.peer):
            inv = getattr(tier, "invalidate", None) if tier is not None \
                else None
            if inv is not None:
                inv(name)

    def _invalidate_bad(self, err: convergent.IntegrityError):
        """Evict tamper-flagged chunk names from every cache tier (L1
        entry, L2 stripes, peer directory + holder copies) so a retry
        refetches from origin instead of replaying the bad ciphertext."""
        for name in err.bad_positions:
            if isinstance(name, str):
                self._invalidate_name(name)

    @_pinned
    def fetch_chunks(self, indices, parallelism: int = DEFAULT_PARALLELISM,
                     materialize: bool = True, streamed: bool = False,
                     queue_depth: int = DEFAULT_QUEUE_DEPTH,
                     decoder: BatchDecoder | None = None,
                     l2_hedge: bool | None = None) -> dict:
        """Batched read: {index: plaintext} for a deduplicated chunk set
        — ``fetch_ciphertexts`` (stage F) then one batched decode
        (stage D) on the caller thread via ``decoder`` (default
        ``self.decoder``; a ``ReadPolicy`` with decode overrides passes
        its own).

        With ``streamed=True`` the two stages run concurrently instead
        of back-to-back: stage F on a producer thread feeding a
        ``queue_depth``-bounded queue, stage D consuming tiles as they
        arrive (``fetch_chunks_streamed``). Byte-identical to the staged
        mode, which stays as the selectable oracle.

        With ``materialize=False`` (the prefetch path) the decode stage
        is skipped entirely — tiers are warmed and the returned dict is
        empty. ``streamed=True`` there selects the streaming fetch
        producer (per-chunk L2 stripe resolution, bounded hand-off)
        with a discarding consumer, so prefetch exercises the same
        fetch path the streamed restore will take.
        """
        if streamed and materialize:
            return self.fetch_chunks_streamed(indices, parallelism,
                                              queue_depth, decoder, l2_hedge)
        if streamed:
            return self._prefetch_streamed(indices, parallelism, queue_depth,
                                           l2_hedge)
        attempts = self._integrity_attempts()
        for round_ in range(attempts):
            try:
                return self._fetch_chunks_staged(indices, parallelism,
                                                 materialize, decoder,
                                                 l2_hedge)
            except convergent.IntegrityError:
                # bad names were evicted from every tier by the staged
                # body; a fresh round refetches only them from origin
                # (the good names are warm L1 hits)
                if round_ == attempts - 1:
                    raise
                self.counters.inc("retry.integrity_refetches")

    def _fetch_chunks_staged(self, indices, parallelism: int,
                             materialize: bool,
                             decoder: BatchDecoder | None = None,
                             l2_hedge: bool | None = None) -> dict:
        dec = decoder if decoder is not None else self.decoder
        t0 = time.perf_counter()
        fb = self.fetch_ciphertexts(indices, parallelism, l2_hedge=l2_hedge)
        fetch_wall = time.perf_counter() - t0
        out: dict[int, bytes] = {}
        decode_wall = 0.0
        if materialize:
            if fb.zero_indices:
                zero = b"\x00" * self.m.chunk_size
                for i in fb.zero_indices:
                    out[i] = zero
            if fb.by_name:
                refs = [self._refs[idxs[0]] for idxs in fb.by_name.values()]
                try:
                    plains, decode_wall = dec.decrypt_batch_timed(
                        refs, fb.ciphertexts)
                except convergent.IntegrityError as e:
                    self._invalidate_bad(e)
                    raise
                for name, idxs in fb.by_name.items():
                    plain = plains[name]
                    for i in idxs:
                        out[i] = plain

        fetch_lats = [lat for name, lat in fb.lats.items()
                      if lat > L1_PROBE_S]
        sim_wall = fb.l1_lat + pipelined_latency(fetch_lats, parallelism)
        self.batch_lat.record(sim_wall)
        nchunks = len(fb.zero_indices) + sum(len(v) for v in fb.by_name.values())
        self.counters.add("read.batched_chunks", nchunks)
        self.last_batch = {
            "chunks": nchunks,
            "names": len(fb.by_name),
            "fetched": len(fb.by_name) - fb.l1_hits,
            "parallelism": int(parallelism),
            "sim_serial_s": fb.l1_lat + sum(fetch_lats),
            "sim_pipelined_s": sim_wall,
            "wall_s": time.perf_counter() - t0,
            "fetch_wall_s": fetch_wall,
            "fetch_busy_s": fetch_wall,     # staged: fetch never waits
            "fetch_blocked_s": 0.0,
            "decode_starved_s": 0.0,
            "decode_wall_s": decode_wall,
            "decode_backend": dec.backend,
            "streamed": False,
        }
        return out

    def _prefetch_streamed(self, indices, parallelism: int,
                           queue_depth: int,
                           l2_hedge: bool | None = None) -> dict:
        """Non-materializing streamed prefetch: the streaming fetch
        producer warms every tier (per-chunk L2 stripe resolution via
        ``get_chunks(on_ready=...)``, bounded hand-off backpressure)
        while this thread discards the ciphertext stream — no decode, no
        accumulation of plaintexts. Returns {} like the staged prefetch."""
        t0 = time.perf_counter()
        q = BoundedQueue(queue_depth)
        holder: dict = {}

        def produce():
            try:
                holder["fb"] = self.fetch_ciphertexts(indices, parallelism,
                                                      sink=q,
                                                      l2_hedge=l2_hedge)
            except BaseException as e:
                holder["err"] = e
                q.poison(e)
            else:
                q.close()

        prod = threading.Thread(target=bind_request(produce),
                                name="prefetch-fetch", daemon=True)
        prod.start()
        try:
            for _ in q:         # drain: tiers warm, nothing materializes
                pass
        except BaseException:
            q.cancel()          # producer puts now drop; it still warms tiers
            prod.join()
            raise
        prod.join()
        fb: FetchedBatch = holder["fb"]
        nchunks = len(fb.zero_indices) + sum(len(v) for v in fb.by_name.values())
        self.counters.add("read.batched_chunks", nchunks)
        self.counters.max_update("stream.queue_hwm", q.high_water)
        self.last_batch = {
            "chunks": nchunks,
            "names": len(fb.by_name),
            "fetched": len(fb.by_name) - fb.l1_hits,
            "parallelism": int(parallelism),
            "wall_s": time.perf_counter() - t0,
            "streamed": True,
            "materialized": False,
            "queue_hwm": q.high_water,
            "queue_depth": q.maxsize,
        }
        return {}

    @_pinned
    def fetch_chunks_streamed(self, indices,
                              parallelism: int = DEFAULT_PARALLELISM,
                              queue_depth: int = DEFAULT_QUEUE_DEPTH,
                              decoder: BatchDecoder | None = None,
                              l2_hedge: bool | None = None) -> dict:
        """Streaming read: stage F runs on a producer thread pushing
        resolved ciphertexts into a ``queue_depth``-bounded queue; stage
        D (``decoder.decrypt_stream``) consumes on this thread, decoding
        tiles while fetch is still in flight. {index: plaintext},
        byte-identical to the staged mode.

        An ``IntegrityError`` mid-stream evicts the bad names from
        every tier and — with a retry policy wired — restarts the read
        (bounded rounds): the restart's good names are warm L1 hits,
        only the evicted bad names travel to origin again.

        ``last_batch`` additionally reports ``fetch_busy_s`` (the fetch
        wall less ``fetch_blocked_s``, its time blocked on the full
        queue), ``decode_starved_s`` (the consumer's time waiting on an
        empty queue), ``overlap_s`` (decode work that ran while fetch
        was busy), ``overlap_fraction``, and the queue's high-water
        mark; the same figures feed the ``decode.overlap_s`` /
        ``stream.queue_hwm`` counters."""
        attempts = self._integrity_attempts()
        for round_ in range(attempts):
            try:
                return self._fetch_chunks_streamed_once(
                    indices, parallelism, queue_depth, decoder, l2_hedge)
            except convergent.IntegrityError:
                if round_ == attempts - 1:
                    raise
                self.counters.inc("retry.integrity_refetches")

    def _fetch_chunks_streamed_once(self, indices, parallelism: int,
                                    queue_depth: int,
                                    decoder: BatchDecoder | None = None,
                                    l2_hedge: bool | None = None) -> dict:
        dec = decoder if decoder is not None else self.decoder
        t0 = time.perf_counter()
        refs_by_name: dict[str, object] = {}
        for i in set(int(i) for i in indices):
            ref = self._refs[i]
            if ref.name != ZERO_CHUNK and ref.name not in refs_by_name:
                refs_by_name[ref.name] = ref
        q = BoundedQueue(queue_depth)
        holder: dict = {}

        def produce():
            ft = time.perf_counter()
            try:
                holder["fb"] = self.fetch_ciphertexts(indices, parallelism,
                                                      sink=q,
                                                      l2_hedge=l2_hedge)
            except BaseException as e:
                holder["err"] = e
                q.poison(e)
            else:
                q.close()
            finally:
                holder["fetch_wall"] = time.perf_counter() - ft

        prod = threading.Thread(target=bind_request(produce),
                                name="stream-fetch", daemon=True)
        prod.start()
        try:
            plains, dstats = dec.decrypt_stream(q, refs_by_name)
        except BaseException as e:
            q.cancel()          # producer puts now drop; it still warms tiers
            prod.join()
            if isinstance(e, convergent.IntegrityError):
                self._invalidate_bad(e)
            raise
        prod.join()
        fb: FetchedBatch = holder["fb"]
        out: dict[int, bytes] = {}
        if fb.zero_indices:
            zero = b"\x00" * self.m.chunk_size
            for i in fb.zero_indices:
                out[i] = zero
        for name, idxs in fb.by_name.items():
            plain = plains[name]
            for i in idxs:
                out[i] = plain
        total = time.perf_counter() - t0
        fetch_wall = holder["fetch_wall"]
        busy = dstats["busy_s"]
        # the producer's wall includes the time it sat on a full queue
        # (decode behind); what is left is fetch at work
        blocked = q.put_wait_s
        fetch_busy = max(0.0, fetch_wall - blocked)
        # overlap identity: at every moment fetch works, or decode does,
        # or both (a blocked fetch waits on decode work, a starved decode
        # on fetch work), so the work of both less the wall ran together
        # — the streaming win. Clamped to the shorter of the two.
        overlap = max(0.0, min(fetch_busy + busy - total, fetch_busy, busy))
        fetch_lats = [lat for lat in fb.lats.values() if lat > L1_PROBE_S]
        sim_wall = fb.l1_lat + pipelined_latency(fetch_lats, parallelism)
        self.batch_lat.record(sim_wall)
        nchunks = len(fb.zero_indices) + sum(len(v) for v in fb.by_name.values())
        self.counters.add("read.batched_chunks", nchunks)
        self.counters.add("decode.overlap_s", overlap)
        self.counters.max_update("stream.queue_hwm", q.high_water)
        self.last_batch = {
            "chunks": nchunks,
            "names": len(fb.by_name),
            "fetched": len(fb.by_name) - fb.l1_hits,
            "parallelism": int(parallelism),
            "sim_serial_s": fb.l1_lat + sum(fetch_lats),
            "sim_pipelined_s": sim_wall,
            "wall_s": total,
            "fetch_wall_s": fetch_wall,
            "fetch_busy_s": fetch_busy,
            "fetch_blocked_s": blocked,
            "decode_starved_s": q.get_wait_s,
            "decode_wall_s": busy,
            "decode_backend": dec.backend,
            "streamed": True,
            "overlap_s": overlap,
            "overlap_fraction": overlap / busy if busy > 0 else 0.0,
            "queue_hwm": q.high_water,
            "queue_depth": q.maxsize,
            "decode_tiles": dstats["tiles"],
            "tiles_overlapped": dstats["tiles_overlapped"],
            "eager_flushes": dstats.get("eager_flushes", 0),
            "eager_holds": dstats.get("eager_holds", 0),
        }
        return out

    # -------------------------------------------------------------- bytes
    def _assemble(self, offset: int, length: int, chunks: dict) -> bytes:
        """Bytes of [offset, offset+length) from prefetched `chunks`
        (falls back to a serial fetch for anything missing)."""
        cs = self.m.chunk_size
        out = bytearray()
        pos = offset
        end = offset + length
        while pos < end:
            ci = pos // cs
            within = pos % cs
            take = min(cs - within, end - pos)
            chunk = chunks.get(ci)
            if chunk is None:
                chunk = self.fetch_chunk(ci)
            out += chunk[within:within + take]
            pos += take
        return bytes(out)

    @_pinned
    def read(self, offset: int, length: int) -> bytes:
        """Serial read: chunks fetched one at a time, in order."""
        return self._assemble(offset, length, {})

    @_pinned
    def read_many(self, ranges, parallelism: int = DEFAULT_PARALLELISM,
                  streamed: bool = False,
                  queue_depth: int = DEFAULT_QUEUE_DEPTH,
                  decoder: BatchDecoder | None = None,
                  l2_hedge: bool | None = None) -> list:
        """Batched read: one `fetch_chunks` over the union chunk set of
        all (offset, length) `ranges` (overlaps deduplicated), then each
        range is assembled from the in-memory chunks. Byte-identical to
        calling `read` per range. ``streamed=True`` overlaps decode with
        fetch (the default restore path via the service layer);
        ``decoder`` overrides the decode backend/tiling per call."""
        ranges = list(ranges)
        idxs = ranges_to_chunks(ranges, self.m.chunk_size)
        chunks = self.fetch_chunks(idxs, parallelism, streamed=streamed,
                                   queue_depth=queue_depth, decoder=decoder,
                                   l2_hedge=l2_hedge)
        with span("repro.restore.assemble"):
            return [self._assemble(off, ln, chunks) for off, ln in ranges]


class CowBlockDevice:
    """Read/write device: immutable base (TieredReader) + encrypted overlay.

    The bitmap is at PAGE granularity; sub-page writes trigger
    read-modify-write exactly as described in §2.1. Reads batch all
    clean (non-overlay) spans into one ``read_many`` call.
    """

    def __init__(self, reader: TieredReader, overlay_key: bytes | None = None):
        self.reader = reader
        self.size = reader.m.image_size
        self.npages = (self.size + PAGE - 1) // PAGE
        self.bitmap = np.zeros(self.npages, dtype=bool)
        self._overlay: dict[int, bytes] = {}      # page -> ciphertext
        self.key = overlay_key or b"\x01" * 32

    # overlay pages are encrypted at rest (worker-local encrypted storage)
    def _store_page(self, page: int, plain: bytes):
        iv = page.to_bytes(16, "big")
        self._overlay[page] = aes.ctr_encrypt(plain, self.key, iv16=iv)
        self.bitmap[page] = True

    def _load_page(self, page: int) -> bytes:
        iv = page.to_bytes(16, "big")
        return aes.ctr_decrypt(self._overlay[page], self.key, iv16=iv)

    def _clean_spans(self, offset: int, end: int) -> list:
        """Maximal contiguous non-overlay byte runs within [offset, end)."""
        spans: list[list[int]] = []
        pos = offset
        while pos < end:
            page = pos // PAGE
            take = min(PAGE - pos % PAGE, end - pos)
            dirty = page < self.npages and bool(self.bitmap[page])
            if not dirty:
                if spans and spans[-1][0] + spans[-1][1] == pos:
                    spans[-1][1] += take
                else:
                    spans.append([pos, take])
            pos += take
        return [(o, ln) for o, ln in spans]

    def read(self, offset: int, length: int,
             parallelism: int = DEFAULT_PARALLELISM) -> bytes:
        end = offset + length
        spans = self._clean_spans(offset, end)
        fetched: dict[int, bytes] = {}
        if spans:
            # clamp to the image; anything past it reads as zeros
            capped = [(o, max(0, min(ln, self.size - o))) for o, ln in spans]
            bufs = self.reader.read_many(
                [(o, ln) for o, ln in capped if ln > 0], parallelism)
            it = iter(bufs)
            for (o, ln), (_, cln) in zip(spans, capped):
                data = next(it) if cln > 0 else b""
                fetched[o] = data.ljust(ln, b"\x00")
        out = bytearray()
        pos = offset
        while pos < end:
            page = pos // PAGE
            within = pos % PAGE
            take = min(PAGE - within, end - pos)
            if page < self.npages and self.bitmap[page]:
                out += self._load_page(page)[within:within + take]
                pos += take
            else:
                # consume the whole clean span this position starts
                span = fetched[pos]
                out += span
                pos += len(span)
        return bytes(out)

    def _base_pages_batched(self, pages: list,
                            parallelism: int = DEFAULT_PARALLELISM) -> dict:
        """{page: PAGE bytes} of base-image content for `pages`, all
        fetched through ONE ``read_many`` batch (pages past the image
        end read as zeros)."""
        capped = [(p, min(PAGE, self.size - p * PAGE)) for p in pages]
        ranges = [(p * PAGE, ln) for p, ln in capped if ln > 0]
        bufs = iter(self.reader.read_many(ranges, parallelism)) if ranges \
            else iter(())
        return {p: (next(bufs).ljust(PAGE, b"\x00") if ln > 0
                    else b"\x00" * PAGE)
                for p, ln in capped}

    def write(self, offset: int, data: bytes,
              parallelism: int = DEFAULT_PARALLELISM):
        pos, end = offset, offset + len(data)
        # a large unaligned write faults at most its two edge pages plus
        # any interior page it only partially covers (none, by
        # construction); batch every base-page fault through one
        # read_many instead of serial read-modify-write per page
        need_base = []
        while pos < end:
            page = pos // PAGE
            within = pos % PAGE
            take = min(PAGE - within, end - pos)
            partial = not (within == 0 and take == PAGE)
            if partial and not (page < self.npages and self.bitmap[page]):
                need_base.append(page)
            pos += take
        base_pages = self._base_pages_batched(need_base, parallelism) \
            if need_base else {}
        pos, src = offset, 0
        while pos < end:
            page = pos // PAGE
            within = pos % PAGE
            take = min(PAGE - within, end - pos)
            if within == 0 and take == PAGE:
                pagebuf = data[src:src + PAGE]
            else:
                # read-modify-write (paper: page-granularity bitmap)
                base = self._load_page(page) if self.bitmap[page] \
                    else base_pages[page]
                pagebuf = base[:within] + data[src:src + take] + base[within + take:]
            self._store_page(page, pagebuf)
            pos += take
            src += take

    @property
    def dirty_bytes(self) -> int:
        return int(self.bitmap.sum()) * PAGE
