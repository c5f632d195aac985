"""ImageService: the multi-tenant read-path client API (paper Fig 4's
local agent, process-wide).

The paper's system serves millions of unique workloads over *shared*
cache/limiter infrastructure: a worker asks its local agent for an
image; it does not hand-assemble L1/L2/limiters/decoders per call. This
module is that agent:

* ``ServiceConfig`` — one dataclass holding every process-wide knob
  (cache tier sizes, admission control, fetch concurrency, decode
  backend, the default ``ReadPolicy``).
* ``ImageService`` — constructed ONCE per process from a config (or
  from pre-built tier objects). Owns the shared L1, the erasure-coded
  L2, the admission ``RejectingLimiter`` (paper §4.2: reject, don't
  queue), the origin-fetch ``BlockingLimiter``, the ``BatchDecoder``
  pool, and a telemetry scope per tenant. Because every image opened
  through one service shares the L1 by content-addressed chunk name,
  cross-tenant dedup (Fig 5) happens — and is observable through the
  per-tenant scoped counters (``service.tenant_counters(t)``).
* ``ImageHandle`` — a session over one opened image
  (``service.open(manifest_blob, tenant_key, root=...)``). Its read
  methods (``restore_tree`` / ``restore_share`` / ``restore_shards`` /
  ``tensor_shard`` / ``prefetch`` / ``tensor``) take a single optional ``ReadPolicy``
  instead of the scattered ``batched=/streamed=/parallelism=`` keyword
  tuple the pre-redesign API threaded through every layer.
* ``ReadPolicy`` — how one read should run: pipeline ``mode``
  (``streamed`` | ``staged`` | ``serial``), fetch ``parallelism``,
  decode tile size / backend overrides, the streamed hand-off queue
  depth, and the idle-queue opportunistic ``eager_flush``.

Handles of the SAME (image, root, tenant) share one ``TieredReader``,
so concurrent cold-starts of one image are single-flighted against each
other — M replicas of a function cost one origin fetch per unique
chunk, not M (the paper's headline scale property).

``ImageReader`` in ``core.loader`` remains as a thin deprecation shim
that builds a private single-image service, so the pre-redesign
byte-identity oracles keep passing unmodified.
"""
from __future__ import annotations

import contextlib
import hashlib
import itertools
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

from repro.core.blockdev import (
    DEFAULT_PARALLELISM,
    DEFAULT_QUEUE_DEPTH,
    FlightTable,
    TieredReader,
)
from repro.core.concurrency import BlockingLimiter, RejectingLimiter
from repro.core.decode import (
    DEFAULT_EAGER_MIN_BYTES,
    DEFAULT_MAX_BATCH_BYTES,
    BatchDecoder,
    known_backend_names,
    resolve_backend_name,
)
from repro.core.layout import (
    CHUNK_SIZE,
    ImageLayout,
    ranges_to_chunks,
    read_tensor,
    shard_byte_ranges,
)
from repro.core.manifest import ZERO_CHUNK, open_manifest
from repro.core.publish import PublishPipeline
from repro.core.retry import CircuitBreaker, RetryPolicy
from repro.core.telemetry import COUNTERS, ScopedCounters, span

_MODES = ("streamed", "staged", "serial")


class ColdStartRejected(RuntimeError):
    """Admission control turned the cold start away (paper §4.2: excess
    starts are rejected, not queued, to bound the demand amplification
    of an empty cache). ``retry_after_s`` > 0 means the brownout ladder
    shed this start — the origin breaker is open — and tells the caller
    when the breaker will next accept probes."""

    def __init__(self, msg: str, retry_after_s: float = 0.0):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


@dataclass(frozen=True)
class ReadPolicy:
    """How ONE read call should run. Replaces the positional knob tuple
    (``batched=/streamed=/parallelism=/decoder=``) the pre-redesign API
    threaded through every layer.

    ``mode``:
      * ``"streamed"`` (default) — fetch streams resolved ciphertexts
        into a bounded queue; decode tiles run while fetch is in flight.
      * ``"staged"``   — two-phase fetch-then-decode (the byte-identity
        oracle for streaming).
      * ``"serial"``   — per-chunk fetch + per-chunk decrypt (the
        reference oracle).

    ``parallelism`` — width of the origin fetch pipeline.
    ``max_batch_bytes`` / ``decode_backend`` — decode-stage overrides
    (``None`` = the service's configured default, which is itself
    ``"auto"`` = per-backend autotuned tile unless the config pins an
    int; an explicit int here always wins over the autotuner).
    ``decode_backend`` names a registered decode backend
    (``core.decode`` registry: ``python``/``xla``/``bitsliced``/
    ``bitsliced-fused``, legacy aliases ``numpy``/``jax``/``fused``,
    the ``serial`` oracle, or ``auto`` to probe the platform).
    ``queue_depth`` — streamed hand-off queue bound (backpressure).
    ``eager_flush`` — idle-queue opportunistic flush: decode the partial
    tile whenever the consumer would otherwise block on the hand-off
    queue (shrinks the decode tail on small/slow-arriving batches at
    some tile-efficiency cost). Tri-state: ``None`` inherits the
    service default, ``True``/``False`` override it either way.
    ``eager_min_bytes`` — minimum partial-tile bytes before an eager
    flush may fire (``None`` = service default): holds tile efficiency
    at scale by refusing to shred slivers into the pool.
    ``l2_hedge`` — hedged stripe GETs in the L2 for this read.
    Tri-state like ``eager_flush``: ``None`` inherits the cache's
    ``hedge_quantile`` default, ``True``/``False`` force it per read
    (forwarded only when the L2 supports hedging).
    """

    mode: str = "streamed"
    parallelism: int = DEFAULT_PARALLELISM
    max_batch_bytes: int | None = None
    decode_backend: str | None = None
    queue_depth: int = DEFAULT_QUEUE_DEPTH
    eager_flush: bool | None = None
    eager_min_bytes: int | None = None
    l2_hedge: bool | None = None

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(f"ReadPolicy.mode must be one of {_MODES}, "
                             f"got {self.mode!r}")
        if self.decode_backend is not None and \
                self.decode_backend not in known_backend_names():
            raise ValueError(f"unknown decode_backend "
                             f"{self.decode_backend!r}; known: "
                             f"{known_backend_names()}")
        if self.parallelism < 1:
            raise ValueError("parallelism must be >= 1")

    # legacy keyword translation (the ImageReader shim)
    @classmethod
    def from_legacy(cls, *, batched: bool = True, streamed: bool = True,
                    parallelism: int = DEFAULT_PARALLELISM) -> "ReadPolicy":
        mode = "serial" if not batched else ("streamed" if streamed
                                             else "staged")
        return cls(mode=mode, parallelism=parallelism)

    @property
    def streamed(self) -> bool:
        return self.mode == "streamed"


@dataclass
class ServiceConfig:
    """Process-wide read-path configuration: everything an
    ``ImageService`` owns, in one place, instead of a knob tuple
    threaded through every call site.

    Tier sizing (``l1_bytes=0`` / ``l2_nodes=0`` disables a tier),
    admission control (``max_coldstarts``; 0 = unlimited), origin fetch
    concurrency (``fetch_concurrency``; 0 = unbounded), the decode pool
    (backend / tile size / threads / eager-flush threshold), session
    caching (``session_cap`` / ``session_ttl_s`` bound the idle-handle
    and parsed-manifest caches a churning image population would
    otherwise grow forever), the simulated origin RTT for benchmarks,
    and the default ``ReadPolicy`` applied when a read passes none."""

    l1_bytes: int = 256 << 20
    l2_nodes: int = 0                   # 0 = no L2 tier
    l2_seed: int = 0
    l2_mem_bytes: int | None = None
    l2_flash_bytes: int | None = None
    max_coldstarts: int = 4             # admission control (§4.2)
    fetch_concurrency: int = 16         # 0 = unbounded origin reads
    decode_backend: str = "auto"        # platform probe (core.decode)
    decode_threads: int | None = None
    # "auto" = per-backend autotuned tile (decode.autotune_tile_bytes:
    # small timed sweep at first use, cached per process). Any explicit
    # int (here or per-read via ReadPolicy.max_batch_bytes) wins.
    max_batch_bytes: int | str = "auto"
    eager_min_bytes: int = DEFAULT_EAGER_MIN_BYTES
    session_cap: int = 64               # LRU session bound (0 = unbounded)
    session_ttl_s: float | None = None  # None = no idle expiry
    manifest_cap: int = 128             # LRU manifest bound (0 = unbounded)
    origin_delay_s: float = 0.0
    # L2 resilience knobs (only used when the service builds its own L2)
    l2_stripe_deadline_s: float | None = None   # None = cache default
    l2_hedge_quantile: float | None = None      # None = hedging off
    l2_infection_threshold: int = 0             # 0 = hot-key salting off
    l2_salt_count: int = 3                      # placement keys when salted
    # peer-tier knobs (used by build_peer_mesh — the mesh spans MANY
    # services, so a service never builds one itself; it receives a
    # per-worker PeerClient via ImageService(peer=...))
    peer_fanout: int = 4                # provisioning-tree arity
    peer_deadline_s: float = 2.0        # bounded wait on a joined flight
    peer_registration: str = "all"      # "all" | "origin" (see peer.py)
    # publish-side knobs (the write path: ``core.publish.PublishPipeline``
    # built lazily by ``ImageService.publish``)
    publish_backend: str | None = None  # None = decode_backend
    publish_tile_bytes: int | str | None = None  # None = backend default
    upload_parallelism: int = 8         # bounded-parallel PUTs per service
    publish_warm_l1: bool = True        # push fresh ciphertexts into L1/peer
    # sidecar file for the publish NameIndex (skip-encryption dedup
    # survives restarts); None = in-memory only
    publish_name_index_path: str | None = None
    # origin-tier resilience (core.retry / core.faults) — ALL off by
    # default: the no-knobs read/write path is byte-for-byte the old one
    retry_attempts: int = 0             # total origin attempts; 0/1 = off
    retry_base_s: float = 0.01          # backoff floor (decorrelated jitter)
    retry_cap_s: float = 0.5            # backoff ceiling
    retry_total_budget_s: float | None = None   # wall budget across attempts
    retry_attempt_timeout_s: float | None = None  # per-attempt deadline
    retry_integrity_refetches: int = 2  # evict+refetch rounds on bad bytes
    retry_seed: int | None = None       # pin the jitter stream (benchmarks)
    breaker_threshold: float | None = None  # error rate to open; None = off
    breaker_window: int = 64            # sliding error-rate window size
    breaker_min_samples: int = 10       # samples before the rate can trip
    breaker_cooldown_s: float = 1.0     # open -> half-open delay
    breaker_half_open_probes: int = 1   # concurrent probes while half-open
    breaker_shed_coldstarts: bool = True  # brownout: shed admissions too
    root: str | None = None             # default root for open()
    default_policy: ReadPolicy = field(default_factory=ReadPolicy)


_SVC_SEQ = itertools.count()        # unique telemetry names per service


class ImageService:
    """Process-wide read-path agent: shared store + cache tiers +
    limiters + decode pool, handing out per-image ``ImageHandle``
    sessions. Construct once, ``open()`` per image."""

    def __init__(self, store, config: ServiceConfig | None = None, *,
                 l1=None, l2=None, peer=None, fetch_limiter=None,
                 admission=None, counters=None, pins=None, refcounts=None):
        cfg = config if config is not None else ServiceConfig()
        self.config = cfg
        self.store = store
        if l1 is not None:
            self.l1 = l1
        elif cfg.l1_bytes > 0:
            from repro.core.cache.local import LocalCache
            # unique counter name: a process may hold several services
            # (benchmark configs, tests), and LocalCache keys its
            # hit/miss telemetry off the name — "l1" for all of them
            # would merge every service's hit_rate into one aggregate
            self.l1 = LocalCache(cfg.l1_bytes,
                                 name=f"svc{next(_SVC_SEQ)}.l1")
        else:
            self.l1 = None
        if l2 is not None:
            self.l2 = l2
        elif cfg.l2_nodes > 0:
            from repro.core.cache.distributed import DistributedCache
            kw = {}
            if cfg.l2_mem_bytes is not None:
                kw["mem_bytes"] = cfg.l2_mem_bytes
            if cfg.l2_flash_bytes is not None:
                kw["flash_bytes"] = cfg.l2_flash_bytes
            if cfg.l2_stripe_deadline_s is not None:
                kw["stripe_deadline_s"] = cfg.l2_stripe_deadline_s
            self.l2 = DistributedCache(
                num_nodes=cfg.l2_nodes, seed=cfg.l2_seed,
                hedge_quantile=cfg.l2_hedge_quantile,
                infection_threshold=cfg.l2_infection_threshold,
                salt_count=cfg.l2_salt_count, **kw)
        else:
            self.l2 = None
        # optional peer tier: this worker's PeerClient into a shared
        # PeerMesh (cache/peer.py), probed between L1 and L2 by every
        # reader this service builds. Injected, never self-built — a
        # mesh spans many workers' services (see build_peer_mesh).
        self.peer = peer
        if fetch_limiter is not None:
            self.fetch_limiter = fetch_limiter
        else:
            self.fetch_limiter = BlockingLimiter(cfg.fetch_concurrency) \
                if cfg.fetch_concurrency > 0 else None
        if admission is not None:
            self.admission = admission
        else:
            self.admission = RejectingLimiter(cfg.max_coldstarts) \
                if cfg.max_coldstarts > 0 else None
        self.counters = counters if counters is not None else COUNTERS
        # origin-tier resilience (defaults off): ONE retry policy and
        # ONE circuit breaker per service, shared by every reader it
        # builds and by the publish pipeline — the breaker's error-rate
        # view must span all of this process's origin traffic
        self.retry = RetryPolicy(
            attempts=cfg.retry_attempts, base_s=cfg.retry_base_s,
            cap_s=cfg.retry_cap_s,
            total_budget_s=cfg.retry_total_budget_s,
            attempt_timeout_s=cfg.retry_attempt_timeout_s,
            integrity_refetches=cfg.retry_integrity_refetches,
            seed=cfg.retry_seed) if cfg.retry_attempts > 1 else None
        self.breaker = CircuitBreaker(
            cfg.breaker_threshold, window=cfg.breaker_window,
            min_samples=cfg.breaker_min_samples,
            cooldown_s=cfg.breaker_cooldown_s,
            half_open_probes=cfg.breaker_half_open_probes,
            counters=self.counters) \
            if cfg.breaker_threshold is not None else None
        # ONE single-flight table across every reader this service hands
        # out: a chunk-name stampede from different images/tenants costs
        # one origin fetch process-wide (names are content addresses)
        self.flights = FlightTable()
        # GC integration (both optional): `pins` is a ``RootPinRegistry``
        # every reader pins during reads (generation roll cannot delete a
        # root mid-restore); `refcounts` is a ``RefcountIndex`` the
        # publish path maintains (wire the same objects into the
        # ``GenerationalGC``)
        self.pins = pins
        self.refcounts = refcounts
        self._publisher: PublishPipeline | None = None
        self._decoders: dict[tuple, BatchDecoder] = {}
        self._scopes: dict[str, ScopedCounters] = {}
        # LRU session/manifest caches (most-recently-used at the end);
        # values carry a last-use stamp for the TTL sweep
        self._sessions: OrderedDict[tuple, list] = OrderedDict()
        self._manifests: OrderedDict[tuple, list] = OrderedDict()
        self._closed = False
        self._lock = threading.Lock()

    # ------------------------------------------------------------ plumbing
    def decoder_for(self, policy: ReadPolicy) -> BatchDecoder:
        """The shared ``BatchDecoder`` matching `policy`'s decode knobs
        (one pool per distinct backend/tile/eager combination, cached —
        stampeding reads share pools instead of spawning them)."""
        cfg = self.config
        eager = policy.eager_flush if policy.eager_flush is not None \
            else bool(cfg.default_policy.eager_flush)
        backend = policy.decode_backend or cfg.decode_backend
        # the cache key uses the CANONICAL name: aliases ("numpy" /
        # "python") and the auto probe share one pool instead of
        # duplicating decoders; the decoder itself keeps the as-given
        # name for telemetry
        key = (resolve_backend_name(backend),
               policy.max_batch_bytes or cfg.max_batch_bytes,
               eager,
               policy.eager_min_bytes if policy.eager_min_bytes is not None
               else cfg.eager_min_bytes)
        with self._lock:
            dec = self._decoders.get(key)
            if dec is None:
                dec = BatchDecoder(backend, max_batch_bytes=key[1],
                                   threads=cfg.decode_threads,
                                   eager_flush=key[2],
                                   eager_min_bytes=key[3])
                # a closed service hands out UNCACHED decoders (reads
                # through live handles keep working, but nothing new is
                # pinned that close() can no longer drain)
                if not self._closed:
                    self._decoders[key] = dec
            return dec

    def tenant_counters(self, tenant: str) -> ScopedCounters:
        """The per-tenant telemetry scope: updates land in both the
        global counters and ``tenant.<t>::<name>`` (cross-tenant L1
        dedup shows up as tenant B's scoped ``read.l1_hits`` on chunks
        tenant A pulled in)."""
        with self._lock:
            sc = self._scopes.get(tenant)
            if sc is None:
                sc = self.counters.scope(f"tenant.{tenant}")
                self._scopes[tenant] = sc
            return sc

    # ---------------------------------------------- session cache plumbing
    def _cache_lookup(self, cache: OrderedDict, key, counter: str):
        """LRU+TTL probe (caller holds the lock): refresh and return the
        entry, or expire it (TTL, ticking `counter` like the insert-path
        sweep does) and return None."""
        entry = cache.get(key)
        if entry is None:
            return None
        now = time.monotonic()
        ttl = self.config.session_ttl_s
        if ttl is not None and now - entry[-1] > ttl:
            del cache[key]
            self.counters.inc(counter)
            return None
        entry[-1] = now
        cache.move_to_end(key)
        return entry

    def _cache_insert(self, cache: OrderedDict, key, values: tuple,
                      cap: int, counter: str):
        """setdefault-style insert (caller holds the lock) + the LRU/TTL
        sweep: idle entries past ``session_ttl_s`` expire, then the
        least-recently-used entries beyond `cap` evict. Returns the
        entry actually cached (a racing builder keeps the first one).
        On a service that closed mid-open, nothing is pinned — the entry
        is returned uncached so close() stays the last word."""
        now = time.monotonic()
        if self._closed:
            return list(values) + [now]
        entry = cache.get(key)
        if entry is None:
            entry = list(values) + [now]
            cache[key] = entry
        else:
            entry[-1] = now
        cache.move_to_end(key)
        ttl = self.config.session_ttl_s
        if ttl is not None:
            for k in [k for k, v in cache.items() if now - v[-1] > ttl]:
                del cache[k]
                self.counters.inc(counter)
        if cap > 0:                     # 0 = unbounded (knob convention)
            while len(cache) > cap:
                cache.popitem(last=False)
                self.counters.inc(counter)
        return entry

    def close(self):
        """Shut the service down: evict every cached session and parsed
        manifest, drain the shared decoder pools (in-flight tiles finish
        first), and clear the process-wide flight table. Reads through
        still-live handles keep working — a handle owns its reader —
        but new ``open()`` calls raise ``RuntimeError``. Idempotent."""
        with self._lock:
            self._closed = True
            decoders = list(self._decoders.values())
            self._decoders.clear()
            self._sessions.clear()
            self._manifests.clear()
            publisher, self._publisher = self._publisher, None
        for dec in decoders:
            dec.close()
        if publisher is not None:
            publisher.close()
        with self.flights.lock:
            self.flights.flights.clear()

    @contextlib.contextmanager
    def admission_slot(self):
        """Hold one admission-control slot; raises ``ColdStartRejected``
        when the service is at ``max_coldstarts`` in-flight (§4.2:
        reject, don't queue) or — brownout ladder, first rung — when the
        origin circuit breaker is open: a cold start that would only
        pile retries onto a failing origin is shed up front with a
        ``retry_after_s`` hint instead of admitted to fail slowly.
        Half-open probing is left to in-flight reads (they hold no
        admission slot), so recovery does not depend on new arrivals."""
        br = self.breaker
        lim = self.admission
        if (br is not None and self.config.breaker_shed_coldstarts
                and br.state == "open"):
            ra = br.retry_after_s()
            self.counters.inc("serve.brownout_shed")
            if lim is not None:
                lim.shed()
            raise ColdStartRejected(
                "cold-start shed: origin breaker open "
                f"(retry after {ra:.2f}s)", retry_after_s=ra)
        if lim is None:
            yield
            return
        if not lim.try_acquire():
            self.counters.inc("serve.coldstart_rejected")
            raise ColdStartRejected("cold-start rejected: concurrency limit")
        try:
            yield
        finally:
            lim.release()

    # -------------------------------------------------------------- open
    def open(self, manifest_blob: bytes, tenant_key: bytes, *,
             root: str | None = None, tenant: str | None = None,
             decoder: BatchDecoder | None = None) -> "ImageHandle":
        """Open an image session. `root` is the root the manifest was
        FETCHED from (defaults to the config root, then the manifest's
        creation root); `tenant` defaults to the manifest's tenant and
        names the telemetry scope. Handles of the same (image, root,
        tenant) share one ``TieredReader``, so concurrent opens
        single-flight their fetches against each other."""
        if self._closed:
            raise RuntimeError("ImageService is closed")
        # parsed-manifest cache: stampeding opens of one image must not
        # re-decrypt the key table and re-decode the layout every time.
        # The cache key includes the tenant key, so a caller with the
        # wrong key still fails authentication in open_manifest instead
        # of hitting another tenant's parse.
        mkey = (hashlib.sha256(manifest_blob).digest(), tenant_key)
        with self._lock:
            parsed = self._cache_lookup(self._manifests, mkey,
                                        "service.manifest_evictions")
        if parsed is None:
            manifest = open_manifest(manifest_blob, tenant_key)
            layout = ImageLayout.from_table(manifest.layout_table,
                                            manifest.chunk_size)
            with self._lock:
                parsed = self._cache_insert(
                    self._manifests, mkey, (manifest, layout),
                    self.config.manifest_cap,
                    "service.manifest_evictions")
        manifest, layout = parsed[0], parsed[1]
        root = root or self.config.root or manifest.root_id
        tenant = tenant if tenant is not None else manifest.tenant
        skey = (manifest.image_id, root, tenant)
        with self._lock:
            cached = self._cache_lookup(self._sessions, skey,
                                        "service.session_evictions")
        if cached is None or decoder is not None:
            scope = self.tenant_counters(tenant)
            reader = TieredReader(
                manifest, self.store, root=root, l1=self.l1, l2=self.l2,
                peer=self.peer, concurrency=self.fetch_limiter,
                origin_delay_s=self.config.origin_delay_s,
                decoder=decoder if decoder is not None
                else self.decoder_for(self.config.default_policy),
                counters=scope, flights=self.flights, pins=self.pins,
                retry=self.retry, breaker=self.breaker)
            if decoder is not None:
                # a caller-owned decoder makes the session unshareable;
                # don't pin it in the cache (a fresh decoder per open()
                # must not grow the session table without bound)
                return ImageHandle(self, manifest, layout, reader,
                                   tenant, scope)
            with self._lock:
                cached = self._cache_insert(
                    self._sessions, skey, (manifest, layout, reader, scope),
                    self.config.session_cap, "service.session_evictions")
        manifest, layout, reader, scope = cached[:4]
        return ImageHandle(self, manifest, layout, reader, tenant, scope)

    # ------------------------------------------------------------- publish
    def publisher(self) -> PublishPipeline:
        """The service's shared write-path pipeline (lazily built):
        batched convergent encryption through the configured decode
        backend, bounded-parallel single-flighted uploads, L1/peer
        warming, and refcount maintenance when the service carries a
        ``RefcountIndex``. Concurrent ``publish`` calls share it, so
        publishers racing on common chunks single-flight their PUTs."""
        with self._lock:
            if self._publisher is None:
                cfg = self.config
                self._publisher = PublishPipeline(
                    self.store,
                    backend=cfg.publish_backend or cfg.decode_backend,
                    tile_bytes=cfg.publish_tile_bytes,
                    upload_parallelism=cfg.upload_parallelism,
                    l1=self.l1 if cfg.publish_warm_l1 else None,
                    peer=self.peer if cfg.publish_warm_l1 else None,
                    refcounts=self.refcounts, counters=self.counters,
                    retry=self.retry,
                    name_index_path=cfg.publish_name_index_path)
            return self._publisher

    def publish(self, tree, *, tenant: str, tenant_key: bytes,
                root: str | None = None, salt_epoch: int = 0,
                image_id: str | None = None,
                chunk_size: int = CHUNK_SIZE) -> tuple:
        """Publish a pytree as an image through the batched write path
        (``core.publish.PublishPipeline``): (manifest blob, CreateStats).
        `root` defaults to the config root. The freshly-uploaded
        ciphertexts warm this service's L1/peer tiers, so the first
        cold-start of a just-published image hits locally."""
        if self._closed:
            raise RuntimeError("ImageService is closed")
        root = root or self.config.root
        if root is None:
            raise ValueError("publish needs a root (or ServiceConfig.root)")
        return self.publisher().publish(
            tree, tenant=tenant, tenant_key=tenant_key, root=root,
            salt_epoch=salt_epoch, image_id=image_id, chunk_size=chunk_size)

    def snapshot(self) -> dict:
        return self.counters.snapshot()


class ImageHandle:
    """A session over one opened image: demand-loading reads through the
    service's shared tiers, every method taking one optional
    ``ReadPolicy`` instead of scattered pipeline keywords."""

    def __init__(self, service: ImageService, manifest, layout: ImageLayout,
                 reader: TieredReader, tenant: str, scope: ScopedCounters):
        self.service = service
        self.manifest = manifest
        self.layout = layout
        self.reader = reader
        self.tenant = tenant
        self.counters = scope

    # ----------------------------------------------------------- plumbing
    def _resolve(self, policy: ReadPolicy | None) -> tuple:
        """(policy, decoder) with the service defaults applied.

        A policy with no decode overrides keeps the handle's bound
        decoder — which is the caller-supplied one when the session was
        opened with ``decoder=`` (the ImageReader shim contract), else
        the service default. An explicit ``eager_flush=True/False`` IS
        a decode override (it can switch eager off against an eager
        service default); ``None`` inherits."""
        p = policy if policy is not None else self.service.config.default_policy
        if p.decode_backend is None and p.max_batch_bytes is None \
                and p.eager_flush is None and p.eager_min_bytes is None:
            return p, self.reader.decoder
        return p, self.service.decoder_for(p)

    def tensor_names(self) -> list:
        return list(self.layout.tensors)

    # -------------------------------------------------------------- reads
    def tensor(self, name: str) -> np.ndarray:
        """Serial restore of one tensor (the reference read path)."""
        return read_tensor(self.layout, name, self.reader.read)

    def restore_tree(self, names=None,
                     policy: ReadPolicy | None = None) -> dict:
        """Flat {path: array} for all (or selected) tensors, via one
        pipelined batch shaped by `policy` (service default: streamed);
        one ``repro.restore`` span."""
        names = names if names is not None else self.tensor_names()
        tensors = [self.layout.tensors[n] for n in names]
        chunks = ranges_to_chunks([(t.offset, t.nbytes) for t in tensors],
                                  self.manifest.chunk_size)
        with span("repro.restore", chunks=len(chunks)):
            return self.restore_shards({n: None for n in names}, policy)

    def restore_shards(self, shard_slices: dict,
                       policy: ReadPolicy | None = None) -> dict:
        """Batched restore of {name: dim_slices | None (full tensor)}.

        Computes every byte range up front, fetches the union chunk set
        once via ``read_many`` under `policy`, then assembles each
        tensor/shard. ``mode="serial"`` reads each range through the
        per-chunk oracle path instead (byte-identical by contract)."""
        p, dec = self._resolve(policy)
        plan = []                       # (name, ranges, out_shape, dtype)
        all_ranges = []
        for name, sl in shard_slices.items():
            t = self.layout.tensors[name]
            dt = np.dtype(t.dtype)
            if not t.shape or sl is None:
                ranges = [(t.offset, t.nbytes)]
                shape = t.shape
            else:
                ranges = shard_byte_ranges(t, sl)
                shape = tuple(e - s for s, e in sl)
            plan.append((name, ranges, shape, dt))
            all_ranges.extend(ranges)
        if p.mode == "serial":
            bufs = iter([self.reader.read(off, ln)
                         for off, ln in all_ranges])
        else:
            bufs = iter(self.reader.read_many(
                all_ranges, p.parallelism, streamed=p.streamed,
                queue_depth=p.queue_depth, decoder=dec,
                l2_hedge=p.l2_hedge))
        out = {}
        with span("repro.restore.assemble"):
            for name, ranges, shape, dt in plan:
                raw = b"".join(next(bufs) for _ in ranges)
                # reshape(()) yields a 0-d array for scalars — identical
                # to the serial read_tensor path
                out[name] = np.frombuffer(raw, dt).reshape(shape)
        return out

    def restore_share(self, share: dict,
                      policy: ReadPolicy | None = None) -> tuple:
        """A replica's share of the image, {path: dim slices | None (the
        whole tensor)}, restored in one ``restore_shards`` batch under
        `policy`. Returns (flat {path: array}, the share's numbers):
        ``held_bytes``, ``image_bytes`` (all of the image's tensors),
        ``chunks`` (the chunk ciphertexts the restore fetched through the
        tiers, counted by the read itself: chunks of equal bytes are one
        name and zero chunks are never fetched), ``chunks_covered`` (the
        chunks the share's byte ranges lie in, from the layout) and
        ``partial_chunks`` (covered chunks of which the share holds only
        part of the tensor bytes). One ``repro.restore.share`` span
        carries them; the counters ``restore.share_bytes`` and
        ``restore.skipped_bytes`` add up the bytes held and left in the
        image."""
        cs = self.manifest.chunk_size
        serial = self._resolve(policy)[0].mode == "serial"
        if serial:
            names = {c.index: c.name for c in self.manifest.chunks}
        held = covered_n = partial = serial_reads = 0
        for name, sl in share.items():
            t = self.layout.tensors[name]
            ranges = ([(t.offset, t.nbytes)] if sl is None or not t.shape
                      else shard_byte_ranges(t, sl))
            covered: dict = {}          # chunk -> bytes of it read
            for off, ln in ranges:
                held += ln
                if serial:      # the oracle fetches each range's chunks
                    serial_reads += sum(names[c] != ZERO_CHUNK for c in
                                        ranges_to_chunks([(off, ln)], cs))
                while ln > 0:
                    n = min(ln, cs - off % cs)
                    covered[off // cs] = covered.get(off // cs, 0) + n
                    off, ln = off + n, ln - n
            end = t.offset + t.nbytes   # tensors start on a chunk
            covered_n += len(covered)
            partial += sum(n < min(end, (c + 1) * cs) - c * cs
                           for c, n in covered.items())
        image = sum(t.nbytes for t in self.layout.tensors.values())
        numbers = {"held_bytes": held, "image_bytes": image,
                   "chunks_covered": covered_n, "partial_chunks": partial}
        with span("repro.restore.share", **numbers) as s:
            flat = self.restore_shards(share, policy)
            # what the read fetched: the batch's distinct ciphertexts
            numbers["chunks"] = (serial_reads if serial
                                 else self.reader.last_batch["names"])
            s.set_metadata(chunks=numbers["chunks"])
        self.counters.inc("restore.share_bytes", held)
        self.counters.inc("restore.skipped_bytes", image - held)
        return flat, numbers

    def tensor_shard(self, name: str, dim_slices: list,
                     policy: ReadPolicy | None = None) -> np.ndarray:
        """Fetch only the bytes of one rectangular shard (batched)."""
        return self.restore_shards({name: dim_slices}, policy)[name]

    def shard_chunks(self, shard_slices: dict) -> list:
        """Chunk indices needed for {tensor_name: [(start, stop) per dim]}."""
        ranges = []
        for name, sl in shard_slices.items():
            t = self.layout.tensors[name]
            ranges.extend(shard_byte_ranges(t, sl))
        return ranges_to_chunks(ranges, self.manifest.chunk_size)

    def prefetch(self, chunk_indices: list,
                 policy: ReadPolicy | None = None):
        """Concurrently warm the cache tiers for `chunk_indices`.

        Non-materializing: ciphertexts land in L1/L2 but are neither
        decrypted nor accumulated. A ``streamed`` policy (the default)
        warms through the streaming fetch producer — per-chunk L2 stripe
        resolution, bounded hand-off — exactly the path the streamed
        restore will take."""
        p, _ = self._resolve(policy)
        self.reader.fetch_chunks(chunk_indices, p.parallelism,
                                 materialize=False, streamed=p.streamed,
                                 queue_depth=p.queue_depth,
                                 l2_hedge=p.l2_hedge)


def single_image_service(store, *, l1=None, l2=None, peer=None,
                         fetch_limiter=None,
                         origin_delay_s: float = 0.0) -> ImageService:
    """A private service with no self-built tiers or limiters — the
    substrate of the ``ImageReader`` deprecation shim and of one-shot
    scripts that inject their own tier objects."""
    cfg = ServiceConfig(l1_bytes=0, l2_nodes=0, fetch_concurrency=0,
                        max_coldstarts=0, origin_delay_s=origin_delay_s)
    return ImageService(store, cfg, l1=l1, l2=l2, peer=peer,
                        fetch_limiter=fetch_limiter)


def build_peer_mesh(config: ServiceConfig, num_workers: int, *,
                    seed: int = 0, transfer_hook=None):
    """A ``PeerMesh`` sized from `config`'s peer knobs. The caller hands
    ``mesh.client(i)`` to worker i's ``ImageService(peer=...)``; fault
    injection goes through ``mesh.set_fault(i, FaultPlan...)`` exactly
    like the L2's per-node plans."""
    from repro.core.cache.peer import PeerMesh
    return PeerMesh(num_workers, fanout=config.peer_fanout,
                    deadline_s=config.peer_deadline_s,
                    registration=config.peer_registration,
                    seed=seed, transfer_hook=transfer_hook)
