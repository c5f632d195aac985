"""The decode stage of the split fetch/decode restore pipeline.

``TieredReader.fetch_ciphertexts`` (blockdev.py) is fetch-I/O only; this
module turns its output — a batch of ciphertexts — into plaintexts in a
staged pass: a batched SHA verify followed by a batched AES-CTR
keystream (``convergent.decrypt_chunks``), instead of PR 1's per-chunk
``decrypt_chunk`` loop on the caller thread.

Two consumption modes:

* **Staged** (``decrypt_batch`` / ``decrypt_batch_timed``): the whole
  fetched set at once, split into cache-resident tiles, decoded on the
  pool. Decode starts after fetch completes.
* **Streaming** (``decrypt_stream``): consume ``(name, ciphertext)``
  pairs from a ``BoundedQueue`` WHILE the fetch stage is still
  producing. Chunks accumulate into ``max_batch_bytes`` tiles with
  exactly the ``_split`` invariants (a tile never exceeds the cap unless
  a single chunk does; arrival order is preserved within the stream) and
  each full tile is dispatched the moment it fills — to the
  GIL-releasing pool, or, for a kernel backend, to the consumer's own
  tile loop, which keeps the next tile in flight behind the current
  one's kernel (``_TileLoop``) — so decode wall-clock hides behind the
  deepest fetch miss instead of starting after it. The streaming
  contract:

  - the stream is drained even after a bad tile, and the final
    ``IntegrityError`` names EVERY bad chunk across all tiles, in sorted
    (deterministic) order — never a partial report;
  - no plaintext of a bad chunk is ever returned;
  - a fetch-side failure (queue poisoned) is re-raised only after all
    dispatched tiles finish, so no decode work is left running.

  With ``eager_flush`` (gated by ``ReadPolicy.eager_flush``) the
  consumer additionally dispatches its PARTIAL tile whenever it would
  otherwise block on an empty hand-off queue: decode capacity that
  would sit idle during a fetch stall chews on whatever has already
  arrived, shrinking the post-fetch decode tail on small or
  slow-arriving batches at some tile-efficiency cost (more, smaller
  tiles). ``stats["eager_flushes"]`` counts how often it fired.

Why batching wins where per-chunk threading could not (ROADMAP item 1):
the per-chunk pull path interleaved ~170 small numpy dispatches per
chunk with python glue, so worker threads thrashed the GIL. The batch
layout instead

* amortizes dispatch: one ``ctr_keystream_many`` T-table pass per TILE
  of chunks, not one per chunk;
* keeps tiles small enough (``max_batch_bytes``, default 256 KiB) that
  each pass's working set stays cache-resident instead of streaming
  multi-MB temporaries through memory;
* decodes tiles on a small thread pool: numpy's large-array kernels and
  hashlib both release the GIL, so with the python-per-chunk overhead
  batched away the decode stage finally scales with cores.

Backends — the decode-backend REGISTRY:

A decode backend is one named object pairing the two batched kernels of
the verify-then-decrypt pass (an ``encrypt_many`` AES block pass and a
``sha_many`` digest pass) with its preferred tile shape and threading
model. ``BatchDecoder``, ``ReadPolicy.decode_backend``,
``convergent.decrypt_chunks`` and the serve launcher's
``--decode-backend`` flag all select one registered backend BY NAME
instead of threading ``encrypt_many``/``sha_backend`` hooks separately:

* ``"python"`` (alias ``"numpy"``): batched numpy T-table AES +
  hashlib verify. hashlib releases the GIL and runs at memory
  bandwidth — the CPU fast path.
* ``"xla"`` (alias ``"jax"``): the ``repro.kernels.aes`` jit'd T-table
  gather pass + hashlib verify (single-threaded tiles: XLA manages its
  own parallelism). The right lowering on GPU, where the byte gather is
  native.
* ``"bitsliced"``: the gather-free Pallas kernels — bit-plane AES-CTR
  (Boyar–Peralta S-box circuit, ``kernels/aes/bitslice_pallas``) +
  lockstep SHA-256 verify (``kernels/sha256``). The TPU VPU lowering;
  off-TPU both kernels run under the Pallas interpreter (what the CPU
  tests drive; on TPU a kernel compiles or raises, never interprets).
* ``"bitsliced-fused"`` (alias ``"fused"``): ONE device program per
  tile (``kernels/fused``) producing digests AND plaintext: a
  lane-dense bitsliced keystream pass with per-CHUNK round keys, then
  one lockstep SHA walk over each ciphertext that XORs the keystream
  in — one dispatch and one readback, where the
  ``sha_many``-then-``encrypt_many`` pair takes two of each.
* ``"auto"`` (the ``ServiceConfig`` default): probe the jax platform —
  ``bitsliced-fused`` on TPU, ``xla`` on GPU, ``python`` on CPU.
* ``"serial"``: the per-chunk ``decrypt_chunk`` oracle — PR 1's caller-
  thread behavior, kept for byte-identity tests and benchmarks (not a
  registry object; it bypasses the batched pass entirely).

Tile sizing: ``BatchDecoder(max_batch_bytes="auto")`` (the
``ServiceConfig`` default) asks ``autotune_tile_bytes`` for the
backend's best tile — a small timed sweep at first use, cached per
process; an explicit ``ServiceConfig``/``ReadPolicy`` integer override
always wins, and ``REPRO_NO_AUTOTUNE=1`` disables the sweep entirely.

``benchmarks/decode_kernels.py`` records every registered backend's
keystream and verify GB/s (and the fused combined pass) into
BENCH_e2e.json and gates regressions.

Forward direction (the PUBLISH side): AES-CTR is symmetric and SHA is
direction-free, so the same registry hooks run chunk *creation* —
``BatchDecoder.encrypt_batch_timed`` (batched convergent encrypt:
derive keys → keystream → name ciphertexts, tiled on the pool) and
``derive_keys_batch`` (keys alone, for the publish pipeline's
names-before-bytes dedup probe). ``core.publish.PublishPipeline``
drives them; the per-chunk ``convergent.encrypt_chunk`` stays as the
serial oracle.
"""
from __future__ import annotations

import os
import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass, field

from repro.core.concurrency import QUEUE_DONE, QUEUE_EMPTY, LazyPool
from repro.core.crypto import convergent
from repro.core.telemetry import COUNTERS, bind_request, span

DEFAULT_MAX_BATCH_BYTES = 256 << 10
DEFAULT_THREADS = max(1, min(4, os.cpu_count() or 1))
DEFAULT_EAGER_MIN_BYTES = 32 << 10
# tiles in flight at most on a two-phase kernel hook: with one tile's
# host work (pack, dispatch, readback, split, digest check) shorter than
# the other's kernel, two keep the device busy
PIPELINE_DEPTH = 2


# ------------------------------------------------------------- registry

@dataclass
class DecodeBackend:
    """One named decode kernel pair: the batched AES block pass + the
    batched SHA digest pass, with the tile/threading shape they want.

    ``loader`` materializes the hooks lazily (kernel imports pull jax;
    constructing the default python backend must not), returning
    ``(encrypt_many, sha_many)`` or ``(encrypt_many, sha_many, fused)``
    where ``None`` selects the numpy T-table core / the ``sha_backend``
    string path / the two-pass route respectively. A ``fused`` hook is
    ``(ciphertexts, keys) -> (digests, plaintexts)`` in one pass —
    ``convergent.decrypt_chunks`` compares the digests before releasing
    plaintext, so tamper semantics are hook-independent. A hook that
    also offers ``submit`` (launch now, ``result()`` later) gets its
    tiles pipelined (``_TileLoop``).
    ``threads=None`` leaves tile threading to the decoder default;
    ``1`` means the kernel owns its parallelism (XLA / Pallas)."""

    name: str
    description: str
    tile_bytes: int = DEFAULT_MAX_BATCH_BYTES
    threads: int | None = None
    loader: object = None
    _hooks: tuple | None = field(default=None, init=False, repr=False)

    def hooks(self) -> tuple:
        if self._hooks is None:
            h = self.loader() if self.loader else (None, None)
            if len(h) == 2:          # legacy two-pass loaders
                h = h + (None,)
            self._hooks = h
        return self._hooks

    @property
    def encrypt_many(self):
        return self.hooks()[0]

    @property
    def sha_many(self):
        return self.hooks()[1]

    @property
    def fused(self):
        return self.hooks()[2]


_REGISTRY: dict[str, DecodeBackend] = {}
_ALIASES: dict[str, str] = {}


def register_backend(backend: DecodeBackend, aliases: tuple = ()) -> None:
    _REGISTRY[backend.name] = backend
    for a in aliases:
        _ALIASES[a] = backend.name


def registered_backends() -> dict:
    """{canonical name: DecodeBackend}, registration order."""
    return dict(_REGISTRY)


def known_backend_names() -> list:
    """Every name ``BatchDecoder``/``ReadPolicy`` accept: canonical
    registry names, their legacy aliases, the serial oracle, and the
    auto probe."""
    return sorted(set(_REGISTRY) | set(_ALIASES) | {"serial", "auto"})


def _auto_backend_name() -> str:
    import jax
    plat = jax.default_backend()
    if plat == "tpu":
        return "bitsliced-fused"
    if plat == "gpu":
        return "xla"
    return "python"


def resolve_backend_name(name: str) -> str:
    """Canonical registry name for `name` (alias- and auto-resolving;
    ``"serial"`` passes through). Raises ``ValueError`` on unknowns."""
    if name == "serial":
        return "serial"
    if name == "auto":
        return _auto_backend_name()
    name = _ALIASES.get(name, name)
    if name not in _REGISTRY:
        raise ValueError(f"unknown decode backend {name!r}; known: "
                         f"{known_backend_names()}")
    return name


def get_backend(name: str) -> DecodeBackend:
    """The registered backend object behind `name` (not ``"serial"``)."""
    return _REGISTRY[resolve_backend_name(name)]


CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_persistent_compilation_cache() -> str:
    """Place jax's persistent compilation cache, so the Pallas decode
    kernels and the serving step compile once per machine, not once per
    process. The one place the program sets it: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it (jax reads that itself), otherwise the
    fixed ``.jax_cache/`` at the checkout root — a path that never moves,
    since the path is part of what a later run looks up. Entry points
    call this at start-up; the tests never do. Returns the directory."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def _load_xla():
    from repro.kernels.aes import encrypt_many_jax
    return encrypt_many_jax, None


def _load_bitsliced():
    from repro.kernels.aes import encrypt_many_bitsliced
    from repro.kernels.sha256 import sha256_many_pallas
    return encrypt_many_bitsliced, sha256_many_pallas


def _load_fused():
    from repro.kernels.aes import encrypt_many_bitsliced
    from repro.kernels.fused import fused_verify_decrypt
    from repro.kernels.sha256 import sha256_many_pallas
    return encrypt_many_bitsliced, sha256_many_pallas, fused_verify_decrypt


register_backend(DecodeBackend(
    "python", "batched numpy T-table AES + hashlib verify (CPU fast "
    "path: hashlib releases the GIL and runs at memory bandwidth)"),
    aliases=("numpy",))
register_backend(DecodeBackend(
    "xla", "jit'd XLA T-table gather AES + hashlib verify (GPU: native "
    "byte gather; single-threaded tiles, XLA owns parallelism)",
    threads=1, loader=_load_xla), aliases=("jax",))
register_backend(DecodeBackend(
    "bitsliced", "gather-free Pallas kernels: bit-plane AES-CTR "
    "(Boyar-Peralta S-box circuit) + lockstep SHA-256 verify (TPU VPU; "
    "Pallas interpreter off-TPU)", threads=1, loader=_load_bitsliced))
register_backend(DecodeBackend(
    "bitsliced-fused", "ONE device program per tile: a lane-dense "
    "bitsliced AES-CTR keystream pass, then a lockstep SHA-256 walk that "
    "XORs it into the ciphertext, per-chunk round keys "
    "(kernels/fused; Pallas on TPU, whole-batch XLA jit elsewhere)",
    threads=1, loader=_load_fused), aliases=("fused",))


# ------------------------------------------------------------- autotune

_TILE_CANDIDATES = (64 << 10, 128 << 10, 256 << 10, 512 << 10, 1 << 20)
_AUTOTUNE_CACHE: dict[str, int] = {}
_AUTOTUNE_LOCK = threading.Lock()
# backend name -> Event while its sweep is running: the lock guards only
# the cache/pending dicts, never a measurement — one backend's
# compile-heavy first sweep must not serialize every OTHER decoder's
# first decode behind it (only same-backend callers wait, on the event)
_AUTOTUNE_PENDING: dict[str, threading.Event] = {}


def _autotune_sweep(backend, *, budget_s: float, chunk_bytes: int) -> int:
    """The timed candidate sweep (no lock held). Each candidate gets ONE
    untimed warmup call first — jit'd backends compile per tile shape,
    and timing the first call would fold compile time into the rate
    (and burn the whole budget on candidate 1 with a cold cache). Only
    the timed run counts toward the rate and ``budget_s``."""
    import numpy as np

    from repro.core.crypto import aes

    enc, sha, fused = backend.hooks()
    rng = np.random.default_rng(0xA070)
    candidates = [backend.tile_bytes] + [
        c for c in _TILE_CANDIDATES if c != backend.tile_bytes]
    best = backend.tile_bytes
    best_rate = 0.0
    spent = 0.0

    def one_pass(cts, keys):
        if fused is not None:
            fused(cts, keys)
        else:
            if sha is not None:
                sha(cts)
            else:
                import hashlib
                for ct in cts:
                    hashlib.sha256(ct).digest()
            aes.ctr_keystream_many(keys, [len(ct) for ct in cts],
                                   encrypt_many=enc)

    for cand in candidates:
        if spent > budget_s:    # checked BEFORE the warmup: an exhausted
            break               # budget must not keep compiling candidates
        nchunks = max(1, cand // chunk_bytes)
        cts = [rng.integers(0, 256, chunk_bytes, np.uint8).tobytes()
               for _ in range(nchunks)]
        keys = [bytes(rng.integers(0, 256, 32, np.uint8))
                for _ in range(nchunks)]
        one_pass(cts, keys)     # warmup: compile + caches, untimed
        t0 = time.perf_counter()
        one_pass(cts, keys)
        dt = time.perf_counter() - t0
        rate = (nchunks * chunk_bytes) / max(dt, 1e-9)
        if rate > best_rate:
            best_rate, best = rate, cand
        spent += dt
    return best


def autotune_tile_bytes(backend_name: str, *, budget_s: float = 0.25,
                        chunk_bytes: int = 4096,
                        force: bool = False) -> int:
    """Best ``max_batch_bytes`` for `backend_name` on THIS machine: a
    small timed sweep over tile-size candidates at first use, cached
    per process. Each candidate decodes one synthetic tile of
    ``chunk_bytes`` chunks through the backend's real combined pass
    (the fused hook when present, else verify + keystream) and the
    highest bytes/s wins; an untimed warmup call per candidate keeps
    jit compile time out of both the rate and the budget.

    The sweep is budgeted: candidates are tried starting from the
    backend's registered default, and once ``budget_s`` of measurement
    has elapsed no further candidates start. The sweep runs OUTSIDE
    ``_AUTOTUNE_LOCK`` — concurrent callers for the SAME backend wait
    on its pending event, while other backends sweep (or read their
    cached tile) in parallel. ``REPRO_NO_AUTOTUNE=1`` (env) disables
    the sweep; explicit ``ServiceConfig``/``ReadPolicy`` integers
    bypass it entirely (see ``BatchDecoder``). ``force=True``
    re-measures."""
    resolved = resolve_backend_name(backend_name)
    if resolved == "serial":
        return DEFAULT_MAX_BATCH_BYTES
    backend = _REGISTRY[resolved]
    if os.environ.get("REPRO_NO_AUTOTUNE"):
        return backend.tile_bytes
    while True:
        with _AUTOTUNE_LOCK:
            if not force and resolved in _AUTOTUNE_CACHE:
                return _AUTOTUNE_CACHE[resolved]
            pending = _AUTOTUNE_PENDING.get(resolved)
            if pending is None:
                pending = _AUTOTUNE_PENDING[resolved] = threading.Event()
                break               # this caller runs the sweep
        pending.wait()              # same-backend sweep in flight
        with _AUTOTUNE_LOCK:
            done = _AUTOTUNE_CACHE.get(resolved)
        if done is not None and not force:
            return done
        # the sweep failed (or force=True): loop and claim it ourselves
    try:
        best = _autotune_sweep(backend, budget_s=budget_s,
                               chunk_bytes=chunk_bytes)
        with _AUTOTUNE_LOCK:
            _AUTOTUNE_CACHE[resolved] = best
        COUNTERS.inc("decode.autotuned_backends")
        return best
    finally:
        with _AUTOTUNE_LOCK:
            _AUTOTUNE_PENDING.pop(resolved, None)
        pending.set()


class BatchDecoder:
    """Decodes {name: ciphertext} batches against manifest ChunkRefs."""

    def __init__(self, backend: str = "numpy",
                 max_batch_bytes: int | str | None = None,
                 threads: int | None = None,
                 sha_backend: str = "hashlib",
                 eager_flush: bool = False,
                 eager_min_bytes: int | None = None):
        resolved = resolve_backend_name(backend)     # raises on unknowns
        # the AS-GIVEN name (aliases included) is what telemetry and
        # last_batch report — except "auto", which reports its probe
        self.backend = resolved if backend == "auto" else backend
        self.backend_obj = _REGISTRY.get(resolved)   # None for "serial"
        self.eager_flush = bool(eager_flush)
        self.eager_min_bytes = DEFAULT_EAGER_MIN_BYTES \
            if eager_min_bytes is None else max(0, int(eager_min_bytes))
        if max_batch_bytes == "auto":
            # measured per backend per process; explicit ints always win
            max_batch_bytes = autotune_tile_bytes(resolved) \
                if self.backend_obj else DEFAULT_MAX_BATCH_BYTES
        elif max_batch_bytes is None:                # backend default
            max_batch_bytes = self.backend_obj.tile_bytes \
                if self.backend_obj else DEFAULT_MAX_BATCH_BYTES
        self.max_batch_bytes = max(1, int(max_batch_bytes))
        self.threads = DEFAULT_THREADS if threads is None else max(1, threads)
        self.sha_backend = sha_backend
        self._encrypt_many = None
        self._sha_many = None
        self._fused = None
        if self.backend_obj is not None:
            (self._encrypt_many, self._sha_many,
             self._fused) = self.backend_obj.hooks()
            if self.backend_obj.threads is not None:
                # the kernel owns its parallelism (XLA / Pallas)
                self.threads = self.backend_obj.threads
        self._pool = LazyPool()
        self.last_wall_s = 0.0
        # decrypt_batch concurrency detection (the last_wall_s footgun):
        # internal hot paths all use decrypt_batch_timed / decrypt_stream
        self._state_lock = threading.Lock()
        self._inflight_batches = 0
        self._warned_concurrent = False

    def decrypt_batch(self, refs: list, ciphertexts: dict) -> dict:
        """refs: ChunkRefs (one per distinct name); ciphertexts:
        {name: bytes}. Returns {name: plaintext}. Tampered ciphertexts
        raise ``IntegrityError`` naming every offending chunk name in
        the batch — no bad chunk's plaintext is ever returned.

        ``last_wall_s`` is a convenience for single-threaded callers
        ONLY: concurrent calls race on it, so this method emits a
        one-time ``RuntimeWarning`` when it detects overlap. Concurrent
        callers (and every internal caller) must use
        ``decrypt_batch_timed``, which never touches shared state."""
        with self._state_lock:
            self._inflight_batches += 1
            concurrent = self._inflight_batches > 1
            warn = concurrent and not self._warned_concurrent
            if warn:
                self._warned_concurrent = True
        try:
            if concurrent:
                COUNTERS.inc("decode.concurrent_decrypt_batch")
            if warn:
                warnings.warn(
                    "BatchDecoder.decrypt_batch called concurrently: "
                    "last_wall_s is unreliable under concurrency; use "
                    "decrypt_batch_timed", RuntimeWarning, stacklevel=2)
            out, wall = self.decrypt_batch_timed(refs, ciphertexts)
            self.last_wall_s = wall
            return out
        finally:
            with self._state_lock:
                self._inflight_batches -= 1

    def decrypt_batch_timed(self, refs: list, ciphertexts: dict) -> tuple:
        """``decrypt_batch`` returning ({name: plaintext}, wall_seconds)
        without touching shared state — safe for one decoder shared
        across stampeding readers."""
        t0 = time.perf_counter()
        out: dict[str, bytes] = {}
        bad_names: list[str] = []
        if self.backend == "serial":
            for ref in refs:
                try:
                    out[ref.name] = convergent.decrypt_chunk(
                        ciphertexts[ref.name], ref.key, ref.sha256)
                except convergent.IntegrityError:
                    bad_names.append(ref.name)
        else:
            tiles = list(self._split(refs, ciphertexts))
            results = None
            if len(tiles) > 1 and self.threads > 1:
                try:
                    results = list(self._pool.get(self.threads).map(
                        bind_request(
                            lambda t: self._decode_tile(t, ciphertexts)),
                        tiles))
                except RuntimeError:
                    # pool shut down concurrently (service.close() racing
                    # an in-flight read): decode inline — reads through
                    # live handles must keep working
                    pass
            if results is None:
                results = _TileLoop(self).run(tiles, ciphertexts)
            for plains, bad in results:
                out.update(plains)
                bad_names.extend(bad)
        if bad_names:
            raise convergent.IntegrityError(
                f"chunk ciphertext hash mismatch: {sorted(bad_names)}",
                sorted(bad_names))
        COUNTERS.add("decode.batched_chunks", len(out))
        return out, time.perf_counter() - t0

    def decrypt_stream(self, queue, refs_by_name: dict) -> tuple:
        """Streaming consumer: drain ``(name, ciphertext)`` pairs from a
        ``BoundedQueue`` (see module docstring for the contract),
        accumulating ``max_batch_bytes`` tiles and dispatching each to
        the pool — or, single-threaded, to this thread's tile loop
        (``_TileLoop``) — while the fetch producer is still running.

        ``refs_by_name`` maps chunk name -> ChunkRef (key + expected
        sha256). Returns ``({name: plaintext}, stats)`` where stats has
        ``busy_s`` (the time at least one tile was in flight, tiles
        that overlap counted once: the overlap-accounting input),
        ``wall_s`` (consumer elapsed), ``tiles`` and ``tiles_overlapped``
        (tiles submitted while an earlier one was still in flight).

        A poisoned queue (fetch failure) re-raises the producer's error
        after all dispatched tiles complete; tampered chunks raise one
        ``IntegrityError`` naming every bad chunk across all tiles.

        With ``eager_flush`` the partial tile is dispatched whenever the
        queue is momentarily empty (``try_get`` returns ``QUEUE_EMPTY``)
        — the idle-queue opportunistic flush of ROADMAP item 1."""
        t0 = time.perf_counter()
        out: dict[str, bytes] = {}
        bad_names: list[str] = []
        results: list = []
        intervals: list = []
        futures: list = []
        pool = self._pool.get(self.threads) \
            if self.backend != "serial" and self.threads > 1 else None
        loop = _TileLoop(self)
        part: list = []
        cts: dict[str, bytes] = {}
        size = 0
        eager = self.eager_flush and self.backend != "serial"
        eager_flushes = 0
        eager_holds = 0

        def flush():
            nonlocal part, cts, size
            if not part:
                return
            if pool is not None:
                try:
                    futures.append(pool.submit(
                        bind_request(self._decode_tile_timed), part, cts))
                except RuntimeError:
                    # pool shut down concurrently (service.close()
                    # racing this stream): fall back to inline decode
                    loop.push(part, cts)
            else:
                loop.push(part, cts)
            part, cts, size = [], {}, 0

        stream_err = None
        try:
            while True:
                if eager and part:
                    item = queue.try_get()
                    if item is QUEUE_EMPTY:
                        # the consumer would block here. Flush the
                        # partial tile only if decode capacity is
                        # actually idle — when pool tiles are still in
                        # flight, an early flush just shreds tile
                        # efficiency without starting any work sooner —
                        # AND the partial has accumulated at least
                        # ``eager_min_bytes``: flushing slivers at scale
                        # trades the whole tile-batching win for a
                        # negligible head start (the threshold is the
                        # ROADMAP item-2 trigger, tuned via
                        # benchmarks/e2e_read_latency.py). Tiles in
                        # flight on this thread's loop are finished
                        # first, while the queue is empty anyway, so the
                        # flush fires with no tile in flight.
                        if size < self.eager_min_bytes:
                            eager_holds += 1
                            COUNTERS.inc("decode.eager_holds")
                        elif all(f.done() for f in futures):
                            loop.drain()
                            flush()
                            eager_flushes += 1
                            COUNTERS.inc("decode.eager_flushes")
                        item = queue.get()
                else:
                    item = queue.get()
                if item is QUEUE_DONE:
                    break
                name, ct = item
                ref = refs_by_name[name]
                if self.backend == "serial":
                    ts = time.perf_counter()
                    try:
                        out[ref.name] = convergent.decrypt_chunk(
                            ct, ref.key, ref.sha256)
                    except convergent.IntegrityError:
                        bad_names.append(ref.name)
                    intervals.append((ts, time.perf_counter()))
                    continue
                if part and size + len(ct) > self.max_batch_bytes:
                    flush()
                part.append(ref)
                cts[name] = ct
                size += len(ct)
            flush()
        except BaseException as e:
            stream_err = e
        # finish EVERY dispatched tile, even after an error, so no decode
        # work is left running and no tile's bad names are lost
        tile_err = None
        try:
            loop.drain()
        except BaseException as e:          # unexpected: not an
            tile_err = e                    # IntegrityError (the loop
        for f in futures:                   # and _decode_tile catch those)
            try:
                plains, bad, interval = f.result()
            except BaseException as e:
                if tile_err is None:
                    tile_err = e
                continue
            results.append((plains, bad))
            intervals.append(interval)
        results += loop.results
        intervals += loop.intervals
        for plains, bad in results:
            out.update(plains)
            bad_names.extend(bad)
        if stream_err is not None:          # fetch failure dominates
            raise stream_err
        if tile_err is not None:
            raise tile_err
        if bad_names:
            raise convergent.IntegrityError(
                f"chunk ciphertext hash mismatch: {sorted(bad_names)}",
                sorted(bad_names))
        COUNTERS.add("decode.batched_chunks", len(out))
        return out, {"busy_s": _covered_s(intervals),
                     "wall_s": time.perf_counter() - t0,
                     "tiles": len(results),
                     "tiles_overlapped": loop.overlapped,
                     "eager_flushes": eager_flushes,
                     "eager_holds": eager_holds}

    # --------------------------------------------------- forward direction
    def derive_keys_batch(self, plaintexts: list, salt: bytes) -> list:
        """Batched convergent key derivation through this backend's SHA
        hook (``forward=`` stage 1: names-before-bytes for the publish
        pipeline's dedup probe)."""
        if self.backend == "serial":
            return [convergent.derive_key(p, salt) for p in plaintexts]
        return convergent.derive_keys(plaintexts, salt,
                                      sha_backend=self.sha_backend,
                                      sha_many=self._sha_many)

    def encrypt_batch_timed(self, plaintexts: list, salt: bytes, *,
                            keys: list | None = None) -> tuple:
        """The FORWARD (``forward=True``) direction of the registry pair:
        batched convergent encryption of N plaintext chunks through the
        same ``encrypt_many``/``sha_many`` hooks the decode path uses,
        tiled by ``max_batch_bytes`` and run on the GIL-releasing pool
        exactly like ``decrypt_batch_timed``. `keys` carries pre-derived
        convergent keys (``derive_keys_batch``) so the publish pipeline
        never hashes a plaintext twice. Returns
        (``EncryptedChunk`` list in input order, wall_seconds); byte-
        identical to the serial ``convergent.encrypt_chunk`` oracle."""
        t0 = time.perf_counter()
        pts = list(plaintexts)
        if not pts:
            return [], 0.0
        if keys is None:
            keys = self.derive_keys_batch(pts, salt)
        if self.backend == "serial":
            out = [convergent.encrypt_chunk(p, salt) for p in pts]
            return out, time.perf_counter() - t0
        tiles = list(self._split_forward(pts, keys))
        if len(tiles) > 1 and self.threads > 1:
            try:
                results = list(self._pool.get(self.threads).map(
                    bind_request(
                        lambda t: self._forward_tile(t[0], salt, t[1])),
                    tiles))
            except RuntimeError:        # pool shut down concurrently
                results = [self._forward_tile(p, salt, k) for p, k in tiles]
        else:
            results = [self._forward_tile(p, salt, k) for p, k in tiles]
        out = [enc for tile in results for enc in tile]
        COUNTERS.add("decode.forward_chunks", len(out))
        return out, time.perf_counter() - t0

    def _forward_tile(self, pts: list, salt: bytes, keys: list) -> list:
        """One tile through the batched forward pass."""
        return convergent.encrypt_chunks(
            pts, salt, keys=keys, sha_backend=self.sha_backend,
            encrypt_many=self._encrypt_many, sha_many=self._sha_many)

    def _split_forward(self, pts: list, keys: list):
        """(plaintexts, keys) tiles under ``max_batch_bytes`` each."""
        part, pkeys, size = [], [], 0
        for p, k in zip(pts, keys):
            if part and size + len(p) > self.max_batch_bytes:
                yield part, pkeys
                part, pkeys, size = [], [], 0
            part.append(p)
            pkeys.append(k)
            size += len(p)
        if part:
            yield part, pkeys

    def close(self):
        """Drain the tile pool (idempotent). Shared decoders are closed
        by ``ImageService.close()``; in-flight tiles finish first."""
        self._pool.shutdown()

    def _decode_tile_timed(self, part: list, ciphertexts: dict) -> tuple:
        """``_decode_tile`` plus its (start, end) (runs on a pool thread;
        the union of the tiles' intervals is the stream's decode busy
        time)."""
        t0 = time.perf_counter()
        plains, bad = self._decode_tile(part, ciphertexts)
        return plains, bad, (t0, time.perf_counter())

    def _decode_tile(self, part: list, ciphertexts: dict) -> tuple:
        """One tile through the batched verify+decrypt pass. Returns
        ({name: plaintext}, [tampered names])."""
        cts = [ciphertexts[r.name] for r in part]
        try:
            with span("repro.decode.tile", chunks=len(cts),
                      bytes=sum(map(len, cts))):
                plains = convergent.decrypt_chunks(
                    cts, [r.key for r in part], [r.sha256 for r in part],
                    sha_backend=self.sha_backend,
                    encrypt_many=self._encrypt_many,
                    sha_many=self._sha_many,
                    fused=self._fused)
        except convergent.IntegrityError as e:
            return {}, [part[i].name for i in e.bad_positions]
        return {r.name: p for r, p in zip(part, plains)}, []

    def _split(self, refs: list, ciphertexts: dict):
        """Tiles under ``max_batch_bytes`` of ciphertext each."""
        part: list = []
        size = 0
        for ref in refs:
            n = len(ciphertexts[ref.name])
            if part and size + n > self.max_batch_bytes:
                yield part
                part, size = [], 0
            part.append(ref)
            size += n
        if part:
            yield part


def _covered_s(intervals: list) -> float:
    """Seconds covered by at least one of the (start, end) intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class _TileLoop:
    """One decode call's tile loop on the calling thread; tiles finish in
    the order they are pushed.

    Where the backend's ``fused`` hook is two-phase — it offers
    ``submit(ciphertexts, keys) -> handle``, and ``handle.result()``
    gives (digests, plaintexts) — up to ``PIPELINE_DEPTH`` tiles are in
    flight: a pushed tile is packed and dispatched (``repro.decode.submit``)
    before the loop waits on the tile before it (``repro.decode.tile``:
    readback, split, the digest check), so the host's work on one tile
    runs while the device works on the other. Otherwise each tile runs
    start to end through ``_decode_tile``.

    ``results`` holds ({name: plaintext}, [bad names]) per finished tile
    (no bad chunk's plaintext among them), ``intervals`` each tile's
    (start, end) from its submit to its finish, ``overlapped`` the
    tiles submitted while another was in flight
    (``decode.tiles_overlapped``)."""

    def __init__(self, decoder: BatchDecoder):
        self._decoder = decoder
        self._submit = getattr(decoder._fused, "submit", None)
        self._inflight: deque = deque()
        self.results: list = []
        self.intervals: list = []
        self.overlapped = 0

    def push(self, part: list, ciphertexts: dict) -> None:
        t0 = time.perf_counter()
        if self._submit is None:
            self.results.append(self._decoder._decode_tile(part, ciphertexts))
            self.intervals.append((t0, time.perf_counter()))
            return
        cts = [ciphertexts[r.name] for r in part]
        nbytes = sum(map(len, cts))
        with span("repro.decode.submit", chunks=len(cts), bytes=nbytes):
            handle = self._submit(cts, [r.key for r in part])
        if self._inflight:
            self.overlapped += 1
            COUNTERS.inc("decode.tiles_overlapped")
        self._inflight.append((part, nbytes, handle, t0))
        if len(self._inflight) >= PIPELINE_DEPTH:
            self._finish()

    def run(self, tiles, ciphertexts: dict) -> list:
        """Push every tile and finish them all; returns ``results``."""
        try:
            for part in tiles:
                self.push(part, ciphertexts)
        finally:
            self.drain()
        return self.results

    def drain(self) -> None:
        """Finish every tile in flight; then raise the first error one of
        them raised (a tampered chunk is a result, not an error)."""
        err = None
        while self._inflight:
            try:
                self._finish()
            except BaseException as e:
                if err is None:
                    err = e
        if err is not None:
            raise err

    def _finish(self) -> None:
        inflight = len(self._inflight)
        part, nbytes, handle, t0 = self._inflight.popleft()
        with span("repro.decode.tile", chunks=len(part), bytes=nbytes,
                  inflight=inflight):
            digests, plains = handle.result()
            try:
                convergent.check_digests(digests, [r.sha256 for r in part])
            except convergent.IntegrityError as e:
                self.results.append(
                    ({}, [part[i].name for i in e.bad_positions]))
            else:
                self.results.append(
                    ({r.name: p for r, p in zip(part, plains)}, []))
        self.intervals.append((t0, time.perf_counter()))
