"""The streaming fetch→decode pipeline and its hardened concurrency
harness: bounded-queue backpressure semantics, multi-thread stress with
byte-identity vs the serial oracle + single-flight dedup + queue-cap
invariants, hypothesis property tests for tiling and stream/staged
equivalence, tamper-mid-stream ordered error aggregation (with L1
eviction of bad ciphertexts), the ``decrypt_batch`` shared-state footgun
warning, and thread-exactness of the telemetry primitives."""
import hashlib
import random
import threading
import time
import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.cache.local import LocalCache
from repro.core.concurrency import BoundedQueue
from repro.core.crypto import aes, convergent
from repro.core.decode import BatchDecoder
from repro.core.loader import ImageReader
from repro.core.manifest import ZERO_CHUNK
from repro.core.telemetry import COUNTERS, Counters, LatencyRecorder

from test_batched_read import CS, KEY, CountingStore, image_truth, make_env

RNG = np.random.default_rng(123)


class Ref:
    """Synthetic ChunkRef with an arbitrary (non-content) name."""

    def __init__(self, name, enc):
        self.name, self.key, self.sha256 = name, enc.key, enc.sha256


def _synthetic_batch(lens, salt=b"salt" * 4):
    chunks = [RNG.integers(0, 256, L, dtype=np.uint8).tobytes() for L in lens]
    encs = [convergent.encrypt_chunk(c, salt) for c in chunks]
    refs = [Ref(f"c{i}", e) for i, e in enumerate(encs)]
    cts = {r.name: e.ciphertext for r, e in zip(refs, encs)}
    want = {f"c{i}": c for i, c in enumerate(chunks)}
    return refs, cts, want


# ----------------------------------------------------------- BoundedQueue

def test_bounded_queue_backpressure_order_and_high_water():
    q = BoundedQueue(2)
    got = []

    def consume():
        for item in q:
            got.append(item)
            time.sleep(0.001)       # slow consumer: producer must block

    t = threading.Thread(target=consume)
    t.start()
    for i in range(25):
        assert q.put(i) is True
    q.close()
    t.join()
    assert got == list(range(25))   # FIFO, nothing dropped or duplicated
    assert 1 <= q.high_water <= 2   # the bound held


def test_bounded_queue_poison_drains_then_raises():
    q = BoundedQueue(4)
    q.put("a")
    q.put("b")
    q.poison(ValueError("fetch blew up"))
    it = iter(q)
    assert next(it) == "a"          # queued items still delivered
    assert next(it) == "b"
    with pytest.raises(ValueError, match="fetch blew up"):
        next(it)


def test_bounded_queue_cancel_unblocks_producer():
    q = BoundedQueue(1)
    assert q.put(0) is True
    results = []

    def producer():
        results.append(q.put(1))    # blocks: queue is full

    t = threading.Thread(target=producer)
    t.start()
    time.sleep(0.02)
    assert not results              # really blocked
    q.cancel()
    t.join(timeout=2)
    assert results == [False]       # dropped, not delivered
    assert q.put(2) is False        # post-cancel puts drop immediately


# --------------------------------------------- streamed restore identity

def test_streamed_restore_matches_serial_and_staged_oracles(tmp_path):
    store, gc, tree, blob, _ = make_env(tmp_path)
    serial = ImageReader(blob, KEY, store).restore_tree(batched=False)
    staged = ImageReader(blob, KEY, store).restore_tree(streamed=False)
    r = ImageReader(blob, KEY, store)
    streamed = r.restore_tree()                     # streamed is the default
    for n in serial:
        assert np.array_equal(serial[n], streamed[n]), n
        assert np.array_equal(serial[n], staged[n]), n
    lb = r.reader.last_batch
    assert lb["streamed"] is True
    assert lb["queue_hwm"] <= lb["queue_depth"]
    assert lb["overlap_s"] >= 0.0
    assert lb["decode_tiles"] >= 1


def test_streamed_decoder_backends_identical(tmp_path):
    store, gc, tree, blob, _ = make_env(tmp_path)
    flats = [ImageReader(blob, KEY, store,
                         decoder=BatchDecoder(b)).restore_tree()
             for b in ("serial", "numpy", "jax")]
    for n, want in tree.items():
        for flat in flats:
            assert np.array_equal(flat[n], np.asarray(want)), n


# ------------------------------------------------------ concurrency stress

def test_streaming_stress_shared_reader_and_decoder(tmp_path):
    """N threads restore OVERLAPPING chunk sets through one shared
    TieredReader + one shared decoder, all in streaming mode: bytes must
    match the serial oracle, origin fetches must equal the unique misses
    (single-flight dedup), and the bounded hand-off queue must never
    exceed its cap."""
    store, gc, tree, blob, _ = make_env(tmp_path, store_cls=CountingStore,
                                        delay_s=0.002)
    l1 = LocalCache(64 << 20, name="l1stream")
    r = ImageReader(blob, KEY, store, l1=l1)
    truth = image_truth(tree)
    nchunks = r.layout.num_chunks
    rng = np.random.default_rng(42)
    depth = 4
    # overlapping subsets; union covers every chunk
    subsets = [sorted(rng.choice(nchunks, size=int(rng.integers(
        nchunks // 2, nchunks + 1)), replace=False).tolist())
        for _ in range(5)] + [list(range(nchunks))]
    # two staged calls race the streamed ones through the same flights
    modes = ["streamed"] * len(subsets) + ["staged", "staged"]
    subsets += [list(range(nchunks)), sorted(subsets[0])]
    COUNTERS.reset()
    store.gets = 0
    barrier = threading.Barrier(len(subsets))
    results, errs = [], []

    def work(idxs, mode):
        try:
            barrier.wait()
            out = r.reader.fetch_chunks(idxs, parallelism=4,
                                        streamed=mode == "streamed",
                                        queue_depth=depth)
            results.append((idxs, out))
        except Exception as e:      # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=work, args=(s, m))
               for s, m in zip(subsets, modes)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    union = set().union(*subsets)
    uniq = len({c.name for c in r.manifest.chunks
                if c.index in union and c.name != ZERO_CHUNK})
    assert store.gets == uniq       # one origin GET per unique missed name
    for idxs, out in results:
        assert sorted(out) == idxs
        for i in idxs:
            assert out[i] == truth[i * CS:(i + 1) * CS]
    hwm = COUNTERS.get("stream.queue_hwm")
    assert 1 <= hwm <= depth        # bounded queue held its cap
    assert r.reader._flights == {}  # nothing leaked


def test_streamed_through_l2_streaming_reconstruction(tmp_path):
    """With an L2 in the stack, the streamed path reconstructs each
    chunk at its k-th stripe (get_chunks on_ready) and stays
    byte-identical; a second cold-L1 streamed restore is served entirely
    from L2."""
    from repro.core.cache.distributed import DistributedCache

    store, gc, tree, blob, _ = make_env(tmp_path, store_cls=CountingStore)
    l2 = DistributedCache(num_nodes=6, seed=1)
    r1 = ImageReader(blob, KEY, store, l1=LocalCache(64 << 20, name="l1a"),
                     l2=l2)
    flat1 = r1.restore_tree()
    origin_gets = store.gets
    r2 = ImageReader(blob, KEY, store, l1=LocalCache(64 << 20, name="l1b"),
                     l2=l2)
    flat2 = r2.restore_tree()
    assert store.gets == origin_gets        # L2 absorbed the second restore
    for n, want in tree.items():
        assert np.array_equal(flat1[n], np.asarray(want)), n
        assert np.array_equal(flat2[n], np.asarray(want)), n


# ---------------------------------------------------- property: tiling

@settings(max_examples=50)
@given(st.lists(st.integers(min_value=0, max_value=300), min_size=0,
                max_size=40),
       st.integers(min_value=1, max_value=512))
def test_split_tiling_invariants(sizes, max_bytes):
    dec = BatchDecoder("numpy", max_batch_bytes=max_bytes)

    class R:
        def __init__(self, name):
            self.name = name

    refs = [R(f"c{i}") for i in range(len(sizes))]
    cts = {f"c{i}": b"x" * n for i, n in enumerate(sizes)}
    tiles = list(dec._split(refs, cts))
    # concatenated tiles == input order; no chunk dropped or duplicated
    assert [r for t in tiles for r in t] == refs
    for t in tiles:
        assert t                                  # never an empty tile
        total = sum(len(cts[r.name]) for r in t)
        # every tile fits the cap unless a single chunk alone exceeds it
        assert total <= dec.max_batch_bytes or len(t) == 1


@settings(max_examples=12)
@given(st.integers(min_value=0, max_value=10),
       st.integers(min_value=1, max_value=4096),
       st.integers(min_value=0, max_value=2 ** 30))
def test_stream_tiles_equal_staged_batch_any_order(nchunks, max_bytes, seed):
    """Streamed tiles decode to the same plaintexts as one staged batch
    regardless of arrival order."""
    rnd = random.Random(seed)
    lens = [rnd.randrange(0, 2048) for _ in range(nchunks)]
    refs, cts, want = _synthetic_batch(lens)
    staged = BatchDecoder("numpy", max_batch_bytes=max_bytes).decrypt_batch(
        refs, cts)
    order = list(range(nchunks))
    rnd.shuffle(order)
    q = BoundedQueue(nchunks + 1)
    for i in order:
        q.put((refs[i].name, cts[refs[i].name]))
    q.close()
    dec = BatchDecoder("numpy", max_batch_bytes=max_bytes)
    plains, stats = dec.decrypt_stream(q, {r.name: r for r in refs})
    assert plains == staged == want
    assert stats["busy_s"] >= 0.0


# ------------------------------------------- two-phase (pipelined) tiles

class TwoPhaseHook:
    """A two-phase ``fused`` hook (hashlib + the serial CTR oracle) that
    logs each ``submit`` and ``result`` and how many tiles were in
    flight; ``result()`` sleeps ``result_sleep_s`` first."""

    def __init__(self, result_sleep_s=0.0):
        self.log: list = []
        self.inflight = 0
        self.max_inflight = 0
        self.result_sleep_s = result_sleep_s

    def __call__(self, cts, keys):
        return self.submit(cts, keys).result()

    def submit(self, cts, keys):
        tile = sum(1 for op, _ in self.log if op == "submit")
        self.log.append(("submit", tile))
        self.inflight += 1
        self.max_inflight = max(self.max_inflight, self.inflight)
        hook = self

        class Handle:
            def result(self):
                time.sleep(hook.result_sleep_s)
                hook.log.append(("result", tile))
                hook.inflight -= 1
                return ([hashlib.sha256(c).digest() for c in cts],
                        [aes.ctr_decrypt(c, k) for c, k in zip(cts, keys)])
        return Handle()


def _decoder(hook, max_batch_bytes):
    """A single-threaded decoder whose ``fused`` hook is `hook`
    (``None``: the real ``bitsliced-fused`` adapter, XLA route)."""
    if hook is None:
        return BatchDecoder("bitsliced-fused", max_batch_bytes=max_batch_bytes)
    dec = BatchDecoder("python", max_batch_bytes=max_batch_bytes, threads=1)
    dec._fused = hook
    return dec


def _stream(refs, cts, order=None):
    q = BoundedQueue(len(refs) + 1)
    for r in (refs if order is None else [refs[i] for i in order]):
        q.put((r.name, cts[r.name]))
    q.close()
    return q


# 11 chunks in 2-chunk tiles: 6 tiles, the last one short
PIPE_LENS = [4096, 4096, 100, 4096, 4096, 4096, 55, 4096, 4096, 4096, 7]


@pytest.mark.parametrize("hook", ["stub", "adapter"])
def test_pipelined_tiles_match_serial_oracle(hook):
    """Streamed and staged decodes through a two-phase hook equal the
    serial oracle over 6 tiles, the last one short; at most 2 tiles
    are ever in flight, every tile but the first is submitted while the
    one before it is, and tile i+1 is submitted before tile i's result
    is read."""
    refs, cts, want = _synthetic_batch(PIPE_LENS)
    serial = BatchDecoder("serial").decrypt_batch(refs, cts)
    stub = TwoPhaseHook() if hook == "stub" else None
    dec = _decoder(stub, 2 * 4096)
    before = COUNTERS.get("decode.tiles_overlapped")
    plains, stats = dec.decrypt_stream(_stream(refs, cts),
                                       {r.name: r for r in refs})
    assert plains == serial == want
    assert stats["tiles"] == 6
    assert stats["tiles_overlapped"] == stats["tiles"] - 1
    assert COUNTERS.get("decode.tiles_overlapped") - before == 5
    assert dec.decrypt_batch(refs, cts) == want
    if stub is not None:
        assert stub.max_inflight == 2 and stub.inflight == 0
        # stream, then the staged batch: each submits tile i+1 first
        for start in (0, 6):
            ops = [op for op in stub.log if start <= op[1] < start + 6]
            assert ops[:3] == [("submit", start), ("submit", start + 1),
                               ("result", start)]
            assert ops[-1] == ("result", start + 5)


@pytest.mark.parametrize("hook", ["stub", "adapter"])
def test_pipelined_tamper_names_only_the_bad_chunk(hook):
    """A chunk tampered in a middle tile is the only name in the
    ``IntegrityError``, and its tile releases no plaintext, while the
    tiles around it still decode."""
    from repro.core.decode import _TileLoop

    refs, cts, want = _synthetic_batch(PIPE_LENS)
    victim = refs[5].name                   # tile 2 of 6
    bad = dict(cts)
    mid = len(bad[victim]) // 2
    bad[victim] = (bad[victim][:mid] + bytes([bad[victim][mid] ^ 0x10])
                   + bad[victim][mid + 1:])
    dec = _decoder(TwoPhaseHook() if hook == "stub" else None, 2 * 4096)
    with pytest.raises(convergent.IntegrityError) as ei:
        dec.decrypt_stream(_stream(refs, bad), {r.name: r for r in refs})
    assert ei.value.bad_positions == [victim]
    tiles = list(dec._split(refs, bad))
    results = _TileLoop(dec).run(tiles, bad)
    assert [b for _, b in results] == [[], [], [victim], [], [], []]
    released = {n: p for plains, _ in results for n, p in plains.items()}
    assert victim not in released and refs[4].name not in released
    assert released == {n: p for n, p in want.items()
                        if n not in (victim, refs[4].name)}


def test_pipelined_poisoned_queue_raises_after_inflight_tile():
    """A fetch failure that reaches the consumer while a tile is in
    flight re-raises only once that tile's result has been read."""
    refs, cts, _ = _synthetic_batch([4096] * 5)
    stub = TwoPhaseHook(result_sleep_s=0.05)
    dec = _decoder(stub, 2 * 4096)
    q = BoundedQueue(8)
    for r in refs:
        q.put((r.name, cts[r.name]))
    q.poison(OSError("origin went away"))
    with pytest.raises(OSError, match="origin went away"):
        dec.decrypt_stream(q, {r.name: r for r in refs})
    # tiles 0 and 1 were submitted; tile 1 was in flight at the poison
    assert stub.log == [("submit", 0), ("submit", 1), ("result", 0),
                        ("result", 1)]
    assert stub.inflight == 0


def test_pipelined_busy_is_the_union_of_tiles_in_flight():
    """Overlapping tiles count once: with each result taking 30 ms,
    ``busy_s`` covers every wait but never passes the stream's wall
    (summing each tile's submit-to-result time would: the next tile is
    in flight through each wait)."""
    refs, cts, _ = _synthetic_batch([4096] * 12)
    stub = TwoPhaseHook(result_sleep_s=0.03)
    plains, stats = _decoder(stub, 2 * 4096).decrypt_stream(
        _stream(refs, cts), {r.name: r for r in refs})
    assert len(plains) == 12 and stats["tiles"] == 6
    assert 6 * 0.03 <= stats["busy_s"] <= stats["wall_s"]


# --------------------------------------------------- tamper mid-stream

class CorruptingStore(CountingStore):
    """Flips the first byte of any chunk whose name is in `corrupt`."""

    corrupt: set = frozenset()

    def get_chunk(self, root, name):
        data = super().get_chunk(root, name)
        if name in self.corrupt:
            return bytes([data[0] ^ 0xFF]) + data[1:]
        return data


def test_tamper_mid_stream_names_all_bad_chunks_and_recovers(tmp_path):
    store, gc, tree, blob, _ = make_env(tmp_path, store_cls=CorruptingStore,
                                        delay_s=0.002)
    l1 = LocalCache(64 << 20, name="l1tms")
    # 1-chunk tiles: the two bad chunks land in DIFFERENT tiles, and the
    # error must still aggregate across all of them
    r = ImageReader(blob, KEY, store, l1=l1,
                    decoder=BatchDecoder("numpy", max_batch_bytes=CS))
    refs = [c for c in r.manifest.chunks if c.name != ZERO_CHUNK]
    names = list(dict.fromkeys(c.name for c in refs))
    bad = {names[-1], names[-2]}    # fetched last -> arrive late in stream
    store.corrupt = bad
    with pytest.raises(convergent.IntegrityError) as ei:
        r.reader.fetch_chunks([c.index for c in refs], parallelism=2,
                              streamed=True, queue_depth=2)
    # ordered, complete aggregation: every bad chunk named, across tiles
    assert ei.value.bad_positions == sorted(bad)
    # the tampered ciphertexts were evicted from L1 (no poisoned cache)
    for n in bad:
        assert l1.peek(n) is None
    assert r.reader._flights == {}
    # origin healed: the retry refetches the evicted names and succeeds
    store.corrupt = frozenset()
    out = r.reader.fetch_chunks([c.index for c in refs], streamed=True)
    truth = image_truth(tree)
    for i, plain in out.items():
        assert plain == truth[i * CS:(i + 1) * CS]


def test_tamper_served_from_l2_evicts_stripes_and_recovers(tmp_path):
    """Bad bytes living in L2 (not origin) must not be replayed forever:
    the integrity failure evicts the chunk's stripes from every L2 node,
    so the retry goes back to origin and succeeds."""
    from repro.core.cache.distributed import DistributedCache

    store, gc, tree, blob, _ = make_env(tmp_path)
    l1 = LocalCache(64 << 20, name="l1l2t")
    l2 = DistributedCache(num_nodes=5, seed=9)
    r = ImageReader(blob, KEY, store, l1=l1, l2=l2)
    victim = next(c for c in r.manifest.chunks if c.name != ZERO_CHUNK)
    l2.put_chunk(victim.name, b"\xee" * CS)     # corrupted-in-place L2 copy
    with pytest.raises(convergent.IntegrityError):
        r.reader.fetch_chunks(list(range(r.layout.num_chunks)),
                              streamed=True)
    assert l1.peek(victim.name) is None         # L1 copy evicted
    assert l2.get_chunk(victim.name, CS)[1] is None   # L2 stripes evicted
    truth = image_truth(tree)
    out = r.reader.fetch_chunks(list(range(r.layout.num_chunks)),
                                streamed=True)
    for i, plain in out.items():
        assert plain == truth[i * CS:(i + 1) * CS]


def test_tamper_staged_path_also_evicts_from_l1(tmp_path):
    store, gc, tree, blob, _ = make_env(tmp_path, store_cls=CorruptingStore)
    l1 = LocalCache(64 << 20, name="l1tss")
    r = ImageReader(blob, KEY, store, l1=l1)
    victim = next(c for c in r.manifest.chunks if c.name != ZERO_CHUNK)
    store.corrupt = {victim.name}
    with pytest.raises(convergent.IntegrityError):
        r.reader.fetch_chunks(list(range(r.layout.num_chunks)),
                              streamed=False)
    assert l1.peek(victim.name) is None
    store.corrupt = frozenset()
    truth = image_truth(tree)
    out = r.reader.fetch_chunks(list(range(r.layout.num_chunks)))
    for i, plain in out.items():
        assert plain == truth[i * CS:(i + 1) * CS]


# ------------------------------------- decrypt_batch shared-state footgun

def test_decrypt_batch_concurrent_stampede_warns_once():
    refs, cts, want = _synthetic_batch([CS] * 8)
    dec = BatchDecoder("numpy")
    orig = dec.decrypt_batch_timed

    def slow_timed(r, c):           # guarantee the calls really overlap
        time.sleep(0.05)
        return orig(r, c)

    dec.decrypt_batch_timed = slow_timed
    barrier = threading.Barrier(4)
    outs, errs = [], []

    def work():
        try:
            barrier.wait()
            outs.append(dec.decrypt_batch(refs, cts))
        except Exception as e:      # pragma: no cover
            errs.append(e)

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errs
    assert all(o == want for o in outs)         # results stay correct
    hits = [w for w in caught if issubclass(w.category, RuntimeWarning)
            and "concurrently" in str(w.message)]
    assert len(hits) == 1                       # one-time warning, not N
    # a second stampede stays silent (already warned on this decoder)
    with warnings.catch_warnings(record=True) as again:
        warnings.simplefilter("always")
        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not [w for w in again if issubclass(w.category, RuntimeWarning)]


def test_decrypt_batch_timed_never_touches_last_wall():
    refs, cts, want = _synthetic_batch([100, 200])
    dec = BatchDecoder("numpy")
    out = dec.decrypt_batch(refs, cts)
    wall_after_batch = dec.last_wall_s
    assert out == want and wall_after_batch > 0.0
    out2, wall = dec.decrypt_batch_timed(refs, cts)
    assert out2 == want and wall > 0.0
    assert dec.last_wall_s == wall_after_batch  # untouched


# ------------------------------------------------------------- telemetry

def test_counters_exact_totals_under_8_thread_hammer():
    COUNTERS.reset()
    n_threads, iters = 8, 5000
    barrier = threading.Barrier(n_threads)

    def work(tid):
        barrier.wait()
        for i in range(iters):
            COUNTERS.inc("hammer.x")
            COUNTERS.add("hammer.y", 2.0)
            COUNTERS.max_update("hammer.z", tid * iters + i)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert COUNTERS.get("hammer.x") == n_threads * iters
    assert COUNTERS.get("hammer.y") == 2.0 * n_threads * iters
    assert COUNTERS.get("hammer.z") == (n_threads - 1) * iters + iters - 1
    snap = COUNTERS.snapshot()
    assert snap["hammer.x"] == n_threads * iters
    COUNTERS.reset()


def test_latency_recorder_concurrent_record_and_read():
    rec = LatencyRecorder("hammer")
    stop = threading.Event()
    reader_errs = []

    def read_loop():
        try:
            while not stop.is_set():
                rec.summary()
                rec.percentile(50)
                rec.ecdf(16)
        except Exception as e:      # pragma: no cover
            reader_errs.append(e)

    writers = [threading.Thread(
        target=lambda: [rec.record(1e-3) for _ in range(4000)])
        for _ in range(7)]
    reader = threading.Thread(target=read_loop)
    reader.start()
    for w in writers:
        w.start()
    for w in writers:
        w.join()
    stop.set()
    reader.join()
    assert not reader_errs
    assert rec.summary()["n"] == 7 * 4000       # every sample retained
    assert rec.percentile(50) == pytest.approx(1e-3)


def test_counters_max_update_monotonic():
    c = Counters()
    c.inc("a")
    c.max_update("b", 5)
    c.max_update("b", 3)            # lower value must not regress the max
    assert c.get("a") == 1 and c.get("b") == 5
