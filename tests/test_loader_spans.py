"""The loader's profiler spans and transfer counters: a cold start and a
publish of a tiny image (8 chunks of 4 KiB) under a ``jax.profiler``
capture. The spans nest as the layers call each other, every span of one
cold start or publish carries its request id on whichever thread it ran,
the ``h2d_bytes``/``d2h_bytes`` stats equal what the adapters copy and the
``xfer.*`` counters, and a producer held on a full hand-off queue counts
as blocked, not as fetching.

All captures live in this one file: a process holds one profiler."""
import contextlib
import threading
import time
import warnings

import jax
import numpy as np
import pytest

from repro.core.concurrency import BoundedQueue
from repro.core.decode import BatchDecoder
from repro.core.gc import GenerationalGC
from repro.core.loader import create_image
from repro.core.service import ImageService, ReadPolicy, ServiceConfig
from repro.core.store import ChunkStore
from repro.core.telemetry import (
    COUNTERS,
    D2H_BYTES,
    H2D_BYTES,
    bind_request,
    request_scope,
    span,
)
from repro.kernels.fused import ops as fused_ops
from repro.kernels.sha256.ops import pack_messages
from repro.serve.coldstart import cold_start

CS = 4096
KEY = b"S" * 32


class _TinyModel:
    """Enough of a model for ``cold_start`` (the engine is never
    stepped)."""

    class cfg:
        vocab_size = 8

    def __init__(self, template):
        self._template = template

    def param_shapes(self):
        return self._template

    def init_decode_state(self, max_batch, max_len):
        return {"pos": np.zeros((max_batch,), np.int32)}

    def decode_step(self, params, state, tokens, pos):  # pragma: no cover
        raise NotImplementedError


@contextlib.contextmanager
def capture(tmp_path):
    """Profile the block; yields a list filled on exit with every host
    event named ``repro.*`` as dicts (name, start, end, stats, line:
    the thread's line on the host plane)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    out: list = []
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    (path,) = (tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    with warnings.catch_warnings():     # the stats' type warns on reading
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if plane.name != "/host:CPU":
                continue
            for line_no, line in enumerate(plane.lines):
                out.extend({"name": e.name, "start": e.start_ns,
                            "end": e.start_ns + e.duration_ns,
                            "stats": dict(e.stats), "line": line_no}
                           for e in line.events
                           if e.name.startswith("repro."))


def named(events, name):
    return [e for e in events if e["name"] == name]


def inside(inner, outer) -> bool:
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


def image(tmp_path, store_cls=ChunkStore):
    """A tree of 8 chunks of 4 KiB in an on-disk store: (store, root,
    blob, tree, template)."""
    store = store_cls(tmp_path / "store")
    root = GenerationalGC(store).active
    rng = np.random.default_rng(13)
    tree = {"a": rng.standard_normal(5 * 1024).astype(np.float32),
            "b": rng.standard_normal(3 * 1024).astype(np.float32)}
    blob, _ = create_image(tree, tenant="t", tenant_key=KEY, store=store,
                           root=root, chunk_size=CS)
    template = jax.eval_shape(lambda: {k: np.zeros_like(v)
                                       for k, v in tree.items()})
    return store, root, blob, tree, template


# ------------------------------------------------------------- helper

def test_request_ids_reach_threads_only_when_bound(tmp_path):
    def work(tag):
        with span("repro.test", tag=tag):
            pass

    def in_thread(fn):
        t = threading.Thread(target=fn)
        t.start()
        t.join()

    with capture(tmp_path) as events:
        with request_scope() as first:
            work("caller")
            in_thread(bind_request(lambda: work("bound")))
            in_thread(lambda: work("plain"))
        with request_scope() as second:
            work("next")
        work("outside")
    got = {e["stats"]["tag"]: e["stats"].get("request") for e in events}
    assert got == {"caller": first, "bound": first, "plain": None,
                   "next": second, "outside": None}
    assert second != first


def test_queue_adds_up_its_waits_only_when_it_blocks():
    q = BoundedQueue(1)
    q.put(1)
    assert q.get() == 1
    assert q.put_wait_s == 0.0 and q.get_wait_s == 0.0
    q.put(2)
    threading.Timer(0.1, q.get).start()
    q.put(3)                                # blocks until the timer's get
    assert q.put_wait_s >= 0.05
    assert q.get() == 3
    threading.Timer(0.1, lambda: q.put(4)).start()
    assert q.get() == 4                     # blocks until the timer's put
    assert q.get_wait_s >= 0.05


# --------------------------------------------------------- cold start

def test_coldstart_spans_nest_and_carry_the_request(tmp_path):
    store, root, blob, tree, template = image(tmp_path)
    # the two-pass Pallas backend, interpreted on the CPU: both kernel
    # adapters (SHA-256, bitsliced AES) run, two chunks per tile
    service = ImageService(store, ServiceConfig(
        root=root, l2_nodes=0, decode_backend="bitsliced",
        max_batch_bytes=2 * CS))
    before = COUNTERS.snapshot()
    try:
        with capture(tmp_path) as events:
            engine, stats = cold_start(_TinyModel(template), blob, KEY,
                                       service)
    finally:
        service.close()
    after = COUNTERS.snapshot()
    for k, v in tree.items():
        assert np.array_equal(np.asarray(engine.params[k]), v)

    (top,) = named(events, "repro.coldstart")
    rid = top["stats"]["request"]
    assert top["stats"]["image_bytes"] == 8 * CS
    assert top["stats"]["tile_bytes"] == 2 * CS
    for name in ("repro.coldstart.admit", "repro.coldstart.open",
                 "repro.restore", "repro.coldstart.place",
                 "repro.restore.assemble", "repro.fetch.l1",
                 "repro.fetch.origin", "repro.decode.tile",
                 "repro.kernel.pack", "repro.kernel.dispatch",
                 "repro.kernel.readback", "repro.kernel.split"):
        assert named(events, name), name
    # every span of the start carries its request, on every thread:
    # the stream-fetch thread's L1 probe and the pool's origin GETs too
    assert {e["stats"].get("request") for e in events} == {rid}
    assert {e["line"] for e in named(events, "repro.fetch.origin")} \
        - {top["line"]}
    assert len(named(events, "repro.fetch.origin")) == 8
    assert sum(e["stats"]["bytes"]
               for e in named(events, "repro.fetch.origin")) == 8 * CS

    (restore,) = named(events, "repro.restore")
    assert restore["stats"]["chunks"] == 8 and inside(restore, top)
    tiles = named(events, "repro.decode.tile")
    assert len(tiles) == 4 and all(t["stats"]["chunks"] == 2 for t in tiles)
    assert all(inside(t, restore) for t in tiles)
    for k in (e for e in events if e["name"].startswith("repro.kernel.")):
        assert any(inside(k, t) and k["line"] == t["line"] for t in tiles)
    (place,) = named(events, "repro.coldstart.place")
    assert inside(place, top) and place["stats"]["h2d_bytes"] == 8 * CS

    # the counters moved by what the spans say was copied
    for counter, stat in ((H2D_BYTES, "h2d_bytes"), (D2H_BYTES, "d2h_bytes")):
        assert after.get(counter, 0) - before.get(counter, 0) == sum(
            e["stats"].get(stat, 0) for e in events) > 0
    assert {"fetch_busy_s", "fetch_blocked_s", "decode_starved_s",
            "overlap_s"} <= set(stats)
    assert not {"fetch_wall_s", "sim_pipelined_s", "sim_serial_s",
                "l2_sim_latency_p50"} & set(stats)


def test_fused_adapter_counts_what_it_copies(tmp_path, monkeypatch):
    """``h2d_bytes`` is the packed message buffer, the block counts and
    the round-key planes; ``d2h_bytes`` the digests and plaintext rows.
    The device pass is stubbed: only the adapter's accounting is under
    test here."""
    rng = np.random.default_rng(5)
    cts = [rng.integers(0, 256, n, np.uint8).tobytes()
           for n in (CS, CS, 1000)]
    keys = [bytes(rng.integers(0, 256, 32, np.uint8)) for _ in cts]
    buf, nb = pack_messages(cts)
    rk = fused_ops.round_key_planes(keys, buf.shape[0])
    out = (jax.numpy.zeros((8, buf.shape[0]), np.int32),
           jax.numpy.zeros(buf.shape, np.int32))
    monkeypatch.setattr(fused_ops, "_fused_device", lambda *a, **k: out)
    before = COUNTERS.snapshot()
    with capture(tmp_path) as events:
        fused_ops.fused_verify_decrypt(cts, keys, pallas=False)
    after = COUNTERS.snapshot()
    (dispatch,) = named(events, "repro.kernel.dispatch")
    (readback,) = named(events, "repro.kernel.readback")
    h2d = buf.nbytes + nb.nbytes + rk.nbytes
    d2h = out[0].nbytes + out[1].nbytes
    assert dispatch["stats"]["h2d_bytes"] == h2d
    assert readback["stats"]["d2h_bytes"] == d2h
    assert after[H2D_BYTES] - before.get(H2D_BYTES, 0) == h2d
    assert after[D2H_BYTES] - before.get(D2H_BYTES, 0) == d2h


def test_fused_submits_count_each_tile_as_before(tmp_path, monkeypatch):
    """Two tiles submitted before either result is read: each tile's
    dispatch and readback spans carry the bytes of its own copies, and
    the ``xfer.*`` counters move by the sum, as two one-call passes
    would move them."""
    rng = np.random.default_rng(6)
    tiles = []
    for lens in ((CS, CS, 1000), (CS, 17)):
        cts = [rng.integers(0, 256, n, np.uint8).tobytes() for n in lens]
        tiles.append((cts, [bytes(rng.integers(0, 256, 32, np.uint8))
                            for _ in cts]))
    h2d, d2h = [], []
    for cts, keys in tiles:
        buf, nb = pack_messages(cts)
        rk = fused_ops.round_key_planes(keys, buf.shape[0])
        h2d.append(buf.nbytes + nb.nbytes + rk.nbytes)
        # int32 digest words (8 a lane) and plaintext rows like `buf`
        d2h.append(8 * buf.shape[0] * 4 + buf.nbytes)

    def device(buf, nb, rk, **kw):
        return (jax.numpy.zeros((8, buf.shape[0]), np.int32),
                jax.numpy.zeros(buf.shape, np.int32))
    monkeypatch.setattr(fused_ops, "_fused_device", device)
    before = COUNTERS.snapshot()
    with capture(tmp_path) as events:
        handles = [fused_ops.submit(cts, keys, pallas=False)
                   for cts, keys in tiles]
        for handle in handles:
            handle.result()
    after = COUNTERS.snapshot()
    dispatch = sorted(named(events, "repro.kernel.dispatch"),
                      key=lambda e: e["start"])
    readback = sorted(named(events, "repro.kernel.readback"),
                      key=lambda e: e["start"])
    assert [e["stats"]["h2d_bytes"] for e in dispatch] == h2d
    assert [e["stats"]["d2h_bytes"] for e in readback] == d2h
    assert dispatch[1]["end"] <= readback[0]["start"]
    assert after[H2D_BYTES] - before.get(H2D_BYTES, 0) == sum(h2d)
    assert after[D2H_BYTES] - before.get(D2H_BYTES, 0) == sum(d2h)


def test_fused_coldstart_spans_nest_per_thread(tmp_path):
    """A cold start through the fused backend (XLA route on the CPU),
    two chunks per tile, four tiles: each tile is a ``repro.decode.submit``
    span (pack, dispatch) and a later ``repro.decode.tile`` span
    (readback, split, digest check); no two ``repro.decode.*`` spans of
    one thread overlap, and every ``repro.kernel.*`` span lies inside a
    ``repro.decode.*`` span of its thread. Tiles 2-4 were submitted
    while the one before was in flight."""
    store, root, blob, tree, template = image(tmp_path)
    service = ImageService(store, ServiceConfig(
        root=root, l2_nodes=0, decode_backend="bitsliced-fused",
        max_batch_bytes=2 * CS))
    before = COUNTERS.snapshot()
    try:
        with capture(tmp_path) as events:
            engine, stats = cold_start(_TinyModel(template), blob, KEY,
                                       service)
    finally:
        service.close()
    after = COUNTERS.snapshot()
    for k, v in tree.items():
        assert np.array_equal(np.asarray(engine.params[k]), v)
    assert stats["decode_tiles"] == 4 and stats["tiles_overlapped"] == 3
    assert after["decode.tiles_overlapped"] - before.get(
        "decode.tiles_overlapped", 0) == 3

    (restore,) = named(events, "repro.restore")
    submits = named(events, "repro.decode.submit")
    tiles = named(events, "repro.decode.tile")
    assert len(submits) == len(tiles) == 4
    assert all(e["stats"]["chunks"] == 2 and e["stats"]["bytes"] == 2 * CS
               for e in submits + tiles)
    assert sorted(t["stats"]["inflight"] for t in tiles) == [1, 2, 2, 2]
    decode = submits + tiles
    assert all(inside(e, restore) for e in decode)
    for a in decode:
        for b in decode:
            if a is not b and a["line"] == b["line"]:
                assert a["end"] <= b["start"] or b["end"] <= a["start"]
    kernel = [e for e in events if e["name"].startswith("repro.kernel.")]
    assert len(kernel) == 16
    for k in kernel:
        assert any(inside(k, d) and k["line"] == d["line"] for d in decode)
    for name, outer in (("repro.kernel.pack", submits),
                        ("repro.kernel.dispatch", submits),
                        ("repro.kernel.readback", tiles),
                        ("repro.kernel.split", tiles)):
        assert all(any(inside(k, d) for d in outer)
                   for k in named(events, name)), name
    for counter, stat in ((H2D_BYTES, "h2d_bytes"), (D2H_BYTES, "d2h_bytes")):
        assert after.get(counter, 0) - before.get(counter, 0) == sum(
            e["stats"].get(stat, 0) for e in events) > 0


class _SlowStore(ChunkStore):
    """Chunk GETs take `delay_s` each."""

    delay_s = 0.0

    def get_chunk(self, root, name, **kw):
        time.sleep(self.delay_s)
        return super().get_chunk(root, name, **kw)


@pytest.mark.parametrize("slow", ["decode", "fetch"])
def test_handoff_waits_are_blocked_or_starved_not_busy(tmp_path,
                                                       monkeypatch, slow):
    """A slow decode holds the producer on a one-slot queue: that time is
    ``fetch_blocked_s`` (the ``repro.stream.put_wait`` spans), not
    ``fetch_busy_s``. A slow origin leaves the decoder waiting:
    ``decode_starved_s`` (``repro.stream.get_wait``)."""
    store, root, blob, _, template = image(tmp_path, _SlowStore)
    if slow == "decode":
        tile = BatchDecoder._decode_tile

        def slow_tile(self, part, cts):
            time.sleep(0.05)
            return tile(self, part, cts)
        monkeypatch.setattr(BatchDecoder, "_decode_tile", slow_tile)
    else:
        store.delay_s = 0.05
    # one decode thread: the consumer decodes each tile itself
    service = ImageService(store, ServiceConfig(root=root, l2_nodes=0,
                                                decode_threads=1))
    policy = ReadPolicy(parallelism=1, queue_depth=1, max_batch_bytes=CS,
                        decode_backend="python")
    try:
        with capture(tmp_path) as events:
            _, stats = cold_start(_TinyModel(template), blob, KEY, service,
                                  policy=policy)
    finally:
        service.close()
    waited = {name: sum(e["end"] - e["start"] for e in named(events, name))
              / 1e9 for name in ("repro.stream.put_wait",
                                 "repro.stream.get_wait")}
    assert waited["repro.stream.put_wait"] == pytest.approx(
        stats["fetch_blocked_s"], abs=0.01)
    assert waited["repro.stream.get_wait"] == pytest.approx(
        stats["decode_starved_s"], abs=0.01)
    if slow == "decode":        # 8 tiles of 50 ms, fetch only waits
        assert stats["fetch_blocked_s"] > 0.2
        assert stats["fetch_busy_s"] < 0.1 < stats["decode_wall_s"]
    else:                       # 8 GETs of 50 ms, decode only waits
        assert stats["fetch_busy_s"] > 0.3
        assert stats["decode_starved_s"] > 0.3
        assert stats["fetch_blocked_s"] < 0.05


# ------------------------------------------------------------ publish

def test_publish_spans_nest_and_carry_the_request(tmp_path):
    store = ChunkStore(tmp_path / "store")
    root = GenerationalGC(store).active
    rng = np.random.default_rng(21)
    tree = {"w": rng.standard_normal(8 * 1024).astype(np.float32)}
    service = ImageService(store, ServiceConfig(root=root, l2_nodes=0,
                                                upload_parallelism=2))
    try:
        with capture(tmp_path) as events:
            service.publish(tree, tenant="t", tenant_key=KEY, chunk_size=CS)
    finally:
        service.close()
    (top,) = named(events, "repro.publish")
    assert top["stats"]["image_bytes"] == 8 * CS
    rid = top["stats"]["request"]
    assert {e["stats"].get("request") for e in events} == {rid}
    stages = ("chunk", "derive_keys", "probe", "encrypt", "upload", "seal")
    for stage in stages:
        spans = named(events, f"repro.publish.{stage}")
        assert spans and all(inside(s, top) for s in spans), stage
    uploads = named(events, "repro.publish.upload")
    assert sum(s["stats"]["chunks"] for s in uploads) == 8
    assert {s["line"] for s in uploads} - {top["line"]}
    (derive,) = named(events, "repro.publish.derive_keys")
    assert derive["stats"] == {"chunks": 8, "bytes": 8 * CS, "request": rid}
