"""The ImageService / ImageHandle / ReadPolicy client API: multi-tenant
concurrency over one shared service (byte identity, cross-tenant dedup
in scoped telemetry, process-wide single-flight), admission control
under real concurrency, the idle-queue eager flush, policy plumbing
through prefetch / restore_share, the float32 serving-dtype cast,
and the ImageReader deprecation shim's equivalence."""
import threading
import time

import numpy as np
import pytest

import jax

from repro.core.cache.local import LocalCache
from repro.core.gc import GenerationalGC
from repro.core.loader import ImageReader, create_image
from repro.core.manifest import ZERO_CHUNK
from repro.core.service import (
    ColdStartRejected,
    ImageService,
    ReadPolicy,
    ServiceConfig,
)
from repro.core.store import ChunkStore
from repro.core.telemetry import COUNTERS, Counters
from repro.serve.coldstart import cold_start

CS = 4096


# ------------------------------------------------------------ fixtures

def make_tenant_images(store, root, *, rows=16, seed=3):
    """3 images / 2 tenants sharing one base tensor (convergent chunk
    names make the base dedup across tenants)."""
    rng = np.random.default_rng(seed)
    base = rng.standard_normal((rows, 1024)).astype(np.float32)
    specs = [
        ("tenantA", b"A" * 32),
        ("tenantA", b"A" * 32),
        ("tenantB", b"B" * 32),
    ]
    images = []
    for i, (tenant, key) in enumerate(specs):
        tree = {"base": base,
                "delta": rng.standard_normal((2, 1024)).astype(np.float32)}
        blob, stats = create_image(tree, tenant=tenant, tenant_key=key,
                                   store=store, root=root, chunk_size=CS,
                                   image_id=f"img{i}")
        images.append((tenant, key, tree, blob, stats))
    return images


class _TinyModel:
    """Minimal model for cold_start: enough surface for ServeEngine
    construction (decode_step is never stepped in these tests)."""

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


class GatedStore(ChunkStore):
    """Chunk GETs block on `gate` — holds accepted cold-starts in-flight
    so admission rejections become deterministic."""

    def __init__(self, root_dir):
        super().__init__(root_dir)
        self.gate = threading.Event()

    def get_chunk(self, root, name):
        self.gate.wait(timeout=30)
        return super().get_chunk(root, name)


# ------------------------------------------- multi-tenant shared service

def test_multitenant_concurrent_coldstarts_shared_service(tmp_path):
    """The acceptance scenario: >=3 distinct images from >=2 tenants
    cold-started concurrently over ONE shared service, byte-identical to
    the per-image serial oracles, cross-tenant L1 dedup visible in
    scoped telemetry, origin traffic bounded by the unique chunk union."""
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    images = make_tenant_images(store, gc.active)
    oracles = [ImageReader(blob, key, store).restore_tree(batched=False)
               for _, key, _, blob, _ in images]

    service = ImageService(store, ServiceConfig(
        l1_bytes=64 << 20, l2_nodes=0, fetch_concurrency=16,
        max_coldstarts=16))
    before = COUNTERS.snapshot()
    results: dict = {}
    errs: list = []
    jobs = [i for i in range(len(images)) for _ in range(2)]   # M = 6
    barrier = threading.Barrier(len(jobs))

    def work(slot, i):
        try:
            tenant, key, _, blob, _ = images[i]
            barrier.wait()
            h = service.open(blob, key)
            results[slot] = (i, h.restore_tree())
        except Exception as e:      # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=work, args=(s, i))
               for s, i in enumerate(jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs and len(results) == len(jobs)
    for _slot, (i, flat) in results.items():
        for n in oracles[i]:
            assert np.array_equal(flat[n], oracles[i][n]), (i, n)

    after = COUNTERS.snapshot()

    def delta(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    origin = delta("read.origin_fetches")
    # shared L1 + the service-wide FlightTable bound origin traffic by
    # the unique chunk-name union across images AND tenants
    unique_union = sum(s.unique_chunks for *_x, s in images)
    assert origin == unique_union, (origin, unique_union)
    # cross-tenant dedup observable: both tenants did reads, but the
    # union was fetched once — and every origin fetch is attributed to
    # exactly one tenant scope
    assert delta("tenant.tenantA::read.batched_chunks") > 0
    assert delta("tenant.tenantB::read.batched_chunks") > 0
    assert delta("tenant.tenantA::read.origin_fetches") + \
        delta("tenant.tenantB::read.origin_fetches") == origin


def test_cross_tenant_l1_hits_in_scoped_telemetry(tmp_path):
    """Tenant A warms the shared L1; tenant B's FIRST read then scores
    scoped L1 hits on the shared base chunks it never fetched — the
    Fig 5 cross-customer dedup, observable per tenant."""
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    images = make_tenant_images(store, gc.active)
    service = ImageService(store, ServiceConfig(
        l1_bytes=64 << 20, l2_nodes=0, fetch_concurrency=0,
        max_coldstarts=0))
    tenant, key, tree, blob, _ = images[0]          # tenantA
    service.open(blob, key).restore_tree()
    mark = COUNTERS.snapshot()
    tenant_b, key_b, tree_b, blob_b, stats_b = images[2]
    flat = service.open(blob_b, key_b).restore_tree()
    for n in tree_b:
        assert np.array_equal(flat[n], np.asarray(tree_b[n]))
    after = COUNTERS.snapshot()

    def delta(name):
        return after.get(name, 0.0) - mark.get(name, 0.0)

    base_chunks = stats_b.dedup_chunks        # chunks shared with tenantA
    assert base_chunks > 0
    assert delta("tenant.tenantB::read.l1_hits") >= base_chunks
    # tenantB only went to origin for its own unique delta chunks
    assert delta("tenant.tenantB::read.origin_fetches") == stats_b.unique_chunks
    # tenantA idle during B's read
    assert delta("tenant.tenantA::read.l1_hits") == 0


def test_same_image_handles_share_reader_and_singleflight(tmp_path):
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    images = make_tenant_images(store, gc.active)
    _, key, tree, blob, stats = images[0]
    service = ImageService(store, ServiceConfig(l1_bytes=64 << 20,
                                                l2_nodes=0))
    h1 = service.open(blob, key)
    h2 = service.open(blob, key)
    assert h1.reader is h2.reader       # one session substrate per image
    before = COUNTERS.get("read.origin_fetches")
    flat1 = h1.restore_tree()
    flat2 = h2.restore_tree(policy=ReadPolicy(mode="staged"))
    fetched = COUNTERS.get("read.origin_fetches") - before
    assert fetched == stats.unique_chunks
    for n in tree:
        assert np.array_equal(flat1[n], flat2[n])


# ------------------------------------------------------ admission control

def test_admission_rejects_exactly_the_excess_under_concurrency(tmp_path):
    """M > max_coldstarts simultaneous cold_starts through one shared
    service: exactly M - max_coldstarts rejections
    (serve.coldstart_rejected), accepted restores byte-identical."""
    store = GatedStore(tmp_path / "s")
    gc = GenerationalGC(store)
    rng = np.random.default_rng(0)
    tree = {"w": rng.standard_normal((2048,)).astype(np.float32)}
    store.gate.set()                    # creation writes need no gate
    blob, _ = create_image(tree, tenant="t", tenant_key=b"T" * 32,
                           store=store, root=gc.active, chunk_size=CS)
    oracle = ImageReader(blob, b"T" * 32, store).restore_tree(batched=False)
    store.gate.clear()                  # now hold every origin GET

    maxc, m = 2, 6
    service = ImageService(store, ServiceConfig(
        l1_bytes=0, l2_nodes=0, fetch_concurrency=0, max_coldstarts=maxc))
    model = _TinyModel(jax.eval_shape(
        lambda: {"w": np.zeros((2048,), np.float32)}))
    before_rej = COUNTERS.get("serve.coldstart_rejected")
    engines, rejected, errs = [], [], []
    barrier = threading.Barrier(m)

    def work():
        try:
            barrier.wait()
            eng, stats = cold_start(model, blob, b"T" * 32, service,
                                    max_batch=1, max_len=8)
            engines.append(eng)
        except ColdStartRejected:
            rejected.append(1)
        except Exception as e:          # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=work) for _ in range(m)]
    for t in threads:
        t.start()
    # the accepted starts are parked on the gated store; wait until the
    # in-flight + rejected picture is complete, then release the fetches
    deadline = time.time() + 10
    while time.time() < deadline:
        if service.admission.inflight == maxc and len(rejected) == m - maxc:
            break
        time.sleep(0.005)
    store.gate.set()
    for t in threads:
        t.join()
    assert not errs
    assert len(engines) == maxc
    assert len(rejected) == m - maxc
    assert COUNTERS.get("serve.coldstart_rejected") - before_rej == m - maxc
    assert service.admission.inflight == 0      # slots released
    for eng in engines:
        assert np.array_equal(np.asarray(eng.params["w"]), oracle["w"])


def test_legacy_coldstart_store_convention_still_works(tmp_path):
    """The deprecated raw-store calling convention (l1/l2/limiter/...)
    keeps working through a private single-image service."""
    from repro.core.concurrency import RejectingLimiter

    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    rng = np.random.default_rng(1)
    tree = {"w": rng.standard_normal((1024,)).astype(np.float32)}
    blob, _ = create_image(tree, tenant="t", tenant_key=b"L" * 32,
                           store=store, root=gc.active, chunk_size=CS)
    model = _TinyModel(jax.eval_shape(
        lambda: {"w": np.zeros((1024,), np.float32)}))
    lim = RejectingLimiter(1)
    eng, stats = cold_start(model, blob, b"L" * 32, store, limiter=lim,
                            l1=LocalCache(8 << 20, name="lg"),
                            max_batch=1, max_len=8)
    assert np.array_equal(np.asarray(eng.params["w"]), np.asarray(tree["w"]))
    assert stats["load_seconds"] > 0
    # mixing the legacy knobs with a real service is a TypeError
    service = ImageService(store, ServiceConfig(l2_nodes=0))
    with pytest.raises(TypeError):
        cold_start(model, blob, b"L" * 32, service, limiter=lim)


# ------------------------------------------------- serving dtype contract

def test_coldstart_promotes_float64_to_float32(tmp_path):
    """cold_start's documented serving-dtype contract: float64 leaves
    (numpy default precision) are promoted to float32; float32 and
    integer leaves pass through untouched."""
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    rng = np.random.default_rng(2)
    tree = {"w64": rng.standard_normal((512,)),               # float64
            "w32": rng.standard_normal((512,)).astype(np.float32),
            "i8": rng.integers(-8, 8, (64,)).astype(np.int8)}
    blob, _ = create_image(tree, tenant="t", tenant_key=b"D" * 32,
                           store=store, root=gc.active, chunk_size=CS)
    template = jax.eval_shape(lambda: {
        "w64": np.zeros((512,), np.float64),
        "w32": np.zeros((512,), np.float32),
        "i8": np.zeros((64,), np.int8)})
    model = _TinyModel(template)
    service = ImageService(store, ServiceConfig(l2_nodes=0))
    eng, _ = cold_start(model, blob, b"D" * 32, service,
                        max_batch=1, max_len=8)
    assert eng.params["w64"].dtype == np.float32
    assert eng.params["w32"].dtype == np.float32
    assert eng.params["i8"].dtype == np.int8
    assert np.allclose(np.asarray(eng.params["w64"]),
                       tree["w64"].astype(np.float32))


# ------------------------------------------------------------ ReadPolicy

def test_readpolicy_validation_and_legacy_mapping():
    with pytest.raises(ValueError):
        ReadPolicy(mode="bogus")
    with pytest.raises(ValueError):
        ReadPolicy(decode_backend="bogus")
    with pytest.raises(ValueError):
        ReadPolicy(parallelism=0)
    assert ReadPolicy.from_legacy(batched=False).mode == "serial"
    assert ReadPolicy.from_legacy(streamed=False).mode == "staged"
    p = ReadPolicy.from_legacy(parallelism=3)
    assert p.mode == "streamed" and p.parallelism == 3


def test_policy_modes_byte_identical_through_service(tmp_path):
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    images = make_tenant_images(store, gc.active)
    _, key, tree, blob, _ = images[0]
    flats = []
    for pol in (ReadPolicy(mode="serial"), ReadPolicy(mode="staged"),
                ReadPolicy(mode="streamed"),
                ReadPolicy(mode="streamed", eager_flush=True),
                ReadPolicy(mode="streamed", max_batch_bytes=CS),
                ReadPolicy(mode="staged", decode_backend="serial")):
        svc = ImageService(store, ServiceConfig(l1_bytes=8 << 20,
                                                l2_nodes=0))
        flats.append(svc.open(blob, key).restore_tree(policy=pol))
    for flat in flats[1:]:
        for n in tree:
            assert np.array_equal(flats[0][n], flat[n]), n


def test_eager_flush_fires_and_stays_identical(tmp_path):
    """With a slow origin and one giant tile budget, the plain streamed
    path decodes everything in ONE post-fetch tile; eager_flush decodes
    partial tiles during fetch stalls instead — more tiles, same bytes,
    visible in telemetry."""
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    rng = np.random.default_rng(4)
    tree = {"w": rng.standard_normal((CS * 12 // 4,)).astype(np.float32)}
    blob, _ = create_image(tree, tenant="t", tenant_key=b"E" * 32,
                           store=store, root=gc.active, chunk_size=CS)

    def run(eager):
        svc = ImageService(store, ServiceConfig(
            l1_bytes=8 << 20, l2_nodes=0, fetch_concurrency=0,
            origin_delay_s=0.01, max_batch_bytes=64 << 20))
        h = svc.open(blob, b"E" * 32)
        flat = h.restore_tree(policy=ReadPolicy(
            mode="streamed", parallelism=2, eager_flush=eager))
        return flat, h.reader.last_batch

    flat_plain, lb_plain = run(False)
    before = COUNTERS.get("decode.eager_flushes")
    flat_eager, lb_eager = run(True)
    assert np.array_equal(flat_plain["w"], flat_eager["w"])
    assert np.array_equal(flat_plain["w"], np.asarray(tree["w"]))
    assert lb_plain["eager_flushes"] == 0
    assert lb_eager["eager_flushes"] >= 1
    assert lb_eager["decode_tiles"] > lb_plain["decode_tiles"]
    assert COUNTERS.get("decode.eager_flushes") - before == \
        lb_eager["eager_flushes"]
    # tri-state: an explicit eager_flush=False overrides an eager
    # service DEFAULT (None would inherit it)
    svc = ImageService(store, ServiceConfig(
        l1_bytes=8 << 20, l2_nodes=0, fetch_concurrency=0,
        origin_delay_s=0.01, max_batch_bytes=64 << 20,
        default_policy=ReadPolicy(eager_flush=True)))
    h = svc.open(blob, b"E" * 32)
    assert h._resolve(None)[1].eager_flush is True           # inherits
    assert h._resolve(ReadPolicy(eager_flush=False))[1].eager_flush is False
    h.restore_tree(policy=ReadPolicy(
        mode="streamed", parallelism=2, eager_flush=False))
    assert h.reader.last_batch["eager_flushes"] == 0


# ----------------------------------------------------- policy plumbing

def test_prefetch_streamed_policy_warms_tiers(tmp_path):
    from test_batched_read import CountingStore
    store = CountingStore(tmp_path / "s")
    gc = GenerationalGC(store)
    images = make_tenant_images(store, gc.active)
    _, key, tree, blob, stats = images[0]
    svc = ImageService(store, ServiceConfig(l1_bytes=32 << 20, l2_nodes=0))
    h = svc.open(blob, key)
    store.gets = 0
    h.prefetch(list(range(h.layout.num_chunks)),
               policy=ReadPolicy(mode="streamed", parallelism=4))
    lb = h.reader.last_batch
    assert lb["streamed"] is True and lb["materialized"] is False
    uniq = len({c.name for c in h.manifest.chunks if c.name != ZERO_CHUNK})
    assert store.gets == uniq
    store.gets = 0
    flat = h.restore_tree()             # all L1 now: no origin traffic
    assert store.gets == 0
    for n in tree:
        assert np.array_equal(flat[n], np.asarray(tree[n]))


def _expert_image(tmp_path):
    """A 2-layer stack of 4 experts (6 KiB rows) and a dense leaf."""
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    rng = np.random.default_rng(5)
    tree = {"moe/experts": rng.standard_normal((2, 4, 1536)).astype(np.float32),
            "dense/w": rng.standard_normal((32, 8)).astype(np.float32)}
    blob, _ = create_image(tree, tenant="t", tenant_key=b"X" * 32,
                           store=store, root=gc.active, chunk_size=CS)
    return store, blob, tree


def test_expert_shard_restore_policy_plumbs(tmp_path):
    """``restore_share`` reads the share under every policy and counts
    the chunks it fetches, those its ranges lie in, and those it holds
    only part of."""
    store, blob, tree = _expert_image(tmp_path)
    svc = ImageService(store, ServiceConfig(l1_bytes=8 << 20, l2_nodes=0))
    h = svc.open(blob, b"X" * 32)
    share = {"moe/experts": [(0, 2), (1, 3), (0, 1536)], "dense/w": None}
    for pol in (None, ReadPolicy(mode="staged"), ReadPolicy(mode="serial")):
        got, numbers = h.restore_share(share, policy=pol)
        assert np.array_equal(got["moe/experts"],
                              np.asarray(tree["moe/experts"])[:, 1:3])
        assert np.array_equal(got["dense/w"], np.asarray(tree["dense/w"]))
        # an expert row is 6 KiB: rows 1-2 of a layer span 4 chunks, the
        # first and last of which also hold bytes of rows 0 and 3
        assert numbers == {"held_bytes": 2 * 2 * 6144 + 32 * 8 * 4,
                           "image_bytes": 2 * 4 * 6144 + 32 * 8 * 4,
                           "chunks": 2 * 4 + 1, "chunks_covered": 2 * 4 + 1,
                           "partial_chunks": 2 * 2}
    assert h.counters.get("restore.share_bytes") == 3 * (2 * 2 * 6144
                                                         + 32 * 8 * 4)
    assert h.counters.get("restore.skipped_bytes") == 3 * 2 * 2 * 6144


@pytest.mark.parametrize("mode", ["streamed", "staged", "serial"])
def test_share_chunks_are_what_the_read_fetched(tmp_path, mode):
    """``chunks`` is counted by the read, not the layout: two layers of
    equal bytes are one set of names, fetched once (the serial oracle
    fetches a chunk per range it lies in); and a read that fetches more
    than the share's ranges counts more, where ``chunks_covered`` does
    not move."""
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    layer = np.random.default_rng(6).standard_normal((4, 1536))
    tree = {"moe/experts": np.stack([layer, layer]).astype(np.float32)}
    blob, _ = create_image(tree, tenant="t", tenant_key=b"X" * 32,
                           store=store, root=gc.active, chunk_size=CS)
    h = ImageService(store, ServiceConfig(l1_bytes=0, l2_nodes=0)).open(
        blob, b"X" * 32)
    share = {"moe/experts": [(0, 2), (1, 3), (0, 1536)]}
    policy = ReadPolicy(mode=mode)
    _, numbers = h.restore_share(share, policy=policy)
    assert numbers["chunks_covered"] == 2 * 4
    assert numbers["chunks"] == (2 * 4 if mode == "serial" else 4)
    if mode == "serial":
        return
    read_many = h.reader.read_many

    def whole_image_too(ranges, *args, **kw):
        ranges = list(ranges)
        everything = (0, h.layout.num_chunks * CS)
        return read_many(ranges + [everything], *args, **kw)[:len(ranges)]
    h.reader.read_many = whole_image_too
    _, more = h.restore_share(share, policy=policy)
    assert more["chunks_covered"] == numbers["chunks_covered"]
    assert more["chunks"] == 6 > numbers["chunks"]


def test_share_through_the_legacy_cold_start(tmp_path):
    """The deprecated raw-store calling convention of ``cold_start``
    restores a share under its legacy knobs."""
    store, blob, tree = _expert_image(tmp_path)
    model = _TinyModel(jax.eval_shape(
        lambda: {"moe": {"experts": np.zeros((2, 2, 1536), np.float32)},
                 "dense": {"w": np.zeros((32, 8), np.float32)}}))
    eng, stats = cold_start(
        model, blob, b"X" * 32, store, batched=True, streamed=False,
        share={"moe/experts": [(0, 2), (0, 2), (0, 1536)], "dense/w": None},
        max_batch=1, max_len=8)
    assert np.array_equal(np.asarray(eng.params["moe"]["experts"]),
                          np.asarray(tree["moe/experts"])[:, 0:2])
    assert np.array_equal(np.asarray(eng.params["dense"]["w"]),
                          np.asarray(tree["dense/w"]))
    assert stats["streamed"] is False
    assert stats["share"]["held_bytes"] == 2 * 2 * 6144 + 32 * 8 * 4


# ------------------------------------------------------ scoped telemetry

def test_scoped_counters_unit():
    c = Counters()
    s = c.scope("tenant.t1")
    s.inc("x")
    s.add("x", 2)
    s.max_update("hwm", 5)
    s.max_update("hwm", 3)
    assert c.get("x") == 3 and s.get("x") == 3
    assert c.get("tenant.t1::x") == 3
    assert s.get("hwm") == 5
    assert s.snapshot() == {"x": 3, "hwm": 5}
    # a second scope is independent in its namespace, shared globally
    s2 = c.scope("tenant.t2")
    s2.inc("x")
    assert c.get("x") == 4 and s.get("x") == 3 and s2.get("x") == 1


def test_bound_decoder_honored_by_policy_reads(tmp_path):
    """A caller-supplied decoder (shim ``decoder=`` / ``open(decoder=)``)
    must drive policy-based reads when the policy carries no decode
    overrides — and policy decode overrides must still win."""
    from repro.core.decode import BatchDecoder

    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    images = make_tenant_images(store, gc.active)
    _, key, tree, blob, _ = images[0]
    shim = ImageReader(blob, key, store, decoder=BatchDecoder("serial"))
    flat = shim.restore_tree(streamed=False)
    assert shim.reader.last_batch["decode_backend"] == "serial"
    for n in tree:
        assert np.array_equal(flat[n], np.asarray(tree[n]))
    svc = ImageService(store, ServiceConfig(l1_bytes=0, l2_nodes=0,
                                            fetch_concurrency=0))
    h = svc.open(blob, key, decoder=BatchDecoder("serial"))
    h.restore_tree(policy=ReadPolicy(mode="staged"))
    assert h.reader.last_batch["decode_backend"] == "serial"
    h.restore_tree(policy=ReadPolicy(mode="staged", decode_backend="numpy"))
    assert h.reader.last_batch["decode_backend"] == "numpy"


def test_imagereader_shim_equals_service(tmp_path):
    """The deprecation shim and a direct service session produce
    identical bytes and expose the same reader surface."""
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    images = make_tenant_images(store, gc.active)
    _, key, tree, blob, _ = images[0]
    shim = ImageReader(blob, key, store)
    svc = ImageService(store, ServiceConfig(l1_bytes=0, l2_nodes=0,
                                            fetch_concurrency=0))
    h = svc.open(blob, key)
    a = shim.restore_tree()
    b = h.restore_tree()
    for n in tree:
        assert np.array_equal(a[n], b[n])
    assert shim.layout.image_size == h.layout.image_size
    assert shim.tensor_names() == h.tensor_names()
    assert np.array_equal(shim.tensor("base"), h.tensor("base"))
    sl = {"base": [(0, 8), (0, 1024)]}
    assert shim.shard_chunks(sl) == h.shard_chunks(sl)
    assert np.array_equal(shim.tensor_shard("base", sl["base"]),
                          h.tensor_shard("base", sl["base"]))


# ------------------------------------------- session LRU+TTL + close()

def _make_images(store, root, n, *, seed=11):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        tree = {"w": rng.standard_normal((512,)).astype(np.float32)}
        blob, _ = create_image(tree, tenant="churn", tenant_key=b"C" * 32,
                               store=store, root=root, chunk_size=CS,
                               image_id=f"churn{i}")
        out.append((tree, blob))
    return out


def test_session_cache_lru_evicts_under_churn(tmp_path):
    """A churning image population stays bounded: the session and
    manifest caches never exceed their caps, evictions tick telemetry,
    and every restore stays byte-identical regardless of eviction."""
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    images = _make_images(store, gc.active, 12)
    svc = ImageService(store, ServiceConfig(
        l1_bytes=8 << 20, l2_nodes=0, fetch_concurrency=0,
        max_coldstarts=0, session_cap=4, manifest_cap=4))
    before = COUNTERS.get("service.session_evictions")
    for _round in range(2):
        for tree, blob in images:
            flat = svc.open(blob, b"C" * 32).restore_tree()
            assert np.array_equal(flat["w"], np.asarray(tree["w"]))
            assert len(svc._sessions) <= 4
            assert len(svc._manifests) <= 4
    assert COUNTERS.get("service.session_evictions") - before >= 8
    # the hottest (most recent) session survived; re-opening it does
    # not rebuild a reader
    _, blob = images[-1]
    h1 = svc.open(blob, b"C" * 32)
    h2 = svc.open(blob, b"C" * 32)
    assert h1.reader is h2.reader


def test_session_ttl_expires_idle_handles(tmp_path):
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    images = _make_images(store, gc.active, 2)
    svc = ImageService(store, ServiceConfig(
        l1_bytes=8 << 20, l2_nodes=0, fetch_concurrency=0,
        max_coldstarts=0, session_ttl_s=0.05))
    _, blob = images[0]
    h1 = svc.open(blob, b"C" * 32)
    assert svc.open(blob, b"C" * 32).reader is h1.reader   # within TTL
    time.sleep(0.08)
    h2 = svc.open(blob, b"C" * 32)                          # expired
    assert h2.reader is not h1.reader
    # the expired handle keeps working (it owns its reader)
    flat = h1.restore_tree()
    assert np.array_equal(flat["w"], np.asarray(images[0][0]["w"]))


def test_service_close_drains_and_rejects_new_opens(tmp_path):
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    images = _make_images(store, gc.active, 2)
    svc = ImageService(store, ServiceConfig(
        l1_bytes=8 << 20, l2_nodes=0, fetch_concurrency=0,
        max_coldstarts=0, decode_threads=2))   # pin >1: the pool only
    _, blob = images[0]                        # spins when threads > 1,
    h = svc.open(blob, b"C" * 32)              # not on 1-CPU hosts
    h.restore_tree()                      # spin the decode pool up
    dec = h.reader.decoder
    assert dec._pool._pool is not None
    svc.close()
    assert dec._pool._pool is None        # pool drained
    assert svc._sessions == {} and svc._manifests == {}
    assert svc.flights.flights == {}
    with pytest.raises(RuntimeError, match="closed"):
        svc.open(blob, b"C" * 32)
    svc.close()                           # idempotent
    # live handles still read after close (they own their reader); a
    # decode re-spins the lazy pool privately
    flat = h.restore_tree()
    assert np.array_equal(flat["w"], np.asarray(images[0][0]["w"]))


def test_eager_min_bytes_holds_small_partials(tmp_path):
    """The smarter eager trigger: with the threshold above the image
    size, idle-queue flushes HOLD (telemetry: eager_holds) and the tile
    structure matches plain streaming; with a zero threshold the old
    flush-on-any-idle behavior returns."""
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    rng = np.random.default_rng(6)
    tree = {"w": rng.standard_normal((CS * 8 // 4,)).astype(np.float32)}
    blob, _ = create_image(tree, tenant="t", tenant_key=b"E" * 32,
                           store=store, root=gc.active, chunk_size=CS)

    def run(eager_min):
        svc = ImageService(store, ServiceConfig(
            l1_bytes=8 << 20, l2_nodes=0, fetch_concurrency=0,
            origin_delay_s=0.01, max_batch_bytes=64 << 20,
            eager_min_bytes=eager_min))
        h = svc.open(blob, b"E" * 32)
        flat = h.restore_tree(policy=ReadPolicy(
            mode="streamed", parallelism=2, eager_flush=True))
        assert np.array_equal(flat["w"], np.asarray(tree["w"]))
        return h.reader.last_batch

    lb_hold = run(1 << 30)
    assert lb_hold["eager_flushes"] == 0
    assert lb_hold["decode_tiles"] == 1   # tile efficiency preserved
    lb_zero = run(0)
    assert lb_zero["eager_flushes"] >= 1
    assert lb_zero["decode_tiles"] > 1


def test_close_racing_inflight_streamed_read_still_byte_identical(tmp_path):
    """close() mid-restore must not break the in-flight read: the
    decoder falls back to inline decode when its pool is shut down
    under it ('live handles keep working'), and nothing re-pins state
    into the closed service."""
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    rng = np.random.default_rng(8)
    tree = {"w": rng.standard_normal((CS * 16 // 4,)).astype(np.float32)}
    blob, _ = create_image(tree, tenant="t", tenant_key=b"R" * 32,
                           store=store, root=gc.active, chunk_size=CS)
    svc = ImageService(store, ServiceConfig(
        l1_bytes=8 << 20, l2_nodes=0, fetch_concurrency=0,
        origin_delay_s=0.005, max_batch_bytes=CS))
    h = svc.open(blob, b"R" * 32)
    out, errs = [], []

    def read():
        try:
            out.append(h.restore_tree(policy=ReadPolicy(
                mode="streamed", parallelism=2)))
        except Exception as e:          # pragma: no cover
            errs.append(e)

    t = threading.Thread(target=read)
    t.start()
    time.sleep(0.01)                    # land mid-stream
    svc.close()
    t.join()
    assert not errs, errs
    assert np.array_equal(out[0]["w"], np.asarray(tree["w"]))
    assert svc._sessions == {} and svc._decoders == {}


# ----------------------------------------------------- limiter underflow

def test_rejecting_limiter_release_clamps_at_zero():
    """Over-release must not go negative and widen the admission gate;
    it ticks limiter.release_underflow instead."""
    from repro.core.concurrency import RejectingLimiter

    lim = RejectingLimiter(2)
    assert lim.try_acquire() and lim.try_acquire()
    assert not lim.try_acquire()                    # full
    lim.release()
    lim.release()
    before = COUNTERS.get("limiter.release_underflow")
    lim.release()                                   # spurious
    lim.release()                                   # spurious
    assert COUNTERS.get("limiter.release_underflow") == before + 2
    assert lim.inflight == 0
    # capacity unchanged: exactly 2 admits, the 3rd rejects
    assert lim.try_acquire() and lim.try_acquire()
    assert not lim.try_acquire()
    lim.release()
    lim.release()


def test_blocking_limiter_release_clamps_at_cap():
    """Extra releases must not mint origin-fetch permits beyond
    max_inflight; they tick limiter.release_underflow."""
    from repro.core.concurrency import BlockingLimiter

    lim = BlockingLimiter(1)
    with lim:
        pass
    before = COUNTERS.get("limiter.release_underflow")
    lim.release()                                   # spurious
    assert COUNTERS.get("limiter.release_underflow") == before + 1
    # still exactly ONE permit: a second concurrent acquire blocks
    lim.acquire()
    blocked = threading.Event()
    acquired = threading.Event()

    def second():
        blocked.set()
        lim.acquire()
        acquired.set()

    t = threading.Thread(target=second)
    t.start()
    blocked.wait(5)
    time.sleep(0.05)
    assert not acquired.is_set()                    # no minted permit
    lim.release()
    assert acquired.wait(5)
    lim.release()
    t.join(5)
