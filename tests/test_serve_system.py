"""Serving cold-start + the full end-to-end system test (deliverable b/c):
train -> checkpoint to chunk store -> corrupt/fail infrastructure ->
cold-start serve through the cache tiers -> generate."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.cache.distributed import DistributedCache
from repro.core.cache.local import LocalCache
from repro.core.concurrency import RejectingLimiter
from repro.core.gc import GenerationalGC
from repro.core.loader import ImageReader, create_image
from repro.core.store import ChunkStore
from repro.models import build_model
from repro.core.service import ImageService, ServiceConfig
from repro.serve.coldstart import cold_start
from repro.serve.engine import Request, ServeEngine
from repro.train.checkpoint import CheckpointManager
from repro.train.loop import LoopConfig, Trainer
from repro.train.step import cast_params


def test_engine_generates(tmp_path):
    cfg = get_config("tinyllama-1.1b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    eng = ServeEngine(m, params, max_batch=2, max_len=48)
    reqs = [Request(i, prompt=[1, 2, 3, 4], max_new=5) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    for r in reqs:
        assert r.done and len(r.out) == 5
        assert all(0 <= t < cfg.vocab_size for t in r.out)


def test_engine_deterministic_across_batching(tmp_path):
    """Same prompt alone vs batched with others -> same greedy tokens."""
    cfg = get_config("smollm-360m").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(1))
    outs = []
    for batchmates in (0, 3):
        eng = ServeEngine(m, params, max_batch=4, max_len=48)
        main = Request(0, prompt=[5, 6, 7], max_new=6)
        eng.submit(main)
        for i in range(batchmates):
            eng.submit(Request(100 + i, prompt=[9, 9], max_new=6))
        eng.run_until_drained()
        outs.append(tuple(main.out))
    assert outs[0] == outs[1]


def test_concurrency_limiter_rejects():
    lim = RejectingLimiter(2)
    assert lim.try_acquire() and lim.try_acquire()
    assert not lim.try_acquire()         # rejected, not queued (§4.2)
    lim.release()
    assert lim.try_acquire()
    assert lim.rejected == 1


def test_expert_shard_restore(tmp_path):
    """A replica holding expert-parallel rank 1 of 2 restores, through
    ``cold_start(share=...)``, exactly its experts' slice of each expert
    leaf (the axis the specs name "expert", axis 1 of the stacked
    (L, E, ...) leaves) and every other leaf whole."""
    full_cfg = get_config("arctic-480b").reduced()
    cfg = dataclasses.replace(full_cfg, ep_size=2, ep_rank=1)
    from repro.train.checkpoint import state_to_tree
    tree = state_to_tree(build_model(full_cfg).init(jax.random.key(0)))
    store = ChunkStore(tmp_path / "s")
    gc = GenerationalGC(store)
    blob, _ = create_image(tree, tenant="t", tenant_key=b"E" * 32, store=store,
                           root=gc.active, chunk_size=4096)
    m = build_model(cfg)
    share = m.param_share()
    engine, stats = cold_start(m, blob, b"E" * 32,
                               ImageService(store, ServiceConfig()),
                               root=gc.active, share=share)
    got = state_to_tree(engine.params)
    lo, hi = cfg.held_experts
    assert (lo, hi) == (cfg.num_experts // 2, cfg.num_experts)
    sliced = [n for n, sl in share.items() if sl is not None]
    assert sorted(sliced) == sorted(n for n in tree if n.split("/")[-1] in
                                    ("w_gate", "w_up", "w_down")
                                    and "/ffn/" in n and "residual" not in n)
    for name, arr in got.items():
        if name in sliced:
            assert arr.shape[1] == tree[name].shape[1] // 2
            np.testing.assert_array_equal(arr, tree[name][:, lo:hi])
        else:
            np.testing.assert_array_equal(arr, tree[name])
    assert stats["share"]["held_bytes"] == sum(a.nbytes for a in got.values())


class TestEndToEnd:
    def test_train_checkpoint_corrupt_serve(self, tmp_path):
        """The capstone: train a small model, checkpoint into the chunk
        store, kill a cache node AND corrupt one stored chunk copy path,
        then cold-start a serving replica and generate."""
        cfg = get_config("smollm-360m").reduced()
        store = ChunkStore(tmp_path / "sys")
        gc = GenerationalGC(store)
        ck = CheckpointManager(store, gc, tenant="sys", tenant_key=b"S" * 32,
                               chunk_size=16384)
        tr = Trainer(cfg, LoopConfig(steps=6, batch=2, seq=16, ckpt_every=6,
                                     log_every=6), ckpt_mgr=ck).init()
        tr.run()
        ck.wait()
        rec = ck.latest()
        assert rec is not None

        # build a params-only image for serving (bf16 cast)
        from repro.train.checkpoint import state_to_tree
        params_bf16 = cast_params(tr.state["params"], jax.numpy.bfloat16)
        tree = state_to_tree(params_bf16)
        tree = {k: np.asarray(v).view(np.uint16) if v.dtype == jax.numpy.bfloat16
                else np.asarray(v) for k, v in tree.items()}
        blob, stats = create_image(tree, tenant="serve", tenant_key=b"V" * 32,
                                   store=store, root=gc.active,
                                   chunk_size=16384)

        l1 = LocalCache(128 << 20)
        l2 = DistributedCache(num_nodes=6, seed=9)
        # prime L2 (the paper's 'priming caches at creation' idea), then
        # fail a node: erasure coding must hide it
        reader0 = ImageReader(blob, b"V" * 32, store, l2=l2)
        reader0.restore_tree()
        l2.fail_node(sorted(l2.nodes)[0])

        model = build_model(cfg)
        import jax.numpy as jnp

        class Bf16Model:
            """view: reinterpret stored uint16 as bf16 params"""
        reader = ImageReader(blob, b"V" * 32, store, l1=l1, l2=l2)
        flat = reader.restore_tree()
        flat = {k: v.view(jnp.bfloat16) if v.dtype == np.uint16 else v
                for k, v in flat.items()}
        from repro.train.checkpoint import tree_from_flat
        template = jax.eval_shape(lambda: cast_params(
            model.init(jax.random.key(0)), jnp.bfloat16))
        params = tree_from_flat(template, flat)

        eng = ServeEngine(model, params, max_batch=2, max_len=32)
        req = Request(0, prompt=[1, 2, 3], max_new=4)
        eng.submit(req)
        eng.run_until_drained()
        assert req.done and len(req.out) == 4

    def test_coldstart_api(self, tmp_path):
        from repro.core.service import ImageService, ReadPolicy, ServiceConfig

        cfg = get_config("smollm-360m").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        from repro.train.checkpoint import state_to_tree
        store = ChunkStore(tmp_path / "cs")
        gc = GenerationalGC(store)
        blob, _ = create_image(state_to_tree(params), tenant="t",
                               tenant_key=b"W" * 32, store=store,
                               root=gc.active, chunk_size=16384)
        # the redesigned convention: one process-wide service owns the
        # tiers and admission control; the read shape is one ReadPolicy
        service = ImageService(store, ServiceConfig(
            l1_bytes=64 << 20, l2_nodes=0, max_coldstarts=1))
        eng, stats = cold_start(model, blob, b"W" * 32, service,
                                policy=ReadPolicy(parallelism=4),
                                max_batch=2, max_len=32)
        assert stats["load_seconds"] > 0
        assert stats["tenant"] == "t"
        assert service.admission.inflight == 0
        req = Request(0, prompt=[4, 5], max_new=3)
        eng.submit(req)
        eng.run_until_drained()
        assert req.done
