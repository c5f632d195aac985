"""Granite 4.0-H (hybrid Mamba-2 / MoE) against its plain reference
(``repro.models.reference.granite_hybrid``) at a reduced size on seeded
random weights: the model's forward, prefill-by-decode through the
``ServeEngine``'s cache, the Mamba-2 chunked scan against its decode
step, the expert-parallel shares against the uncut layer, the dropless
decode dispatch, and a cold start that restores one replica's share."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.gc import GenerationalGC
from repro.core.loader import create_image
from repro.core.service import ImageService, ServiceConfig
from repro.core.store import ChunkStore
from repro.core.telemetry import COUNTERS
from repro.models import RunFlags, build_model
from repro.models import mamba2 as m2
from repro.models.moe import moe_apply, moe_init
from repro.models.reference import granite_hybrid as ref
from repro.serve.coldstart import cold_start
from repro.serve.engine import Request, ServeEngine
from repro.train.checkpoint import state_to_tree

GRANITE = get_config("granite-4.0-h-small")
PROMPT = [7, 201, 33, 90, 4, 150, 18, 77, 250, 3]


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def small(**kw):
    """Reduced granite: one period of (attention, mamba2), 4 experts."""
    return GRANITE.reduced(**kw)


def reference_logits(cfg, params, tokens):
    return ref.forward(state_to_tree(params), tokens, ref.sizes(cfg))


def test_published_layout():
    cfg = GRANITE
    kinds = ref.sizes(cfg).kinds
    assert kinds[:10] == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert kinds.count("attention") == 4 and len(kinds) == 40
    din, heads, gn, conv = m2.dims(cfg)
    assert (din, heads, gn, conv) == (8192, 128, 128, 8448)
    assert cfg.d_ff * cfg.shared_experts == 1536


def test_reference_matches_forward():
    """The model's full forward (chunked scan across 3 chunks, the last
    padded) against the reference's, in float32 at the highest matmul
    precision; the capacity factor leaves the prefill dispatch no drop."""
    cfg = small(param_dtype="float32", ssm_chunk=4, capacity_factor=2.0)
    m = build_model(cfg, RunFlags(dtype="float32"))
    params = m.init(jax.random.key(0))
    toks = jnp.asarray([PROMPT], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = m.prefill(params, {"tokens": toks},
                           m.init_decode_state(1, 16, jnp.float32))
    want = reference_logits(cfg, params, PROMPT)[-1]
    assert rel_err(got[0, :cfg.vocab_size], want) < 1e-5


@pytest.mark.parametrize("fault", [None, "mamba2 out_proj zeroed"])
def test_engine_prefill_by_decode_matches_reference(fault):
    """The serving path as configured (bf16 parameters, bf16 compute,
    bf16 caches): the ``ServeEngine`` feeds the prompt one token at a
    time through ``decode_step`` and the first token's logits come
    within 3 % (relative L2) of the float32 reference's last position.
    bf16 alone reads under 1.5 %; one Mamba-2 mixer's output left out
    reads far above the tolerance."""
    cfg = small()
    m = build_model(cfg)
    params = m.init(jax.random.key(1))
    assert {a.dtype for a in jax.tree.leaves(params)} == {jnp.dtype("bfloat16")}
    want = reference_logits(cfg, params, PROMPT)[-1]
    if fault:
        mixer = params["g0"]["s1"]["mixer"]
        mixer["out_proj"] = jnp.zeros_like(mixer["out_proj"])
    eng = ServeEngine(m, params, max_batch=2, max_len=32)
    req = Request(0, prompt=PROMPT, max_new=1)
    eng.submit(req)
    eng.run_until_drained()
    err = rel_err(req.first_logits, want)
    if fault:
        assert err > 0.1, err
    else:
        assert err < 0.03, err
        assert req.out == [int(np.argmax(req.first_logits))]


def test_chunked_scan_matches_decode_steps():
    cfg = small(param_dtype="float32", ssm_chunk=3)
    p, _ = m2.mamba2_init(jax.random.key(0), "m", cfg)
    x = jax.random.normal(jax.random.key(1), (2, 10, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, last = m2.mamba2_apply(p, x, cfg, jnp.float32, return_state=True)
        state = m2.mamba2_decode_state(cfg, 2, jnp.float32)
        steps = []
        for t in range(x.shape[1]):
            yt, state = m2.mamba2_decode(p, x[:, t], state, cfg, jnp.float32)
            steps.append(yt)
    np.testing.assert_allclose(y, jnp.stack(steps, 1), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(last["ssm"], state["ssm"], rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(last["conv"], state["conv"])


def _share_params(p, cfg):
    lo, hi = cfg.held_experts
    return {k: (v[lo:hi] if k in ("w_gate", "w_up", "w_down") else v)
            for k, v in p.items()}


def test_nine_shares_add_up_to_the_uncut_layer():
    """Nine expert-parallel ranks, each computing its experts' part and
    the shared expert: the parts, with the shared expert counted once,
    add up to the uncut layer (and to the reference's layer)."""
    cfg = small(param_dtype="float32", num_experts=18, experts_per_token=5)
    p, _ = moe_init(jax.random.key(0), "moe", cfg)
    x = jax.random.normal(jax.random.key(1), (3, 1, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole = moe_apply(p, x, cfg, jnp.float32, dropless=True)
        shared = ref.swiglu(x[:, 0], p["shared"]["w_gate"],
                            p["shared"]["w_up"], p["shared"]["w_down"])
        parts = []
        for r in range(9):
            c = dataclasses.replace(cfg, ep_size=9, ep_rank=r)
            assert c.held_experts == (2 * r, 2 * r + 2)
            parts.append(moe_apply(_share_params(p, c), x, c, jnp.float32,
                                   dropless=True)[:, 0])
        hp = ref.sizes(cfg)
        flat = {"router": p["router"], **{k: p[k] for k in
                                          ("w_gate", "w_up", "w_down")},
                **{f"shared/{k}": v for k, v in p["shared"].items()}}
        want = ref.moe(flat, x[:, 0], hp)
    total = sum(parts) - 8 * shared
    np.testing.assert_allclose(total, whole[:, 0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_decode_dispatch_drops_no_token():
    """Granite's routing at decode: 4 tokens, top-10 of 72 experts, so
    the capacity rule gives 1 slot per expert while the 40 assignments
    crowd some experts with 2 or more. The decode path computes every
    assignment; the capacity path does not."""
    cfg = small(param_dtype="float32", num_experts=72, experts_per_token=10,
                d_ff=16, shared_experts=0)
    p, _ = moe_init(jax.random.key(0), "moe", cfg)
    x = jax.random.normal(jax.random.key(2), (4, 1, cfg.d_model))
    assert max(1, int(4 * 10 * cfg.capacity_factor / 72)) == 1
    _, experts = jax.lax.top_k(x[:, 0] @ p["router"], 10)
    assert np.bincount(np.asarray(experts).ravel()).max() >= 2
    flat = {"router": p["router"], "w_gate": p["w_gate"], "w_up": p["w_up"],
            "w_down": p["w_down"]}
    hp = ref.sizes(cfg)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda t: ref.moe(
            {**flat, "shared/w_gate": jnp.zeros((cfg.d_model, 1)),
             "shared/w_up": jnp.zeros((cfg.d_model, 1)),
             "shared/w_down": jnp.zeros((1, cfg.d_model))}, t, hp))(x)
        dropless = moe_apply(p, x, cfg, jnp.float32, dropless=True)
        capped = moe_apply(p, x, cfg, jnp.float32)
    np.testing.assert_allclose(dropless, want, rtol=1e-4, atol=1e-5)
    assert rel_err(capped, want) > 0.05


def test_cold_start_restores_exactly_the_share(tmp_path):
    """``cold_start(share=...)`` of rank 1 of 2 from the uncut image: the
    held slices, bf16 as stored, and no chunk but those they cover read
    from origin."""
    full = small(num_experts=4, experts_per_token=2)
    cfg = dataclasses.replace(full, ep_size=2, ep_rank=1)
    tree = state_to_tree(build_model(full).init(jax.random.key(3)))
    store = ChunkStore(tmp_path / "s")
    root = GenerationalGC(store).active
    blob, _ = create_image(tree, tenant="t", tenant_key=b"G" * 32,
                           store=store, root=root, chunk_size=4096)
    m = build_model(cfg)
    share = m.param_share()
    service = ImageService(store, ServiceConfig(root=root, l1_bytes=0))
    handle = service.open(blob, b"G" * 32)
    wanted = handle.shard_chunks({n: sl or [(0, d) for d in tree[n].shape]
                                  for n, sl in share.items()})
    gets = COUNTERS.get("store.chunk_gets")
    engine, stats = cold_start(m, blob, b"G" * 32, service, share=share)
    gets = COUNTERS.get("store.chunk_gets") - gets
    got = state_to_tree(engine.params)
    assert set(got) == set(tree)
    for name, arr in got.items():
        assert arr.dtype == jnp.bfloat16
        want = tree[name] if share[name] is None else tree[name][:, 2:4]
        np.testing.assert_array_equal(arr.view(np.uint16),
                                      want.view(np.uint16))
    numbers = stats["share"]
    assert numbers["chunks_covered"] == len(wanted) < handle.layout.num_chunks
    # chunks of equal bytes (the norms' ones) are one name, fetched once
    names = {handle.manifest.chunks[c].name for c in wanted}
    assert numbers["chunks"] == stats["origin_fetches"] == gets == len(names)
    assert numbers["chunks"] < numbers["chunks_covered"]
    assert numbers["held_bytes"] == sum(a.nbytes for a in got.values())
    assert numbers["image_bytes"] == sum(a.nbytes for a in tree.values())
