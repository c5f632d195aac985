"""Serial cold starts of one expert-parallel replica's share of a published
image, a closed loop of one client.

The configuration states a replica that holds a share of the model
(``sizes``: ``ep_size``, ``ep_rank``), with its held image (``image``),
and the published image it restores from (``published_image``: every
expert). Set-up makes the published image leaf by leaf: each leaf drawn
on the device from ``--seed`` by ``data.py``'s rule (leaf ``i`` of the
sorted paths from ``fold_in(fold_in(key, 0), i)``, normal with spread
0.02), except the leaves that ``init_kind`` names, which take the
published model's init; copied to the host, laid into the on-disk origin
in ``reference.py``'s image format (``publish_leaves``), and dropped but
for the replica's slice. The whole tree never sits in memory at once.
Last, it warms up every program a cold start runs (``coldstart.warm_up``).

Each unit is one cold start of the share, as a new replica of the
deployment pays it: a fresh ``ImageService``, ``cold_start(...,
share=model.param_share())``, and one request of ``new_tokens`` for
``prompt``. ``check`` reads the restored share back against the seed's
slices, byte for byte (``coldstart``'s readings), and holds the logits
that gave the first token against ``bench/granite_reference.py`` run on
the device on the seed's share (``logits_rel_err``, relative L2), and
the program's held experts' part of each MoE layer, on the restored
weights, against the reference's on inputs drawn from the seed
(``held_experts_rel_err``); the reference is computed once, at the
first check. ``verify`` adds ``coldstart``'s tampered restore.

Traffic parameters: those of ``coldstart``. Prompt ids are taken modulo
the vocabulary the configuration runs (the rehearsal's is smaller).
"""
from __future__ import annotations

import hashlib
import os
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
from cryptography.hazmat.primitives.ciphers.aead import AESGCM

from bench import data, granite_reference, reference
from bench.drivers import coldstart
from bench.harness import kernel_launches
from bench.model import build

# Limit of the first-token logits' relative L2 error against the float32
# reference. The program computes in bfloat16 (weights as stored,
# activations and caches rounded at every layer); PERF.md gives the
# readings it rests on: the program's largest, and the control's, the
# reference on weights rounded one precision lower (float8_e4m3fn).
LOGITS_LIMIT = 0.05
# Limit of the held experts' part of each MoE layer, relative L2 against
# the reference, on HELD_TOKENS inputs drawn from the seed. Only the held
# experts: beside the shared expert, their part of a layer's output is
# too small for the logits to show a fault in it. Tokens whose k-th and
# (k+1)-th router logits lie within MARGIN of each other (relative) are
# left out: bfloat16 rounds each logit by up to 2**-8 of itself, so the
# two may close by 2**-7 and a right program route them either way.
# PERF.md gives the readings.
HELD_LIMIT = 0.05
HELD_TOKENS = 256
MARGIN = 2.0 ** -6
PUBLISH_THREADS = 8


def init_kind(path: str) -> str:
    """How a leaf is drawn: ``normal`` (spread 0.02), or the published
    init of norm weights and Mamba-2's D (``ones``), A_log, dt_bias and
    the depthwise conv's weight and bias (``conv``)."""
    name = path.rsplit("/", 1)[-1]
    if name in ("scale", "D") or path.endswith("mixer/norm"):
        return "ones"
    if name in ("A_log", "dt_bias"):
        return name
    return "conv" if name in ("conv_w", "conv_b") else "normal"


def leaf_maker(names: list, d_conv: int):
    """``make(key, path, shape, dtype) -> leaf`` of the seed's tree, whose
    leaves are named `names` in sorted order: leaf ``i`` drawn from
    ``fold_in(fold_in(key, 0), i)``. The published init: norm weights and
    D are 1; A_log is log U(1, 16); dt_bias the inverse softplus of
    dt ~ logU(1e-3, 1e-1) (floor 1e-4); the conv's weight and bias
    U(-b, b), b = 1/sqrt(d_conv) (torch's Conv1d default, the fan-in
    being the kernel's length)."""
    index = {n: i for i, n in enumerate(names)}

    @partial(jax.jit, static_argnums=(2, 3, 4))
    def draw(key, i, kind, shape, dtype):
        k = jax.random.fold_in(jax.random.fold_in(key, 0), i)
        u = partial(jax.random.uniform, k, shape, jnp.float32)
        if kind == "ones":
            value = jnp.ones(shape, jnp.float32)
        elif kind == "A_log":
            value = jnp.log(u(1.0, 16.0))
        elif kind == "dt_bias":
            dt = jnp.maximum(jnp.exp(u(np.log(1e-3), np.log(1e-1))), 1e-4)
            value = dt + jnp.log(-jnp.expm1(-dt))
        elif kind == "conv":
            value = u(-1.0, 1.0) / np.sqrt(d_conv)
        else:
            value = data.SCALE * jax.random.normal(k, shape, jnp.float32)
        return value.astype(dtype)

    return lambda key, path, shape, dtype: draw(
        key, index[path], init_kind(path), tuple(shape), np.dtype(dtype))


def publish_leaves(shapes: dict, leaf, keep, *, tenant_key: bytes,
                   root: str, epoch: int, chunk_size: int, put_chunk,
                   image_id: str = "bench", tenant: str = "bench") -> tuple:
    """``reference.publish`` of the tree whose leaves ``leaf(path)`` gives
    one at a time: `shapes` is {path: ShapeDtypeStruct}; each chunk is
    hashed, encrypted and put on one of ``PUBLISH_THREADS`` threads.
    Returns (sealed manifest, {path: keep(path, leaf)})."""
    salt = reference.make_salt(epoch, root)
    layout = reference.expected_layout(
        {p: np.broadcast_to(np.zeros((), s.dtype), s.shape)
         for p, s in shapes.items()}, chunk_size)

    def seal(win):
        if not win.any():
            return reference.ZERO_NAME, b"", b"\x00" * 32
        pt = win.tobytes() + b"\x00" * (chunk_size - win.nbytes)
        key = hashlib.sha256(salt + pt).digest()
        ct = reference.ctr_encrypt(key, pt)
        digest = hashlib.sha256(ct).digest()
        put_chunk(digest.hex(), ct)
        return digest.hex(), digest, key

    chunks, keys, kept = [], [], {}
    with ThreadPoolExecutor(PUBLISH_THREADS) as pool:
        for path, *_ in layout:
            a = leaf(path)
            raw = np.ascontiguousarray(a).reshape(-1).view(np.uint8)
            wins = [raw[lo:lo + chunk_size]
                    for lo in range(0, raw.nbytes, chunk_size)]
            for name, digest, key in pool.map(seal, wins):
                chunks.append([len(chunks), name, digest])
                keys.append(key)
            kept[path] = keep(path, a)
    body = msgpack.packb({
        "image_id": image_id, "tenant": tenant, "root_id": root,
        "salt": salt, "chunk_size": chunk_size,
        "image_size": chunk_size * len(chunks) or chunk_size,
        "layout": layout, "chunks": chunks}, use_bin_type=True)
    nonce = os.urandom(12)
    sealed = AESGCM(tenant_key).encrypt(nonce, b"".join(keys), body)
    return msgpack.packb({"body": body, "nonce": nonce,
                          "key_ct": sealed[:-16], "tag": sealed[-16:]},
                         use_bin_type=True), kept


def published_config(config: dict) -> dict:
    """The configuration of the uncut model whose image is published: every
    expert held, checked against ``published_image`` where the held
    image is stated too."""
    sizes = dict(config["sizes"], ep_size=1, ep_rank=0)
    return dict(config, sizes=sizes, image=config.get("published_image")
                if "image" in config else None)


def share_slice(a, sl):
    """The part of leaf `a` that dim slices `sl` (or None: all) hold."""
    return a if sl is None else np.ascontiguousarray(
        a[tuple(slice(lo, hi) for lo, hi in sl)])


def setup(ctx):
    from repro.core.gc import GenerationalGC
    from repro.core.service import ServiceConfig
    from repro.core.store import ChunkStore
    from repro.train.checkpoint import tree_from_flat

    config = ctx.cell.config
    model, template = build(config)
    _, published = build(published_config(config))
    share = model.param_share() or {}
    traffic = dict(ctx.cell.traffic)
    hp = granite_reference.sizes(config)
    traffic["prompt"] = [t % hp.vocab for t in traffic["prompt"]]
    store = ChunkStore(ctx.workdir / "store")
    cfg = ServiceConfig(root=GenerationalGC(store).active,
                        max_batch_bytes=traffic["max_batch_bytes"])
    shapes = data.flat(published)
    make = leaf_maker(list(shapes), hp.d_conv)
    key = data.seed_key(ctx.seed)
    tenant_key = bytes.fromhex(traffic["tenant_key_hex"])
    chunk_size = int(traffic["chunk_bytes"])
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.make_image"):
        blob, want = publish_leaves(
            shapes,
            lambda p: np.asarray(make(key, p, shapes[p].shape,
                                      shapes[p].dtype)),
            lambda p, a: share_slice(a, share.get(p)),
            tenant_key=tenant_key, root=cfg.root,
            epoch=int(traffic["salt_epoch"]), chunk_size=chunk_size,
            put_chunk=lambda name, ct: store.put_if_absent(cfg.root, name,
                                                           ct))
    image_bytes = sum(int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
                      for s in shapes.values())
    ctx.log(f"image of {image_bytes} B laid into origin in "
            f"{time.perf_counter() - t0:.3f}s; {data.tree_bytes(want)} B "
            f"held")
    state = {"model": model, "store": store, "config": cfg, "blob": blob,
             "tenant_key": tenant_key, "want": want, "traffic": traffic,
             "share": model.param_share(), "hp": hp, "reference": None,
             "held_x": held_input(ctx.seed, hp.d_model),
             "held_reference": None,
             "tamper": coldstart.tamper_target(
                 {p: np.broadcast_to(np.zeros((), s.dtype), s.shape)
                  for p, s in shapes.items()}, chunk_size)}
    coldstart.warm_up(ctx, state, tree_from_flat(template, want))
    return state


def unit(ctx, state, i: int) -> dict:
    """One cold start of the share to its first token; returns its record."""
    from repro.core.service import ImageService
    from repro.core.telemetry import COUNTERS
    from repro.serve.coldstart import cold_start
    from repro.serve.engine import Request

    traffic = state["traffic"]
    before = COUNTERS.snapshot()
    t0 = time.perf_counter()
    service = ImageService(state["store"], state["config"])
    try:
        with jax.profiler.TraceAnnotation("bench.coldstart"):
            engine, stats = cold_start(state["model"], state["blob"],
                                       state["tenant_key"], service,
                                       share=state["share"])
        with jax.profiler.TraceAnnotation("bench.first_token"):
            req = Request(i, prompt=list(traffic["prompt"]),
                          max_new=int(traffic["new_tokens"]))
            engine.submit(req)
            engine.run_until_drained()
    finally:
        service.close()
    seconds = time.perf_counter() - t0
    return {"seconds": seconds,
            "load_seconds": stats["load_seconds"],
            "decode_wall_s": stats["decode_wall_s"],
            "share": stats.get("share"),
            "launches": kernel_launches(before, COUNTERS.snapshot()),
            "answered": req.done and len(req.out) == traffic["new_tokens"],
            "logits": req.first_logits,
            "params": engine.params}


def held_input(seed: int, d_model: int) -> np.ndarray:
    """The inputs of the held-experts comparison: HELD_TOKENS rows of
    unit spread (the scale of a normed hidden state), in bfloat16."""
    key = jax.random.fold_in(data.seed_key(seed), 1)
    x = jax.random.normal(key, (HELD_TOKENS, d_model), jnp.float32)
    return np.asarray(x.astype(jnp.bfloat16))


def program_held(state, params) -> list:
    """The program's held experts' part of each MoE layer on the check's
    inputs: its ``moe_apply`` on the restored layer as the decode step
    calls it (one token a slot, dropless), with the shared expert left
    out of the configuration."""
    import dataclasses

    from repro.models.moe import moe_apply

    if "held_program" not in state:
        model = state["model"]
        cfg = dataclasses.replace(model.cfg, shared_experts=0)
        dtype = jnp.dtype(model.flags.dtype)
        state["held_program"] = jax.jit(lambda p, x: moe_apply(
            p, x[:, None, :], cfg, dtype, dropless=True)[:, 0])
    flat = data.flat(params)
    x = jnp.asarray(state["held_x"])
    return [np.asarray(state["held_program"](
        granite_reference.ffn_of(flat, i, state["hp"]), x))
        for i in range(len(state["hp"].kinds))]


def reference_held(state) -> list:
    if state["held_reference"] is None:
        with jax.profiler.TraceAnnotation("bench.reference"):
            state["held_reference"] = granite_reference.held_outputs(
                state["want"], state["held_x"], state["hp"])
    return state["held_reference"]


def held_rel_err(got: list, want: list) -> float:
    """The largest over the layers of the relative L2 error of `got`
    against the reference's (output, margin), over the tokens whose
    router margin exceeds MARGIN."""
    worst = 0.0
    for g, (w, margin) in zip(got, want, strict=True):
        keep = margin > MARGIN
        g = np.asarray(g, np.float64)[keep]
        w = np.asarray(w, np.float64)[keep]
        diff = np.linalg.norm(g - w)
        worst = max(worst, diff / np.linalg.norm(w) if diff else 0.0)
    return float(worst)


def reference_logits(state) -> np.ndarray:
    if state["reference"] is None:
        with jax.profiler.TraceAnnotation("bench.reference"):
            state["reference"] = granite_reference.last_logits(
                state["want"], state["traffic"]["prompt"], state["hp"])
    return state["reference"]


def logits_rel_err(got, want) -> float:
    if got is None:
        return float("inf")
    got = np.asarray(got, np.float64)[:want.shape[0]]
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(ctx, state, rec) -> dict:
    """The unit's share read back from HBM against the seed's slices, and
    its first token's logits against the reference; the restored share
    is let go here."""
    held = held_rel_err(program_held(state, rec["params"]),
                        reference_held(state))
    got = coldstart.check(ctx, state, rec)
    got["logits_rel_err"] = logits_rel_err(rec.pop("logits"),
                                           reference_logits(state))
    got["held_experts_rel_err"] = held
    share = rec["share"] or {}
    ctx.log(f"repro.restore.share: {share.get('held_bytes')} B held of a "
            f"{share.get('image_bytes')} B image, {share.get('chunks')} "
            f"chunks fetched of {share.get('chunks_covered')} covered "
            f"({share.get('partial_chunks')} partial); logits_rel_err "
            f"{got['logits_rel_err']:.6g}, held_experts_rel_err {held:.6g}")
    return got


def limits() -> dict:
    return dict({k: 0 for k in coldstart.READINGS},
                tampered_restores_accepted=0, logits_rel_err=LOGITS_LIMIT,
                held_experts_rel_err=HELD_LIMIT)


def verify(ctx, state, records) -> tuple:
    """The exact readings added up, the largest logits error, and the
    tampered restore. Returns ({check: {value, limit}}, failed units)."""
    lim = limits()
    values = {k: sum(r["readings"][k] for r in records)
              for k in coldstart.READINGS}
    values["tampered_restores_accepted"] = \
        coldstart.tampered_restore_accepted(ctx, state)
    for k in ("logits_rel_err", "held_experts_rel_err"):
        values[k] = max(r["readings"][k] for r in records)
    failed = sum(any(v > lim[k] for k, v in r["readings"].items())
                 for r in records)
    return {k: {"value": v, "limit": lim[k]} for k, v in values.items()}, \
        failed


def end_to_end(records) -> dict:
    return coldstart.end_to_end(records)


def control(ctx) -> dict:
    """The control of ``correct``: the seed's share rounded one precision
    below the configuration's (bfloat16 -> float8_e4m3fn), placed on the
    device in the program's place and held to ``check``'s comparisons
    (the logits are the reference's on the rounded share). It has to
    read as not correct."""
    config = ctx.cell.config
    model, _ = build(config)
    _, published = build(published_config(config))
    share = model.param_share() or {}
    hp = granite_reference.sizes(config)
    shapes = data.flat(published)
    make = leaf_maker(list(shapes), hp.d_conv)
    key = data.seed_key(ctx.seed)
    want = {p: share_slice(np.asarray(make(key, p, s.shape, s.dtype)),
                           share.get(p)) for p, s in shapes.items()}
    low = {p: np.asarray(jnp.asarray(a).astype(jnp.float8_e4m3fn)
                         .astype(a.dtype)) for p, a in want.items()}
    prompt = [t % hp.vocab for t in ctx.cell.traffic["prompt"]]
    placed = jax.device_put(low)
    got = reference.restore_readings(
        placed, want, {p: ctx.platform for p in placed}, ctx.platform)
    del placed
    got["logits_rel_err"] = logits_rel_err(
        granite_reference.last_logits(low, prompt, hp),
        granite_reference.last_logits(want, prompt, hp))
    x = held_input(ctx.seed, hp.d_model)
    got["held_experts_rel_err"] = held_rel_err(
        [y for y, _ in granite_reference.held_outputs(low, x, hp)],
        granite_reference.held_outputs(want, x, hp))
    return got

