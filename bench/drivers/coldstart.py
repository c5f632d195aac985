"""Serial cold starts, a closed loop of one client.

Set-up makes the seed's parameter tree on the device, copies it to the
host, and lays the image into an on-disk ``ChunkStore`` (origin) with
the reference implementation of the image format: the image stands for
one that a training job published elsewhere, and the program's own
publish is the publish cells' to time. Last, it warms up every program
a cold start runs (``warm_up``: the decode kernels at the image's tile
shapes, the serving step).

Each unit of the window is one cold start, as a replica on a new host
pays it: a fresh ``ImageService`` (its L1 starts empty),
``repro.serve.coldstart.cold_start``, and one request of one new token
through the replica's ``ServeEngine``. The unit ends when that token is
back. Right after it, outside the window, ``check`` reads every
restored parameter back from the device, compares it with the seed tree
byte for byte, and lets the restored image go. After the window,
``verify`` alters one byte of one stored chunk and restores that chunk's
leaf through a fresh service of the same configuration: the restore has
to refuse it (``IntegrityError``), as the image format's verification
promises.

Traffic parameters (``bench/traffic/<name>.json``): ``tenant_key_hex``
(the tenant's 32-byte key), ``chunk_bytes`` and ``salt_epoch`` of the
image, ``max_batch_bytes`` (the decode tile, ``ServiceConfig``'s key: a
whole number of bytes, or ``"auto"`` for the program's per-process
sweep), ``prompt`` (token ids of the request) and ``new_tokens``;
``ServiceConfig`` keeps every other default.
"""
from __future__ import annotations

import time

import jax

from bench import data, reference
from bench.harness import kernel_launches
from bench.model import build

WARM_LEAVES = 4         # single-chunk leaves the warm-up restores
READINGS = ("bytes_differing", "leaves_not_in_hbm", "requests_unanswered")


def setup(ctx):
    from repro.core.gc import GenerationalGC
    from repro.core.service import ServiceConfig
    from repro.core.store import ChunkStore

    traffic = ctx.cell.traffic
    model, template = build(ctx.cell.config)
    store = ChunkStore(ctx.workdir / "store")
    config = ServiceConfig(root=GenerationalGC(store).active,
                           max_batch_bytes=traffic["max_batch_bytes"])
    with jax.profiler.TraceAnnotation("bench.make_tree"):
        tree = data.host_tree(data.tree_maker(template),
                              data.seed_key(ctx.seed), 0)
    want = data.flat(tree)
    tenant_key = bytes.fromhex(traffic["tenant_key_hex"])
    chunk_size = int(traffic["chunk_bytes"])
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.make_image"):
        blob = reference.publish(
            want, tenant_key=tenant_key, root=config.root,
            epoch=int(traffic["salt_epoch"]), chunk_size=chunk_size,
            put_chunk=lambda name, ct: store.put_if_absent(config.root,
                                                           name, ct))
    ctx.log(f"image of {data.tree_bytes(tree)} B laid into origin in "
            f"{time.perf_counter() - t0:.3f}s")
    state = {"model": model, "store": store, "config": config,
             "blob": blob, "tenant_key": tenant_key, "want": want,
             "traffic": traffic, "image_bytes": data.tree_bytes(tree),
             "tamper": tamper_target(want, chunk_size)}
    warm_up(ctx, state, tree)
    return state


def tamper_target(want: dict, chunk_size: int) -> tuple:
    """(leaf, chunk index) to alter: the middle chunk of the smallest leaf
    that fills at least two chunks, so its restore decodes whole tiles
    (of the smallest leaf where none does)."""
    path, off, nbytes, _, _ = min(
        reference.expected_layout(want, chunk_size),
        key=lambda r: (r[2] < 2 * chunk_size, r[2]))
    return path, off // chunk_size + -(-nbytes // chunk_size) // 2


def warm_up(ctx, state, tree) -> None:
    """Every program a cold start runs, without a whole cold start: a
    fresh service opens the image and restores its smallest leaves, one
    chunk each, through the decode tiles of the full restore; an engine
    over the seed tree serves the cell's request (the decode step)."""
    from repro.core.service import ImageService
    from repro.serve.engine import Request, ServeEngine

    t0 = time.perf_counter()
    service = ImageService(state["store"], state["config"])
    try:
        handle = service.open(state["blob"], state["tenant_key"])
        decoder = service.decoder_for(state["config"].default_policy)
        ctx.log(f"decode tile {decoder.max_batch_bytes} B "
                f"({decoder.backend})")
        sizes = handle.layout.tensors
        small = sorted(sizes, key=lambda n: sizes[n].nbytes)[:WARM_LEAVES]
        handle.restore_tree(names=small)
    finally:
        service.close()
    engine = ServeEngine(state["model"], jax.device_put(tree))
    engine.submit(Request(-1, prompt=list(state["traffic"]["prompt"]),
                          max_new=int(state["traffic"]["new_tokens"])))
    engine.run_until_drained()
    del engine
    ctx.log(f"warm-up {time.perf_counter() - t0:.3f}s")


def unit(ctx, state, i: int) -> dict:
    """One cold start to its first token; returns its record."""
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
                                       state["tenant_key"], service)
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
            "image_bytes": state["image_bytes"],
            "launches": kernel_launches(before, COUNTERS.snapshot()),
            "answered": req.done and len(req.out) == traffic["new_tokens"],
            "params": engine.params}


def check(ctx, state, rec) -> dict:
    """The unit's parameters, read back from HBM, against the seed tree;
    the restored image is let go here."""
    params = data.flat(rec.pop("params"))
    platforms = {p: ({d.platform for d in a.devices()}.pop()
                     if isinstance(a, jax.Array) and len(a.devices()) == 1
                     else "host") for p, a in params.items()}
    got = reference.restore_readings(params, state["want"], platforms,
                                     ctx.platform)
    got["requests_unanswered"] = int(not rec["answered"])
    return got


def tampered_restore_accepted(ctx, state) -> int:
    """1 when a restore returns although one byte of a stored chunk of
    the leaf it reads was altered, else 0 (it raised ``IntegrityError``).
    The chunk's stored bytes are put back afterwards."""
    from repro.core.crypto.convergent import IntegrityError
    from repro.core.service import ImageService

    store, root = state["store"], state["config"].root
    leaf, index = state["tamper"]
    body, _ = reference.open_manifest(state["blob"], state["tenant_key"])
    name = next(c[1] for c in body["chunks"] if c[0] == index)
    good = store.get_chunk(root, name)
    bad = bytearray(good)
    bad[len(bad) // 2] ^= 0x01
    store.delete_chunk(root, name)
    store.put_if_absent(root, name, bytes(bad))
    service = ImageService(store, state["config"])
    try:
        service.open(state["blob"], state["tenant_key"]).restore_tree(
            names=[leaf])
        accepted = 1
    except IntegrityError:
        accepted = 0
    finally:
        service.close()
        store.delete_chunk(root, name)
        store.put_if_absent(root, name, good)
    ctx.log(f"tampered chunk {index} of {leaf}: "
            f"{'accepted' if accepted else 'refused'}")
    return accepted


def verify(ctx, state, records) -> tuple:
    """The units' readings added up, and the tampered restore. Returns
    ({check: {value, limit}}, failed units)."""
    totals = {k: sum(r["readings"][k] for r in records) for k in READINGS}
    totals["tampered_restores_accepted"] = tampered_restore_accepted(
        ctx, state)
    failed = sum(any(r["readings"].values()) for r in records)
    return {k: {"value": v, "limit": 0} for k, v in totals.items()}, failed


def end_to_end(records) -> dict:
    return {"coldstart_s": sum(r["seconds"] for r in records) / len(records)}
