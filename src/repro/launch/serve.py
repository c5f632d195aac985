"""Production-style serving launcher: cold-start a replica from a chunk
store manifest and serve a batch of synthetic requests.

The flags build ONE ``ServiceConfig``; everything the read path shares —
L1/L2 tiers, admission control, origin-fetch concurrency, the decode
pool — is owned by a single process-wide ``ImageService``, and the
per-restore pipeline shape is one ``ReadPolicy``.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      [--store DIR --image IMAGE_ID] [--requests 8]
If no --store is given, a model is initialized, imaged into a temp store,
and then cold-started from it (full loop demo).
"""
from __future__ import annotations

import argparse
import tempfile
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--store", default=None)
    ap.add_argument("--image", default=None)
    ap.add_argument("--root", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the architecture's small CPU-test "
                         "config (--no-reduced: its published widths)")
    ap.add_argument("--l1-bytes", type=int, default=256 << 20,
                    help="shared worker-local L1 cache size (0 = no L1)")
    ap.add_argument("--l2-nodes", type=int, default=6,
                    help="erasure-coded L2 cluster size (0 = no L2)")
    ap.add_argument("--l2-stripe-deadline-ms", type=float, default=None,
                    help="per-stripe GET deadline in ms: a stripe node "
                         "that never answers (blackholed) costs this "
                         "timeout instead of a hang (default: the "
                         "cache's built-in deadline)")
    ap.add_argument("--l2-hedge-quantile", type=float, default=None,
                    help="hedged stripe GETs: race one extra request "
                         "against any stripe slower than this quantile "
                         "of recent stripe latencies, e.g. 0.95 "
                         "(default: hedging off)")
    ap.add_argument("--l2-infection-threshold", type=int, default=0,
                    help="hot-key salting: windowed per-chunk request "
                         "count past which a chunk is salted into "
                         "multiple placement keys (0 = salting off)")
    ap.add_argument("--l2-salt-count", type=int, default=3,
                    help="placement keys an infected chunk is salted "
                         "into (reads round-robin, writes fan out)")
    ap.add_argument("--peer-workers", type=int, default=0,
                    help="peer provisioning mesh size: simulate this "
                         "many workers sharing a FaaSNet-style peer "
                         "tier and join it as worker 0, probed between "
                         "L1 and L2 (0 = no peer tier)")
    ap.add_argument("--peer-fanout", type=int, default=4,
                    help="provisioning-tree arity: joiners of an "
                         "in-flight chunk receive it through a tree "
                         "this wide rooted at the fetching worker")
    ap.add_argument("--peer-registration", default="all",
                    choices=["all", "origin"],
                    help="which workers advertise chunks in the peer "
                         "directory: all = every acquirer (origin, L2, "
                         "peer transfers — the tree compounds); origin "
                         "= origin-fetchers only")
    ap.add_argument("--peer-deadline-ms", type=float, default=2000.0,
                    help="bounded wait on a joined peer flight before "
                         "falling through to L2/origin")
    ap.add_argument("--peer-fault", default=None, metavar="WID:KIND",
                    help="peer fault injection, e.g. 3:crashed or "
                         "1:blackholed — apply that FaultPlan to worker "
                         "WID in the mesh (transfers from it fail and "
                         "fall through)")
    ap.add_argument("--max-coldstarts", type=int, default=4,
                    help="admission control: concurrent cold starts this "
                         "replica accepts before REJECTING (RejectingLimiter, "
                         "paper §4.2)")
    ap.add_argument("--fetch-concurrency", type=int, default=16,
                    help="bound on concurrent origin chunk fetches across "
                         "all restores (BlockingLimiter); 0 = unbounded")
    ap.add_argument("--parallelism", type=int, default=8,
                    help="per-restore fetch pipeline width")
    from repro.core.decode import known_backend_names

    ap.add_argument("--decode-backend", default="auto",
                    choices=known_backend_names(),
                    help="post-fetch batch decode backend from the "
                         "core.decode registry: auto (default) = probe "
                         "the platform (bitsliced-fused on TPU, python "
                         "on CPU); python/numpy = T-table numpy AES + "
                         "hashlib; bitsliced-fused/fused = ONE fused "
                         "verify+decrypt pass per tile; xla/jax = jit'd "
                         "gather pass + hashlib; bitsliced = gather-free "
                         "Pallas AES + lockstep SHA verify kernels; "
                         "serial = per-chunk oracle")
    ap.add_argument("--max-batch-bytes", type=int, default=None,
                    help="decode tile size in bytes (default: per-"
                         "backend autotuned at first use — a small "
                         "timed sweep, cached per process; an explicit "
                         "value here pins the tile and skips the sweep)")
    ap.add_argument("--eager-min-bytes", type=int, default=None,
                    help="minimum partial-tile bytes before an eager "
                         "flush may fire (default: ServiceConfig's "
                         "tuned threshold)")
    ap.add_argument("--read-path", default="streamed",
                    choices=["streamed", "staged", "serial"],
                    help="ReadPolicy.mode: streamed = decode tiles overlap "
                         "the fetch via a bounded hand-off queue; staged = "
                         "two-phase fetch-then-decode; serial = the "
                         "per-chunk byte-identity oracle")
    ap.add_argument("--publish", action="store_true",
                    help="image the model through the batched write path "
                         "(core.publish.PublishPipeline via the service: "
                         "vectorized encryption, bounded-parallel dedup'd "
                         "PUTs, L1 warming) instead of the serial "
                         "create_image oracle")
    ap.add_argument("--upload-parallelism", type=int, default=8,
                    help="bounded-parallel PUTs on the publish path")
    ap.add_argument("--eager-flush", action="store_true",
                    help="idle-queue opportunistic flush: decode the "
                         "partial tile whenever the streamed consumer "
                         "would otherwise block")
    ap.add_argument("--retry-attempts", type=int, default=0,
                    help="origin retry policy: attempts per origin "
                         "GET/PUT before giving up (0/1 = retries off, "
                         "today's single-attempt behavior)")
    ap.add_argument("--retry-base-ms", type=float, default=10.0,
                    help="backoff floor per retry (decorrelated jitter: "
                         "sleep ~ U[base, prev*3], capped)")
    ap.add_argument("--retry-cap-ms", type=float, default=500.0,
                    help="backoff ceiling per retry")
    ap.add_argument("--retry-budget-ms", type=float, default=None,
                    help="total wall-clock budget across one call's "
                         "retries; exhausting it raises the last error "
                         "(default: unbounded)")
    ap.add_argument("--retry-attempt-timeout-ms", type=float, default=None,
                    help="per-attempt origin deadline, forwarded to "
                         "stores that accept deadline_s (a hung origin "
                         "read costs this instead of a hang)")
    ap.add_argument("--breaker-threshold", type=float, default=None,
                    help="origin circuit breaker: error rate over the "
                         "sliding window that trips it open, e.g. 0.5 "
                         "(default: breaker off)")
    ap.add_argument("--breaker-window", type=int, default=64,
                    help="breaker sliding window size (origin outcomes)")
    ap.add_argument("--breaker-min-samples", type=int, default=10,
                    help="outcomes required in-window before the "
                         "breaker may trip")
    ap.add_argument("--breaker-cooldown-ms", type=float, default=1000.0,
                    help="open -> half-open cooldown; shed cold starts "
                         "carry it as retry-after")
    ap.add_argument("--breaker-half-open-probes", type=int, default=1,
                    help="concurrent origin probes allowed half-open")
    ap.add_argument("--no-breaker-shed", action="store_true",
                    help="keep admitting cold starts while the breaker "
                         "is open (default: shed with retry-after)")
    ap.add_argument("--origin-fault", default=None, metavar="SPEC",
                    help="origin fault injection (FaultyStore wrap): "
                         "'unavailable', or comma k=v pairs of "
                         "error_p/corrupt_p/delay_ms, e.g. "
                         "error_p=0.1,corrupt_p=0.01,delay_ms=5")
    ap.add_argument("--publish-name-index", default=None, metavar="PATH",
                    help="persist the publish-path plaintext-hash -> "
                         "chunk-name cache to this sidecar file (loaded "
                         "on start, atomically saved after publish), so "
                         "re-publishes skip encryption across processes")
    args = ap.parse_args()

    from repro.core.decode import enable_persistent_compilation_cache
    print(f"jax persistent compilation cache: "
          f"{enable_persistent_compilation_cache()}")

    import jax

    from repro.configs import get_config
    from repro.core.gc import GenerationalGC
    from repro.core.loader import create_image
    from repro.core.service import ImageService, ReadPolicy, ServiceConfig
    from repro.core.store import ChunkStore
    from repro.models import build_model
    from repro.serve.coldstart import cold_start
    from repro.serve.engine import Request
    from repro.train.checkpoint import state_to_tree

    cfg = get_config(args.arch).reduced() if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    key = b"S" * 32

    pending_tree = None
    if args.store and args.image:
        store = ChunkStore(args.store)
        blob = store.get_manifest(args.root or "R1", args.image)
        root = args.root or "R1"
    else:
        store = ChunkStore(tempfile.mkdtemp(prefix="repro-serve-"))
        gc = GenerationalGC(store)
        params = model.init(jax.random.key(0))
        root = gc.active
        if args.publish:
            # imaged below, through the service's batched write path —
            # the fresh ciphertexts then warm the L1 the cold start hits
            pending_tree = state_to_tree(params)
            blob = None
        else:
            blob, stats = create_image(state_to_tree(params), tenant="serve",
                                       tenant_key=key, store=store,
                                       root=root)
            print(f"imaged {stats.total_chunks} chunks "
                  f"({stats.bytes_total/1e6:.1f} MB)")

    # ONE config object owns every shared read-path knob: cache tiers,
    # admission control (reject excess cold starts) and fetch concurrency
    # (block excess origin reads) are separate bounds (§4.2)
    policy = ReadPolicy(mode=args.read_path, parallelism=args.parallelism,
                        eager_flush=args.eager_flush,
                        eager_min_bytes=args.eager_min_bytes)
    svc_cfg = ServiceConfig(
        l1_bytes=args.l1_bytes,
        l2_nodes=args.l2_nodes,
        l2_stripe_deadline_s=(args.l2_stripe_deadline_ms / 1e3
                              if args.l2_stripe_deadline_ms is not None
                              else None),
        l2_hedge_quantile=args.l2_hedge_quantile,
        l2_infection_threshold=args.l2_infection_threshold,
        l2_salt_count=args.l2_salt_count,
        max_coldstarts=args.max_coldstarts,
        fetch_concurrency=args.fetch_concurrency,
        decode_backend=args.decode_backend,
        peer_fanout=args.peer_fanout,
        peer_deadline_s=args.peer_deadline_ms / 1e3,
        peer_registration=args.peer_registration,
        root=root,
        upload_parallelism=args.upload_parallelism,
        default_policy=policy,
        retry_attempts=args.retry_attempts,
        retry_base_s=args.retry_base_ms / 1e3,
        retry_cap_s=args.retry_cap_ms / 1e3,
        retry_total_budget_s=(args.retry_budget_ms / 1e3
                              if args.retry_budget_ms is not None else None),
        retry_attempt_timeout_s=(args.retry_attempt_timeout_ms / 1e3
                                 if args.retry_attempt_timeout_ms is not None
                                 else None),
        breaker_threshold=args.breaker_threshold,
        breaker_window=args.breaker_window,
        breaker_min_samples=args.breaker_min_samples,
        breaker_cooldown_s=args.breaker_cooldown_ms / 1e3,
        breaker_half_open_probes=args.breaker_half_open_probes,
        breaker_shed_coldstarts=not args.no_breaker_shed,
        publish_name_index_path=args.publish_name_index,
    )
    if args.origin_fault:
        from repro.core.faults import FaultyStore, OriginFaultPlan
        if args.origin_fault.strip() == "unavailable":
            plan = OriginFaultPlan.unavailable()
        else:
            kv = dict(p.split("=", 1)
                      for p in args.origin_fault.split(",") if p)
            plan = OriginFaultPlan.flaky(
                error_p=float(kv.get("error_p", 0.0)),
                corrupt_p=float(kv.get("corrupt_p", 0.0)),
                delay_s=float(kv.get("delay_ms", 0.0)) / 1e3)
        store = FaultyStore(store, plan)
        print(f"origin fault injection: {plan}")
    if args.max_batch_bytes is not None:
        svc_cfg.max_batch_bytes = args.max_batch_bytes
    if args.eager_min_bytes is not None:
        svc_cfg.eager_min_bytes = args.eager_min_bytes
    peer = None
    if args.peer_workers > 0:
        from repro.core.service import build_peer_mesh
        mesh = build_peer_mesh(svc_cfg, args.peer_workers)
        if args.peer_fault:
            from repro.core.cache.distributed import FaultPlan
            wid, kind = args.peer_fault.split(":", 1)
            mesh.set_fault(int(wid), getattr(FaultPlan, kind)())
        peer = mesh.client(0)
        print(f"peer mesh: {args.peer_workers} workers, fanout "
              f"{args.peer_fanout}, registration {args.peer_registration}"
              f"{', fault ' + args.peer_fault if args.peer_fault else ''}")
    service = ImageService(store, svc_cfg, peer=peer)
    if pending_tree is not None:
        t0 = time.time()
        blob, stats = service.publish(pending_tree, tenant="serve",
                                      tenant_key=key)
        print(f"published {stats.total_chunks} chunks "
              f"({stats.bytes_total/1e6:.1f} MB) in {time.time()-t0:.2f}s "
              f"[batched pipeline, {stats.unique_chunks} uploaded, "
              f"{stats.dedup_chunks} dedup'd]")
    t0 = time.time()
    engine, stats = cold_start(model, blob, key, service, policy=policy,
                               max_batch=4, max_len=64)
    pipe = ""
    if stats.get("fetch_busy_s") is not None:   # serial mode has no split
        pipe = (f", fetch {stats['fetch_busy_s']:.2f}s busy "
                f"+ {stats['fetch_blocked_s']:.2f}s blocked on decode, "
                f"decode[{stats['decode_backend']}] "
                f"{stats['decode_wall_s']:.2f}s "
                f"+ {stats['decode_starved_s']:.2f}s starved")
    if stats.get("streamed"):
        pipe += (f", {stats['overlap_s']:.2f}s decode hidden under fetch "
                 f"(queue hwm {stats['queue_hwm']}"
                 f"{', eager flushes %d' % stats['eager_flushes'] if args.eager_flush else ''})")
    print(f"cold start {time.time()-t0:.2f}s [{args.read_path}] "
          f"(load {stats['load_seconds']:.2f}s, tenant {stats['tenant']}, "
          f"origin fetches {stats['origin_fetches']:.0f}{pipe})")

    reqs = [Request(i, prompt=[1 + i, 2, 3], max_new=args.max_new)
            for i in range(args.requests)]
    for r in reqs:
        engine.submit(r)
    run = engine.run_until_drained()
    done = sum(r.done for r in reqs)
    print(f"served {done}/{len(reqs)} requests in {run['steps']} decode steps "
          f"({run['seconds']:.2f}s)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: {r.out}")
    service.close()        # drain decoder pools + session caches


if __name__ == "__main__":
    main()
