"""Command-line training AND serving entry point (reference
``parallelism/main/ParallelWrapperMain.java`` — the training half; the
``serve`` subcommand is the production-serving half the reference kept
in ParallelInference).

Usage:
    python -m deeplearning4j_tpu.cli --model lenet --dataset mnist \\
        --epochs 2 --batch-size 64 --workers 8 --output /tmp/model.zip \\
        --stats /tmp/stats.jsonl --dashboard /tmp/dash.html

    python -m deeplearning4j_tpu.cli serve --model /ckpts --port 8080 \\
        --batch-limit 32 --max-wait-ms 5
    # --model: zoo name (fresh weights — smoke), checkpoint zip, or a
    # checkpoint DIRECTORY (newest valid checkpoint; /reload re-polls it)

    python -m deeplearning4j_tpu.cli flight-dump /ckpts
    # read a flight-recorder black box (file, or the newest
    # flight_recorder_*.json in a directory) as a human timeline
"""

from __future__ import annotations

import argparse
import sys
import time


# (height, width, channels) per image dataset; None = non-image
DATASET_SHAPES = {
    "mnist": (28, 28, 1),
    "svhn": (32, 32, 3),
    "tinyimagenet": (64, 64, 3),
    "iris": None,
    "uci": None,
}


def build_dataset(name: str, batch_size: int, num_examples):
    from deeplearning4j_tpu.data.fetchers import (
        SvhnDataSetIterator,
        TinyImageNetDataSetIterator,
        UciSequenceDataSetIterator,
    )
    from deeplearning4j_tpu.data.mnist import (
        IrisDataSetIterator,
        MnistDataSetIterator,
    )

    name = name.lower()
    if name == "mnist":
        return MnistDataSetIterator(batch_size, train=True,
                                    num_examples=num_examples), 10
    if name == "iris":
        return IrisDataSetIterator(batch_size), 3
    if name == "svhn":
        return SvhnDataSetIterator(batch_size, num_examples=num_examples), 10
    if name == "tinyimagenet":
        return TinyImageNetDataSetIterator(batch_size,
                                           num_examples=num_examples), 200
    if name == "uci":
        return UciSequenceDataSetIterator(batch_size,
                                          num_examples=num_examples), 6
    raise SystemExit(f"Unknown dataset '{name}'")


def build_model(name: str, num_classes: int, dataset: str,
                compute_dtype=None, remat_policy=None):
    from deeplearning4j_tpu.models.selector import ModelSelector

    global_knobs = {}
    if compute_dtype:
        global_knobs["compute_dtype"] = compute_dtype
    if remat_policy:
        global_knobs["remat_policy"] = remat_policy
    kwargs = {"num_classes": num_classes, **global_knobs}
    shape = DATASET_SHAPES.get(dataset.lower())
    if shape is not None:
        # size the model's input to the dataset (zoo models accept
        # height/width/channels) — otherwise the first step dies with an
        # opaque XLA shape mismatch
        kwargs.update(height=shape[0], width=shape[1], channels=shape[2])
    try:
        model = ModelSelector.select(name, **kwargs)
    except TypeError:
        # model without spatial kwargs (e.g. text models): drop only the
        # spatial sizing, keep the precision/remat knobs
        model = ModelSelector.select(name, num_classes=num_classes,
                                     **global_knobs)
    return model.init()


def serve_main(argv) -> int:
    """``serve`` subcommand: checkpoint/zoo model → warmed bucketed
    engine → HTTP server (serving/ package)."""
    ap = argparse.ArgumentParser(
        prog="deeplearning4j_tpu serve",
        description="Serve a model over HTTP: bucketed dynamic batching, "
                    "compile-cache warmup, backpressure, hot reload",
    )
    ap.add_argument("--model", default=None,
                    help="zoo model name (fresh weights — smoke runs), "
                         "checkpoint zip, or checkpoint DIRECTORY "
                         "(newest valid; also the /reload source). "
                         "Optional with --registry-dir (the registry "
                         "names the models)")
    ap.add_argument("--registry-dir", default=None,
                    help="serve a model REGISTRY instead of one model: "
                         "multi-model routing (POST /models/<name>/"
                         "predict|generate, GET /models/<name>/healthz), "
                         "canary routing of newly published versions "
                         "with auto-rollback, per-tenant quotas, LRU "
                         "cold-model eviction. Pair with a trainer's "
                         "cli fit --publish-to for the continuous "
                         "train→serve loop")
    ap.add_argument("--canary-fraction", type=float, default=0.1,
                    help="share of a model's traffic routed to a newly "
                         "validated version while its canary window runs")
    ap.add_argument("--canary-window", type=float, default=30.0,
                    help="canary window SECONDS: a clean window auto-"
                         "promotes; any dispatch failure, latency blow-up "
                         "or score regression trips auto-rollback")
    ap.add_argument("--tenant-quota", type=int, default=None,
                    help="max in-flight requests per tenant (X-Tenant "
                         "header / payload key); beyond it THAT tenant "
                         "gets typed 503s, others are unaffected")
    ap.add_argument("--max-live-models", type=int, default=4,
                    help="warmed engines held live; colder models are "
                         "LRU-evicted and rewarmed on demand")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080,
                    help="0 binds an ephemeral port (printed at startup)")
    ap.add_argument("--batch-limit", type=int, default=32,
                    help="max examples per device dispatch")
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="dispatch deadline: a non-full batch waits at most "
                         "this long for co-travelers")
    ap.add_argument("--queue-limit", type=int, default=256,
                    help="bounded request queue; beyond it requests are "
                         "rejected 503 (backpressure)")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated batch-size buckets (default: "
                         "powers of two up to --batch-limit)")
    ap.add_argument("--seq-buckets", default=None,
                    help="comma-separated sequence-length buckets for "
                         "rank-3 inputs (default: the zoo model's "
                         "serving_seq_buckets hint, if any)")
    ap.add_argument("--workers", type=int, default=1,
                    help=">1 shards each dispatched batch over that many "
                         "devices (mesh data axis)")
    ap.add_argument("--mesh", default=None, metavar="BxM",
                    help="serve TENSOR-PARALLEL on a 2-D (batch, model) "
                         "mesh, e.g. '2x4': weights are policy-sharded "
                         "over the model axis (no device holds the full "
                         "model), batches over the batch axis; a bare "
                         "'4' means 4x1 (pure batch). Checkpoints of any "
                         "topology reshard onto the mesh at load, "
                         "device-to-device. Supersedes --workers; "
                         "incompatible with --int8-serving")
    ap.add_argument("--mesh-policy", action="append", default=None,
                    metavar="PATTERN=DIM",
                    help="override the sharding policy for params whose "
                         "tree path matches the regex PATTERN: DIM is "
                         "the axis index to split over 'model', or 'r' "
                         "to replicate (repeatable; first match wins, "
                         "overrides are checked before the policy's own "
                         "rules)")
    ap.add_argument("--cpu-mesh", type=int, default=None, metavar="N",
                    help="force an N-device virtual CPU mesh before jax "
                         "initializes (a 2x4 --mesh needs 8)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="explicit /reload source (default: --model when "
                         "it is a directory)")
    ap.add_argument("--num-classes", type=int, default=10,
                    help="zoo-name models only: output classes")
    ap.add_argument("--int8-serving", action="store_true",
                    help="serve int8 weight-quantized dense/output heads "
                         "(per-channel scales; opt-in — fp32 model weights "
                         "are untouched; refused when the zoo model's "
                         "serving_int8 hint is False)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip bucket pre-compilation (first request per "
                         "shape then pays the compile)")
    ap.add_argument("--gen-slots", type=int, default=0,
                    help="enable POST /generate with this many continuous-"
                         "batching decode slots (0 = off); the model must "
                         "have an incremental-decode path (TransformerLM "
                         "KV cache or a recurrent net's carried state)")
    ap.add_argument("--gen-max-length", type=int, default=None,
                    help="decode slab length per slot (default: the "
                         "model's max_length / 256 for recurrent nets); "
                         "prompt + max_new must fit it")
    ap.add_argument("--gen-prefill-buckets", default=None,
                    help="comma-separated prompt-length buckets for "
                         "prefill padding (default: the model's "
                         "serving_seq_buckets hint, else powers of two)")
    ap.add_argument("--gen-queue-limit", type=int, default=64,
                    help="bounded generation admission queue; beyond it "
                         "requests are rejected 503 (backpressure)")
    ap.add_argument("--spec-decode-k", type=int, default=1,
                    help="speculative decoding: propose up to k tokens "
                         "per slot per dispatch and verify them in ONE "
                         "batched step (1 = off); greedy output stays "
                         "bit-identical to token-by-token decode")
    ap.add_argument("--spec-draft-mode", default="ngram",
                    choices=("ngram", "truncated"),
                    help="draft source with --spec-decode-k > 1: 'ngram' "
                         "(per-engine table learned from prompts and "
                         "accepted tokens — free) or 'truncated' (half-"
                         "depth model pass; transformers only)")
    ap.add_argument("--prefix-cache-mb", type=float, default=0.0,
                    help="shared-prefix KV cache budget in MiB (0 = "
                         "off): a request whose prompt hashes to a "
                         "cached entry copies the prefix KV into its "
                         "slot instead of re-running prefill; LRU-bytes "
                         "eviction, counted against the slab memory "
                         "estimate")
    ap.add_argument("--smoke", action="store_true",
                    help="serve ONE local request through the HTTP stack, "
                         "print the result, shut down (CI gate)")
    ap.add_argument("--controllers", action="store_true",
                    help="with --smoke: arm the adaptive-capacity loop "
                         "(loadgen ControllerHub + DeadlineTuner on a "
                         "deliberately tight SLO) and replay a short "
                         "compressed builtin load plan against the live "
                         "server — passes only if a verdict-carrying "
                         "controller_retune flight event fires")
    ap.add_argument("--cluster", action="store_true",
                    help="registry mode only: join the multi-replica "
                         "tier coordinated through the registry dir's "
                         "fsync'd journal — heartbeats, one epoch-fenced "
                         "canary controller per window, cross-replica "
                         "gate aggregation (a regression ANY replica "
                         "sees rolls back everywhere), cluster-wide "
                         "tenant budgets")
    ap.add_argument("--replica-id", default=None,
                    help="stable replica identity in the cluster journal "
                         "(default: r<pid>)")
    ap.add_argument("--heartbeat-s", type=float, default=1.0,
                    help="cluster heartbeat period; liveness is judged "
                         "against --lease-ttl-s")
    ap.add_argument("--lease-ttl-s", type=float, default=None,
                    help="heartbeat staleness after which a replica is "
                         "lost and its leases stealable (default: 3x "
                         "--heartbeat-s)")
    ap.add_argument("--global-tenant-quota", type=int, default=None,
                    help="cluster-WIDE max in-flight per tenant, split "
                         "into per-replica budget shares that rebalance "
                         "on heartbeat (idle replicas lend headroom)")
    args = ap.parse_args(argv)
    if args.model is None and args.registry_dir is None:
        ap.error("one of --model or --registry-dir is required")
    if args.mesh and args.int8_serving:
        ap.error("--mesh and --int8-serving do not compose: int8 "
                 "per-channel scales would be sharded by the TP policy")

    if args.cpu_mesh:
        import os as _os

        flags = _os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            _os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{int(args.cpu_mesh)}").strip()
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")

    from deeplearning4j_tpu.models.selector import ZOO, ModelSelector
    from deeplearning4j_tpu.serving import (
        BucketPolicy,
        InferenceEngine,
        InferenceServer,
    )

    if args.registry_dir is not None:
        return _serve_registry(args)

    batch_buckets = (None if args.buckets is None
                     else [int(b) for b in args.buckets.split(",")])
    seq_buckets = (None if args.seq_buckets is None
                   else [int(t) for t in args.seq_buckets.split(",")])
    key = args.model.lower()
    if key in ZOO and seq_buckets is None:
        # zoo models carry a per-model sequence-bucket hint
        seq_buckets = ZOO[key].serving_seq_buckets
    buckets = BucketPolicy(batch_buckets=batch_buckets,
                           max_batch=args.batch_limit,
                           seq_buckets=seq_buckets)

    mesh = None
    engine_cls = InferenceEngine
    if args.mesh:
        from deeplearning4j_tpu.parallel.serving_mesh import ServingMesh
        from deeplearning4j_tpu.serving.sharded import ShardedInferenceEngine

        mesh = ServingMesh.from_spec(args.mesh)
        engine_cls = ShardedInferenceEngine
        print(f"mesh: {mesh.n_data}x{mesh.n_model} (batch x model), "
              f"{mesh.n_devices} devices", flush=True)
    elif args.workers > 1:
        from deeplearning4j_tpu.parallel.mesh import TrainingMesh

        mesh = TrainingMesh(data=args.workers)
    # serving metrics publish into the process-wide registry, so a
    # co-located trainer (or anything else using obs.default_registry)
    # and this server share ONE Prometheus surface
    from deeplearning4j_tpu.obs.metrics import default_registry
    from deeplearning4j_tpu.serving.metrics import ServingMetrics

    eng_kwargs = dict(buckets=buckets, mesh=mesh,
                      metrics=ServingMetrics(registry=default_registry()))
    if args.mesh and args.mesh_policy:
        eng_kwargs["policy_overrides"] = args.mesh_policy
    if args.int8_serving:
        if key in ZOO and not getattr(ZOO[key], "serving_int8", True):
            ap.error(f"--int8-serving: zoo model {key!r} declares "
                     "serving_int8=False (its heads do not tolerate "
                     "weight quantization)")
        eng_kwargs["int8_serving"] = True
    if args.checkpoint_dir:
        eng_kwargs["checkpoint_dir"] = args.checkpoint_dir
    if key in ZOO:
        model, origin = ModelSelector.load_or_init(
            args.model, num_classes=args.num_classes)
        engine = engine_cls(model, **eng_kwargs)
    else:
        # checkpoint zip/dir: from_checkpoint records the content
        # fingerprint, so a periodic no-change /reload poll is a no-op
        engine = engine_cls.from_checkpoint(args.model, **eng_kwargs)
        origin = engine.describe()["source"]
    print(f"serving {type(engine.model).__name__} from {origin} "
          f"({engine.buckets!r})", flush=True)
    if args.mesh:
        rep = engine.shard_report
        print(f"sharded: policy {rep['policy']}, "
              f"{rep['per_device_bytes']:,}/{rep['total_bytes']:,} "
              f"bytes per device "
              f"({rep['replicated_bytes']:,} replicated), "
              f"reshard host bytes "
              f"{int(engine.reshard_stats.host_bytes)}", flush=True)
    if not args.no_warmup:
        shape = engine.example_shape()
        if shape is None:
            print("warmup skipped: model conf declares no input type "
                  "(first request per bucket compiles lazily)", flush=True)
        else:
            rep = engine.warmup()
            print(f"warmup: {rep['shapes']} shapes, {rep['compiles']} "
                  f"compiles, {rep['seconds']}s", flush=True)
            # hardware-efficiency gauges for the warmed forward: FLOPs/
            # bytes/peak-memory of the top bucket + a serving MFU gauge
            # driven by the measured request rate (obs/cost.py)
            cost = engine.publish_cost_metrics()
            if "error" not in cost:
                print(f"cost: {cost.get('flops_per_example', 0):.3e} "
                      f"FLOPs/example at bucket {cost['bucket']} "
                      "(MFU gauge live on /metrics)", flush=True)

    generation = None
    if args.gen_slots > 0:
        from deeplearning4j_tpu.serving.generate import GenerationEngine
        from deeplearning4j_tpu.serving.metrics import GenerationMetrics

        gen_buckets = (None if args.gen_prefill_buckets is None
                       else [int(t)
                             for t in args.gen_prefill_buckets.split(",")])
        gen_kwargs = dict(
            n_slots=args.gen_slots,
            max_length=args.gen_max_length,
            prefill_buckets=gen_buckets,
            queue_limit=args.gen_queue_limit,
            spec_decode_k=args.spec_decode_k,
            draft_mode=args.spec_draft_mode,
            prefix_cache_mb=args.prefix_cache_mb,
            metrics=GenerationMetrics(registry=default_registry()))
        try:
            if args.mesh:
                from deeplearning4j_tpu.parallel.serving_mesh import (
                    ShardingPolicyError,
                )
                from deeplearning4j_tpu.serving.sharded import (
                    sharded_generation_engine,
                )

                try:
                    generation = sharded_generation_engine(
                        engine.model, mesh, **gen_kwargs)
                except ShardingPolicyError as e:
                    # a model the mesh cannot decode (recurrent backend,
                    # non-divisible heads) still serves /predict sharded
                    print(f"sharded generation disabled: {e}", flush=True)
            else:
                generation = GenerationEngine(engine.model, **gen_kwargs)
        except TypeError as e:
            print(f"generation disabled: {e}", flush=True)
        if generation is not None:
            if not args.no_warmup:
                rep = generation.warmup()
                print(f"generation warmup: buckets {rep.get('buckets')}, "
                      f"compiles {rep.get('compiles')}, "
                      f"{rep.get('seconds')}s", flush=True)
            extras = ""
            if generation.spec_decode_k > 1:
                extras += (f", spec k={generation.spec_decode_k} "
                           f"[{generation.draft_mode}]")
            if args.prefix_cache_mb > 0:
                extras += f", prefix cache {args.prefix_cache_mb:g}MiB"
            print(f"generation: {generation.n_slots} slots x "
                  f"max_length {generation.max_length} "
                  f"({generation.backend.kind} backend, "
                  f"{generation.memory_report['cache_bytes']:,} cache "
                  f"bytes{extras})", flush=True)

    server = InferenceServer(
        engine, host=args.host, port=args.port,
        batch_limit=args.batch_limit, max_wait_ms=args.max_wait_ms,
        queue_limit=args.queue_limit, generation=generation)
    print(f"listening on http://{args.host}:{server.port} "
          "(POST /predict, /predict_npy"
          + (", /generate" if generation is not None else "")
          + ", /reload; GET /healthz, /metrics, /alerts)",
          flush=True)
    if args.smoke:
        import http.client
        import json as _json

        shape = engine.example_shape() or (1,)
        server.start()
        conn = http.client.HTTPConnection(args.host, server.port, timeout=30)
        x = [[0.0] * shape[-1]] if len(shape) == 1 else None
        if x is None:
            import numpy as _np

            x = _np.zeros((1,) + tuple(shape), _np.float32).tolist()
        conn.request("POST", "/predict", _json.dumps({"inputs": x}))
        resp = conn.getresponse()
        body = _json.loads(resp.read())
        conn.close()
        ok = resp.status == 200 and "outputs" in body
        print(f"smoke: HTTP {resp.status} "
              f"{'ok' if ok else body}", flush=True)
        if ok and args.controllers:
            ok = _smoke_controllers(args, server, engine, shape)
        server.shutdown()
        return 0 if ok else 1
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        print("shutting down (draining queue)", flush=True)
        server.shutdown()
    return 0


def _smoke_controllers(args, server, engine, shape) -> bool:
    """``serve --smoke --controllers``: arm the observe→act loop
    against the live server and replay a compressed builtin plan
    through the real HTTP stack. The SLO target is deliberately tight
    so real request latency breaches it — the DeadlineTuner must shed
    the batcher deadline and record a verdict-carrying
    ``controller_retune`` flight event, which is the pass criterion."""
    from deeplearning4j_tpu.loadgen import (
        BUILTIN_PLANS,
        ControllerHub,
        DeadlineTuner,
        LoadRunner,
        http_target,
    )
    from deeplearning4j_tpu.obs import flight as _flight
    from deeplearning4j_tpu.obs.metrics import default_registry
    from deeplearning4j_tpu.obs.slo import build_default_evaluator

    stream = BUILTIN_PLANS["diurnal_flash"]().compile(duration_s=6.0)
    evaluator = build_default_evaluator(
        registry=default_registry(), latency_slo_ms=0.01)
    hub = ControllerHub(evaluator, [
        DeadlineTuner(server.batcher, engine=engine, cooldown_s=0.5)])
    runner = LoadRunner(
        stream, http_target(f"{args.host}:{server.port}", tuple(shape)),
        compression=4.0, on_tick=hub.tick)
    rec = _flight.default_flight_recorder()
    seq0 = rec.recorded_total
    report = runner.run()
    retunes = [e for e in rec.events()
               if e["seq"] >= seq0 and e["kind"] == "controller_retune"]
    d = report.describe()
    print(f"controllers: replayed {d['submitted']} requests "
          f"(ok={report.ok()}, p99={d['p99_ms']}ms) -> "
          f"{len(retunes)} retune(s), max_wait_ms="
          f"{server.batcher.max_wait_s * 1e3:.3f}", flush=True)
    for e in retunes[:3]:
        print(f"  controller_retune: {e.get('action')} "
              f"verdict={e.get('verdict')} alerts={e.get('alerts')}",
              flush=True)
    return report.ok() > 0 and bool(retunes)


def _serve_registry(args) -> int:
    """Registry mode of the ``serve`` subcommand: multi-model routing
    with canary deployment (serving/registry.py)."""
    from deeplearning4j_tpu.obs.metrics import default_registry
    from deeplearning4j_tpu.serving import (
        InferenceServer,
        ModelRegistry,
        ModelRouter,
    )
    from deeplearning4j_tpu.serving.metrics import ServingMetrics

    registry = ModelRegistry(args.registry_dir)
    cluster = None
    if getattr(args, "cluster", False):
        import os as _os

        from deeplearning4j_tpu.serving import ClusterCoordinator

        replica_id = args.replica_id or f"r{_os.getpid()}"
        cluster = ClusterCoordinator(
            args.registry_dir, replica_id,
            heartbeat_s=args.heartbeat_s,
            lease_ttl_s=args.lease_ttl_s,
            global_tenant_quota=args.global_tenant_quota,
            metrics_registry=default_registry())
    router = ModelRouter(
        registry, batch_limit=args.batch_limit,
        max_wait_ms=args.max_wait_ms, queue_limit=args.queue_limit,
        max_live_models=args.max_live_models,
        tenant_quota=args.tenant_quota,
        canary_fraction=args.canary_fraction,
        canary_window_s=args.canary_window,
        gen_slots=args.gen_slots, gen_max_length=args.gen_max_length,
        gen_spec_decode_k=args.spec_decode_k,
        gen_draft_mode=args.spec_draft_mode,
        gen_prefix_cache_mb=args.prefix_cache_mb,
        metrics=ServingMetrics(registry=default_registry()),
        cluster=cluster)
    if cluster is not None:
        # heartbeats carry this replica's per-tenant in-flight counts —
        # the lend/borrow signal for cluster-wide budget shares
        cluster.start(inflight_fn=router.tenant_inflight)
        print(f"cluster: replica {cluster.replica_id} "
              f"(heartbeat {cluster.heartbeat_s:g}s, lease ttl "
              f"{cluster.lease_ttl_s:g}s, global tenant quota "
              f"{args.global_tenant_quota})", flush=True)
    names = registry.models()
    print(f"registry {args.registry_dir}: models {names or '(none yet)'} "
          f"(canary {args.canary_fraction:.0%} for "
          f"{args.canary_window:.0f}s, "
          f"tenant quota {args.tenant_quota})", flush=True)
    if not args.no_warmup:
        # admit (build + warm) up to max_live_models eagerly so the
        # first request per model never pays the rewarm stall
        for name in names[: args.max_live_models]:
            try:
                router.managed(name)
                print(f"warmed {name} "
                      f"(v{registry.get(name)['active_version']})",
                      flush=True)
            except Exception as e:  # noqa: BLE001 — a model without an
                # active version yet must not block serving the others
                print(f"warmup skipped for {name}: {e}", flush=True)
    server = InferenceServer(
        router=router, host=args.host, port=args.port,
        batch_limit=args.batch_limit, max_wait_ms=args.max_wait_ms,
        queue_limit=args.queue_limit)
    print(f"listening on http://{args.host}:{server.port} "
          "(POST /models/<name>/predict|generate, /predict with a "
          "\"model\" key; GET /models/<name>/healthz, /healthz, "
          "/metrics, /alerts)", flush=True)
    if args.smoke:
        import http.client
        import json as _json

        import numpy as _np

        if not names:
            print("smoke: registry holds no models", flush=True)
            return 1
        name = names[0]
        mm = router.managed(name)
        shape = mm.active.engine.example_shape() or (1,)
        x = _np.zeros((1,) + tuple(shape), _np.float32).tolist()
        server.start()
        conn = http.client.HTTPConnection(args.host, server.port,
                                          timeout=30)
        conn.request("POST", f"/models/{name}/predict",
                     _json.dumps({"inputs": x}),
                     headers={"X-Tenant": "smoke"})
        resp = conn.getresponse()
        body = _json.loads(resp.read())
        ok = resp.status == 200 and "outputs" in body
        print(f"smoke: HTTP {resp.status} model={name} "
              f"version={body.get('model_version')} "
              f"{'ok' if ok else body}", flush=True)
        server.shutdown()
        if cluster is not None:
            cluster.shutdown()
        return 0 if ok else 1
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        print("shutting down (draining queues)", flush=True)
        server.shutdown()
    finally:
        if cluster is not None:
            cluster.shutdown()
    return 0


def flight_dump_main(argv) -> int:
    """``flight-dump`` subcommand: render flight-recorder dumps
    (obs/flight.py) as a human-readable event timeline — the postmortem
    reader for a diverged/killed run's black box. Several files (or a
    directory holding more than one ``flight_recorder_<pid>.json`` —
    the trainer's and the server's rings over one deployment) merge
    into ONE time-ordered timeline with each event's pid inline."""
    import json as _json

    ap = argparse.ArgumentParser(
        prog="deeplearning4j_tpu flight-dump",
        description="Read flight-recorder dump(s): one line per event, "
                    "newest last; multiple dumps (or a directory of "
                    "them) merge into one time-ordered timeline",
    )
    ap.add_argument("paths", nargs="+",
                    help="dump file(s), and/or directories (e.g. the "
                         "checkpoint dir) holding flight_recorder_*.json "
                         "— ALL dumps found are merged by timestamp")
    ap.add_argument("--last", type=int, default=None,
                    help="only the newest N events")
    ap.add_argument("--json", action="store_true",
                    help="raw JSON body instead of the rendered timeline")
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.obs.flight import (
        find_dumps,
        format_dump,
        merge_dumps,
    )

    files = []
    for p in args.paths:
        found = find_dumps(p)
        if not found:
            print(f"no flight-recorder dump at {p!r}", file=sys.stderr)
            return 1
        files.extend(f for f in found if f not in files)
    bodies = []
    for path in files:
        with open(path) as f:
            bodies.append(_json.load(f))
    body = bodies[0] if len(bodies) == 1 else merge_dumps(bodies)
    if args.json:
        print(_json.dumps(body, indent=1))
    else:
        print(":\n".join(files) + ":")
        print(format_dump(body, last=args.last))
    return 0


def alerts_main(argv) -> int:
    """``alerts`` subcommand: the operator view of a live process's
    SLO alert engine — fetch ``GET /alerts`` from a serving or
    training metrics endpoint and render the verdict + rule states
    (one-shot), or ``--watch`` it. Polling IS evaluation: the engine
    ticks on scrape, so a watched process is a monitored process.
    Exit code (one-shot): 0 healthy/degraded, 2 critical — wire it
    straight into rollout gates."""
    import json as _json
    import urllib.request

    ap = argparse.ArgumentParser(
        prog="deeplearning4j_tpu alerts",
        description="Render a live process's /alerts: health verdict, "
                    "firing/pending/ok rule states, reasons",
    )
    ap.add_argument("url",
                    help="base URL of a serving or --metrics-port "
                         "endpoint (e.g. http://127.0.0.1:8080); "
                         "/alerts is appended unless the path already "
                         "names it")
    ap.add_argument("--watch", nargs="?", const=2.0, type=float,
                    default=None, metavar="SECONDS",
                    help="re-poll every N seconds (default 2) until "
                         "interrupted")
    ap.add_argument("--json", action="store_true",
                    help="raw JSON body instead of the rendered table")
    ap.add_argument("--firing-only", action="store_true",
                    help="only pending/firing rules in the table")
    args = ap.parse_args(argv)

    url = args.url.rstrip("/")
    if not url.endswith("/alerts"):
        url += "/alerts"

    def fetch() -> dict:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return _json.loads(resp.read())

    def render(body: dict) -> str:
        v = body.get("verdict", {})
        lines = [f"verdict: {v.get('status', '?').upper()} "
                 f"({v.get('n_firing', 0)} firing / "
                 f"{v.get('n_rules', 0)} rules, "
                 f"ticks={body.get('ticks')})"]
        for st in body.get("alerts", []):
            if args.firing_only and st.get("state") == "ok":
                continue
            mark = {"firing": "!!", "pending": " ~"}.get(
                st.get("state"), "  ")
            val = st.get("value")
            lines.append(
                f"{mark} {st.get('state', '?'):<8} "
                f"{st.get('severity', '?'):<8} {st.get('name'):<38} "
                f"{'' if val is None else f'value={val:.6g} '}"
                f"{st.get('reason', '')}".rstrip())
        return "\n".join(lines)

    try:
        body = fetch()
    except OSError as e:
        print(f"cannot reach {url}: {e}", file=sys.stderr)
        return 1
    if args.watch is None:
        print(_json.dumps(body, indent=1) if args.json else render(body))
        return 2 if body.get("verdict", {}).get("status") == "critical" \
            else 0
    try:
        while True:
            print(_json.dumps(body, indent=1) if args.json
                  else render(body), flush=True)
            while True:
                time.sleep(max(float(args.watch), 0.1))
                try:
                    body = fetch()
                    break
                except OSError as e:
                    # do NOT re-render the last good verdict: a dead
                    # server re-printed as "HEALTHY" every interval
                    # would mask exactly the outage being watched
                    print(f"poll failed: {e}", file=sys.stderr)
    except KeyboardInterrupt:
        return 0


def lint_main(argv) -> int:
    """``lint`` subcommand: run the invariant analyzer
    (deeplearning4j_tpu/analysis) over the package — the static half of
    the chaos contract. Exit 0 iff no active finding AND no stale
    baseline entry."""
    import json as _json
    import os as _os

    ap = argparse.ArgumentParser(
        prog="deeplearning4j_tpu lint",
        description="AST invariant linter: durability (fsync-before-"
                    "replace, fslayer routing), typed errors, trace "
                    "safety (host syncs in jitted bodies, jnp in "
                    "probes), event schema",
    )
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to lint (default: the "
                         "installed deeplearning4j_tpu package)")
    ap.add_argument("--root", default=None,
                    help="tree root findings are reported relative to "
                         "(default: the package's parent, i.e. the "
                         "repo root)")
    ap.add_argument("--baseline", default=None,
                    help="baseline suppression file (default: "
                         "LINT_BASELINE.json next to the package; "
                         "--no-baseline disables)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore any baseline file")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--verbose", action="store_true",
                    help="also list baseline-suppressed findings")
    ap.add_argument("--write-baseline", default=None, metavar="PATH",
                    help="triage helper: write the current ACTIVE "
                         "findings as a fresh baseline to PATH (review "
                         "the diff; reasons start as TODO)")
    ap.add_argument("--events-table", action="store_true",
                    help="print the generated flight-event/seam table "
                         "(the block ARCHITECTURE.md embeds) and exit")
    ap.add_argument("--alerts-table", action="store_true",
                    help="print the generated SLO alert-rule table "
                         "(the block ARCHITECTURE.md embeds) and exit")
    args = ap.parse_args(argv)

    if args.events_table:
        from deeplearning4j_tpu.analysis.tables import render_event_table

        print(render_event_table())
        return 0
    if args.alerts_table:
        from deeplearning4j_tpu.analysis.tables import render_alert_table

        print(render_alert_table())
        return 0

    import deeplearning4j_tpu as _pkg
    from deeplearning4j_tpu.analysis import run_lint
    from deeplearning4j_tpu.analysis.baseline import (
        BASELINE_NAME,
        write_baseline,
    )

    pkg_dir = _os.path.dirname(_os.path.abspath(_pkg.__file__))
    root = _os.path.abspath(args.root) if args.root else \
        _os.path.dirname(pkg_dir)
    paths = args.paths or [pkg_dir]
    baseline = None
    if not args.no_baseline:
        baseline = args.baseline or _os.path.join(root, BASELINE_NAME)
    report = run_lint(root, paths, baseline_path=baseline)

    if args.write_baseline:
        from deeplearning4j_tpu.analysis.baseline import load_baseline

        # regenerate over ALL current findings — active AND already-
        # suppressed — carrying forward the reviewed reasons, so
        # pointing --write-baseline at the live baseline adds the new
        # entries instead of silently discarding the triaged ones
        reasons = {}
        if baseline and _os.path.exists(baseline):
            reasons = {str(e["fingerprint"]): e["reason"]
                       for e in load_baseline(baseline)
                       if "reason" in e}
        all_findings = sorted(report.active + report.suppressed,
                              key=lambda f: (f.path, f.line, f.rule))
        write_baseline(args.write_baseline, all_findings, reasons)
        n_new = len(report.active)
        print(f"wrote {len(all_findings)} entr"
              f"{'y' if len(all_findings) == 1 else 'ies'} to "
              f"{args.write_baseline} ({n_new} new — fill in the TODO "
              "reasons)")
        return 0
    if args.json:
        print(_json.dumps(report.to_dict(), indent=1))
    else:
        print(report.format(verbose=args.verbose))
    return report.exit_code


def chaos_main(argv) -> int:
    """``chaos`` subcommand: run the invariant-checked resilience drill
    matrix (chaos/drills.py), a subset of it, or an operator-supplied
    declarative fault plan armed around a stock workload. Exit 0 iff
    every selected drill is green (skips don't fail)."""
    import json as _json

    ap = argparse.ArgumentParser(
        prog="deeplearning4j_tpu chaos",
        description="Chaos drills: declarative fault plans × real "
                    "workloads, judged by the cross-cutting resilience "
                    "invariants (typed errors, bit-parity where "
                    "promised, ordered forensics, no torn artifacts, "
                    "bounded recovery)")
    ap.add_argument("--list", action="store_true",
                    help="list the registered seams and drills, then exit")
    ap.add_argument("--fast", action="store_true",
                    help="single-fault drills only (the tier-1 subset); "
                         "default runs paired-fault storms too")
    ap.add_argument("--drill", action="append", default=None,
                    help="run only this drill (repeatable)")
    ap.add_argument("--plan", default=None,
                    help="a ChaosPlan JSON file (or inline JSON) to arm "
                         "around --workload instead of the named matrix")
    ap.add_argument("--workload", default="fit",
                    help="stock workload for --plan: fit | "
                         "checkpoint_fit | generate | registry | tune")
    ap.add_argument("--out", default="BENCH_chaos.json",
                    help="scorecard JSON path ('' disables the write)")
    ap.add_argument("--cpu-mesh", type=int, default=None, metavar="N",
                    help="force an N-device virtual CPU mesh before jax "
                         "initializes (the elastic drills need >= 8)")
    args = ap.parse_args(argv)

    if args.cpu_mesh:
        import os as _os

        flags = _os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            _os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{int(args.cpu_mesh)}").strip()
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")

    from deeplearning4j_tpu.chaos import drills, list_seams, load_plan

    if args.list:
        print("seams:")
        for s in list_seams():
            print(f"  {s['seam']:<28} [{s['kind']}/{s['subsystem']}] "
                  f"{s['description']}")
        print("drills:")
        for d in drills.DRILLS.values():
            tag = "paired" if d.paired else "single"
            tier = "fast" if d.fast else "slow"
            print(f"  {d.name:<38} [{tag}/{tier}/{d.workload}] "
                  f"{d.description}")
        return 0

    if args.plan:
        plan = load_plan(args.plan)
        print(plan.describe(), flush=True)
        result = drills.run_custom(plan, args.workload)
        scorecard = {"drills": [result.to_dict()], "n_drills": 1,
                     "n_green": int(result.ok),
                     "n_red": int(not result.ok), "n_skipped": 0,
                     "n_paired": 0,
                     "silent_corruption_findings":
                         [c for c in result.checks if not c["ok"]],
                     "ok": result.ok}
    else:
        scorecard = drills.run_matrix(fast_only=args.fast,
                                      names=args.drill, verbose=True)
    if args.out:
        with open(args.out, "w") as f:
            _json.dump(scorecard, f, indent=1)
        print(f"scorecard -> {args.out}", flush=True)
    print(f"chaos: {scorecard['n_green']} green / "
          f"{scorecard['n_red']} red / {scorecard['n_skipped']} skipped "
          f"({scorecard['n_paired']} paired-fault)", flush=True)
    return 0 if scorecard["ok"] else 1


def tune_main(argv) -> int:
    """``tune`` subcommand: hyperparameter search over the stock MLP
    factory on a named dataset (tune/ package — Arbiter equivalent).
    The space JSON maps parameter names onto the factory's keywords:

        {"params": {"lr":  {"type": "continuous", "low": 1e-4,
                            "high": 1e-1, "scale": "log"},
                    "l2":  {"type": "continuous", "low": 1e-6,
                            "high": 1e-2, "scale": "log"},
                    "widths": {"type": "layer_widths",
                               "count": {"type": "integer",
                                         "low": 1, "high": 2},
                               "width": {"type": "discrete",
                                         "values": [16, 32, 64]}}}}

    Trials whose samples differ only in lr/l1/l2/weight-decay/seed train
    as ONE vmapped population program; structural samples (widths, ...)
    fall back to the thread-pool engine automatically.
    """
    import functools
    import json as _json

    import numpy as np

    ap = argparse.ArgumentParser(
        prog="deeplearning4j_tpu tune",
        description="Hyperparameter search: ASHA over a search space, "
                    "vmapped population training, crash-safe resume",
    )
    ap.add_argument("--space", required=True,
                    help="space JSON file (see subcommand docstring)")
    ap.add_argument("--dataset", default="iris",
                    help="mnist | iris | svhn | tinyimagenet")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--num-examples", type=int, default=None)
    ap.add_argument("--population", type=int, default=8,
                    help="number of trials sampled (and the vmapped "
                         "population width when trials are stackable)")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "population", "pool"],
                    help="auto: population when every trial compiles to "
                         "the same program, else thread pool")
    ap.add_argument("--min-budget", type=int, default=32,
                    help="first ASHA rung, in optimizer steps")
    ap.add_argument("--max-budget", type=int, default=256,
                    help="final rung (total steps a surviving trial gets)")
    ap.add_argument("--eta", type=int, default=3,
                    help="ASHA halving rate: top 1/eta survive each rung")
    ap.add_argument("--steps-per-call", type=int, default=8,
                    help="population engine: batches per stacked "
                         "lax.scan dispatch (train/pipeline.py bundling)")
    ap.add_argument("--store", default=None,
                    help="study directory: crash-safe JSONL trial "
                         "journal + per-trial checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="replay the store, skip finished trials, resume "
                         "in-flight ones from their newest valid "
                         "checkpoint")
    ap.add_argument("--keep-last", type=int, default=2,
                    help="checkpoints retained per trial")
    ap.add_argument("--retain-best", type=int, default=3,
                    help="after the study: keep only the best-k trials' "
                         "checkpoint dirs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grid", action="store_true",
                    help="grid search instead of seeded random sampling")
    ap.add_argument("--workers", type=int, default=None,
                    help="pool engine threads (default: #devices)")
    ap.add_argument("--val-batches", type=int, default=4,
                    help="batches held out of the tail of the stream for "
                         "rung scoring")
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    from deeplearning4j_tpu.train.earlystopping import (
        DataSetLossCalculator,
        ScoreCalculatorObjective,
    )
    from deeplearning4j_tpu.tune import (
        AshaScheduler,
        SearchSpace,
        Study,
        mlp_factory,
    )

    if args.resume and not args.store:
        raise SystemExit("--resume requires --store")
    with open(args.space) as f:
        params = SearchSpace.params_from_json(f.read())

    if args.val_batches < 1:
        raise SystemExit("--val-batches must be >= 1 (rung scoring needs "
                         "held-out data)")
    it, num_classes = build_dataset(args.dataset, args.batch_size,
                                    args.num_examples)
    batches = list(it)
    if len(batches) <= args.val_batches:
        raise SystemExit(
            f"dataset yields {len(batches)} batches; need more than "
            f"--val-batches={args.val_batches}")
    train, val = batches[:-args.val_batches], batches[-args.val_batches:]
    feat = np.asarray(train[0].features)
    if feat.ndim > 2:
        raise SystemExit(
            "tune drives the flat MLP factory; use a dataset with flat "
            f"features (got rank-{feat.ndim})")
    n_in = int(feat.shape[1])

    space = SearchSpace(
        functools.partial(mlp_factory, n_in, num_classes), params)
    objective = ScoreCalculatorObjective(
        DataSetLossCalculator(ExistingDataSetIterator(val)))
    study = Study(
        space, train, objective,
        scheduler=AshaScheduler(args.min_budget, args.max_budget,
                                eta=args.eta),
        num_trials=args.population, seed=args.seed, engine=args.engine,
        store_dir=args.store, steps_per_call=args.steps_per_call,
        keep_last=args.keep_last, retain_best=args.retain_best,
        workers=args.workers, grid=args.grid)
    t0 = time.time()
    result = study.run(resume=args.resume)
    dt = time.time() - t0
    print(f"engine={result.engine} trials={len(result.trials)} "
          f"rungs={study.scheduler.rungs} in {dt:.1f}s", flush=True)
    for t in result.trials:
        print(f"  {t.id} {t.status:<9} rung={t.rung} "
              f"score={t.final_score} {_json.dumps(t.to_dict()['overrides'])}",
              flush=True)
    if result.best_trial is None:
        print("no completed trials", flush=True)
        return 1
    print(f"best: {result.best_trial.id} "
          f"score={result.best_trial.final_score} "
          f"{_json.dumps(result.best_trial.to_dict()['overrides'])}",
          flush=True)
    return 0


def loadgen_main(argv) -> int:
    """``cli loadgen``: compile a declarative load plan into its
    deterministic request stream (identical seeds MUST replay identical
    streams — the fingerprint printed here is the proof) and optionally
    replay it against a live server under time compression."""
    import json as _json
    import textwrap

    ap = argparse.ArgumentParser(
        prog="deeplearning4j_tpu loadgen",
        description="compile + replay declarative load plans "
                    "(loadgen/plan.py)")
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--plan", default=None,
                     help="load-plan JSON file (LoadPlan serde)")
    src.add_argument("--builtin", default="diurnal_flash",
                     help="builtin plan name (--list shows them)")
    ap.add_argument("--list", action="store_true",
                    help="list builtin plans and exit")
    ap.add_argument("--seed", type=int, default=None,
                    help="override the plan's seed")
    ap.add_argument("--duration-s", type=float, default=None,
                    help="override the plan's simulated duration")
    ap.add_argument("--tick-s", type=float, default=None,
                    help="override the controller/alert tick spacing")
    ap.add_argument("--compression", type=float, default=10.0,
                    help="simulated seconds per wall second during "
                         "--replay")
    ap.add_argument("--compile-only", action="store_true",
                    help="compile + fingerprint only, even when a "
                         "--replay target is given (the determinism "
                         "check in scripts/drive_loadgen.py)")
    ap.add_argument("--replay", default=None, metavar="HOST:PORT",
                    help="replay the stream against a live server's "
                         "POST /predict")
    ap.add_argument("--shape", default="4",
                    help="comma-separated per-example feature shape "
                         "for --replay payloads (must match the served "
                         "model's input)")
    ap.add_argument("--json", action="store_true",
                    help="emit machine-readable JSON")
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.loadgen import BUILTIN_PLANS, load_plan

    if args.list:
        for name, factory in sorted(BUILTIN_PLANS.items()):
            print(f"--builtin {name}:")
            print(textwrap.indent(factory().describe(), "  "))
        return 0
    try:
        if args.plan is not None:
            plan = load_plan(args.plan)
        else:
            if args.builtin not in BUILTIN_PLANS:
                ap.error(f"unknown builtin {args.builtin!r} "
                         f"(known: {sorted(BUILTIN_PLANS)})")
            plan = BUILTIN_PLANS[args.builtin]()
        if args.tick_s is not None:
            plan.tick_s = float(args.tick_s)
        stream = plan.compile(duration_s=args.duration_s, seed=args.seed)
    except (ValueError, KeyError, OSError) as e:
        print(f"loadgen: invalid plan: {e}", file=sys.stderr)
        return 2
    info = stream.describe()
    if not args.json:
        print(f"plan {info['plan']} seed={info['seed']}: "
              f"{info['n_requests']} requests over "
              f"{stream.plan.duration_s:g}s sim, tenants "
              f"{info['tenants']}")
        print(f"fingerprint: {info['fingerprint']}")
    if args.replay is None or args.compile_only:
        if args.json:
            print(_json.dumps(info, indent=1, sort_keys=True))
        return 0

    from deeplearning4j_tpu.loadgen import LoadRunner, http_target

    shape = tuple(int(s) for s in args.shape.split(","))
    runner = LoadRunner(stream, http_target(args.replay, shape),
                        compression=args.compression)
    report = runner.run()
    d = report.describe()
    if args.json:
        print(_json.dumps({"plan": info, "report": d}, indent=1,
                          sort_keys=True))
    else:
        print(f"replayed {d['submitted']} requests in {d['wall_s']}s "
              f"wall ({d['sim_s']}s sim): ok={report.ok()} "
              f"p50={d['p50_ms']}ms p99={d['p99_ms']}ms")
        print(f"outcomes: {d['outcomes']}")
    return 0 if report.ok() > 0 else 1


def data_main(argv) -> int:
    """``cli data pack|verify`` — the record-shard toolchain.

    ``pack`` drains a named dataset into a shard directory (the same
    builder the trainer uses, so a packed directory trains bit-identical
    to the in-memory iterator); ``verify`` CRC-checks every shard a
    manifest names and exits non-zero on any damage — the offline half
    of the torn-shard contract (the online half is the loader's typed
    skip-and-continue).
    """
    import json
    import os

    ap = argparse.ArgumentParser(prog="deeplearning4j_tpu data")
    sub = ap.add_subparsers(dest="action", required=True)

    pk = sub.add_parser("pack", help="drain a dataset into record shards")
    pk.add_argument("--dataset", default="mnist",
                    help="mnist | iris | svhn | tinyimagenet | uci")
    pk.add_argument("--batch-size", type=int, default=64)
    pk.add_argument("--num-examples", type=int, default=None)
    pk.add_argument("--out", required=True, help="shard directory")
    pk.add_argument("--shard-size", type=int, default=8,
                    help="batches per shard file")
    pk.add_argument("--seed", type=int, default=0,
                    help="pinned into the manifest (loader shuffles "
                         "derive from it by default)")

    vf = sub.add_parser("verify", help="CRC-check every shard in a dir")
    vf.add_argument("dir", help="shard directory (with manifest.json)")
    vf.add_argument("--json", action="store_true",
                    help="machine-readable per-shard report")

    args = ap.parse_args(argv)
    if args.action == "pack":
        from deeplearning4j_tpu.data.shards import pack_iterator

        it, _num_classes = build_dataset(args.dataset, args.batch_size,
                                         args.num_examples)
        manifest = pack_iterator(it, args.out,
                                 batches_per_shard=args.shard_size,
                                 seed=args.seed)
        print(f"packed {manifest['total_batches']} batches "
              f"(batch size {manifest['batch_size']}) into "
              f"{manifest['num_shards']} shard(s) at {args.out}",
              flush=True)
        return 0

    from deeplearning4j_tpu.data.shards import TornShardError, verify_dir

    try:
        report = verify_dir(args.dir)
    except TornShardError as e:
        if args.json:
            print(json.dumps({"ok": False, "error": str(e)}))
        else:
            print(f"verify failed: {e}", flush=True)
        return 1
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        for r in report["shards"]:
            tag = "ok" if r["ok"] else f"BAD ({r['error']})"
            print(f"{os.path.basename(r['path'])}: {r['records']} "
                  f"record(s) {tag}", flush=True)
        print(f"{report['num_shards']} shard(s), {report['bad']} bad",
              flush=True)
    return 0 if report["ok"] else 1


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    from deeplearning4j_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    if argv[:1] == ["serve"]:
        return serve_main(argv[1:])
    if argv[:1] == ["tune"]:
        return tune_main(argv[1:])
    if argv[:1] == ["flight-dump"]:
        return flight_dump_main(argv[1:])
    if argv[:1] == ["alerts"]:
        return alerts_main(argv[1:])
    if argv[:1] == ["chaos"]:
        return chaos_main(argv[1:])
    if argv[:1] == ["lint"]:
        return lint_main(argv[1:])
    if argv[:1] == ["loadgen"]:
        return loadgen_main(argv[1:])
    if argv[:1] == ["data"]:
        return data_main(argv[1:])
    ap = argparse.ArgumentParser(
        prog="deeplearning4j_tpu",
        description="Train a zoo model (ParallelWrapperMain equivalent)",
    )
    ap.add_argument("--model", required=True,
                    help="zoo model name (lenet, simplecnn, resnet50, ...)")
    ap.add_argument("--dataset", default="mnist",
                    help="mnist | iris | svhn | tinyimagenet | uci")
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--num-examples", type=int, default=None)
    ap.add_argument("--workers", type=int, default=1,
                    help=">1 trains data-parallel over that many devices")
    ap.add_argument("--output", default=None, help="checkpoint zip path")
    ap.add_argument("--stats", default=None, help="JSONL stats path")
    ap.add_argument("--dashboard", default=None, help="HTML dashboard path")
    ap.add_argument("--compute-dtype", default=None,
                    choices=["bfloat16", "float32"],
                    help="mixed precision (bf16 compute, fp32 masters)")
    ap.add_argument("--remat-policy", default=None,
                    choices=["save_conv_outputs", "dots", "nothing"],
                    help="backward rematerialization (memory knob)")
    ap.add_argument("--sharded-update", action="store_true",
                    help="ZeRO-1 weight update for --workers>1: updater "
                         "state and update compute sharded 1/N over the "
                         "data axis (numerics unchanged)")
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help="pipelined training loop: bundle K optimizer "
                         "steps into one in-graph lax.scan dispatch "
                         "(numerics unchanged; ragged tails fall back to "
                         "single steps)")
    ap.add_argument("--queue-size", type=int, default=4,
                    help="async prefetch queue depth of the fit loop")
    ap.add_argument("--data-dir", default=None,
                    help="train from a record-shard directory (cli data "
                         "pack) via the multi-worker ShardedLoader "
                         "instead of the in-memory --dataset iterator; "
                         "--dataset still sizes the model. The stream "
                         "order is deterministic in (seed, epoch, step) "
                         "and its position rides in checkpoints, so "
                         "--resume replays the exact batch stream")
    ap.add_argument("--data-workers", type=int, default=2,
                    help="decoder threads of the sharded loader "
                         "(any count yields the identical stream)")
    ap.add_argument("--data-seed", type=int, default=0,
                    help="shard/record shuffle seed of the sharded "
                         "loader")
    ap.add_argument("--augment", default=None,
                    help="on-device augmentation spec fused ahead of the "
                         "train step, e.g. "
                         "'normalize:0.13:0.31,crop:2,noise:0.01' "
                         "(jitted once; zero steady-state retraces)")
    ap.add_argument("--telemetry", action="store_true",
                    help="in-graph training telemetry: per-step gradient/"
                         "param global norms, update:param ratio and loss "
                         "scale computed inside the jitted step (bit-"
                         "identical training, at most one host fetch per "
                         "dispatch)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="expose training metrics over HTTP on this port "
                         "(GET /metrics: JSON, or Prometheus text via "
                         "Accept/?format=prometheus, plus /alerts, the "
                         "verdict-enriched /healthz, /debug/flight "
                         "[?since_seq=N incremental] and /debug/profile); "
                         "implies --telemetry")
    ap.add_argument("--flight-dir", default=None,
                    help="flight recorder black box: record training "
                         "events into a bounded ring and dump them here "
                         "on divergence/fatal exit/SIGTERM and every 30s "
                         "(default: --checkpoint-dir when set; read dumps "
                         "with the flight-dump subcommand)")
    ap.add_argument("--cost-report", action="store_true",
                    help="publish static FLOPs/bytes/peak-memory and MFU "
                         "gauges for the compiled train step (implies "
                         "--telemetry metrics accounting; pair with "
                         "--metrics-port to scrape them)")
    ap.add_argument("--skip-nonfinite", action="store_true",
                    help="fault tolerance: skip (don't apply) any step "
                         "whose global gradient is non-finite, and enable "
                         "dynamic loss scaling under --compute-dtype")
    ap.add_argument("--max-bad-steps", type=int, default=None,
                    help="abort after this many CONSECUTIVE skipped "
                         "non-finite steps (implies --skip-nonfinite)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="crash-safe checkpoint directory: one atomic "
                         "checkpoint per epoch, keep-last-k retention")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="checkpoints retained in --checkpoint-dir")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest VALID checkpoint from "
                         "--checkpoint-dir before training (corrupt/"
                         "truncated ones are skipped). Checkpoints are "
                         "device-count portable: a run checkpointed with "
                         "--workers N resumes under any --workers M "
                         "(parallel/reshard.py re-places the state)")
    ap.add_argument("--publish-to", default=None,
                    help="continuous train→serve deployment: publish "
                         "every checkpoint this run writes to a serving "
                         "model REGISTRY directory, each gated by a "
                         "held-out validation step (non-finite or "
                         "regressed snapshots are refused typed, never "
                         "activated). Requires --checkpoint-dir; pair "
                         "with cli serve --registry-dir for canary "
                         "routing + auto-rollback on the serving side")
    ap.add_argument("--publish-model", default=None,
                    help="registry model name to publish under "
                         "(default: --model)")
    ap.add_argument("--publish-val-batches", type=int, default=2,
                    help="batches held out of the dataset tail for the "
                         "publish validation score")
    ap.add_argument("--elastic", action="store_true",
                    help="survive losing part of the mesh mid-fit: "
                         "checkpoint every epoch's worth of steps, and on "
                         "a mesh failure re-form a smaller mesh from the "
                         "surviving devices, reshard the newest valid "
                         "checkpoint onto it and resume in place "
                         "(requires --checkpoint-dir; see the "
                         "mesh_shrink/reshard_done/elastic_resume events "
                         "in flight-dump)")
    ap.add_argument("--elastic-max-retries", type=int, default=2,
                    help="recoveries before --elastic gives up with "
                         "ElasticRecoveryExhaustedError")
    ap.add_argument("--elastic-min-devices", type=int, default=1,
                    help="give up when fewer devices than this survive")
    args = ap.parse_args(argv)

    if args.data_dir:
        if args.elastic or args.publish_to:
            raise SystemExit("--data-dir cannot combine with --elastic/"
                             "--publish-to yet (both materialize the "
                             "epoch as a list, which would discard the "
                             "loader's resume position)")
        from deeplearning4j_tpu.data.loader import ShardedLoader
        from deeplearning4j_tpu.data.shards import load_manifest

        manifest = load_manifest(args.data_dir)
        lshape = (manifest["schema"].get("labels") or {}).get("shape")
        if lshape:
            # the one-hot width IS the class count; --dataset still
            # names the input geometry for build_model
            num_classes = int(lshape[0])
        else:
            _, num_classes = build_dataset(args.dataset, args.batch_size,
                                           args.num_examples)
        it = ShardedLoader(args.data_dir, num_workers=args.data_workers,
                           seed=args.data_seed)
        print(f"sharded loader: {manifest['num_shards']} shard(s), "
              f"{manifest['total_batches']} batches/epoch, "
              f"{args.data_workers} worker(s), seed {args.data_seed}",
              flush=True)
    else:
        it, num_classes = build_dataset(args.dataset, args.batch_size,
                                        args.num_examples)
    model = None
    if args.resume:
        if not args.checkpoint_dir:
            raise SystemExit("--resume requires --checkpoint-dir")
        import os

        from deeplearning4j_tpu.train.faults import load_latest_valid

        try:
            if os.path.isdir(args.checkpoint_dir):
                model, ckpt_path = load_latest_valid(args.checkpoint_dir)
                print(f"resumed from {ckpt_path} (iteration "
                      f"{model.iteration}, epoch {model.epoch}); "
                      "--model/--compute-dtype/--remat-policy come from "
                      "the checkpoint", flush=True)
                from deeplearning4j_tpu.train.model_serializer import (
                    ModelSerializer,
                )

                topo = (ModelSerializer.checkpoint_meta(ckpt_path)
                        .get("topology") or {})
                n_from = topo.get("n_devices")
                if n_from is not None and n_from != args.workers:
                    print(f"cross-topology resume: checkpoint written on "
                          f"{n_from} device(s), resuming on "
                          f"{args.workers} (state is canonical — "
                          "parallel/reshard.py re-places it)", flush=True)
        except FileNotFoundError as e:
            print(f"resume: {e}", flush=True)
        if model is None:
            # restart-wrapper friendly: no (valid) checkpoint yet means
            # this IS the first launch — start fresh instead of dying
            print(f"resume: no valid checkpoint in {args.checkpoint_dir}; "
                  "starting fresh", flush=True)
    if model is None:
        model = build_model(args.model, num_classes, args.dataset,
                            compute_dtype=args.compute_dtype,
                            remat_policy=args.remat_policy)
    if args.data_dir:
        dstate = getattr(model, "_data_state", None)
        if args.resume and dstate is not None:
            # the checkpoint carries the data position next to the RNG
            # chain; restoring it replays the exact batch stream the
            # interrupted run would have consumed
            it.restore_state(dstate)
            print(f"data resume: epoch {dstate['epoch']} shard pos "
                  f"{dstate['shard_pos']} record pos "
                  f"{dstate['record_pos']} ({dstate['batches']} batches "
                  "consumed)", flush=True)
    if args.augment:
        from deeplearning4j_tpu.data.augment import parse_augment_spec

        stage = parse_augment_spec(args.augment, seed=args.data_seed)
        model.set_augmentation(stage)
        print(f"augmentation: {stage.spec()} (jitted on-device, keyed "
              "by iteration)", flush=True)
    if args.skip_nonfinite or args.max_bad_steps is not None:
        from deeplearning4j_tpu.train.faults import FaultPolicy

        model.set_fault_policy(FaultPolicy(
            skip_nonfinite=True,
            max_consecutive_bad_steps=args.max_bad_steps,
            keep_last=args.keep_last,
        ))
    # pipelined-loop knobs: the fit paths (and ParallelWrapper) read them
    # off the configuration each epoch
    model.conf.global_conf.steps_per_call = args.steps_per_call
    model.conf.global_conf.async_queue_size = args.queue_size
    if args.telemetry or args.metrics_port is not None or args.cost_report:
        model.conf.global_conf.telemetry = True
    print(f"model={args.model} ({model.num_params():,} params) "
          f"dataset={args.dataset} epochs={args.epochs}", flush=True)

    metrics_server = None
    if args.metrics_port is not None or args.cost_report:
        from deeplearning4j_tpu.obs.metrics import MetricsListener

        # MetricsListener publishes steps/samples/loss + the telemetry
        # stream into the process-wide registry; --cost-report needs it
        # too — its MFU gauge's throughput term is the
        # train_steps_per_sec gauge this listener maintains
        model.add_listeners(MetricsListener())
    if args.metrics_port is not None:
        from deeplearning4j_tpu.obs.exporter import start_metrics_server

        metrics_server = start_metrics_server(args.metrics_port)
        print(f"metrics on http://127.0.0.1:{metrics_server.port}/metrics "
              "(JSON; Prometheus text via Accept: text/plain or "
              "?format=prometheus)", flush=True)

    flight_dir = args.flight_dir or args.checkpoint_dir
    if flight_dir is not None:
        from deeplearning4j_tpu.obs.flight import (
            FlightRecorderListener,
            install_signal_dump,
        )

        # the black box lands next to the checkpoints: bounded event
        # ring, dumped on divergence / fatal fit exit / SIGTERM, and
        # every 30s so even SIGKILL leaves an at-most-30s-stale dump
        model.add_listeners(FlightRecorderListener(directory=flight_dir))
        try:
            install_signal_dump()
        except ValueError:
            pass  # not on the main thread (embedded use); periodic +
            # exception dumps still cover the black-box contract

    storage = None
    if args.stats or args.dashboard:
        from deeplearning4j_tpu.ui import FileStatsStorage, InMemoryStatsStorage, StatsListener

        storage = (FileStatsStorage(args.stats) if args.stats
                   else InMemoryStatsStorage())
        model.add_listeners(StatsListener(storage, session_id="cli"))

    publish_listener = None
    if args.publish_to and not args.checkpoint_dir:
        raise SystemExit("--publish-to requires --checkpoint-dir (the "
                         "publish listener rides the checkpoint cadence)")
    if args.checkpoint_dir:
        import os

        from deeplearning4j_tpu.train.faults import prune_checkpoints
        from deeplearning4j_tpu.train.listeners import CheckpointListener

        # directory-level retention: CheckpointListener only prunes files
        # IT wrote, so a restart loop (--resume under a supervisor) would
        # otherwise grow the directory by keep_last zips per incarnation
        if os.path.isdir(args.checkpoint_dir):
            prune_checkpoints(args.checkpoint_dir, args.keep_last)
        if args.publish_to and args.elastic:
            raise SystemExit("--publish-to cannot combine with --elastic "
                             "yet (the elastic driver owns checkpoint "
                             "cadence); publish from a non-elastic fit")
        if args.publish_to:
            from deeplearning4j_tpu.data.iterators import (
                ExistingDataSetIterator,
            )
            from deeplearning4j_tpu.serving.registry import ModelRegistry
            from deeplearning4j_tpu.train.earlystopping import (
                DataSetLossCalculator,
            )
            from deeplearning4j_tpu.train.listeners import (
                RegistryPublishListener,
            )

            # genuinely hold the validation tail OUT of training (the
            # tune subcommand's split): a gate that scores trained-on
            # data would miss exactly the overfit regressions it exists
            # to catch
            n_val = max(int(args.publish_val_batches), 1)
            batches = list(it)
            if len(batches) <= n_val:
                raise SystemExit(
                    f"dataset yields {len(batches)} batches; need more "
                    f"than --publish-val-batches={n_val}")
            val = batches[-n_val:]
            it = ExistingDataSetIterator(batches[:-n_val])
            publish_registry = ModelRegistry(args.publish_to)
            publish_listener = RegistryPublishListener(
                args.checkpoint_dir, publish_registry,
                args.publish_model or args.model,
                validator=DataSetLossCalculator(
                    ExistingDataSetIterator(val)).calculate_score,
                save_every_n_epochs=1, keep_mode="last",
                keep_last=args.keep_last)
            model.add_listeners(publish_listener)
            print(f"publishing to registry {args.publish_to} as "
                  f"{args.publish_model or args.model!r} "
                  f"({n_val} held-out validation batches)", flush=True)
        elif not args.elastic:
            # under --elastic the driver owns checkpointing (same dir,
            # iteration cadence) — a second epoch listener would double
            # every write and fight the pruning
            model.add_listeners(CheckpointListener(
                args.checkpoint_dir, save_every_n_epochs=1,
                keep_mode="last", keep_last=args.keep_last))

    if args.cost_report:
        from deeplearning4j_tpu.obs import cost as _cost

        # static cost sheet of the compiled step (published before the
        # fit so the MFU gauge is scrapeable for the whole run; the
        # throughput term fills in once MetricsListener starts
        # publishing steps/sec)
        sample = next(iter(it))
        it.reset()
        rep = _cost.publish_train_cost(model, sample,
                                       steps_per_call=args.steps_per_call)
        if "error" in rep:
            print(f"cost-report unavailable: {rep['error']}", flush=True)
        else:
            print(f"cost-report: {rep.get('flops_per_step', 0):.3e} "
                  f"FLOPs/step, {rep.get('bytes_per_step', 0):.3e} "
                  f"bytes/step, peak memory "
                  f"{rep.get('peak_memory_bytes', 0):,} bytes "
                  f"(K={rep['steps_per_call']})", flush=True)

    t0 = time.time()
    if args.elastic:
        import jax as _jax

        from deeplearning4j_tpu.train.faults import ElasticFitDriver

        if not args.checkpoint_dir:
            raise SystemExit("--elastic requires --checkpoint-dir "
                             "(recovery resumes from its checkpoints)")
        batches = list(it)
        driver = ElasticFitDriver(
            model, args.checkpoint_dir,
            # always honor --workers: the non-elastic paths treat
            # workers=1 as single-device, so must this one
            devices=_jax.devices()[: args.workers],
            max_retries=args.elastic_max_retries,
            min_devices=args.elastic_min_devices,
            # one epoch's worth of steps per checkpoint (what --elastic
            # documents); batches is exactly one epoch of the iterator
            checkpoint_every_n_iterations=max(len(batches), 1),
            keep_last=args.keep_last,
            sharded_update=args.sharded_update or None,
            steps_per_call=args.steps_per_call)
        model = driver.fit(batches, epochs=args.epochs)
        if driver.recoveries:
            print(f"elastic: survived {driver.recoveries} mesh "
                  "failure(s); see flight-dump for the recovery "
                  "timeline", flush=True)
    elif args.workers > 1:
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

        pw_b = ParallelWrapper.builder(model).workers(args.workers)
        if args.sharded_update:
            pw_b.sharded_update(True)
        pw = pw_b.build()
        pw.fit(it, epochs=args.epochs)
    else:
        model.fit(it, epochs=args.epochs)
    print(f"trained {model.iteration} iterations in {time.time()-t0:.1f}s, "
          f"final score {float(model.score_):.4f}", flush=True)
    if args.data_dir:
        # the stream's rolling fingerprint — an interrupted+resumed run
        # must print the same hex as the uninterrupted oracle (the
        # drive script's bit-identity gate)
        st = it.data_state()
        print(f"data stream fingerprint {st['fingerprint']} "
              f"(batches={st['batches']})", flush=True)
        it.shutdown()
    if flight_dir is not None:
        from deeplearning4j_tpu.obs.flight import default_flight_recorder

        # final dump on CLEAN exit too: a successful run's forensics
        # (data_resume, shard_skip, recoveries survived) are part of
        # the black-box record, not only failures
        default_flight_recorder().dump()
    if publish_listener is not None:
        print(f"published {len(publish_listener.published)} snapshot(s) "
              f"to {args.publish_to}, "
              f"{len(publish_listener.refused)} refused by validation",
              flush=True)
    if metrics_server is not None:
        metrics_server.shutdown()
    if args.skip_nonfinite or args.max_bad_steps is not None:
        print(f"skipped non-finite steps: {model.bad_step_count}",
              flush=True)

    if args.output:
        from deeplearning4j_tpu.train.model_serializer import ModelSerializer

        ModelSerializer.write_model(model, args.output)
        print(f"saved {args.output}", flush=True)
    if args.dashboard and storage is not None:
        from deeplearning4j_tpu.ui import render_dashboard

        render_dashboard(storage, path=args.dashboard)
        print(f"dashboard {args.dashboard}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
