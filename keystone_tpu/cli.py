"""CLI dispatcher — the bin/run-pipeline.sh analogue.

    python -m keystone_tpu.cli <PipelineName> [pipeline flags...]
    python -m keystone_tpu.cli serve --model model.pkl [serve flags...]
    python -m keystone_tpu.cli worker --connect HOST:PORT [worker flags...]
    python -m keystone_tpu.cli check <PipelineName> [check flags...]
    python -m keystone_tpu.cli check --model model.pkl [check flags...]
    python -m keystone_tpu.cli --list
"""

from __future__ import annotations

import importlib
import os
import sys

_PIPELINE_MODULES = {
    "MnistRandomFFT": "keystone_tpu.pipelines.mnist_random_fft",
    "LinearPixels": "keystone_tpu.pipelines.linear_pixels",
    "RandomPatchCifar": "keystone_tpu.pipelines.random_patch_cifar",
    "NewsgroupsPipeline": "keystone_tpu.pipelines.newsgroups",
    "TimitPipeline": "keystone_tpu.pipelines.timit",
    "ImageNetSiftLcsFV": "keystone_tpu.pipelines.imagenet_sift_lcs_fv",
    "VOCSIFTFisher": "keystone_tpu.pipelines.voc_sift_fisher",
    "AmazonReviewsPipeline": "keystone_tpu.pipelines.amazon_reviews",
    "KernelTimitPipeline": "keystone_tpu.pipelines.kernel_timit",
    "KernelCifarPipeline": "keystone_tpu.pipelines.kernel_cifar",
    "KernelRidgeTimitPipeline": "keystone_tpu.pipelines.kernel_ridge_timit",
}


def _serve_main(argv) -> int:
    """``serve`` subcommand: load a saved fitted pipeline (or the
    current version from a model registry) and expose it over HTTP
    (POST /predict, GET /healthz, GET /replicas, POST /swap,
    GET /metrics, plus the live ops surface GET /statusz, GET /tracez,
    GET /requestz/<id>) through the micro-batching replica fleet
    (keystone_tpu/serve) with request-scoped tracing into an always-on
    bounded flight recorder."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m keystone_tpu.cli serve",
        description="serve a saved fitted pipeline over HTTP with "
        "dynamic micro-batching, admission control, a multi-device "
        "replica fleet, and registry-driven live model hot-swap",
    )
    ap.add_argument(
        "--model",
        action="append",
        default=None,
        metavar="PATH | NAME=PATH",
        help="path to a FittedPipeline saved via save()/fit_or_load().  "
        "Repeatable with NAME=PATH pairs for a MULTI-TENANT deploy "
        "(serve/tenants.py): every named model is co-served behind one "
        "fleet, shared featurization prefixes computed once per flush "
        "via the cross-pipeline stage pool; requests route by the "
        "'tenant' body field.",
    )
    ap.add_argument(
        "--model-dir",
        action="append",
        default=None,
        metavar="DIR | NAME=DIR",
        help="versioned model registry root (serve/registry.py): serve "
        "the CURRENT version (falling back past corrupt ones), enable "
        "POST /swap, and (with --watch) hot-swap newly published "
        "versions live.  Repeatable with NAME=DIR pairs for a "
        "registry-backed multi-tenant deploy (each tenant serves its "
        "registry's CURRENT version; /swap and --watch need a "
        "single-tenant deploy).  At least one --model/--model-dir is "
        "required; mixing named and unnamed entries is an error.",
    )
    ap.add_argument(
        "--replicas",
        type=int,
        default=1,
        help="serving fleet size: one FrozenApplier clone per local "
        "device (cycling when replicas > devices); flushes are routed "
        "to the least-loaded replica whose breaker admits work",
    )
    ap.add_argument(
        "--workers",
        type=int,
        default=0,
        help="PROCESS fleet (serve/procfleet.py): serve with this many "
        "one-replica worker processes instead of worker threads — each "
        "loads the model + AOT artifacts, primes, and computes applies "
        "over a shared-memory wire, so a multi-core host's throughput "
        "is bounded by cores, not the GIL.  0 (default) = the threaded "
        "fleet.  Exclusive with --replicas > 1; single-tenant only.",
    )
    ap.add_argument(
        "--hosts",
        default=None,
        metavar="HOST[:SLOTS],...",
        help="CROSS-HOST fleet (serve/net.py; requires --workers >= 1): "
        "a host map of boxes where workers may be spawned, e.g. "
        "'local:2,gpu-a:4,gpu-b:4'.  'local' spawns on this machine; "
        "remote hosts are reached over ssh and connect back to "
        "--listen-host:--listen-port over TCP.  Each worker beats a "
        "heartbeat lease; an expired lease is treated as death (the "
        "flush re-serves on a survivor) and the worker self-fences so "
        "a healed partition cannot double-serve.  Without --hosts, "
        "--workers stays on the shared-memory transport.",
    )
    ap.add_argument(
        "--lease-s",
        type=float,
        default=None,
        metavar="SECONDS",
        help="heartbeat lease length for the cross-host fleet (default "
        "5.0): both sides beat every lease/4; this much silence fences "
        "the worker / declares it dead at the router",
    )
    ap.add_argument(
        "--listen-host",
        default=None,
        metavar="ADDR",
        help="interface the cross-host fleet's registration listener "
        "binds (default 127.0.0.1 — set 0.0.0.0 when workers connect "
        "from other boxes)",
    )
    ap.add_argument(
        "--listen-port",
        type=int,
        default=None,
        help="registration listener port (default 0 = ephemeral)",
    )
    ap.add_argument(
        "--autoscale",
        default=None,
        metavar="MIN:MAX",
        help="SLO-driven autoscaling (serve/autoscale.py): a control "
        "thread watches windowed occupancy, queue depth, SLO burn, and "
        "the shared-pool hit rate, growing the fleet to MAX under "
        "pressure, retiring idle workers down to MIN, and retuning the "
        "dispatch window live (visible in GET /statusz).  Pair with "
        "--workers (the floor spawns as processes).",
    )
    ap.add_argument(
        "--watch",
        type=float,
        default=None,
        metavar="SECONDS",
        help="poll --model-dir's CURRENT pointer this often and blue/"
        "green hot-swap new versions into the fleet (prime in the "
        "background, commit at the flush boundary; requires --model-dir)",
    )
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument(
        "--max-wait-ms",
        type=float,
        default=None,
        help="flush the micro-batch when the oldest request has waited "
        "this long (or when --max-batch requests are queued).  Default: "
        "the installed PhysicalPlan's value if the model ships one, "
        "else 5.0 — passing a value always wins (the explicit tier of "
        "the planner precedence ladder)",
    )
    ap.add_argument(
        "--queue-bound",
        type=int,
        default=128,
        help="admission control: reject (HTTP 429) past this queue depth",
    )
    ap.add_argument(
        "--deadline-ms",
        type=float,
        default=None,
        help="default per-request deadline; doomed requests are shed "
        "(HTTP 504) instead of executed",
    )
    ap.add_argument(
        "--slo-ms",
        type=float,
        default=None,
        help="latency objective for GET /statusz's SLO error-budget "
        "burn rate (default: --deadline-ms when set)",
    )
    ap.add_argument(
        "--slo-target",
        type=float,
        default=0.99,
        help="fraction of requests that must beat the objective "
        "(burn rate = windowed bad fraction / (1 - target))",
    )
    ap.add_argument(
        "--slo-window-s",
        type=float,
        default=None,
        help="sliding window the SLO burn rate (and rollout guardrails) "
        "measure over (default 60s): shorter windows react faster but "
        "judge canaries on fewer samples",
    )
    ap.add_argument(
        "--canary",
        type=float,
        default=None,
        metavar="FRACTION",
        help="guarded rollouts (serve/rollout.py): --watch swaps stage "
        "the new version to this fraction of traffic (seeded hash of "
        "request id — replayable), judge it against the SLO-burn/error-"
        "rate/p99 guardrails, then auto-commit or roll back and "
        "quarantine the version.  Requires --watch.",
    )
    ap.add_argument(
        "--bake-s",
        type=float,
        default=0.0,
        help="post-commit bake: watch the SLO burn this long after a "
        "canary commit and auto-revert to the prior version on "
        "sustained violation (0 = off; needs --canary)",
    )
    ap.add_argument(
        "--no-recorder",
        action="store_true",
        help="disable the in-memory flight recorder (request tracing; "
        "GET /tracez and GET /requestz/<id> answer 409).  HTTP "
        "responses still echo a request id (client log correlation); "
        "nothing records or resolves it server-side",
    )
    ap.add_argument(
        "--trace-dump",
        default=None,
        metavar="DIR",
        help="durable flight-recorder snapshots: POST /tracez/dump "
        "writes the recorder state into DIR (atomic publish), and a "
        "final snapshot is written at shutdown — the artifact "
        "tools/trace_report.py reads offline for post-incident "
        "analysis.  Needs the recorder (conflicts with --no-recorder).",
    )
    ap.add_argument(
        "--no-supervise",
        action="store_true",
        help="disable the replica supervisor (self-healing: dead/wedged "
        "worker detection, in-place restart, quarantine after repeated "
        "deaths).  On by default.",
    )
    ap.add_argument(
        "--heartbeat-s",
        type=float,
        default=30.0,
        help="wedge budget: a replica worker holding one flush longer "
        "than this is declared wedged and restarted — size it above "
        "the slowest honest apply",
    )
    ap.add_argument(
        "--restart-limit",
        type=int,
        default=3,
        help="supervisor restarts allowed per replica within "
        "--restart-window-s before the slot is quarantined",
    )
    ap.add_argument(
        "--restart-window-s",
        type=float,
        default=60.0,
        help="the sliding window the restart budget counts over",
    )
    ap.add_argument(
        "--hedge-ms",
        type=float,
        default=None,
        help="hedged dispatch (off by default): re-enqueue a batch "
        "still unflushed after max(this, 3x the EWMA batch time) on a "
        "second replica; first claim wins, the loser is cancelled "
        "without device work.  Needs --replicas >= 2.",
    )
    ap.add_argument(
        "--no-bisect",
        action="store_true",
        help="disable batch-failure bisection (poison-request "
        "isolation + content quarantine).  On by default.",
    )
    ap.add_argument(
        "--no-artifacts",
        action="store_true",
        help="skip the AOT artifact tier: ignore pre-lowered "
        "executables published next to the model (the escape hatch "
        "when a published artifact is suspected bad) — priming rides "
        "the compile-cache/fresh-compile rungs of the ladder instead",
    )
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument(
        "--example-shape",
        default=None,
        metavar="D0[,D1,...]",
        help="per-datum input shape (e.g. '24' or '3,32,32'): primes "
        "every padding bucket's compiled program BEFORE serving, so no "
        "request ever pays a trace+compile against its deadline.  "
        "Without it the first request per bucket compiles in-band.",
    )
    args = ap.parse_args(argv)
    models = list(args.model or [])
    model_dirs = list(args.model_dir or [])
    if not models and not model_dirs:
        ap.error("at least one of --model / --model-dir is required")

    def _named(spec: str) -> bool:
        # NAME=PATH only when the prefix is a plain tenant name and the
        # whole spec is not itself an existing path — a single
        # --model ./runs/lr=0.1/model.pkl must stay a path
        name, sep, _ = spec.partition("=")
        return bool(sep) and bool(name) and os.sep not in name and not (
            os.path.exists(spec)
        )

    named = [m for m in models + model_dirs if _named(m)]
    multi = bool(named) or (len(models) + len(model_dirs)) > 1
    if multi and len(named) != len(models) + len(model_dirs):
        ap.error(
            "multi-tenant deploys name every entry: --model NAME=PATH / "
            "--model-dir NAME=DIR"
        )
    if not multi and models and model_dirs:
        ap.error("pass one --model OR one --model-dir, not both")
    if args.watch is not None and (multi or not model_dirs):
        ap.error("--watch requires a single-tenant --model-dir deploy")

    from keystone_tpu.serve import HttpFrontend, serve, serve_multi

    example = None
    if args.example_shape:
        import numpy as np

        shape = tuple(int(d) for d in args.example_shape.split(","))
        example = np.zeros(shape, np.float32)
    autoscale = None
    if args.autoscale:
        try:
            lo, _, hi = args.autoscale.partition(":")
            autoscale = dict(min_workers=int(lo), max_workers=int(hi))
        except ValueError:
            ap.error("--autoscale takes MIN:MAX (e.g. 1:4)")
    if args.workers and args.replicas > 1:
        ap.error("--workers (process fleet) and --replicas are exclusive")
    if args.workers and multi:
        ap.error("--workers is single-tenant only (the shared stage "
                 "pool needs in-process walks)")
    if args.hosts and not args.workers:
        ap.error("--hosts (cross-host fleet) requires --workers >= 1")
    if args.hosts and multi:
        ap.error("--hosts is single-tenant only")
    if args.trace_dump and args.no_recorder:
        ap.error("--trace-dump needs the flight recorder; drop "
                 "--no-recorder")
    if args.canary is not None and args.watch is None:
        ap.error("--canary guards --watch swaps; add --watch SECONDS")
    if args.bake_s and args.canary is None:
        ap.error("--bake-s needs --canary")
    fleet_kw = (
        dict(workers=args.workers)
        if args.workers
        else dict(replicas=args.replicas)
    )
    if args.hosts:
        fleet_kw["hosts"] = args.hosts
        net_opts = {}
        if args.lease_s is not None:
            net_opts["lease_s"] = args.lease_s
        if args.listen_host is not None:
            net_opts["listen_host"] = args.listen_host
        if args.listen_port is not None:
            net_opts["listen_port"] = args.listen_port
        if net_opts:
            fleet_kw["worker_opts"] = net_opts
    serve_kw = dict(
        max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        queue_bound=args.queue_bound,
        deadline_ms=args.deadline_ms,
        example=example,
        recorder=not args.no_recorder,
        **fleet_kw,
        slo_ms=args.slo_ms,
        slo_target=args.slo_target,
        supervise=not args.no_supervise,
        heartbeat_s=args.heartbeat_s,
        restart_limit=args.restart_limit,
        restart_window_s=args.restart_window_s,
        hedge_ms=args.hedge_ms,
        bisect=not args.no_bisect,
        autoscale=autoscale,
        slo_window_s=args.slo_window_s,
    )
    registry = None
    artifacts = None
    if multi:
        # registry multi-model deploy: each named entry loads a saved
        # model (NAME=PATH) or a registry's CURRENT version (NAME=DIR);
        # the fleet co-serves them with cross-pipeline prefix sharing
        from keystone_tpu.serve import ModelRegistry
        from keystone_tpu.workflow import FittedPipeline

        tenants = {}
        parts = []
        for spec in models:
            name, _, path = spec.partition("=")
            tenants[name] = FittedPipeline.load(path)
            parts.append(f"{name}={path}")
        for spec in model_dirs:
            name, _, root = spec.partition("=")
            reg = ModelRegistry(root)
            fitted, version = reg.load()
            tenants[name] = fitted
            parts.append(f"{name}={root} ({version})")
            if not args.no_artifacts:
                # the multi applier has no per-tenant bucket-program
                # install (the walk serves), but the bundle's
                # pre-seeded compile-cache entries — this PR's last
                # cold rung — apply process-wide: seed them so the
                # deploy's primes hit the cache tier
                arts = reg.load_artifacts(version)
                if arts:
                    from keystone_tpu.utils.compile_cache import (
                        seed_compile_cache,
                    )

                    seed_compile_cache(arts)
        svc = serve_multi(tenants, **serve_kw)
        version = "multi"
        source = ", ".join(parts)
    elif model_dirs:
        from keystone_tpu.serve import ModelRegistry

        registry = ModelRegistry(model_dirs[0])
        fitted, version = registry.load()
        if not args.no_artifacts:
            # best-effort AOT tier: absent/corrupt artifacts mean this
            # deploy compiles — never that it fails
            artifacts = registry.load_artifacts(version)
        source = f"{model_dirs[0]} ({version})"
        svc = serve(fitted, version=version, artifacts=artifacts, **serve_kw)
    else:
        from keystone_tpu.workflow import FittedPipeline

        fitted = FittedPipeline.load(models[0])
        version, source = "v0", models[0]
        svc = serve(fitted, version=version, **serve_kw)
    watcher = None
    if args.watch is not None:
        from keystone_tpu.serve import RegistryWatcher

        rollout_cfg = None
        if args.canary is not None:
            from keystone_tpu.serve import RolloutConfig

            rollout_cfg = RolloutConfig(
                canary=args.canary, bake_s=args.bake_s
            )
        watcher = RegistryWatcher(
            svc, registry, poll_seconds=args.watch, rollout=rollout_cfg
        ).start()
    front = HttpFrontend(
        svc,
        host=args.host,
        port=args.port,
        registry=registry,
        trace_dump_dir=args.trace_dump,
    )
    print(
        f"serving {source} on http://{args.host}:{front.port} "
        f"(replicas={svc.replicas}, max_batch={args.max_batch}, "
        f"max_wait_ms={svc.max_wait_s * 1000.0:g}, "
        f"queue_bound={args.queue_bound}"
        + (f", watching every {args.watch:g}s" if watcher else "")
        + (", tracing off" if args.no_recorder else ", tracing on")
        + (", artifacts on" if artifacts else "")
        + ")",
        flush=True,
    )
    try:
        front.serve_forever()
    except KeyboardInterrupt:
        print("shutting down (draining in-flight requests)", flush=True)
    finally:
        if watcher is not None:
            watcher.stop()
        front.server.server_close()
        if args.trace_dump:
            # the shutdown snapshot: whatever the recorder holds when
            # the process exits survives for the post-incident read
            try:
                path = svc.dump_trace(args.trace_dump)
                if path:
                    print(f"trace dump written to {path}", flush=True)
            except OSError as e:
                print(f"trace dump failed: {e}", flush=True)
        svc.close()
    return 0


def _worker_main(argv) -> int:
    """``worker`` subcommand: one remote replica of a cross-host
    serving fleet (serve/net.py).  Connects back to a router started
    with ``serve --hosts``, receives the deploy payload over the wire,
    builds + primes the applier (the same cold-start ladder the
    process fleet runs), and serves applies until the router says bye
    — reconnecting with bounded backoff through partitions, and
    self-fencing whenever its heartbeat lease lapses."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m keystone_tpu.cli worker",
        description="run one remote serving worker: connect to a "
        "router's registration listener, receive the model over TCP, "
        "prime, and serve under a heartbeat lease",
    )
    ap.add_argument(
        "--connect",
        required=True,
        metavar="HOST:PORT",
        help="the router's registration listener (printed by serve "
        "--hosts, or read service.listen_address)",
    )
    ap.add_argument(
        "--name",
        default=None,
        help="worker label in router logs/metrics (default "
        "<hostname>-<pid>)",
    )
    ap.add_argument(
        "--connect-attempts",
        type=int,
        default=30,
        help="bounded connect/reconnect retries (backoff+jitter) "
        "before giving up on an unreachable router",
    )
    ap.add_argument(
        "--backoff-seed",
        type=int,
        default=None,
        help="seed the reconnect jitter (reproducible drills)",
    )
    args = ap.parse_args(argv)
    from keystone_tpu.serve.net import run_worker

    return run_worker(
        args.connect,
        name=args.name,
        connect_attempts=args.connect_attempts,
        backoff_seed=args.backoff_seed,
    )


def _export_main(argv) -> int:
    """``export`` subcommand: freeze a saved fitted pipeline and write
    its AOT artifacts — the whole frozen apply lowered at every padding
    bucket and serialized with ``jax.export`` — either into a model
    registry version dir (``--model-dir``: the next ``serve``/watcher
    deploy of that version loads instead of compiling) or as a
    standalone bundle directory (``--out``)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m keystone_tpu.cli export",
        description="freeze a saved model and publish pre-lowered AOT "
        "apply executables (jax.export) so serve cold start, hot-swap, "
        "and supervisor heals stop paying compile time",
    )
    ap.add_argument(
        "--model",
        default=None,
        help="path to a FittedPipeline saved via save()/fit_or_load(); "
        "with --model-dir the artifacts are published alongside it as "
        "a NEW registry version",
    )
    ap.add_argument(
        "--model-dir",
        default=None,
        metavar="DIR",
        help="model registry root: with --model, publish model + "
        "artifacts as a new version; without, export artifacts for the "
        "registry's CURRENT version in place",
    )
    ap.add_argument(
        "--example-shape",
        required=True,
        metavar="D0[,D1,...]",
        help="per-datum input shape (e.g. '64' or '3,32,32') the "
        "bucket programs are lowered for — must match what serve will "
        "receive",
    )
    ap.add_argument(
        "--dtype",
        default="float32",
        help="per-datum input dtype (default float32)",
    )
    ap.add_argument(
        "--max-batch",
        type=int,
        default=32,
        help="serve-side max_batch: buckets default to the same "
        "powers-of-two-up-to-max-batch the service pads with",
    )
    ap.add_argument(
        "--buckets",
        default=None,
        metavar="B0[,B1,...]",
        help="explicit padding-bucket sizes (overrides --max-batch)",
    )
    ap.add_argument(
        "--out",
        default=None,
        metavar="DIR",
        help="write the bundle to this directory instead of a registry "
        "(MANIFEST.json + one .hlo blob per bucket, BLAKE2b sidecars)",
    )
    ap.add_argument(
        "--plan",
        action="store_true",
        help="cost-based physical planning at freeze "
        "(keystone_tpu.planner): micro-profile candidate "
        "implementations on seeded sampling batches and ship the "
        "PhysicalPlan in the manifest — every install of this bundle "
        "serves the planned configuration (inspect: keystone plan)",
    )
    ap.add_argument(
        "--plan-seed",
        type=int,
        default=0,
        help="sampling seed for --plan (plan identity includes it)",
    )
    args = ap.parse_args(argv)
    if args.model is None and args.model_dir is None:
        ap.error("pass --model and/or --model-dir")
    if args.out is None and args.model_dir is None:
        ap.error("pass --out or --model-dir (somewhere to write artifacts)")

    import numpy as np

    from keystone_tpu.serve.service import default_buckets

    shape = tuple(int(d) for d in args.example_shape.split(","))
    example = np.zeros(shape, np.dtype(args.dtype))
    if args.buckets:
        buckets = tuple(int(b) for b in args.buckets.split(","))
    else:
        buckets = default_buckets(args.max_batch)

    registry = None
    version = None
    if args.model is not None:
        from keystone_tpu.workflow import FittedPipeline

        fitted = FittedPipeline.load(args.model)
    else:
        from keystone_tpu.serve import ModelRegistry

        registry = ModelRegistry(args.model_dir)
        fitted, version = registry.load()
    if args.plan:
        from keystone_tpu.planner import build_plan

        rng = np.random.default_rng(args.plan_seed)
        sample = rng.normal(size=(32,) + shape).astype(np.dtype(args.dtype))
        plan = build_plan(
            fitted, example=sample, max_batch=max(buckets),
            seed=args.plan_seed,
        )
        frozen = fitted.freeze(plan=plan)
        print(f"planned: {plan.fingerprint()} (keystone plan to inspect)")
    else:
        frozen = fitted.freeze()
    bundle = frozen.export_artifacts(example=example, buckets=buckets)
    ents = bundle["manifest"]["entries"]
    n_cache = sum(
        1 for e in ents.values() if e.get("kind") == "compile_cache"
    )
    n = len(bundle["blobs"]) - n_cache
    if n_cache:
        print(
            f"captured {n_cache} persistent-compile-cache entr"
            f"{'y' if n_cache == 1 else 'ies'} (pre-seeded backend "
            "compiles ship with the bundle)"
        )
    if args.model_dir is not None:
        from keystone_tpu.serve import ModelRegistry

        registry = registry or ModelRegistry(args.model_dir)
        if version is None:
            version = registry.publish(fitted, artifacts=bundle)
            print(
                f"published {version} (+{n} AOT bucket programs) to "
                f"{args.model_dir}"
            )
        else:
            registry.publish_artifacts(version, bundle)
            print(
                f"wrote {n} AOT bucket programs for existing version "
                f"{version} in {args.model_dir}"
            )
    if args.out is not None:
        from keystone_tpu.serve.registry import write_artifact_bundle

        write_artifact_bundle(args.out, bundle, describe="export bundle")
        print(f"wrote bundle ({n} bucket programs) to {args.out}")
    man = bundle["manifest"]
    print(
        f"buckets={man['buckets']} item_shape={tuple(man['item_shape'])} "
        f"dtype={man['dtype']} jax={man['jax_version']} "
        f"platforms={man['platforms']} signature={man['signature']}"
    )
    return 0


def _check_main(argv) -> int:
    """``check`` subcommand: run the pre-flight static analyzer
    (``keystone_tpu.analysis``) over a bundled pipeline (assembled on
    tiny synthetic data) or a saved fitted model, print findings with
    graph locations, and exit non-zero when any error-severity finding
    is present — the cheap gate to run before committing a long fit or
    bringing up a serve fleet."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m keystone_tpu.cli check",
        description="static pre-flight analysis: shape/dtype propagation, "
        "solver precision lint, robustness-config lint, signature audit",
    )
    ap.add_argument(
        "pipeline",
        nargs="?",
        help="bundled pipeline name (see --list); mutually exclusive "
        "with --model",
    )
    ap.add_argument(
        "--model",
        help="path to a FittedPipeline saved via save()/fit_or_load(); "
        "analyzed in apply mode (the freeze/serve contract)",
    )
    ap.add_argument(
        "--example-shape",
        default=None,
        metavar="D0[,D1,...]",
        help="per-datum input shape seeding shape propagation from the "
        "open source (with --model; bundled pipelines derive it from "
        "their synthetic training data)",
    )
    ap.add_argument(
        "--deadline",
        type=float,
        default=None,
        help="intended fit/apply deadline (seconds): enables the "
        "deadline-feasibility estimate against profiled stage costs",
    )
    ap.add_argument(
        "--dot",
        metavar="OUT",
        default=None,
        help="write a Graphviz DOT of the graph with findings overlaid "
        "(red = error, yellow = warning)",
    )
    ap.add_argument(
        "--no-solver-lint",
        action="store_true",
        help="skip the precision pass (solver jaxpr tracing)",
    )
    ap.add_argument(
        "--json", action="store_true", help="emit the report as JSON"
    )
    args = ap.parse_args(argv)
    if bool(args.pipeline) == bool(args.model):
        ap.error("pass exactly one of <PipelineName> or --model")

    from keystone_tpu.analysis import ALL_PASSES, DEFAULT_PASSES, analyze

    mode = "fit"
    if args.model:
        from keystone_tpu.workflow import FittedPipeline

        pipe = FittedPipeline.load(args.model)
        example = None
        if args.example_shape:
            example = tuple(int(d) for d in args.example_shape.split(","))
        mode = "apply"
    else:
        from keystone_tpu.analysis.bundled import build_bundled

        try:
            pipe, example = build_bundled(args.pipeline)
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 2
    passes = (
        DEFAULT_PASSES + ("plan",) if args.no_solver_lint else ALL_PASSES
    )
    report = analyze(
        pipe,
        example=example,
        deadline=args.deadline,
        passes=passes,
        mode=mode,
    )
    if args.json:
        import json

        print(json.dumps(report.to_dict(), indent=2))
    else:
        print(report.render())
    if args.dot:
        from keystone_tpu.workflow.viz import to_dot

        with open(args.dot, "w") as f:
            f.write(to_dot(pipe.graph, findings=report.findings))
        print(f"wrote findings overlay to {args.dot}")
    return 0 if report.ok else 1


def _plan_main(argv) -> int:
    """``plan`` subcommand: inspect (or build) a cost-based
    ``PhysicalPlan`` — per-stage candidates, sampled costs, the chosen
    winner and why, and the serving knobs (``keystone_tpu.planner``).
    Reads the plan a published registry version or exported bundle
    ships in its manifest, a raw ``plan.json``, or builds one fresh by
    sampling a saved fitted model."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m keystone_tpu.cli plan",
        description="show or build the cost-based physical plan that "
        "ships with a model: candidate implementations, sampled cost "
        "curves, winners, and serving knobs",
    )
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument(
        "--model-dir",
        metavar="DIR",
        help="model registry root: read the plan the CURRENT (or "
        "--version) version's artifact manifest ships",
    )
    src.add_argument(
        "--bundle",
        metavar="DIR",
        help="exported artifact bundle directory (MANIFEST.json)",
    )
    src.add_argument(
        "--file", metavar="PLAN.json", help="a raw serialized plan file"
    )
    src.add_argument(
        "--model",
        metavar="MODEL.pkl",
        help="build a plan NOW by sampling this saved fitted pipeline "
        "(needs --example-shape)",
    )
    ap.add_argument(
        "--version",
        default=None,
        help="registry version (with --model-dir; default CURRENT)",
    )
    ap.add_argument(
        "--example-shape",
        default=None,
        metavar="D0[,D1,...]",
        help="per-datum input shape for --model sampling batches",
    )
    ap.add_argument(
        "--dtype", default="float32", help="--model sampling dtype"
    )
    ap.add_argument(
        "--seed", type=int, default=0, help="--model sampling seed"
    )
    ap.add_argument(
        "--out",
        default=None,
        metavar="PLAN.json",
        help="write the (read or built) plan to this file",
    )
    ap.add_argument(
        "--explain",
        action="store_true",
        help="full explain: every candidate's samples, fitted curve, "
        "cost at the serving batch, and the winner's why",
    )
    ap.add_argument(
        "--json", action="store_true", help="emit the plan dict as JSON"
    )
    args = ap.parse_args(argv)

    import json

    from keystone_tpu.planner import PhysicalPlan, build_plan

    plan = None
    if args.file:
        with open(args.file) as f:
            plan = PhysicalPlan.from_dict(json.load(f))
    elif args.bundle:
        with open(os.path.join(args.bundle, "MANIFEST.json")) as f:
            manifest = json.load(f).get("manifest") or {}
        if manifest.get("plan") is None:
            print("bundle ships no plan (exported without planning)",
                  file=sys.stderr)
            return 1
        plan = PhysicalPlan.from_dict(manifest["plan"])
    elif args.model_dir:
        from keystone_tpu.serve import ModelRegistry

        reg = ModelRegistry(args.model_dir)
        version = args.version or (reg.versions() or [None])[-1]
        if version is None:
            print(f"no versions published in {args.model_dir}",
                  file=sys.stderr)
            return 1
        bundle = reg.load_artifacts(version)
        plan_dict = ((bundle or {}).get("manifest") or {}).get("plan")
        if plan_dict is None:
            print(f"version {version} ships no plan", file=sys.stderr)
            return 1
        plan = PhysicalPlan.from_dict(plan_dict)
    else:
        if not args.example_shape:
            ap.error("--model needs --example-shape for sampling batches")
        import numpy as np

        from keystone_tpu.workflow import FittedPipeline

        shape = tuple(int(d) for d in args.example_shape.split(","))
        rng = np.random.default_rng(args.seed)
        example = rng.normal(size=(32,) + shape).astype(np.dtype(args.dtype))
        fitted = FittedPipeline.load(args.model)
        plan = build_plan(fitted, example=example, seed=args.seed)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(plan.to_dict(), f, indent=2, sort_keys=True)
        print(f"wrote plan {plan.fingerprint()} to {args.out}")
    if args.json:
        print(json.dumps(plan.to_dict(), indent=2, sort_keys=True))
    elif args.explain:
        print(plan.explain())
    else:
        print(
            f"plan {plan.fingerprint()}  backend={plan.backend} "
            f"source={plan.source} stages={len(plan.stages)}"
        )
        for s in plan.stages:
            print(f"  {s.gate}: {s.winner}  ({s.why})")
        for k in sorted(plan.knobs):
            print(f"  knob {k} = {plan.knobs[k]}")
        print("(--explain for candidates, sampled costs, and fits)")
    problems = plan.validate()
    for code, msg in problems:
        print(f"WARNING [{code}] {msg}", file=sys.stderr)
    return 0


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("--list", "-l", "--help", "-h"):
        print("usage: python -m keystone_tpu.cli <PipelineName> [flags]")
        print("       python -m keystone_tpu.cli serve --model model.pkl [flags]")
        print("       python -m keystone_tpu.cli worker --connect HOST:PORT [flags]")
        print("       python -m keystone_tpu.cli export --model model.pkl --example-shape D0[,D1,...] [flags]")
        print("       python -m keystone_tpu.cli check <PipelineName>|--model model.pkl [flags]")
        print("       python -m keystone_tpu.cli plan --model-dir DIR|--bundle DIR|--file plan.json|--model model.pkl [flags]")
        print("pipelines:")
        for name in _PIPELINE_MODULES:
            print(f"  {name}")
        return 0
    name, rest = argv[0], argv[1:]
    if name == "check":
        return _check_main(rest)
    if name == "plan":
        return _plan_main(rest)
    if name == "serve":
        from keystone_tpu.utils.compile_cache import enable_compilation_cache

        enable_compilation_cache()
        return _serve_main(rest)
    if name == "worker":
        from keystone_tpu.utils.compile_cache import enable_compilation_cache

        enable_compilation_cache()
        return _worker_main(rest)
    if name == "export":
        from keystone_tpu.utils.compile_cache import enable_compilation_cache

        enable_compilation_cache()
        return _export_main(rest)
    if name not in _PIPELINE_MODULES:
        print(f"unknown pipeline {name!r}; use --list", file=sys.stderr)
        return 2
    # only now touch jax: --list/--help/typos shouldn't pay the import
    from keystone_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    state_dir = os.environ.get("KEYSTONE_STATE_DIR")
    if state_dir:
        # saved-prefix reload (workflow/state.py SavedStateLoadRule):
        # loader datasets are named, so featurized prefixes persisted by
        # save_pipeline_state in an earlier process are reused here
        from keystone_tpu.workflow import PipelineEnv

        PipelineEnv.state_dir = state_dir
    mod = importlib.import_module(_PIPELINE_MODULES[name])
    mod.main(rest)
    return 0


if __name__ == "__main__":
    sys.exit(main())
