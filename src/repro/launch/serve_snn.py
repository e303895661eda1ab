"""SNN serving launcher: packed spiking inference on a synthetic stream.

The spiking counterpart of launch/serve.py — packs a model once with
``repro.deploy.deploy`` and serves a mixed-size synthetic request stream
through the bucket-cached :class:`~repro.deploy.engine.SNNServeEngine`.

Run:  PYTHONPATH=src python -m repro.launch.serve_snn [--full] [--bits 4]

``--async`` routes the stream through the continuous-batching tier
(repro.serve_async): per-request futures, ``--workers`` threads,
``--deadline-ms`` admission deadlines, graceful drain on exit.
``--rate R`` (async only) switches submission to an open-loop Poisson
arrival process at R requests/s; the sync-vs-async open-loop comparison
lives in ``python -m repro.serve_async.loadgen --mode both``.

The live observability plane (obs/README.md) hangs off three flags:
``--metrics-port`` starts the in-process HTTP server (/metrics,
/healthz, /spans) for scraping DURING the run; ``--trace`` exports the
span ring as a Chrome/Perfetto trace on exit; ``--hold S`` keeps the
server (and process) alive S extra seconds after serving so an external
scraper can catch the final state — the CI obs-smoke leg curls inside
that window.  Any of ``--metrics``/``--metrics-port``/``--trace``
enables the registry; with none of them the hot path keeps its no-op
instruments.
"""

from __future__ import annotations

import argparse


def main():
    from repro.configs import add_geometry_flags
    from repro.launch.profiling import add_profile_flag, maybe_trace
    from repro.obs import add_metrics_flag, add_server_flag

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="vgg9",
                    choices=("vgg9", "vgg16", "resnet18", "resnet50"))
    ap.add_argument("--bits", type=int, default=4, choices=(2, 4, 8))
    add_geometry_flags(ap)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="serve through the continuous-batching async "
                         "tier (repro.serve_async): emplace-on-arrival "
                         "admission, pipelined rollouts, per-request "
                         "futures, graceful drain on exit")
    ap.add_argument("--workers", type=int, default=1,
                    help="async-tier worker threads (with --async)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="async admission deadline; expired requests "
                         "resolve as explicit timeout results "
                         "(with --async)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered load in requests/s; >0 submits on a "
                         "seeded Poisson arrival schedule (open loop) "
                         "instead of enqueueing everything up front")
    ap.add_argument("--data-parallel", action="store_true",
                    help="shard_map the forward over local devices")
    ap.add_argument("--package", default="",
                    help="save the packed model npz here (and reload it "
                         "before serving, exercising the artifact path)")
    ap.add_argument("--show-graph", action="store_true",
                    help="print the declarative model graph (the one "
                         "topology the train/int/packaged lowerings share), "
                         "including fusion-group membership + VMEM footprint")
    ap.add_argument("--fusion", default="off", choices=("off", "auto"),
                    help="multi-layer fusion: 'auto' plans VMEM-resident "
                         "fusion groups (repro.graph.fusion) so grouped "
                         "layers' inter-member spikes never touch HBM")
    add_profile_flag(ap, "/tmp/repro_trace/serve_snn")
    add_metrics_flag(ap, "/tmp/repro_metrics/serve_snn.jsonl")
    add_server_flag(ap)
    ap.add_argument("--trace", nargs="?",
                    const="/tmp/repro_metrics/serve_snn.trace.json",
                    default=None, metavar="PATH",
                    help="export the span ring as a Chrome trace_event "
                         "JSON on exit (load in chrome://tracing or "
                         "ui.perfetto.dev); validate with "
                         "python -m repro.obs.validate PATH --trace")
    ap.add_argument("--slo-p95-ms", type=float, default=250.0,
                    help="watchdog p95 latency SLO (ms)")
    ap.add_argument("--watchdog-dir", default="",
                    help="flight-recorder artifact directory; empty = no "
                         "artifacts on trip")
    ap.add_argument("--hold", type=float, default=0.0, metavar="S",
                    help="keep the process (and --metrics-port server) "
                         "alive S seconds after serving, for external "
                         "scrapes")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import time

    import jax
    import numpy as np

    from repro import obs
    from repro.deploy import (
        SNNEngineConfig, SNNRequest, SNNServeEngine, deploy, deploy_config,
        load,
    )
    from repro.models import snn_cnn

    # enable BEFORE constructing the engine — instruments bind at
    # construction time (no-op handles otherwise).  Any live-plane flag
    # implies the registry.
    metrics_on = bool(args.metrics or args.trace
                      or args.metrics_port is not None)
    registry = obs.enable_default() if metrics_on else None

    cfg = deploy_config(args.model, args.bits, smoke=args.smoke,
                        fusion="auto" if args.fusion == "auto" else ())
    if args.show_graph:
        print(cfg.graph().summary())
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    t0 = time.perf_counter()
    model = deploy(params, cfg)
    print(f"packed {cfg.model} W{args.bits} in "
          f"{time.perf_counter() - t0:.2f}s: "
          f"{len(model.layers)} layers, "
          f"{model.nbytes_packed() / 1e6:.2f} MB packed "
          f"({model.compression_ratio():.1f}x vs fp32)")
    if args.package:
        model.save(args.package)
        model = load(args.package)
        print(f"saved + reloaded package: {args.package}")

    eng = SNNServeEngine(model, SNNEngineConfig(
        max_batch=args.max_batch, data_parallel=args.data_parallel))
    aeng = None
    if args.use_async:
        from repro.serve_async import AsyncEngineConfig, AsyncSNNServeEngine

        aeng = AsyncSNNServeEngine(eng, AsyncEngineConfig(
            workers=args.workers, default_deadline_ms=args.deadline_ms))

    server = None
    if args.metrics_port is not None:
        server = obs.ObsServer(registry, port=args.metrics_port,
                               health_fn=(aeng or eng).health)
        port = server.start()
        print(f"[obs] serving http://127.0.0.1:{port}/metrics "
              f"(/healthz, /spans?since=N)")

    n_exe = eng.warmup()
    print(f"warmup compiled {n_exe} bucket executables: {eng.buckets}")

    rng = np.random.default_rng(0)
    sample = jax.numpy.asarray(rng.random(
        (2, cfg.img_size, cfg.img_size,
         cfg.in_channels)).astype(np.float32))

    if metrics_on:
        # Model telemetry is a SAMPLED eager pass (spike stats are host
        # floats — under jit they would be tracers), one per run, not
        # per request.  It runs BEFORE serving because its per-layer
        # spike rates double as the watchdog's calibration snapshot:
        # live drift is judged against what the model did at deploy
        # time, and the attribution pass puts snn_layer_time_us on
        # /metrics before the first scrape.
        _, layer_records = obs.instrumented_forward(
            cfg, model.float_params, sample, package=model,
            registry=registry)
        for row in layer_records:
            print(f"[obs] {row['layer']:<12} rate={row['rate']:.3f} "
                  f"saturation={row['saturation']:.3f} "
                  f"silent={row['silent']:.3f} resets={row['resets']}")
        calibration = {row["layer"]: row["rate"] for row in layer_records}

        from repro.perfmodel.roofline import V5E

        # a CPU run predicts the v5e it stands in for; on a device the
        # roofline is that device's own table entry (unknown kinds raise)
        _, timed_records = obs.timed_forward(
            cfg, model.float_params, sample, package=model,
            registry=registry,
            peaks=V5E if jax.default_backend() == "cpu" else None)
        summ = obs.attribution_summary(timed_records)
        print(f"[obs] attribution: {summ['nodes']} nodes, "
              f"{summ['wall_us'] / 1e3:.1f}ms measured vs "
              f"{summ['predicted_us']:.1f}us roofline "
              f"(hottest {summ['hottest_layer']} "
              f"{summ['hottest_wall_us'] / 1e3:.1f}ms)")

        watchdog = obs.Watchdog(
            registry, calibration=calibration,
            cfg=obs.WatchdogConfig(slo_p95_ms=args.slo_p95_ms,
                                   artifact_dir=args.watchdog_dir or None))
        eng.attach_watchdog(watchdog)

    images = [rng.random((cfg.img_size, cfg.img_size,
                          cfg.in_channels)).astype(np.float32)
              for _ in range(min(args.requests, 16))]
    if args.use_async:
        from repro.serve_async import (
            poisson_schedule, run_open_loop_async,
        )

        aeng.start()
        t0 = time.perf_counter()
        with maybe_trace(args.profile):
            if args.rate > 0:
                rep = run_open_loop_async(
                    aeng, np.stack(images),
                    poisson_schedule(args.rate, args.requests),
                    deadline_ms=args.deadline_ms)
                print(rep.summary())
            else:
                futs = [aeng.submit(images[uid % len(images)])
                        for uid in range(args.requests)]
                done = sum(f.result(timeout=300).ok for f in futs)
                print(f"{done}/{args.requests} futures resolved ok")
            aeng.close()        # graceful drain: flushes queue+pipeline
        stats = aeng.stats(wall_s=time.perf_counter() - t0)
        a = stats["async"]
        print(f"async tier: {a['workers']} workers, "
              f"{a['submitted']} submitted / {a['completed']} completed "
              f"/ {a['timeouts']} timeout / {a['cancelled']} cancelled, "
              f"{a['slots_recycled']} slot recycles "
              f"(capacity {a['slot_capacity']}), "
              f"p99={stats['latency_p99_ms']:.1f}ms")
    else:
        for uid in range(args.requests):
            eng.add_request(SNNRequest(
                uid=uid, image=images[uid % len(images)]))
        t0 = time.perf_counter()
        with maybe_trace(args.profile):
            eng.run_until_done(max_steps=args.requests)
        stats = eng.stats(wall_s=time.perf_counter() - t0)
    print(f"served {stats['requests']} requests in {stats['wall_s']:.2f}s "
          f"({stats['images_per_s']:.1f} img/s, "
          f"{stats['batches']} batches, {stats['compiles']} compiles, "
          f"latency p50={stats['latency_p50_ms']:.1f}ms "
          f"p95={stats['latency_p95_ms']:.1f}ms, "
          f"queue avg={stats['queue_avg_ms']:.1f}ms vs "
          f"compute avg={stats['compute_avg_ms']:.1f}ms, "
          f"padding waste={stats['padding_waste']:.0%})")

    if metrics_on:
        util = obs.package_code_utilization(model, registry=registry)
        for name, h in util.items():
            print(f"[obs] {name:<12} W{h['bits']} code util "
                  f"{h['utilization']:.2f} clip {h['clip_frac']:.3f}")
        wd = eng._watchdog
        tripped = sorted({t["rule"] for t in wd.trips})
        print(f"[obs] watchdog: {wd.trips_total} trips"
              + (f" ({', '.join(tripped)})" if tripped else ""))

    if args.hold > 0:
        print(f"[obs] holding {args.hold:.0f}s for external scrapes "
              "(ctrl-c to stop early)")
        deadline = time.perf_counter() + args.hold
        try:
            while time.perf_counter() < deadline:
                time.sleep(0.2)
        except KeyboardInterrupt:
            pass

    if args.metrics:
        out = obs.write_jsonl(registry, args.metrics,
                              meta={"entry": "serve_snn",
                                    "model": args.model,
                                    "bits": args.bits})
        print(f"[obs] metrics written to {out} — validate with "
              f"`python -m repro.obs.validate {out}`")
    if args.trace:
        out = obs.export_chrome_trace(registry, args.trace,
                                      meta={"entry": "serve_snn",
                                            "model": args.model,
                                            "bits": args.bits})
        print(f"[obs] Chrome trace written to {out} — load in "
              f"chrome://tracing, validate with "
              f"`python -m repro.obs.validate {out} --trace`")
    if server is not None:
        server.stop()


if __name__ == "__main__":
    main()
