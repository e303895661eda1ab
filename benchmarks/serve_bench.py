"""Serve-path benchmark: packed SNN deployment + batched spiking serving.

Measures what the deploy subsystem buys on the serving path:

  * ``deploy_ms``    — one-shot pack cost (paid once, off the hot path)
  * ``percall_us``   — forward that re-quantizes every layer per call
                       (the old ``int_deploy`` hot path)
  * ``packaged_us``  — same forward from the pre-packed DeployedModel
  * engine records   — mixed-size synthetic stream through
                       SNNServeEngine: img/s, latency percentiles,
                       compile counts (zero recompiles after warmup)
  * open-loop records — the SAME seeded Poisson arrival process offered
                       to the sync engine and the async tier
                       (repro.serve_async): offered vs achieved rps,
                       p50/p95/p99, queue/compute split — see
                       benchmarks/README.md "Open-loop load testing"

Emits CSV lines via bench_lib and writes ``BENCH_serve.json`` next to
this file (``BENCH_serve_full.json`` under ``--full``, so paper-size
runs never clobber the smoke-geometry baseline the CI gate diffs).
Run:  PYTHONPATH=src python benchmarks/serve_bench.py --smoke
"""

from __future__ import annotations

import time

try:                       # `python benchmarks/serve_bench.py` (script) …
    import bench_lib
except ImportError:        # … or `from benchmarks import serve_bench`
    from benchmarks import bench_lib


def run(smoke: bool = True, model: str = "vgg9", requests: int = 24,
        max_batch: int = 8, out: str | None = None,
        metrics: str | None = None,
        metrics_port: int | None = None,
        openloop_rps: float = 40.0) -> str:
    import jax
    import numpy as np

    from repro import obs
    from repro.deploy import (
        SNNEngineConfig, SNNRequest, SNNServeEngine, deploy, deploy_config,
    )
    from repro.models import snn_cnn

    # --metrics/--metrics-port turn the live plane on for the bench run
    # itself (watch a bench from a browser tab); without them the
    # default registry stays disabled and the engines keep their no-op
    # instruments — the timings the gate diffs are unchanged either way.
    live = metrics is not None or metrics_port is not None
    registry = obs.enable_default() if live else obs.default_registry()
    server = None
    if metrics_port is not None:
        server = obs.ObsServer(registry, port=metrics_port)
        print(f"[obs] serving http://127.0.0.1:{server.start()}/metrics")

    bench_lib.reset_records()      # suites must not inherit stale records
    print("name,us_per_call,derived")
    for bits in (2, 4, 8):
        cfg = deploy_config(model, bits, smoke=smoke)
        params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
        images = np.asarray(
            np.random.default_rng(0).random(
                (4, cfg.img_size, cfg.img_size, cfg.in_channels)),
            np.float32)

        t0 = time.perf_counter()
        packed = deploy(params, cfg)
        jax.block_until_ready([lp.qt.data for lp in packed.layers.values()])
        deploy_ms = (time.perf_counter() - t0) * 1e3

        percall = jax.jit(
            lambda p, x, cfg=cfg: snn_cnn.apply(p, cfg, x))
        packaged = jax.jit(
            lambda m, x: m.apply(x))
        us_percall = bench_lib.time_call(percall, params, images)
        us_packaged = bench_lib.time_call(packaged, packed, images)
        bench_lib.emit(
            f"snn_forward/{model}/w{bits}/percall", us_percall,
            f"bits={bits};layers={len(packed.layers)}")
        bench_lib.emit(
            f"snn_forward/{model}/w{bits}/packaged", us_packaged,
            f"bits={bits};deploy_ms={deploy_ms:.1f}"
            f";speedup={us_percall / max(us_packaged, 1e-9):.2f}x"
            f";packed_mb={packed.nbytes_packed() / 1e6:.3f}"
            f";compression={packed.compression_ratio():.1f}x")

        # mixed-size request stream through the bucket-cached engine
        eng = SNNServeEngine(packed, SNNEngineConfig(max_batch=max_batch))
        # default-threshold watchdog: zero trips is part of the bench
        # record (a healthy run must not burn its SLO) — with the
        # registry disabled no rule ever finds an instrument and the
        # count stays 0 for free
        wdog = obs.Watchdog(registry)
        eng.attach_watchdog(wdog)
        eng.warmup()
        warm_compiles = eng.compile_count
        rng = np.random.default_rng(bits)
        uid = 0
        t0 = time.perf_counter()
        while uid < requests:
            burst = int(rng.integers(1, max_batch + 1))
            for _ in range(min(burst, requests - uid)):
                eng.add_request(SNNRequest(
                    uid=uid,
                    image=rng.random((cfg.img_size, cfg.img_size,
                                      cfg.in_channels)).astype(np.float32)))
                uid += 1
            eng.step()
        stats = eng.run_until_done(max_steps=requests)
        wall = time.perf_counter() - t0
        recompiles = eng.compile_count - warm_compiles
        assert recompiles == 0, f"recompiled after warmup: {recompiles}"
        bench_lib.emit(
            f"snn_serve/{model}/w{bits}", 1e6 * wall / stats["requests"],
            f"bits={bits};images_per_s={stats['requests'] / wall:.1f}"
            f";batches={stats['batches']};compiles={stats['compiles']}"
            f";recompiles_after_warmup={recompiles}"
            f";latency_p50_ms={stats['latency_p50_ms']:.2f}"
            f";latency_p95_ms={stats['latency_p95_ms']:.2f}"
            # informational split (not structural — see gate.py): where
            # request latency goes and how much compute padding burns
            f";queue_avg_ms={stats['queue_avg_ms']:.2f}"
            f";compute_avg_ms={stats['compute_avg_ms']:.2f}"
            f";padding_waste={stats['padding_waste']:.3f}"
            # live-plane health (informational): a healthy bench run
            # must not trip the SLO/drift watchdog or overflow the ring
            f";watchdog_trips={wdog.trips_total}"
            f";span_drops={registry.span_stats()['dropped']}")

    # -- open-loop Poisson comparison (W4 only — one load point) -------------
    # Closed-loop records above measure the engine at its own pace; the
    # open-loop pair offers the SAME seeded Poisson arrival process to
    # the synchronous engine and the async continuous-batching tier and
    # reports offered vs achieved throughput SEPARATELY (equal only when
    # the tier kept up).  All load-dependent keys (offered/achieved rps,
    # percentiles, queue/compute split, timeouts) are informational —
    # the gate diffs only `bits` and `recompiles_after_warmup` here
    # (see gate.py STRUCTURAL_KEYS): batch count under open-loop
    # arrivals depends on timing, so `batches`/`compiles` are
    # deliberately absent from these records.
    from repro.serve_async import (
        AsyncEngineConfig, AsyncSNNServeEngine, poisson_schedule,
        run_open_loop_async, run_open_loop_sync,
    )

    bits = 4
    cfg = deploy_config(model, bits, smoke=smoke)
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    packed = deploy(params, cfg)
    images = np.asarray(
        np.random.default_rng(1).random(
            (8, cfg.img_size, cfg.img_size, cfg.in_channels)), np.float32)
    schedule = poisson_schedule(openloop_rps, requests, seed=0)

    reports = {}
    for mode in ("sync", "async"):
        eng = SNNServeEngine(packed, SNNEngineConfig(max_batch=max_batch))
        eng.warmup()
        warm = eng.compile_count
        if mode == "sync":
            reports[mode] = run_open_loop_sync(eng, images, schedule)
            eng.close()
        else:
            aeng = AsyncSNNServeEngine(eng, AsyncEngineConfig(workers=1))
            aeng.start()
            reports[mode] = run_open_loop_async(aeng, images, schedule)
            aeng.close()
        recompiles = eng.compile_count - warm
        assert recompiles == 0, f"recompiled under load: {recompiles}"
        rep = reports[mode]
        bench_lib.emit(
            f"snn_serve_openloop/{model}/w{bits}/{mode}",
            1e6 * rep.wall_s / max(rep.completed, 1),
            f"bits={bits};recompiles_after_warmup={recompiles}"
            f";offered_rps={rep.offered_rps:.1f}"
            f";achieved_rps={rep.achieved_rps:.1f}"
            f";completed={rep.completed};timeouts={rep.timeouts}"
            f";latency_p50_ms={rep.latency_p50_ms:.2f}"
            f";latency_p95_ms={rep.latency_p95_ms:.2f}"
            f";latency_p99_ms={rep.latency_p99_ms:.2f}"
            f";queue_avg_ms={rep.queue_avg_ms:.2f}"
            f";compute_avg_ms={rep.compute_avg_ms:.2f}")

    if metrics is not None:
        path = obs.write_jsonl(registry, metrics,
                               meta={"entry": "serve_bench",
                                     "model": model})
        trace = obs.export_chrome_trace(
            registry, metrics + ".trace.json",
            meta={"entry": "serve_bench", "model": model})
        print(f"[obs] metrics written to {path}, Chrome trace to {trace}")
    if server is not None:
        server.stop()
    return bench_lib.write_json("serve" if smoke else "serve_full",
                                path=out)


def main():
    import argparse

    from repro.configs import add_geometry_flags

    ap = argparse.ArgumentParser(description=__doc__)
    add_geometry_flags(ap)
    ap.add_argument("--model", default="vgg9",
                    choices=("vgg9", "vgg16", "resnet18"))
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--rate", type=float, default=40.0,
                    help="offered load (req/s) for the open-loop "
                         "sync-vs-async comparison records")
    ap.add_argument("--out", default=None,
                    help="write BENCH json here instead of the committed "
                         "baseline path (what the CI gate leg does)")
    from repro.obs import add_metrics_flag, add_server_flag

    add_metrics_flag(ap, "/tmp/repro_metrics/serve_bench.jsonl")
    add_server_flag(ap)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(smoke=args.smoke, model=args.model, requests=args.requests,
        max_batch=args.max_batch, out=args.out, metrics=args.metrics,
        metrics_port=args.metrics_port, openloop_rps=args.rate)


if __name__ == "__main__":
    main()
