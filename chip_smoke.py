#!/usr/bin/env python3
"""Smoke check of the packed spiking serve path on a TPU.

Run from the root of a checkout, in ONE process (the chip belongs to
one process at a time):

    python chip_smoke.py              # one chip: kernels + serve phases
    python chip_smoke.py --chips 4    # four chips: the data-parallel
                                      # engine vs one device, nothing else

Phases (one chip):
  device   JAX sees a TPU and the kernel backend resolves to ``pallas``.
  kernels  fused_conv (stride 1 and 2, c_out > 128), fused_nce and one
           fused_group chain at paper widths, W2/W4/W8, each compared bit
           for bit with its jnp reference on the same chip.
  serve    vgg16 W4 (fusion off and auto) and resnet18 W4 at paper width
           (random weights from a seed, thresholds balanced on seeded
           images), built through deploy_config() and deploy(): 16 requests through SNNServeEngine and the same 16
           through AsyncSNNServeEngine.  Logits must equal the jnp-backend
           engine's on the chip, and no bucket may compile after warmup.

A VMEM-budget fallback to the reference path is an error here, not a
warning.  The images/s, p50 latency and compile seconds printed are
smoke numbers from one short run, not benchmark results.  The last line
of stdout is ``{"ok": true, "device": {...}}``; any failure exits
non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
N_REQUESTS = 16
T_KERNEL, B_KERNEL = 4, 8


def fail(reason: str) -> None:
    print(f"chip_smoke: FAIL: {reason}", file=sys.stderr)
    sys.exit(1)


def check(cond: bool, reason: str) -> None:
    if not cond:
        fail(reason)


def same(a, b, what: str) -> None:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    check(a.shape == b.shape, f"{what}: shape {a.shape} != {b.shape}")
    bad = int(np.sum(a != b))
    check(bad == 0, f"{what}: {bad} of {a.size} values differ from the "
                    f"jnp reference")


KERNEL_CALL = "tpu_custom_call"


def kernel_calls(exe) -> bool:
    """Whether a compiled executable runs a Pallas (Mosaic) kernel."""
    return KERNEL_CALL in exe.as_text()


# -- kernels ----------------------------------------------------------------

def phase_kernels() -> None:
    import jax

    from repro.core import packing
    from repro.kernels import fused_conv_ops, fused_group_ops, fused_nce_ops
    from repro.kernels import use_backend
    from repro.quant import PrecisionConfig, quantize, quantize_conv

    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 64))

    def spikes(*shape):
        s = jax.random.uniform(next(keys), (T_KERNEL, B_KERNEL) + shape) < 0.2
        return packing.pack_bool(s)

    def both(name, fn, *args):
        """Run ``fn`` on the pallas and jnp backends; compare bit for bit.

        The backend is read only while tracing, so each side gets its own
        function object (jit caches traces by function identity) and its
        executable is checked for the Pallas kernel call, or its absence.
        """
        outs = {}
        for be in ("pallas", "jnp"):
            with use_backend(be):
                exe = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
            check(kernel_calls(exe) == (be == "pallas"),
                  f"{name}: the {be} executable "
                  f"{'lacks' if be == 'pallas' else 'holds'} a "
                  f"{KERNEL_CALL}")
            outs[be] = jax.block_until_ready(exe(*args))
        for i, (k, r) in enumerate(zip(outs["pallas"], outs["jnp"])):
            same(k, r, f"{name} output {i}")
        print(f"  kernel {name}: bit-exact vs jnp (pallas executable holds "
              f"{KERNEL_CALL}, jnp one none)")

    for bits in (2, 4, 8):
        pc = PrecisionConfig(bits=bits)
        for c_in, c_out, hw, stride in ((128, 256, 16, 1), (128, 256, 16, 2)):
            w = jax.random.normal(next(keys), (3, 3, c_in, c_out))
            qct, sp = quantize_conv(w, pc), spikes(hw, hw, c_in)
            both(f"fused_conv {c_in}->{c_out} {hw}x{hw} s{stride} W{bits}",
                 lambda sp, qct, stride=stride:
                 fused_conv_ops.fused_conv_rollout(
                     sp, qct, stride=stride, leak_shift=3, threshold_q=48),
                 sp, qct)
        for d_in in (512, 2048):
            qt = quantize(jax.random.normal(next(keys), (512, d_in)), pc)
            sp = spikes(d_in)
            both(f"fused_nce {d_in}->512 W{bits}",
                 lambda sp, qt, d_in=d_in:
                 fused_nce_ops.fused_nce_rollout(
                     sp, qt, d_in=d_in, leak_shift=3, threshold_q=64),
                 sp, qt)
        # vgg16's first fused chain at 32x32: conv, pool, conv, conv
        qcts = [quantize_conv(jax.random.normal(next(keys), (3, 3, ci, co)),
                              pc)
                for ci, co in ((64, 64), (64, 128), (128, 128))]

        def group(sp, qcts):
            a, b, c = (("conv", q, 48) for q in qcts)
            return fused_group_ops.fused_group_rollout(
                sp, (a, ("pool", 2), b, c), leak_shift=3)

        both(f"fused_group 64@32,pool,128,128 W{bits}", group,
             spikes(32, 32, 64), qcts)


# -- serve ------------------------------------------------------------------

def _package(model: str, fusion):
    import jax

    from repro.deploy import deploy, deploy_config
    from repro.models import snn_cnn

    cfg = deploy_config(model, bits=4, smoke=False, fusion=fusion)
    params = snn_cnn.init(jax.random.PRNGKey(SEED), cfg)
    # threshold balancing on seeded images keeps every layer firing, so
    # the bit-exact comparisons cover live spike trains, not silence
    params = snn_cnn.calibrate(params, cfg, _images(cfg, 8))
    return cfg, deploy(params, cfg)


def _images(cfg, n: int):
    import numpy as np

    rng = np.random.default_rng(SEED)
    return rng.random((n, cfg.img_size, cfg.img_size, cfg.in_channels),
                      dtype=np.float32)


def _serve_sync(pkg, images, ecfg):
    from repro.deploy import SNNRequest, SNNServeEngine

    eng = SNNServeEngine(pkg, ecfg)
    t0 = time.perf_counter()
    n_warm = eng.warmup()
    compile_s = time.perf_counter() - t0
    for i, im in enumerate(images):
        eng.add_request(SNNRequest(uid=i, image=im))
    t0 = time.perf_counter()
    stats = eng.run_until_done()
    wall = time.perf_counter() - t0
    logits = [eng.pop_result(i).logits for i in range(len(images))]
    return eng, n_warm, compile_s, wall, stats, logits


def phase_serve() -> None:
    import numpy as np

    from repro.deploy import SNNEngineConfig
    from repro.kernels import use_backend
    from repro.serve_async import AsyncSNNServeEngine

    # one bucket: sync, async and reference batches all run the same
    # compiled shape, whatever cohort sizes the async tier forms
    ecfg = SNNEngineConfig(max_batch=8, buckets=(8,))
    for model, fusion in (("vgg16", ()), ("vgg16", "auto"),
                          ("resnet18", ())):
        name = f"{model} W4 fusion={'auto' if fusion else 'off'}"
        cfg, pkg = _package(model, fusion)
        images = _images(cfg, N_REQUESTS)

        eng, n_warm, compile_s, wall, stats, logits = _serve_sync(
            pkg, images, ecfg)
        with use_backend("jnp"):
            ref_eng, *_, ref = _serve_sync(pkg, images, ecfg)
        # the two engines really compiled different paths
        check(all(map(kernel_calls, eng._compiled.values())),
              f"{name}: a pallas-engine bucket has no {KERNEL_CALL}")
        check(not any(map(kernel_calls, ref_eng._compiled.values())),
              f"{name}: a jnp-engine bucket holds a {KERNEL_CALL}")
        for i in range(N_REQUESTS):
            same(logits[i], ref[i], f"{name} sync request {i} logits")
        lg = np.stack(logits)
        check(bool(np.all(np.isfinite(lg))), f"{name}: non-finite logits")
        check(float(np.ptp(lg)) > 0.0,
              f"{name}: every logit is equal — the comparison is vacuous")

        aeng = AsyncSNNServeEngine(eng).start()
        t0 = time.perf_counter()
        futs = [aeng.submit(im) for im in images]
        res = [f.result(timeout=600) for f in futs]
        async_wall = time.perf_counter() - t0
        astats = aeng.close()
        for i, r in enumerate(res):
            check(r.status == "ok", f"{name} async request {i}: {r.status}")
            same(r.logits, ref[i], f"{name} async request {i} logits")
        recompiles = eng.compile_count - n_warm
        check(recompiles == 0,
              f"{name}: {recompiles} recompiles after warmup")
        print(f"  serve {name}: sync + async logits bit-exact vs jnp "
              f"(pallas executable holds {KERNEL_CALL}, jnp one none), "
              f"recompiles_after_warmup=0")
        print(f"  smoke numbers (one short run, not a benchmark): {name}: "
              f"compile {compile_s:.2f} s for {n_warm} bucket(s); sync "
              f"{N_REQUESTS / wall:.1f} images/s, p50 "
              f"{stats['latency_p50_ms']:.2f} ms; async "
              f"{N_REQUESTS / async_wall:.1f} images/s, p50 "
              f"{astats['latency_p50_ms']:.2f} ms")


# -- four chips ----------------------------------------------------------------

def phase_data_parallel(n_dev: int) -> None:
    """vgg16 W4 served by the shard_map data-parallel engine across all
    local devices, against the same requests on one device."""
    from repro.deploy import SNNEngineConfig, SNNRequest

    cfg, pkg = _package("vgg16", ())
    images = _images(cfg, N_REQUESTS)
    dp_cfg = SNNEngineConfig(max_batch=N_REQUESTS, data_parallel=True)
    eng, n_warm, compile_s, wall, stats, logits = _serve_sync(
        pkg, images, dp_cfg)
    check(all(b % n_dev == 0 for b in eng.buckets),
          f"buckets {eng.buckets} are not resolved for {n_dev} devices")
    # the one-device comparison serves the same padded buckets
    one = _serve_sync(pkg, images, SNNEngineConfig(
        max_batch=N_REQUESTS, buckets=eng.buckets))[-1]
    for i in range(N_REQUESTS):
        same(logits[i], one[i], f"data-parallel request {i} logits")
    check(eng.compile_count == n_warm, "recompiles after warmup")
    check(all(map(kernel_calls, eng._compiled.values())),
          f"a data-parallel bucket has no {KERNEL_CALL}")

    # the batch must spread over every device, one slice each
    req = SNNRequest(uid=N_REQUESTS, image=images[0])
    req.queue_s, req._t0 = 0.0, time.perf_counter()
    st = eng.begin_step([req], bucket=eng.buckets[-1])
    shards = st.logits.addressable_shards
    devs = {s.device for s in shards}
    rows = sorted(s.data.shape[0] for s in shards)
    eng.finish_step(st)
    check(len(devs) == n_dev and rows == [eng.buckets[-1] // n_dev] * n_dev,
          f"bucket {eng.buckets[-1]} landed on {len(devs)} device(s) with "
          f"rows {rows}")
    print(f"  data-parallel vgg16 W4 over {n_dev} devices: buckets "
          f"{eng.buckets}, logits bit-exact vs one device, "
          f"{eng.buckets[-1] // n_dev} rows per device")
    print(f"  smoke numbers (one short run, not a benchmark): compile "
          f"{compile_s:.2f} s for {n_warm} bucket(s); "
          f"{N_REQUESTS / wall:.1f} images/s, p50 "
          f"{stats['latency_p50_ms']:.2f} ms")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data-parallel engine phase")
    args = ap.parse_args()

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        fail(f"no repository source next to this script ({src}/repro)")
    sys.path.insert(0, str(src))

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no usable device: {str(e).splitlines()[0]}")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU, JAX's first device is {dev.platform} "
             f"({dev.device_kind})")
    from repro.kernels import get_backend

    check(get_backend() == "pallas",
          f"kernel backend is {get_backend()!r}, not 'pallas'")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"backend pallas, compile cache {cache}")
    # a VMEM-budget fallback would serve the reference path silently
    warnings.filterwarnings("error", message=".*falling back",
                            category=RuntimeWarning)

    if args.chips == 4:
        phase_data_parallel(len(devices))
    else:
        phase_kernels()
        phase_serve()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
