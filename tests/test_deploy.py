"""Deployment runtime: one-shot packing, artifact roundtrip, serve engine.

The contract under test (ISSUE 4 acceptance criteria):

  * the packed forward (``deploy(params, cfg)`` -> ``DeployedModel.apply``)
    is bit-exact with the per-call ``int_deploy`` forward across
    INT2/INT4/INT8 and both model families;
  * the save/load npz roundtrip is bit-exact with the in-memory package;
  * ``SNNServeEngine`` compiles exactly once per batch bucket and serves
    a mixed-size request stream with ZERO recompiles after warmup;
  * (ISSUE 7) every served request carries the latency SPLIT
    (``queue_s`` + ``compute_s`` <= ``latency_s``), ``stats()`` reports
    padding waste exactly, and an enabled metrics registry sees the
    full enqueue -> fill -> dispatch -> wait -> drain trace while a
    disabled one costs the engine nothing but no-op calls.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.deploy import (
    SNNEngineConfig,
    SNNRequest,
    SNNServeEngine,
    deploy,
    load,
)
from repro.models import snn_cnn
from repro.quant.formats import PrecisionConfig


def int_cfg(model="vgg9", bits=4, timesteps=3):
    return snn_cnn.SNNConfig(
        model=model, img_size=16, timesteps=timesteps, scale=0.15,
        n_classes=4, int_deploy=True, precision=PrecisionConfig(bits=bits))


def make_images(cfg, n=2, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.random(
        (n, cfg.img_size, cfg.img_size, cfg.in_channels)), jnp.float32)


# ---------------------------------------------------------------------------
# package: bit-exactness vs the per-call path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4, 8])
def test_packaged_forward_bit_exact_vgg(bits):
    cfg = int_cfg("vgg9", bits)
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    images = make_images(cfg)
    ref = snn_cnn.apply(params, cfg, images)          # re-quantizes per call
    model = deploy(params, cfg)
    out = model.apply(images)                          # zero quantization
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("bits", [2, 8])
def test_packaged_forward_bit_exact_resnet(bits):
    """Covers strides, 1x1 projection shortcuts, and the OR merge."""
    cfg = int_cfg("resnet18", bits, timesteps=2)
    params = snn_cnn.init(jax.random.PRNGKey(1), cfg)
    images = make_images(cfg, n=1, seed=1)
    ref = snn_cnn.apply(params, cfg, images)
    out = deploy(params, cfg).apply(images)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_packaged_spike_rates_match_percall():
    """Not just the logits: every spiking layer's firing rates agree."""
    cfg = int_cfg("vgg9", 4)
    params = snn_cnn.init(jax.random.PRNGKey(2), cfg)
    images = make_images(cfg, seed=2)
    _, ref_rates = snn_cnn.apply_with_rates(params, cfg, images)
    _, pkg_rates = deploy(params, cfg).apply_with_rates(images)
    assert pkg_rates == ref_rates


def test_packaged_forward_folds_calibrated_gain():
    cfg = int_cfg("vgg9", 4)
    params = snn_cnn.init(jax.random.PRNGKey(3), cfg)
    params = snn_cnn.calibrate(params, cfg, make_images(cfg, seed=3))
    images = make_images(cfg, seed=4)
    ref = snn_cnn.apply(params, cfg, images)
    out = deploy(params, cfg).apply(images)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_package_compression_and_layer_walk():
    cfg = int_cfg("vgg9", 2)
    model = deploy(snn_cnn.init(jax.random.PRNGKey(0), cfg), cfg)
    # post-stem convs + fc1 are packed; stem + head stay float
    assert "fc1" in model.layers
    assert "convs.0" not in model.layers
    assert set(model.float_params) == {"convs", "head"}
    assert model.compression_ratio() > 4.0  # 2-bit weights ≪ fp32
    assert model.nbytes_packed() < model.nbytes_dense_fp32()


def test_deploy_rejects_float_cfg():
    cfg = snn_cnn.SNNConfig(model="vgg9", img_size=16, timesteps=2,
                            scale=0.15, n_classes=4,
                            precision=PrecisionConfig(bits=16))
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="integer datapath"):
        deploy(params, cfg)
    with pytest.raises(ValueError, match="integer path"):
        snn_cnn.apply(params, cfg, make_images(cfg), package=object())


def test_deployed_model_is_jit_transparent():
    """The package rides through jit as a pytree argument (the property
    the engine's bucket cache relies on)."""
    cfg = int_cfg("vgg9", 4)
    model = deploy(snn_cnn.init(jax.random.PRNGKey(0), cfg), cfg)
    images = make_images(cfg)
    jitted = jax.jit(lambda m, x: m.apply(x))
    np.testing.assert_array_equal(np.asarray(jitted(model, images)),
                                  np.asarray(model.apply(images)))


# ---------------------------------------------------------------------------
# artifact roundtrip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model_name", ["vgg9", "resnet18"])
def test_save_load_roundtrip_bit_exact(tmp_path, model_name):
    cfg = int_cfg(model_name, 4, timesteps=2)
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    model = deploy(params, cfg)
    path = model.save(os.fspath(tmp_path / "model.npz"))
    loaded = load(path)

    assert loaded.cfg == model.cfg
    assert set(loaded.layers) == set(model.layers)
    for name, lp in model.layers.items():
        lq = loaded.layers[name]
        assert (lq.kind, lq.stride, lq.qt.bits) == (lp.kind, lp.stride,
                                                    lp.qt.bits)
        np.testing.assert_array_equal(np.asarray(lq.qt.data),
                                      np.asarray(lp.qt.data))
        np.testing.assert_array_equal(np.asarray(lq.qt.scale),
                                      np.asarray(lp.qt.scale))
        np.testing.assert_array_equal(np.asarray(lq.theta_q),
                                      np.asarray(lp.theta_q))

    images = make_images(cfg)
    np.testing.assert_array_equal(np.asarray(loaded.apply(images)),
                                  np.asarray(model.apply(images)))


def test_load_rejects_future_format(tmp_path):
    import json

    cfg = int_cfg("vgg9", 4, timesteps=2)
    model = deploy(snn_cnn.init(jax.random.PRNGKey(0), cfg), cfg)
    path = model.save(os.fspath(tmp_path / "model.npz"))
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    manifest = json.loads(str(arrays["__manifest__"][()]))
    manifest["version"] = 999
    arrays["__manifest__"] = np.array(json.dumps(manifest))
    with open(path, "wb") as f:
        np.savez(f, **arrays)
    with pytest.raises(ValueError, match="format v999"):
        load(path)


# ---------------------------------------------------------------------------
# serve engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def packed_model():
    cfg = int_cfg("vgg9", 4, timesteps=2)
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    return deploy(params, cfg)


def test_engine_compiles_once_per_bucket(packed_model):
    ecfg = SNNEngineConfig(max_batch=4, buckets=(2, 4))
    eng = SNNServeEngine(packed_model, ecfg)
    assert eng.buckets == (2, 4)
    assert eng.warmup() == 2
    assert eng.compile_count == 2

    # mixed-size stream: bursts of 1..4 requests, ZERO recompiles
    cfg = packed_model.cfg
    rng = np.random.default_rng(0)
    uid = 0
    for burst in (1, 3, 4, 2, 1):
        for _ in range(burst):
            eng.add_request(SNNRequest(
                uid=uid, image=rng.random(
                    (cfg.img_size, cfg.img_size, cfg.in_channels)
                ).astype(np.float32)))
            uid += 1
        eng.step()
    stats = eng.run_until_done()
    assert stats["requests"] == uid
    assert stats["compiles"] == 2
    assert eng.compile_count == 2
    assert set(stats["buckets"]) <= {"2", "4"}


def test_engine_padded_batch_matches_direct_forward(packed_model):
    """A single request padded up to a bucket must score exactly like an
    unpadded direct forward of that image (pad rows never leak)."""
    cfg = packed_model.cfg
    rng = np.random.default_rng(1)
    img = rng.random((cfg.img_size, cfg.img_size,
                      cfg.in_channels)).astype(np.float32)
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=4,
                                                       buckets=(4,)))
    eng.add_request(SNNRequest(uid=0, image=img))
    assert eng.step() == 1
    direct = np.asarray(packed_model.apply(jnp.asarray(img[None])))[0]
    np.testing.assert_allclose(eng.done[0].logits, direct,
                               rtol=1e-5, atol=1e-6)
    assert eng.done[0].pred == int(np.argmax(direct))
    assert eng.done[0].latency_s >= eng.done[0].compute_s >= 0.0


def test_engine_rejects_bad_shapes_and_float_cfg(packed_model):
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=2))
    with pytest.raises(ValueError, match="image shape"):
        eng.add_request(SNNRequest(uid=0, image=np.zeros((8, 8, 3),
                                                         np.float32)))
    float_model = dataclasses.replace(
        packed_model,
        cfg=dataclasses.replace(packed_model.cfg, int_deploy=False))
    with pytest.raises(ValueError, match="packed integer"):
        SNNServeEngine(float_model, SNNEngineConfig())


def test_engine_bucket_resolution():
    ecfg = SNNEngineConfig(max_batch=8)
    assert ecfg.resolved_buckets() == (1, 2, 4, 8)
    assert ecfg.resolved_buckets(n_dev=4) == (4, 8)
    assert SNNEngineConfig(max_batch=6).resolved_buckets() == (1, 2, 4, 6)
    assert SNNEngineConfig(buckets=(3, 5)).resolved_buckets(2) == (4, 6)


def test_engine_run_until_done_raises_on_truncation(packed_model):
    """Exhausting max_steps with requests still queued must raise, not
    return stats that silently cover only the served prefix."""
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=1,
                                                       buckets=(1,)))
    rng = np.random.default_rng(3)
    for uid in range(3):
        eng.add_request(SNNRequest(
            uid=uid, image=rng.random(
                (cfg.img_size, cfg.img_size, cfg.in_channels)
            ).astype(np.float32)))
    with pytest.raises(RuntimeError, match="still queued"):
        eng.run_until_done(max_steps=1)
    # the drained remainder completes normally
    stats = eng.run_until_done()
    assert stats["requests"] == 3


def test_engine_latency_survives_wall_clock_step(packed_model, monkeypatch):
    """Latency accounting must come from a monotonic clock: a simulated
    wall-clock step (NTP slew / DST) between enqueue and completion must
    not produce negative or hour-scale latencies."""
    import time as time_mod

    wall = iter([1e9, 1e9 - 3600.0, 1e9 - 7200.0])  # clock stepping BACK

    def jumping_wall_clock():
        try:
            return next(wall)
        except StopIteration:
            return 1e9 - 7200.0

    monkeypatch.setattr(time_mod, "time", jumping_wall_clock)
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=2,
                                                       buckets=(2,)))
    rng = np.random.default_rng(4)
    for uid in range(2):
        eng.add_request(SNNRequest(
            uid=uid, image=rng.random(
                (cfg.img_size, cfg.img_size, cfg.in_channels)
            ).astype(np.float32)))
    stats = eng.run_until_done()
    assert stats["requests"] == 2
    for req in eng.done.values():
        assert 0.0 <= req.latency_s < 60.0
        assert 0.0 <= req.compute_s <= req.latency_s
    assert 0.0 < stats["latency_max_ms"] < 60_000.0
    assert stats["latency_p50_ms"] >= 0.0


def test_engine_stats_accounting(packed_model):
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=2,
                                                       buckets=(2,)))
    rng = np.random.default_rng(2)
    for uid in range(5):
        eng.add_request(SNNRequest(
            uid=uid, image=rng.random(
                (cfg.img_size, cfg.img_size, cfg.in_channels)
            ).astype(np.float32)))
    stats = eng.run_until_done()
    assert stats["requests"] == 5
    assert stats["batches"] == 3          # 2 + 2 + 1
    assert stats["buckets"] == {"2": 3}
    assert stats["images_per_s"] > 0
    assert stats["latency_p95_ms"] >= stats["latency_p50_ms"] > 0
    assert stats["packed_mbytes"] > 0
    assert stats["compression_x"] > 1
    # served inputs are dropped; pop_result drains the results dict
    req = eng.pop_result(0)
    assert req.image is None and req.logits is not None
    assert 0 not in eng.done and len(eng.done) == 4
    # counts/throughput/avg/max come from running totals: draining every
    # result must not zero the serving stats
    for uid in range(1, 5):
        eng.pop_result(uid)
    drained = eng.stats()
    assert drained["requests"] == 5
    assert drained["images_per_s"] > 0
    assert drained["latency_avg_ms"] > 0
    assert drained["latency_max_ms"] >= stats["latency_p95_ms"]

def test_engine_close_drains_partial_bucket(packed_model):
    """Graceful shutdown: close(drain=True) flushes whatever is queued —
    including a partial bucket — then refuses new work.  Idempotent."""
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=4,
                                                       buckets=(4,)))
    rng = np.random.default_rng(11)
    for uid in range(3):                   # 3 < bucket: a partial batch
        eng.add_request(SNNRequest(
            uid=uid, image=rng.random(
                (cfg.img_size, cfg.img_size, cfg.in_channels)
            ).astype(np.float32)))
    stats = eng.close()
    assert stats["requests"] == 3
    assert len(eng.queue) == 0
    assert all(eng.pop_result(uid).logits is not None for uid in range(3))
    assert eng.health()["closed"] is True
    with pytest.raises(RuntimeError, match="closed"):
        eng.add_request(SNNRequest(uid=9, image=rng.random(
            (cfg.img_size, cfg.img_size, cfg.in_channels)
        ).astype(np.float32)))
    assert eng.close()["requests"] == 3    # second close: no-op


def test_engine_context_manager_drains_on_clean_exit(packed_model):
    cfg = packed_model.cfg
    rng = np.random.default_rng(12)
    with SNNServeEngine(packed_model,
                        SNNEngineConfig(max_batch=2,
                                        buckets=(2,))) as eng:
        eng.add_request(SNNRequest(
            uid=0, image=rng.random(
                (cfg.img_size, cfg.img_size, cfg.in_channels)
            ).astype(np.float32)))
    assert eng.total_requests == 1         # drained at __exit__
    # an exception path must NOT spend time serving the backlog
    with pytest.raises(RuntimeError, match="boom"):
        with SNNServeEngine(packed_model,
                            SNNEngineConfig(max_batch=2,
                                            buckets=(2,))) as eng2:
            eng2.add_request(SNNRequest(
                uid=0, image=rng.random(
                    (cfg.img_size, cfg.img_size, cfg.in_channels)
                ).astype(np.float32)))
            raise RuntimeError("boom")
    assert eng2.total_requests == 0 and len(eng2.queue) == 0


# ---------------------------------------------------------------------------
# observability: latency split, padding waste, metrics integration
# ---------------------------------------------------------------------------

def _queue_requests(eng, cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    for uid in range(n):
        eng.add_request(SNNRequest(
            uid=uid, image=rng.random(
                (cfg.img_size, cfg.img_size, cfg.in_channels)
            ).astype(np.float32)))


def test_engine_latency_split(packed_model):
    """queue_s (enqueue -> admit) and compute_s (batched forward) are
    disjoint sub-intervals of latency_s — the split can never exceed the
    whole."""
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=2,
                                                       buckets=(2,)))
    _queue_requests(eng, cfg, 5, seed=5)
    stats = eng.run_until_done()
    assert len(eng.done) == 5
    for req in eng.done.values():
        assert req.queue_s >= 0.0
        assert req.compute_s > 0.0
        assert req.latency_s >= req.queue_s + req.compute_s
    assert stats["queue_avg_ms"] >= 0.0
    assert stats["compute_avg_ms"] > 0.0
    assert stats["latency_avg_ms"] >= (stats["queue_avg_ms"]
                                       + stats["compute_avg_ms"])
    assert stats["queue_p95_ms"] >= 0.0


def test_engine_padding_waste_exact(packed_model):
    """5 requests into (4,)-bucketed batches: 4 + 1 -> two batches of 4
    slots, 3 of them padding -> waste = 3/8 exactly."""
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=4,
                                                       buckets=(4,)))
    _queue_requests(eng, cfg, 5, seed=6)
    stats = eng.run_until_done()
    assert stats["batches"] == 2
    assert eng.total_slots == 8
    assert eng.total_padded_slots == 3
    assert stats["padding_waste"] == pytest.approx(3 / 8)
    # a full stream of exact-bucket batches wastes nothing
    eng2 = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=2,
                                                        buckets=(2,)))
    _queue_requests(eng2, cfg, 4, seed=7)
    assert eng2.run_until_done()["padding_waste"] == 0.0


def test_engine_metrics_integration(packed_model):
    """With an explicit enabled registry the engine emits the full
    request trace; counters/histograms reconcile with stats()."""
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=4, buckets=(2, 4)),
                         registry=reg)
    eng.warmup()
    _queue_requests(eng, cfg, 6, seed=8)
    stats = eng.run_until_done()   # 4 + 2: both buckets exercised

    assert reg.counter("snn_serve_requests_total").value == 6
    assert reg.counter("snn_serve_batches_total").value == stats["batches"]
    miss = reg.counter("snn_serve_compile_total",
                       labels={"result": "miss"})
    hit = reg.counter("snn_serve_compile_total", labels={"result": "hit"})
    assert miss.value == eng.compile_count == 2
    assert hit.value == stats["batches"]   # every step after warmup hits
    assert reg.gauge("snn_serve_queue_depth").value == 0.0

    from repro.obs import LATENCY_EDGES_US
    assert reg.histogram("snn_serve_queue_us", LATENCY_EDGES_US).count == 6
    assert reg.histogram("snn_serve_latency_us",
                         LATENCY_EDGES_US).count == 6
    assert reg.histogram("snn_serve_compute_us",
                         LATENCY_EDGES_US).count == stats["batches"]

    events = [ev["event"] for ev in reg.spans()]
    assert events.count("enqueue") == 6
    assert events.count("drain") == 6
    for phase in ("take", "fill", "dispatch", "wait", "readback",
                  "resolve"):
        assert events.count(phase) == stats["batches"], phase
    assert events.count("compile") == 2    # warmup misses only
    # the trace is ordered: a forward's fill precedes its dispatch,
    # which precedes its requests' drains
    assert events.index("enqueue") < events.index("fill") \
        < events.index("dispatch") < events.index("drain")
    # drain spans carry the split in microseconds
    drain = [ev for ev in reg.spans() if ev["event"] == "drain"]
    for ev in drain:
        assert ev["latency_us"] >= ev["queue_us"] + ev["compute_us"] > 0.0


def test_engine_disabled_registry_is_noop(packed_model):
    """Without opt-in the engine binds the shared no-op instrument and
    records nothing — the overhead contract the serve bench gate relies
    on."""
    from repro.obs import NULL_INSTRUMENT, MetricsRegistry

    reg = MetricsRegistry(enabled=False)
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=2,
                                                       buckets=(2,)),
                         registry=reg)
    assert eng._m_requests is NULL_INSTRUMENT
    assert eng._m_latency_us is NULL_INSTRUMENT
    _queue_requests(eng, cfg, 2, seed=9)
    stats = eng.run_until_done()
    assert stats["requests"] == 2          # stats() still fully works
    assert reg.metrics() == [] and reg.spans() == []
