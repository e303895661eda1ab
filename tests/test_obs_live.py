"""Live observability plane: HTTP server, Chrome trace, attribution,
watchdogs.

The contract under test (ISSUE 9):

  * ``ObsServer`` exposes /metrics (Prometheus text), /healthz
    (liveness + watchdog state) and /spans?since= (incremental drain)
    from a background thread, and a scrape landing mid-``step()`` never
    deadlocks or 500s;
  * the Chrome trace export renders the span ring as trace_event JSON —
    requests are flow-connected enqueue -> drain, worker phases and
    requests become duration events, and the export passes its own validator
    (and ``python -m repro.obs.validate --trace``);
  * ``AttributionExecutor`` leaves logits bit-exact while attributing
    blocked wall time per node and joining it against the roofline
    prediction (``snn_layer_time_us``, ``predicted_vs_measured``);
  * the watchdog trips on injected spike-rate drift and injected p95
    SLO burn, LATCHES (one trip per excursion), re-arms through the
    hysteresis band, dumps a flight-recorder artifact that validates,
    and stays silent on a healthy run.
"""

import json
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro import obs
from repro.obs.registry import MetricsRegistry
from repro.obs.watchdog import Watchdog, WatchdogConfig, histogram_quantile
from repro.perfmodel.roofline import V5E


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def _serve_registry() -> MetricsRegistry:
    """A registry shaped like a short engine run (synthetic spans with
    the real field names — the chrometrace golden input)."""
    reg = MetricsRegistry()
    reg.counter("snn_serve_requests_total", "req").inc(2)
    reg.gauge("snn_serve_queue_depth", "depth").set(0)
    reg.event("enqueue", uid=0, queue_depth=1)
    reg.event("enqueue", uid=1, queue_depth=2)
    t = reg._t0
    reg.phase("take", t + 0.0100, t + 0.0101, cohort=0, bucket=2, n=2)
    reg.phase("fill", t + 0.0101, t + 0.0102, cohort=0, bucket=2, n=2)
    reg.phase("compile", t + 0.0102, t + 0.0117, cohort=0, bucket=2, n=2)
    reg.phase("dispatch", t + 0.0117, t + 0.0119, cohort=0, bucket=2, n=2)
    reg.phase("wait", t + 0.0119, t + 0.0125, cohort=0, bucket=2, n=2)
    reg.event("drain", uid=0, queue_us=100.0, compute_us=800.0,
              latency_us=950.0)
    reg.event("drain", uid=1, queue_us=120.0, compute_us=800.0,
              latency_us=970.0)
    return reg


# ---------------------------------------------------------------------------
# HTTP server
# ---------------------------------------------------------------------------

def test_server_endpoints_and_shutdown():
    reg = _serve_registry()
    srv = obs.ObsServer(reg, port=0,
                        health_fn=lambda: {"queue_depth": 3})
    port = srv.start()
    assert port > 0
    base = f"http://127.0.0.1:{port}"

    status, ctype, body = _get(base + "/metrics")
    assert status == 200 and ctype == obs.PROMETHEUS_CONTENT_TYPE
    text = body.decode()
    assert "snn_serve_requests_total 2.0" in text
    assert "# TYPE snn_serve_queue_depth gauge" in text

    status, ctype, body = _get(base + "/healthz")
    hz = json.loads(body)
    assert status == 200 and "application/json" in ctype
    assert hz["status"] == "ok" and hz["queue_depth"] == 3
    assert hz["spans"]["appended"] == 9

    # incremental drain: cursor in, cursor out
    _, _, body = _get(base + "/spans?since=0")
    page = json.loads(body)
    assert [ev["event"] for ev in page["spans"]][:3] == \
        ["enqueue", "enqueue", "take"]
    cursor = page["next_since"]
    _, _, body = _get(base + f"/spans?since={cursor}")
    assert json.loads(body)["spans"] == []
    reg.event("drain", uid=2, latency_us=1.0)
    _, _, body = _get(base + f"/spans?since={cursor}")
    assert [ev["event"] for ev in json.loads(body)["spans"]] == ["drain"]

    with pytest.raises(urllib.error.HTTPError) as e:
        _get(base + "/spans?since=abc")
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(base + "/nope")
    assert e.value.code == 404

    srv.stop()
    with pytest.raises(Exception):
        _get(base + "/metrics")


def test_healthz_degrades_on_health_fn_failure_and_watchdog_trips():
    reg = MetricsRegistry()

    def boom():
        raise RuntimeError("engine fell over")

    srv = obs.ObsServer(reg, port=0, health_fn=boom)
    base = f"http://127.0.0.1:{srv.start()}"
    hz = json.loads(_get(base + "/healthz")[2])
    assert hz["status"] == "degraded" \
        and "engine fell over" in hz["health_error"]
    srv.stop()

    srv = obs.ObsServer(
        reg, port=0,
        health_fn=lambda: {"watchdog": {"trips_total": 2}})
    base = f"http://127.0.0.1:{srv.start()}"
    hz = json.loads(_get(base + "/healthz")[2])
    assert hz["status"] == "tripped"
    srv.stop()


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

def test_chrome_trace_golden_synthetic_spans(tmp_path):
    reg = _serve_registry()
    doc = obs.to_chrome_trace(reg, meta={"entry": "test"})
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["entry"] == "test"

    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    # process/track metadata present
    assert any(e["name"] == "process_name" for e in by_ph["M"])
    # each request: one flow start (enqueue) and one matching finish
    starts = [e for e in by_ph["s"]]
    finishes = [e for e in by_ph["f"]]
    assert {e["id"] for e in starts} == {0, 1}
    assert {e["id"] for e in finishes} == {0, 1}
    # drain becomes a duration event spanning the request's latency
    req = {e["name"]: e for e in by_ph["X"]}
    r0 = req["request/0"]
    assert r0["dur"] == pytest.approx(950.0)
    assert r0["ts"] >= 0
    # the worker's phase spans become duration events on the batch
    # track, starting where they started
    assert req["compile/b2"]["dur"] == pytest.approx(1500.0)
    assert req["dispatch/b2"]["dur"] == pytest.approx(200.0)
    assert req["wait"]["dur"] == pytest.approx(600.0)
    assert req["wait"]["ts"] == pytest.approx(11_900.0, abs=0.01)
    from repro.obs.chrometrace import TRACKS
    assert {req[k]["tid"] for k in ("take", "fill", "compile/b2",
                                   "dispatch/b2", "wait")} \
        == {TRACKS["batch"]}
    # flow start/finish share the binding category
    assert all(e["cat"] == "request" for e in starts + finishes)

    # exported file round-trips through both validators
    path = str(tmp_path / "t.trace.json")
    obs.export_chrome_trace(reg, path, meta={"entry": "test"})
    assert obs.validate_chrome_trace(path) == []
    from repro.obs import validate as vcli
    assert vcli.main([path, "--trace"]) == 0
    assert vcli.main([str(tmp_path / "missing.json"), "--trace"]) == 1


def test_chrome_trace_validator_rejects_malformed(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "pid": 1, "ts": -5, "dur": 1, "name": "x"},
        {"ph": "f", "pid": 1, "ts": 1, "id": 9, "cat": "request",
         "name": "orphan"},
        {"pid": 1, "ts": 1},
    ]}))
    problems = obs.validate_chrome_trace(str(p))
    assert any("ts" in s for s in problems)          # negative timestamp
    assert any("flow" in s for s in problems)        # finish without start
    assert any("ph" in s for s in problems)          # event without phase
    p.write_text("[]")
    assert obs.validate_chrome_trace(str(p))         # not an object


# ---------------------------------------------------------------------------
# per-layer attribution
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def deployed():
    from repro.deploy import deploy, deploy_config
    from repro.models import snn_cnn

    cfg = deploy_config("vgg9", bits=4, smoke=True)
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    model = deploy(params, cfg)
    rng = np.random.default_rng(0)
    images = jax.numpy.asarray(rng.random(
        (2, cfg.img_size, cfg.img_size, cfg.in_channels)),
        jax.numpy.float32)
    return cfg, model, images


def test_attribution_records_and_metrics(deployed):
    cfg, model, images = deployed
    ref = np.asarray(model.apply(images))
    reg = MetricsRegistry()
    logits, records = obs.timed_forward(cfg, model.float_params, images,
                                        package=model, registry=reg,
                                        peaks=V5E)
    np.testing.assert_array_equal(np.asarray(logits), ref)

    from repro.graph import build_graph
    n_nodes = len(build_graph(cfg).nodes)
    assert len(records) == n_nodes
    for r in records:
        assert r["wall_us"] > 0
    # conv/dense rows carry a roofline prediction + bottleneck verdict
    attributed = [r for r in records
                  if r["kind"] in ("conv", "dense", "residual",
                                   "fusion_group")]
    assert attributed and all(
        r["predicted_us"] > 0 and r["ratio"] > 0
        and r["bottleneck"] in ("compute", "memory") for r in attributed)
    # one gauge per node, one predicted_vs_measured span per node
    gauges = reg.find_all("snn_layer_time_us")
    assert len(gauges) == n_nodes
    spans = [ev for ev in reg.spans()
             if ev["event"] == "predicted_vs_measured"]
    assert len(spans) == n_nodes
    assert all("node" in ev and "kind" not in ev for ev in spans)

    summ = obs.attribution_summary(records)
    assert summ["nodes"] == n_nodes
    assert summ["wall_us"] >= summ["hottest_wall_us"] > 0
    assert summ["hottest_layer"] in {r["layer"] for r in records}


def test_predict_node_us_roofline_consistency():
    from repro.graph import build_graph
    from repro.obs.attribution import predict_node_us
    from repro.deploy import deploy_config
    cfg = deploy_config("vgg9", bits=4, smoke=True)
    graph = build_graph(cfg)
    convs = [n for n in graph.nodes if type(n).__name__ == "Conv"]
    p = predict_node_us(convs[1], cfg.timesteps, 2, 4)   # non-stem conv
    # predicted_us is rounded to 4 decimals at emission; a packed
    # integer layer runs at the int8 peak
    assert p["predicted_us"] == pytest.approx(
        max(p["flops"] / V5E.int8_ops, p["bytes"] / V5E.hbm_bw) * 1e6,
        abs=1e-4)
    stem = predict_node_us(convs[0], cfg.timesteps, 2, 4)  # float stem
    assert stem["compute_us"] == pytest.approx(
        stem["flops"] / V5E.bf16_flops * 1e6, abs=1e-4)
    assert p["predicted_us"] == max(p["compute_us"], p["memory_us"])
    # more timesteps -> strictly more predicted work
    p2 = predict_node_us(convs[1], cfg.timesteps * 2, 2, 4)
    assert p2["predicted_us"] > p["predicted_us"]
    # pool has no roofline story
    pools = [n for n in graph.nodes if type(n).__name__ == "Pool"]
    assert predict_node_us(pools[0], cfg.timesteps, 2, 4) is None


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_histogram_quantile_upper_edge():
    reg = MetricsRegistry()
    h = reg.histogram("lat", edges=(10.0, 100.0, 1000.0))
    for v in (5.0,) * 90 + (500.0,) * 10:
        h.observe(v)
    assert histogram_quantile(h, 0.5) == 10.0
    assert histogram_quantile(h, 0.95) == 1000.0    # upper edge, not interp
    assert histogram_quantile(h, 0.90) == 10.0
    assert histogram_quantile(reg.histogram("empty", edges=(1.0,)),
                              0.95) == 0.0
    # overflow mass reports the last finite edge
    h2 = reg.histogram("of", edges=(10.0,))
    h2.observe(99.0)
    assert histogram_quantile(h2, 0.95) == 10.0


def test_watchdog_trips_on_injected_spike_drift(tmp_path):
    reg = MetricsRegistry()
    g = reg.gauge("snn_layer_spike_rate", "rate", {"layer": "convs.1"})
    g.set(0.05)
    wd = Watchdog(reg, calibration={"convs.1": 0.05},
                  cfg=WatchdogConfig(artifact_dir=str(tmp_path)))
    assert wd.check() == []                         # at calibration: quiet
    g.set(0.5)                                      # inject 10x drift
    fired = wd.check()
    assert [t["rule"] for t in fired] == ["spike_rate_drift"]
    assert fired[0]["layer"] == "convs.1"
    assert reg.find("snn_watchdog_trips_total",
                    {"rule": "spike_rate_drift"}).value == 1
    # the trip span landed
    assert [ev for ev in reg.spans() if ev["event"] == "watchdog"]
    # LATCHED: the breach persists, no second trip
    assert wd.check() == [] and wd.trips_total == 1

    # flight recorder: snapshot + trace, both validate via the CLI
    assert len(wd.artifacts) == 2
    from repro.obs import validate as vcli
    jsonl = [a for a in wd.artifacts if a.endswith(".jsonl")][0]
    trace = [a for a in wd.artifacts if a.endswith(".trace.json")][0]
    assert "spike_rate_drift" in jsonl
    assert vcli.main([jsonl, "--require-spans", "watchdog",
                      "--require-metrics",
                      "snn_watchdog_trips_total"]) == 0
    assert vcli.main([trace, "--trace"]) == 0

    # recovery through the hysteresis band re-arms and emits a clear
    g.set(0.05)
    for _ in range(8):                              # EWMA needs to decay
        wd.check()
    assert [ev for ev in reg.spans() if ev["event"] == "watchdog_clear"]
    g.set(0.5)
    assert [t["rule"] for t in wd.check()] == ["spike_rate_drift"]
    assert wd.trips_total == 2


def test_watchdog_trips_on_injected_p95_breach():
    reg = MetricsRegistry()
    h = reg.histogram("snn_serve_latency_us", obs.LATENCY_EDGES_US, "lat")
    for _ in range(100):
        h.observe(1_000.0)                          # healthy: p95 = 1ms
    wd = Watchdog(reg, cfg=WatchdogConfig(slo_p95_ms=50.0))
    assert wd.check() == []
    for _ in range(2000):                           # drown p95 in slowness
        h.observe(400_000.0)
    fired = wd.check()
    assert [t["rule"] for t in fired] == ["latency_slo"]
    assert fired[0]["p95_ms"] > 50.0
    assert wd.check() == []                         # latched
    hz = wd.health()
    assert hz["trips_total"] == 1
    assert hz["tripped_rules"] == ["latency_slo"]
    assert hz["last_trip"]["rule"] == "latency_slo"


def test_watchdog_queue_and_padding_rules():
    reg = MetricsRegistry()
    q = reg.gauge("snn_serve_queue_depth", "depth")
    p = reg.gauge("snn_serve_padding_waste", "waste")
    wd = Watchdog(reg, cfg=WatchdogConfig(queue_depth_limit=10.0,
                                          padding_ceiling=0.5))
    q.set(2)
    p.set(0.1)
    assert wd.check() == []
    q.set(100)
    p.set(0.9)
    # EWMA (alpha 0.4) needs two samples to pull padding past the 0.5
    # ceiling; queue jumps past its limit on the first
    fired = wd.check() + wd.check()
    assert sorted(t["rule"] for t in fired) == \
        ["padding_waste", "queue_growth"]


def test_watchdog_healthy_run_never_trips():
    """Rule counters are registered eagerly (visible at 0 on /metrics);
    a registry with healthy signals fires nothing."""
    reg = _serve_registry()
    reg.gauge("snn_serve_padding_waste", "w").set(0.1)
    wd = Watchdog(reg, calibration={"convs.1": 0.05})
    for _ in range(5):
        assert wd.check() == []
    assert wd.trips_total == 0
    text = obs.to_prometheus(reg)
    assert 'snn_watchdog_trips_total{rule="latency_slo"} 0.0' in text
    assert 'snn_watchdog_trips_total{rule="spike_rate_drift"} 0.0' in text
    assert "snn_watchdog_checks_total 5.0" in text


# ---------------------------------------------------------------------------
# engine integration: scrape + watchdog while serving
# ---------------------------------------------------------------------------

def test_concurrent_scrape_and_watchdog_while_engine_steps(deployed):
    from repro.deploy import SNNEngineConfig, SNNRequest, SNNServeEngine

    cfg, model, _ = deployed
    reg = MetricsRegistry()
    eng = SNNServeEngine(model, SNNEngineConfig(max_batch=4), registry=reg)
    # absurd SLO so the run itself trips the watchdog mid-serve
    wd = Watchdog(reg, cfg=WatchdogConfig(slo_p95_ms=1e-6))
    eng.attach_watchdog(wd)
    srv = obs.ObsServer(reg, port=0, health_fn=eng.health)
    base = f"http://127.0.0.1:{srv.start()}"
    eng.warmup()

    failures = []
    stop = threading.Event()

    def scrape():
        while not stop.is_set():
            try:
                status, _, body = _get(base + "/metrics")
                if status != 200 or b"snn_serve" not in body:
                    failures.append((status, body[:100]))
                _get(base + "/healthz")
            except Exception as e:      # noqa: BLE001 — record, don't die
                failures.append(repr(e))

    t = threading.Thread(target=scrape, daemon=True)
    t.start()
    rng = np.random.default_rng(0)
    try:
        for uid in range(8):
            eng.add_request(SNNRequest(
                uid=uid, image=rng.random(
                    (cfg.img_size, cfg.img_size,
                     cfg.in_channels)).astype(np.float32)))
            eng.step()
    finally:
        stop.set()
        t.join(timeout=10)
    assert not failures, failures[:3]

    # the engine's per-microbatch check tripped the absurd SLO
    assert wd.trips_total >= 1
    hz = json.loads(_get(base + "/healthz")[2])
    assert hz["status"] == "tripped"
    assert hz["watchdog"]["trips_total"] == wd.trips_total
    assert hz["requests_total"] == 8
    assert hz["compile_cache"]["compiles"] == len(eng.buckets)
    # the final scrape sees everything the run recorded
    text = _get(base + "/metrics")[2].decode()
    assert "snn_serve_requests_total 8.0" in text
    assert 'snn_watchdog_trips_total{rule="latency_slo"} 1.0' in text
    srv.stop()
