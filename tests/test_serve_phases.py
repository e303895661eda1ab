"""The serving worker's phase spans.

The contract under test:

  * with the registry DISABLED, one async cohort yields ``take``,
    ``fill``, ``dispatch``, ``wait``, ``readback`` and ``resolve`` spans
    sharing one cohort id, in that order, and the worker's spans never
    overlap;
  * a bucket-executable miss yields a ``compile`` span (warmup's carry
    cohort -1, a miss on the serving path the cohort that caused it);
  * the synchronous ``step()`` path records the same phases, and so
    does the data-parallel path on four CPU devices;
  * the phase ring is bounded and its windowed read reports a window
    it can no longer hold whole;
  * every profiler annotation the phases use starts with
    ``snn_serve_step/``, and ``engine.phase`` records a span at its
    exit with the fields its block set.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.deploy import (SNNEngineConfig, SNNRequest, SNNServeEngine,
                          deploy, deploy_config)
from repro.deploy.engine import annotation
from repro.models import snn_cnn
from repro.obs import PHASES, MetricsRegistry, registry
from repro.serve_async import AsyncEngineConfig, AsyncSNNServeEngine

LOOP = ["take", "fill", "dispatch", "wait", "readback", "resolve"]


@pytest.fixture(scope="module")
def packed_model():
    cfg = deploy_config("vgg9", bits=4, smoke=True)
    return deploy(snn_cnn.init(jax.random.PRNGKey(0), cfg), cfg)


def _image(cfg, seed=0):
    return np.random.default_rng(seed).random(
        (cfg.img_size, cfg.img_size, cfg.in_channels)).astype(np.float32)


def _cohort(spans, cohort):
    return [s.phase for s in spans if s.cohort == cohort]


def _assert_sequential(spans):
    for a, b in zip(spans, spans[1:]):
        assert a.t0 <= a.t1 <= b.t0, (a, b)


def test_async_cohort_phases_with_registry_disabled(packed_model):
    reg = MetricsRegistry(enabled=False)
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=2, buckets=(2,)),
                         registry=reg)
    eng.warmup()
    with AsyncSNNServeEngine(eng, AsyncEngineConfig(workers=1)) as aeng:
        assert aeng.submit(_image(packed_model.cfg)).result(timeout=120).ok
    spans, complete = reg.phases()
    assert complete and reg.spans() == []          # no per-request events
    worker = [s for s in spans if s.phase != "compile"]
    _assert_sequential(worker)
    cohorts = {s.cohort for s in worker if s.phase == "dispatch"}
    assert len(cohorts) == 1
    cohort = cohorts.pop()
    assert _cohort(spans, cohort) == LOOP
    for s in spans:
        if s.cohort == cohort:
            assert (s.bucket, s.n) == (2, 1), s
    # polls that took nothing carry no cohort
    assert all(s.phase == "take" and s.n == 0
               for s in worker if s.cohort == -1)


def test_compile_spans_on_bucket_misses(packed_model):
    reg = MetricsRegistry(enabled=False)
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=4, buckets=(2, 4)),
                         registry=reg)
    eng._executable(2)                       # a warmup-style build
    for i in range(3):                       # bucket 4: a miss in-line
        eng.add_request(SNNRequest(uid=i, image=_image(packed_model.cfg, i)))
    eng.step()
    spans, _ = reg.phases()
    compiles = [s for s in spans if s.phase == "compile"]
    assert [(s.bucket, s.cohort) for s in compiles] == [(2, -1), (4, 0)]
    assert compiles[1].n == 3
    # the in-line compile sits between its cohort's fill and dispatch
    assert _cohort(spans, 0) == ["take", "fill", "compile", "dispatch",
                                 "wait", "readback", "resolve"]
    _assert_sequential([s for s in spans if s.cohort == 0])
    eng.step()                               # nothing queued: no spans
    assert len(reg.phases()[0]) == len(spans)


def test_sync_step_phases_and_enabled_export(packed_model):
    """``step()`` records the loop's phases per forward; an enabled
    registry also carries them as span events named by phase."""
    reg = MetricsRegistry()
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=2, buckets=(2,)),
                         registry=reg)
    eng.warmup()
    for i in range(4):
        eng.add_request(SNNRequest(uid=i, image=_image(packed_model.cfg, i)))
    eng.run_until_done()
    spans, complete = reg.phases()
    assert complete
    worker = [s for s in spans if s.phase != "compile"]
    _assert_sequential(worker)
    assert [s.phase for s in worker] == LOOP * 2
    assert [s.cohort for s in worker] == [0] * 6 + [1] * 6
    events = [ev for ev in reg.spans() if ev["event"] in PHASES]
    assert sorted(ev["event"] for ev in events) == \
        sorted(s.phase for s in spans)
    wait = next(ev for ev in events if ev["event"] == "wait")
    span = next(s for s in spans if s.phase == "wait")
    assert wait["dur_us"] == pytest.approx((span.t1 - span.t0) * 1e6)
    assert wait["ts_us"] == pytest.approx((span.t1 - reg._t0) * 1e6)
    assert (wait["cohort"], wait["bucket"], wait["n"]) == (0, 2, 2)


def test_data_parallel_phases_on_four_devices():
    code = textwrap.dedent("""
        import json
        import jax, numpy as np
        from repro.deploy import (SNNEngineConfig, SNNRequest,
                                  SNNServeEngine, deploy, deploy_config)
        from repro.models import snn_cnn
        from repro.obs import MetricsRegistry

        cfg = deploy_config("vgg9", bits=4, smoke=True)
        pkg = deploy(snn_cnn.init(jax.random.PRNGKey(0), cfg), cfg)
        reg = MetricsRegistry(enabled=False)
        eng = SNNServeEngine(pkg, SNNEngineConfig(max_batch=8,
                                                  buckets=(8,),
                                                  data_parallel=True),
                             registry=reg)
        eng.warmup()
        img = np.zeros((cfg.img_size, cfg.img_size, cfg.in_channels),
                       np.float32)
        for i in range(5):
            eng.add_request(SNNRequest(uid=i, image=img))
        eng.step()
        spans, complete = reg.phases()
        print(json.dumps({"devices": len(jax.devices()),
                          "complete": complete,
                          "spans": [list(s) for s in spans]}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=420, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["complete"]
    spans = out["spans"]
    assert [(s[0], s[3]) for s in spans] == \
        [("compile", -1)] + [(p, 0) for p in LOOP]
    assert all((s[4], s[5]) == (8, 5) for s in spans[1:])
    for a, b in zip(spans[1:], spans[2:]):
        assert a[1] <= a[2] <= b[1], (a, b)


def test_phase_ring_is_bounded_and_reports_a_lost_window(monkeypatch):
    monkeypatch.setattr(registry, "PHASE_RING", 4)
    reg = MetricsRegistry(enabled=False)
    for i in range(6):                       # starts 0..5; 0 and 1 drop
        reg.phase("wait", float(i), i + 0.5, cohort=i)
    spans, complete = reg.phases()
    assert [s.cohort for s in spans] == [2, 3, 4, 5] and not complete
    assert not reg.phases(1.0, 4.0)[1]       # span 1 belonged here
    spans, complete = reg.phases(2.0, 4.0)   # nothing it lost started here
    assert complete and [s.cohort for s in spans] == [2, 3]
    assert reg.spans() == []                 # disabled: no events
    reg.reset()
    assert reg.phases() == ([], True)


def test_annotations_name_the_serving_step():
    names = [annotation(p, b) for p in PHASES for b in (1, 4)]
    assert len(set(names)) == 5 + 2 * 2
    assert all(n.startswith("snn_serve_step/") for n in names)
    # the dispatch keeps the name device traces have always shown
    assert {"snn_serve_step/b1", "snn_serve_step/b4",
            "snn_serve_step/compile/b4"} <= set(names)


def test_phase_helper_records_on_exit_with_fields_set_inside(
        packed_model):
    """``engine.phase`` is the one way a site opens a phase: the span
    starts at entry, is recorded at exit, and carries the cohort, bucket
    and rows the block learned; an exception still records it."""
    reg = MetricsRegistry(enabled=False)
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=4, buckets=(2, 4)),
                         registry=reg)
    with eng.phase("take") as ph:
        assert reg.phases()[0] == []          # nothing until exit
        ph.cohort, ph.bucket, ph.n = eng.new_cohort(), 4, 3
    with pytest.raises(RuntimeError):
        with eng.phase("dispatch", 7, 8, 5):   # a bucket not configured
            raise RuntimeError("boom")
    spans, complete = reg.phases()
    assert complete
    assert [s[:1] + s[3:] for s in spans] == [("take", 0, 4, 3),
                                              ("dispatch", 7, 8, 5)]
    assert spans[0].t0 == ph.t0 <= spans[0].t1 <= spans[1].t0
    assert eng.new_cohort() == 1
