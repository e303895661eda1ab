"""Async continuous-batching serve tier (ISSUE 10 acceptance criteria).

The contract under test:

  * **bit-exactness** — a request served through the async tier scores
    exactly like the same image through the synchronous engine (both
    tiers share one bucket-cached executable when the bucket is pinned);
  * **no lost or duplicated results** — N threads submitting
    concurrently get exactly N distinct resolved futures and the engine
    totals reconcile;
  * **deadlines** — an expired request resolves as an explicit
    ``timeout`` result, never a hung future;
  * **graceful drain** — ``close(drain=True)`` flushes the queue and
    pipeline (every future resolves ok); ``close(drain=False)``
    resolves the backlog as ``cancelled``;
  * **zero recompiles after warmup** survives concurrent admission;
  * the tier emits ``recycle`` / ``evict`` spans and keeps the slot /
    queue gauges current (the Chrome-trace slot-lifetime rows).

Plus plain unit tests for the pieces (RequestQueue, SlotManager,
SNNFuture, poisson_schedule) — those need no device and run in
microseconds.
"""

import threading
import time

import jax
import numpy as np
import pytest

from repro.deploy import SNNEngineConfig, SNNRequest, SNNServeEngine, deploy
from repro.deploy.engine import InflightStep
from repro.models import snn_cnn
from repro.quant.formats import PrecisionConfig
from repro.serve_async import (
    AsyncEngineConfig,
    AsyncSNNServeEngine,
    Closed,
    Full,
    QueueEntry,
    RequestQueue,
    SlotManager,
    SNNFuture,
    poisson_schedule,
    run_open_loop_async,
    run_open_loop_sync,
)
from repro.serve_async.futures import AsyncResult


# ---------------------------------------------------------------------------
# unit: queue / slots / futures / schedule (no device)
# ---------------------------------------------------------------------------

def _entry(uid, deadline=None):
    return QueueEntry(req=SNNRequest(uid=uid, image=None),
                      future=SNNFuture(uid), deadline=deadline)


def test_queue_fifo_and_cohort_take():
    q = RequestQueue()
    for uid in range(5):
        q.put(_entry(uid))
    ready, expired = q.take(3, timeout=0)
    assert [e.req.uid for e in ready] == [0, 1, 2] and not expired
    ready, _ = q.take(3, timeout=0)
    assert [e.req.uid for e in ready] == [3, 4]
    assert len(q) == 0


def test_queue_bounded_admission_and_close():
    q = RequestQueue(maxsize=2)
    q.put(_entry(0))
    q.put(_entry(1))
    with pytest.raises(Full):
        q.put(_entry(2))
    q.close()
    with pytest.raises(Closed):
        q.put(_entry(3))
    # closed queues still hand out what they hold (graceful-drain order)
    ready, _ = q.take(4, timeout=0)
    assert [e.req.uid for e in ready] == [0, 1]


def test_queue_requeue_goes_to_front_even_when_closed():
    q = RequestQueue()
    q.put(_entry(0))
    q.close()
    q.requeue(_entry(7))
    ready, _ = q.take(2, timeout=0)
    assert [e.req.uid for e in ready] == [7, 0]


def test_queue_take_splits_expired_entries():
    q = RequestQueue()
    now = time.perf_counter()
    q.put(_entry(0, deadline=now - 1.0))     # already expired
    q.put(_entry(1, deadline=now + 60.0))
    q.put(_entry(2))                          # no deadline
    ready, expired = q.take(3, timeout=0)
    assert [e.req.uid for e in ready] == [1, 2]
    assert [e.req.uid for e in expired] == [0]


def test_queue_put_wakes_blocked_taker():
    q = RequestQueue()
    got = []

    def taker():
        ready, _ = q.take(1, timeout=5.0)
        got.extend(ready)

    th = threading.Thread(target=taker)
    th.start()
    time.sleep(0.02)                          # taker is parked in wait
    q.put(_entry(9))
    th.join(timeout=5.0)
    assert not th.is_alive() and got[0].req.uid == 9


def test_slot_manager_backpressure_and_recycling():
    sm = SlotManager(2)
    a, b = sm.acquire(10), sm.acquire(11)
    assert {a, b} == {0, 1}
    assert sm.acquire(12) is None             # full -> backpressure
    uid, held = sm.release(a)
    assert uid == 10 and held >= 0.0
    assert sm.occupied() == 1 and sm.free_count() == 1
    assert sm.acquire(12) == a                # LIFO reuse of the hot slot
    assert sm.total_acquired == 3
    assert sm.total_recycled == 1             # third seat on 2 slots


@pytest.mark.parametrize("many", [False, True],
                         ids=["release", "release_many"])
def test_slot_manager_release_order(many):
    """Releasing a cohort's slots in one ``release_many`` round leaves the
    free list, and so the next acquisitions, as ``release`` on each slot
    in turn does; an unheld slot raises in both."""
    sm = SlotManager(4)
    a, b, c, d = (sm.acquire(10 + i) for i in range(4))

    def release(slots):
        if many:
            return sm.release_many(slots)
        return [sm.release(s) for s in slots]

    got = release([a, c, b])
    assert [uid for uid, _ in got] == [10, 12, 11]
    assert all(held > 0.0 for _, held in got)
    assert sm.occupied() == 1 and sm.free_count() == 3
    assert [sm.acquire(20 + i) for i in range(4)] == [b, c, a, None]
    assert release([d]) and sm.free_count() == 1
    with pytest.raises(KeyError):
        release([d])
    assert sm.free_count() == 1


def test_future_resolves_once_first_write_wins():
    f = SNNFuture(0)
    assert not f.done()
    assert f.resolve(AsyncResult(uid=0, status="ok"))
    assert not f.resolve(AsyncResult(uid=0, status="timeout"))
    assert f.result(timeout=0).status == "ok"


def test_future_caller_timeout_is_not_request_timeout():
    f = SNNFuture(0)
    with pytest.raises(TimeoutError):
        f.result(timeout=0.01)
    # the future stays valid and can still resolve
    f.resolve(AsyncResult(uid=0, status="ok"))
    assert f.result(timeout=0).ok


def test_poisson_schedule_seeded_and_sane():
    a = poisson_schedule(50.0, 200, seed=3)
    b = poisson_schedule(50.0, 200, seed=3)
    np.testing.assert_array_equal(a, b)       # sync/async replay identically
    assert np.all(np.diff(a) > 0)             # strictly increasing arrivals
    mean_gap = float(a[-1]) / len(a)
    assert 0.5 / 50.0 < mean_gap < 2.0 / 50.0  # ~1/rate
    with pytest.raises(ValueError):
        poisson_schedule(0.0, 4)


# ---------------------------------------------------------------------------
# integration: the tier over a real packed model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def packed_model():
    cfg = snn_cnn.SNNConfig(
        model="vgg9", img_size=16, timesteps=2, scale=0.15, n_classes=4,
        int_deploy=True, precision=PrecisionConfig(bits=4))
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    return deploy(params, cfg)


def _images(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.random((n, cfg.img_size, cfg.img_size,
                       cfg.in_channels)).astype(np.float32)


def test_async_results_bit_exact_with_sync_engine(packed_model):
    """Same image, same pinned bucket -> identical logits whichever tier
    served it (the executable is shared; batch rows are independent)."""
    cfg = packed_model.cfg
    images = _images(cfg, 8, seed=1)

    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=4, buckets=(4,)))
    eng.warmup()
    for i in range(8):
        eng.add_request(SNNRequest(uid=i, image=images[i]))
    eng.run_until_done()
    ref = {i: eng.pop_result(i) for i in range(8)}
    eng.close()

    eng2 = SNNServeEngine(packed_model,
                          SNNEngineConfig(max_batch=4, buckets=(4,)))
    with AsyncSNNServeEngine(eng2, AsyncEngineConfig(workers=2)) as aeng:
        futs = [aeng.submit(images[i]) for i in range(8)]
        res = [f.result(timeout=120) for f in futs]
    for i, r in enumerate(res):
        assert r.ok
        np.testing.assert_array_equal(r.logits, ref[i].logits)
        assert r.pred == ref[i].pred


def _cohort_logits(bucket, n_classes, seed):
    """A bucket's logits with ties: row 0 has two equal maxima, row 1 is
    constant — the first maximum is the prediction."""
    lg = np.random.default_rng(seed).standard_normal(
        (bucket, n_classes)).astype(np.float32)
    lg[0, 1] = lg[0, -1] = lg[0].max() + 1.0
    if bucket > 1:
        lg[1] = 0.5
    return lg


def _stub_forward(eng, logits):
    """Make ``eng.launch`` hand back ``logits`` (a host array) in place
    of the device forward, so the resolve path sees chosen rows."""
    def launch(batch, images, cohort):
        return InflightStep(batch=batch, bucket=images.shape[0],
                            n=len(batch), logits=logits,
                            t0=time.perf_counter(), cohort=cohort)
    eng.launch = launch


def _totals(eng):
    return (eng.total_requests, eng.total_batches, eng.total_slots,
            eng.total_padded_slots, dict(eng.per_bucket))


@pytest.mark.parametrize("sink", ["done", "futures"])
@pytest.mark.parametrize("n", [1, 3, 4], ids=["one", "partial", "full"])
def test_cohort_resolve_parity(packed_model, n, sink):
    """A cohort resolved in one pass gives each request its own logits
    row bit for bit, the row's first-maximum prediction (ties included)
    and status ok; ``compute_s`` is the cohort's, ``latency_s`` covers
    ``queue_s``; and the engine's totals match the same requests served
    one cohort at a time through ``step()``."""
    cfg = packed_model.cfg
    bucket = 4
    ecfg = SNNEngineConfig(max_batch=bucket, buckets=(bucket,))
    images = _images(cfg, n, seed=8)
    logits = _cohort_logits(bucket, cfg.n_classes, seed=n)

    ref = SNNServeEngine(packed_model, ecfg)
    _stub_forward(ref, logits)
    for i in range(n):
        ref.add_request(SNNRequest(uid=i, image=images[i]))
    assert ref.step() == n and not ref.queue

    eng = SNNServeEngine(packed_model, ecfg)
    _stub_forward(eng, logits)
    if sink == "done":
        batch = [SNNRequest(uid=i, image=images[i]) for i in range(n)]
        for req in batch:
            req._t0 = time.perf_counter()
        for req in batch:
            req.queue_s = time.perf_counter() - req._t0
        assert eng.finish_step(eng.begin_step(batch)) == n
        got = [eng.pop_result(i) for i in range(n)]
        status = ["ok"] * n
    else:
        aeng = AsyncSNNServeEngine(eng)          # served inline at close
        futs = [aeng.submit(im) for im in images]
        aeng.close(drain=True)
        got = [f.result(timeout=0) for f in futs]
        status = [r.status for r in got]
        assert aeng.stats()["async"]["completed"] == n
        assert len(aeng._reservoir) == n
        assert aeng.slots.occupied() == 0
    assert [r.uid for r in got] == list(range(n))

    for i, r in enumerate(got):
        np.testing.assert_array_equal(r.logits, logits[i])
        assert r.pred == int(np.argmax(logits[i]))
        assert type(r.pred) is int
        assert r.latency_s >= r.queue_s >= 0.0
    assert status == ["ok"] * n
    assert got[0].pred == 1                      # the tie: first maximum
    if n > 1:
        assert got[1].pred == 0
    assert len({r.compute_s for r in got}) == 1 and got[0].compute_s > 0

    for e in (ref, eng):
        assert _totals(e) == (n, 1, bucket, bucket - n, {bucket: 1})
    lats = [r.latency_s for r in got]
    assert eng.total_latency_s == pytest.approx(sum(lats))
    assert eng.max_latency_s == max(lats)
    assert eng.total_queue_s == pytest.approx(sum(r.queue_s for r in got))
    assert eng.total_request_compute_s == pytest.approx(
        n * got[0].compute_s)
    ref_lats = [ref.pop_result(i).latency_s for i in range(n)]
    assert ref.total_latency_s == pytest.approx(sum(ref_lats))
    assert ref.max_latency_s == max(ref_lats)


def test_sink_yields_once_the_cohort_is_resolved(packed_model, monkeypatch):
    """The futures sink ends with ``time.sleep(0)``, after every future of
    the cohort is resolved: the threads those futures woke then get the
    interpreter lock, and can submit their next requests, before the
    worker polls the queue for the next cohort."""
    import repro.serve_async.engine as tier

    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=4, buckets=(4,)))
    _stub_forward(eng, _cohort_logits(4, cfg.n_classes, seed=0))
    aeng = AsyncSNNServeEngine(eng)
    futs = [aeng.submit(im) for im in _images(cfg, 3, seed=9)]
    yields = []
    sleep = time.sleep

    def recording_sleep(s):
        if s == 0:
            yields.append([f.done() for f in futs])
        sleep(s)

    monkeypatch.setattr(tier.time, "sleep", recording_sleep)
    aeng.close(drain=True)                   # one cohort, served inline
    assert yields == [[True, True, True]]


def test_concurrent_submitters_lose_nothing(packed_model):
    """N threads x M submissions: every future resolves ok exactly once,
    predictions match a per-image reference, totals reconcile."""
    cfg = packed_model.cfg
    images = _images(cfg, 4, seed=2)
    n_threads, per_thread = 4, 6
    total = n_threads * per_thread

    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=4, buckets=(1, 2, 4)))
    aeng = AsyncSNNServeEngine(eng, AsyncEngineConfig(workers=2))
    aeng.warmup()
    warm = eng.compile_count
    aeng.start()

    results = {}
    lock = threading.Lock()

    def client(tid):
        futs = [(i, aeng.submit(images[(tid + i) % len(images)]))
                for i in range(per_thread)]
        for i, f in futs:
            r = f.result(timeout=120)
            with lock:
                results[(tid, i)] = r

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    stats = aeng.close()

    assert len(results) == total                      # nothing lost
    uids = [r.uid for r in results.values()]
    assert len(set(uids)) == total                    # nothing duplicated
    assert all(r.ok for r in results.values())
    a = stats["async"]
    assert a["submitted"] == a["completed"] == total  # exact totals
    assert a["timeouts"] == a["cancelled"] == 0
    assert eng.total_requests == total
    assert eng.compile_count - warm == 0              # zero recompiles
    # concurrency went beyond one cohort: slots were recycled
    assert aeng.slots.total_acquired == total


def test_deadline_exceeded_resolves_as_timeout_not_hang(packed_model):
    """A request whose admission deadline passes resolves with an
    explicit timeout result as soon as a worker touches the queue."""
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=2, buckets=(2,)))
    aeng = AsyncSNNServeEngine(eng)       # workers NOT started yet
    img = _images(packed_model.cfg, 1)[0]
    fut = aeng.submit(img, deadline_ms=5.0)
    live = aeng.submit(img)               # no deadline: must still serve
    time.sleep(0.05)                      # let the deadline lapse
    aeng.start()
    r = fut.result(timeout=120)
    assert r.status == "timeout" and not r.ok
    assert "deadline" in r.detail
    assert live.result(timeout=120).ok
    stats = aeng.close()
    assert stats["async"]["timeouts"] == 1
    assert stats["async"]["completed"] == 1


def test_close_drain_flushes_queue_and_pipeline(packed_model):
    """Graceful drain: whatever is queued when close(drain=True) is
    called still gets served — every future resolves ok."""
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=4, buckets=(4,)))
    aeng = AsyncSNNServeEngine(eng, AsyncEngineConfig(workers=1))
    images = _images(packed_model.cfg, 6, seed=3)
    futs = [aeng.submit(im) for im in images]   # queued, no workers yet
    aeng.start()
    stats = aeng.close(drain=True)              # races the workers: ok
    assert all(f.result(timeout=120).ok for f in futs)
    assert stats["async"]["completed"] == len(futs)
    with pytest.raises(Closed):
        aeng.submit(images[0])


def test_close_drain_serves_inline_when_never_started(packed_model):
    """close(drain=True) on a tier whose workers never started still
    owes every queued request an answer — served on the closing thread."""
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=2, buckets=(2,)))
    aeng = AsyncSNNServeEngine(eng)
    futs = [aeng.submit(im) for im in _images(packed_model.cfg, 3, seed=4)]
    stats = aeng.close(drain=True)
    assert all(f.result(timeout=0).ok for f in futs)
    assert stats["async"]["completed"] == 3


def test_close_without_drain_cancels_backlog(packed_model):
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=2, buckets=(2,)))
    aeng = AsyncSNNServeEngine(eng)
    futs = [aeng.submit(im) for im in _images(packed_model.cfg, 3, seed=5)]
    stats = aeng.close(drain=False)
    for f in futs:
        r = f.result(timeout=0)
        assert r.status == "cancelled" and not r.ok
    assert stats["async"]["cancelled"] == 3
    assert stats["async"]["completed"] == 0


@pytest.mark.parametrize("bucket,before_start", [(2, False), (4, True)],
                         ids=["bucket2", "bucket4-one-cohort"])
def test_async_tier_emits_recycle_spans_and_gauges(packed_model, bucket,
                                                   before_start):
    """With an enabled registry the tier adds evict/recycle spans and
    slot/queue gauges on top of the engine's request trace — one
    ``recycle`` and one ``drain`` per served request, also when a cohort
    holds several (four queued before the worker starts, bucket 4)."""
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=bucket, buckets=(bucket,)),
                         registry=reg)
    aeng = AsyncSNNServeEngine(eng, AsyncEngineConfig(workers=1))
    images = _images(packed_model.cfg, 4, seed=6)
    timed_out = aeng.submit(images[0], deadline_ms=1.0)
    time.sleep(0.01)
    if before_start:
        futs = [aeng.submit(im) for im in images]
        aeng.start()
    else:
        aeng.start()
        futs = [aeng.submit(im) for im in images]
    assert all(f.result(timeout=120).ok for f in futs)
    aeng.close()
    assert timed_out.result(timeout=0).status == "timeout"

    events = [ev["event"] for ev in reg.spans()]
    assert events.count("enqueue") == 5        # emplace-on-arrival spans
    assert events.count("recycle") == 4        # one per served request
    assert events.count("drain") == 4
    assert events.count("evict") == 1
    recycles = [ev for ev in reg.spans() if ev["event"] == "recycle"]
    drains = [ev for ev in reg.spans() if ev["event"] == "drain"]
    assert all(ev["held_us"] > 0 for ev in recycles)
    uids = sorted(f.uid for f in futs)
    assert sorted(ev["uid"] for ev in recycles) == uids
    assert sorted(ev["uid"] for ev in drains) == uids
    if before_start:                           # the four shared one cohort
        assert eng.total_batches == 1
    assert reg.counter("snn_serve_evictions_total").value == 1
    assert reg.counter("snn_serve_submitted_total").value == 5
    assert reg.gauge("snn_serve_slot_occupancy").value == 0.0  # all freed
    assert reg.gauge("snn_serve_queue_depth").value == 0.0

    # the new span kinds render on the slots/requests tracks, and the
    # whole trace still validates
    from repro.obs.chrometrace import TRACKS, span_to_events, to_chrome_trace

    slot_rows = [e for ev in recycles for e in span_to_events(ev)]
    assert all(e["tid"] == TRACKS["slots"] and e["ph"] == "X"
               for e in slot_rows)
    evict_evs = span_to_events(
        next(ev for ev in reg.spans() if ev["event"] == "evict"))
    assert {e["ph"] for e in evict_evs} == {"i", "f"}
    doc = to_chrome_trace(reg)
    assert any(e.get("name", "").startswith("slot/")
               for e in doc["traceEvents"])


def test_open_loop_drivers_share_one_schedule(packed_model):
    """Both drivers complete the same seeded arrival process; offered
    and achieved throughput are reported separately and every request's
    latency split survives into the report."""
    cfg = packed_model.cfg
    images = _images(cfg, 4, seed=7)
    schedule = poisson_schedule(200.0, 10, seed=1)

    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=4, buckets=(1, 2, 4)))
    eng.warmup()
    rep_s = run_open_loop_sync(eng, images, schedule)
    eng.close()

    eng2 = SNNServeEngine(packed_model,
                          SNNEngineConfig(max_batch=4, buckets=(1, 2, 4)))
    aeng = AsyncSNNServeEngine(eng2, AsyncEngineConfig(workers=1))
    aeng.warmup()
    aeng.start()
    rep_a = run_open_loop_async(aeng, images, schedule)
    aeng.close()

    for rep in (rep_s, rep_a):
        assert rep.completed == rep.requests == 10
        assert rep.timeouts == 0 and rep.cancelled == 0
        assert rep.offered_rps == pytest.approx(10 / float(schedule[-1]))
        assert 0 < rep.achieved_rps <= rep.offered_rps * 1.01
        assert rep.latency_p50_ms <= rep.latency_p95_ms \
            <= rep.latency_p99_ms <= rep.latency_max_ms
        assert rep.queue_avg_ms >= 0 and rep.compute_avg_ms > 0
    assert rep_s.mode == "sync" and rep_a.mode == "async"
