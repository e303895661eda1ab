"""The serve engine's recycled host bucket arrays.

The contract under test:

  * a bucket array belongs to one forward from ``fill`` until
    ``finish_step``: two cohorts in flight hold distinct arrays, an
    array is reused only after the forward that read it finished, and
    every answer matches the same images pushed through the same
    executable in a fresh ``np.zeros`` array (the fill this replaced);
  * a short cohort in an array that last held a full one reads zero
    padding rows;
  * misses stay at or below ``max_inflight x workers`` per bucket, every
    other fill hits, and both counts reach ``stats()`` (engine and async
    tier) and the registry's ``snn_serve_fill_buffer_total``;
  * the synchronous ``step()`` path answers as before (the
    data-parallel engine's arrays are checked in
    ``test_distributed.py``);
  * a step that raises before ``finish_step`` drops its array, and
    threads sharing the free lists never hand one array to two steps.
"""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.deploy import SNNEngineConfig, SNNRequest, SNNServeEngine, deploy
from repro.deploy.engine import InflightStep
from repro.models import snn_cnn
from repro.obs import MetricsRegistry
from repro.quant.formats import PrecisionConfig
from repro.serve_async import AsyncEngineConfig, AsyncSNNServeEngine


@pytest.fixture(scope="module")
def packed_model():
    cfg = snn_cnn.SNNConfig(
        model="vgg9", img_size=16, timesteps=2, scale=0.15, n_classes=4,
        int_deploy=True, precision=PrecisionConfig(bits=4))
    return deploy(snn_cnn.init(jax.random.PRNGKey(0), cfg), cfg)


def _images(cfg, n, seed):
    return np.random.default_rng(seed).random(
        (n, cfg.img_size, cfg.img_size, cfg.in_channels)).astype(np.float32)


def _batch(images, uid0=0):
    batch = [SNNRequest(uid=uid0 + i, image=im)
             for i, im in enumerate(images)]
    for req in batch:
        req._t0 = time.perf_counter()
    return batch


def _zeros_fill(eng, images, bucket):
    """A fresh zeroed bucket array filled row by row."""
    cfg = eng.cfg
    out = np.zeros((bucket, cfg.img_size, cfg.img_size, cfg.in_channels),
                   np.float32)
    for i, im in enumerate(images):
        out[i] = im
    return out


def _direct_logits(eng, images, bucket):
    """``images`` through the bucket's executable in a fresh array."""
    x = jnp.asarray(_zeros_fill(eng, images, bucket))
    return np.asarray(eng._executable(bucket)(eng.model, x))[:len(images)]


def _fill(eng, images, bucket):
    with eng.phase("fill") as ph:
        return eng.fill(_batch(images), ph, bucket)


def test_inflight_cohorts_hold_their_own_arrays(packed_model):
    """Cohorts A and B in flight together hold distinct arrays; C,
    dispatched once A has finished and while B is still in flight,
    reuses A's; every answer matches its images served alone."""
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=4,
                                                       buckets=(4,)))
    imgs = {k: _images(cfg, n, seed) for k, n, seed in
            (("a", 4, 1), ("b", 3, 2), ("c", 4, 3))}
    st_a = eng.begin_step(_batch(imgs["a"], 0))
    st_b = eng.begin_step(_batch(imgs["b"], 10))
    buf_a, buf_b = st_a.images, st_b.images
    assert buf_a is not None and buf_b is not None and buf_a is not buf_b
    assert eng.finish_step(st_a) == 4
    assert st_a.images is None                 # handed back
    st_c = eng.begin_step(_batch(imgs["c"], 20))
    assert st_c.images is buf_a                # recycled, B's still held
    assert eng.finish_step(st_b) == 3
    assert eng.finish_step(st_c) == 4
    got = {"a": [eng.pop_result(u).logits for u in range(4)],
           "b": [eng.pop_result(u).logits for u in range(10, 13)],
           "c": [eng.pop_result(u).logits for u in range(20, 24)]}
    for k, want in imgs.items():
        np.testing.assert_array_equal(np.stack(got[k]),
                                      _direct_logits(eng, want, 4))
    assert eng.fill_buffers_allocated == {4: 2}
    assert eng.fill_buffer_hits == 1


@pytest.mark.parametrize("n", [1, 3])
def test_short_cohort_zeroes_padding_of_a_full_array(packed_model, n):
    """A cohort of ``n`` in the array a full cohort left: rows past ``n``
    read zero, the array equals a fresh zeroed fill, and the answers are
    those of the images served alone."""
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=4,
                                                       buckets=(4,)))
    full = _images(cfg, 4, seed=4)
    eng.finish_step(eng.begin_step(_batch(full)))
    held = eng._fill_free[4][-1]
    np.testing.assert_array_equal(held, full)  # what the full one left

    short = _images(cfg, n, seed=5)
    buf = _fill(eng, short, 4)
    assert buf is held
    assert not buf[n:].any()
    np.testing.assert_array_equal(buf, _zeros_fill(eng, short, 4))
    eng._fill_free[4].append(buf)

    st = eng.begin_step(_batch(short, 100))
    eng.finish_step(st)
    np.testing.assert_array_equal(
        np.stack([eng.pop_result(100 + i).logits for i in range(n)]),
        _direct_logits(eng, short, 4))


@pytest.mark.parametrize("workers", [1, 2])
def test_steady_state_misses_stay_within_the_pipeline(packed_model,
                                                      workers):
    """Through the async tier, a bucket allocates at most one array per
    rollout in flight (``max_inflight x workers``); every other fill
    hits.  The counts reach the tier's ``stats()`` and the registry."""
    cfg = packed_model.cfg
    reg = MetricsRegistry()
    eng = SNNServeEngine(packed_model,
                         SNNEngineConfig(max_batch=4, buckets=(4,)),
                         registry=reg)
    eng.warmup()
    acfg = AsyncEngineConfig(workers=workers, max_inflight=2)
    images = _images(cfg, 96, seed=6)
    with AsyncSNNServeEngine(eng, acfg) as aeng:
        futs = [aeng.submit(im) for im in images]
        res = [f.result(timeout=120) for f in futs]
        stats = aeng.stats()
    assert all(r.ok for r in res)
    fb = stats["fill_buffers"]
    allocated = sum(fb["allocated"].values())
    assert fb["allocated"] and all(
        0 < v <= acfg.max_inflight * workers
        for v in fb["allocated"].values())
    assert fb["hits"] + allocated == stats["batches"]
    miss = reg.counter("snn_serve_fill_buffer_total",
                       labels={"result": "miss"})
    hit = reg.counter("snn_serve_fill_buffer_total",
                      labels={"result": "hit"})
    assert (miss.value, hit.value) == (allocated, fb["hits"])
    got = np.stack([r.logits for r in res])
    want = np.concatenate([_direct_logits(eng, images[i:i + 4], 4)
                           for i in range(0, len(images), 4)])
    np.testing.assert_array_equal(got, want)


def test_sync_step_path_answers_and_recycles(packed_model):
    """``step()`` over a mixed stream: each bucket allocates once, every
    later forward reuses it, and each answer matches its images in a
    fresh zeroed array."""
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=4,
                                                       buckets=(2, 4)))
    images = _images(cfg, 14, seed=7)
    uid, want = 0, []
    for burst in (4, 1, 4, 2, 3):
        for im in images[uid:uid + burst]:
            eng.add_request(SNNRequest(uid=uid, image=im))
            uid += 1
        eng.step()
        chunk = images[uid - burst:uid]
        want.append(_direct_logits(eng, chunk, eng.bucket_for(burst)))
    got = np.stack([eng.pop_result(u).logits for u in range(uid)])
    np.testing.assert_array_equal(got, np.concatenate(want))
    stats = eng.stats()
    assert stats["fill_buffers"] == {"hits": 3,
                                     "allocated": {"2": 1, "4": 1}}


def test_step_that_raises_drops_its_array(packed_model):
    """A launch that fails never returns its array: the next fill of the
    bucket allocates again."""
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=2,
                                                       buckets=(2,)))
    eng.warmup()
    real = eng._executable

    def broken(*a, **k):
        raise RuntimeError("device lost")

    eng._executable = broken
    with pytest.raises(RuntimeError, match="device lost"):
        eng.begin_step(_batch(_images(cfg, 2, seed=8)))
    eng._executable = real
    assert eng._fill_free.get(2, []) == []
    eng.finish_step(eng.begin_step(_batch(_images(cfg, 2, seed=9), 10)))
    assert eng.fill_buffers_allocated == {2: 2}
    assert eng.fill_buffer_hits == 0


def test_threads_never_share_an_array(packed_model):
    """Sixteen threads fill, hold and finish steps on one engine with a
    short switch interval: no array is held by two steps at once, each
    thread allocates at most one per bucket, and hits plus misses count
    every fill."""
    cfg = packed_model.cfg
    eng = SNNServeEngine(packed_model, SNNEngineConfig(max_batch=4,
                                                       buckets=(2, 4)))
    logits = np.zeros((4, cfg.n_classes), np.float32)
    images = _images(cfg, 4, seed=10)
    held, lock, errors = set(), threading.Lock(), []
    n_threads, rounds = 16, 40

    def work(tid):
        try:
            for r in range(rounds):
                n = 1 + (tid + r) % 4
                batch = _batch(images[:n], uid0=(tid * rounds + r) * 4)
                with eng.phase("fill") as ph:
                    buf = eng.fill(batch, ph)
                with lock:
                    assert id(buf) not in held
                    held.add(id(buf))
                assert not buf[n:].any()
                np.testing.assert_array_equal(buf[:n], images[:n])
                st = InflightStep(batch=batch, bucket=buf.shape[0], n=n,
                                  logits=logits[:buf.shape[0]],
                                  t0=time.perf_counter(), cohort=-1,
                                  images=buf)
                with lock:
                    held.discard(id(buf))
                eng.finish_step(st, sink=lambda b: None)
        except Exception as e:              # reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    allocated = sum(eng.fill_buffers_allocated.values())
    assert eng.fill_buffer_hits + allocated == n_threads * rounds
    assert all(v <= n_threads for v in eng.fill_buffers_allocated.values())
    assert eng.total_batches == n_threads * rounds
