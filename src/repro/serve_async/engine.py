"""Asynchronous continuous-batching front-end over ``SNNServeEngine``.

The synchronous engine's ``step()`` loop is batch-formation-bound: a
request that arrives while a rollout is on the device waits for the
rollout to drain, for the host to form the next batch, and for the
transfer — all serialized on one thread.  This tier splits those onto
a request path and a worker path:

  * ``submit(image)`` is **emplace-on-arrival**: the request is
    validated against the served model, stamped, queued, and its
    :class:`~repro.serve_async.futures.SNNFuture` returned — all on the
    caller's thread, waking an idle worker immediately.
  * Each **worker thread** drives the engine's slot-level hooks
    (``begin_step`` / ``finish_step``) with a short in-flight pipeline:
    while rollout k runs its T timesteps on the device, the worker
    seats newly arrived requests into slots freed by rollout k-1,
    builds and DISPATCHES rollout k+1 (jax async dispatch — the
    host->device transfer overlaps rollout k's compute), and only then
    blocks on rollout k.  Slots recycle at rollout boundaries — with a
    layer-major full-T datapath the rollout is the atomic scheduling
    quantum, so "admitting into a partially-drained rollout" means a
    new arrival is transferred and queued behind the in-flight rollout
    mid-T-loop instead of waiting for it to drain; per-timestep
    preemption would need state-carrying kernels (see ROADMAP, real-TPU
    item).
  * **Deadlines** are admission deadlines: an entry whose deadline
    passes before a worker seats it resolves as an explicit ``timeout``
    result (span ``evict``) — never a hung future.  Once seated, a
    request always completes its rollout.
  * ``close(drain=True)`` is **graceful drain**: admission stops
    (queue closes), workers flush everything queued plus their
    pipelines, then join.  ``drain=False`` cancels the backlog with
    explicit ``cancelled`` results.

Bit-exactness: the tier reuses the SAME bucket-cached AOT executables
as the synchronous engine and the forward is batch-row independent, so
a request's logits are identical whichever tier (and whichever cohort)
served it — the parity test pins this per request at a fixed bucket.

Observability rides the engine's registry: the worker's phase spans,
always recorded, each opened through ``engine.phase`` — ``take`` and
``fill`` (slot seating plus the engine's ``fill``) by the worker loop,
the rest by the engine's ``launch`` and ``finish_step``; the
per-request ``enqueue`` / ``drain`` spans come from the engine too.
The tier adds ``evict`` / ``recycle`` spans, the
``snn_serve_slot_occupancy`` and ``snn_serve_inflight`` gauges, and
submit/evict/cancel counters.
The ``queue_growth`` watchdog rule works unchanged — the tier keeps
``snn_serve_queue_depth`` current from ITS queue.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from repro.deploy.engine import InflightStep, SNNRequest, SNNServeEngine
from repro.serve_async.futures import (
    STATUS_CANCELLED,
    STATUS_OK,
    STATUS_TIMEOUT,
    AsyncResult,
    SNNFuture,
)
from repro.serve_async.queue import Closed, Full, QueueEntry, RequestQueue
from repro.serve_async.slots import SlotManager


@dataclasses.dataclass
class AsyncEngineConfig:
    #: worker threads driving the engine hooks.  One saturates a single
    #: device; more help when per-request host work (image fill, drain
    #: bookkeeping) is the bottleneck.
    workers: int = 1
    #: dispatched-but-uncollected rollouts each worker keeps in flight
    #: (2 = classic double buffering: form/transfer k+1 under k).
    max_inflight: int = 2
    #: bounded admission; 0 = unbounded.  A full queue resolves the
    #: future as ``cancelled`` (detail "queue full") at submit time.
    queue_limit: int = 0
    #: admission deadline applied when ``submit`` gets none; None = no
    #: deadline.
    default_deadline_ms: Optional[float] = None
    #: how long an idle worker sleeps in ``take`` before rechecking
    #: shutdown (arrivals interrupt the wait immediately regardless).
    idle_wait_s: float = 0.05
    #: per-request latencies retained for the percentile estimates in
    #: ``stats()`` (futures carry exact per-request numbers; running
    #: totals in the engine stay exact regardless).
    reservoir: int = 8192


class AsyncSNNServeEngine:
    """Continuous-batching async tier (see module docstring).

    Composes rather than subclasses: ``engine`` is a fully-constructed
    synchronous :class:`SNNServeEngine` whose compile cache, accounting
    totals, instruments, and watchdog the tier reuses — the datapath
    and its contracts stay fixed while the scheduling layer grows.
    """

    def __init__(self, engine: SNNServeEngine,
                 acfg: Optional[AsyncEngineConfig] = None):
        self.engine = engine
        self.acfg = acfg or AsyncEngineConfig()
        if self.acfg.workers < 1:
            raise ValueError("need at least one worker")
        if self.acfg.max_inflight < 1:
            raise ValueError("need at least one in-flight rollout")
        self.obs = engine.obs
        self.queue = RequestQueue(maxsize=self.acfg.queue_limit)
        cap_per_worker = min(engine.ecfg.max_batch, engine.buckets[-1])
        self._cohort_cap = cap_per_worker
        self.slots = SlotManager(
            cap_per_worker * self.acfg.max_inflight * self.acfg.workers)

        self._lock = threading.Lock()     # uid counter, pending, totals
        self._uid = 0
        self._pending: Dict[int, QueueEntry] = {}
        self._reservoir: deque = deque(maxlen=self.acfg.reservoir)
        self.submitted = 0
        self.completed = 0
        self.timeouts = 0
        self.cancelled = 0

        self._threads: List[threading.Thread] = []
        self._closed = False

        m = self.obs
        self._m_queue_depth = m.gauge("snn_serve_queue_depth",
                                      "requests waiting for a batch")
        self._m_slot_occ = m.gauge("snn_serve_slot_occupancy",
                                   "held slots / slot capacity")
        self._m_inflight = m.gauge("snn_serve_inflight",
                                   "dispatched, uncollected rollouts")
        self._m_submitted = m.counter("snn_serve_submitted_total",
                                      "requests accepted at submit")
        self._m_evictions = m.counter("snn_serve_evictions_total",
                                      "deadline-expired requests evicted")
        self._m_cancelled = m.counter("snn_serve_cancelled_total",
                                      "requests cancelled at shutdown or "
                                      "admission")

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "AsyncSNNServeEngine":
        """Spawn the worker threads (idempotent).  Call ``warmup()``
        first if compile time must stay off the serving path."""
        if self._threads:
            return self
        for wid in range(self.acfg.workers):
            t = threading.Thread(target=self._worker, args=(wid,),
                                 name=f"snn-serve-worker-{wid}",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def warmup(self) -> int:
        return self.engine.warmup()

    def close(self, drain: bool = True, timeout: Optional[float] = None
              ) -> dict:
        """Graceful shutdown: stop admission, then either flush the
        backlog through the engine (``drain=True``) or resolve it with
        explicit ``cancelled`` results.  Joins the workers.  Idempotent;
        returns the final :meth:`stats`."""
        with self._lock:
            if self._closed:
                return self.stats()
            self._closed = True
        if not drain:
            for entry in self.queue.drain_all():
                self._cancel(entry, "engine shut down without draining")
        self.queue.close()
        for t in self._threads:
            t.join(timeout=timeout)
        # never-started (or join-timed-out) workers leave a backlog; a
        # graceful close still owes those requests an answer
        leftovers = self.queue.drain_all()
        if leftovers and drain and not any(t.is_alive()
                                           for t in self._threads):
            self._serve_inline(leftovers)
        else:
            for entry in leftovers:
                self._cancel(entry, "engine closed before admission")
        self._m_queue_depth.set(0)
        self.engine.close(drain=True)
        return self.stats()

    def __enter__(self) -> "AsyncSNNServeEngine":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(drain=exc_type is None)

    # -- request path --------------------------------------------------------

    def submit(self, image: np.ndarray,
               deadline_ms: Optional[float] = None) -> SNNFuture:
        """Emplace-on-arrival admission: validate, stamp, queue, return
        the future — all on the caller's thread.  Thread-safe; uids are
        assigned internally and returned on the future."""
        if self._closed:
            raise Closed("async engine is closed")
        with self._lock:
            uid = self._uid
            self._uid += 1
            self.submitted += 1
        req = SNNRequest(uid=uid, image=np.asarray(image, np.float32))
        self.engine.validate_request(req)
        fut = SNNFuture(uid)
        req._t0 = time.perf_counter()
        if deadline_ms is None:
            deadline_ms = self.acfg.default_deadline_ms
        deadline = None if deadline_ms is None \
            else req._t0 + deadline_ms / 1e3
        entry = QueueEntry(req=req, future=fut, deadline=deadline)
        try:
            self.queue.put(entry)
        except (Full, Closed) as e:
            self._cancel(entry, str(e))
            return fut
        self._m_submitted.inc()
        depth = len(self.queue)
        self._m_queue_depth.set(depth)
        self.obs.event("enqueue", uid=uid, queue_depth=depth)
        return fut

    # -- worker path ---------------------------------------------------------

    def _worker(self, wid: int) -> None:
        inflight: deque = deque()
        while True:
            want = min(self._cohort_cap, self.slots.free_count())
            if want > 0:
                # poll when a rollout is in flight (its compute is the
                # batching window); otherwise sleep until an arrival or
                # shutdown wakes us
                timeout = 0.0 if inflight else self.acfg.idle_wait_s
                with self.engine.phase("take") as ph:
                    ready, expired = self.queue.take(want, timeout=timeout)
                    for entry in expired:
                        self._evict(entry)
                    if ready:
                        ph.cohort, ph.n = self.engine.new_cohort(), len(ready)
                        ph.bucket = self.engine.bucket_for(ph.n)
                st = self._dispatch(ready, ph.cohort) if ready else None
                if st is not None:
                    inflight.append(st)
                    self._m_inflight.set(len(inflight))
                    if len(inflight) < self.acfg.max_inflight:
                        continue        # keep the transfer pipe full
            if inflight:
                self.engine.finish_step(inflight.popleft(),
                                        sink=self._sink)
                self._m_inflight.set(len(inflight))
                continue
            if self.queue.closed and len(self.queue) == 0:
                return
            if want == 0:
                # every slot is held by a peer's in-flight rollout;
                # yield until one drains
                time.sleep(0.0005)

    def _dispatch(self, ready: List[QueueEntry], cohort: int
                  ) -> Optional[InflightStep]:
        """Seat ``ready`` in slots and fill its bucket array, one ``fill``
        phase of cohort ``cohort``, then launch it on the engine."""
        eng = self.engine
        batch: List[SNNRequest] = []
        with eng.phase("fill", cohort) as ph:
            t_admit = ph.t0
            for entry in ready:
                slot = self.slots.acquire(entry.req.uid)
                if slot is None:        # lost a race to a peer worker
                    self.queue.requeue(entry)
                    continue
                entry.slot = slot
                entry.req.queue_s = t_admit - entry.req._t0
                with self._lock:
                    self._pending[entry.req.uid] = entry
                batch.append(entry.req)
            self._m_queue_depth.set(len(self.queue))
            self._m_slot_occ.set(self.slots.occupancy())
            if not batch:               # whole cohort lost the race
                return None
            images = eng.fill(batch, ph)
        return eng.launch(batch, images, cohort)

    def _sink(self, batch: List[SNNRequest]) -> None:
        """finish_step's drain hook for a whole cohort: one round on the
        tier's lock and one on the slots' for the cohort, then resolve
        each future — results never pile up in ``engine.done``.  The
        per-request ``recycle`` events run only on an enabled registry."""
        with self._lock:
            entries = [self._pending.pop(req.uid) for req in batch]
            self.completed += len(batch)
            self._reservoir.extend((req.latency_s, req.queue_s)
                                   for req in batch)
        released = self.slots.release_many([e.slot for e in entries])
        if self.obs.enabled:
            for entry, (uid, held_s) in zip(entries, released):
                self.obs.event("recycle", slot=entry.slot, uid=uid,
                               held_us=held_s * 1e6)
        self._m_slot_occ.set(self.slots.occupancy())
        for entry, req in zip(entries, batch):
            entry.future.resolve(AsyncResult(
                uid=req.uid, status=STATUS_OK, logits=req.logits,
                pred=req.pred, latency_s=req.latency_s, queue_s=req.queue_s,
                compute_s=req.compute_s))
        # Hand the interpreter lock to the threads these futures woke:
        # callers in this process can then submit their next requests
        # before the worker polls the queue for the next cohort.  Without
        # it the worker finds the queue empty and blocks on the rollout
        # still in flight, so only one rollout is on the device at a time.
        time.sleep(0)

    def _evict(self, entry: QueueEntry) -> None:
        waited = time.perf_counter() - entry.req._t0
        self._m_evictions.inc()
        self.obs.event("evict", uid=entry.req.uid,
                       waited_us=waited * 1e6)
        with self._lock:
            self.timeouts += 1
        entry.future.resolve(AsyncResult(
            uid=entry.req.uid, status=STATUS_TIMEOUT, latency_s=waited,
            queue_s=waited,
            detail=f"admission deadline expired after {waited * 1e3:.1f}ms"))

    def _cancel(self, entry: QueueEntry, detail: str) -> None:
        self._m_cancelled.inc()
        with self._lock:
            self.cancelled += 1
        entry.future.resolve(AsyncResult(
            uid=entry.req.uid, status=STATUS_CANCELLED, detail=detail))

    def _serve_inline(self, entries: List[QueueEntry]) -> None:
        """Drain a leftover backlog on the closing thread (workers never
        started): cohort at a time through the same hooks."""
        now = time.perf_counter()
        live: List[QueueEntry] = []
        for entry in entries:
            if entry.expired(now):
                self._evict(entry)
            else:
                live.append(entry)
        for i in range(0, len(live), self._cohort_cap):
            st = self._dispatch(live[i:i + self._cohort_cap],
                                self.engine.new_cohort())
            self.engine.finish_step(st, sink=self._sink)

    # -- introspection -------------------------------------------------------

    def stats(self, wall_s: Optional[float] = None) -> dict:
        """Engine running totals + async-tier accounting.  Latency
        percentiles come from the tier's bounded reservoir (async
        results bypass ``engine.done``); ``latency_p99_ms`` joins the
        p50/p95 pair because tail latency under offered load is the
        number the open-loop benchmark exists to watch."""
        s = self.engine.stats(wall_s=wall_s)
        with self._lock:
            pairs = list(self._reservoir)
            submitted, completed = self.submitted, self.completed
            timeouts, cancelled = self.timeouts, self.cancelled
        lats = sorted(l for l, _ in pairs)
        queues = sorted(q for _, q in pairs)
        pctl = self.engine._pctl
        s["latency_p50_ms"] = 1e3 * pctl(lats, 0.5)
        s["latency_p95_ms"] = 1e3 * pctl(lats, 0.95)
        s["latency_p99_ms"] = 1e3 * pctl(lats, 0.99)
        s["queue_p95_ms"] = 1e3 * pctl(queues, 0.95)
        s["async"] = {
            "workers": self.acfg.workers,
            "max_inflight": self.acfg.max_inflight,
            "queue_depth": len(self.queue),
            "slot_capacity": self.slots.capacity,
            "slots_held": self.slots.occupied(),
            "slots_recycled": self.slots.total_recycled,
            "submitted": submitted,
            "completed": completed,
            "timeouts": timeouts,
            "cancelled": cancelled,
            "closed": self._closed,
        }
        return s

    def health(self) -> dict:
        """/healthz payload: the engine section plus the tier's queue /
        slot / worker state (``ObsServer(health_fn=async_engine.health)``)."""
        body = self.engine.health()
        body["async"] = {
            "queue_depth": len(self.queue),
            "queue_closed": self.queue.closed,
            "slots_held": self.slots.occupied(),
            "slot_capacity": self.slots.capacity,
            "workers_alive": sum(t.is_alive() for t in self._threads),
            "submitted": self.submitted,
            "completed": self.completed,
            "timeouts": self.timeouts,
            "cancelled": self.cancelled,
        }
        return body

    def attach_watchdog(self, watchdog) -> None:
        self.engine.attach_watchdog(watchdog)

    def graph_summary(self) -> str:
        return self.engine.graph_summary()
