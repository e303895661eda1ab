"""Batched serve engine for packed spiking models.

The spiking analogue of serve/engine.py's continuous-batching LM engine:
requests are single rate-coded inferences (one image in, one logit
vector out after T timesteps), so there is no KV state to keep live —
the scheduling problem collapses to micro-batching.  The engine pulls up
to ``max_batch`` queued requests per step, pads them to the smallest
configured batch **bucket**, and runs one jit-compiled forward of the
:class:`~repro.deploy.package.DeployedModel` per bucket shape (the
packaged-executor lowering of the model graph —
``repro.graph.PackagedExecutor``; the engine itself never touches the
quantizer or the topology).

Buckets are the latency/compile trade: XLA specializes on the batch
dimension, so serving raw ragged batch sizes would recompile on every
new size.  The engine AOT-compiles (``jit.lower().compile()``) one
executable per bucket on first use and caches it — after warmup a mixed
size request stream runs with ZERO recompiles (``compile_count`` stays
at the bucket count; tests assert on it).  The packed model rides as a
pytree *argument* of the compiled forward, not as baked-in constants,
so hot-swapping a package never invalidates the cache.

``data_parallel=True`` wraps the forward in a ``shard_map`` over the
local devices' ``data`` axis (bucket sizes round up to a device
multiple) — the single-host version of the production mesh in
launch/mesh.py.  The package is replicated onto every device once, at
construction, and each microbatch is placed split over the axis before
dispatch, so every device runs its own slice of the bucket.

Accounting: every request records its latency SPLIT — ``queue_s``
(enqueue -> bucket admit: the batch-formation share the ROADMAP calls
the current p95 bottleneck) separately from ``compute_s`` (the batched
forward's share); ``stats()`` aggregates throughput (img/s), per-bucket
batch counts, padding waste (padded slots / bucket slots), and the
compile count.  benchmarks/serve_bench.py turns these into
BENCH_serve.json.

Scheduling hooks: ``step()`` is a thin composition of two slot-level
hooks — ``begin_step(batch)`` dispatches a formed microbatch WITHOUT
blocking (jax async dispatch; returns an :class:`InflightStep`) and
``finish_step(st, sink=...)`` blocks, accounts, and hands the completed
cohort to ``sink`` in one call.  The asynchronous continuous-batching tier
(``repro.serve_async``) drives these hooks from worker threads,
pipelining the next microbatch's host->device transfer under the
current device step; ``close()`` gives both tiers graceful drain
semantics (flush the partial bucket, then refuse new work).

Observability: the engine binds instruments from a
:class:`repro.obs.MetricsRegistry` (the process default unless one is
passed) at construction — request/batch/compile-hit/miss counters,
queue-depth / batch-occupancy / padding-waste gauges, queue/compute/
latency histograms, and per-request ``enqueue`` / ``drain`` span
events.  With the default registry disabled (the default) every
instrument is a shared no-op, so the serving hot path pays only empty
method calls — the bench-gate serve baseline holds either way.

The worker's phases are recorded whatever the registry's state: one
:class:`repro.obs.PhaseSpan` per phase per forward, carrying the id of
the cohort that caused it (``new_cohort``, drawn when a take yields a
cohort; see ``MetricsRegistry.phase``).  In loop order they follow one
another without overlap: ``take`` (the caller's pop from its queue),
``fill`` (seating the cohort and filling the bucket array), ``compile``
(a bucket-executable miss only), ``dispatch`` (host->device transfer
plus the executable call), ``wait`` (``block_until_ready``),
``readback`` (logits to the host) and ``resolve`` (accounting and the
cohort's sink, once per cohort).  Every site opens its phase with
:meth:`SNNServeEngine.phase`, which also runs the block under a
``jax.profiler.TraceAnnotation`` (:func:`annotation`), so a
``--profile`` trace names the host's phase on the device's clock.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.deploy.package import DeployedModel

ANNOTATION_PREFIX = "snn_serve_step/"


def annotation(phase: str, bucket: int = 0) -> str:
    """Profiler annotation of a worker phase: ``snn_serve_step/<phase>``;
    a dispatch keeps the name device traces have always shown,
    ``snn_serve_step/b<bucket>``, and a compile is
    ``snn_serve_step/compile/b<bucket>``."""
    if phase == "dispatch":
        return f"{ANNOTATION_PREFIX}b{bucket}"
    if phase == "compile":
        return f"{ANNOTATION_PREFIX}compile/b{bucket}"
    return ANNOTATION_PREFIX + phase


class Phase:
    """One worker phase as a context manager: its block runs under the
    profiler annotation ``name`` and, on exit, lands in ``registry``'s
    phase ring.  ``cohort``, ``bucket`` and ``n`` may be set inside the
    block, once they are known."""

    __slots__ = ("_registry", "_phase", "_name", "_ann", "t0", "cohort",
                 "bucket", "n")

    def __init__(self, registry, phase: str, name: str, cohort: int = -1,
                 bucket: int = 0, n: int = 0):
        self._registry, self._phase, self._name = registry, phase, name
        self.cohort, self.bucket, self.n = cohort, bucket, n

    def __enter__(self) -> "Phase":
        self.t0 = time.perf_counter()
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        self._registry.phase(self._phase, self.t0, time.perf_counter(),
                             self.cohort, self.bucket, self.n)


@dataclasses.dataclass
class SNNRequest:
    uid: int
    image: Optional[np.ndarray]      # (H, W, C) float in [0, 1]; dropped
                                     # (set to None) once served
    # filled by the engine:
    logits: Optional[np.ndarray] = None
    pred: Optional[int] = None
    latency_s: float = 0.0           # enqueue -> result (queue + compute
                                     # + drain bookkeeping)
    queue_s: float = 0.0             # enqueue -> bucket admit
    compute_s: float = 0.0           # the batched forward's share


@dataclasses.dataclass
class InflightStep:
    """One dispatched-but-not-collected microbatch: the handle
    :meth:`SNNServeEngine.begin_step` returns and
    :meth:`SNNServeEngine.finish_step` consumes.  ``logits`` is the
    device array of the in-flight forward — jax dispatch is
    asynchronous, so holding an InflightStep means the device (or the
    XLA CPU stream) is still working while the host forms the next
    microbatch.  The async tier (repro.serve_async) keeps a short deque
    of these to overlap host->device transfer with compute."""

    batch: List[SNNRequest]
    bucket: int
    n: int
    logits: object                  # un-materialized device array
    t0: float                       # perf_counter at dispatch
    cohort: int                     # id of the phase spans it caused


@dataclasses.dataclass
class SNNEngineConfig:
    max_batch: int = 8
    # batch-size buckets the engine compiles for; () = powers of two up
    # to max_batch.  A partial microbatch pads up to the next bucket.
    buckets: Tuple[int, ...] = ()
    # shard_map the forward over the local devices' data axis
    data_parallel: bool = False

    def resolved_buckets(self, n_dev: int = 1) -> Tuple[int, ...]:
        bks = self.buckets
        if not bks:
            bks, b = [], 1
            while b < self.max_batch:
                bks.append(b)
                b *= 2
            bks.append(self.max_batch)
        up = lambda b: -(-b // n_dev) * n_dev  # ceil to a device multiple
        return tuple(sorted({up(b) for b in bks}))


class SNNServeEngine:
    """Micro-batching serve loop over a packed SNN.

    ``model`` is a :class:`DeployedModel` (one-shot packed weights +
    folded thresholds) — the engine never touches the quantizer.
    """

    def __init__(self, model: DeployedModel, ecfg: SNNEngineConfig,
                 registry: Optional["obs.MetricsRegistry"] = None):
        cfg = model.cfg
        if not cfg.int_path:
            raise ValueError("SNNServeEngine serves the packed integer "
                             "path (cfg needs int_deploy + quantized)")
        self.model = model
        self.ecfg = ecfg
        self.cfg = cfg
        self.queue: deque = deque()
        self.done: Dict[int, SNNRequest] = {}
        self._closed = False
        # begin_step/finish_step may be driven from the async tier's
        # worker threads (repro.serve_async): the compile cache and the
        # O(1) accounting totals each get a lock; the hot path inside
        # one microbatch stays lock-free.
        self._compile_lock = threading.Lock()
        self._acct_lock = threading.Lock()

        self._mesh = None
        self._batch_sharding = None
        n_dev = 1
        if ecfg.data_parallel:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.launch.mesh import make_mesh

            n_dev = len(jax.devices())
            self._mesh = make_mesh((n_dev,), ("data",))
            self._batch_sharding = NamedSharding(self._mesh, P("data"))
            self.model = jax.device_put(model, NamedSharding(self._mesh, P()))
        self.buckets = ecfg.resolved_buckets(n_dev)
        # (phase, bucket) -> annotation name, built once per bucket
        self._annotations = {(p, b): annotation(p, b)
                             for p in obs.PHASES for b in (0,) + self.buckets}
        self._cohorts = itertools.count()
        self._fwd = self._build_forward()
        # bucket -> AOT-compiled executable; compiles happen exactly here
        self._compiled: Dict[int, jax.stages.Compiled] = {}
        self.compile_count = 0
        # O(1)-memory batch accounting (a long-lived server must not
        # accumulate per-batch records): bucket -> count, plus totals
        self.per_bucket: Dict[int, int] = {}
        self.total_batches = 0
        self.total_compute_s = 0.0
        self.total_padded_slots = 0
        self.total_slots = 0
        # ...and O(1) request accounting, so draining ``done`` through
        # pop_result never zeroes the serving stats
        self.total_requests = 0
        self.total_latency_s = 0.0
        self.total_queue_s = 0.0
        self.total_request_compute_s = 0.0
        self.max_latency_s = 0.0

        # Instruments bind once, here: with a disabled registry (the
        # process default unless the caller enabled/passed one) every
        # handle is the shared no-op and the loop below never branches
        # on "is observability on".
        self.obs = registry if registry is not None else \
            obs.default_registry()
        m = self.obs
        self._m_requests = m.counter("snn_serve_requests_total",
                                     "requests completed")
        self._m_batches = m.counter("snn_serve_batches_total",
                                    "microbatches served")
        self._m_compile_miss = m.counter("snn_serve_compile_total",
                                         "bucket executable builds",
                                         labels={"result": "miss"})
        self._m_compile_hit = m.counter("snn_serve_compile_total",
                                        "bucket executable cache hits",
                                        labels={"result": "hit"})
        self._m_queue_depth = m.gauge("snn_serve_queue_depth",
                                      "requests waiting for a batch")
        self._m_occupancy = m.gauge("snn_serve_batch_occupancy",
                                    "real requests / bucket slots, last "
                                    "batch")
        self._m_pad_waste = m.gauge("snn_serve_padding_waste",
                                    "padded slots / bucket slots, last "
                                    "batch")
        self._m_queue_us = m.histogram("snn_serve_queue_us",
                                       obs.LATENCY_EDGES_US,
                                       "enqueue -> bucket admit")
        self._m_compute_us = m.histogram("snn_serve_compute_us",
                                         obs.LATENCY_EDGES_US,
                                         "batched forward share")
        self._m_latency_us = m.histogram("snn_serve_latency_us",
                                         obs.LATENCY_EDGES_US,
                                         "enqueue -> drain")
        # optional SLO/drift watchdog (obs/watchdog.py) — checked once
        # per microbatch, after the batch's instruments are current
        self._watchdog = None

    def attach_watchdog(self, watchdog) -> None:
        """Attach an :class:`repro.obs.Watchdog`; ``step()`` evaluates
        its rules once per microbatch and ``health()`` folds its state
        into /healthz."""
        self._watchdog = watchdog

    def health(self) -> dict:
        """Liveness payload for the /healthz endpoint: queue depth,
        compile-cache state, running totals, watchdog state."""
        body = {
            "queue_depth": len(self.queue),
            "closed": self._closed,
            "undrained_results": len(self.done),
            "requests_total": self.total_requests,
            "batches_total": self.total_batches,
            "compile_cache": {
                "buckets": [int(b) for b in self.buckets],
                "compiled": sorted(int(b) for b in self._compiled),
                "compiles": self.compile_count,
            },
            "model": {
                "name": self.cfg.model, "bits": self.cfg.precision.bits,
                "timesteps": self.cfg.timesteps,
            },
        }
        if self._watchdog is not None:
            body["watchdog"] = self._watchdog.health()
        return body

    def graph_summary(self) -> str:
        """The served model's declarative graph, one line per node —
        including fusion-group membership + per-group VMEM footprint
        when the package's cfg carries fusion annotations (the engine's
        compiled forwards lower those chains through the fused group
        kernel)."""
        from repro.graph import build_graph

        return build_graph(self.cfg).summary()

    # -- compile plumbing ----------------------------------------------------

    def _build_forward(self):
        def fwd(package: DeployedModel, images: jnp.ndarray) -> jnp.ndarray:
            return package.apply(images)

        if self._mesh is None:
            return fwd
        from jax.sharding import PartitionSpec as P

        # model replicated, batch split over the data axis.  check_vma
        # off: pallas_call declares no varying-axes rule, and every
        # device's forward is independent anyway (no collectives)
        return jax.shard_map(fwd, mesh=self._mesh,
                             in_specs=(P(), P("data")),
                             out_specs=P("data"), check_vma=False)

    def phase(self, phase: str, cohort: int = -1, bucket: int = 0,
              n: int = 0) -> Phase:
        """Open worker phase ``phase`` (one of ``obs.PHASES``) of cohort
        ``cohort``: a :class:`Phase` that runs its block under the
        phase's annotation and records the span into this engine's
        registry on exit."""
        name = self._annotations.get((phase, bucket))
        if name is None:                # a bucket the config does not list
            name = self._annotations[phase, bucket] = annotation(phase,
                                                                 bucket)
        return Phase(self.obs, phase, name, cohort, bucket, n)

    def new_cohort(self) -> int:
        """A fresh cohort id, the span that caused a forward's phases."""
        return next(self._cohorts)

    def _executable(self, bucket: int, cohort: int = -1, n: int = 0):
        exe = self._compiled.get(bucket)
        if exe is None:
            with self._compile_lock:     # concurrent workers build once
                exe = self._compiled.get(bucket)
                if exe is not None:
                    self._m_compile_hit.inc()
                    return exe
                self._m_compile_miss.inc()
                cfg = self.cfg
                with self.phase("compile", cohort, bucket, n):
                    spec = jax.ShapeDtypeStruct(
                        (bucket, cfg.img_size, cfg.img_size,
                         cfg.in_channels),
                        jnp.float32, sharding=self._batch_sharding)
                    exe = jax.jit(self._fwd).lower(self.model,
                                                   spec).compile()
                self._compiled[bucket] = exe
                self.compile_count += 1
        else:
            self._m_compile_hit.inc()
        return exe

    def warmup(self) -> int:
        """Pre-compile every bucket (pulls compile time off the serving
        path).  Returns the number of executables built."""
        for b in self.buckets:
            self._executable(b)
        return len(self._compiled)

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    # -- request plumbing ----------------------------------------------------

    def validate_request(self, req: SNNRequest) -> None:
        """Admission check shared by the synchronous queue and the async
        tier's emplace-on-arrival path: the image must match the served
        model's geometry BEFORE it is accepted, so a bad request fails
        at submit time instead of poisoning a formed microbatch."""
        cfg = self.cfg
        want = (cfg.img_size, cfg.img_size, cfg.in_channels)
        if tuple(req.image.shape) != want:
            raise ValueError(f"request {req.uid}: image shape "
                             f"{tuple(req.image.shape)} != model {want}")

    def add_request(self, req: SNNRequest):
        if self._closed:
            raise RuntimeError(
                "engine is closed — close() drained the queue; build a "
                "new engine (or use repro.serve_async for live admission "
                "control)")
        self.validate_request(req)
        # perf_counter, NOT time.time(): latency deltas must come from a
        # monotonic clock — a wall-clock step (NTP slew, DST) would
        # corrupt p50/p95/max and flap the benchmark gate
        req._t0 = time.perf_counter()
        self.queue.append(req)
        self._m_queue_depth.set(len(self.queue))
        self.obs.event("enqueue", uid=req.uid, queue_depth=len(self.queue))

    # -- main loop -----------------------------------------------------------

    def begin_step(self, batch: List[SNNRequest], bucket: Optional[int] = None,
                   cohort: Optional[int] = None) -> InflightStep:
        """Slot-level admission hook: dispatch one FORMED microbatch and
        return without blocking on the result.

        The split from :meth:`finish_step` is what the async tier
        (repro.serve_async) pipelines on: jax dispatch is asynchronous,
        so the host->device transfer and compute of this microbatch
        overlap whatever the caller does next — including forming and
        dispatching the next microbatch before collecting this one.
        The synchronous :meth:`step` simply calls the pair back to back.

        ``batch`` requests must already carry ``queue_s`` (enqueue ->
        admit) and ``_t0``.  Records the ``fill``, ``compile`` (on a
        miss) and ``dispatch`` phases of cohort ``cohort`` (default: a
        fresh id).  A caller with more to do inside ``fill`` (the async
        tier seats slots) opens that phase itself and calls
        :meth:`fill` and :meth:`launch` in place of this."""
        if not batch:
            raise ValueError("begin_step needs a non-empty batch")
        if cohort is None:
            cohort = self.new_cohort()
        with self.phase("fill", cohort) as ph:
            images = self.fill(batch, ph, bucket)
        return self.launch(batch, images, cohort)

    def fill(self, batch: List[SNNRequest], ph: Phase,
             bucket: Optional[int] = None) -> np.ndarray:
        """The cohort's padded bucket array (``bucket`` rows, default the
        smallest that holds ``batch``), inside the open ``fill`` phase
        ``ph``, which it tells the bucket and row count."""
        n = len(batch)
        if bucket is None:
            bucket = self.bucket_for(n)
        ph.bucket, ph.n = bucket, n
        self._m_occupancy.set(n / bucket)
        self._m_pad_waste.set((bucket - n) / bucket)
        images = np.zeros((bucket, self.cfg.img_size, self.cfg.img_size,
                           self.cfg.in_channels), np.float32)
        for i, req in enumerate(batch):
            images[i] = req.image
        return images

    def launch(self, batch: List[SNNRequest], images: np.ndarray,
               cohort: int) -> InflightStep:
        """Transfer a filled bucket array and call its executable (the
        ``compile`` phase on a miss, then ``dispatch``)."""
        n, bucket = len(batch), images.shape[0]
        exe = self._executable(bucket, cohort, n)
        with self.phase("dispatch", cohort, bucket, n) as ph:
            if self._batch_sharding is None:
                images = jnp.asarray(images)
            else:
                images = jax.device_put(images, self._batch_sharding)
            logits = exe(self.model, images)
        return InflightStep(batch=batch, bucket=bucket, n=n, logits=logits,
                            t0=ph.t0, cohort=cohort)

    def finish_step(self, st: InflightStep,
                    sink: Optional[Callable[[List[SNNRequest]], None]] = None
                    ) -> int:
        """Block on a dispatched microbatch, account it, and hand the
        whole completed cohort to ``sink`` in one call (default: the
        ``done`` dict the synchronous ``pop_result`` drains — the async
        tier passes a sink that resolves futures instead, so ``done``
        never grows there).  Records the ``wait``, ``readback`` and
        ``resolve`` phases.  Returns the number of requests completed."""
        bucket, n, cohort = st.bucket, st.n, st.cohort
        with self.phase("wait", cohort, bucket, n):
            jax.block_until_ready(st.logits)
        with self.phase("readback", cohort, bucket, n):
            logits = np.asarray(st.logits)
        with self.phase("resolve", cohort, bucket, n):
            self._resolve(st, logits, sink)
        return n

    def _resolve(self, st: InflightStep, logits: np.ndarray,
                 sink: Optional[Callable[[List[SNNRequest]], None]]) -> None:
        """Resolve a finished cohort in one pass: one timestamp, one
        argmax, one accounting round for the whole cohort; only filling
        each request's result stays per request.  The per-request
        histograms and ``drain`` events run only on an enabled
        registry."""
        now = time.perf_counter()
        dt = now - st.t0
        bucket, n, batch = st.bucket, st.n, st.batch
        preds = logits[:n].argmax(axis=1).tolist()
        lats = [now - req._t0 for req in batch]
        with self._acct_lock:
            self.per_bucket[bucket] = self.per_bucket.get(bucket, 0) + 1
            self.total_batches += 1
            self.total_compute_s += dt
            self.total_padded_slots += bucket - n
            self.total_slots += bucket
            self.total_requests += n
            self.total_latency_s += sum(lats)
            self.total_queue_s += sum(req.queue_s for req in batch)
            self.total_request_compute_s += dt * n
            self.max_latency_s = max(self.max_latency_s, *lats)
        self._m_batches.inc()
        self._m_requests.inc(n)
        self._m_compute_us.observe(dt * 1e6)

        for req, row, pred, lat in zip(batch, logits, preds, lats):
            req.image = None        # consumed — don't retain every input
            req.logits = row
            req.pred = pred
            req.compute_s = dt
            req.latency_s = lat
        if self.obs.enabled:
            for req in batch:
                self._m_queue_us.observe(req.queue_s * 1e6)
                self._m_latency_us.observe(req.latency_s * 1e6)
                self.obs.event("drain", uid=req.uid,
                               queue_us=req.queue_s * 1e6,
                               compute_us=dt * 1e6,
                               latency_us=req.latency_s * 1e6)
        if sink is None:
            self.done.update((req.uid, req) for req in batch)
        else:
            sink(batch)
        if self._watchdog is not None:
            # after the drain loop: the histograms/gauges the rules read
            # already include this microbatch
            self._watchdog.check()

    def step(self) -> int:
        """Serve one microbatch (up to max_batch queued requests, padded
        to the next bucket).  Returns the number of requests completed."""
        if not self.queue:
            return 0
        batch: List[SNNRequest] = []
        cap = min(self.ecfg.max_batch, self.buckets[-1])
        with self.phase("take") as ph:
            t_admit = time.perf_counter()
            while self.queue and len(batch) < cap:
                req = self.queue.popleft()
                req.queue_s = t_admit - req._t0
                batch.append(req)
            self._m_queue_depth.set(len(self.queue))
            ph.cohort, ph.bucket, ph.n = (self.new_cohort(),
                                          self.bucket_for(len(batch)),
                                          len(batch))
        return self.finish_step(self.begin_step(batch, cohort=ph.cohort))

    def pop_result(self, uid: int) -> SNNRequest:
        """Remove and return a completed request.  Long-lived servers
        must drain ``done`` through here (or clear it) — the engine never
        evicts on its own.  Counts/throughput/avg/max in ``stats()`` come
        from running totals and survive draining; only the latency
        percentiles are limited to the results still held."""
        return self.done.pop(uid)

    def run_until_done(self, max_steps: int = 10_000) -> dict:
        for _ in range(max_steps):
            if not self.queue:
                break
            self.step()
        if self.queue:
            # returning normally here would silently truncate the stream:
            # throughput/latency stats would cover only the served prefix
            # while looking complete
            raise RuntimeError(
                f"run_until_done: {len(self.queue)} requests still queued "
                f"after max_steps={max_steps} — raise max_steps or drain "
                f"with step()")
        return self.stats()

    def close(self, drain: bool = True) -> dict:
        """Graceful shutdown: flush any partial bucket still queued
        (``drain=True``, the default) instead of stranding requests,
        then refuse further ``add_request`` calls.  ``drain=False``
        explicitly abandons the queue — the count of stranded requests
        goes into the ``close`` span so the abandonment is observable,
        never silent.  Idempotent; returns the final :meth:`stats`.

        The engine is also a context manager: ``with SNNServeEngine(...)
        as eng: ...`` drains on exit, so a crashing caller cannot leak a
        half-served queue."""
        if self._closed:
            return self.stats()
        drained = 0
        stranded = 0
        if drain:
            while self.queue:
                drained += self.step()
        else:
            stranded = len(self.queue)
            self.queue.clear()
        self._closed = True
        self._m_queue_depth.set(0)
        self.obs.event("close", drained=drained, stranded=stranded)
        return self.stats()

    def __enter__(self) -> "SNNServeEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(drain=exc_type is None)

    # -- accounting ----------------------------------------------------------

    def _pctl(self, lats: List[float], q: float) -> float:
        # nearest-rank percentile: ceil(q n) - 1, NOT int(q n) (which
        # selects the max for any n <= 1/(1-q))
        return lats[max(0, math.ceil(q * len(lats)) - 1)] if lats else 0.0

    def stats(self, wall_s: Optional[float] = None) -> dict:
        """Aggregate serving stats.  Counts, throughput, and avg/max
        latency come from O(1) running totals, so they stay correct after
        results are drained with :meth:`pop_result`; the latency
        percentiles are computed over the results still held in ``done``.
        Throughput is requests completed per second of batched compute
        (``total_compute_s``) — pass ``wall_s`` to rate against an
        externally measured wall instead (only meaningful when it spans
        every completed request)."""
        lats = sorted(r.latency_s for r in self.done.values())
        queues = sorted(r.queue_s for r in self.done.values())
        wall = wall_s if wall_s is not None else self.total_compute_s
        n = self.total_requests
        return {
            "requests": n,
            "batches": self.total_batches,
            "compiles": self.compile_count,
            "buckets": {str(k): v
                        for k, v in sorted(self.per_bucket.items())},
            "wall_s": wall,
            "images_per_s": n / max(wall, 1e-9),
            "latency_avg_ms": 1e3 * self.total_latency_s / n if n else 0.0,
            # the latency SPLIT: batch formation vs device compute —
            # the number that tells you whether to tune buckets or
            # kernels (ROADMAP: current p95 is batch-formation-bound)
            "queue_avg_ms": 1e3 * self.total_queue_s / n if n else 0.0,
            "compute_avg_ms":
                1e3 * self.total_request_compute_s / n if n else 0.0,
            "queue_p95_ms": 1e3 * self._pctl(queues, 0.95),
            "latency_p50_ms": 1e3 * self._pctl(lats, 0.5),
            "latency_p95_ms": 1e3 * self._pctl(lats, 0.95),
            "latency_max_ms": 1e3 * self.max_latency_s,
            # padded slots / bucket slots over every served batch: the
            # compute wasted forming full buckets from partial batches
            "padding_waste":
                self.total_padded_slots / max(self.total_slots, 1),
            "packed_mbytes": self.model.nbytes_packed() / 1e6,
            "compression_x": round(self.model.compression_ratio(), 2),
        }
