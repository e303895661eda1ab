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
``finish_step(st, sink=...)`` blocks, accounts, and hands each completed
request to ``sink``.  The asynchronous continuous-batching tier
(``repro.serve_async``) drives these hooks from worker threads,
pipelining the next microbatch's host->device transfer under the
current device step; ``close()`` gives both tiers graceful drain
semantics (flush the partial bucket, then refuse new work).

Observability: the engine binds instruments from a
:class:`repro.obs.MetricsRegistry` (the process default unless one is
passed) at construction — request/batch/compile-hit/miss counters,
queue-depth / batch-occupancy / padding-waste gauges, queue/compute/
latency histograms, and span events for enqueue -> admit -> compile ->
step -> drain.  With the default registry disabled (the default) every
instrument is a shared no-op, so the serving hot path pays only empty
method calls — the bench-gate serve baseline holds either way.  Each
device step also runs under a ``jax.profiler.TraceAnnotation`` named by
bucket, so ``--profile`` traces read as ``snn_serve_step/b<bucket>``
instead of anonymous dispatches.
"""

from __future__ import annotations

import dataclasses
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
    pad_frac: float


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

    def _executable(self, bucket: int):
        exe = self._compiled.get(bucket)
        if exe is None:
            with self._compile_lock:     # concurrent workers build once
                exe = self._compiled.get(bucket)
                if exe is not None:
                    self._m_compile_hit.inc()
                    return exe
                self._m_compile_miss.inc()
                t0 = time.perf_counter()
                cfg = self.cfg
                spec = jax.ShapeDtypeStruct(
                    (bucket, cfg.img_size, cfg.img_size, cfg.in_channels),
                    jnp.float32, sharding=self._batch_sharding)
                exe = jax.jit(self._fwd).lower(self.model, spec).compile()
                self._compiled[bucket] = exe
                self.compile_count += 1
                self.obs.event("compile", bucket=bucket, result="miss",
                               compile_us=(time.perf_counter() - t0) * 1e6)
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
                   queue_depth: Optional[int] = None) -> InflightStep:
        """Slot-level admission hook: dispatch one FORMED microbatch and
        return without blocking on the result.

        The split from :meth:`finish_step` is what the async tier
        (repro.serve_async) pipelines on: jax dispatch is asynchronous,
        so the host->device transfer and compute of this microbatch
        overlap whatever the caller does next — including forming and
        dispatching the next microbatch before collecting this one.
        The synchronous :meth:`step` simply calls the pair back to back.

        ``batch`` requests must already carry ``queue_s`` (enqueue ->
        admit) and ``_t0``; ``queue_depth`` is what the admit span
        reports as still waiting (defaults to the engine's own queue —
        the async tier passes its own queue's depth)."""
        n = len(batch)
        if n == 0:
            raise ValueError("begin_step needs a non-empty batch")
        if bucket is None:
            bucket = self.bucket_for(n)
        if queue_depth is None:
            queue_depth = len(self.queue)
        pad_frac = (bucket - n) / bucket
        self._m_occupancy.set(n / bucket)
        self._m_pad_waste.set(pad_frac)
        self.obs.event("admit", n=n, bucket=bucket, pad_frac=pad_frac,
                       queue_depth=queue_depth)
        exe = self._executable(bucket)

        images = np.zeros((bucket, self.cfg.img_size, self.cfg.img_size,
                           self.cfg.in_channels), np.float32)
        for i, req in enumerate(batch):
            images[i] = req.image
        t0 = time.perf_counter()
        # the annotation names this dispatch in --profile traces
        # (snn_serve_step/b<bucket>) — zero work when nothing is tracing
        with jax.profiler.TraceAnnotation(f"snn_serve_step/b{bucket}"):
            if self._batch_sharding is None:
                images = jnp.asarray(images)
            else:
                images = jax.device_put(images, self._batch_sharding)
            logits = exe(self.model, images)
        return InflightStep(batch=batch, bucket=bucket, n=n, logits=logits,
                            t0=t0, pad_frac=pad_frac)

    def finish_step(self, st: InflightStep,
                    sink: Optional[Callable[[SNNRequest], None]] = None
                    ) -> int:
        """Block on a dispatched microbatch, account it, and hand every
        completed request to ``sink`` (default: the ``done`` dict the
        synchronous ``pop_result`` drains — the async tier passes a sink
        that resolves futures instead, so ``done`` never grows there).
        Returns the number of requests completed."""
        logits = np.asarray(jax.block_until_ready(st.logits))
        dt = time.perf_counter() - st.t0
        bucket, n = st.bucket, st.n
        with self._acct_lock:
            self.per_bucket[bucket] = self.per_bucket.get(bucket, 0) + 1
            self.total_batches += 1
            self.total_compute_s += dt
            self.total_padded_slots += bucket - n
            self.total_slots += bucket
        self._m_batches.inc()
        self._m_compute_us.observe(dt * 1e6)
        self.obs.event("step", bucket=bucket, n=n, pad_frac=st.pad_frac,
                       compute_us=dt * 1e6)

        now = time.perf_counter()
        for i, req in enumerate(st.batch):
            req.image = None        # consumed — don't retain every input
            req.logits = logits[i]
            req.pred = int(np.argmax(logits[i]))
            req.compute_s = dt
            req.latency_s = now - req._t0
            with self._acct_lock:
                self.total_requests += 1
                self.total_latency_s += req.latency_s
                self.total_queue_s += req.queue_s
                self.total_request_compute_s += dt
                self.max_latency_s = max(self.max_latency_s, req.latency_s)
            self._m_requests.inc()
            self._m_queue_us.observe(req.queue_s * 1e6)
            self._m_latency_us.observe(req.latency_s * 1e6)
            self.obs.event("drain", uid=req.uid,
                           queue_us=req.queue_s * 1e6,
                           compute_us=req.compute_s * 1e6,
                           latency_us=req.latency_s * 1e6)
            if sink is None:
                self.done[req.uid] = req
            else:
                sink(req)
        if self._watchdog is not None:
            # after the drain loop: the histograms/gauges the rules read
            # already include this microbatch
            self._watchdog.check()
        return n

    def step(self) -> int:
        """Serve one microbatch (up to max_batch queued requests, padded
        to the next bucket).  Returns the number of requests completed."""
        if not self.queue:
            return 0
        batch: List[SNNRequest] = []
        cap = min(self.ecfg.max_batch, self.buckets[-1])
        t_admit = time.perf_counter()
        while self.queue and len(batch) < cap:
            req = self.queue.popleft()
            req.queue_s = t_admit - req._t0
            batch.append(req)
        self._m_queue_depth.set(len(self.queue))
        return self.finish_step(self.begin_step(batch))

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
