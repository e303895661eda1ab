"""Runtime observability: metrics registry, request tracing, SNN telemetry.

One registry serves every runtime surface (see obs/README.md for the
naming contract and the overhead policy):

  * the serve engine (deploy/engine.py) records its worker's phases
    (take -> fill -> compile -> dispatch -> wait -> readback -> resolve,
    one span each per forward, always on), traces requests through
    enqueue -> drain, and keeps queue-depth / batch-occupancy /
    padding-waste gauges current;
  * the trainer (train/trainer.py) records step time, loss, grad-norm
    and lr, and exposes levanter-style per-step callback hooks;
  * the graph layer yields per-layer spike rates, saturation/reset
    counts, and weight code-utilization histograms for any executor
    via :class:`~repro.obs.telemetry.TelemetryExecutor` — no kernel
    changes.

The LIVE plane sits on top of the same registry:

  * :class:`~repro.obs.server.ObsServer` — an in-process HTTP thread
    exposing ``/metrics`` (Prometheus text), ``/healthz`` (liveness +
    watchdog state) and ``/spans?since=`` (incremental span drain);
  * :mod:`~repro.obs.chrometrace` — the span ring exported as a
    Chrome/Perfetto ``trace_event`` JSON, requests flow-connected
    enqueue -> drain;
  * :class:`~repro.obs.attribution.AttributionExecutor` — per-layer
    blocked-wall-time attribution joined against the roofline
    prediction (``snn_layer_time_us``, ``predicted_vs_measured``);
  * :class:`~repro.obs.watchdog.Watchdog` — EWMA-baselined SLO/drift
    monitors that dump a flight-recorder artifact on trip.

The process default registry is DISABLED until something opts in
(``--metrics`` on a launcher, :func:`enable_default` in code); disabled,
every instrument is a shared no-op and the hot paths pay only an empty
method call; only the worker's phase spans still land in their bounded
ring, and each packed conv layer still leaves its lowering record.
``python -m repro.obs.validate`` schema-checks emitted
JSONL artifacts (``--trace`` for Chrome trace exports).
"""

from repro.obs.attribution import (  # noqa: F401
    AttributionExecutor,
    attribution_summary,
    predict_node_us,
    timed_forward,
)
from repro.obs.chrometrace import (  # noqa: F401
    export_chrome_trace,
    span_to_events,
    to_chrome_trace,
    validate_chrome_trace,
)
from repro.obs.exporters import (    # noqa: F401
    SCHEMA_VERSION,
    read_jsonl,
    to_prometheus,
    validate_jsonl,
    write_jsonl,
)
from repro.obs.server import (       # noqa: F401
    PROMETHEUS_CONTENT_TYPE,
    ObsServer,
    add_server_flag,
)
from repro.obs.watchdog import (     # noqa: F401
    Watchdog,
    WatchdogConfig,
    histogram_quantile,
)
from repro.obs.registry import (     # noqa: F401
    FRACTION_EDGES,
    LATENCY_EDGES_US,
    LOWERING_PATHS,
    NULL_INSTRUMENT,
    PHASES,
    Counter,
    Gauge,
    Histogram,
    Lowering,
    MetricsRegistry,
    PhaseSpan,
    default_registry,
    disable_default,
    enable_default,
)
from repro.obs.telemetry import (    # noqa: F401
    TelemetryExecutor,
    code_histogram,
    instrumented_forward,
    package_code_utilization,
    spike_stats,
)


def add_metrics_flag(ap, default_path: str) -> None:
    """The shared ``--metrics [PATH]`` launcher flag (twin of
    profiling.add_profile_flag): bare ``--metrics`` emits JSONL to
    ``default_path``, an explicit argument overrides the destination,
    omitted entirely leaves the default registry disabled."""
    ap.add_argument("--metrics", nargs="?", const=default_path, default=None,
                    metavar="PATH",
                    help="enable the metrics registry and write a JSONL "
                         f"snapshot to PATH (default {default_path}) on "
                         "exit; validate with python -m repro.obs.validate")
