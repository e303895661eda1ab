"""Per-layer / per-group device-time attribution for the graph executors.

``snn_serve_compute_us`` says what a whole forward costs; this module
says WHERE it goes.  :class:`AttributionExecutor` wraps any graph
executor (float / int / packaged — same delegation contract as
``TelemetryExecutor``) in a **timed mode**: after every node it blocks
(``jax.block_until_ready``) and records the blocked wall time per
``(kind, name)``.  Blocking per node serializes jax's async dispatch,
so a timed pass measures attribution, not end-to-end latency — like the
telemetry pass it is SAMPLED (one eager forward per ``--metrics`` run),
never inline on the serving path.

Each node also gets an analytic prediction from the same first-principles
model the committed ``benchmarks/BENCH_predicted.json`` rows are built
with — the kernel ``CostEstimate`` formulas (packed-weight bytes +
1-bit spike-plane traffic, MXU MACs) fed into the peaks of the chip the
forward runs on (``perfmodel.roofline.PEAKS``, looked up by
``device_kind``; integer layers against the int8 peak, the float stem
against bf16).  A CPU run names the chip it predicts (``peaks=``).
Every node emits:

  * ``snn_layer_time_us{layer=...,kind=...}`` — measured blocked wall
    time (gauge; the live /metrics series the acceptance criteria curl);
  * a ``predicted_vs_measured`` span — ``wall_us``, ``predicted_us``,
    ``ratio`` (host-over-roofline, the same join predicted_report
    commits for whole kernels) and the roofline ``bottleneck`` label —
    rendered as a duration event on the *layers* track by
    obs/chrometrace.py.

Fusion groups are attributed at the chain boundary (one row per group,
prediction summed over members) — interior planes never leave VMEM, so
finer timing does not exist by construction.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax

from repro.graph.executors import WrappedExecutor as _WrappedExecutor
from repro.graph.spec import Conv, Dense, Residual
from repro.obs.registry import MetricsRegistry, default_registry

from repro.perfmodel.roofline import (
    V5E,
    DevicePeaks,
    device_peaks,
    pick_bottleneck,
)


def _node_terms(spec, timesteps: int, batch: int, bits: int):
    """(int8 flops, float flops, bytes) of one node's full T-step
    rollout at batch ``batch``, or ``None`` for nodes the model has
    nothing to say about (encode / pool / readout)."""
    T, B = timesteps, batch
    int_path = bits <= 8                     # packed integer datapath

    def _conv_terms(c: Conv):
        w_bits = 32 if c.stem else bits      # stem stays on the float twin
        weight_bytes = c.k * c.k * c.c_in * c.c_out * w_bits / 8
        in_hw = c.out_hw * c.stride
        plane_bits = 32 if c.stem else 1     # analog currents in, else 1-bit
        act_bytes = T * B * (in_hw * in_hw * c.c_in * plane_bits
                             + c.out_hw * c.out_hw * c.c_out) / 8
        flops = 2.0 * c.macs * T * B
        on_int = int_path and not c.stem
        return (flops if on_int else 0.0, 0.0 if on_int else flops,
                weight_bytes + act_bytes)

    if isinstance(spec, Conv):
        return _conv_terms(spec)
    if isinstance(spec, Dense):
        flops = 2.0 * spec.macs * T * B
        bytes_ = spec.d_in * spec.d_out * bits / 8 \
            + T * B * (spec.d_in + spec.d_out) / 8
        return (flops, 0.0, bytes_) if int_path else (0.0, flops, bytes_)
    if isinstance(spec, Residual):
        terms = [_conv_terms(c)
                 for c in (*spec.body, *((spec.proj,) if spec.proj else ()))]
        return tuple(sum(t[i] for t in terms) for i in range(3))
    return None


def _roofline(int_flops: float, float_flops: float, bytes_: float,
              peaks: DevicePeaks) -> Dict:
    t_comp = int_flops / peaks.int8_ops + float_flops / peaks.bf16_flops
    t_mem = bytes_ / peaks.hbm_bw
    return {
        "predicted_us": round(max(t_comp, t_mem) * 1e6, 4),
        "compute_us": round(t_comp * 1e6, 4),
        "memory_us": round(t_mem * 1e6, 4),
        "bottleneck": pick_bottleneck(t_comp, t_mem, 0.0),
        "flops": int_flops + float_flops,
        "bytes": bytes_,
    }


def predict_node_us(spec, timesteps: int, batch: int, bits: int,
                    peaks: DevicePeaks = V5E) -> Optional[Dict]:
    """Roofline prediction for one node's full T-step rollout at batch
    ``batch`` on a chip with ``peaks``: compute term from MXU MACs (2
    ops/MAC; packed integer layers at the int8 peak, float layers at
    bf16), memory term from packed-weight bytes + 1-bit spike-plane
    traffic (the fused kernels' CostEstimate accounting).  Returns
    ``None`` for nodes the model has nothing to say about (encode /
    pool / readout)."""
    terms = _node_terms(spec, timesteps, batch, bits)
    return None if terms is None else _roofline(*terms, peaks)


class AttributionExecutor(_WrappedExecutor):
    """Timed wrapper: blocked wall time per node, roofline prediction
    alongside.  ``records`` rows: ``{"layer", "kind", "wall_us",
    "predicted_us", "bottleneck", "ratio"}`` in execution order."""

    kind = "attribution"

    def __init__(self, inner, registry: Optional[MetricsRegistry] = None,
                 prefix: str = "snn_layer",
                 peaks: Optional[DevicePeaks] = None):
        super().__init__(inner)
        # the chip the forward runs on; unknown kinds raise
        self.peaks = peaks if peaks is not None else device_peaks(
            jax.devices()[0].device_kind)
        self.obs = registry if registry is not None else default_registry()
        self.prefix = prefix
        self.records: List[Dict] = []
        self._batch = 0                     # set from the Encode input

    def encode(self, spec, images):
        self._batch = int(images.shape[0])
        return self._timed("encode", spec.name, spec,
                           lambda: self.inner.encode(spec, images))

    def conv(self, spec, x):
        return self._timed("conv", spec.name, spec,
                           lambda: self.inner.conv(spec, x))

    def pool(self, spec, x):
        return self._timed("pool", spec.name, spec,
                           lambda: self.inner.pool(spec, x))

    def residual(self, spec, x):
        return self._timed("residual", spec.name, spec,
                           lambda: self.inner.residual(spec, x))

    def fused_group(self, group, specs, x):
        return self._timed("fusion_group", group.name, list(specs),
                           lambda: self.inner.fused_group(group, specs, x))

    def dense(self, spec, x):
        return self._timed("dense", spec.name, spec,
                           lambda: self.inner.dense(spec, x))

    def readout(self, spec, x):
        return self._timed("readout", spec.name, spec,
                           lambda: self.inner.readout(spec, x))

    # -- the timed mode ------------------------------------------------------

    def _predict(self, spec_or_list) -> Optional[Dict]:
        cfg = self.inner.cfg
        bits = cfg.precision.bits if cfg.precision.quantized else 32
        if isinstance(spec_or_list, list):      # fusion group: sum members
            terms = [t for t in (_node_terms(s, cfg.timesteps, self._batch,
                                             bits) for s in spec_or_list)
                     if t is not None]
            if not terms:
                return None
            return _roofline(*(sum(t[i] for t in terms) for i in range(3)),
                             self.peaks)
        return predict_node_us(spec_or_list, cfg.timesteps, self._batch,
                               bits, self.peaks)

    def _timed(self, kind: str, name: str, spec, fn):
        t0 = time.perf_counter()
        out = fn()
        # block HERE: the wall below is this node's device+host share,
        # not whenever jax's async dispatch happens to flush
        jax.block_until_ready(out)
        wall_us = (time.perf_counter() - t0) * 1e6
        pred = self._predict(spec)
        row = {"layer": name, "kind": kind,
               "wall_us": round(wall_us, 2),
               "predicted_us": pred["predicted_us"] if pred else None,
               "bottleneck": pred["bottleneck"] if pred else None,
               "ratio": round(wall_us / pred["predicted_us"], 2)
               if pred and pred["predicted_us"] > 0 else None}
        self.records.append(row)
        labels = {"layer": name, "kind": kind}
        self.obs.gauge(f"{self.prefix}_time_us",
                       "blocked wall time of one timed forward, per node",
                       labels).set(wall_us)
        # "kind" is the JSONL line discriminator (exporters schema), so
        # the span carries the node kind as "node" — same convention as
        # the layer_telemetry spans
        span = {("node" if k == "kind" else k): v
                for k, v in row.items() if v is not None}
        self.obs.event("predicted_vs_measured", **span)
        return out


def timed_forward(cfg, params, images, package=None,
                  registry: Optional[MetricsRegistry] = None,
                  peaks: Optional[DevicePeaks] = None):
    """One eager TIMED forward of the model ``cfg`` describes — the
    attribution twin of ``instrumented_forward``: builds the graph,
    picks the float/int/packaged lowering, wraps it in
    :class:`AttributionExecutor`, runs it.  Returns ``(logits,
    records)`` and emits ``snn_layer_time_us`` + ``predicted_vs_measured``
    into ``registry`` (default: the process default).  ``peaks``: the
    chip to predict (default: the live device's entry)."""
    from repro.graph import build_graph, executor_for, run_graph

    graph = build_graph(cfg)
    ex = AttributionExecutor(executor_for(graph, params, package=package),
                             registry=registry, peaks=peaks)
    logits = run_graph(graph, ex, images)
    return logits, ex.records


def attribution_summary(records: List[Dict]) -> Dict:
    """Roll a timed pass up for humans/bench records: total measured
    wall, total predicted, the host-over-roofline ratio, and the
    heaviest node."""
    timed = [r for r in records if r["wall_us"] is not None]
    wall = sum(r["wall_us"] for r in timed)
    pred = sum(r["predicted_us"] or 0.0 for r in timed)
    top = max(timed, key=lambda r: r["wall_us"], default=None)
    return {
        "wall_us": round(wall, 1),
        "predicted_us": round(pred, 1),
        "host_over_roofline_x": round(wall / pred, 1) if pred else None,
        "hottest_layer": top["layer"] if top else None,
        "hottest_wall_us": round(top["wall_us"], 1) if top else None,
        "nodes": len(timed),
    }
