"""Pluggable executors: lower one :class:`ModelGraph` three ways.

An executor is the lowering strategy for a graph traversal — it decides
what each node *kind* does, while the graph decides which nodes exist
and in what order.  The three deployment-relevant lowerings:

``FloatExecutor``     float/BPTT twin: fake-quant (QAT) conv/dense when
                      the precision is quantized, average pools, rate-
                      preserving residual merge.  The training path.
``IntExecutor``       per-call integer path: every post-stem layer runs
                      the fused packed kernels (kernels/fused_conv +
                      fused_nce), quantizing from the float params on
                      each call; binary-preserving max pools and spike-OR
                      residual merge.
``PackagedExecutor``  the same integer lowering fed from a
                      ``repro.deploy.DeployedModel`` — pre-packed weights
                      + folded per-channel thresholds, zero quantization
                      on the hot path.  Bit-exact with IntExecutor.

``CalibratingExecutor`` is the fourth traversal: Diehl-style threshold
balancing as a float forward with a per-layer gain hook (see
graph/passes.py).

Every executor records a ``trace`` of ``(kind, name, stride)`` rows in
execution order.  Because pool and merge ops are *methods of the
executor*, not copies of the topology, the float and integer paths
cannot disagree about which layers exist — the parity tests assert the
traces are identical across all three executors.

The shared traversal is :func:`run_graph`; models/snn_cnn.apply is now a
thin shim over it.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.snn_layers import (
    avgpool_t,
    maxpool_t,
    readout_apply,
    spiking_conv_apply,
    spiking_conv_group_int_apply,
    spiking_conv_int_apply,
    spiking_dense_apply,
    spiking_dense_int_apply,
)
from repro.graph import fusion as _fusion
from repro.graph.spec import (
    Conv,
    Dense,
    Encode,
    ModelGraph,
    Pool,
    Readout,
    Residual,
    get_path,
)


def _record_rate(rates, x) -> None:
    if rates is not None:
        rates.append(float(jnp.mean(x.astype(jnp.float32))))


class Executor:
    """Node-kind contract shared by every lowering.

    Subclasses implement the private hooks (``_conv``, ``_pool``,
    ``_merge``, ``_dense``); the public methods own trace recording and
    the residual-block wiring so the block structure is lowered exactly
    once, here, for every executor.
    """

    kind = "base"

    #: whether this lowering consumes :class:`FusionGroup` annotations
    #: (multi-layer VMEM-resident rollouts).  Fusion is an integer-
    #: datapath deployment concept: the float/BPTT twin always lowers
    #: per layer, so grouped and ungrouped training are identical.
    supports_groups = False

    def __init__(self, graph: ModelGraph, params):
        self.graph = graph
        self.cfg = graph.cfg
        self.lif = graph.cfg.lif
        self.params = params
        self.trace: List[Tuple] = []

    def param(self, spec):
        """The spec's float params, resolved by its dotted path."""
        return get_path(self.params, spec.name)

    # -- public node methods (shared wiring + trace) -------------------------
    def encode(self, spec: Encode, images: jnp.ndarray) -> jnp.ndarray:
        self.trace.append(("encode", spec.name, 1))
        return jnp.broadcast_to(images, (spec.timesteps, *images.shape))

    def conv(self, spec: Conv, x: jnp.ndarray) -> jnp.ndarray:
        self.trace.append(("conv", spec.name, spec.stride))
        return self._conv(spec, x)

    def pool(self, spec: Pool, x: jnp.ndarray) -> jnp.ndarray:
        self.trace.append(("pool", spec.name, 1))
        return self._pool(spec, x)

    def residual(self, spec: Residual, x: jnp.ndarray) -> jnp.ndarray:
        self.trace.append(("residual", spec.name, spec.stride))
        group = _fusion.body_group(self.graph, spec) \
            if (self.graph.groups and self.supports_groups) else None
        if group is not None:
            # body chain as one fused rollout; the shortcut still reads
            # the pre-body plane, so only the body joins the group
            h = self.fused_group(group, spec.body, x)
        else:
            h = x
            for body_conv in spec.body:
                h = self.conv(body_conv, h)
        sc = self.conv(spec.proj, x) if spec.proj is not None else x
        return self._merge(h, sc)

    def fused_group(self, group, specs, x: jnp.ndarray) -> jnp.ndarray:
        """Lower a whole fusion group's member chain in one kernel call.
        Only group-aware lowerings implement this; ``run_graph`` and
        ``residual`` never route here unless ``supports_groups``."""
        raise NotImplementedError(
            f"{type(self).__name__} does not lower fusion groups")

    def dense(self, spec: Dense, x: jnp.ndarray) -> jnp.ndarray:
        self.trace.append(("dense", spec.name, 1))
        return self._dense(spec, x)

    def readout(self, spec: Readout, x: jnp.ndarray) -> jnp.ndarray:
        self.trace.append(("readout", spec.name, 1))
        if spec.spatial_mean:
            x = jnp.mean(x, axis=(2, 3))    # (T, B, H, W, C) -> (T, B, C)
        return readout_apply(self.param(spec), x)

    # -- lowering hooks ------------------------------------------------------
    def _conv(self, spec: Conv, x):
        raise NotImplementedError

    def _pool(self, spec: Pool, x):
        raise NotImplementedError

    def _merge(self, h, sc):
        raise NotImplementedError

    def _dense(self, spec: Dense, x):
        raise NotImplementedError


class FloatExecutor(Executor):
    """Float/BPTT lowering: the surrogate-gradient training path.  With a
    quantized precision the conv/dense weights go through QAT fake-quant
    (the forward the paper trains with)."""

    kind = "float"

    def __init__(self, graph: ModelGraph, params):
        super().__init__(graph, params)
        pc = graph.cfg.precision
        self.pc = pc if pc.quantized else None

    def _conv(self, spec, x):
        return spiking_conv_apply(self.param(spec), x, self.lif, self.pc,
                                  stride=spec.stride)

    def _pool(self, spec, x):
        return avgpool_t(x, spec.window)

    def _merge(self, h, sc):
        return (h + sc) * 0.5   # spike-rate-preserving residual merge

    def _dense(self, spec, x):
        return spiking_dense_apply(self.param(spec), x, self.lif, self.pc)


class IntExecutor(FloatExecutor):
    """Per-call integer lowering: post-stem layers run the fused packed
    kernels, re-quantizing the float params on every call.  The stem conv
    consumes direct-encoded analog currents, so it stays on the float
    twin (fake-quant included) and casts its binary spikes to int32 for
    the packed datapath.  Pools become binary-preserving max pools (an OR
    for {0,1} planes) and the residual merge a spike OR, so inter-layer
    traffic stays 1-bit packable."""

    kind = "int"
    supports_groups = True

    def fused_group(self, group, specs, x: jnp.ndarray) -> jnp.ndarray:
        """One fused kernel call for the whole member chain: inter-member
        1-bit planes stay in VMEM (kernels/fused_group).  Trace rows are
        the SAME per-member rows the ungrouped lowering records — fusion
        changes where planes live, not which layers exist, so the
        executor-parity contract on traces is preserved."""
        members, layers = [], []
        for spec in specs:
            if isinstance(spec, Conv):
                self.trace.append(("conv", spec.name, spec.stride))
                members.append(("conv", self._operands(spec, "qct")))
                layers.append(spec.name)
            else:
                self.trace.append(("pool", spec.name, 1))
                members.append(("pool", spec.window))
        return spiking_conv_group_int_apply(members, x, self.lif,
                                            self.cfg.precision,
                                            layers=tuple(layers))

    def _operands(self, spec, key: str) -> dict:
        """Where the packed layer's weights come from — the one hook the
        packaged lowering overrides.  ``key`` is the packed-tensor kwarg
        of the target int twin (``qct`` conv / ``qt`` dense)."""
        return {"params": self.param(spec)}

    def _conv(self, spec, x):
        if spec.stem:
            return super()._conv(spec, x).astype(jnp.int32)
        kw = self._operands(spec, "qct")
        return spiking_conv_int_apply(kw.pop("params"), x, self.lif,
                                      self.cfg.precision,
                                      stride=spec.stride, layer=spec.name,
                                      **kw)

    def _pool(self, spec, x):
        return maxpool_t(x, spec.window)

    def _merge(self, h, sc):
        return jnp.maximum(h, sc)   # spike OR: binary-preserving merge

    def _dense(self, spec, x):
        kw = self._operands(spec, "qt")
        return spiking_dense_int_apply(kw.pop("params"), x, self.lif,
                                       self.cfg.precision, **kw)


class PackagedExecutor(IntExecutor):
    """Integer lowering fed from a deploy package: identical traversal
    and kernels as :class:`IntExecutor`, but every packed layer's
    operands (weights + folded per-channel thresholds) come from the
    ``DeployedModel`` — the hot path never touches the quantizer.
    ``params`` only needs the float leaves (stem + head), which is
    exactly ``package.float_params``."""

    kind = "packaged"

    def __init__(self, graph: ModelGraph, params, package):
        super().__init__(graph, params)
        self.package = package
        want = {s.name for s in graph.packable_specs()}
        have = set(package.layers)
        if want != have:
            raise ValueError(
                f"deploy package layers desync the model graph: "
                f"missing={sorted(want - have)} extra={sorted(have - want)}")

    def _operands(self, spec, key: str) -> dict:
        lp = self.package.layers[spec.name]
        return {"params": None, key: lp.qt, "threshold_q": lp.theta_q}


class WrappedExecutor:
    """Delegating base for instrumenting wrappers (obs telemetry, obs
    time attribution): forwards every node method plus ``trace`` /
    ``supports_groups`` to ``inner``, so :func:`run_graph` sees a normal
    executor and any lowering (including future ones) can be wrapped
    without touching graph code.  Subclasses override exactly the node
    methods they want to observe; the trace stays on the inner executor,
    so executor-parity tests hold through any wrapper stack."""

    kind = "wrapped"

    def __init__(self, inner):
        self.inner = inner

    @property
    def trace(self):
        return self.inner.trace

    @property
    def supports_groups(self):
        return getattr(self.inner, "supports_groups", False)

    def encode(self, spec, images):
        return self.inner.encode(spec, images)

    def conv(self, spec, x):
        return self.inner.conv(spec, x)

    def pool(self, spec, x):
        return self.inner.pool(spec, x)

    def residual(self, spec, x):
        return self.inner.residual(spec, x)

    def fused_group(self, group, specs, x):
        return self.inner.fused_group(group, specs, x)

    def dense(self, spec, x):
        return self.inner.dense(spec, x)

    def readout(self, spec, x):
        return self.inner.readout(spec, x)


# ---------------------------------------------------------------------------
# the shared traversal
# ---------------------------------------------------------------------------

def run_graph(graph: ModelGraph, executor: Executor, images: jnp.ndarray,
              rates: Optional[list] = None) -> jnp.ndarray:
    """Drive one forward pass of ``graph`` under ``executor``.

    ``images`` is (B, H, W, C) analog input; returns (B, n_classes)
    logits.  ``rates`` (a list, eager-only) collects each spiking
    layer's mean firing rate — recorded after every top-level Conv,
    after every Residual merge, and after every Dense, matching the
    historical ``apply_with_rates`` instrumentation points.

    Fusion groups: when the graph carries :class:`FusionGroup`
    annotations and the executor ``supports_groups``, each top-level
    group's member chain lowers through ``executor.fused_group`` in one
    kernel call (residual-body groups are handled inside
    ``Executor.residual``).  ``rates`` needs every member's output
    plane, which a fused chain keeps in VMEM, so rate-instrumented runs
    lower top-level groups per member — bit-exact with the fused chain,
    just with the HBM round trips the instrumentation requires.
    """
    fused_at = {}
    if graph.groups and executor.supports_groups and rates is None:
        top_index = {node.name: i for i, node in enumerate(graph.nodes)}
        for g in graph.groups:
            if g.members[0] in top_index:       # residual bodies are not
                fused_at[top_index[g.members[0]]] = g

    x: jnp.ndarray = images
    i = 0
    while i < len(graph.nodes):
        node = graph.nodes[i]
        group = fused_at.get(i)
        if group is not None:
            specs = graph.nodes[i:i + len(group.members)]
            x = executor.fused_group(group, specs, x)
            i += len(group.members)
            continue
        if isinstance(node, Encode):
            x = executor.encode(node, x)
        elif isinstance(node, Conv):
            x = executor.conv(node, x)
            _record_rate(rates, x)
        elif isinstance(node, Pool):
            x = executor.pool(node, x)
        elif isinstance(node, Residual):
            x = executor.residual(node, x)
            _record_rate(rates, x)
        elif isinstance(node, Dense):
            x = x.reshape(x.shape[0], x.shape[1], -1)   # (T, B, feat)
            x = executor.dense(node, x)
            _record_rate(rates, x)
        elif isinstance(node, Readout):
            return executor.readout(node, x)
        else:  # pragma: no cover — new spec kinds must be wired here
            raise TypeError(f"no lowering for node {type(node).__name__}")
        i += 1
    raise ValueError("graph has no Readout node")


def executor_for(graph: ModelGraph, params, package=None) -> Executor:
    """Pick the lowering the config + operands ask for: packaged when a
    deploy package is supplied, per-call integer when ``cfg.int_path``,
    float/BPTT otherwise."""
    if package is not None:
        if not graph.cfg.int_path:
            raise ValueError("a deploy package drives the integer path "
                             "only (cfg needs int_deploy + quantized)")
        return PackagedExecutor(graph, params, package)
    if graph.cfg.int_path:
        return IntExecutor(graph, params)
    return FloatExecutor(graph, params)
