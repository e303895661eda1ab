"""The paper's benchmark models: VGG-16-SNN and ResNet-18-SNN, and
ResNet-50-SNN at ImageNet geometry.

These are the networks behind L-SPINE's §III-D comparison (VGG-16:
CPU 23.97 s vs engine 4.83 ms INT2 / 16.94 ms INT8; ResNet-18: 34.43 s
vs 7.84/16.84 ms).  Spiking convolutional stacks with shift-add LIF
dynamics, trainable by BPTT + surrogate gradients, quantizable to the
packed L-SPINE format.

``scale`` shrinks every channel count (scale=1 is the paper-size model;
smoke tests use scale≈1/16).  Input: (B, H, W, C) analog images, encoded
with direct (constant-current) coding over T timesteps.

This module is a thin shim over the declarative model-graph API
(repro.graph): the topology is defined ONCE per family
(``graph.vgg_graph`` / ``graph.resnet_graph``) and every entry point
here — ``init``, ``calibrate``, ``apply``, ``apply_with_rates``,
``count_macs`` — is a traversal of that graph under the appropriate
executor:

  * float/BPTT training twin        (graph.FloatExecutor),
  * per-call integer deployment     (graph.IntExecutor — every
    post-stem layer through the fused packed kernels, re-quantizing
    per call), selected by ``cfg.int_deploy`` + a quantized precision,
  * packaged serving (``package=`` a ``repro.deploy.DeployedModel`` —
    pre-packed weights + folded thresholds, zero quantization on the
    hot path; graph.PackagedExecutor).  Bit-exact with the per-call
    path.

The plan constants and ``effective_plan`` live in repro.graph.build and
are re-exported here for backward compatibility.
"""

from __future__ import annotations

import dataclasses

from repro.core.lif import LIFConfig
from repro.graph import (
    build_graph,
    executor_for,
    graph_calibrate,
    graph_init,
    run_graph,
)
from repro.graph.build import (         # noqa: F401 — re-exported compat
    RESNET18_STAGES,
    VGG9_PLAN,
    VGG16_PLAN,
    _base_plan,
    effective_plan,
)
from repro.quant.formats import PrecisionConfig


@dataclasses.dataclass(frozen=True)
class SNNConfig:
    model: str = "vgg16"          # vgg16 | vgg9 | resnet18 | resnet50
    n_classes: int = 10
    in_channels: int = 3
    img_size: int = 32
    timesteps: int = 4
    scale: float = 1.0
    lif: LIFConfig = LIFConfig(leak_shift=3, threshold=1.0)
    precision: PrecisionConfig = PrecisionConfig(bits=16)
    # integer deployment: route every spiking layer after the
    # direct-encoded stem through the fused packed kernels
    # (kernels/fused_conv + fused_nce) instead of the float/fake-quant
    # training twins.  Requires a quantized ``precision``.
    int_deploy: bool = False
    # multi-layer fusion request (repro.graph.fusion.apply_fusion):
    # () = none, "auto" = planner-proposed groups, or an explicit
    # tuple-of-member-name-tuples.  Must stay hashable (configs key
    # caches) — lists are normalized to tuples by deploy_config / the
    # package loader.  Only the integer lowerings consume it.
    fusion: object = ()

    def ch(self, c: int) -> int:
        return max(8, int(c * self.scale))

    @property
    def int_path(self) -> bool:
        return self.int_deploy and self.precision.quantized

    def graph(self):
        """The declarative model graph this config describes."""
        return build_graph(self)


# ---------------------------------------------------------------------------
# graph-lowered entry points
# ---------------------------------------------------------------------------

def init(key, cfg: SNNConfig):
    """Initialize the float params pytree (graph traversal; draws are
    bit-identical with the historical per-family init)."""
    return graph_init(key, build_graph(cfg))


def calibrate(params, cfg: SNNConfig, images):
    """Returns params with balanced per-layer gains (one fwd pass) —
    Diehl-style threshold balancing, see graph/passes.py."""
    return graph_calibrate(params, build_graph(cfg), images)


def _graph_apply(params, cfg: SNNConfig, images, rates=None, package=None):
    graph = build_graph(cfg)
    ex = executor_for(graph, params, package=package)
    return run_graph(graph, ex, images, rates=rates)


def apply(params, cfg: SNNConfig, images, package=None):
    """Forward: (B, H, W, C) images in [0,1] -> (B, n_classes) logits.

    With ``cfg.int_deploy`` every layer past the direct-encoded stem
    runs on the fused integer datapath with 1-bit spike traffic between
    layers.  With ``package`` (a ``repro.deploy.DeployedModel``) the
    integer layers consume pre-packed weights + folded thresholds — the
    zero-quantization serving path; ``params`` then only needs the float
    stem/head leaves (``package.float_params``).  Bit-exact either way.
    """
    return _graph_apply(params, cfg, images, package=package)


def apply_with_rates(params, cfg: SNNConfig, images, package=None):
    """Forward pass that also reports per-spiking-layer mean firing rates
    (eager-only instrumentation — used to compare the float and integer
    deployment paths' spike activity)."""
    rates: list = []
    logits = _graph_apply(params, cfg, images, rates=rates, package=package)
    return logits, rates


def count_macs(cfg: SNNConfig) -> int:
    """Synaptic-op count per inference (one timestep x T) — feeds the
    paper's latency/energy model in benchmarks/.  A graph traversal, so
    it can never drift from the topology the forwards execute."""
    return build_graph(cfg).count_macs()


# -- legacy per-family aliases (the graph dispatches internally) ------------

def vgg_apply(params, cfg: SNNConfig, images, _rates=None, package=None):
    return _graph_apply(params, cfg, images, rates=_rates, package=package)


def resnet_apply(params, cfg: SNNConfig, images, _rates=None, package=None):
    return _graph_apply(params, cfg, images, rates=_rates, package=package)


def vgg_init(key, cfg: SNNConfig):
    return graph_init(key, build_graph(cfg))


def resnet_init(key, cfg: SNNConfig):
    return graph_init(key, build_graph(cfg))
