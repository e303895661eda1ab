"""Graph builders for the paper's model families.

``vgg_graph(cfg)`` / ``resnet_graph(cfg)`` / ``bottleneck_graph(cfg)``
turn an ``SNNConfig`` into the one :class:`~repro.graph.spec.ModelGraph`
every lowering shares; ``build_graph(cfg)`` dispatches on ``cfg.model``
(and memoizes — configs are frozen dataclasses, so the graph for a given
config is built once).

The channel plans (VGG16_PLAN / VGG9_PLAN / RESNET18_STAGES /
RESNET50_STAGES), each model's paper-size input geometry
(``MODEL_GEOMETRY``) and the pool-dropping ``effective_plan`` rule live
here; models/snn_cnn.py re-exports the plans.
"""

from __future__ import annotations

import functools
import itertools

from repro.graph.spec import (
    Conv,
    Dense,
    Encode,
    ModelGraph,
    Pool,
    Readout,
    Residual,
)

VGG16_PLAN = [64, 64, "P", 128, 128, "P", 256, 256, 256, "P",
              512, 512, 512, "P", 512, 512, 512, "P"]
# shallow variant for quantization sweeps: BPTT through 13 thresholded
# layers is noisy at small step budgets; 5 convs isolate the precision
# effect (benchmarks/fig45)
VGG9_PLAN = [64, 64, "P", 128, 128, "P", 256, "P"]
RESNET18_STAGES = [(64, 2, 1), (128, 2, 2), (256, 2, 2), (512, 2, 2)]
# ResNet-50 (He et al., arXiv 1512.03385, Table 1): (bottleneck width,
# blocks, stride) per stage; a block's output is BOTTLENECK_EXPANSION x
# its bottleneck width
RESNET50_STAGES = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]
BOTTLENECK_EXPANSION = 4

# paper-size input geometry where a model departs from SNNConfig's
# CIFAR default (32x32, 10 classes): resnet50 is the ImageNet model
MODEL_GEOMETRY = {"resnet50": {"img_size": 224, "n_classes": 1000}}


def effective_plan(img_size: int, base_plan=None):
    """VGG plan with pools dropped once the spatial dim reaches 2 — lets
    reduced smoke configs (img 16) share the paper-size definition."""
    plan, hw = [], img_size
    for item in (base_plan if base_plan is not None else VGG16_PLAN):
        if item == "P":
            if hw <= 2:
                continue
            hw //= 2
        plan.append(item)
    return plan


def _base_plan(cfg):
    return VGG9_PLAN if cfg.model == "vgg9" else VGG16_PLAN


def vgg_graph(cfg) -> ModelGraph:
    """VGG-family graph: plan-driven conv/pool stack, one spiking FC
    (``fc1``), non-spiking readout head.

    Key schedule (pinned by the pre-graph ``vgg_init``): one key per plan
    item (convs take theirs positionally by conv index) plus fc1 at
    ``n-2`` and the head at ``n-1``.
    """
    plan = effective_plan(cfg.img_size, _base_plan(cfg))
    n_keys = len(plan) + 2
    nodes = [Encode("encode", timesteps=cfg.timesteps)]
    hw, c_in, ci, pi = cfg.img_size, cfg.in_channels, 0, 0
    for item in plan:
        if item == "P":
            nodes.append(Pool(f"pool.{pi}"))
            hw //= 2
            pi += 1
        else:
            c_out = cfg.ch(item)
            nodes.append(Conv(f"convs.{ci}", c_in, c_out, k=3, stride=1,
                              stem=(ci == 0), out_hw=hw, key_index=ci))
            c_in = c_out
            ci += 1
    d_hidden = cfg.ch(512)
    nodes.append(Dense("fc1", d_in=hw * hw * c_in, d_out=d_hidden,
                       key_index=n_keys - 2))
    nodes.append(Readout("head", d_in=d_hidden, d_out=cfg.n_classes,
                         key_index=n_keys - 1))
    return ModelGraph(cfg=cfg, nodes=tuple(nodes), n_init_keys=n_keys)


def resnet_graph(cfg) -> ModelGraph:
    """ResNet-18-family graph: stem conv, four stages of basic blocks
    (stride + 1x1 projection on stage entry), global-avg-pool readout.

    Key schedule (pinned by the pre-graph ``resnet_init``): a fixed split
    of 64 consumed sequentially — stem, then conv1/conv2/proj per block,
    head last.
    """
    nodes = [Encode("encode", timesteps=cfg.timesteps)]
    ki = itertools.count()
    hw, c = cfg.img_size, cfg.ch(64)
    nodes.append(Conv("stem", cfg.in_channels, c, k=3, stride=1, stem=True,
                      out_hw=hw, key_index=next(ki)))
    c_in, bi = c, 0
    for c_base, n_blocks, stride in RESNET18_STAGES:
        c_out = cfg.ch(c_base)
        for b in range(n_blocks):
            s = stride if b == 0 else 1
            hw //= s
            conv1 = Conv(f"blocks.{bi}.conv1", c_in, c_out, k=3, stride=s,
                         out_hw=hw, key_index=next(ki))
            conv2 = Conv(f"blocks.{bi}.conv2", c_out, c_out, k=3, stride=1,
                         out_hw=hw, key_index=next(ki))
            proj = None
            if s != 1 or c_in != c_out:
                proj = Conv(f"blocks.{bi}.proj", c_in, c_out, k=1, stride=s,
                            out_hw=hw, key_index=next(ki))
            nodes.append(Residual(f"blocks.{bi}", body=(conv1, conv2),
                                  proj=proj, stride=s))
            c_in = c_out
            bi += 1
    nodes.append(Readout("head", d_in=c_in, d_out=cfg.n_classes,
                         key_index=next(ki), spatial_mean=True))
    return ModelGraph(cfg=cfg, nodes=tuple(nodes), n_init_keys=64)


def bottleneck_graph(cfg) -> ModelGraph:
    """ResNet-50 v1.5 graph (ImageNet geometry): a 7x7 stride-2 float
    stem, a 2x2 max pool, four stages of bottleneck blocks — 1x1 reduce,
    3x3 carrying the stage's stride (v1.5), 1x1 expand to
    ``BOTTLENECK_EXPANSION`` x the bottleneck width, each followed by
    LIF — with a 1x1 projection (same stride) on each stage entry and a
    spike-OR merge, then the global-avg-pool readout.  Convs are SAME:
    a stride-s conv gives ceil(hw / s).  One init key per parameter
    layer, in traversal order (stem, then conv1/conv2/conv3/proj per
    block, head last).
    """
    nodes = [Encode("encode", timesteps=cfg.timesteps)]
    ki = itertools.count()
    hw = -(-cfg.img_size // 2)
    c_in = cfg.ch(64)
    nodes.append(Conv("stem", cfg.in_channels, c_in, k=7, stride=2,
                      stem=True, out_hw=hw, key_index=next(ki)))
    nodes.append(Pool("pool.0"))
    hw //= 2
    bi = 0
    for c_base, n_blocks, stride in RESNET50_STAGES:
        mid = cfg.ch(c_base)
        c_out = BOTTLENECK_EXPANSION * mid
        for b in range(n_blocks):
            s = stride if b == 0 else 1
            hw_in, hw = hw, -(-hw // s)
            name = f"blocks.{bi}"
            body = (
                Conv(f"{name}.conv1", c_in, mid, k=1, stride=1,
                     out_hw=hw_in, key_index=next(ki)),
                Conv(f"{name}.conv2", mid, mid, k=3, stride=s, out_hw=hw,
                     key_index=next(ki)),
                Conv(f"{name}.conv3", mid, c_out, k=1, stride=1,
                     out_hw=hw, key_index=next(ki)))
            proj = None
            if s != 1 or c_in != c_out:
                proj = Conv(f"{name}.proj", c_in, c_out, k=1, stride=s,
                            out_hw=hw, key_index=next(ki))
            nodes.append(Residual(name, body=body, proj=proj, stride=s))
            c_in = c_out
            bi += 1
    nodes.append(Readout("head", d_in=c_in, d_out=cfg.n_classes,
                         key_index=next(ki), spatial_mean=True))
    return ModelGraph(cfg=cfg, nodes=tuple(nodes), n_init_keys=next(ki))


@functools.lru_cache(maxsize=64)
def build_graph(cfg) -> ModelGraph:
    """The family dispatch every shim goes through.  Memoized: configs
    are frozen (hashable) dataclasses and graphs are immutable.

    A config carrying a ``fusion`` request (``"auto"`` or explicit member
    tuples — see repro.graph.fusion) gets its groups planned/validated
    here, so every consumer of the graph sees the same annotation."""
    if cfg.model == "resnet18":
        g = resnet_graph(cfg)
    elif cfg.model == "resnet50":
        g = bottleneck_graph(cfg)
    elif cfg.model in ("vgg9", "vgg16"):
        g = vgg_graph(cfg)
    else:
        raise ValueError(f"unknown model family {cfg.model!r} "
                         "(known: vgg9, vgg16, resnet18, resnet50)")
    fusion = getattr(cfg, "fusion", ())
    if fusion:
        from repro.graph.fusion import apply_fusion  # local: no cycle
        g = apply_fusion(g, fusion)
    return g
