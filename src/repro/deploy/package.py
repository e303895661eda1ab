"""One-shot model packing for the SNN deployment runtime.

The training checkpoint is a float pytree; the integer forward only needs
the packed L-SPINE operands.  :func:`deploy` traverses the declarative
model graph (repro.graph) ONCE, quantizes + packs every post-stem
conv/dense layer
(``QuantizedConvTensor`` / ``QuantizedTensor``), folds the float firing
threshold into a per-channel integer ``theta_q`` vector, and records the
per-layer geometry — so the hot serving path never touches the
quantizer again (the per-call ``int_deploy`` forward reruns the 2/4-bit
MSE clip search on every layer of every request; the packed forward is
bit-exact with it and does none of that).

Artifact contract (``save`` / ``load``): one flat ``.npz`` holding

    __manifest__            JSON header: format version, serialized
                            SNNConfig, per-layer kind/bits/geometry
    layer:<name>:data       packed int32 weight words
    layer:<name>:scale      float32 per-channel quantizer scales
    layer:<name>:theta      int32 per-channel folded thresholds
    param:<dotted.path>     float leaves the integer path still needs
                            (the direct-encoded stem and the readout head)

Layer names are flat dotted paths into the model structure
(``convs.3``, ``fc1``, ``blocks.2.proj``), shared between the in-memory
package, the npz keys, and the forward's lookups.

``DeployedModel`` is a registered pytree, so it can be passed straight
through ``jax.jit`` / ``shard_map`` as a runtime argument — the serve
engine (deploy/engine.py) compiles one executable per batch bucket with
the whole package as an operand, not as baked-in constants.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lif import LIFConfig
from repro.core.snn_layers import (
    _fold_threshold_q,
    pack_conv_weights,
    pack_dense_weights,
)
from repro.quant.formats import (
    PrecisionConfig,
    QuantizedConvTensor,
    QuantizedTensor,
)

PACKAGE_FORMAT_VERSION = 2
# v1: per-layer operands only (pre-fusion).  v2 adds the "groups"
# manifest section — per-fusion-group operand bundles (member order,
# datapath width, VMEM working set, bundle bytes) — and the cfg's
# ``fusion`` request.  v1 packages still load: they simply carry no
# groups, lowering layer by layer exactly as they always did.
COMPAT_FORMAT_VERSIONS = (1, 2)


# ---------------------------------------------------------------------------
# containers
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedLayer:
    """One deployed layer: packed integer weights + folded thresholds.

    kind:     "conv" (fused_conv rollout) or "dense" (fused_nce rollout).
    qt:       packed weights — QuantizedConvTensor (conv) or
              QuantizedTensor (dense, (d_out, d_in) layout).
    theta_q:  (c_out,) int32 per-channel integer thresholds
              (theta / scale[c], the fold snn_layers applies per call).
    stride:   conv stride baked into the layer geometry (1 for dense).
    """

    kind: str
    qt: Union[QuantizedTensor, QuantizedConvTensor]
    theta_q: jnp.ndarray
    stride: int = 1

    # -- pytree protocol (stride/kind are static geometry) -------------------
    def tree_flatten(self):
        return (self.qt, self.theta_q), (self.kind, self.stride)

    @classmethod
    def tree_unflatten(cls, aux, children):
        qt, theta_q = children
        kind, stride = aux
        return cls(kind, qt, theta_q, stride)

    @property
    def geometry(self) -> Dict:
        """Static layer geometry recorded in the package manifest."""
        if self.kind == "conv":
            return {"kh": self.qt.kh, "kw": self.qt.kw,
                    "c_in": self.qt.c_in, "c_out": self.qt.c_out,
                    "c_in_pad": self.qt.c_in_pad, "stride": self.stride}
        d_out, d_in = self.qt.shape
        return {"d_in": d_in, "d_out": d_out,
                "group_size": self.qt.group_size}

    def nbytes_packed(self) -> int:
        return self.qt.nbytes_packed() + int(np.prod(self.theta_q.shape)) * 4


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeployedModel:
    """A fully packed SNN ready for the batched serve engine.

    cfg:           the SNNConfig the package was built for (int_path).
    float_params:  the float leaves the integer forward still needs —
                   the direct-encoded stem conv and the non-spiking
                   readout head (their inputs/outputs are not 1-bit).
    layers:        flat name -> PackedLayer for every fused-kernel layer.
    """

    cfg: "SNNConfig"  # noqa: F821 — imported lazily to avoid a cycle
    float_params: Dict
    layers: Dict[str, PackedLayer]

    # -- pytree protocol -----------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.layers))
        children = (self.float_params, [self.layers[n] for n in names])
        return children, (self.cfg, names)

    @classmethod
    def tree_unflatten(cls, aux, children):
        cfg, names = aux
        float_params, packed = children
        return cls(cfg, float_params, dict(zip(names, packed)))

    # -- forward -------------------------------------------------------------
    def apply(self, images: jnp.ndarray) -> jnp.ndarray:
        """Packed integer forward: (B, H, W, C) images -> (B, n_classes)
        logits, bit-exact with the per-call ``int_deploy`` forward."""
        from repro.models import snn_cnn

        return snn_cnn.apply(self.float_params, self.cfg, images,
                             package=self)

    def apply_with_rates(self, images: jnp.ndarray):
        from repro.models import snn_cnn

        return snn_cnn.apply_with_rates(self.float_params, self.cfg, images,
                                        package=self)

    # -- accounting ----------------------------------------------------------
    def nbytes_packed(self) -> int:
        """HBM bytes of all packed layers (weights + scales + thetas)."""
        return sum(lp.nbytes_packed() for lp in self.layers.values())

    def nbytes_dense_fp32(self) -> int:
        return sum(lp.qt.nbytes_dense_fp32() for lp in self.layers.values())

    def compression_ratio(self) -> float:
        return self.nbytes_dense_fp32() / max(self.nbytes_packed(), 1)

    def _group_manifest(self):
        """Per-fusion-group operand bundles for the v2 manifest: member
        order, datapath width, estimated VMEM working set, and the
        bundle's packed bytes (the members' weight+theta payload the
        fused rollout streams in together)."""
        from repro.graph import build_graph, group_vmem_bytes

        graph = build_graph(self.cfg)
        bundles = []
        for g in graph.groups:
            bundles.append({
                "name": g.name,
                "members": list(g.members),
                "bits": self.cfg.precision.bits,
                "vmem_bytes": int(group_vmem_bytes(graph, g)),
                "packed_bytes": sum(
                    self.layers[m].nbytes_packed()
                    for m in g.members if m in self.layers),
            })
        return bundles

    # -- persistence -----------------------------------------------------------
    def save(self, path: str) -> str:
        """Write the package as one flat npz (see module docstring)."""
        arrays: Dict[str, np.ndarray] = {}
        manifest = {
            "version": PACKAGE_FORMAT_VERSION,
            "cfg": _cfg_to_dict(self.cfg),
            "layers": {},
            "groups": self._group_manifest(),
            "float_params": [],
        }
        for name, lp in self.layers.items():
            manifest["layers"][name] = {
                "kind": lp.kind,
                "bits": lp.qt.bits,
                "shape": list(lp.qt.shape),
                "geometry": lp.geometry,
            }
            arrays[f"layer:{name}:data"] = np.asarray(lp.qt.data)
            arrays[f"layer:{name}:scale"] = np.asarray(lp.qt.scale)
            arrays[f"layer:{name}:theta"] = np.asarray(lp.theta_q)
        for pth, arr in _flatten_params(self.float_params):
            manifest["float_params"].append(pth)
            arrays[f"param:{pth}"] = np.asarray(arr)
        arrays["__manifest__"] = np.array(json.dumps(manifest))
        with open(path, "wb") as f:
            np.savez(f, **arrays)
        return path


def load(path: str) -> DeployedModel:
    """Rebuild a :class:`DeployedModel` from :meth:`DeployedModel.save`."""
    with np.load(path, allow_pickle=False) as z:
        manifest = json.loads(str(z["__manifest__"][()]))
        if manifest["version"] not in COMPAT_FORMAT_VERSIONS:
            raise ValueError(
                f"package format v{manifest['version']} != "
                f"v{PACKAGE_FORMAT_VERSION} reader")
        cfg = _cfg_from_dict(manifest["cfg"])
        layers = {}
        for name, meta in manifest["layers"].items():
            data = jnp.asarray(z[f"layer:{name}:data"])
            scale = jnp.asarray(z[f"layer:{name}:scale"])
            theta = jnp.asarray(z[f"layer:{name}:theta"])
            geo = meta["geometry"]
            if meta["kind"] == "conv":
                qt = QuantizedConvTensor(
                    data=data, scale=scale, shape=tuple(meta["shape"]),
                    bits=meta["bits"], c_in_pad=geo["c_in_pad"])
                layers[name] = PackedLayer("conv", qt, theta,
                                           stride=geo["stride"])
            else:
                qt = QuantizedTensor(
                    data=data, scale=scale, zero=None,
                    shape=tuple(meta["shape"]), bits=meta["bits"],
                    group_size=geo["group_size"])
                layers[name] = PackedLayer("dense", qt, theta)
        float_params = _unflatten_params(
            {p: jnp.asarray(z[f"param:{p}"])
             for p in manifest["float_params"]})
    return DeployedModel(cfg=cfg, float_params=float_params, layers=layers)


# ---------------------------------------------------------------------------
# the one-shot pack
# ---------------------------------------------------------------------------

def _pack_conv(p, pc: PrecisionConfig, lif: LIFConfig,
               stride: int = 1) -> PackedLayer:
    qct = pack_conv_weights(p, pc)
    return PackedLayer("conv", qct, _fold_threshold_q(qct.scale, lif),
                       stride=stride)


def _pack_dense(p, pc: PrecisionConfig, lif: LIFConfig) -> PackedLayer:
    qt = pack_dense_weights(p, pc)         # packed (d_out, d_in)
    return PackedLayer("dense", qt, _fold_threshold_q(qt.scale, lif))


def deploy(params, cfg) -> DeployedModel:
    """Pack a float SNN checkpoint for integer deployment, in one pass.

    Traverses the declarative model graph (``repro.graph.build_graph``)
    once: every spec the integer executor routes through the fused
    packed kernels (``graph.packable_specs()`` — post-stem convs,
    residual-block convs + projections, the FC head) is quantized
    (threshold-balancing gain folded into the weights first, exactly as
    the per-call path does), packed, and gets its per-channel integer
    threshold vector.  The direct-encoded stem and the readout head stay
    float (their activations are not 1-bit).  Because the pack walk and
    the forwards share one graph, a topology edit cannot desync them —
    the result drives a forward that is bit-exact with the per-call
    ``int_deploy`` path.
    """
    from repro.graph import build_graph
    from repro.graph.spec import Conv, Dense, get_path, set_path

    if not cfg.int_path:
        raise ValueError(
            "deploy() packs the integer datapath: cfg needs "
            "int_deploy=True and a quantized precision (bits in {2,4,8})")
    if not cfg.precision.symmetric:
        raise ValueError(
            "deploy(): the integer threshold fold assumes symmetric "
            "quantization (a zero point cannot fold into theta_q)")
    pc, lif = cfg.precision, cfg.lif
    graph = build_graph(cfg)
    layers: Dict[str, PackedLayer] = {}
    float_params: Dict = {}
    for spec in graph.param_specs():
        if isinstance(spec, Conv) and not spec.stem:
            layers[spec.name] = _pack_conv(get_path(params, spec.name), pc,
                                           lif, stride=spec.stride)
        elif isinstance(spec, Dense):
            layers[spec.name] = _pack_dense(get_path(params, spec.name), pc,
                                            lif)
        else:   # stem conv + readout head stay float
            set_path(float_params, spec.name,
                     dict(get_path(params, spec.name)))

    return DeployedModel(cfg=cfg, float_params=float_params, layers=layers)


def deploy_config(model: str = "vgg9", bits: int = 4, smoke: bool = True,
                  fusion=()):
    """The int-deploy ``SNNConfig`` every serve entry point shares:
    reduced smoke geometry (CI-sized, matches the kernel test configs)
    or the paper-size model (its input geometry from
    ``repro.graph.build.MODEL_GEOMETRY``: CIFAR by default, ImageNet
    for resnet50).  Keeps the launcher, benchmark, and example
    measuring the same model.  ``fusion`` is the multi-layer fusion
    request (``()`` / ``"auto"`` / explicit member tuples — see
    repro.graph.fusion)."""
    from repro.graph.build import MODEL_GEOMETRY
    from repro.models.snn_cnn import SNNConfig

    fusion = _normalize_fusion(fusion)
    pc = PrecisionConfig(bits=bits)
    if smoke:
        return SNNConfig(model=model, img_size=16, timesteps=3,
                         scale=0.15, n_classes=4, int_deploy=True,
                         precision=pc, fusion=fusion)
    return SNNConfig(model=model, int_deploy=True, precision=pc,
                     fusion=fusion, **MODEL_GEOMETRY.get(model, {}))


# ---------------------------------------------------------------------------
# (de)serialization helpers
# ---------------------------------------------------------------------------

def _cfg_to_dict(cfg) -> Dict:
    # asdict recurses into the nested LIFConfig/PrecisionConfig fields
    return dataclasses.asdict(cfg)


def _normalize_fusion(fusion):
    """Hashable form of a fusion request: JSON round-trips tuples as
    lists, and SNNConfig must stay hashable (it keys graph/jit caches)."""
    if isinstance(fusion, str) or not fusion:
        return fusion if fusion else ()
    return tuple(tuple(m) for m in fusion)


def _cfg_from_dict(d: Dict):
    from repro.models.snn_cnn import SNNConfig

    d = dict(d)
    d["lif"] = LIFConfig(**d["lif"])
    d["precision"] = PrecisionConfig(**d["precision"])
    # absent in v1 manifests (pre-fusion packages lower layer by layer)
    d["fusion"] = _normalize_fusion(d.get("fusion", ()))
    return SNNConfig(**d)


def _flatten_params(tree, prefix: str = ""):
    """Yield (dotted path, array) for a nested dict/list float pytree."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flatten_params(tree[k], f"{prefix}{k}.")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _flatten_params(v, f"{prefix}{i}.")
    else:
        yield prefix[:-1], tree


def _unflatten_params(flat: Dict[str, jnp.ndarray]):
    """Inverse of :func:`_flatten_params` (numeric components -> lists).
    ``_flatten_params`` yields paths with list indices ascending, which is
    exactly the append order :func:`repro.graph.spec.set_path` needs."""
    from repro.graph.spec import set_path

    root: Dict = {}
    for path, arr in flat.items():
        set_path(root, path, arr)
    return root
