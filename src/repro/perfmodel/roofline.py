"""Roofline term derivation for each (arch x shape x mesh) cell.

Three instruments (methodology in EXPERIMENTS.md §Roofline):

1. compute term — exact global FLOPs from the scan-aware jaxpr counter
   (perfmodel/flops.py), / (chips * 197 TF/s bf16).
2. memory + collective terms — XLA cost_analysis / HLO text of the
   partitioned module.  XLA counts a scan body once, so we compile the
   model at depth L=1 and L=2 and extrapolate:
       per_layer = c(L2) - c(L1);   total = c(L1) + (n_layers-1)*per_layer
   This is exact for the layer stack (the only loop carrying collectives);
   inner chunk loops (attention/SSD) hold no collectives and their VMEM-
   resident tiles are what a fused kernel would keep on-chip anyway, so
   the differential approximates ideal-fusion HBM traffic — the correct
   baseline for a roofline.
3. fit check — full-depth compile provides memory_analysis + proves the
   production mesh shards every cell (launch/dryrun.py).

Hardware constants: :data:`PEAKS`, one table of published per-chip
peaks keyed by ``jax.Device.device_kind``.  The offline LM roofline
above models a TPU v5e (:data:`V5E`: 197 TFLOP/s bf16, 393 TOP/s int8,
819 GB/s HBM, ~50 GB/s per ICI link).
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published peaks of one accelerator chip."""

    bf16_flops: float    # FLOP/s on the MXU in bf16
    int8_ops: float      # OP/s on the MXU in int8
    hbm_bw: float        # HBM bytes/s
    link_bw: float       # bytes/s per chip-to-chip link
    source: str


V5E = DevicePeaks(
    bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
    link_bw=1600e9 / 8 / 4,             # 1,600 Gbit/s over 4 ICI links
    source='Google Cloud documentation, "TPU v5e"')

# keyed by jax's ``Device.device_kind``
PEAKS = {"TPU v5 lite": V5E}


def device_peaks(device_kind: str) -> DevicePeaks:
    """The peaks of a live device.  A kind missing from :data:`PEAKS` is
    an error, never a silent v5e stand-in: a roofline share against the
    wrong chip's peak is a wrong number."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it to perfmodel.roofline.PEAKS with "
            f"its source") from None


# the offline LM roofline's chip
PEAK_FLOPS = V5E.bf16_flops
HBM_BW = V5E.hbm_bw
LINK_BW = V5E.link_bw

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results"

# Tie-break priority for the dominant roofline term.  ``max()`` returns
# the FIRST maximal element, so equal times resolve compute > memory >
# collective — deterministically, not by whatever tuple fallthrough
# (e.g. string comparison of the labels) happens to order them.
_BOTTLENECK_PRIORITY = ("compute", "memory", "collective")


def pick_bottleneck(t_comp: float, t_mem: float, t_coll: float) -> str:
    """Name of the dominant term, ties broken by _BOTTLENECK_PRIORITY."""
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    return max(_BOTTLENECK_PRIORITY, key=lambda k: terms[k])


def override_depth(cfg, n_layers: int):
    """Clone cfg at a reduced depth (layer-pattern safe)."""
    kw = {"n_layers": n_layers}
    if cfg.global_attn_layers:
        kw["global_attn_layers"] = tuple(
            i for i in cfg.global_attn_layers if i < n_layers
        ) or (0,)
    if cfg.encdec is not None:
        kw["encdec"] = dataclasses.replace(cfg.encdec, n_layers=n_layers)
    return dataclasses.replace(cfg, **kw)


def exact_flops(arch: str, shape_name: str, quant_bits: int = 16) -> int:
    """Global FLOPs of the cell's step function (jaxpr counter)."""
    from repro.configs import SHAPES, get_config
    from repro.launch import specs as S
    from repro.launch import steps as St
    from repro.perfmodel.flops import count_fn_flops
    from repro.quant.formats import PrecisionConfig
    from repro.train.optimizer import OptConfig

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        cfg = dataclasses.replace(cfg, remat="dots")
    if quant_bits != 16:
        cfg = dataclasses.replace(
            cfg, precision=PrecisionConfig(bits=quant_bits, group_size=-1))
    params = S.param_specs_struct(cfg)
    if shape.kind == "train":
        fn = St.make_train_step(cfg, OptConfig())
        opt = S.opt_specs_struct(params)
        batch = S.train_batch_specs(cfg, shape)
        return count_fn_flops(fn, params, opt, batch)
    if shape.kind == "prefill":
        fn = St.make_prefill_step(cfg)
        batch = S.prefill_batch_specs(cfg, shape)
        return count_fn_flops(fn, params, batch)
    fn = St.make_decode_step(cfg)
    cache = S.cache_specs_struct(cfg, shape)
    import jax.numpy as jnp
    import jax
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return count_fn_flops(fn, params, cache, tokens)


def depth_differential(arch: str, shape_name: str, *, multi_pod=False,
                       quant_bits: int = 16, force=False, tag: str = "",
                       cfg_override=None) -> dict:
    """bytes/collectives per device, extrapolated from L1/L2 compiles."""
    from repro.configs import get_config
    from repro.launch import dryrun as D

    cfg = cfg_override if cfg_override is not None else get_config(arch)
    L = cfg.n_layers
    recs = {}
    for depth in (1, 2):
        recs[depth] = D.run_cell_cfg(
            override_depth(cfg, depth), arch, shape_name,
            tag_suffix=f"__depth{depth}{tag}", multi_pod=multi_pod,
            quant_bits=quant_bits, force=force,
        )
        if not recs[depth]["ok"]:
            return {"ok": False, "error": recs[depth].get("error"),
                    "depth_failed": depth}

    def extrap(key, sub=None):
        def get(r):
            v = r.get(key, 0) or 0
            if sub is not None:
                v = (v or {}).get(sub, 0) or 0
            return float(v)
        c1, c2 = get(recs[1]), get(recs[2])
        return c1 + (L - 1) * max(0.0, c2 - c1)

    out = {
        "ok": True,
        "bytes_per_device": extrap("hbm_bytes_est"),
        "bytes_cost_analysis": extrap("bytes_per_device"),
        "coll_bytes_per_device": extrap("collective_bytes", "total"),
        "coll_breakdown": {
            k: extrap("collective_bytes", k)
            for k in ("all-gather", "all-reduce", "reduce-scatter",
                      "all-to-all", "collective-permute")
        },
        "depth1": recs[1], "depth2": recs[2],
    }
    return out


def roofline_cell(arch: str, shape_name: str, *, multi_pod=False,
                  quant_bits: int = 16, force=False, tag: str = "",
                  cfg_override=None) -> dict:
    from repro.configs import SHAPES, get_config

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    chips = 512 if multi_pod else 256
    rec = {"arch": arch, "shape": shape_name, "tag": tag,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "chips": chips, "quant_bits": quant_bits}

    from repro.launch import dryrun as D

    flops = exact_flops(arch, shape_name, quant_bits)
    full = D.run_cell_cfg(cfg_override, arch, shape_name,
                          tag_suffix=tag, multi_pod=multi_pod,
                          quant_bits=quant_bits, force=force)
    if not full["ok"]:
        rec.update(ok=False, error=full.get("error"))
        return rec
    diff = {
        "bytes_per_device": float(full["hbm_bytes_est"]),
        "coll_bytes_per_device": float(
            full["collective_bytes"].get("total", 0)),
        "coll_breakdown": {
            k: float(full["collective_bytes"].get(k, 0))
            for k in ("all-gather", "all-reduce", "reduce-scatter",
                      "all-to-all", "collective-permute")
        },
    }

    t_comp = flops / (chips * PEAK_FLOPS)
    t_mem = diff["bytes_per_device"] / HBM_BW
    t_coll = diff["coll_bytes_per_device"] / LINK_BW

    toks = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_act = cfg.active_params()
    model_flops = (6 if shape.kind == "train" else 2) * n_act * toks

    rec.update(
        ok=True,
        hlo_flops_global=float(flops),
        model_flops=float(model_flops),
        useful_ratio=float(model_flops / flops) if flops else 0.0,
        bytes_per_device=diff["bytes_per_device"],
        coll_bytes_per_device=diff["coll_bytes_per_device"],
        coll_breakdown=diff["coll_breakdown"],
        compute_s=t_comp, memory_s=t_mem, collective_s=t_coll,
        bottleneck=pick_bottleneck(t_comp, t_mem, t_coll),
        step_s_lower_bound=max(t_comp, t_mem, t_coll),
        roofline_fraction=float(
            t_comp / max(t_comp, t_mem, t_coll, 1e-30)),
    )
    return rec
