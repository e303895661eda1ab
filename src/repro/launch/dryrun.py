import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: pjit must
partition every step function onto the 16x16 (single-pod) and 2x16x16
(multi-pod) meshes, the compiled module must fit per-device memory, and
cost_analysis/HLO give the roofline terms for EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out f.json]

Results are cached per cell in benchmarks/results/dryrun/<cell>.json so the
full sweep is resumable.
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config, supported_shapes
from repro.distributed import sharding as shd
from repro.launch import specs as S
from repro.launch import steps as St
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.train import optimizer as opt

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# HLO collective ops whose output bytes we sum (async *-start counted once)
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


# ops that materialize a buffer in HBM (outputs written once, read ~once
# downstream).  bitcast/tuple/get-tuple-element/parameter are zero-traffic;
# nested-computation parameter re-declarations would double count.
_MATERIALIZING = (
    "fusion", "dot", "convolution",
    "transpose", "reduce", "gather", "dynamic-slice",
    "concatenate", "pad", "slice", "broadcast", "iota", "reduce-window",
    "select-and-scatter", "rng", "all-gather", "all-reduce",
    "reduce-scatter", "all-to-all", "collective-permute", "custom-call",
    "exponential", "add", "multiply", "subtract", "divide", "select",
    "compare", "tanh", "maximum", "minimum", "negate", "rsqrt", "sqrt",
)
# NOT charged, with reasons (methodology in EXPERIMENTS.md §Roofline):
#   dynamic-update-slice / scatter — in-place on TPU with donation; the
#     update slice's producer is already charged.  Charging output size
#     would claim the whole KV cache is rewritten every step.
#   convert — XLA:CPU materializes bf16->f32 operand upcasts because its
#     dot can't mix precisions; the TPU MXU consumes bf16 with f32
#     accumulation natively (register-level, no HBM round trip).
#   copy — loop-carry copies that donation/aliasing elides on TPU.
_INPLACE = ("dynamic-update-slice", "scatter", "convert", "copy")

_OP_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ((?:\([^)]*\))|(?:\S+)) ([a-z][\w\-]*)\(",
    re.M,
)

# loads: output is a view/read of an existing buffer — charge once
_LOAD_OPS = ("dynamic-slice", "gather", "slice")
# XLA:CPU names fusions after their constituent ops.  A fusion made only of
# data-movement ops (dtype upcasts for the CPU dot, loop-carry copies,
# in-place cache updates, layout bitcasts) has no TPU-HBM traffic beyond
# what its producers/consumers are already charged.
_DATA_MOVEMENT = {
    "wrapped", "convert", "copy", "bitcast", "dynamic", "update", "slice",
    "select", "broadcast", "reshape", "concatenate", "pad", "transpose",
    "fusion",
}


def _is_data_movement_fusion(name: str) -> bool:
    tokens = set(re.split(r"[_\-.0-9]+", name)) - {""}
    return tokens <= _DATA_MOVEMENT


def _type_bytes(ty: str) -> int:
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(ty):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DTYPE_BYTES[dt]
    return nbytes


_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_TRIP_RE = re.compile(r'known_trip_count[":{\s]+n["\s:]+(\d+)')


def _split_computations(hlo_text: str):
    """-> (entry_name, {comp_name: body_text}).  Line-based: a computation
    starts at an unindented ``[ENTRY] %name (...) ... {`` line (parameter
    lists may contain nested parens — tuple-typed loop carries) and ends at
    the matching unindented ``}``."""
    comps = {}
    entry = None
    cur_name, cur_lines = None, []
    for line in hlo_text.splitlines():
        if cur_name is None:
            if line[:1] not in ("", " ", "\t") and line.rstrip().endswith("{"):
                m = _COMP_HDR.match(line)
                if m:
                    cur_name = m.group(2)
                    cur_lines = [line]
                    if m.group(1):
                        entry = cur_name
        else:
            cur_lines.append(line)
            if line.startswith("}"):
                comps[cur_name] = "\n".join(cur_lines)
                cur_name = None
    return entry, comps


def _trip_count(while_op_line: str, cond_text: str) -> int:
    """Trip count: XLA's known_trip_count backend_config when present,
    else the largest s32 scalar constant in the loop condition."""
    m = _TRIP_RE.search(while_op_line)
    if m:
        return int(m.group(1))
    vals = [int(v) for v in _CONST_RE.findall(cond_text)]
    return max(vals) if vals else 1


def analyze_hlo(hlo_text: str) -> dict:
    """TPU-semantics cost walk of the partitioned module.

    * while bodies are charged x trip-count (parsed from the condition) —
      the fix for HLO-text/cost_analysis counting a scan body once;
    * entry parameters are read once; slices/gathers charge their output
      once (a read); other materializing ops charge 2x (write + re-read);
    * data-movement-only fusions, converts, copies, scatters and DUS are
      free: on TPU they are register-level, aliased in-place, or absent
      (XLA:CPU materializes dot-operand upcasts the MXU does natively).

    Returns {"hbm_bytes": int, "collectives": {kind: bytes, "total": ...}}.
    """
    entry, comps = _split_computations(hlo_text)
    colls: dict = {}

    def comp_cost(name: str, mult: float, seen) -> float:
        if name not in comps or name in seen:
            return 0.0
        body = comps[name]
        total = 0.0
        for m in _OP_RE.finditer(body):
            op_name, ty, op = m.group(1), m.group(2), m.group(3)
            line = m.group(0)
            if op == "while":
                line_end = body.find("\n", m.start())
                op_line = body[m.start():line_end]
                cond_m = re.search(r"condition=%?([\w.\-]+)", op_line)
                body_m = re.search(r"body=%?([\w.\-]+)", op_line)
                trips = _trip_count(
                    op_line, comps.get(cond_m.group(1), "") if cond_m else "")
                if body_m:
                    total += comp_cost(body_m.group(1), mult * max(1, trips),
                                       seen | {name})
                continue
            if op == "conditional":
                continue  # branches ~ balanced; rare in our models
            base_kind = op[:-6] if op.endswith("-start") else op
            if base_kind in ("all-gather", "all-reduce", "reduce-scatter",
                             "all-to-all", "collective-permute"):
                b = _type_bytes(ty) * mult
                colls[base_kind] = colls.get(base_kind, 0) + b
                colls["total"] = colls.get("total", 0) + b
                total += 2 * _type_bytes(ty) * mult
            elif op in _LOAD_OPS:
                total += _type_bytes(ty) * mult
            elif op == "fusion" and _is_data_movement_fusion(op_name):
                continue
            elif op in _MATERIALIZING:
                total += 2 * _type_bytes(ty) * mult
        return total

    hbm = 0.0
    if entry:
        for m in _OP_RE.finditer(comps[entry]):
            if m.group(3) == "parameter":
                hbm += _type_bytes(m.group(2))
        hbm += comp_cost(entry, 1.0, frozenset())
    return {"hbm_bytes": int(hbm), "collectives": colls}


def hbm_bytes_estimate(hlo_text: str) -> int:
    return analyze_hlo(hlo_text)["hbm_bytes"]


def collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes per collective kind from (S)HLO text."""
    totals: dict = {}
    for m in _COLL_RE.finditer(hlo_text):
        ty, kind = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(ty):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        totals[kind] = totals.get(kind, 0) + nbytes
        totals["total"] = totals.get("total", 0) + nbytes
    return totals


def build_cell(arch: str, shape_name: str, mesh, quant_bits: int = 16,
               cfg=None):
    """Returns (fn, args, in_shardings, out_shardings, donate)."""
    import dataclasses

    from repro.quant.formats import PrecisionConfig

    if cfg is None:
        cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train" and cfg.remat == "none":
        cfg = dataclasses.replace(cfg, remat="dots")
    if quant_bits != 16 and not cfg.precision.quantized:
        cfg = dataclasses.replace(
            cfg, precision=PrecisionConfig(bits=quant_bits, group_size=-1)
        )
    dp = dp_axes(mesh)

    params_struct = S.param_specs_struct(cfg)
    pspecs = shd.param_specs(params_struct, mesh)
    pshard = shd.to_shardings(pspecs, mesh)

    if shape.kind == "train":
        opt_struct = S.opt_specs_struct(params_struct)
        ospecs = {"m": pspecs, "v": pspecs, "step": P()}
        oshard = shd.to_shardings(ospecs, mesh)
        batch = S.train_batch_specs(cfg, shape)
        bspecs = {k: shd.batch_spec(k, v.shape, mesh, dp) for k, v in
                  batch.items()}
        bshard = shd.to_shardings(bspecs, mesh)
        opt_cfg = opt.OptConfig()
        fn = St.make_train_step(cfg, opt_cfg)
        mshard = jax.tree.map(
            lambda _: NamedSharding(mesh, P()),
            {"grad_norm": 0, "lr": 0, "loss": 0},
        )
        return (
            fn,
            (params_struct, opt_struct, batch),
            (pshard, oshard, bshard),
            (pshard, oshard, mshard),
            (0, 1),
        )

    def logits_spec(batch_dim: int) -> P:
        b_ax = None
        if batch_dim % mesh.shape["data"] == 0:
            b_ax = "data"
        # vocab on 'model' only when divisible (hymba/mamba2/whisper/granite
        # vocabs are not multiples of 16)
        v_ax = "model" if cfg.vocab % mesh.shape["model"] == 0 else None
        return P(b_ax, v_ax)

    if shape.kind == "prefill":
        batch = S.prefill_batch_specs(cfg, shape)
        bspecs = {k: shd.batch_spec(k, v.shape, mesh, dp) for k, v in
                  batch.items()}
        bshard = shd.to_shardings(bspecs, mesh)
        cache = S.cache_specs_struct(cfg, shape)
        cspecs = shd.cache_specs(cache, mesh, dp)
        cshard = shd.to_shardings(cspecs, mesh)
        lshard = NamedSharding(mesh, logits_spec(shape.global_batch))
        fn = St.make_prefill_step(cfg)
        return (fn, (params_struct, batch), (pshard, bshard),
                (lshard, cshard), ())

    # decode
    cache = S.cache_specs_struct(cfg, shape)
    cspecs = shd.cache_specs(cache, mesh, dp)
    cshard = shd.to_shardings(cspecs, mesh)
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tspec = shd.batch_spec("tokens", tokens.shape, mesh, dp)
    tshard = NamedSharding(mesh, tspec)
    lshard = NamedSharding(mesh, logits_spec(shape.global_batch))
    fn = St.make_decode_step(cfg)
    return (fn, (params_struct, cache, tokens), (pshard, cshard, tshard),
            (lshard, cshard), (1,))


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             quant_bits: int = 16, force: bool = False) -> dict:
    return run_cell_cfg(None, arch, shape_name, multi_pod=multi_pod,
                        quant_bits=quant_bits, force=force)


def run_cell_cfg(cfg, arch: str, shape_name: str, *, tag_suffix: str = "",
                 multi_pod: bool = False, quant_bits: int = 16,
                 force: bool = False) -> dict:
    """Lower + compile one cell (optionally with a modified cfg, e.g. the
    depth-1/2 variants of the roofline differential or a perf experiment)."""
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}__{shape_name}__{mesh_tag}" + (
        f"__w{quant_bits}" if quant_bits != 16 else "") + tag_suffix
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    cache_file = RESULTS_DIR / f"{tag}.json"
    if cache_file.exists() and not force:
        cached = json.loads(cache_file.read_text())
        # never reuse failures or records from an older analysis schema
        if cached.get("ok") and cached.get("schema") == 4:
            return cached

    t0 = time.time()
    rec = {"cell": tag, "arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "quant_bits": quant_bits}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args, in_sh, out_sh, donate = build_cell(
            arch, shape_name, mesh, quant_bits, cfg=cfg)
        with mesh:
            jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate)
            lowered = jfn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        ca = compiled.cost_analysis() or {}
        rec["flops_per_device"] = float(ca.get("flops", -1.0))
        rec["bytes_per_device"] = float(ca.get("bytes accessed", -1.0))
        ma = None
        try:
            ma = compiled.memory_analysis()
        except Exception:
            pass
        if ma is not None:
            for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                         "output_size_in_bytes", "generated_code_size_in_bytes"):
                v = getattr(ma, attr, None)
                if v is not None:
                    rec[attr] = int(v)
        hlo = compiled.as_text()
        analysis = analyze_hlo(hlo)           # trip-count-scaled walk
        rec["collective_bytes"] = analysis["collectives"] or {"total": 0}
        rec["hbm_bytes_est"] = analysis["hbm_bytes"]
        rec["collective_bytes_body_once"] = collective_bytes(hlo)
        rec["schema"] = 4
        rec["n_devices"] = int(len(mesh.devices.flat))
        rec["lower_s"] = round(t_lower, 1)
        rec["compile_s"] = round(t_compile, 1)
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["wall_s"] = round(time.time() - t0, 1)
    cache_file.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quant-bits", type=int, default=16)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--roofline", action="store_true",
                    help="derive roofline terms (jaxpr flops + L1/L2 "
                         "differential) instead of the full-depth compile")
    args = ap.parse_args()

    if args.roofline:
        from repro.perfmodel.roofline import roofline_cell

        cells = ([(args.arch, args.shape)] if args.arch else
                 [(a, s) for a in ARCH_IDS for s in supported_shapes(a)])
        out_dir = RESULTS_DIR.parent / "roofline"
        out_dir.mkdir(parents=True, exist_ok=True)
        for arch, shp in cells:
            rec = roofline_cell(arch, shp, multi_pod=args.multi_pod,
                                quant_bits=args.quant_bits, force=args.force)
            small = {k: v for k, v in rec.items()
                     if k not in ("depth1", "depth2")}
            name = f"{arch}__{shp}__{'2x16x16' if args.multi_pod else '16x16'}"
            if args.quant_bits != 16:
                name += f"__w{args.quant_bits}"
            (out_dir / f"{name}.json").write_text(
                json.dumps(small, indent=2))
            if rec.get("ok"):
                print(f"[ROOF] {arch:24s} {shp:12s} "
                      f"comp={rec['compute_s']:.4f}s mem={rec['memory_s']:.4f}s "
                      f"coll={rec['collective_s']:.4f}s -> {rec['bottleneck']}"
                      f" frac={rec['roofline_fraction']:.2f}", flush=True)
            else:
                print(f"[ROOF-FAIL] {arch} {shp}: {rec.get('error')}",
                      flush=True)
        return

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shp in supported_shapes(arch):
                cells.append((arch, shp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    n_ok = 0
    for arch, shp in cells:
        for mp in meshes:
            rec = run_cell(arch, shp, multi_pod=mp,
                           quant_bits=args.quant_bits, force=args.force)
            status = "OK " if rec["ok"] else "FAIL"
            print(f"[{status}] {rec['cell']:56s} "
                  f"flops/dev={rec.get('flops_per_device', -1):.3e} "
                  f"coll={rec.get('collective_bytes', {}).get('total', 0):.3e} "
                  f"wall={rec['wall_s']}s", flush=True)
            if not rec["ok"]:
                print("   ", rec["error"], flush=True)
            n_ok += rec["ok"]
    print(f"{n_ok} cells OK")


if __name__ == "__main__":
    main()
