"""Fusion-group planning: propose + validate multi-layer VMEM-resident
rollout chains on a :class:`~repro.graph.spec.ModelGraph`.

L-SPINE's predicted HBM-traffic win comes from keeping spikes and
membranes on-chip; single-layer fusion (kernels/fused_conv) still writes
every layer's 1-bit output planes back to HBM for the next layer to
re-read.  A :class:`~repro.graph.spec.FusionGroup` annotation chains 2+
layers' full T-step rollouts into ONE kernel call
(kernels/fused_group) so the inter-member planes never leave VMEM — and
this module is where such chains are proposed and policed:

  * :func:`plan_fusion_groups` — greedy legal proposal: maximal chains
    of contiguous stride-1 post-stem Convs (with interleaved Pools) at
    the top level, plus each stride-1 Residual body (conv1 → conv2),
    each chain capped by the computed VMEM budget.
  * :func:`validate_group` — the legality rules, with actionable errors:
    groups must be ≥2 contiguous conv/pool members, post-stem, stride 1,
    one kernel size, entirely inside one region (all top-level, or exactly one residual
    block's body — a chain cannot cross a residual boundary because the
    shortcut needs the pre-body plane), single-precision, pool-divisible,
    and within the per-core VMEM budget (kernels/vmem.py — the SAME
    formula the kernels enforce, so the planner can never admit a group
    the kernel would refuse).
  * :func:`apply_fusion` — attach a fusion request (``"auto"`` or an
    explicit member-name tuple-of-tuples, e.g. from ``cfg.fusion``) to a
    graph; ``()`` is a no-op and the graph lowers exactly as before.

Executors consume the annotation through ``run_graph`` — see
graph/executors.py.  The float/BPTT lowering ignores groups entirely
(fusion is an integer-datapath deployment concept).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

from repro.graph.spec import (
    Conv,
    FusionGroup,
    ModelGraph,
    Pool,
    Residual,
)
from repro.kernels import vmem as _vmem

FusionRequest = Union[str, Sequence[Sequence[str]], None]


def _round32(c: int) -> int:
    return -(-c // 32) * 32


def _graph_bits(graph: ModelGraph, group: Optional[FusionGroup]
                = None) -> int:
    """The weight precision the group's packed members lower at (for the
    VMEM estimate): the group's pinned bits, else the cfg's quantized
    bits, else 8 (a conservative stand-in for unquantized graphs, where
    groups are inert anyway)."""
    if group is not None and group.bits is not None:
        return group.bits
    pc = getattr(graph.cfg, "precision", None)
    if pc is not None and getattr(pc, "quantized", False):
        return pc.bits
    return 8


class _Located:
    """A resolved member: its spec plus where it lives (top-level node
    index, or (block name, body index) inside a Residual)."""

    def __init__(self, spec, top_index=None, block=None, body_index=None):
        self.spec = spec
        self.top_index = top_index
        self.block = block
        self.body_index = body_index


def _locate(graph: ModelGraph, name: str) -> _Located:
    for i, node in enumerate(graph.nodes):
        if node.name == name:
            return _Located(node, top_index=i)
        if isinstance(node, Residual):
            for j, bc in enumerate(node.body):
                if bc.name == name:
                    return _Located(bc, block=node.name, body_index=j)
            if node.proj is not None and node.proj.name == name:
                raise ValueError(
                    f"fusion group member {name!r} is a projection "
                    f"shortcut: it runs in PARALLEL with the block body "
                    f"(both read the pre-body plane), so it cannot join "
                    f"a sequential fusion chain")
    raise ValueError(f"fusion group member {name!r} is not a layer of "
                     f"this graph (known layers: "
                     f"{[s.name for s in graph.iter_flat()]})")


def _member_geometry(graph: ModelGraph,
                     group: FusionGroup) -> List[Dict]:
    """Per-member geometry dicts for :func:`_vmem.group_rollout_vmem_bytes`,
    walking the spatial/channel chain.  Assumes the group already passed
    the structural rules (validate_group calls this last)."""
    bits = _graph_bits(graph, group)
    specs = [_locate(graph, m).spec for m in group.members]
    hw = specs[0].out_hw        # stride-1 SAME: input dims == output dims
    ch = specs[0].c_in
    geoms: List[Dict] = []
    for spec in specs:
        if isinstance(spec, Conv):
            geoms.append({"kind": "conv", "h": hw, "w": hw,
                          "cin_pad": _round32(spec.c_in),
                          "kh": spec.k, "kw": spec.k,
                          "n": _round32(spec.c_out), "bits": bits})
            ch = spec.c_out
        else:                   # Pool
            geoms.append({"kind": "pool", "h": hw, "w": hw,
                          "c": _round32(ch), "window": spec.window})
            hw //= spec.window
    return geoms


def group_vmem_bytes(graph: ModelGraph, group: FusionGroup) -> int:
    """Estimated VMEM working set of the group's fused rollout (one
    batch element, every member's membrane resident) — the number
    ``ModelGraph.summary()`` prints and :func:`validate_group` budgets."""
    return _vmem.group_rollout_vmem_bytes(_member_geometry(graph, group))


def validate_group(graph: ModelGraph, group: FusionGroup,
                   budget: Optional[int] = None) -> FusionGroup:
    """Check one fusion group against the legality rules; returns the
    group, or raises ``ValueError`` naming the rule and the fix."""
    if len(group.members) < 2:
        raise ValueError(
            f"fusion group {group.name!r} has {len(group.members)} "
            f"member(s); a group fuses 2+ layers (a single layer is "
            f"already fused by kernels/fused_conv — drop the annotation)")
    if len(set(group.members)) != len(group.members):
        raise ValueError(f"fusion group {group.name!r} repeats a member: "
                         f"{group.members}")

    located = [_locate(graph, m) for m in group.members]

    # precision: one packed datapath width per chain
    pc = getattr(graph.cfg, "precision", None)
    if group.bits is not None:
        cfg_bits = pc.bits if (pc is not None
                               and getattr(pc, "quantized", False)) else None
        if group.bits != cfg_bits:
            raise ValueError(
                f"fusion group {group.name!r} is precision-mixed: group "
                f"pins W{group.bits} but the graph lowers its packed "
                f"layers at W{cfg_bits} "
                f"(cfg.precision) — a fused chain's inter-member planes "
                f"ride one datapath width; re-deploy the whole graph at "
                f"W{group.bits} or drop the pin")

    # member kinds + stem + stride
    for loc in located:
        spec = loc.spec
        if not isinstance(spec, (Conv, Pool)):
            raise ValueError(
                f"fusion group {group.name!r} member {spec.name!r} is a "
                f"{type(spec).__name__}: only conv/pool chains fuse (the "
                f"dense head and readout have their own kernels)")
        if isinstance(spec, Conv) and spec.stem:
            raise ValueError(
                f"fusion group {group.name!r} starts at the stem "
                f"{spec.name!r}: the stem consumes analog encoded "
                f"currents (not 1-bit spikes), so it stays on the float "
                f"twin and cannot join a packed fusion chain")
        if isinstance(spec, Conv) and spec.stride != 1:
            raise ValueError(
                f"fusion group {group.name!r} member {spec.name!r} has "
                f"stride {spec.stride}: a stride change re-shapes the "
                f"plane mid-chain; fuse up to the stride boundary and "
                f"let the strided layer run its own fused_conv call")
    ks = sorted({loc.spec.k for loc in located
                 if isinstance(loc.spec, Conv)})
    if len(ks) > 1:
        raise ValueError(
            f"fusion group {group.name!r} mixes kernel sizes {ks}: the "
            f"fused kernel lays every member's plane out on one padded "
            f"grid, so a chain runs one k; split the group where k "
            f"changes")
    if not isinstance(located[0].spec, Conv):
        raise ValueError(
            f"fusion group {group.name!r} starts at pool "
            f"{located[0].spec.name!r}: a chain starts at a conv (fold a "
            f"leading pool into the previous group instead)")

    # region: all top-level, or exactly one residual body
    blocks = {loc.block for loc in located}
    if len(blocks) > 1:
        inside = sorted(b for b in blocks if b is not None)
        raise ValueError(
            f"fusion group {group.name!r} crosses a residual boundary "
            f"(members span {inside + (['top-level'] if None in blocks else [])}): "
            f"the shortcut of each block reads the PRE-body plane, which "
            f"a fused chain would keep in VMEM; fuse within one block "
            f"body or between blocks, never across")
    if blocks == {None}:
        idxs = [loc.top_index for loc in located]
        if idxs != list(range(idxs[0], idxs[0] + len(idxs))):
            raise ValueError(
                f"fusion group {group.name!r} members are not contiguous "
                f"in execution order (node indices {idxs}): inter-member "
                f"planes chain through VMEM, so the members must be "
                f"adjacent layers")
    else:
        (block,) = blocks
        body = next(n.body for n in graph.nodes
                    if isinstance(n, Residual) and n.name == block)
        if tuple(group.members) != tuple(c.name for c in body):
            raise ValueError(
                f"fusion group {group.name!r} must cover block "
                f"{block!r}'s full body in order "
                f"({[c.name for c in body]}), got {list(group.members)}: "
                f"the merge consumes the body's final plane")

    # pool divisibility along the spatial chain
    hw = located[0].spec.out_hw
    for loc in located:
        if isinstance(loc.spec, Pool):
            if hw % loc.spec.window or hw < loc.spec.window:
                raise ValueError(
                    f"fusion group {group.name!r} pools a {hw}x{hw} "
                    f"plane by {loc.spec.window}: not divisible; end the "
                    f"group before {loc.spec.name!r}")
            hw //= loc.spec.window

    # VMEM budget — the same formula the kernels enforce
    need = group_vmem_bytes(graph, group)
    cap = budget if budget is not None else _vmem.vmem_budget_bytes()
    if need > cap:
        raise ValueError(
            f"fusion group {group.name!r} ({' + '.join(group.members)}) "
            f"needs ~{_vmem.format_bytes(need)} of VMEM > budget "
            f"{_vmem.format_bytes(cap)}: every member's membrane + the "
            f"inter-member planes must be resident at once; split the "
            f"chain (or raise REPRO_VMEM_BUDGET if the core allows)")
    return group


def plan_fusion_groups(graph: ModelGraph,
                       budget: Optional[int] = None
                       ) -> Tuple[FusionGroup, ...]:
    """Propose legal fusion groups for ``graph``: maximal contiguous
    chains of stride-1 post-stem Convs/Pools of one kernel size at the
    top level, plus each all-stride-1 Residual body, every chain capped
    by the VMEM budget.
    Returns possibly-empty groups; every returned group passes
    :func:`validate_group`."""
    cap = budget if budget is not None else _vmem.vmem_budget_bytes()
    proposals: List[Tuple[str, ...]] = []

    def _fits(members: Sequence[str]) -> bool:
        probe = FusionGroup("probe", tuple(members))
        return group_vmem_bytes(graph, probe) <= cap

    # top-level chains
    i, nodes = 0, graph.nodes
    while i < len(nodes):
        node = nodes[i]
        if not (isinstance(node, Conv) and not node.stem
                and node.stride == 1):
            i += 1
            continue
        members = [node.name]
        hw = node.out_hw
        j = i + 1
        while j < len(nodes):
            nxt = nodes[j]
            if isinstance(nxt, Conv) and not nxt.stem and nxt.stride == 1 \
                    and nxt.k == node.k:
                cand = members + [nxt.name]
            elif isinstance(nxt, Pool) and hw % nxt.window == 0 \
                    and hw >= nxt.window:
                cand = members + [nxt.name]
            else:
                break
            if not _fits(cand):
                break
            members = cand
            if isinstance(nxt, Pool):
                hw //= nxt.window
            j += 1
        if len(members) >= 2:
            proposals.append(tuple(members))
            i = j
        else:
            i += 1

    # residual bodies: conv1 -> conv2 when the block entry is stride 1
    # (strided entries re-shape the plane inside conv1, which the chain
    # contract excludes) and the body runs one kernel size
    for node in nodes:
        if isinstance(node, Residual) \
                and all(c.stride == 1 for c in node.body) \
                and len({c.k for c in node.body}) == 1:
            members = tuple(c.name for c in node.body)
            if len(members) >= 2 and _fits(members):
                proposals.append(members)

    groups = tuple(
        validate_group(graph, FusionGroup(f"fuse.{k}", m), budget=cap)
        for k, m in enumerate(proposals))
    return groups


def apply_fusion(graph: ModelGraph, fusion: FusionRequest) -> ModelGraph:
    """Attach fusion groups per a request (``cfg.fusion``):

      ``()`` / ``None``      — no-op, graph lowers exactly as today
      ``"auto"``             — :func:`plan_fusion_groups`
      ``((name, ...), ...)`` — explicit member chains, each validated

    Returns a new graph (ModelGraph is frozen); the node tuple is
    untouched, so params/init/calibration are unaffected.
    """
    if not fusion:
        return graph
    if fusion == "auto":
        groups = plan_fusion_groups(graph)
    elif isinstance(fusion, str):
        raise ValueError(f"unknown fusion request {fusion!r} "
                         f"(expected 'auto' or explicit member tuples)")
    else:
        groups = tuple(
            validate_group(graph, FusionGroup(f"fuse.{k}", tuple(m)))
            for k, m in enumerate(fusion))
        seen: Dict[str, str] = {}
        for g in groups:
            for m in g.members:
                if m in seen:
                    raise ValueError(
                        f"layer {m!r} is a member of both {seen[m]!r} "
                        f"and {g.name!r}; fusion groups must be disjoint")
                seen[m] = g.name
    if not groups:
        return graph
    return dataclasses.replace(graph, groups=groups)


def body_group(graph: ModelGraph, block: Residual
               ) -> Optional[FusionGroup]:
    """The fusion group covering ``block``'s body, if annotated."""
    body_names = tuple(c.name for c in block.body)
    for g in graph.groups:
        if g.members == body_names:
            return g
    return None
