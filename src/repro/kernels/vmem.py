"""Shared VMEM budget accounting for the fused rollout kernels.

The fused kernels' entire win is VMEM residency: the membrane tile (and,
for fusion groups, every inter-layer spike plane) lives in on-chip
scratch for the whole T-step rollout.  That only works if the working
set fits the kernel's scoped VMEM limit (16 MiB by default on a TPU
v5e); a kernel whose buffers exceed it fails to compile.  This module
makes the working set an explicit, shared number:

  * :func:`conv_rollout_vmem_bytes` — the per-batch-element VMEM working
    set of one fused conv rollout (kernels/fused_conv), from static
    geometry alone.
  * :func:`group_rollout_vmem_bytes` — the same for a multi-layer fusion
    group (kernels/fused_group), where every member's weights and
    membrane scratch are resident at once.
  * :func:`vmem_budget_bytes` — the budget both the kernels (loud
    ``ValueError`` / unfused fallback) and the fusion planner
    (``repro.graph.fusion`` group legality) check against.  One number,
    one formula site: the planner can never admit a group the kernel
    would refuse.

The formulas count the buffers of the kernels as written, including the
double-buffered operand and result blocks the Pallas pipeline allocates,
the int32 intermediates of the in-kernel unpack, and the lane padding of
narrow blocks.  They overcount: compiled for a v5e, every paper-width
kernel fits a scoped VMEM limit 2-6x below its estimate (the smallest
``vmem_limit_bytes`` each compiles under), so the default budget, under
the 16 MiB default limit, needs no ``vmem_limit_bytes`` in any kernel.
Override the budget with the ``REPRO_VMEM_BUDGET`` env var (bytes) for
experiments or tests.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence, Tuple

# default scoped VMEM limit of a Mosaic kernel on a TPU v5e core; the
# budget keeps headroom under it for the compiler's internal scratch
VMEM_BYTES_PER_CORE = 16 * 1024 * 1024
DEFAULT_BUDGET_BYTES = 12 * 1024 * 1024

_ENV_VAR = "REPRO_VMEM_BUDGET"
_LANES = 128


def vmem_budget_bytes() -> int:
    """The per-core VMEM byte budget fused kernels must fit in.
    ``REPRO_VMEM_BUDGET`` (bytes) overrides the default — used by tests
    to exercise the over-budget paths without allocating real memory."""
    env = os.environ.get(_ENV_VAR)
    if env:
        try:
            return int(env)
        except ValueError as e:
            raise ValueError(
                f"{_ENV_VAR}={env!r} is not an integer byte count") from e
    return DEFAULT_BUDGET_BYTES


def _lanes(x: int) -> int:
    """Lane extent a buffer occupies: the last dim rounds up to 128."""
    return -(-x // _LANES) * _LANES


def _rows(x: int) -> int:
    """Sublane extent of an int32 buffer: rounds up to 8."""
    return -(-x // 8) * 8


def _conv_core_bytes(*, pq: int, cin_pad: int, kh: int, kw: int,
                     l_out: int, n: int, bits: int) -> int:
    """The buffers one conv layer keeps live while it computes, apart
    from its input plane block: weights (double-buffered block), one
    tap's unpacked weights, the tap slice, the int32 current, membrane
    and spike tiles, and the theta / membrane / packed-spike blocks."""
    k_flat = kh * kw * cin_pad
    lo = _lanes(l_out)
    return (2 * k_flat * bits // 8 * _lanes(n) // n   # packed weights x2
            + 9 * cin_pad * _lanes(n)         # tap codes: int32 x2 + int8
            + 2 * cin_pad * lo                # tap slice (int8) + copy
            + 2 * _lanes(pq) * cin_pad * 5    # unpacked plane int32 + int8
            + 8 * n * lo * 4                  # i_syn, dot, v, spikes,
                                              # scratch, v out block x2
            + 2 * n * _LANES * 4              # theta column block x2
            + 2 * _rows(n // 32) * lo * 4)    # packed out block x2


def conv_rollout_vmem_bytes(*, n_phase: int, pq: int, cin_pad: int,
                            kh: int, kw: int, l_out: int, n: int,
                            bits: int, tile: Optional[Tuple[int, int]] = None
                            ) -> int:
    """VMEM working set of one fused conv rollout step, per batch
    element, from static geometry.

    ``n_phase`` phase planes of ``pq`` flattened pixels each arrive per
    timestep (kernels/fused_common.phase_planes), ``cin_pad`` is the
    32-padded input channel count, ``l_out`` the flattened output grid
    (Ho * wq), ``n`` the 32-padded c_out and ``bits`` the weight
    precision.

    ``tile`` = (pixels, channels) counts one output block of a tiled
    rollout (kernels/fused_conv ``tile``) instead of the whole layer:
    ``channels`` of the c_out and ``pixels`` lanes of the flattened
    output grid.  A tiled 1x1 conv reads the same pixels of its one
    phase plane; a larger kernel reads the whole plane (a block of fewer
    pixels would need a halo).
    """
    if tile is not None:
        pixels, n = tile
        if (kh, kw) == (1, 1):
            n_phase, pq, l_out = 1, pixels, pixels
        elif pixels != l_out:
            raise ValueError(f"a {kh}x{kw} conv tiles channels only")
    plane_block = 2 * n_phase * _rows(cin_pad // 32) * _lanes(pq) * 4
    return (plane_block
            + (n_phase - 1) * 2 * _lanes(pq) * cin_pad * 5
            + _conv_core_bytes(pq=pq, cin_pad=cin_pad, kh=kh, kw=kw,
                               l_out=l_out, n=n, bits=bits))


def group_rollout_vmem_bytes(members: Sequence[Dict]) -> int:
    """VMEM working set of a multi-layer fusion-group rollout (one batch
    element, all members' weights and membranes resident at once).

    ``members`` is a sequence of geometry dicts:

      {"kind": "conv", "h", "w", "cin_pad", "kh", "kw", "n", "bits"}
          h/w are the member's (unpadded) input plane dims — stride-1
          SAME convs, so output dims equal input dims; ``n`` is the
          32-padded c_out.
      {"kind": "pool", "h", "w", "c", "window"}
          c is the (padded) channel count of the pooled plane.

    Each conv member counts its full single-layer working set on its
    padded (h + kh) x (w + kw - 1) grid; each pool counts its one-hot
    selection operand (double-buffered int8) and its int32 window-max
    and result planes.  The sum is conservative — buffers of different
    members mostly do not coexist, apart from weights and membranes —
    which is the right direction for a budget.
    """
    total = 0
    grid_w = None               # width of the plane grid entering a member
    for i, m in enumerate(members):
        if m["kind"] == "conv":
            wq = m["w"] + m["kw"] - 1
            pq = (m["h"] + m["kh"]) * wq
            if i == 0:
                total += 2 * _rows(m["cin_pad"] // 32) * _lanes(pq) * 4
            total += _conv_core_bytes(
                pq=pq, cin_pad=m["cin_pad"], kh=m["kh"], kw=m["kw"],
                l_out=m["h"] * wq, n=m["n"], bits=m["bits"])
            grid_w = wq
        elif m["kind"] == "pool":
            p = m["window"]
            l_in = m["h"] * grid_w
            l_max = l_in + (p - 1) * (grid_w + 1)
            nxt = members[i + 1] if i + 1 < len(members) else None
            ho, wo = m["h"] // p, m["w"] // p
            if nxt is not None and nxt["kind"] == "conv":
                grid_w = wo + nxt["kw"] - 1
                p_out = (ho + nxt["kh"]) * grid_w
            else:
                grid_w = wo
                p_out = ho * wo
            total += (2 * _rows(l_in) * _lanes(p_out)       # selection x2
                      + 2 * m["c"] * _lanes(l_max) * 4      # window max
                      + m["c"] * _lanes(p_out) * 4)         # pooled plane
        else:
            raise ValueError(f"unknown member kind {m['kind']!r}")
    return total


def format_bytes(n: int) -> str:
    """Human-readable byte count for error messages and summaries."""
    if n >= 1024 * 1024:
        return f"{n / (1024 * 1024):.1f} MiB"
    if n >= 1024:
        return f"{n / 1024:.1f} KiB"
    return f"{n} B"
