"""Pallas TPU kernel: fused packed-conv spiking rollout.

The fused_nce design extended to convolutions: all T timesteps of one
spiking conv layer run in a single ``pallas_call`` with no intermediate
HBM traffic.  Dataflow per batch element:

    grid (B, T), T innermost
    t-th step:
      packed phase planes (S, cin_pad/32, Pq) --VPU shift/mask--> (cin_pad, Pq)
      per tap: packed weight rows --VPU shift/mask--> (cin_pad, n) INTb
               MXU: i_syn += Wtap^T . plane[:, off:off+L]   int8 -> int32
      VPU:  v -= v>>leak; v += i_syn; spike = v>=theta; reset
      VPU:  spike tile re-packed to 1-bit channel words, written to HBM

Layout (kernels/fused_common.py): channels on rows, pixels on lanes.
ops.py zero-pads the plane, splits it into ``stride**2`` phase planes
(phase (a, c) holds padded pixels (stride*i + a, stride*j + c)) and
flattens each over a grid of width ``wq``.  Output pixel (i, j) of tap
(di, dj) then reads phase (di % s, dj % s) at lane
``(i + di//s) * wq + j + dj//s``: every tap is one contiguous lane slice
at a static offset, for any stride, and no strided slice or
lane-splitting reshape reaches the compiler.  The kernel computes the
(Ho, wq) output grid; the ``wq - Wo`` trailing columns of each row are
junk that ops.py slices off.

The int32 membrane tile (n, Ho*wq) lives in a VMEM scratch buffer for
the whole T-step scan (T is the innermost grid dim, so each b sees
t = 0..T-1 consecutively and scratch persists).  Per timestep the only
HBM traffic is one packed input plane (1 bit/event) and one packed
output tile.  Weights stay resident per b across all T steps (index map
constant in t).  The whole padded c_out is one block: at the paper's
widths it fits VMEM, and a block that spans the array's last two dims
meets the (8, 128) tiling rule for every c_out.

Geometry contract (enforced by ops.py): channels are packed to
``cin_pad = 32*ceil(c_in/32)`` 1-bit fields, the weights are the
QuantizedConvTensor words transposed to (kh*kw*cin_pad*bits/32, n) with
the same per-tap cin_pad layout (quant.quantize_conv), and n (padded
c_out) is a multiple of 32.  Zero-padded spike bits and zero weight
codes are inert in the accumulate, and the ``n_out`` mask zeroes spikes
of padded output channels so the packed words match
``packing.pack_bool`` bit-for-bit.

Spatial tiling (Ho blocks with halo DMA) is a follow-up — one batch
element's plane must fit the VMEM budget, which holds for the paper's
32x32 CNN workloads.  The working set (kernels/vmem.py — the same
formula the fusion planner budgets groups with) is checked against
``vmem_budget_bytes()`` and an oversized geometry raises ``ValueError``
here; ops.py pre-checks the same number and falls back to the unfused
reference path with a ``RuntimeWarning``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.kernels import fused_common as _fc
from repro.kernels import vmem as _vmem


def _fused_conv_kernel(s_ref, w_ref, th_ref, v_ref, o_ref, v_acc,
                       *, bits: int, taps: Tuple, cin_pad: int,
                       l_out: int, n_out: int, leak_shift: int,
                       v_reset_q: int, soft_reset: bool):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        v_acc[...] = jnp.zeros_like(v_acc)

    planes = {p: _fc.unpack_spikes(s_ref[0, 0, p], cin_pad)
              for p in sorted({p for p, _ in taps})}
    i_syn = _fc.conv_accumulate(planes, w_ref, taps, cin_pad, bits, l_out)
    v, spikes = _fc.lif_fire(
        v_acc[...], i_syn, th_ref[...], _fc.row_mask(i_syn.shape, n_out),
        leak_shift=leak_shift, v_reset_q=v_reset_q, soft_reset=soft_reset)
    v_acc[...] = v
    v_ref[0] = v            # index map constant in t: written back once
    o_ref[0, 0] = packing.pack_bool(spikes, axis=-2)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "kh", "kw", "cin_pad", "stride", "ho", "wo",
                     "wq", "n_out", "leak_shift", "v_reset_q",
                     "soft_reset", "interpret"),
)
def fused_conv_rollout_pallas(
    spikes_packed_t: jnp.ndarray,  # (T, B, stride**2, cin_pad/32, Pq) int32
    w_packed_t: jnp.ndarray,       # (kh*kw*cin_pad*bits/32, n) int32
    theta_q: jnp.ndarray,          # (n, 1) int32 per-channel thresholds
    *,
    bits: int,
    kh: int,
    kw: int,
    cin_pad: int,
    stride: int,
    ho: int,
    wo: int,
    wq: int,                       # width of the flattened phase grid
    n_out: int,                    # true c_out (<= n); masks padded channels
    leak_shift: int,
    v_reset_q: int = 0,
    soft_reset: bool = True,
    interpret: bool = False,
):
    t_steps, b, n_phase, wc, pq = spikes_packed_t.shape
    kw_rows, n = w_packed_t.shape
    if cin_pad % 32 or wc * 32 != cin_pad:
        raise ValueError(
            f"cin_pad={cin_pad} must be a 32-multiple matching the "
            f"{wc} packed channel words (caller ops.py must pad channels)")
    k_flat = kh * kw * cin_pad
    vpw_w = packing.values_per_word(bits)
    if kw_rows * vpw_w != k_flat:
        raise ValueError(
            f"packed contraction mismatch: weights describe "
            f"{kw_rows * vpw_w}, the taps need k={k_flat}")
    if n_phase != stride * stride:
        raise ValueError(f"stride {stride} needs {stride * stride} phase "
                         f"planes, got {n_phase}")
    taps = _fc.tap_offsets(kh, kw, stride, wq)
    l_out = ho * wq
    if wo > wq or max(off for _, off in taps) + l_out > pq:
        raise ValueError("phase planes shorter than the gather footprint "
                         "(caller ops.py must pre-pad)")
    if n % 32:
        raise ValueError("caller (ops.py) must pad c_out to a 32-multiple")
    if theta_q.shape != (n, 1):
        raise ValueError(
            f"theta_q must be ({n}, 1) per-channel thresholds, "
            f"got {theta_q.shape} (caller ops.py must pad)")
    need = _vmem.conv_rollout_vmem_bytes(
        n_phase=n_phase, pq=pq, cin_pad=cin_pad, kh=kh, kw=kw,
        l_out=l_out, n=n, bits=bits)
    budget = _vmem.vmem_budget_bytes()
    if need > budget:
        raise ValueError(
            f"fused_conv working set exceeds the per-core VMEM budget: "
            f"needs ~{_vmem.format_bytes(need)} > "
            f"{_vmem.format_bytes(budget)} for {n_phase} phase plane(s) "
            f"of {pq} pixels x {cin_pad} channels, k={kh}x{kw}, out "
            f"{ho}x{wo}, n={n}, w{bits}.  Dispatch through fused_conv_ops "
            f"to fall back to the unfused path, or raise "
            f"REPRO_VMEM_BUDGET if your core has more VMEM.")
    kernel = functools.partial(
        _fused_conv_kernel,
        bits=bits, taps=taps, cin_pad=cin_pad, l_out=l_out, n_out=n_out,
        leak_shift=leak_shift, v_reset_q=v_reset_q, soft_reset=soft_reset,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, t_steps),
        in_specs=[
            pl.BlockSpec((1, 1, n_phase, wc, pq),
                         lambda i, t: (t, i, 0, 0, 0)),
            pl.BlockSpec((kw_rows, n), lambda i, t: (0, 0)),
            pl.BlockSpec((n, 1), lambda i, t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n, l_out), lambda i, t: (i, 0, 0)),
            pl.BlockSpec((1, 1, n // 32, l_out), lambda i, t: (t, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, l_out), jnp.int32),
            jax.ShapeDtypeStruct((t_steps, b, n // 32, l_out), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((n, l_out), jnp.int32)],
        # batch elements are independent; T carries the membrane
        # recurrence through scratch and must stay sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * t_steps * b * ho * wo * k_flat * n,
            bytes_accessed=(
                spikes_packed_t.size * 4              # planes
                + w_packed_t.size * 4 + n * 4         # weights, theta
                + b * l_out * n * 4                   # membrane out
                + t_steps * b * l_out * n // 8),      # spikes out
            transcendentals=0,
        ),
        interpret=interpret,
    )(spikes_packed_t, w_packed_t, theta_q)
