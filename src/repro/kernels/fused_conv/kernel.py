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
constant in t).  Where the layer fits VMEM the whole padded c_out and
the whole output grid are one block, which spans the array's last two
dims and so meets the (8, 128) tiling rule for every c_out.

Geometry contract (enforced by ops.py): channels are packed to
``cin_pad = 32*ceil(c_in/32)`` 1-bit fields, the weights are the
QuantizedConvTensor words transposed to (kh*kw*cin_pad*bits/32, n) with
the same per-tap cin_pad layout (quant.quantize_conv), and n (padded
c_out) is a multiple of 32.  Zero-padded spike bits and zero weight
codes are inert in the accumulate, and the ``n_out`` mask zeroes spikes
of padded output channels so the packed words match
``packing.pack_bool`` bit-for-bit.

A layer too wide for that (ImageNet-size planes, 1x1 convs of 256-2048
channels) runs with ``tile`` = (pixels, channels): grid (B, c_out tiles,
pixel tiles, T), T still innermost, one membrane scratch per output
block (:func:`_tiled_call`).  Pixel tiles are for 1x1 convs, whose
output lane q reads input lane q of one phase plane, so a block needs
no halo; a larger kernel tiles c_out only (row blocks with a halo DMA
are a follow-up).  The working set (kernels/vmem.py — the same formula
the fusion planner budgets groups with, counting one tile where there
is one) is checked against ``vmem_budget_bytes()`` and an oversized
geometry raises ``ValueError`` here; ops.py picks the tile with the same
formula and falls back to the unfused reference path, with a
``RuntimeWarning``, only where no tile fits.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

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
                       v_reset_q: int, soft_reset: bool,
                       tiled: bool = False):
    # grid (B, T), or (B, channel tiles, pixel tiles, T) when tiled
    t = pl.program_id(3 if tiled else 1)

    @pl.when(t == 0)
    def _init():
        v_acc[...] = jnp.zeros_like(v_acc)

    planes = {p: _fc.unpack_spikes(s_ref[0, 0, p], cin_pad)
              for p in sorted({p for p, _ in taps})}
    i_syn = _fc.conv_accumulate(planes, w_ref, taps, cin_pad, bits, l_out)
    # rows past n_out are padded channels; row r of channel tile c is
    # channel c * rows + r
    if tiled:
        n_out = n_out - pl.program_id(1) * v_acc.shape[0]
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
                     "soft_reset", "tile", "interpret"),
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
    tile: Optional[Tuple[int, int]] = None,
    interpret: bool = False,
):
    """``tile`` = (pixels, channels) runs the rollout as a grid of output
    blocks (see :func:`_tiled_call`); None keeps one block per batch
    element."""
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
    if tile is not None:
        pixels, rows = tile
        if n % rows or rows % 32:
            raise ValueError(f"a tile of {rows} channels must be a "
                             f"32-multiple dividing n={n}")
        if pixels != l_out and ((kh, kw) != (1, 1) or pixels % 128):
            raise ValueError(f"a block of {pixels} pixels needs a 1x1 "
                             f"conv and a 128-multiple")
    need = _vmem.conv_rollout_vmem_bytes(
        n_phase=n_phase, pq=pq, cin_pad=cin_pad, kh=kh, kw=kw,
        l_out=l_out, n=n, bits=bits, tile=tile)
    budget = _vmem.vmem_budget_bytes()
    if need > budget:
        raise ValueError(
            f"fused_conv working set exceeds the per-core VMEM budget: "
            f"needs ~{_vmem.format_bytes(need)} > "
            f"{_vmem.format_bytes(budget)} for {n_phase} phase plane(s) "
            f"of {pq} pixels x {cin_pad} channels, k={kh}x{kw}, out "
            f"{ho}x{wo}, n={n}, tile {tile}, w{bits}.  Dispatch through "
            f"fused_conv_ops to pick a tile that fits or fall back to the "
            f"unfused path, or raise REPRO_VMEM_BUDGET if your core has "
            f"more VMEM.")
    kernel = functools.partial(
        _fused_conv_kernel,
        bits=bits, taps=taps, cin_pad=cin_pad, n_out=n_out,
        leak_shift=leak_shift, v_reset_q=v_reset_q, soft_reset=soft_reset,
    )
    cost = pl.CostEstimate(
        flops=2 * t_steps * b * ho * wo * k_flat * n,
        bytes_accessed=(
            spikes_packed_t.size * 4              # planes
            + w_packed_t.size * 4 + n * 4         # weights, theta
            + b * l_out * n * 4                   # membrane out
            + t_steps * b * l_out * n // 8),      # spikes out
        transcendentals=0,
    )
    if tile is not None:
        return _tiled_call(
            functools.partial(kernel, l_out=tile[0], tiled=True),
            spikes_packed_t, w_packed_t, theta_q, tile=tile, taps=taps,
            l_out=l_out, cost=cost, interpret=interpret)
    return pl.pallas_call(
        functools.partial(kernel, l_out=l_out),
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
        cost_estimate=cost,
        interpret=interpret,
    )(spikes_packed_t, w_packed_t, theta_q)


def _tiled_call(kernel, sp, w, theta, *, tile: Tuple[int, int],
                taps: Tuple, l_out: int, cost, interpret: bool):
    """The rollout as a grid of (pixels, rows) output blocks: grid
    (B, c_out tiles, pixel tiles, T), T innermost with one membrane
    scratch per block, so the block's membrane stays in VMEM for its
    whole rollout.  A channel tile reads the whole input plane and its
    own columns of the weights; a pixel tile (1x1 convs only, which need
    no halo) reads the same lanes of the one phase plane its tap reads,
    phase 0, and the weights stay resident while its pixel tiles run.
    The flattened pixels are zero-padded to whole blocks, and the
    channel tile is a leading dim of the weights and outputs, so every
    block spans its array's last two dims or 128-lane multiples of
    them.  Returns the untiled call's (v, out) arrays."""
    t_steps, b, n_phase, wc, pq = sp.shape
    kw_rows, n = w.shape
    pixels, rows = tile
    n_c = n // rows
    n_r = -(-l_out // pixels)
    p_out = n_r * pixels
    if taps == ((0, 0),):          # 1x1: output lane q reads plane 0's q
        sp = jnp.pad(sp[:, :, :1], ((0, 0),) * 4 + ((0, p_out - pq),))
        in_block = (1, 1, 1, wc, pixels)
    else:
        in_block = (1, 1, n_phase, wc, pq)
    v, out = pl.pallas_call(
        kernel,
        grid=(b, n_c, n_r, t_steps),
        in_specs=[
            pl.BlockSpec(in_block, lambda i, c, r, t: (t, i, 0, 0, r)),
            pl.BlockSpec((None, kw_rows, rows), lambda i, c, r, t: (c, 0, 0)),
            pl.BlockSpec((None, rows, 1), lambda i, c, r, t: (c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, None, rows, pixels),
                         lambda i, c, r, t: (i, c, 0, r)),
            pl.BlockSpec((1, 1, None, rows // 32, pixels),
                         lambda i, c, r, t: (t, i, c, 0, r)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_c, rows, p_out), jnp.int32),
            jax.ShapeDtypeStruct((t_steps, b, n_c, rows // 32, p_out),
                                 jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, pixels), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=cost,
        interpret=interpret,
    )(sp, w.reshape(kw_rows, n_c, rows).transpose(1, 0, 2),
      theta.reshape(n_c, rows, 1))
    return (v.reshape(b, n, p_out)[..., :l_out],
            out.reshape(t_steps, b, n // 32, p_out)[..., :l_out])
