"""Public entry point for the fused packed-conv rollout (backend-dispatched).

Dispatch rules (see repro.kernels.backend):
  'jnp'       -> ref.fused_conv_rollout_ref (bit-identical scan composition)
  'interpret' -> kernel.fused_conv_rollout_pallas(interpret=True)
  'pallas'    -> kernel.fused_conv_rollout_pallas (compiled, TPU)

The kernel path zero-pads the packed spike planes spatially (explicit
SAME/VALID pads from ref.conv_pads — the exact amounts the oracle's XLA
convolution uses), splits them into ``stride**2`` phase planes, moves
the channel words onto rows and flattens the pixels onto lanes
(``fused_common.phase_planes``), pads c_out to a 32-multiple, and transposes the
packed weight words to (k*bits/32, c_out).  It then slices the junk grid
columns and the padding back off (``fused_common.grid_to_nhwc``).  Zero spike
words are inert in the accumulate and the kernel masks spikes of padded
channels, so padding never changes the visible bits.

A layer whose whole output per batch element does not fit the VMEM
budget (kernels/vmem.py — the single budget formula shared with the
kernel's own check and the fusion planner) runs the kernel over a grid
of output tiles (:func:`conv_tile`): blocks of c_out, and for a 1x1
conv blocks of output pixels too.  A layer that fits keeps the untiled
kernel.  Only geometry that no tile fits falls back to the unfused
reference path, with a ``RuntimeWarning``, instead of emitting a kernel
that cannot stay resident; calling kernel.py directly with such geometry
raises.  Each call records how it lowered its layer in the process
registry (``repro.obs.MetricsRegistry.lowered``).
"""

from __future__ import annotations

import warnings
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core import packing
from repro.core.lif import as_theta_vector
from repro.kernels import backend as _backend
from repro.kernels import fused_common as _fc
from repro.kernels import vmem as _vmem
from repro.kernels.fused_conv import kernel as _kernel
from repro.kernels.fused_conv import ref as _ref
from repro.quant.formats import QuantizedConvTensor


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def conv_tile(*, n_phase: int, pq: int, cin_pad: int, kh: int, kw: int,
              l_out: int, n: int, bits: int, budget: int
              ) -> Tuple[Optional[Tuple[int, int]], int]:
    """The output tile a fused conv runs with, and its VMEM working set.

    Returns ``(None, need)`` where the whole layer fits ``budget`` (the
    untiled kernel), else the (pixels, channels) tile that fits with the
    least repeated work: each channel tile unpacks the input plane again
    and each pixel tile the weights, so the cost counted is
    ``pixel tiles x kh*kw*n + channel tiles x padded input lanes``.
    Channel tiles are 32-multiples dividing ``n``; pixel tiles, for 1x1
    convs only (a larger kernel's block would need a halo), are
    128-lane multiples.  Where no tile fits either, returns
    ``(None, need)`` with the whole layer's need, over the budget.
    """
    geo = dict(n_phase=n_phase, pq=pq, cin_pad=cin_pad, kh=kh, kw=kw,
               l_out=l_out, n=n, bits=bits)
    need = _vmem.conv_rollout_vmem_bytes(**geo)
    if need <= budget:
        return None, need
    rows = [32 * d for d in range(n // 32, 0, -1) if (n // 32) % d == 0]
    pixels = [l_out]
    if (kh, kw) == (1, 1):
        n_r = 2
        while pixels[-1] > 128:
            p = _round_up(-(-l_out // n_r), 128)
            if p < pixels[-1]:
                pixels.append(p)
            n_r += 1
    best, best_cost = (None, need), None
    for r in rows:
        for p in pixels:
            tile_need = _vmem.conv_rollout_vmem_bytes(**geo, tile=(p, r))
            if tile_need > budget:
                continue
            n_r = -(-l_out // p)
            lanes = n_r * p if (kh, kw) == (1, 1) else n_phase * pq
            cost = n_r * kh * kw * n + (n // r) * lanes
            if best_cost is None or cost < best_cost:
                best, best_cost = ((p, r), tile_need), cost
    return best


def _record(layer: str, path: str, tile=None, need: int = 0) -> None:
    from repro.obs import default_registry   # local: obs imports graph

    default_registry().lowered(layer, path, tile, need)


def fused_conv_rollout(
    spikes_packed_t: jnp.ndarray,  # (T, B, H, W, ceil(c_in/32)) int32
    qct: QuantizedConvTensor,      # packed HWIO integer codes
    *,
    stride: int = 1,
    padding: _ref.Padding = "SAME",
    leak_shift: int,
    threshold_q: int,
    v_reset_q: int = 0,
    soft_reset: bool = True,
    layer: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All T timesteps of one spiking conv layer in a single fused pass.

    ``threshold_q`` is a scalar (legacy, broadcast to every channel) or a
    per-output-channel int32 vector of length ``c_out`` — the per-channel
    integer threshold fold (theta_q[c] ~ theta / scale[c]) that rides as
    a row-vector operand on the kernel.

    ``layer`` names the layer in the lowering record.

    Returns (v_T: (B, Ho, Wo, c_out) int32,
             out_spikes_packed: (T, B, Ho, Wo, ceil(c_out/32)) int32),
    bit-exact with the unfused `unpack -> int conv -> lif_step ->
    pack_bool` chain of ref.py.
    """
    t_steps, b, h, w, win = spikes_packed_t.shape
    if win != packing.packed_last_dim(qct.c_in, 1):
        raise ValueError(
            f"spike plane carries {win} channel words, weights expect "
            f"{packing.packed_last_dim(qct.c_in, 1)} (c_in={qct.c_in})")
    if qct.c_in_pad != win * 32:
        raise ValueError("quantize_conv cin_pad drifted from the spike "
                         "word layout — requantize the weights")
    theta = as_theta_vector(threshold_q, qct.c_out)

    if _backend.get_backend() == "jnp":
        _record(layer, "unfused")
        return _ref.fused_conv_rollout_ref(
            spikes_packed_t, qct, stride=stride, padding=padding,
            leak_shift=leak_shift, threshold_q=theta,
            v_reset_q=v_reset_q, soft_reset=soft_reset,
        )

    (plh, phh), (plw, phw) = _ref.conv_pads(h, w, qct.kh, qct.kw, stride,
                                            padding)
    ho, wo = (_ref.conv_out_size(h, qct.kh, stride, plh, phh),
              _ref.conv_out_size(w, qct.kw, stride, plw, phw))
    words_out = packing.packed_last_dim(qct.c_out, 1)
    if t_steps == 0:  # degenerate rollout: match lax.scan's empty-ys result
        return (jnp.zeros((b, ho, wo, qct.c_out), jnp.int32),
                jnp.zeros((0, b, ho, wo, words_out), jnp.int32))

    n_pad = _round_up(qct.c_out, 32)
    sp, wq = _fc.phase_planes(spikes_packed_t, ((plh, phh), (plw, phw)),
                          qct.kh, qct.kw, stride, ho, wo)

    # explicit VMEM residency check (the budget the fusion planner and
    # the kernel's own ValueError share): a layer too large for one block
    # per batch element runs in output tiles, and geometry no tile fits
    # degrades to the bit-exact unfused reference path
    budget = _vmem.vmem_budget_bytes()
    tile, need = conv_tile(
        n_phase=stride * stride, pq=sp.shape[-1], cin_pad=qct.c_in_pad,
        kh=qct.kh, kw=qct.kw, l_out=ho * wq, n=n_pad, bits=qct.bits,
        budget=budget)
    if need > budget:
        warnings.warn(
            f"fused_conv geometry (plane {h}x{w}x{qct.c_in_pad}, "
            f"out {ho}x{wo}x{n_pad}, stride {stride}, w{qct.bits}) needs "
            f"~{_vmem.format_bytes(need)} of VMEM > budget "
            f"{_vmem.format_bytes(budget)}, and no output tile of it "
            f"fits; falling back to the unfused reference path "
            f"(bit-exact, but per-timestep HBM traffic)",
            RuntimeWarning, stacklevel=2)
        _record(layer, "unfused")
        return _ref.fused_conv_rollout_ref(
            spikes_packed_t, qct, stride=stride, padding=padding,
            leak_shift=leak_shift, threshold_q=theta,
            v_reset_q=v_reset_q, soft_reset=soft_reset,
        )
    wpk = jnp.pad(qct.data, ((0, n_pad - qct.c_out), (0, 0))).T
    # padded channels' theta value is irrelevant: their spikes are masked
    # by n_out inside the kernel before the reset uses theta
    thp = jnp.pad(theta[:, None], ((0, n_pad - qct.c_out), (0, 0)))

    v, out = _kernel.fused_conv_rollout_pallas(
        sp, wpk, thp,
        bits=qct.bits, kh=qct.kh, kw=qct.kw, cin_pad=qct.c_in_pad,
        stride=stride, ho=ho, wo=wo, wq=wq, n_out=qct.c_out,
        leak_shift=leak_shift,
        v_reset_q=v_reset_q, soft_reset=soft_reset, tile=tile,
        interpret=(_backend.get_backend() == "interpret"),
    )
    _record(layer, "fused" if tile is None else "fused_tiled", tile, need)
    return (_fc.grid_to_nhwc(v, ho, wq, wo, qct.c_out),
            _fc.grid_to_nhwc(out, ho, wq, wo, words_out))
