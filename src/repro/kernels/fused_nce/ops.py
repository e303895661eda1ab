"""Public entry point for the fused NCE rollout (backend-dispatched).

Dispatch rules (see repro.kernels.backend):
  'jnp'       -> ref.fused_nce_rollout_ref (bit-identical scan composition)
  'interpret' -> kernel.fused_nce_rollout_pallas(interpret=True)
  'pallas'    -> kernel.fused_nce_rollout_pallas (compiled, TPU)

The kernel path pads output neurons to a 32-multiple and the packed
contraction dim of both operands to a common k (multiple of 128), hands
the kernel the transposed words (neurons and k on rows, batch on lanes —
kernels/fused_common.py), then transposes and slices the padding back
off.  Zero spike words are inert in the accumulate and the kernel masks
spikes of padded neurons, so padding never changes the visible bits.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core import packing
from repro.core.lif import as_theta_vector
from repro.kernels import backend as _backend
from repro.kernels.fused_nce import kernel as _kernel
from repro.kernels.fused_nce import ref as _ref
from repro.quant.formats import QuantizedTensor

_K_ALIGN = 128  # multiple of 32 (spike word) and of 32/bits for all bits


def _pad_axis(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def fused_nce_rollout(
    spikes_packed_t: jnp.ndarray,  # (T, B, ceil(d_in/32)) int32
    qt: QuantizedTensor,           # packed (d_out, d_in) integer codes
    *,
    d_in: int,
    leak_shift: int,
    threshold_q: int,
    v_reset_q: int = 0,
    soft_reset: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All T timesteps of one NCE layer in a single fused pass.

    ``threshold_q`` is a scalar (legacy, broadcast to every neuron) or a
    per-output-channel int32 vector of length ``d_out`` — the per-channel
    integer threshold fold (theta_q[c] ~ theta / scale[c]) that rides as
    a row-vector operand on the kernel.

    Returns (v_T: (B, d_out) int32,
             out_spikes_packed: (T, B, ceil(d_out/32)) int32), bit-exact
    with the unfused `spike_matmul -> lif_step -> pack_bool` chain.
    """
    n = qt.shape[0]
    theta = as_theta_vector(threshold_q, n)
    be = _backend.get_backend()
    if be == "jnp":
        return _ref.fused_nce_rollout_ref(
            spikes_packed_t, qt, d_in=d_in, leak_shift=leak_shift,
            threshold_q=theta, v_reset_q=v_reset_q,
            soft_reset=soft_reset,
        )

    t_steps, b, _ = spikes_packed_t.shape
    if t_steps == 0:  # degenerate rollout: match lax.scan's empty-ys result
        return (jnp.zeros((b, n), jnp.int32),
                jnp.zeros((0, b, packing.packed_last_dim(n, 1)), jnp.int32))
    vpw_w = packing.values_per_word(qt.bits)
    # common padded contraction dim: spike words to k/32, weight words to
    # k/vpw_w — padded spike words are zero, so the extra columns are inert
    sp = _pad_axis(spikes_packed_t, 2, _K_ALIGN // 32).transpose(0, 2, 1)
    wp = _pad_axis(_pad_axis(qt.data, 0, 32), 1, _K_ALIGN // vpw_w).T
    # padded neurons' theta value is irrelevant: their spikes are masked
    # by n_out inside the kernel before the reset uses theta
    thp = _pad_axis(theta[:, None], 0, 32)
    v, out = _kernel.fused_nce_rollout_pallas(
        sp, wp, thp,
        bits=qt.bits, n_out=n, leak_shift=leak_shift,
        v_reset_q=v_reset_q, soft_reset=soft_reset,
        interpret=(be == "interpret"),
    )
    words_out = packing.packed_last_dim(n, 1)
    return v[:n].T, out[:, :words_out].transpose(0, 2, 1)
