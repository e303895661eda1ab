"""Pallas TPU kernel: fused multi-precision NCE rollout.

The paper's headline datapath in one ``pallas_call``: all T timesteps of
one layer's spike-gated accumulate + shift-add LIF update run without any
intermediate HBM traffic.  Dataflow:

    grid (T,)
    t-th step:
      packed spikes  (k/32, m)       --VPU shift/mask--> (k, m) binary
      packed weights (k*bits/32, n)  --VPU shift/mask--> (k, n) INTb codes,
                                        one k chunk at a time
      MXU:  i_syn = Wq^T . s          int8 x int8 -> int32   (n, m)
      VPU:  v -= v>>leak; v += i_syn; spike = v>=theta; reset
      VPU:  spike tile re-packed to 1-bit words, written to HBM

The int32 membrane tile lives in a VMEM scratch buffer for the whole
T-step scan (TPU scratch persists across grid steps, and T is the grid).
Per timestep the only HBM traffic is the packed input-spike block (1
bit/event) and the packed output-spike block — the unfused chain
(`spike_matmul` -> `lif_step` -> `pack_bool`) moves the int32 current and
membrane tensors through HBM at every step instead.  Weights stay
resident across all T steps (index map constant in t), so the packed
weight block is fetched once per rollout.

Layout (kernels/fused_common.py): neurons on rows, batch on lanes.  The
kernel takes the pack_bool spike words as (T, k/32, m) and the
QuantizedTensor words as (k*bits/32, n) — both transposed by ops.py —
and returns (n, m) membranes and (T, n/32, m) spike words.  Every block
spans its whole array, so the (8, 128) tiling rule holds for any m, n.

Padding contract (enforced by ops.py): n % 32 == 0 and the packed k
words of spikes/weights describe the same padded k (multiple of 128).
Zero-padded spike words contribute nothing to the accumulate, and the
`n_out` mask zeroes spikes from padded output neurons so the packed
words match ``packing.pack_bool`` bit-for-bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.kernels import fused_common as _fc

# contraction values unpacked per weight chunk: bounds the int32 unpack
# intermediates at K_CHUNK * n * 4 bytes whatever the layer's d_in
K_CHUNK = 512


def _fused_nce_kernel(s_ref, w_ref, th_ref, v_ref, o_ref, v_acc,
                      *, bits: int, leak_shift: int,
                      v_reset_q: int, soft_reset: bool, n_out: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        v_acc[...] = jnp.zeros_like(v_acc)

    k = s_ref.shape[1] * 32
    s = _fc.unpack_spikes(s_ref[0], k)                  # (k, m)
    vpw = packing.values_per_word(bits)
    i_syn = None
    for k0 in range(0, k, K_CHUNK):
        kc = min(K_CHUNK, k - k0)
        d = _fc.codes_dot(_fc.weight_codes(w_ref, k0 // vpw, kc // vpw, bits),
                          s[k0:k0 + kc])
        i_syn = d if i_syn is None else i_syn + d       # (n, m)

    v, spikes = _fc.lif_fire(
        v_acc[...], i_syn, th_ref[...], _fc.row_mask(i_syn.shape, n_out),
        leak_shift=leak_shift, v_reset_q=v_reset_q, soft_reset=soft_reset)
    v_acc[...] = v
    v_ref[...] = v          # index map constant in t: written back once
    o_ref[0] = packing.pack_bool(spikes, axis=-2)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "n_out", "leak_shift",
                     "v_reset_q", "soft_reset", "interpret"),
)
def fused_nce_rollout_pallas(
    spikes_packed_t: jnp.ndarray,  # (T, k/32, m) int32, pack_bool words^T
    w_packed_t: jnp.ndarray,       # (k*bits/32, n) int32, packed codes^T
    theta_q: jnp.ndarray,          # (n, 1) int32 per-channel thresholds
    *,
    bits: int,
    n_out: int,                    # true d_out (<= n); masks padded neurons
    leak_shift: int,
    v_reset_q: int = 0,
    soft_reset: bool = True,
    interpret: bool = False,
):
    t_steps, win, m = spikes_packed_t.shape
    kw_rows, n = w_packed_t.shape
    vpw_w = packing.values_per_word(bits)
    k = win * 32
    if kw_rows * vpw_w != k:
        raise ValueError(
            f"packed k mismatch: spikes describe k={k}, weights "
            f"{kw_rows * vpw_w} (caller ops.py must pad both)")
    if k % 128:
        raise ValueError(f"k={k} must be a multiple of 128 (ops.py pads)")
    if n % 32:
        raise ValueError(f"n={n} must be a multiple of 32 (spike word)")
    if theta_q.shape != (n, 1):
        raise ValueError(
            f"theta_q must be ({n}, 1) per-channel thresholds, "
            f"got {theta_q.shape} (caller ops.py must pad)")
    kernel = functools.partial(
        _fused_nce_kernel,
        bits=bits, leak_shift=leak_shift,
        v_reset_q=v_reset_q, soft_reset=soft_reset, n_out=n_out,
    )
    return pl.pallas_call(
        kernel,
        grid=(t_steps,),
        in_specs=[
            pl.BlockSpec((1, win, m), lambda t: (t, 0, 0)),
            pl.BlockSpec((kw_rows, n), lambda t: (0, 0)),
            pl.BlockSpec((n, 1), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n, m), lambda t: (0, 0)),
            pl.BlockSpec((1, n // 32, m), lambda t: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, m), jnp.int32),
            jax.ShapeDtypeStruct((t_steps, n // 32, m), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((n, m), jnp.int32)],
        # T carries the membrane recurrence through scratch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * t_steps * m * k * n,
            bytes_accessed=(spikes_packed_t.size * 4 + w_packed_t.size * 4
                            + n * 4 + m * n * 4 + t_steps * m * n // 8),
            transcendentals=0,
        ),
        interpret=interpret,
    )(spikes_packed_t, w_packed_t, theta_q)
