"""Spiking layers (functional init/apply, dict-pytree params).

Each layer computes synaptic currents with a (optionally fake-quantized)
linear/conv op and applies LIF dynamics over T timesteps.  Training uses
the float/surrogate twin; deployment uses the integer path through the
NCE (core/nce.py) with packed weights — ``spiking_dense_int_apply``
runs the whole T-step layer through the fused NCE rollout kernel
(kernels/fused_nce), the deployment twin of ``spiking_dense_apply``.

These are the per-layer primitives the graph executors
(repro.graph.executors) lower ModelGraph nodes onto; model topology
lives in the graph, never here.

Layout convention: time axis first — activations are (T, B, ...).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.lif import LIFConfig, lif_rollout_float
from repro.quant.formats import PrecisionConfig
from repro.quant.qat import fake_quant


def _fold_threshold_q(scale, lif: LIFConfig) -> jnp.ndarray:
    """Fold the float threshold into the integer domain per output channel
    (theta_q[c] ~ theta / scale[c]).

    ``scale`` is the quantizer's per-channel scale array, shape
    ``(n_out, n_groups)`` — one group for the per-channel quantization the
    integer datapath uses, so the fold is exact per channel; grouped
    scales average across groups (the accumulate ignores group boundaries
    and the fold can only carry one constant per channel).  The result is
    a traced-friendly int32 vector: it rides as an array operand on the
    fused kernels, so the fold works under jit.
    """
    s = jnp.asarray(scale, jnp.float32)
    if s.ndim > 1:
        s = jnp.mean(s, axis=-1)
    s = s.reshape(-1)
    theta = jnp.round(lif.threshold / jnp.maximum(s, 1e-12))
    return jnp.maximum(theta, 1.0).astype(jnp.int32)


def pack_dense_weights(params, pc: PrecisionConfig):
    """Quantize + pack a dense layer's float params to the NCE format,
    threshold-balancing gain folded in.  The single code site behind both
    the per-call int twin and the one-shot deploy package
    (repro.deploy.package) — their bit-exactness contract lives here.
    Returns a packed ``QuantizedTensor`` in (d_out, d_in) layout."""
    from repro.quant.ptq import quantize

    w = params["w"]                       # (d_in, d_out) float
    if "g" in params:
        w = w * params["g"]
    return quantize(w.T, pc)


def pack_conv_weights(params, pc: PrecisionConfig):
    """Conv twin of :func:`pack_dense_weights`: HWIO float params ->
    packed ``QuantizedConvTensor`` (gain folded in)."""
    from repro.quant.ptq import quantize_conv

    w = params["w"]                       # (kh, kw, c_in, c_out) float
    if "g" in params:
        w = w * params["g"]
    return quantize_conv(w, pc)


def _maybe_fq(w: jnp.ndarray, pc: Optional[PrecisionConfig]) -> jnp.ndarray:
    if pc is not None and pc.quantized:
        # weights are (in, out) / conv OIHW-flattened; fake-quant groups run
        # along the last axis, so transpose to put the contraction last.
        return jnp.swapaxes(
            fake_quant(jnp.swapaxes(w, -1, -2), pc), -1, -2
        )
    return w


# ---------------------------------------------------------------------------
# Dense spiking layer
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32):
    scale = (2.0 / d_in) ** 0.5
    return {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale,
            "g": jnp.ones((d_out,), dtype)}


def spiking_dense_apply(
    params,
    spikes_t: jnp.ndarray,      # (T, B, d_in) — {0,1} spikes or float currents
    lif: LIFConfig,
    pc: Optional[PrecisionConfig] = None,
):
    """Synaptic accumulation + LIF rollout.  Returns (T, B, d_out) spikes."""
    w = _maybe_fq(params["w"], pc)
    i_syn_t = jnp.einsum("tbi,io->tbo", spikes_t.astype(w.dtype), w)
    if "g" in params:  # threshold-balancing gain (calibrated + learnable)
        i_syn_t = i_syn_t * params["g"]
    v0 = jnp.zeros(i_syn_t.shape[1:], i_syn_t.dtype)
    _, s_t = lif_rollout_float(v0, i_syn_t, lif)
    return s_t


def spiking_dense_int_apply(
    params,
    spikes_t: jnp.ndarray,      # (T, B, d_in) — {0,1} binary spikes
    lif: LIFConfig,
    pc: PrecisionConfig,
    threshold_q=None,
    qt=None,
):
    """Integer deployment twin of :func:`spiking_dense_apply`.

    Quantizes ``params['w']`` (with the calibrated threshold-balancing
    gain ``g`` folded in, when present) to the packed NCE format and runs
    all T timesteps through the fused NCE rollout kernel: spikes are
    bit-packed once on entry, the membrane stays on-chip for the whole
    scan, and the layer's output spikes come back as 1-bit words.  The
    float threshold is folded into the integer domain per output channel
    (theta_q[c] ~ theta / scale[c]) exactly as core/nce.py folds scaling
    out of the datapath.

    Quantization (incl. the 2/4-bit MSE clip search) reruns on every
    call when quantizing from float params; latency-sensitive callers
    should quantize once at deployment time (repro.deploy.package) and
    pass the packed ``qt`` (with ``threshold_q``) instead — ``params``
    is then ignored.

    Returns (T, B, d_out) {0,1} int32 spikes.
    """
    from repro.kernels import fused_nce_ops

    if qt is None:
        qt = pack_dense_weights(params, pc)
    if qt.bits != pc.bits:
        raise ValueError(f"packed weights are {qt.bits}-bit, "
                         f"precision asks for {pc.bits}-bit")
    if threshold_q is None:
        threshold_q = _fold_threshold_q(qt.scale, lif)
    d_out, d_in = qt.shape
    packed_in = packing.pack_bool(spikes_t.astype(jnp.int32))
    _, packed_out = fused_nce_ops.fused_nce_rollout(
        packed_in, qt, d_in=d_in, leak_shift=lif.leak_shift,
        threshold_q=threshold_q, soft_reset=lif.soft_reset,
    )
    return packing.unpack_bool(packed_out, d_out)


# ---------------------------------------------------------------------------
# Conv2D spiking layer (NHWC)
# ---------------------------------------------------------------------------

def conv_init(key, c_in: int, c_out: int, k: int = 3, dtype=jnp.float32):
    scale = (2.0 / (c_in * k * k)) ** 0.5
    return {"w": jax.random.normal(key, (k, k, c_in, c_out), dtype) * scale,
            "g": jnp.ones((c_out,), dtype)}


def _conv2d(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def spiking_conv_apply(
    params,
    spikes_t: jnp.ndarray,      # (T, B, H, W, C)
    lif: LIFConfig,
    pc: Optional[PrecisionConfig] = None,
    stride: int = 1,
):
    w = params["w"]
    if pc is not None and pc.quantized:
        # per-output-channel groups: reshape (k,k,ci,co)->(co, k*k*ci)
        k1, k2, ci, co = w.shape
        wt = w.transpose(3, 0, 1, 2).reshape(co, k1 * k2 * ci)
        wt = fake_quant(wt, pc)
        w = wt.reshape(co, k1, k2, ci).transpose(1, 2, 3, 0)
    conv = lambda x: _conv2d(x.astype(w.dtype), w, stride=stride)
    i_syn_t = jax.vmap(conv)(spikes_t)
    if "g" in params:  # threshold-balancing gain (calibrated + learnable)
        i_syn_t = i_syn_t * params["g"]
    v0 = jnp.zeros(i_syn_t.shape[1:], i_syn_t.dtype)
    _, s_t = lif_rollout_float(v0, i_syn_t, lif)
    return s_t


def spiking_conv_int_apply(
    params,
    spikes_t: jnp.ndarray,      # (T, B, H, W, C) — {0,1} binary spikes
    lif: LIFConfig,
    pc: PrecisionConfig,
    stride: int = 1,
    threshold_q=None,
    qct=None,
    layer: str = "",
):
    """Integer deployment twin of :func:`spiking_conv_apply`.

    Quantizes ``params['w']`` (HWIO, with the calibrated gain ``g``
    folded in when present) to the packed im2col conv format and runs all
    T timesteps through the fused conv rollout kernel
    (kernels/fused_conv): spike planes are bit-packed along the channel
    axis once on entry, the membrane stays on-chip for the whole scan,
    and the output spikes come back as 1-bit channel words.  The float
    threshold folds into the integer domain per output channel
    (theta_q[c] ~ theta / scale[c]), exactly like the dense twin.

    Quantization (incl. the 2/4-bit MSE clip search) reruns on every
    call when quantizing from float params; latency-sensitive callers
    should quantize once at deployment time (repro.deploy.package) and
    pass the packed ``qct`` (with ``threshold_q``) instead — ``params``
    is then ignored.

    ``layer`` names the layer in the kernel's lowering record.

    Returns (T, B, Ho, Wo, c_out) {0,1} int32 spikes (SAME padding, as
    the float path's ``_conv2d``).
    """
    from repro.kernels import fused_conv_ops

    if qct is None:
        qct = pack_conv_weights(params, pc)
    if qct.bits != pc.bits:
        raise ValueError(f"packed weights are {qct.bits}-bit, "
                         f"precision asks for {pc.bits}-bit")
    if threshold_q is None:
        threshold_q = _fold_threshold_q(qct.scale, lif)
    packed_in = packing.pack_bool(spikes_t.astype(jnp.int32))
    _, packed_out = fused_conv_ops.fused_conv_rollout(
        packed_in, qct, stride=stride, padding="SAME",
        leak_shift=lif.leak_shift, threshold_q=threshold_q,
        soft_reset=lif.soft_reset, layer=layer,
    )
    return packing.unpack_bool(packed_out, qct.c_out)


def spiking_conv_group_int_apply(
    members,
    spikes_t: jnp.ndarray,      # (T, B, H, W, C) — {0,1} binary spikes
    lif: LIFConfig,
    pc: PrecisionConfig,
    layers=(),
):
    """Fusion-group twin of :func:`spiking_conv_int_apply`: a chain of
    2+ stride-1 conv layers (with optional interleaved max pools) runs
    its whole T-step rollout in ONE fused kernel call
    (kernels/fused_group), so the 1-bit inter-member spike planes stay
    in VMEM instead of round-tripping HBM between layers.

    ``members`` is the executor-shaped chain: ``("conv", operands)``
    entries carry the same operands dict the single-layer twin takes
    (float ``params`` to quantize per call, or pre-packed ``qct`` +
    ``threshold_q`` from a deploy package), ``("pool", window)`` entries
    the pool window.  Thresholds fold exactly as the single-layer twin;
    the chain is bit-exact with composing :func:`spiking_conv_int_apply`
    and :func:`maxpool_t` member by member.  ``layers`` names the conv
    members in the kernels' lowering record.

    Returns (T, B, HoF, WoF, c_outF) {0,1} int32 spikes.
    """
    from repro.kernels import fused_group_ops

    chain = []
    last_c_out = None
    for m in members:
        if m[0] == "conv":
            _, operands = m
            qct = operands.get("qct")
            if qct is None:
                qct = pack_conv_weights(operands["params"], pc)
            if qct.bits != pc.bits:
                raise ValueError(f"packed weights are {qct.bits}-bit, "
                                 f"precision asks for {pc.bits}-bit")
            theta = operands.get("threshold_q")
            if theta is None:
                theta = _fold_threshold_q(qct.scale, lif)
            chain.append(("conv", qct, theta))
            last_c_out = qct.c_out
        else:
            chain.append(("pool", m[1]))
    packed_in = packing.pack_bool(spikes_t.astype(jnp.int32))
    _, packed_out = fused_group_ops.fused_group_rollout(
        packed_in, tuple(chain),
        leak_shift=lif.leak_shift, soft_reset=lif.soft_reset, layers=layers,
    )
    return packing.unpack_bool(packed_out, last_c_out)


def avgpool_t(spikes_t: jnp.ndarray, window: int = 2) -> jnp.ndarray:
    """Average pooling applied per timestep (keeps spike statistics)."""

    def pool(x):
        return jax.lax.reduce_window(
            x,
            0.0,
            jax.lax.add,
            (1, window, window, 1),
            (1, window, window, 1),
            "VALID",
        ) / (window * window)

    return jax.vmap(pool)(spikes_t.astype(jnp.float32))


def maxpool_t(spikes_t: jnp.ndarray, window: int = 2) -> jnp.ndarray:
    """Max pooling per timestep — the binary-preserving pool the integer
    deployment path uses (an OR over the window for {0,1} spikes, so the
    pooled plane stays 1-bit packable; training keeps :func:`avgpool_t`)."""

    def pool(x):
        return jax.lax.reduce_window(
            x,
            jnp.array(0, x.dtype),
            jax.lax.max,
            (1, window, window, 1),
            (1, window, window, 1),
            "VALID",
        )

    return jax.vmap(pool)(spikes_t)


def readout_apply(params, spikes_t: jnp.ndarray) -> jnp.ndarray:
    """Non-spiking readout: accumulate currents over T, no threshold.

    Returns (B, n_classes) logits = mean_t (spikes_t @ W).
    """
    w = params["w"]
    i_syn_t = jnp.einsum("tbi,io->tbo", spikes_t.astype(w.dtype), w)
    return jnp.mean(i_syn_t, axis=0)
