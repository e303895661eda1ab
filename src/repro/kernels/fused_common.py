"""In-kernel building blocks shared by the fused rollout kernels
(fused_nce, fused_conv, fused_group).

Layout.  Inside every fused kernel, channels (or neurons) run along the
sublane (row) axis and pixels (or batch rows) along the lane axis:

    spikes    (C, P)       unpacked from (C/32, P) words
    weights   (K/vpw, N)   packed words, transposed; tap rows unpack to (K_t, N)
    membrane  (N, P)       int32, VMEM-resident across the T-step scan
    out words (N/32, P)    re-packed with packing.pack_bool(axis=-2)

Every packed axis is therefore unpacked along rows
(``packing.unpack(..., axis=-2)``).  Mosaic, the TPU kernel compiler,
splits and merges rows freely but rejects reshapes that split one lane
into many (``infer-vector-layout: unsupported shape cast``), and a
lane-axis unpack pads each 4-16-wide field axis to 128 lanes.  The field
layout is the one ``core/packing.py`` defines; only the axis differs, so
the ops layers transpose the HBM arrays and the ``ref.py`` oracles stay
as they are.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import packing


def tap_offsets(kh: int, kw: int, stride: int, wq: int) -> Tuple:
    """(phase index, lane offset) per tap, in (kh, kw) weight order, for
    the phase planes :func:`phase_planes` lays out with grid width wq."""
    return tuple(((di % stride) * stride + dj % stride,
                  (di // stride) * wq + dj // stride)
                 for di in range(kh) for dj in range(kw))


def phase_planes(sp: jnp.ndarray, pads, kh: int, kw: int, stride: int,
                 ho: int, wo: int) -> Tuple[jnp.ndarray, int]:
    """Kernel-layout input planes for a (kh, kw, stride) conv.

    ``sp``: (T, B, H, W, words) pack_bool planes.  Returns
    ((T, B, stride**2, words, hq*wq) int32, wq): the zero-padded plane
    split into phases (phase a*stride + c holds padded pixels
    (stride*i + a, stride*j + c)), channel words on rows, pixels
    flattened row-major over width ``wq`` on lanes.  One extra row is
    kept when the widest tap runs past the last grid row.
    """
    t, b, h, w, wc = sp.shape
    s = stride
    wq = wo + (kw - 1) // s
    hq = ho + (kh - 1) // s + (1 if (kw - 1) // s else 0)
    (plh, _), (plw, _) = pads
    # pad (or crop unused trailing pixels) to the (s*hq, s*wq) canvas
    x = jax.lax.pad(sp, jnp.int32(0),
                    [(0, 0, 0), (0, 0, 0), (plh, s * hq - h - plh, 0),
                     (plw, s * wq - w - plw, 0), (0, 0, 0)])
    x = x.reshape(t, b, hq, s, wq, s, wc).transpose(0, 1, 3, 5, 6, 2, 4)
    return x.reshape(t, b, s * s, wc, hq * wq), wq


def grid_to_nhwc(x: jnp.ndarray, ho: int, wq: int, wo: int,
                 c: int) -> jnp.ndarray:
    """(..., N, ho*wq) kernel output -> (..., ho, wo, c): drop the junk
    grid columns and the padded channels."""
    x = x.reshape(*x.shape[:-1], ho, wq)[..., :wo]
    nd = x.ndim
    x = x.transpose(*range(nd - 3), nd - 2, nd - 1, nd - 3)
    return x[..., :c]


def unpack_spikes(words: jnp.ndarray, c: int) -> jnp.ndarray:
    """(c/32, P) 1-bit words -> (c, P) int8 {0,1} spikes."""
    return packing.unpack_bool(words, c, axis=-2).astype(jnp.int8)


def weight_codes(w_ref, row0: int, rows: int, bits: int) -> jnp.ndarray:
    """int8 codes (rows*vpw, N) of packed word rows [row0, row0+rows) of
    a transposed (K/vpw, N) weight ref.  Unpacked one contraction chunk
    at a time so the int32 unpack intermediates stay chunk-sized."""
    words = w_ref[pl.ds(row0, rows), :]
    vpw = packing.values_per_word(bits)
    return packing.unpack(words, bits, rows * vpw, axis=-2).astype(jnp.int8)


def codes_dot(codes: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """(K, N) int8 codes . (K, P) int8 spikes -> (N, P) int32 on the MXU
    (binary x int: every multiply is a masked pass-through)."""
    return jax.lax.dot_general(
        codes, x, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def conv_accumulate(planes: Sequence[jnp.ndarray], w_ref,
                    taps: Sequence[Tuple[int, int]], cin_pad: int,
                    bits: int, l_out: int) -> jnp.ndarray:
    """Convolution as one accumulated dot per tap.

    ``planes`` are (cin_pad, P) int8 spike planes flattened row-major
    over a grid of width ``wq``; ``taps`` lists (plane index, lane
    offset) per kernel tap in the (kh, kw) order quantize_conv flattens
    the weight taps in (:func:`tap_offsets`).  Output pixel ``q`` of the (Ho, wq) output grid
    reads lane ``q + offset`` of its tap's plane, so each tap is one
    contiguous lane slice; the ``wq - Wo`` trailing columns of every
    output row are junk the caller discards or masks.
    """
    rows = cin_pad // packing.values_per_word(bits)
    acc = None
    for ti, (plane, off) in enumerate(taps):
        d = codes_dot(weight_codes(w_ref, ti * rows, rows, bits),
                      planes[plane][:, off:off + l_out])
        acc = d if acc is None else acc + d
    return acc


def lif_fire(v: jnp.ndarray, i_syn: jnp.ndarray, theta: jnp.ndarray,
             valid: jnp.ndarray, *, leak_shift: int, v_reset_q: int,
             soft_reset: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shift-add LIF step on an (N, P) membrane tile: leak, integrate,
    fire where ``v >= theta`` (theta an (N, 1) per-channel column) and
    ``valid`` holds, reset.  ``valid`` masks padded channels (and junk
    pixels) so the re-packed words match pack_bool of the reference.
    Returns (v, spikes int32)."""
    v = v - (v >> leak_shift) + i_syn
    fire = (v >= theta) & valid
    spikes = fire.astype(jnp.int32)
    if soft_reset:
        v = v - spikes * theta
    else:
        v = jnp.where(fire, jnp.int32(v_reset_q), v)
    return v, spikes


def row_mask(shape, n_out: int) -> jnp.ndarray:
    """True on the first ``n_out`` rows (the real channels)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) < n_out
