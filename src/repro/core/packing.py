"""Sub-word SIMD packing — the storage format behind L-SPINE's datapath.

L-SPINE's FPGA datapath packs 16x INT2 / 4x INT4 / 1x INT8 operands into a
single word and reconfigures its adder tree per precision.  On TPU we keep
the *storage* half of that idea: weights (and spike trains) live in HBM as
densely packed int32 words, and the unpack happens on-chip (VPU shifts and
masks inside a Pallas kernel, or the jnp reference path below).

Conventions
-----------
* Values are packed along the LAST axis ("contraction-major"): a single
  int32 word load yields ``32 // bits`` consecutive elements of the
  contraction dimension, so the unpacked tile is MXU-contiguous.
* Signed packing: values are stored as unsigned fields
  (``val + 2**(bits-1)``) and re-centred on unpack.  This keeps the
  pack/unpack pure shift+mask — no sign-extension ladders — mirroring the
  paper's adder-friendly encoding.
* ``bits=1`` packing is used for spike trains (binary events).
* ``axis=-2`` packs the same fields along the second-to-last axis
  instead: word row ``r`` holds values ``r*vpw .. r*vpw+vpw-1`` of that
  axis.  The fused TPU kernels keep their packed axis on the sublane
  (row) axis in this form, because a TPU vector tile can split and merge
  rows in registers but cannot split one lane into many.  It is the same
  field layout, so ``pack(x, b, axis=-2)`` equals
  ``pack(x.swapaxes(-1, -2), b).swapaxes(-1, -2)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SUPPORTED_BITS = (1, 2, 4, 8)
WORD_BITS = 32


def values_per_word(bits: int) -> int:
    if bits not in SUPPORTED_BITS:
        raise ValueError(f"bits must be one of {SUPPORTED_BITS}, got {bits}")
    return WORD_BITS // bits


def packed_last_dim(n: int, bits: int) -> int:
    """Number of int32 words needed to hold ``n`` values of width ``bits``."""
    vpw = values_per_word(bits)
    return (n + vpw - 1) // vpw


def _packed_axis(ndim: int, axis: int) -> int:
    if axis not in (-1, -2) or ndim < -axis:
        raise ValueError(f"axis must be -1 or -2 of a {ndim}-d array, "
                         f"got {axis}")
    return ndim + axis


def _field_offsets(shape, ax: int, bits: int) -> jnp.ndarray:
    """Bit offset of each field, lowest field first, along axis ``ax`` of
    ``shape`` (an iota, so it also traces inside a TPU kernel)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, ax) * bits


def pack(values: jnp.ndarray, bits: int, axis: int = -1) -> jnp.ndarray:
    """Pack signed integers of width ``bits`` along ``axis`` (-1 or -2).

    values: integer array, each element in [-2^(bits-1), 2^(bits-1) - 1]
            (or {0,1} for bits=1).
    Returns int32 array whose ``axis`` has packed_last_dim(n, bits) words.
    """
    vpw = values_per_word(bits)
    ax = _packed_axis(values.ndim, axis)
    n = values.shape[ax]
    pad = (-n) % vpw
    v = values.astype(jnp.int32)
    if bits > 1:
        v = v + (1 << (bits - 1))  # bias to unsigned field
    if pad:
        widths = [(0, 0)] * v.ndim
        widths[ax] = (0, pad)
        v = jnp.pad(v, widths)
    v = v.reshape(*v.shape[:ax], (n + pad) // vpw, vpw, *v.shape[ax + 1:])
    offs = _field_offsets(v.shape, ax + 1, bits)
    # Fields are disjoint, so summing the shifted fields == bitwise-or.
    words = jnp.sum((v & ((1 << bits) - 1)) << offs, axis=ax + 1)
    return words.astype(jnp.int32)


def unpack(words: jnp.ndarray, bits: int, n: int,
           axis: int = -1) -> jnp.ndarray:
    """Inverse of :func:`pack`; returns int32 values, ``n`` along axis."""
    vpw = values_per_word(bits)
    ax = _packed_axis(words.ndim, axis)
    w = jnp.expand_dims(words, ax + 1)
    shape = (*words.shape[:ax + 1], vpw, *words.shape[ax + 1:])
    fields = (w >> _field_offsets(shape, ax + 1, bits)) & ((1 << bits) - 1)
    flat = fields.reshape(*words.shape[:ax], words.shape[ax] * vpw,
                          *words.shape[ax + 1:])
    flat = jax.lax.slice_in_dim(flat, 0, n, axis=ax).astype(jnp.int32)
    if bits > 1:
        flat = flat - (1 << (bits - 1))
    return flat


def pack_bool(values: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Pack a boolean/{0,1} array along ``axis``, 32 per int32 word."""
    return pack(values.astype(jnp.int32), bits=1, axis=axis)


def unpack_bool(words: jnp.ndarray, n: int, axis: int = -1) -> jnp.ndarray:
    return unpack(words, bits=1, n=n, axis=axis)


# ---------------------------------------------------------------------------
# numpy twins (used by the data pipeline and checkpoint tooling off-device)
# ---------------------------------------------------------------------------

def pack_np(values: np.ndarray, bits: int) -> np.ndarray:
    vpw = values_per_word(bits)
    n = values.shape[-1]
    pad = (-n) % vpw
    v = values.astype(np.int64)
    if bits > 1:
        v = v + (1 << (bits - 1))
    if pad:
        v = np.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
    v = v.reshape(*v.shape[:-1], -1, vpw)
    offs = (np.arange(vpw) * bits).astype(np.int64)
    words = np.sum((v & ((1 << bits) - 1)) << offs, axis=-1)
    # int32 wrap for the top field is intentional (bit-identical to device).
    return words.astype(np.uint32).astype(np.int32)


def unpack_np(words: np.ndarray, bits: int, n: int) -> np.ndarray:
    vpw = values_per_word(bits)
    offs = (np.arange(vpw) * bits).astype(np.int64)
    fields = (words.astype(np.uint32)[..., None] >> offs) & ((1 << bits) - 1)
    flat = fields.reshape(*words.shape[:-1], -1)[..., :n].astype(np.int64)
    if bits > 1:
        flat = flat - (1 << (bits - 1))
    return flat.astype(np.int32)
