"""Property tests for the sub-word SIMD packing layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, strategies as st

from repro.core import packing


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
@pytest.mark.parametrize("n", [1, 31, 32, 33, 100, 256])
def test_roundtrip_shapes(bits, n):
    lo = 0 if bits == 1 else -(1 << (bits - 1))
    hi = 2 if bits == 1 else (1 << (bits - 1))
    v = jax.random.randint(jax.random.PRNGKey(n * bits), (3, n), lo, hi,
                           jnp.int32)
    w = packing.pack(v, bits)
    assert w.shape == (3, packing.packed_last_dim(n, bits))
    assert w.dtype == jnp.int32
    u = packing.unpack(w, bits, n)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@settings(max_examples=50, deadline=None)
@given(
    bits=st.sampled_from([1, 2, 4, 8]),
    n=st.integers(1, 200),
    seed=st.integers(0, 2**31 - 1),
)
def test_roundtrip_property(bits, n, seed):
    g = np.random.default_rng(seed)
    lo = 0 if bits == 1 else -(1 << (bits - 1))
    hi = 1 if bits == 1 else (1 << (bits - 1)) - 1
    v = g.integers(lo, hi, size=(2, n), endpoint=True).astype(np.int32)
    u = packing.unpack_np(packing.pack_np(v, bits), bits, n)
    np.testing.assert_array_equal(u, v)


@settings(max_examples=25, deadline=None)
@given(bits=st.sampled_from([1, 2, 4, 8]), n=st.integers(1, 128),
       seed=st.integers(0, 2**31 - 1))
def test_numpy_and_jax_packing_bit_identical(bits, n, seed):
    g = np.random.default_rng(seed)
    lo = 0 if bits == 1 else -(1 << (bits - 1))
    hi = 1 if bits == 1 else (1 << (bits - 1)) - 1
    v = g.integers(lo, hi, size=(n,), endpoint=True).astype(np.int32)
    w_np = packing.pack_np(v, bits)
    w_jx = np.asarray(packing.pack(jnp.asarray(v), bits))
    np.testing.assert_array_equal(w_np, w_jx)


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
@pytest.mark.parametrize("n", [1, 33, 64])
def test_row_axis_is_the_transposed_lane_layout(bits, n):
    """axis=-2 (the fused kernels' on-chip form) packs the same fields as
    the last-axis layout stored in HBM, only transposed."""
    lo = 0 if bits == 1 else -(1 << (bits - 1))
    hi = 2 if bits == 1 else (1 << (bits - 1))
    v = jax.random.randint(jax.random.PRNGKey(n + bits), (2, n, 5), lo, hi,
                           jnp.int32)
    w = packing.pack(v, bits, axis=-2)
    assert w.shape == (2, packing.packed_last_dim(n, bits), 5)
    np.testing.assert_array_equal(
        np.asarray(w),
        np.asarray(packing.pack(v.swapaxes(-1, -2), bits).swapaxes(-1, -2)))
    np.testing.assert_array_equal(
        np.asarray(packing.unpack(w, bits, n, axis=-2)), np.asarray(v))
    with pytest.raises(ValueError, match="axis"):
        packing.pack(v, bits, axis=0)


def test_compression_density():
    # 16x INT2 per int32 word — the SIMD payload the paper packs
    for bits, vpw in ((2, 16), (4, 8), (8, 4), (1, 32)):
        assert packing.values_per_word(bits) == vpw
