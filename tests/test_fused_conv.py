"""Bit-exactness matrix for the fused packed-conv rollout kernel.

The fused kernel (interpret mode) must reproduce, bit for bit, the
unfused composition it replaces — `lif_rollout_int` over integer
XLA-convolution currents, with outputs packed by `pack_bool` along the
channel axis — across precisions, reset modes, strides, and
spatial/channel shapes that exercise the padding edges.  Also covers the
`spiking_conv_int_apply` layer wrapper, the shared float-path edge
cases, and the snn_cnn integer deployment forward.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, strategies as st

from repro.core import packing
from repro.core.lif import LIFConfig, lif_rollout_int
from repro.kernels import fused_conv_ops, use_backend
from repro.kernels.fused_conv import ref as conv_ref
from repro.quant import PrecisionConfig, quantize_conv, unpack_conv_codes


def _unfused_oracle(spp, qct, *, stride, padding, leak_shift, threshold_q,
                    v_reset_q, soft_reset):
    """lif_rollout_int over XLA integer convolutions — independent of the
    im2col composition in ref.py/kernel.py (string padding goes straight
    to lax.conv, cross-checking the explicit-pads geometry helpers)."""
    codes = unpack_conv_codes(qct)
    s_t = packing.unpack_bool(spp, qct.c_in).astype(jnp.int32)
    i_t = jax.vmap(lambda s: jax.lax.conv_general_dilated(
        s, codes, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC")))(s_t)
    v0 = jnp.zeros(i_t.shape[1:], jnp.int32)
    v, o_t = lif_rollout_int(
        v0, i_t, leak_shift=leak_shift, threshold_q=threshold_q,
        v_reset_q=v_reset_q, soft_reset=soft_reset)
    return v, packing.pack_bool(o_t)


def _rollout_case(bits, soft, t_steps, b, h, w, cin, cout, *, stride=1,
                  padding="SAME", ksize=3, threshold_q=8, leak_shift=3,
                  v_reset_q=0, rate=0.3, seed=0):
    key = jax.random.PRNGKey(seed + bits * 1000 + t_steps * 7 + cin + h)
    sp = (jax.random.uniform(key, (t_steps, b, h, w, cin)) < rate).astype(
        jnp.int32)
    spp = packing.pack_bool(sp)
    wf = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (ksize, ksize, cin, cout))
    qct = quantize_conv(wf, PrecisionConfig(bits=bits))

    v_o, s_o = _unfused_oracle(
        spp, qct, stride=stride, padding=padding, leak_shift=leak_shift,
        threshold_q=threshold_q, v_reset_q=v_reset_q, soft_reset=soft)
    with use_backend("interpret"):
        v_k, s_k = fused_conv_ops.fused_conv_rollout(
            spp, qct, stride=stride, padding=padding, leak_shift=leak_shift,
            threshold_q=threshold_q, v_reset_q=v_reset_q, soft_reset=soft)
    np.testing.assert_array_equal(np.asarray(v_o), np.asarray(v_k))
    np.testing.assert_array_equal(np.asarray(s_o), np.asarray(s_k))


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("soft", [True, False])
@pytest.mark.parametrize("stride", [1, 2])
def test_fused_conv_matrix(bits, soft, stride):
    _rollout_case(bits, soft, 3, b=2, h=8, w=8, cin=16, cout=24,
                  stride=stride)


@pytest.mark.parametrize("h,w,cin,cout,stride,ksize,padding", [
    (7, 9, 5, 7, 2, 3, "SAME"),     # odd spatial, sub-word channels
    (6, 6, 33, 16, 1, 3, "SAME"),   # cin just over one 32-bit spike word
    (5, 5, 8, 130, 2, 3, "SAME"),   # cout just over one 128-channel tile
    (8, 8, 16, 24, 1, 3, "VALID"),  # no padding at all
    (4, 4, 12, 20, 1, 1, "SAME"),   # 1x1 conv (projection-shortcut shape)
    (9, 7, 3, 40, 2, 1, "SAME"),    # strided 1x1 projection, odd plane
])
def test_fused_conv_shape_edges(h, w, cin, cout, stride, ksize, padding):
    _rollout_case(4, True, 3, b=2, h=h, w=w, cin=cin, cout=cout,
                  stride=stride, ksize=ksize, padding=padding)


def test_fused_conv_hard_reset_nonzero_v_reset():
    _rollout_case(8, False, 4, b=1, h=6, w=6, cin=8, cout=12, v_reset_q=-3)


def test_fused_conv_single_and_long_rollout():
    _rollout_case(2, True, 1, b=2, h=6, w=6, cin=8, cout=16)
    _rollout_case(2, True, 8, b=1, h=6, w=6, cin=8, cout=16)


def test_fused_conv_ref_matches_oracle_composition():
    """ref.py itself is the same composition (guards the jnp backend)."""
    sp = (jax.random.uniform(jax.random.PRNGKey(0), (4, 2, 7, 7, 9)) < 0.4)
    spp = packing.pack_bool(sp.astype(jnp.int32))
    qct = quantize_conv(
        jax.random.normal(jax.random.PRNGKey(1), (3, 3, 9, 14)),
        PrecisionConfig(bits=2))
    v_o, s_o = _unfused_oracle(
        spp, qct, stride=2, padding="SAME", leak_shift=2, threshold_q=16,
        v_reset_q=0, soft_reset=True)
    v_r, s_r = conv_ref.fused_conv_rollout_ref(
        spp, qct, stride=2, padding="SAME", leak_shift=2, threshold_q=16)
    np.testing.assert_array_equal(np.asarray(v_o), np.asarray(v_r))
    np.testing.assert_array_equal(np.asarray(s_o), np.asarray(s_r))


def test_conv_pads_match_lax_string_padding():
    """Explicit pads reproduce XLA's SAME geometry, stride 1 and 2,
    even and odd extents."""
    for h, w, k, s in [(8, 8, 3, 1), (7, 9, 3, 2), (5, 5, 1, 2),
                       (16, 16, 3, 2), (6, 10, 1, 1)]:
        x = jnp.ones((1, h, w, 2), jnp.int32)
        wgt = jnp.ones((k, k, 2, 3), jnp.int32)
        want = jax.lax.conv_general_dilated(
            x, wgt, (s, s), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        pads = conv_ref.conv_pads(h, w, k, k, s, "SAME")
        got = jax.lax.conv_general_dilated(
            x, wgt, (s, s), pads,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# ---------------------------------------------------------------------------
# layer wrapper
# ---------------------------------------------------------------------------

def test_spiking_conv_int_apply_matches_rollout():
    """The layer wrapper == manual quantize + fused rollout, eagerly."""
    from repro.core.snn_layers import conv_init, spiking_conv_int_apply

    lif = LIFConfig(leak_shift=3, soft_reset=True)
    pc = PrecisionConfig(bits=4)
    params = conv_init(jax.random.PRNGKey(0), 8, 24)
    sp = (jax.random.uniform(jax.random.PRNGKey(1), (3, 2, 8, 8, 8)) < 0.3
          ).astype(jnp.int32)

    out = spiking_conv_int_apply(params, sp, lif, pc, threshold_q=16)
    assert out.shape == (3, 2, 8, 8, 24)
    qct = quantize_conv(params["w"] * params["g"], pc)
    _, packed = fused_conv_ops.fused_conv_rollout(
        packing.pack_bool(sp), qct, leak_shift=3, threshold_q=16)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(packing.unpack_bool(packed, 24)))


def test_spiking_conv_int_apply_jit_contract():
    """Explicit threshold_q works under jit, and the per-channel auto-fold
    is traced-friendly: theta_q rides as an array operand on the fused
    kernel, so jit and eager agree bit for bit."""
    from repro.core.snn_layers import conv_init, spiking_conv_int_apply

    params = conv_init(jax.random.PRNGKey(2), 4, 8)
    sp = (jax.random.uniform(jax.random.PRNGKey(3), (2, 1, 6, 6, 4)) < 0.3
          ).astype(jnp.int32)
    lif, pc = LIFConfig(), PrecisionConfig(bits=4)

    out = jax.jit(lambda p, s: spiking_conv_int_apply(
        p, s, lif, pc, threshold_q=16))(params, sp)
    assert out.shape == (2, 1, 6, 6, 8)
    # the auto-fold works under jit (per-channel theta is an operand, not
    # a static scalar) and matches the eager fold exactly
    out_jit = jax.jit(lambda p, s: spiking_conv_int_apply(
        p, s, lif, pc))(params, sp)
    out_eager = spiking_conv_int_apply(params, sp, lif, pc)
    np.testing.assert_array_equal(np.asarray(out_jit), np.asarray(out_eager))


def test_int_conv_rate_tracks_float_path():
    """On the same binary input, the integer layer's firing rate stays
    within quantization tolerance of the fake-quant float twin's."""
    from repro.core.snn_layers import conv_init, spiking_conv_apply, \
        spiking_conv_int_apply

    lif = LIFConfig(leak_shift=3, threshold=0.5)
    pc = PrecisionConfig(bits=8)
    params = conv_init(jax.random.PRNGKey(4), 16, 32)
    sp = (jax.random.uniform(jax.random.PRNGKey(5), (4, 2, 12, 12, 16))
          < 0.3).astype(jnp.float32)
    r_f = float(jnp.mean(spiking_conv_apply(params, sp, lif, pc)))
    r_i = float(jnp.mean(spiking_conv_int_apply(
        params, sp.astype(jnp.int32), lif, pc).astype(jnp.float32)))
    assert 0.0 < r_f < 0.9 and 0.0 < r_i < 0.9
    assert abs(r_f - r_i) < 0.1, (r_f, r_i)


# ---------------------------------------------------------------------------
# float-path edge cases shared with the fused path (geometry contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,w,cin,cout,stride", [
    (7, 7, 3, 8, 2),    # odd spatial, stride 2
    (9, 5, 33, 8, 1),   # channels not divisible by the 32-bit pack width
    (8, 8, 16, 24, 2),  # even plane, stride 2
])
def test_float_and_int_conv_agree_on_geometry(h, w, cin, cout, stride):
    """spiking_conv_apply and spiking_conv_int_apply produce the same
    output geometry for every stride/shape the models use."""
    from repro.core.snn_layers import conv_init, spiking_conv_apply, \
        spiking_conv_int_apply

    lif = LIFConfig(leak_shift=3, threshold=0.5)
    params = conv_init(jax.random.PRNGKey(6), cin, cout)
    sp = (jax.random.uniform(jax.random.PRNGKey(7), (2, 1, h, w, cin))
          < 0.4).astype(jnp.float32)
    out_f = spiking_conv_apply(params, sp, lif, stride=stride)
    out_i = spiking_conv_int_apply(params, sp.astype(jnp.int32), lif,
                                   PrecisionConfig(bits=4), stride=stride)
    assert out_f.shape == out_i.shape
    assert np.isfinite(np.asarray(out_f)).all()
    assert set(np.unique(np.asarray(out_i))) <= {0, 1}


@pytest.mark.parametrize("soft", [True, False])
def test_float_conv_reset_modes(soft):
    """Both LIF reset modes run and spike on the float conv path."""
    from repro.core.snn_layers import conv_init, spiking_conv_apply

    lif = LIFConfig(leak_shift=3, threshold=0.3, soft_reset=soft)
    params = conv_init(jax.random.PRNGKey(8), 8, 16)
    sp = (jax.random.uniform(jax.random.PRNGKey(9), (4, 2, 7, 7, 8))
          < 0.5).astype(jnp.float32)
    out = spiking_conv_apply(params, sp, lif)
    assert out.shape == (4, 2, 7, 7, 16)
    assert 0.0 < float(jnp.mean(out)) < 1.0


# ---------------------------------------------------------------------------
# snn_cnn integer deployment
# ---------------------------------------------------------------------------

def _deploy_cfgs(model, bits=8):
    from repro.models.snn_cnn import SNNConfig

    cfg = SNNConfig(model=model, img_size=16, timesteps=3, scale=0.15,
                    n_classes=4, lif=LIFConfig(leak_shift=3, threshold=0.5),
                    precision=PrecisionConfig(bits=bits))
    return cfg, dataclasses.replace(cfg, int_deploy=True)


def test_snn_cnn_vgg_int_forward_matches_float_rates():
    """vgg integer forward: per-layer firing rates within quantization
    tolerance of the float path's (same params, same input)."""
    from repro.models import snn_cnn

    cfg_f, cfg_i = _deploy_cfgs("vgg9")
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg_f)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    params = snn_cnn.calibrate(params, cfg_f, x)
    logits_f, rates_f = snn_cnn.apply_with_rates(params, cfg_f, x)
    logits_i, rates_i = snn_cnn.apply_with_rates(params, cfg_i, x)
    assert logits_i.shape == logits_f.shape == (2, 4)
    assert np.isfinite(np.asarray(logits_i)).all()
    assert len(rates_f) == len(rates_i)
    for rf, ri in zip(rates_f, rates_i):
        assert 0.0 < ri < 0.95
        assert abs(rf - ri) < 0.12, (rates_f, rates_i)


def test_snn_cnn_resnet_int_forward():
    """resnet integer deployment exercises stride-2 blocks and 1x1
    projection shortcuts end to end.  The OR residual merge lifts rates
    above the float path's averaging merge, so the activity check is a
    band, not a per-layer delta."""
    from repro.models import snn_cnn

    cfg_f, cfg_i = _deploy_cfgs("resnet18")
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg_f)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    params = snn_cnn.calibrate(params, cfg_f, x)
    logits_f, rates_f = snn_cnn.apply_with_rates(params, cfg_f, x)
    logits_i, rates_i = snn_cnn.apply_with_rates(params, cfg_i, x)
    assert logits_i.shape == logits_f.shape == (2, 4)
    assert np.isfinite(np.asarray(logits_i)).all()
    for rf, ri in zip(rates_f, rates_i):
        assert 0.0 < ri < 0.95
        assert 0.3 < ri / rf < 3.0, (rates_f, rates_i)


def test_snn_cnn_int_deploy_needs_quantized_precision():
    """int_deploy with bits=16 silently stays on the float path (the
    int_path property gates on a quantized precision)."""
    from repro.models import snn_cnn

    cfg = snn_cnn.SNNConfig(model="vgg9", img_size=16, timesteps=2,
                            scale=0.15, n_classes=4, int_deploy=True,
                            precision=PrecisionConfig(bits=16))
    assert not cfg.int_path
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 16, 16, 3))
    logits = snn_cnn.apply(params, cfg, x)
    assert logits.shape == (1, 4)


# ---------------------------------------------------------------------------
# property sweep
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(
    bits=st.sampled_from([2, 4, 8]),
    t_steps=st.integers(1, 4),
    h=st.integers(3, 10),
    w=st.integers(3, 10),
    cin=st.integers(1, 40),
    cout=st.integers(1, 40),
    stride=st.sampled_from([1, 2]),
    theta=st.integers(1, 32),
    seed=st.integers(0, 2**31 - 1),
)
def test_fused_conv_roundtrip_property(bits, t_steps, h, w, cin, cout,
                                       stride, theta, seed):
    """pack -> fused conv rollout (interpret) -> unpack round trip:
    output spikes unpack to the oracle's exact train and the packed words
    carry no stray bits beyond c_out."""
    key = jax.random.PRNGKey(seed % (2**31 - 1))
    sp = (jax.random.uniform(key, (t_steps, 2, h, w, cin)) < 0.5).astype(
        jnp.int32)
    spp = packing.pack_bool(sp)
    qct = quantize_conv(
        jax.random.normal(jax.random.PRNGKey(seed % 97), (3, 3, cin, cout)),
        PrecisionConfig(bits=bits))
    v_o, s_o = _unfused_oracle(
        spp, qct, stride=stride, padding="SAME", leak_shift=3,
        threshold_q=theta, v_reset_q=0, soft_reset=True)
    with use_backend("interpret"):
        v_k, s_k = fused_conv_ops.fused_conv_rollout(
            spp, qct, stride=stride, leak_shift=3, threshold_q=theta)
    np.testing.assert_array_equal(np.asarray(s_o), np.asarray(s_k))
    np.testing.assert_array_equal(np.asarray(v_o), np.asarray(v_k))
    u_k = packing.unpack_bool(s_k, cout)
    np.testing.assert_array_equal(
        np.asarray(packing.pack_bool(u_k)), np.asarray(s_k))


@pytest.mark.parametrize("family", ["fused_conv", "fused_nce", "fused_group"])
def test_pallas_call_builds_with_current_compiler_params(family):
    """Each fused kernel's pallas_call entry builds and runs in interpret
    mode with the installed jax's TPU compiler params (a renamed params
    class broke every kernel on its first call, interpret included)."""
    from repro.kernels.fused_conv import kernel as ck
    from repro.kernels.fused_group import kernel as gk
    from repro.kernels.fused_nce import kernel as nk

    t, bits, n = 2, 4, 32
    w = jnp.zeros((9 * 32 * bits // 32, n), jnp.int32)     # (K*bits/32, n)
    th = jnp.full((n, 1), 8, jnp.int32)
    if family == "fused_nce":
        v, o = nk.fused_nce_rollout_pallas(
            jnp.zeros((t, 4, 3), jnp.int32), w[:16], th, bits=bits,
            n_out=n, leak_shift=3, interpret=True)
        assert v.shape == (n, 3) and o.shape == (t, 1, 3)
    elif family == "fused_conv":
        # one 4x4 output plane, SAME 3x3, stride 1: a 6x6 padded grid
        v, o = ck.fused_conv_rollout_pallas(
            jnp.zeros((t, 1, 1, 1, 7 * 6), jnp.int32), w, th, bits=bits,
            kh=3, kw=3, cin_pad=32, stride=1, ho=4, wo=4, wq=6, n_out=n,
            leak_shift=3, interpret=True)
        assert v.shape == (1, n, 4 * 6) and o.shape == (t, 1, 1, 4 * 6)
    else:
        geoms = (("conv", bits, 3, 32, 4, 4, n, n),
                 ("conv", bits, 3, n, 4, 4, n, n))
        v, o = gk.fused_group_rollout_pallas(
            jnp.zeros((t, 1, 1, 7 * 6), jnp.int32), w, th, w, th,
            geoms=geoms, leak_shift=3, interpret=True)
        assert v.shape == (1, n, 4 * 6) and o.shape == (t, 1, 1, 4 * 6)
    assert v.dtype == o.dtype == jnp.int32
