"""Compile the served path's fused kernels for a TPU v5e that is described,
not attached: Mosaic (the TPU kernel compiler) must accept every kernel
at paper widths.  Interpret-mode tests cannot see its layout and tiling
rules; this file guards them on a CPU-only host at no chip time.

Nothing touches the TPU library until a test of this file runs: the
topology is described in a module fixture, which skips where it cannot
be described, and every sharding and shape is built from fixtures.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import fused_conv_ops, fused_group_ops, fused_nce_ops
from repro.kernels import use_backend
from repro.quant.formats import QuantizedConvTensor, QuantizedTensor

T, B = 4, 8


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _qct(sds, c_in, c_out, bits, k=3):
    cp = -(-c_in // 32) * 32
    return QuantizedConvTensor(sds((c_out, k * k * cp * bits // 32)),
                               sds((c_out, 1), jnp.float32),
                               (k, k, c_in, c_out), bits, cp)


def _compiled_text(fn, *args) -> str:
    with use_backend("pallas"):
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("c_in,c_out,hw,stride,bits", [
    (128, 256, 16, 1, 4),     # c_out > 128: the packed-output block rule
    (64, 128, 32, 2, 8),      # resnet stage entry: phase planes
    (512, 512, 4, 1, 8),      # largest weight block of vgg16/resnet18
])
def test_fused_conv_compiles_for_v5e(sds, c_in, c_out, hw, stride, bits):
    text = _compiled_text(
        lambda sp, q, th: fused_conv_ops.fused_conv_rollout(
            sp, q, stride=stride, leak_shift=3, threshold_q=th),
        sds((T, B, hw, hw, c_in // 32)), _qct(sds, c_in, c_out, bits),
        sds((c_out,)))
    assert "tpu_custom_call" in text


def test_fused_nce_compiles_for_v5e(sds):
    qt = QuantizedTensor(sds((512, 512 * 2 // 32)),
                         sds((512, 1), jnp.float32), None, (512, 512), 2, -1)
    text = _compiled_text(
        lambda sp, q, th: fused_nce_ops.fused_nce_rollout(
            sp, q, d_in=512, leak_shift=3, threshold_q=th),
        sds((T, B, 512 // 32)), qt, sds((512,)))
    assert "tpu_custom_call" in text


def test_fused_group_compiles_for_v5e(sds):
    """vgg16's first fused chain at 32x32: conv, pool, conv, conv."""
    qcts = [_qct(sds, ci, co, 4) for ci, co in ((64, 64), (64, 128),
                                                (128, 128))]
    ths = [sds((q.c_out,)) for q in qcts]

    def chain(sp, qcts, ths):
        a, b, c = (("conv", q, th) for q, th in zip(qcts, ths))
        return fused_group_ops.fused_group_rollout(
            sp, (a, ("pool", 2), b, c), leak_shift=3)

    text = _compiled_text(chain, sds((T, B, 32, 32, 2)), qcts, ths)
    assert text.count("tpu_custom_call") >= 1
