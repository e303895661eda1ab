"""The fused conv rollout in output tiles (kernels/fused_conv ``tile``).

A layer whose whole output per batch element does not fit the VMEM
budget runs the kernel over blocks of c_out and, for 1x1 convs, blocks
of output pixels.  In interpret mode the tiled kernel must be bit-exact
with the unfused reference (fused_conv/ref.py) whichever tile a small
``REPRO_VMEM_BUDGET`` forces; a layer that fits keeps the untiled grid;
each lowering leaves its record in the process registry; and at paper
size every packed conv of resnet50 runs a Pallas kernel while vgg16's
and resnet18's keep their untiled kernels.
"""

import warnings
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import packing
from repro.kernels import fused_conv_ops, use_backend, vmem
from repro.kernels.fused_conv import ops as conv_ops
from repro.quant import PrecisionConfig, quantize_conv

T, B = 3, 2


@pytest.fixture
def registry(monkeypatch):
    """A fresh process registry, so each test reads its own records."""
    reg = obs.MetricsRegistry(enabled=False)
    monkeypatch.setattr(obs.registry, "_DEFAULT", reg)
    return reg


def _layer(h, c_in, c_out, k, bits, seed=0):
    key = jax.random.PRNGKey(seed + h + c_in + c_out + k + bits)
    sp = (jax.random.uniform(key, (T, B, h, h, c_in)) < 0.3).astype(jnp.int32)
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (k, k, c_in, c_out))
    qct = quantize_conv(w, PrecisionConfig(bits=bits))
    theta = jnp.arange(c_out, dtype=jnp.int32) % 7 + 3
    return packing.pack_bool(sp), qct, theta


def _geometry(h, c_in, c_out, k, stride, bits):
    """conv_rollout_vmem_bytes' geometry of the layer, as ops.py lays it
    out (fused_common.phase_planes)."""
    ho = -(-h // stride)
    reach = (k - 1) // stride
    wq = ho + reach
    hq = ho + reach + (1 if reach else 0)
    return dict(n_phase=stride * stride, pq=hq * wq,
                cin_pad=-(-c_in // 32) * 32, kh=k, kw=k, l_out=ho * wq,
                n=-(-c_out // 32) * 32, bits=bits)


# (h, c_in, c_out, k, stride, bits, soft_reset, the tile the budget forces)
CASES = {
    "channels": (8, 64, 128, 3, 1, 4, True, (80, 64)),
    "pixels": (16, 64, 32, 1, 1, 4, True, (128, 32)),
    "both": (16, 64, 128, 1, 1, 4, True, (128, 32)),
    "both_w2_hard_reset": (16, 96, 96, 1, 1, 2, False, (128, 32)),
    "both_w8": (16, 64, 128, 1, 1, 8, True, (128, 32)),
    "stride2_projection": (32, 64, 96, 1, 2, 4, True, (128, 32)),
    "stride2_projection_channels": (16, 64, 96, 1, 2, 4, True, (64, 32)),
}


@pytest.mark.parametrize("case", CASES)
def test_tiled_rollout_bit_exact_with_reference(case, registry, monkeypatch):
    h, c_in, c_out, k, stride, bits, soft, tile = CASES[case]
    sp, qct, theta = _layer(h, c_in, c_out, k, bits)
    kw = dict(stride=stride, leak_shift=3, threshold_q=theta,
              soft_reset=soft, v_reset_q=-2)
    with use_backend("jnp"):
        v_ref, o_ref = fused_conv_ops.fused_conv_rollout(sp, qct, **kw)
    geo = _geometry(h, c_in, c_out, k, stride, bits)
    budget = vmem.conv_rollout_vmem_bytes(**geo, tile=tile)
    assert budget < vmem.conv_rollout_vmem_bytes(**geo)
    monkeypatch.setenv("REPRO_VMEM_BUDGET", str(budget))
    with use_backend("interpret"), warnings.catch_warnings():
        warnings.simplefilter("error")          # no fallback
        v, o = fused_conv_ops.fused_conv_rollout(sp, qct, layer="x", **kw)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    assert 0 < float(jnp.mean(packing.unpack_bool(o_ref, c_out))) < 1
    assert registry.lowerings()[-1] == obs.Lowering("x", "fused_tiled", tile,
                                                    budget)


def _pallas_grids(fn, *args):
    """The grid of every pallas_call ``fn`` traces to, nested jits too."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["grid_mapping"].grid
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def test_layer_that_fits_keeps_the_untiled_grid(registry):
    sp, qct, theta = _layer(8, 64, 64, 3, 4)
    with use_backend("interpret"):
        grids = _pallas_grids(
            lambda s: fused_conv_ops.fused_conv_rollout(
                s, qct, leak_shift=3, threshold_q=theta, layer="x"), sp)
    assert grids == [(B, T)]
    (rec,) = registry.lowerings()
    assert (rec.path, rec.tile) == ("fused", None)
    assert rec.vmem_bytes == vmem.conv_rollout_vmem_bytes(
        **_geometry(8, 64, 64, 3, 1, 4))


def test_tiled_layer_runs_a_grid_of_blocks(registry, monkeypatch):
    h, c_in, c_out, k, stride, bits, _, tile = CASES["both"]
    sp, qct, theta = _layer(h, c_in, c_out, k, bits)
    geo = _geometry(h, c_in, c_out, k, stride, bits)
    monkeypatch.setenv("REPRO_VMEM_BUDGET", str(
        vmem.conv_rollout_vmem_bytes(**geo, tile=tile)))
    with use_backend("interpret"):
        grids = _pallas_grids(
            lambda s: fused_conv_ops.fused_conv_rollout(
                s, qct, leak_shift=3, threshold_q=theta), sp)
    # (B, channel tiles, pixel tiles, T): 128 channels in 32s, 256 pixels
    assert grids == [(B, 4, 2, T)]


def test_tile_choice_and_its_limits():
    geo = _geometry(56, 256, 64, 1, 1, 4)
    whole = vmem.conv_rollout_vmem_bytes(**geo)
    assert conv_ops.conv_tile(**geo, budget=whole) == (None, whole)
    tile, need = conv_ops.conv_tile(**geo, budget=whole - 1)
    pixels, rows = tile
    assert need == vmem.conv_rollout_vmem_bytes(**geo, tile=tile) < whole
    assert pixels % 128 == 0 and pixels < geo["l_out"] and rows == 64
    # a 3x3 conv tiles channels only: a pixel block would need a halo
    geo3 = _geometry(8, 64, 32, 3, 1, 4)
    with pytest.raises(ValueError, match="tiles channels only"):
        vmem.conv_rollout_vmem_bytes(**geo3, tile=(128, 32))
    # nothing fits: the whole layer's need comes back, over the budget
    assert conv_ops.conv_tile(**geo3, budget=4096) == (
        None, vmem.conv_rollout_vmem_bytes(**geo3))


def test_no_tile_fits_falls_back_and_records_it(registry, monkeypatch):
    sp, qct, theta = _layer(8, 32, 32, 3, 4)
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "4096")
    with use_backend("interpret"):
        with pytest.warns(RuntimeWarning, match="no output tile"):
            fused_conv_ops.fused_conv_rollout(
                sp, qct, leak_shift=3, threshold_q=theta, layer="x")
    assert registry.lowerings() == [obs.Lowering("x", "unfused", None, 0)]


def _paper_size_lowerings(model, fusion=()):
    """Trace the served forward at paper size without running it: every
    packed layer is lowered, so its record is written."""
    from repro.deploy import deploy, deploy_config
    from repro.models import snn_cnn

    cfg = deploy_config(model, 4, smoke=False, fusion=fusion)
    pkg = jax.eval_shape(lambda k: deploy(snn_cnn.init(k, cfg), cfg),
                         jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, cfg.img_size, cfg.img_size,
                              cfg.in_channels), jnp.float32)
    with use_backend("interpret"), warnings.catch_warnings():
        warnings.simplefilter("error")          # no fallback anywhere
        jax.eval_shape(lambda p, x: p.apply(x), pkg, x)
    return obs.default_registry().lowerings()


def test_resnet50_every_packed_conv_runs_a_pallas_kernel(registry):
    recs = _paper_size_lowerings("resnet50")
    assert len(recs) == len({r.layer for r in recs}) == 52
    assert Counter(r.path for r in recs) == {"fused": 25, "fused_tiled": 27}
    budget = vmem.vmem_budget_bytes()
    assert all(0 < r.vmem_bytes <= budget for r in recs)
    assert all((r.tile is None) == (r.path == "fused") for r in recs)


@pytest.mark.parametrize("model,fusion,paths", [
    ("resnet18", (), {"fused": 19}),
    ("vgg16", "auto", {"fused": 1, "fused_group": 11})])
def test_benchmarked_models_keep_their_untiled_kernels(model, fusion, paths,
                                                       registry):
    recs = _paper_size_lowerings(model, fusion)
    assert Counter(r.path for r in recs) == paths
    assert all(r.tile is None for r in recs)


def test_jnp_backend_records_the_reference_path(registry):
    sp, qct, theta = _layer(8, 32, 32, 3, 4)
    with use_backend("jnp"):
        fused_conv_ops.fused_conv_rollout(sp, qct, leak_shift=3,
                                          threshold_q=theta, layer="x")
    assert registry.lowerings() == [obs.Lowering("x", "unfused", None, 0)]


def test_lowering_record_keeps_one_entry_per_layer():
    reg = obs.MetricsRegistry(enabled=False)
    reg.lowered("a", "fused", None, 10)
    reg.lowered("b", "fused_tiled", (128, 32), 20)
    reg.lowered("a", "fused", None, 11)             # relowered: moves last
    reg.lowered("c", "unfused")
    assert [r.layer for r in reg.lowerings()] == ["b", "a", "c"]
    with pytest.raises(ValueError, match="unknown lowering path"):
        reg.lowered("d", "tiled")
    reg.reset()
    assert reg.lowerings() == []


@pytest.fixture(scope="module")
def v5e():
    """A v5e chip described, not attached, with the persistent compile
    cache kept out (a compile for it cannot be read back here)."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("c_in,c_out,hw,stride,tile", [
    (64, 256, 56, 1, (1152, 256)),      # 1x1 expand at 56x56: pixel blocks
    (1024, 2048, 14, 2, (49, 512)),     # stride-2 projection: c_out blocks
])
def test_tiled_kernel_compiles_for_v5e(v5e, registry, c_in, c_out, hw,
                                       stride, tile):
    """Mosaic accepts the tiled blocks of ResNet-50's widest 1x1 convs at
    paper size (the interpret-mode tests cannot see its layout rules)."""
    from repro.quant.formats import QuantizedConvTensor

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    qct = QuantizedConvTensor(sds((c_out, c_in * 4 // 32)),
                              sds((c_out, 1), jnp.float32),
                              (1, 1, c_in, c_out), 4, c_in)
    with use_backend("pallas"):
        text = jax.jit(lambda sp, q, th: fused_conv_ops.fused_conv_rollout(
            sp, q, stride=stride, leak_shift=3, threshold_q=th, layer="x")
        ).lower(sds((T, B, hw, hw, c_in // 32)), qct,
                sds((c_out,))).compile().as_text()
    assert "tpu_custom_call" in text
    (rec,) = registry.lowerings()
    assert (rec.path, rec.tile) == ("fused_tiled", tile)
