"""The benchmark's own op and byte counts against the program's graph."""

import pytest

import _paths  # noqa: F401
from chipbench import ops, peaks, spec
from smoke_cells import smoke_conf

CONFIGS = ["vgg16_w4_fused", "resnet18_w4"]


def _conf(name):
    return spec.load_json(spec.HERE / "configs" / f"{name}.json")


@pytest.mark.parametrize("name", CONFIGS)
def test_macs_equal_program_count(name):
    from repro.deploy import deploy_config
    from repro.graph import build_graph

    conf = _conf(name)
    fusion = () if conf["fusion"] == "off" else conf["fusion"]
    cfg = deploy_config(conf["model"], bits=conf["bits"], smoke=False,
                        fusion=fusion)
    assert ops.macs_per_image(conf) == build_graph(cfg).count_macs()
    assert ops.ops_per_image(conf) == 2 * build_graph(cfg).count_macs()


@pytest.mark.parametrize("name", CONFIGS)
def test_smoke_geometry_matches_program(name):
    from chipbench.harness import program_config

    program_config(smoke_conf(name))       # raises on any difference


@pytest.mark.parametrize("name,macs,packed_mb", [
    ("vgg16_w4_fused", 1_257_000_960, 7.9), ("resnet18_w4", 2_221_690_880,
                                             5.6)])
def test_sizes(name, macs, packed_mb):
    conf = _conf(name)
    assert ops.macs_per_image(conf) == macs
    packed = sum(l.weight_bytes(conf["bits"]) for l in ops.layers(conf)
                 if l.packed)
    assert abs(packed / 1e6 - packed_mb) < 0.1


def test_least_time_compute_bound_at_bucket_64():
    conf = _conf("vgg16_w4_fused")
    least = ops.packed_least_s(conf, 64, peaks.V5E)
    packed_ops = sum(2 * conf["timesteps"] * l.macs * 64
                     for l in ops.layers(conf) if l.packed)
    assert least["memory_bound"] == 0
    assert least["seconds"] == pytest.approx(packed_ops / 393e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite") is peaks.V5E
