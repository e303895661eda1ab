"""Smoke-geometry stand-ins for the benchmark's configurations: the
program's CPU-test geometry (``deploy_config(smoke=True)``: 16x16
images, T=3, channels scaled by 0.15, 4 classes), for CPU tests."""

import json

import _paths  # noqa: F401
from chipbench import spec


def _ch(c):
    return max(8, int(c * 0.15))


def smoke_conf(name: str, bits: int = None) -> dict:
    conf = json.loads((spec.HERE / "configs" / f"{name}.json").read_text())
    # the CPU's default precision for float32 matmuls is float32
    conf.update(smoke=True, img_size=16, timesteps=3, n_classes=4,
                float_matmul="f32")
    if conf["family"] == "vgg":
        # at 16x16 the fourth pool is not applied either
        plan, hw = [], 16
        for c in conf["plan"]:
            if c == "P":
                if hw <= 2:
                    continue
                hw //= 2
            plan.append(c if c == "P" else _ch(c))
        conf["plan"] = plan
        conf["fc_hidden"] = _ch(conf["fc_hidden"])
    else:
        conf["stem_channels"] = _ch(conf["stem_channels"])
        conf["stages"] = [[_ch(c), n, s] for c, n, s in conf["stages"]]
    if bits is not None:
        conf["bits"] = bits
    return conf


def smoke_cell(name: str, traffic: dict, bits: int = None) -> spec.Cell:
    return spec.Cell(name=f"{name}.smoke", chips=1,
                     conf=smoke_conf(name, bits), traffic=traffic,
                     end_to_end=[], per_layer=[])


CLOSED = {"loop": "closed", "clients": 8, "max_batch": 4, "buckets": [4],
          "data_parallel": False, "warm_s": 0.3}
OPEN = {"loop": "open", "rate_per_s": 40.0, "max_batch": 4,
        "buckets": [1, 2, 4], "data_parallel": False, "warm_s": 0.3}
