"""VGG family: the ``plan`` of conv widths and pools ("P"), then the
spiking fc1 of ``fc_hidden`` and the float readout.  The first conv is
the float stem; every later conv and fc1 are packed."""

from typing import List

from chipbench.ops import Layer


def layers(conf: dict) -> List[Layer]:
    out: List[Layer] = []
    hw, c_in, ci, pi = conf["img_size"], conf["in_channels"], 0, 0
    for item in conf["plan"]:
        if item == "P":
            out.append(Layer(f"pool.{pi}", "pool", c_in, c_in, 2, 2,
                             hw, hw // 2))
            hw //= 2
            pi += 1
        else:
            out.append(Layer(f"convs.{ci}", "conv", c_in, item, 3, 1, hw, hw,
                             packed=ci > 0))
            c_in = item
            ci += 1
    out.append(Layer("fc1", "dense", hw * hw * c_in, conf["fc_hidden"],
                     packed=True))
    out.append(Layer("head", "readout", conf["fc_hidden"],
                     conf["n_classes"]))
    return out
