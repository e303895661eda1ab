"""ResNet family: a 3x3 float stem of ``stem_channels``, then ``stages``
of [width, blocks, stride] basic blocks (two 3x3 convs, a 1x1
projection where the stride or the width changes, spike-OR merge), a
spatial mean and the float readout.  A block's projection is listed
before its body, so that the merge reads the body's output as the
previous node."""

from typing import List

from chipbench.ops import Layer


def layers(conf: dict) -> List[Layer]:
    hw, c = conf["img_size"], conf["stem_channels"]
    out = [Layer("stem", "conv", conf["in_channels"], c, 3, 1, hw, hw)]
    prev, bi = "stem", 0
    for c_out, n_blocks, stride in conf["stages"]:
        for b in range(n_blocks):
            s = stride if b == 0 else 1
            name = f"blocks.{bi}"
            hw_out = hw // s
            short = prev
            if s != 1 or c != c_out:
                out.append(Layer(f"{name}.proj", "conv", c, c_out, 1, s, hw,
                                 hw_out, packed=True, src=prev))
                short = f"{name}.proj"
            out.append(Layer(f"{name}.conv1", "conv", c, c_out, 3, s, hw,
                             hw_out, packed=True, src=prev))
            out.append(Layer(f"{name}.conv2", "conv", c_out, c_out, 3, 1,
                             hw_out, hw_out, packed=True))
            out.append(Layer(f"{name}.merge", "merge", c_out, c_out,
                             hw_in=hw_out, hw_out=hw_out, src=short))
            prev = f"{name}.merge"
            c, hw = c_out, hw_out
            bi += 1
    out.append(Layer("head", "readout", c, conf["n_classes"], src=prev,
                     spatial_mean=True))
    return out
