"""ResNet bottleneck family (ResNet-50 v1.5): a ``stem_kernel`` x
``stem_kernel`` float stem of ``stem_channels`` with stride
``stem_stride``, a 2x2 max pool of its spikes where ``stem_pool``, then
``stages`` of [width, blocks, stride] bottleneck blocks (1x1 to the
width, 3x3 carrying the stride, 1x1 to ``expansion`` x the width; a 1x1
projection with the stride where the stride or the width changes;
spike-OR merge), a spatial mean and the float readout.  SAME padding: a
stride-s layer gives ceil(hw / s).  A block's projection is listed
before its body, so that the merge reads the body's output as the
previous node."""

from typing import List

from chipbench.ops import Layer


def _out(hw: int, stride: int) -> int:
    return -(-hw // stride)


def layers(conf: dict) -> List[Layer]:
    hw, c = conf["img_size"], conf["stem_channels"]
    k, s = conf["stem_kernel"], conf["stem_stride"]
    out = [Layer("stem", "conv", conf["in_channels"], c, k, s, hw,
                 _out(hw, s))]
    hw, prev = _out(hw, s), "stem"
    if conf["stem_pool"]:
        out.append(Layer("pool.0", "pool", c, c, 2, 2, hw, hw // 2))
        hw, prev = hw // 2, "pool.0"
    bi = 0
    for width, n_blocks, stride in conf["stages"]:
        c_out = conf["expansion"] * width
        for b in range(n_blocks):
            s = stride if b == 0 else 1
            name = f"blocks.{bi}"
            hw_out = _out(hw, s)
            short = prev
            if s != 1 or c != c_out:
                out.append(Layer(f"{name}.proj", "conv", c, c_out, 1, s, hw,
                                 hw_out, packed=True, src=prev))
                short = f"{name}.proj"
            out.append(Layer(f"{name}.conv1", "conv", c, width, 1, 1, hw,
                             hw, packed=True, src=prev))
            out.append(Layer(f"{name}.conv2", "conv", width, width, 3, s,
                             hw, hw_out, packed=True))
            out.append(Layer(f"{name}.conv3", "conv", width, c_out, 1, 1,
                             hw_out, hw_out, packed=True))
            out.append(Layer(f"{name}.merge", "merge", c_out, c_out,
                             hw_in=hw_out, hw_out=hw_out, src=short))
            prev = f"{name}.merge"
            c, hw = c_out, hw_out
            bi += 1
    out.append(Layer("head", "readout", c, conf["n_classes"], src=prev,
                     spatial_mean=True))
    return out
