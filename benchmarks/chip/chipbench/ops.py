"""The benchmark's own layer table of a configuration, and the
operations and bytes each layer's work needs.

The table is made from the configuration file alone, by the layer
function of its family (``families/<family>.py``), never from the
program's graph, so the yardstick stays fixed whatever the program
does.  One MAC is one synaptic accumulate of one timestep; an operation
is two MACs' worth of work (multiply and add), as the int8 peak counts them.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
from pathlib import Path
from typing import List, Optional

WORD_BITS = 32
FAMILIES = Path(__file__).resolve().parents[1] / "families"


def _words(n: int, bits: int) -> int:
    return -(-n * bits // WORD_BITS)


@dataclasses.dataclass(frozen=True)
class Layer:
    """One node of the served forward.  A list of them is in an order
    that respects every data dependency (a block's projection comes
    before its body, so the merge reads the body's output as the
    previous node).

    kind: ``conv`` (stem or packed), ``pool``, ``dense`` (packed, spiking),
    ``readout`` (float head), ``merge`` (residual spike OR).  ``packed``
    marks the layers on the packed integer datapath: every conv after the
    stem and the spiking dense layer.  ``src`` names the layer whose output
    this one reads (``None``: the previous node); a merge reads ``src`` and
    the previous node.
    """

    name: str
    kind: str
    c_in: int = 0
    c_out: int = 0
    k: int = 1
    stride: int = 1
    hw_in: int = 0
    hw_out: int = 0
    packed: bool = False
    src: Optional[str] = None
    spatial_mean: bool = False

    @property
    def macs(self) -> int:
        """MACs of one timestep for one image."""
        if self.kind == "conv":
            return self.hw_out ** 2 * self.k ** 2 * self.c_in * self.c_out
        if self.kind in ("dense", "readout"):
            return self.c_in * self.c_out
        return 0

    def weight_bytes(self, bits: int) -> int:
        """Packed bytes a kernel call reads once: weight words (channels
        padded to a spike word), one float scale and one int threshold per
        output channel."""
        c_in_pad = WORD_BITS * _words(self.c_in, 1)
        k_flat = self.k * self.k * c_in_pad if self.kind == "conv" \
            else self.c_in
        return 4 * (self.c_out * _words(k_flat, bits) + 2 * self.c_out)

    def spike_bytes(self, timesteps: int) -> int:
        """1-bit spike planes one image reads and writes over a rollout."""
        if self.kind == "conv":
            pix_in, pix_out = self.hw_in ** 2, self.hw_out ** 2
        else:
            pix_in = pix_out = 1
        return 4 * timesteps * (pix_in * _words(self.c_in, 1)
                                + pix_out * _words(self.c_out, 1))


@functools.lru_cache(maxsize=None)
def _family(family: str):
    path = FAMILIES / f"{family}.py"
    if not path.is_file():
        raise ValueError(f"unknown model family {family!r}: no {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_family_{family}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layers(conf: dict) -> List[Layer]:
    """The configuration's forward as a flat list of :class:`Layer`,
    built by ``families/<family>.py`` (a new family is a new file)."""
    return _family(conf["family"]).layers(conf)


def macs_per_image(conf: dict) -> int:
    """Synaptic MACs of one image over the whole rollout, every layer."""
    return conf["timesteps"] * sum(l.macs for l in layers(conf))


def ops_per_image(conf: dict) -> int:
    return 2 * macs_per_image(conf)


def packed_least_s(conf: dict, slots: int, peaks) -> dict:
    """Least time the chip could take for the packed layers' work in one
    forward over ``slots`` dispatched rows: per layer, the larger of its
    operations over the int8 peak and its bytes (weights once per call,
    spike planes per row) over HBM bandwidth.  Returns the sum and how
    many layers each bound governs."""
    t_steps, bits = conf["timesteps"], conf["bits"]
    total, n_compute, n_memory = 0.0, 0, 0
    for layer in layers(conf):
        if not layer.packed:
            continue
        ops = 2 * t_steps * layer.macs * slots
        nbytes = layer.weight_bytes(bits) + slots * layer.spike_bytes(t_steps)
        t_ops, t_mem = ops / peaks.int8_ops, nbytes / peaks.hbm_bw
        total += max(t_ops, t_mem)
        if t_ops >= t_mem:
            n_compute += 1
        else:
            n_memory += 1
    return {"seconds": total, "compute_bound": n_compute,
            "memory_bound": n_memory}
