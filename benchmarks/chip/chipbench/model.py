"""Weights and images from the seed, made by the benchmark itself.

One jitted call makes, on the device: He-normal float weights for every
layer in the program's parameter layout (dotted paths such as
``convs.3`` or ``blocks.2.proj``), the pool of request images, and the
per-channel gains ``g`` balanced on the first images of the pool so that
every layer fires (Diehl-style: the pre-gain current's standard
deviation is brought to 1.1 x threshold, with max pools and a residual
OR as the served forward has them).  The program then packs these
weights with its own deploy step; the reference quantizes them itself.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import ops as _ops
from chipbench import reference as _ref

N_CALIBRATION = 16
BALANCE_TARGET = 1.1


def seed_key(seed: int):
    """A PRNG key for any whole seed, including ones past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _init(conf: dict, key) -> dict:
    params: dict = {}
    for i, layer in enumerate(_ops.layers(conf)):
        k = jax.random.fold_in(key, i)
        if layer.kind == "conv":
            fan_in = layer.k * layer.k * layer.c_in
            shape = (layer.k, layer.k, layer.c_in, layer.c_out)
        elif layer.kind in ("dense", "readout"):
            fan_in, shape = layer.c_in, (layer.c_in, layer.c_out)
        else:
            continue
        w = jax.random.normal(k, shape, jnp.float32) * (2.0 / fan_in) ** 0.5
        leaf = {"w": w}
        if layer.kind != "readout":
            leaf["g"] = jnp.ones((layer.c_out,), jnp.float32)
        _ref.set_path(params, layer.name, leaf)
    return params


def _balance(i_t: jnp.ndarray, threshold: float) -> jnp.ndarray:
    red = tuple(range(i_t.ndim - 1))
    std = jnp.std(i_t, axis=red) + 1e-6
    return jnp.clip(BALANCE_TARGET * threshold / std, 0.05, 100.0)


def _calibrate(conf: dict, params: dict, images: jnp.ndarray) -> dict:
    """Gains from one float forward of the calibration images."""
    gains = {}

    def spiking(layer, x):
        w = _ref.get_path(params, layer.name)["w"]
        if layer.kind == "dense":
            i = jnp.einsum("tbi,io->tbo", x, w, precision=_ref.HIGHEST)
        else:
            i = _ref.conv(x, w, layer.stride)
        g = _balance(i, conf["threshold"])
        gains[layer.name] = g
        return _ref.lif_float(i * g, conf)

    _ref.walk(conf, images, spiking, lambda layer, x: x)
    for name, g in gains.items():
        _ref.get_path(params, name)["g"] = g
    return params


def make(conf: dict, seed: int, n_images: int):
    """(float params, (n_images, H, W, C) images), on the device."""
    shape = (n_images, conf["img_size"], conf["img_size"],
             conf["in_channels"])

    @jax.jit
    def build(key):
        kw, ki = jax.random.split(key)
        images = jax.random.uniform(ki, shape, jnp.float32)
        params = _calibrate(conf, _init(conf, kw), images[:N_CALIBRATION])
        return params, images

    return build(seed_key(seed))
