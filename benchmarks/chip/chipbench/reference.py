"""Plain reference of the served spiking CNN, written from the
configuration file alone.  It imports nothing of the program and takes
nothing the program made: it quantizes the benchmark's own float
weights itself and runs every layer in straightforward ``jax.numpy``
with ``Precision.HIGHEST`` and float32 sums (integer layers exactly: 0/1
spikes times small integer codes, sums far below 2**24).

Semantics, as the configuration's ``datapath`` states them:

* float layers (stem and readout): matmul inputs as the configuration's
  ``float_matmul`` says (see :func:`float_inputs`), float32 sums.
* stem: direct-encoded image, conv with per-output-channel absmax
  fake-quantized weights, times the gain, float LIF
  (``v = v * beta + i``; spike where ``v >= threshold``; subtract).
* packed layers: per-output-channel symmetric codes of ``w * g`` with the
  clip fraction that minimizes the squared error (bits <= 4), integer
  threshold ``max(round(threshold / scale), 1)``, integer LIF
  (``v = v - (v >> leak_shift) + i``; spike where ``v >= theta``;
  subtract).
* 2x2 max pools, residual OR, fc1 flattened in (H, W, C) order, and a
  float readout averaged over T.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import ops as _ops

HIGHEST = jax.lax.Precision.HIGHEST
N_FRACS = 16
TIE_REL = 1e-4    # clip fractions whose errors differ by less than this
                  # share tie (float32 sums of up to 4,608 squares)
MAX_TIES = 64
PAIRS = 8         # the closest ties also tried two at a time
EXACT = 1e-6      # an answer matches within this share of the logits' RMS


# -- parameter paths --------------------------------------------------------

def get_path(tree, path: str):
    for part in path.split("."):
        tree = tree[int(part)] if isinstance(tree, list) else tree[part]
    return tree


def set_path(tree: dict, path: str, value) -> None:
    """Set ``value`` at a dotted path, growing dicts and lists (a numeric
    component is a list index) on the way."""
    parts = path.split(".")
    node = tree
    for part, nxt in zip(parts[:-1], parts[1:]):
        child_default = [] if nxt.isdigit() else {}
        if isinstance(node, list):
            i = int(part)
            while len(node) <= i:
                node.append(None)
            if node[i] is None:
                node[i] = child_default
            node = node[i]
        else:
            node = node.setdefault(part, child_default)
    last = parts[-1]
    if isinstance(node, list):
        i = int(last)
        while len(node) <= i:
            node.append(None)
        node[i] = value
    else:
        node[last] = value


# -- quantization -----------------------------------------------------------

def qrange(bits: int):
    return -(1 << (bits - 1)), (1 << (bits - 1)) - 1


def quantize(w: jnp.ndarray, bits: int, flip=None):
    """(c_out, K) float weights -> (codes as float32, scale (c_out,),
    tie (c_out,)).  ``tie`` is how far the second best clip fraction's
    squared error lies above the best one's, relative to the best
    (infinite where there is no search); ``flip`` (bool, (c_out,)) takes
    the second best fraction in those channels instead."""
    qmin, qmax = qrange(bits)
    absmax = jnp.max(jnp.abs(w), axis=-1)
    tie = jnp.full(w.shape[:1], jnp.inf, jnp.float32)
    if bits <= 4:
        fracs = jnp.linspace(0.25, 1.0, N_FRACS, dtype=jnp.float32)

        def mse(frac):
            s = jnp.maximum(absmax * frac / qmax, 1e-8)
            q = jnp.clip(jnp.round(w / s[:, None]), qmin, qmax)
            return jnp.mean((q * s[:, None] - w) ** 2, axis=-1)

        mses = jax.lax.map(mse, fracs)
        # stable: the best is the first of equal errors, as argmin takes it
        order = jnp.argsort(mses, axis=0)
        cols = jnp.arange(w.shape[0])
        first, second = mses[order[0], cols], mses[order[1], cols]
        tie = (second - first) / jnp.maximum(first, 1e-30)
        pick = order[0] if flip is None else jnp.where(flip, order[1],
                                                       order[0])
        scale = jnp.maximum(absmax * fracs[pick] / qmax, 1e-8)
    else:
        scale = jnp.maximum(absmax / qmax, 1e-8)
    codes = jnp.clip(jnp.round(w / scale[:, None]), qmin, qmax)
    return codes, scale, tie


def fake_quant_absmax(w: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(c_out, K) -> dequantized (c_out, K), absmax scale, no search."""
    qmin, qmax = qrange(bits)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-1, keepdims=True) / qmax,
                        1e-8)
    return jnp.round(jnp.clip(w / scale, qmin, qmax)) * scale


def theta_q(scale: jnp.ndarray, threshold: float) -> jnp.ndarray:
    t = jnp.round(threshold / jnp.maximum(scale, 1e-12))
    return jnp.maximum(t, 1.0).astype(jnp.int32)


def _hwio_rows(w):          # (k, k, ci, co) -> (co, k*k*ci)
    return w.transpose(3, 0, 1, 2).reshape(w.shape[3], -1)


def _rows_hwio(rows, shape):
    kh, kw, ci, co = shape
    return rows.reshape(co, kh, kw, ci).transpose(1, 2, 3, 0)


def prepare(conf: dict, params, bits: int, flips: Dict) -> Dict:
    """Per layer operands at ``bits``: stem fake-quantized float weights,
    packed layers' integer codes and thresholds with each channel's clip
    search ``tie`` (see :func:`quantize`; ``flips[name]`` picks the second
    best fraction), the head's weights."""
    out = {}
    for layer in _ops.layers(conf):
        if layer.kind not in ("conv", "dense", "readout"):
            continue
        p = get_path(params, layer.name)
        if layer.kind == "readout":
            out[layer.name] = {"w": p["w"]}
        elif not layer.packed:                       # the float stem
            w = p["w"]
            out[layer.name] = {
                "w": _rows_hwio(fake_quant_absmax(_hwio_rows(w), bits),
                                w.shape),
                "g": p["g"]}
        elif layer.kind == "conv":
            w = p["w"] * p["g"]
            codes, scale, tie = quantize(_hwio_rows(w), bits,
                                         flips[layer.name])
            out[layer.name] = {"w": _rows_hwio(codes, w.shape),
                               "theta": theta_q(scale, conf["threshold"]),
                               "tie": tie}
        else:
            codes, scale, tie = quantize((p["w"] * p["g"]).T, bits,
                                         flips[layer.name])
            out[layer.name] = {"w": codes.T,
                               "theta": theta_q(scale, conf["threshold"]),
                               "tie": tie}
    return out


def no_flips(conf: dict) -> Dict:
    return {layer.name: np.zeros(layer.c_out, bool)
            for layer in _ops.layers(conf)
            if layer.packed and layer.kind in ("conv", "dense")}


# -- layers -----------------------------------------------------------------

def float_inputs(conf: dict, *xs):
    """The float layers' matmul inputs at the configuration's
    ``float_matmul``: ``bf16`` rounds them to bfloat16 for one pass with
    float32 accumulation (a TPU's default precision for float32),
    ``f32`` keeps them."""
    if conf["float_matmul"] == "bf16":
        return tuple(x.astype(jnp.bfloat16) for x in xs)
    if conf["float_matmul"] == "f32":
        return xs
    raise ValueError(f"unknown float_matmul {conf['float_matmul']!r}")


def conv(x: jnp.ndarray, w: jnp.ndarray, stride: int) -> jnp.ndarray:
    """(T, B, H, W, C) x HWIO -> (T, B, Ho, Wo, c_out) float32, SAME
    padding, exact for float32 inputs and exact products otherwise."""
    t, b = x.shape[:2]
    y = jax.lax.conv_general_dilated(
        x.reshape(t * b, *x.shape[2:]), w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    return y.reshape(t, b, *y.shape[1:])


def maxpool(x: jnp.ndarray) -> jnp.ndarray:
    t, b, h, w, c = x.shape
    return x.reshape(t, b, h // 2, 2, w // 2, 2, c).max(axis=(3, 5))


def lif_float(i_t: jnp.ndarray, conf: dict) -> jnp.ndarray:
    beta = 1.0 - 2.0 ** (-conf["leak_shift"])
    thr = conf["threshold"]
    v = jnp.zeros(i_t.shape[1:], jnp.float32)
    out = []
    for t in range(i_t.shape[0]):
        v = v * beta + i_t[t]
        s = (v - thr >= 0).astype(jnp.float32)
        v = v - s * thr
        out.append(s)
    return jnp.stack(out)


def lif_int(i_t: jnp.ndarray, theta: jnp.ndarray, conf: dict) -> jnp.ndarray:
    i_t = jnp.round(i_t).astype(jnp.int32)
    v = jnp.zeros(i_t.shape[1:], jnp.int32)
    out = []
    for t in range(i_t.shape[0]):
        v = v - (v >> conf["leak_shift"]) + i_t[t]
        s = (v >= theta).astype(jnp.int32)
        v = v - s * theta
        out.append(s)
    return jnp.stack(out).astype(jnp.float32)


def walk(conf: dict, images: jnp.ndarray,
         spiking: Callable[[_ops.Layer, jnp.ndarray], jnp.ndarray],
         readout: Callable[[_ops.Layer, jnp.ndarray], jnp.ndarray]):
    """Drive the configuration's layers over (B, H, W, C) images,
    direct-encoded to (T, B, H, W, C).  ``spiking`` runs one conv or
    dense layer on its input spikes (or currents, for the stem)."""
    x = jnp.broadcast_to(images, (conf["timesteps"], *images.shape))
    outs = {}
    for layer in _ops.layers(conf):
        src = outs[layer.src] if layer.src is not None else x
        if layer.kind == "pool":
            x = maxpool(x)
        elif layer.kind == "merge":
            x = jnp.maximum(x, src)
        elif layer.kind == "readout":
            return readout(layer, src)
        else:
            if layer.kind == "dense":
                src = src.reshape(*src.shape[:2], -1)
            x = spiking(layer, src)
        outs[layer.name] = x
    raise ValueError("configuration has no readout")


def forward(conf: dict, operands: Dict, images: jnp.ndarray) -> jnp.ndarray:
    """(B, H, W, C) images -> (B, n_classes) logits."""

    def spiking(layer, x):
        op = operands[layer.name]
        if layer.kind == "dense":
            i = jnp.einsum("tbi,io->tbo", x, op["w"], precision=HIGHEST)
            return lif_int(i, op["theta"], conf)
        if not layer.packed:
            i = conv(*float_inputs(conf, x, op["w"]), layer.stride)
            return lif_float(i * op["g"], conf)
        return lif_int(conv(x, op["w"], layer.stride), op["theta"], conf)

    def readout(layer, x):
        if layer.spatial_mean:
            x = jnp.mean(x, axis=(2, 3))
        x, w = float_inputs(conf, x, operands[layer.name]["w"])
        return jnp.mean(jnp.einsum("tbi,io->tbo", x, w, precision=HIGHEST,
                                   preferred_element_type=jnp.float32),
                        axis=0)

    return walk(conf, images, spiking, readout)


def logits(conf: dict, params, images, bits: int, served=None,
           block: int = 32):
    """Reference logits of ``images`` (host array), ``block`` rows per
    call so that the whole model's activations stay small.  Returns
    (logits, the (layer, channel) pairs whose clip search the served
    answers settled).

    The configuration's clip fraction is the one of least squared error.
    Where two fractions' errors lie within ``TIE_REL`` of each other,
    float32 rounding in the order of the sums decides, and either is the
    configuration's answer.  With ``served`` logits, where some differ
    from the reference's, the other choice of such channels is tried, one
    channel at a time (two at a time among the ``PAIRS`` closest ties
    where no single one helps), on up to two blocks of the answers that
    differ; a choice is kept where it makes more answers match exactly,
    and the search goes on from there.  A wrong answer matches no choice,
    so nothing that is wrong is fitted away."""
    prep = jax.jit(lambda p, f: prepare(conf, p, bits, f))
    fwd = jax.jit(lambda o, x: forward(conf, o, x))
    images = np.asarray(images, np.float32)

    def run(flips, rows):
        operands = prep(params, flips)
        out = []
        for i in range(0, len(rows), block):
            chunk = images[rows[i:i + block]]
            pad = block - len(chunk)
            if pad:
                chunk = np.concatenate(
                    [chunk, np.zeros((pad, *chunk.shape[1:]), np.float32)])
            out.append(np.asarray(fwd(operands, chunk))[:block - pad])
        return np.concatenate(out), operands

    every = np.arange(len(images))
    flips = no_flips(conf)
    ref, operands = run(flips, every)
    if served is None:
        return ref, []
    served = np.asarray(served, np.float32)
    atol = EXACT * float(np.sqrt(np.mean(np.square(served, dtype=np.float64))))

    def matched(got, rows):
        return np.max(np.abs(got - served[rows]), axis=1) <= atol

    ties = sorted((float(g), name, int(c))
                  for name, op in operands.items() if "tie" in op
                  for c, g in enumerate(np.asarray(op["tie"]))
                  if g <= TIE_REL)[:MAX_TIES]
    ties = [(name, c) for _, name, c in ties]
    singles = [(t,) for t in ties]
    pairs = [(a, b) for i, a in enumerate(ties[:PAIRS])
             for b in ties[i + 1:PAIRS]]
    settled = []
    exact = matched(ref, every)
    while not exact.all():
        rows = every[~exact][:2 * block]
        best, gain = None, 0
        for moves in (singles, pairs):
            for move in moves:
                if any(t in settled for t in move):
                    continue
                trial = {k: v.copy() for k, v in flips.items()}
                for name, c in move:
                    trial[name][c] = True
                n = int(matched(run(trial, rows)[0], rows).sum())
                if n > gain:
                    best, gain = move, n
            if best:
                break
        if not best:
            break
        for name, c in best:
            flips[name][c] = True
        got, _ = run(flips, every)
        now = matched(got, every)
        if now.sum() <= exact.sum():
            for name, c in best:
                flips[name][c] = False
            break
        ref, exact = got, now
        settled += list(best)
    return ref, settled
