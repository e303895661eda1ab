"""Public entry point for the multi-layer fused-group rollout.

Dispatch rules (see repro.kernels.backend):
  'jnp'       -> ref.fused_group_rollout_ref (per-layer fused_conv chain)
  'interpret' -> kernel.fused_group_rollout_pallas(interpret=True)
  'pallas'    -> kernel.fused_group_rollout_pallas (compiled, TPU)

Member encoding (shared with ref.py and core.snn_layers):

    ("conv", qct: QuantizedConvTensor, threshold_q: scalar | (c_out,))
    ("pool", window: int)

The chain contract this layer enforces before any kernel is built:
at least two members, the first a conv, every conv stride-1 SAME with
the same weight precision and kernel size, channels threading exactly (member i's c_out
is member i+1's c_in, pools preserving channels), and every pool
dividing its plane.  Violations raise ValueError with the offending
member — the graph-level planner (repro.graph.fusion) front-runs these
with layer *names*, so executor-driven calls should never trip them.

A chain whose working set exceeds the VMEM budget (kernels/vmem.py, the
same formula the planner budgets with) falls back to the bit-exact
per-layer reference with a ``RuntimeWarning`` rather than emitting a
kernel that cannot stay resident.
"""

from __future__ import annotations

import warnings
from typing import Sequence, Tuple

import jax.numpy as jnp

from repro.core import packing
from repro.core.lif import as_theta_vector
from repro.kernels import backend as _backend
from repro.kernels import vmem as _vmem
from repro.kernels import fused_common as _fc
from repro.kernels.fused_group import kernel as _kernel
from repro.kernels.fused_group import ref as _ref


def _round32(x: int) -> int:
    return -(-x // 32) * 32


def _record(layers: Sequence[str], path: str, need: int = 0) -> None:
    """Each conv member's lowering record (one group estimate for all)."""
    from repro.obs import default_registry   # local: obs imports graph

    for layer in layers:
        default_registry().lowered(layer, path, None, need)


def _normalize_members(members: Sequence[Tuple], h: int, w: int,
                       win: int) -> Tuple[Tuple, ...]:
    """Validate the chain and normalize thresholds to (c_out,) vectors.

    Returns the normalized member tuple; raises ValueError on any chain
    contract violation.  Tracks the plane through the chain so the
    errors carry concrete geometry.
    """
    if len(members) < 2:
        raise ValueError(
            f"a fusion group fuses 2+ members, got {len(members)} — "
            f"use fused_conv_rollout for a single layer")
    if members[0][0] != "conv":
        raise ValueError("a fusion group must start at a conv member "
                         f"(got {members[0][0]!r})")

    norm = []
    ch = None
    bits = None
    for mi, m in enumerate(members):
        if m[0] == "conv":
            _, qct, theta = m
            if mi == 0:
                if win != packing.packed_last_dim(qct.c_in, 1):
                    raise ValueError(
                        f"spike plane carries {win} channel words, the "
                        f"first member expects "
                        f"{packing.packed_last_dim(qct.c_in, 1)} "
                        f"(c_in={qct.c_in})")
                if qct.c_in_pad != win * 32:
                    raise ValueError(
                        "quantize_conv cin_pad drifted from the spike "
                        "word layout — requantize the weights")
            elif qct.c_in != ch:
                raise ValueError(
                    f"member {mi}: conv expects c_in={qct.c_in} but the "
                    f"chain carries {ch} channels — fusion members must "
                    f"thread channels exactly")
            if bits is None:
                bits = qct.bits
            elif qct.bits != bits:
                raise ValueError(
                    f"member {mi}: w{qct.bits} weights in a w{bits} "
                    f"group — a fusion group runs ONE datapath width "
                    f"(precision-mixed chains must stay unfused)")
            if qct.kh != qct.kw:
                raise ValueError(
                    f"member {mi}: non-square kernel "
                    f"{qct.kh}x{qct.kw} is not fusable")
            if norm and qct.kh != norm[0][1].kh:
                raise ValueError(
                    f"member {mi}: {qct.kh}x{qct.kh} kernel in a "
                    f"{norm[0][1].kh}x{norm[0][1].kh} chain — one kernel "
                    f"size per group (the planes share one padded grid)")
            norm.append(("conv", qct, as_theta_vector(theta, qct.c_out)))
            ch = qct.c_out
        elif m[0] == "pool":
            _, window = m
            if ch is None:
                raise ValueError("a pool cannot lead a fusion group")
            if h % window or w % window:
                raise ValueError(
                    f"member {mi}: pool window {window} does not divide "
                    f"the {h}x{w} plane it receives")
            h, w = h // window, w // window
            norm.append(("pool", window))
        else:
            raise ValueError(f"unknown group member kind {m[0]!r}")
    return tuple(norm)


def _chain_geoms(members: Sequence[Tuple], h: int,
                 w: int) -> Tuple[Tuple, ...]:
    """Static geom rows for kernel.py, walking the plane through the
    chain.  Channel padding chains: a conv's padded c_out (round32) IS
    the next member's cin_pad, matching quantize_conv's own rounding."""
    geoms = []
    for m in members:
        if m[0] == "conv":
            _, qct, _ = m
            geoms.append(("conv", qct.bits, qct.kh, qct.c_in_pad, h, w,
                          _round32(qct.c_out), qct.c_out))
        else:
            _, window = m
            cp = geoms[-1][6]  # previous conv's padded width
            geoms.append(("pool", window, h, w, cp))
            h, w = h // window, w // window
    return tuple(geoms)


def fused_group_rollout(
    spikes_packed_t: jnp.ndarray,  # (T, B, H, W, ceil(c_in/32)) int32
    members: Sequence[Tuple],
    *,
    leak_shift: int,
    v_reset_q: int = 0,
    soft_reset: bool = True,
    layers: Sequence[str] = (),
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All T timesteps of a whole fusion-group chain in one fused pass.

    Returns (v_T: (B, Ho, Wo, c_out) int32 — the LAST conv member's
    final membrane, pre-pool if a pool ends the chain — and
    out_spikes_packed: (T, B, HoF, WoF, ceil(c_outF/32)) int32), bit-
    exact with the per-layer fused_conv composition of ref.py.
    ``layers`` names the conv members in the lowering record.
    """
    t_steps, b, h, w, win = spikes_packed_t.shape
    members = _normalize_members(members, h, w, win)
    convs = [m for m in members if m[0] == "conv"]
    last_qct = convs[-1][1]

    if _backend.get_backend() == "jnp":
        _record(layers, "unfused")
        return _ref.fused_group_rollout_ref(
            spikes_packed_t, members, leak_shift=leak_shift,
            v_reset_q=v_reset_q, soft_reset=soft_reset)

    # walk the chain for output geometry (convs are stride-1 SAME)
    hf, wf, h_lc, w_lc = h, w, h, w
    for m in members:
        if m[0] == "conv":
            h_lc, w_lc = hf, wf
        else:
            hf, wf = hf // m[1], wf // m[1]
    words_out = packing.packed_last_dim(last_qct.c_out, 1)
    if t_steps == 0:  # degenerate rollout: match lax.scan's empty-ys shape
        return (jnp.zeros((b, h_lc, w_lc, last_qct.c_out), jnp.int32),
                jnp.zeros((0, b, hf, wf, words_out), jnp.int32))

    geoms = _chain_geoms(members, h, w)
    need = _vmem.group_rollout_vmem_bytes(_kernel._geom_vmem_dicts(geoms))
    budget = _vmem.vmem_budget_bytes()
    if need > budget:
        warnings.warn(
            f"fused group chain of {len(members)} members "
            f"({len(convs)} convs, input {h}x{w}x{convs[0][1].c_in}, "
            f"w{last_qct.bits}) needs ~{_vmem.format_bytes(need)} of "
            f"VMEM > budget {_vmem.format_bytes(budget)}; falling back "
            f"to the per-layer reference path (bit-exact, but inter-"
            f"member planes round-trip HBM)",
            RuntimeWarning, stacklevel=2)
        _record(layers, "unfused")
        return _ref.fused_group_rollout_ref(
            spikes_packed_t, members, leak_shift=leak_shift,
            v_reset_q=v_reset_q, soft_reset=soft_reset)

    k = convs[0][1].kh
    pad = (k - 1) // 2
    sp, _ = _fc.phase_planes(
        spikes_packed_t, ((pad, k - 1 - pad), (pad, k - 1 - pad)),
        k, k, 1, h, w)
    operands = []
    for _, qct, theta in convs:
        n_pad = _round32(qct.c_out)
        operands.append(jnp.pad(qct.data, ((0, n_pad - qct.c_out),
                                           (0, 0))).T)
        # padded channels' theta is irrelevant: the kernel masks their
        # spikes by n_out before the reset uses theta
        operands.append(jnp.pad(theta[:, None],
                                ((0, n_pad - qct.c_out), (0, 0))))
    operands += [jnp.asarray(_kernel.pool_selection(geoms, i))
                 for i, g in enumerate(geoms) if g[0] == "pool"]

    v, out = _kernel.fused_group_rollout_pallas(
        sp[:, :, 0], *operands, geoms=geoms, leak_shift=leak_shift,
        v_reset_q=v_reset_q, soft_reset=soft_reset,
        interpret=(_backend.get_backend() == "interpret"))

    _record(layers, "fused_group", need)
    grid_f = _kernel.chain_grids(geoms)[-1][1]
    v = _fc.grid_to_nhwc(v, h_lc, w_lc + k - 1, w_lc, last_qct.c_out)
    out = _fc.grid_to_nhwc(out, hf, grid_f, wf, words_out)
    return v, out
