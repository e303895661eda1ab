"""Pallas TPU kernel: multi-layer fused-group spiking rollout.

The fused_conv design extended across layers: ALL T timesteps of a
fusion group — a chain of stride-1 SAME convs with optional interleaved
max pools — run in a single ``pallas_call``, so the 1-bit inter-member
spike planes live and die in VMEM and NEVER touch HBM.  Dataflow per
batch element:

    grid (B, T), T innermost
    t-th step:
      packed input plane (cin_pad/32, Pq) --VPU shift/mask--> (cin_pad, Pq)
      for each member in the chain:
        conv: one accumulated MXU dot per tap (fused_common.conv_accumulate)
              VPU: LIF on this member's OWN VMEM membrane scratch
              spike plane stays a VMEM value -> next member's input
        pool: window max over lane-shifted copies, then a one-hot
              selection matmul that keeps the window corners and lays
              them out as the next member's padded plane
      final plane re-packed to 1-bit channel words, written to HBM

Layout (kernels/fused_common.py): channels on rows, pixels on lanes,
every plane flattened row-major over a grid whose width is the next
conv's padded width ``w + k - 1``.  A conv computes its (h, w + k - 1)
output grid, so the ``k - 1`` junk columns of each row are exactly the
right pad of that row and the left pad of the next once the plane is
shifted by ``pad_lo`` rows and columns: masking them to zero and
shifting is the whole re-pad between convs.  Pools change the grid, and
the (static) one-hot operand built by ops.py does that gather on the
MXU.

Each conv member keeps its int32 membrane tile (n_i, h_i*wq_i) in its
own VMEM scratch for the whole T-step scan (T is the innermost grid
dim, so scratch persists across t).  Per timestep the only HBM traffic
is ONE packed input plane and ONE packed output plane.  Weights for
every member stay resident per batch element (index maps constant in
t), fetched once, exactly like fused_conv.

Geometry contract (enforced by ops.py): every conv is stride 1 with SAME
padding (pad lo = (k-1)//2 — the exact amounts ref.conv_pads derives)
and one kernel size k for the whole chain, channels chain 32-padded
(member i's padded c_out IS member i+1's cin_pad; padded channels carry
masked-to-zero spikes and zero weight codes, so they are inert), pools
divide their plane exactly.  The whole working set must fit the shared
VMEM budget (kernels/vmem.py — the same formula the fusion planner
uses); oversized chains raise here and fall back to the per-layer
reference in ops.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.kernels import fused_common as _fc
from repro.kernels import vmem as _vmem

# geom rows (static, hashable):
#   ("conv", bits, k, cin_pad, h, w, n_pad, n_out)   h/w: in == out dims
#   ("pool", window, h, w, c_pad)                    h/w: input dims


def _conv_geoms(geoms) -> Tuple:
    return tuple(g for g in geoms if g[0] == "conv")


def _geom_vmem_dicts(geoms):
    out = []
    for g in geoms:
        if g[0] == "conv":
            _, bits, k, cin_pad, h, w, n_pad, _ = g
            out.append({"kind": "conv", "h": h, "w": w, "cin_pad": cin_pad,
                        "kh": k, "kw": k, "n": n_pad, "bits": bits})
        else:
            _, window, h, w, c_pad = g
            out.append({"kind": "pool", "h": h, "w": w, "c": c_pad,
                        "window": window})
    return out


def chain_grids(geoms) -> Tuple:
    """Per member: (input grid width, output grid width, output grid
    positions).  A conv's output grid is its padded width; a pool's is
    the next conv's padded plane, or the compact pooled plane when no
    conv follows."""
    k = _conv_geoms(geoms)[0][2]
    rows, grid_w = [], None
    for i, g in enumerate(geoms):
        if g[0] == "conv":
            _, _, _, _, h, w, _, _ = g
            wq = w + k - 1
            rows.append((wq, wq, h * wq))
            grid_w = wq
        else:
            _, p, h, w, _ = g
            ho, wo = h // p, w // p
            conv_next = i + 1 < len(geoms) and geoms[i + 1][0] == "conv"
            w_out = wo + k - 1 if conv_next else wo
            p_out = (ho + k) * w_out if conv_next else ho * wo
            rows.append((grid_w, w_out, p_out))
            grid_w = w_out
    return tuple(rows)


def pool_selection(geoms, index: int) -> np.ndarray:
    """The one-hot (L_in, P_out) int8 operand of pool member ``index``:
    column q picks the window corner that lands on grid position q of
    the next member's (zero-padded) plane; pad positions stay zero."""
    k = _conv_geoms(geoms)[0][2]
    _, p, h, w, _ = geoms[index]
    grid_in, w_out, p_out = chain_grids(geoms)[index]
    conv_next = index + 1 < len(geoms) and geoms[index + 1][0] == "conv"
    pad = (k - 1) // 2 if conv_next else 0
    sel = np.zeros((h * grid_in, p_out), np.int8)
    for i in range(h // p):
        for j in range(w // p):
            sel[p * i * grid_in + p * j, (i + pad) * w_out + j + pad] = 1
    return sel


def _fused_group_kernel(*refs, geoms, leak_shift: int, v_reset_q: int,
                        soft_reset: bool):
    convs = _conv_geoms(geoms)
    n_conv = len(convs)
    n_pool = len(geoms) - n_conv
    s_ref = refs[0]
    w_refs = refs[1:1 + 2 * n_conv:2]
    th_refs = refs[2:2 + 2 * n_conv:2]
    sel_refs = refs[1 + 2 * n_conv:1 + 2 * n_conv + n_pool]
    v_ref, o_ref = refs[1 + 2 * n_conv + n_pool:3 + 2 * n_conv + n_pool]
    v_accs = refs[3 + 2 * n_conv + n_pool:]
    grids = chain_grids(geoms)
    k = convs[0][2]
    pad_lo = (k - 1) // 2

    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        for acc in v_accs:
            acc[...] = jnp.zeros_like(acc)

    # the group's input plane: the one per-timestep HBM read
    x = _fc.unpack_spikes(s_ref[0, 0], convs[0][3])     # (cin_pad, Pq)
    ci = pi = 0
    v_last = None
    for gi, g in enumerate(geoms):
        grid_in, grid_out, _ = grids[gi]
        nxt = geoms[gi + 1][0] if gi + 1 < len(geoms) else None
        if g[0] == "conv":
            _, bits, _, cin_pad, h, w, n_pad, n_out = g
            l_out = h * grid_out
            i_syn = _fc.conv_accumulate([x], w_refs[ci],
                                        _fc.tap_offsets(k, k, 1, grid_out),
                                        cin_pad, bits, l_out)
            valid = _fc.row_mask(i_syn.shape, n_out)
            if nxt == "conv":
                # junk grid columns become the next conv's zero padding
                col = jax.lax.rem(
                    jax.lax.broadcasted_iota(jnp.int32, i_syn.shape, 1),
                    grid_out)
                valid = valid & (col < w)
            v, spikes = _fc.lif_fire(
                v_accs[ci][...], i_syn, th_refs[ci][...], valid,
                leak_shift=leak_shift, v_reset_q=v_reset_q,
                soft_reset=soft_reset)
            v_accs[ci][...] = v
            v_last = v
            x = spikes                                  # (n_pad, l_out)
            if nxt == "conv":
                lead = pad_lo * (grid_out + 1)
                x = jnp.concatenate(
                    [jnp.zeros((n_pad, lead), jnp.int32), x,
                     jnp.zeros((n_pad, (h + k) * grid_out - lead - l_out),
                               jnp.int32)], axis=1).astype(jnp.int8)
            ci += 1
        else:
            _, p, h, w, c_pad = g
            l_in = h * grid_in
            xi = x.astype(jnp.int32)
            xe = jnp.concatenate(
                [xi, jnp.zeros((c_pad, (p - 1) * (grid_in + 1)), jnp.int32)],
                axis=1)
            # non-overlapping window max == the binary-preserving OR pool
            # maxpool_t applies between unfused layers
            m = xe[:, :l_in]
            for a in range(p):
                for b in range(p):
                    if a or b:
                        off = a * grid_in + b
                        m = jnp.maximum(m, xe[:, off:off + l_in])
            x = jax.lax.dot_general(
                m.astype(jnp.int8), sel_refs[pi][...],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)       # (c_pad, p_out)
            if nxt == "conv":
                x = x.astype(jnp.int8)
            pi += 1

    v_ref[0] = v_last           # last conv's membrane, constant-in-t map
    o_ref[0, 0] = packing.pack_bool(x.astype(jnp.int32), axis=-2)


@functools.partial(
    jax.jit,
    static_argnames=("geoms", "leak_shift", "v_reset_q", "soft_reset",
                     "interpret"),
)
def fused_group_rollout_pallas(
    spikes_packed_t: jnp.ndarray,   # (T, B, cin_pad/32, Pq) int32 words
    *packed_operands: jnp.ndarray,  # per conv: w_packed^T, theta; then
                                    # one selection operand per pool
    geoms: Tuple,
    leak_shift: int,
    v_reset_q: int = 0,
    soft_reset: bool = True,
    interpret: bool = False,
):
    convs = _conv_geoms(geoms)
    if geoms[0][0] != "conv":
        raise ValueError("a fusion group starts at a conv member")
    n_pool = len(geoms) - len(convs)
    if len(packed_operands) != 2 * len(convs) + n_pool:
        raise ValueError(
            f"{len(convs)} conv members and {n_pool} pools need "
            f"{2 * len(convs) + n_pool} operands (w, theta per conv, one "
            f"selection per pool), got {len(packed_operands)}")
    need = _vmem.group_rollout_vmem_bytes(_geom_vmem_dicts(geoms))
    budget = _vmem.vmem_budget_bytes()
    if need > budget:
        raise ValueError(
            f"fused group working set exceeds the per-core VMEM budget: "
            f"needs ~{_vmem.format_bytes(need)} > "
            f"{_vmem.format_bytes(budget)} for chain {geoms} — dispatch "
            f"through fused_group_ops (or the fusion planner) to split "
            f"or fall back instead of miscompiling.")

    t_steps, b, wc, pq = spikes_packed_t.shape
    _, _, k, cin_pad0, h0, w0, _, _ = convs[0]
    wq0 = w0 + k - 1
    if wc * 32 != cin_pad0 or pq < (h0 + k - 1) * wq0 + k - 1:
        raise ValueError(
            f"input plane {wc}x{pq} words does not match the first "
            f"member's padded geometry {h0}x{w0}x{cin_pad0}, k={k} "
            f"(caller ops.py builds it with fused_common.phase_planes)")
    for gi, g in enumerate(convs):
        _, bits, kg, cin_pad, _, _, n_pad, _ = g
        wp, th = packed_operands[2 * gi], packed_operands[2 * gi + 1]
        if kg != k:
            raise ValueError(f"member {gi}: k={kg} in a k={k} chain")
        if wp.shape != (k * k * cin_pad * bits // 32, n_pad):
            raise ValueError(
                f"member {gi}: packed weights {wp.shape} != "
                f"({k * k * cin_pad * bits // 32}, {n_pad}) for geom {g}")
        if th.shape != (n_pad, 1):
            raise ValueError(f"member {gi}: theta {th.shape} != ({n_pad}, 1)")
        if n_pad % 32 or cin_pad % 32:
            raise ValueError("caller ops.py must 32-pad channels")

    grids = chain_grids(geoms)
    lc = convs[-1]
    n_lc, l_lc = lc[6], lc[4] * (lc[5] + k - 1)
    cf, lf = (lc[6] if geoms[-1][0] == "conv" else geoms[-1][4]), grids[-1][2]

    kernel = functools.partial(
        _fused_group_kernel, geoms=geoms, leak_shift=leak_shift,
        v_reset_q=v_reset_q, soft_reset=soft_reset)

    const = lambda i, t: (0, 0)  # noqa: E731 — resident across b and t
    in_specs = [pl.BlockSpec((1, 1, wc, pq), lambda i, t: (t, i, 0, 0))]
    in_specs += [pl.BlockSpec(op.shape, const) for op in packed_operands]

    flops = sum(2 * t_steps * b * g[4] * g[5] * g[2] * g[2] * g[3] * g[6]
                for g in convs)
    op_bytes = sum(op.size * op.dtype.itemsize for op in packed_operands)
    return pl.pallas_call(
        kernel,
        grid=(b, t_steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, n_lc, l_lc), lambda i, t: (i, 0, 0)),
            pl.BlockSpec((1, 1, cf // 32, lf), lambda i, t: (t, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_lc, l_lc), jnp.int32),
            jax.ShapeDtypeStruct((t_steps, b, cf // 32, lf), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g[6], g[4] * (g[5] + k - 1)), jnp.int32)
            for g in convs],
        # batch elements are independent; T carries every member's
        # membrane recurrence through scratch and must stay sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=(spikes_packed_t.size * 4     # planes in
                            + op_bytes                   # weights, selections
                            + b * n_lc * l_lc * 4        # membrane out
                            + t_steps * b * cf * lf // 8),  # spikes out
            transcendentals=0,
        ),
        interpret=interpret,
    )(spikes_packed_t, *packed_operands)
