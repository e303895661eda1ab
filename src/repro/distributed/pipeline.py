"""Pipeline parallelism over the pod axis (GPipe schedule).

At 1000+ nodes the inter-pod links are the scarce resource: pure DP moves
full gradients across them every step, while pipelining moves only
microbatch activations at one stage boundary.  This module repurposes the
``pod`` mesh axis as pipeline stages:

* layer-stacked params are sharded P('pod') on the LAYER dim — each pod
  holds n_layers/n_stages contiguous layers (+ a replicated copy of the
  embedding for the first/last stage work);
* inside ``shard_map`` over 'pod', a ``lax.scan`` runs the GPipe schedule:
  n_micro + n_stages - 1 ticks; each tick every stage processes the
  microbatch it holds and ``ppermute``s activations to the next stage;
* the whole schedule is differentiable (ppermute transposes to the
  reverse permutation), so ``jax.grad`` of the scanned forward yields the
  1F1B-equivalent backward wave and per-stage gradients land exactly on
  the stage that owns the layers.

Bubble fraction = (n_stages-1)/(n_micro + n_stages - 1) — pick
n_micro >= 4x stages.  Inter-pod traffic per step = 2 x n_micro x
microbatch activation bytes (fwd + bwd), vs 2 x param bytes for DP.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import transformer as T


def stage_fwd(stage_params, cfg: ArchConfig, x, windows):
    """Run this stage's slice of the layer stack on activations x."""
    body = functools.partial(T._block_full, cfg=cfg, prefix_len=0)
    if cfg.remat != "none":
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)),
                               (stage_params, windows))
    return x, aux


def make_pipeline_loss(cfg: ArchConfig, mesh, n_micro: int,
                       data_axis: str | None = None):
    """Returns loss_fn(params, batch) running the GPipe schedule over the
    'pod' axis of `mesh`.  params['layers'] leaves must be sharded P('pod')
    on their leading (layer) dim; embed/final_norm replicated.  With
    data_axis set, the batch is additionally split over that axis (DP
    inside each pipeline stage)."""
    n_stages = mesh.shape["pod"]
    assert cfg.n_layers % n_stages == 0
    windows_all = jnp.asarray(T.window_schedule(cfg))

    def inner(layers_shard, embed, final_norm_g, tokens, labels):
        # layers_shard: this stage's (L/stages, ...) params (shard_map view)
        stage = jax.lax.axis_index("pod")
        n_ticks = n_micro + n_stages - 1
        Bm = tokens.shape[0] // n_micro
        d = cfg.d_model
        windows = jax.lax.dynamic_slice_in_dim(
            windows_all, stage * (cfg.n_layers // n_stages),
            cfg.n_layers // n_stages)

        toks_m = tokens.reshape(n_micro, Bm, -1)
        lbls_m = labels.reshape(n_micro, Bm, -1)

        def tick(carry, t):
            # carry: (recv_buf (Bm,S,d), loss_sum, count_sum)
            recv, loss_sum, cnt_sum = carry
            # stage 0 ingests microbatch t (when in range)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x0 = T._embed_tokens({"embed": embed}, cfg,
                                 toks_m[mb_idx]).astype(recv.dtype)
            x_in = jnp.where(stage == 0, x0, recv)
            active = jnp.where(
                stage == 0,
                (t < n_micro),
                (t - stage >= 0) & (t - stage < n_micro),
            )
            x_out, _ = stage_fwd(layers_shard, cfg, x_in, windows)
            x_out = jnp.where(active, x_out, jnp.zeros_like(x_out))
            # last stage computes the loss for its current microbatch
            is_last = stage == n_stages - 1
            h = L.apply_norm(cfg.norm, {"g": final_norm_g}, x_out) \
                if cfg.norm == "rmsnorm" else x_out
            logits = h.astype(jnp.float32) @ embed.astype(jnp.float32).T
            lb = lbls_m[jnp.clip(t - (n_stages - 1), 0, n_micro - 1)]
            mask = (lb >= 0).astype(jnp.float32) * jnp.where(
                is_last & active, 1.0, 0.0)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(
                logits, jnp.clip(lb, 0)[..., None], axis=-1)[..., 0]
            loss_sum = loss_sum + jnp.sum((lse - tgt) * mask)
            cnt_sum = cnt_sum + jnp.sum(mask)
            # ship activations downstream (stage i -> i+1); ring closes
            # harmlessly (last->first arrivals are overwritten by x0)
            nxt = jax.lax.ppermute(
                x_out, "pod",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, loss_sum, cnt_sum), None

        recv0 = jnp.zeros((Bm, toks_m.shape[2], d), jnp.bfloat16
                          if cfg.dtype == "bfloat16" else jnp.float32)
        (recv, loss_sum, cnt_sum), _ = jax.lax.scan(
            tick, (recv0, jnp.zeros((), jnp.float32),
                   jnp.zeros((), jnp.float32)),
            jnp.arange(n_micro + n_stages - 1))
        # total loss lives on the last stage; share it
        axes = ("pod",) + ((data_axis,) if data_axis else ())
        loss_sum = jax.lax.psum(loss_sum, axes)
        cnt_sum = jax.lax.psum(cnt_sum, axes)
        return loss_sum / jnp.maximum(cnt_sum, 1.0)

    bspec = P(data_axis) if data_axis else P()

    def loss_fn(params, batch):
        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P("pod"), params["layers"]),
                P(), P(),
                bspec, bspec,
            ),
            out_specs=P(),
            check_vma=False,
        )(params["layers"], params["embed"],
          params["final_norm"]["g"] if "g" in params["final_norm"]
          else jnp.ones((cfg.d_model,)),
          batch["tokens"], batch["labels"])

    return loss_fn


def pipeline_param_specs(params, mesh):
    """Sharding specs for pipeline mode: layer stack over 'pod', the rest
    replicated (a production system would nest TP inside each stage)."""
    def spec(path, leaf):
        top = str(getattr(path[0], "key", ""))
        if top == "layers":
            return P("pod")
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)
