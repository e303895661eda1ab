"""Distribution tests that need >1 device: run in subprocesses with
--xla_force_host_platform_device_count so the sharding logic is exercised
for real (shard_map collectives, elastic restore across mesh shapes,
pjit'd train step)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_py(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_ef_int8_allreduce_shard_map():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import ef_int8_allreduce_mean

        mesh = make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
        e = jnp.zeros((8, 128))

        @jax.jit
        def step(g, e):
            f = jax.shard_map(
                lambda gi, ei: ef_int8_allreduce_mean(gi[0], ei[0], "data"),
                mesh=mesh, in_specs=(P("data"), P("data")),
                out_specs=P(None), check_vma=False)
            return f(g, e)

        out, _ = step(g, e)
        true = jnp.mean(g, axis=0)
        err = float(jnp.max(jnp.abs(out - true)))
        assert err < 0.15, err
        print("wire-error", err)
    """))


def test_elastic_restore_across_meshes(tmp_path):
    print(run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.checkpoint import CheckpointManager

        # save from a (4,2) mesh
        mesh_a = make_mesh((4, 2), ("data", "model"))
        sh_a = NamedSharding(mesh_a, P("data", "model"))
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8), sh_a)
        cm = CheckpointManager({str(tmp_path)!r})
        cm.save(1, {{"w": w}})

        # restore onto a (2,4) mesh — elastic resharding on load
        mesh_b = make_mesh((2, 4), ("data", "model"))
        sh_b = NamedSharding(mesh_b, P("data", "model"))
        out = cm.restore(1, {{"w": jax.eval_shape(lambda: w)}},
                         shardings={{"w": sh_b}})
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(64.0).reshape(8, 8))
        assert out["w"].sharding.is_equivalent_to(sh_b, 2)
        print("elastic restore OK")
    """))


def test_pjit_train_step_on_mesh():
    """A smoke train step pjit'd onto a 4x2 mesh with the production
    sharding rules — the single-host analogue of the pod dry-run."""
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch import specs as S
        from repro.launch.steps import make_train_step
        from repro.models.api import get_model
        from repro.train import optimizer as opt

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("olmo-1b", smoke=True)
        mb = get_model(cfg)
        params = mb.init(jax.random.PRNGKey(0))
        pspecs = shd.param_specs(params, mesh)
        pshard = shd.to_shardings(pspecs, mesh)
        params = jax.device_put(params, pshard)
        ostate = opt.init(params)
        batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
                 "labels": jnp.ones((8, 32), jnp.int32)}
        bshard = {k: NamedSharding(mesh, P("data", None)) for k in batch}
        batch = jax.device_put(batch, bshard)
        step = jax.jit(make_train_step(cfg, opt.OptConfig()),
                       donate_argnums=(0, 1))
        with mesh:
            params, ostate, m = step(params, ostate, batch)
            params, ostate, m = step(params, ostate, batch)
        assert np.isfinite(float(m["loss"]))
        print("pjit train step OK, loss", float(m["loss"]))
    """))


def test_dryrun_single_cell_quick():
    """End-to-end dry-run machinery on a tiny mesh cell (the real 16x16
    sweep is exercised by benchmarks; this guards the plumbing)."""
    print(run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.dryrun import run_cell
        rec = run_cell("whisper-base", "train_4k")
        assert rec["ok"], rec.get("error")
        assert rec["collective_bytes"]["total"] > 0
        print("dryrun cell OK", rec["flops_per_device"])
    """, devices=512, timeout=560))


def test_pipeline_parallel_matches_single_device():
    """GPipe over a 2-stage pod axis: pipeline loss == plain loss, and
    gradients land on the owning stage."""
    print(run_py("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs.base import ArchConfig
        from repro.distributed.pipeline import (
            make_pipeline_loss, pipeline_param_specs)
        from repro.distributed import sharding as shd
        from repro.models import transformer as T

        cfg = ArchConfig(
            name="pp-test", family="dense", n_layers=4, d_model=32,
            n_heads=4, n_kv=4, d_ff=64, vocab=128, norm="rmsnorm",
            dtype="float32")
        params = T.init(jax.random.PRNGKey(0), cfg)
        B, S = 8, 16
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 128)
        batch = {"tokens": toks, "labels": toks}

        ref = float(T.loss_fn(params, cfg, batch))

        mesh = make_mesh((2, 4), ("pod", "data"))
        pp_loss = make_pipeline_loss(cfg, mesh, n_micro=4)
        specs = pipeline_param_specs(params, mesh)
        sharded = jax.device_put(params, shd.to_shardings(specs, mesh))
        with mesh:
            out = float(jax.jit(pp_loss)(sharded, batch))
            g = jax.jit(jax.grad(lambda p, b: pp_loss(p, b)))(sharded, batch)
        print("plain", ref, "pipeline", out)
        assert abs(out - ref) / ref < 2e-3, (out, ref)
        gn = float(sum(jnp.sum(x.astype(jnp.float32)**2)
                       for x in jax.tree.leaves(g)) ** 0.5)
        assert np.isfinite(gn) and gn > 0
        print("pipeline OK, gradnorm", gn)
    """, devices=8))


def test_attention_cp_preserves_loss():
    """The context-parallel attention constraint is semantics-preserving:
    same loss with and without the hint on a (data=2, model=4) mesh."""
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import layers as Ly
        from repro.models import transformer as T

        cfg = get_config("hymba-1.5b", smoke=True)
        params = T.init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                  cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        ref = float(T.loss_fn(params, cfg, batch))

        mesh = make_mesh((2, 4), ("data", "model"))
        sh = NamedSharding(mesh, P("data", "model", None, None, None, None))
        Ly.set_attention_cp(
            hint=lambda x: jax.lax.with_sharding_constraint(x, sh),
            q_chunk=16, kv_chunk=16)
        try:
            with mesh:
                # force the chunked path so the constraint actually applies
                out = float(jax.jit(
                    lambda p, b: T.loss_fn(p, cfg, b))(params, batch))
        finally:
            Ly.set_attention_cp()
        print("ref", ref, "cp", out)
        assert abs(out - ref) / abs(ref) < 5e-3, (ref, out)
        print("attention-CP preserves loss")
    """, devices=8))


def test_data_parallel_snn_engine_matches_single_device():
    """The serve engine's shard_map data-parallel path on 4 devices:
    buckets resolve to device multiples, every device computes its own
    slice of a bucket, and the logits are bit-exact with one device."""
    print(run_py("""
        import time
        import jax, numpy as np
        from repro.deploy import (SNNEngineConfig, SNNRequest,
                                  SNNServeEngine, deploy, deploy_config)
        from repro.models import snn_cnn

        cfg = deploy_config("vgg9", bits=4, smoke=True)
        pkg = deploy(snn_cnn.init(jax.random.PRNGKey(0), cfg), cfg)
        imgs = np.random.default_rng(0).random(
            (6, cfg.img_size, cfg.img_size, cfg.in_channels), np.float32)

        def serve(ecfg):
            eng = SNNServeEngine(pkg, ecfg)
            for i, im in enumerate(imgs):
                eng.add_request(SNNRequest(uid=i, image=im))
            eng.run_until_done()
            return eng, np.stack([eng.pop_result(i).logits
                                  for i in range(len(imgs))])

        eng4, dp = serve(SNNEngineConfig(max_batch=8, data_parallel=True))
        assert eng4.buckets == (4, 8), eng4.buckets
        _, one = serve(SNNEngineConfig(max_batch=8, buckets=eng4.buckets))
        np.testing.assert_array_equal(dp, one)

        req = SNNRequest(uid=99, image=imgs[0])
        req.queue_s, req._t0 = 0.0, time.perf_counter()
        n_compiled = eng4.compile_count
        st = eng4.begin_step([req], bucket=8)
        shards = st.logits.addressable_shards
        assert len({s.device for s in shards}) == 4, shards
        assert all(s.data.shape[0] == 2 for s in shards), shards
        eng4.finish_step(st)
        assert eng4.compile_count == n_compiled
        print("data-parallel engine OK")
    """, devices=4))
