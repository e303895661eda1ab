"""Fusion groups as a first-class graph concept (ISSUE 8).

The contract under test:

  * legality matrix — ``validate_group``/``apply_fusion`` reject every
    illegal chain (residual-boundary crossing, projection members,
    strides, stems, non-contiguity, kernel-size and precision mixing,
    VMEM budget, ...)
    with an actionable error, and ``plan_fusion_groups`` only proposes
    groups that pass the same rules;
  * bit-exactness — the multi-layer fused_group kernel matches the
    per-layer reference chain, and a GROUPED graph's integer lowering
    (per-call and packaged, logits and rates) matches the UNGROUPED
    lowering bit for bit at every precision: fusion is a lowering
    strategy, never a numeric change;
  * artifact v2 — packages carry per-group operand bundles in the
    manifest, round-trip through npz, and v1 (pre-fusion) packages
    still load;
  * telemetry — a fused chain is recorded as one aggregate row at its
    boundary, with stats equal to the ungrouped last member's;
  * VMEM budget — over-budget chains degrade to the bit-exact reference
    path with a RuntimeWarning (ops) or raise (kernel), sharing one
    formula with the planner.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.deploy import deploy, load
from repro.deploy.package import PACKAGE_FORMAT_VERSION
from repro.graph import (
    apply_fusion,
    body_group,
    build_graph,
    group_vmem_bytes,
    plan_fusion_groups,
    validate_group,
)
from repro.graph.spec import Conv, FusionGroup, Residual
from repro.kernels import fused_conv_ops, fused_group_ops, use_backend
from repro.kernels import vmem as _vmem
from repro.models import snn_cnn
from repro.quant.formats import PrecisionConfig
from repro.quant.ptq import quantize_conv


def small_cfg(model="vgg9", bits=4, fusion=(), timesteps=2):
    return snn_cnn.SNNConfig(
        model=model, img_size=16, timesteps=timesteps, scale=0.15,
        n_classes=4, int_deploy=True, precision=PrecisionConfig(bits=bits),
        fusion=fusion)


def make_images(cfg, n=2, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.random(
        (n, cfg.img_size, cfg.img_size, cfg.in_channels)), jnp.float32)


def ungrouped_graph(model="vgg9", bits=4):
    return build_graph(small_cfg(model, bits))


# ---------------------------------------------------------------------------
# planner: auto proposals are legal and shaped as documented
# ---------------------------------------------------------------------------

def test_auto_plan_vgg9_one_top_level_chain():
    g = ungrouped_graph("vgg9")
    groups = plan_fusion_groups(g)
    assert [gr.members for gr in groups] == [
        ("convs.1", "pool.0", "convs.2", "convs.3", "pool.1",
         "convs.4", "pool.2")]
    for gr in groups:                      # every proposal re-validates
        validate_group(g, gr)


def test_auto_plan_resnet18_stride1_bodies_only():
    g = ungrouped_graph("resnet18")
    groups = plan_fusion_groups(g)
    # stride-1 blocks 0,1,3,5,7 fuse; strided entries (2,4,6) do not
    assert [gr.members for gr in groups] == [
        (f"blocks.{i}.conv1", f"blocks.{i}.conv2") for i in (0, 1, 3, 5, 7)]
    # each proposal is exactly one block's body, findable by body_group
    fused = apply_fusion(g, "auto")
    bodies = [body_group(fused, n) for n in fused.nodes
              if isinstance(n, Residual)]
    assert [b.members for b in bodies if b is not None] \
        == [gr.members for gr in groups]


def test_auto_plan_respects_budget(monkeypatch):
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "1024")     # nothing fits
    assert plan_fusion_groups(ungrouped_graph("vgg9")) == ()


def test_build_graph_applies_cfg_fusion():
    g = build_graph(small_cfg("vgg9", fusion="auto"))
    assert g.groups and g.groups[0].members[0] == "convs.1"
    # () request is inert: identical graph, identical topology
    g0 = ungrouped_graph("vgg9")
    assert g0.groups == ()
    assert apply_fusion(g0, ()) is g0


def test_topology_fingerprint_extends_not_rewrites():
    g0 = ungrouped_graph("vgg9")
    g1 = apply_fusion(g0, "auto")
    t0, t1 = g0.topology(), g1.topology()
    assert t1[:len(t0)] == t0              # node rows untouched
    assert t1 != t0                        # grouped graphs never alias
    assert t1[len(t0):][0][:2] == ("fusion", g1.groups[0].name)


def test_summary_reports_membership_and_vmem():
    g = apply_fusion(ungrouped_graph("vgg9"), "auto")
    s = g.summary()
    assert "[fuse.0]" in s
    assert "VMEM" in s and "fusion fuse.0:" in s
    est = group_vmem_bytes(g, g.groups[0])
    assert 0 < est <= _vmem.vmem_budget_bytes()


# ---------------------------------------------------------------------------
# legality matrix: every illegal chain is named and explained
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("members,match", [
    (("convs.1",), "fuses 2\\+ layers"),
    (("convs.1", "convs.1"), "repeats a member"),
    (("convs.1", "nope"), "not a layer of this graph"),
    (("convs.0", "convs.1"), "stem"),
    (("pool.0", "convs.2"), "starts at pool"),
    (("convs.2", "convs.4"), "not contiguous"),
    (("convs.1", "fc1"), "only conv/pool chains fuse"),
])
def test_illegal_vgg9_groups(members, match):
    g = ungrouped_graph("vgg9")
    with pytest.raises(ValueError, match=match):
        validate_group(g, FusionGroup("bad", members))


@pytest.mark.parametrize("members,match", [
    # chains cannot cross a residual boundary: the shortcut reads the
    # pre-body plane the chain would keep in VMEM
    (("blocks.0.conv2", "blocks.1.conv1"), "crosses a residual boundary"),
    (("blocks.0.conv1", "blocks.0.conv2", "blocks.1.conv1"),
     "crosses a residual boundary"),
    # a projection shortcut runs in parallel with the body
    (("blocks.2.conv1", "blocks.2.proj"), "PARALLEL"),
    # strided entry re-shapes the plane mid-chain
    (("blocks.2.conv1", "blocks.2.conv2"), "stride 2"),
    # a body group must cover the body in execution order
    (("blocks.0.conv2", "blocks.0.conv1"), "full body in order"),
])
def test_illegal_resnet18_groups(members, match):
    g = ungrouped_graph("resnet18")
    with pytest.raises(ValueError, match=match):
        validate_group(g, FusionGroup("bad", members))


def _with_k(graph, name, k):
    """``graph`` with conv ``name`` swapped for a k x k kernel."""
    nodes = tuple(dataclasses.replace(n, k=k) if n.name == name else n
                  for n in graph.nodes)
    return dataclasses.replace(graph, nodes=nodes)


def test_mixed_kernel_size_group_rejected_and_not_planned():
    # the fused kernel lays every member out on one padded grid: a
    # stride-1 1x1 conv may not join a 3x3 chain
    g = _with_k(ungrouped_graph("vgg9"), "convs.3", 1)
    with pytest.raises(ValueError, match="mixes kernel sizes"):
        validate_group(g, FusionGroup("bad", ("convs.2", "convs.3")))
    # ...and the planner stops the chain there instead of proposing it
    for gr in plan_fusion_groups(g):
        ks = {n.k for n in g.nodes if n.name in gr.members
              and isinstance(n, Conv)}
        assert len(ks) == 1, gr.members


def test_precision_mixed_group_rejected():
    g = ungrouped_graph("vgg9", bits=4)
    with pytest.raises(ValueError, match="precision-mixed"):
        validate_group(g, FusionGroup("bad", ("convs.2", "convs.3"), bits=2))
    # the matching pin is fine
    validate_group(g, FusionGroup("ok", ("convs.2", "convs.3"), bits=4))


def test_over_budget_group_rejected(monkeypatch):
    g = ungrouped_graph("vgg9")
    grp = FusionGroup("big", ("convs.2", "convs.3"))
    validate_group(g, grp)                  # fits the real budget
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "4096")
    with pytest.raises(ValueError, match="VMEM"):
        validate_group(g, grp)


def test_apply_fusion_rejects_overlap_and_unknown_request():
    g = ungrouped_graph("vgg9")
    with pytest.raises(ValueError, match="disjoint"):
        apply_fusion(g, (("convs.2", "convs.3"), ("convs.3", "pool.1")))
    with pytest.raises(ValueError, match="unknown fusion request"):
        apply_fusion(g, "magic")


# ---------------------------------------------------------------------------
# kernel level: chain contract + bit-exactness vs the per-layer reference
# ---------------------------------------------------------------------------

def _conv_member(key, c_in, c_out, bits, k=3, theta=48):
    w = jax.random.normal(key, (k, k, c_in, c_out), jnp.float32)
    return ("conv", quantize_conv(w, PrecisionConfig(bits=bits)), theta)


def _spikes(key, t, b, h, w, c, p=0.25):
    sp = (jax.random.uniform(key, (t, b, h, w, c)) < p).astype(jnp.int32)
    return packing.pack_bool(sp)


def test_ops_chain_contract_errors():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    sp = _spikes(ks[0], 2, 1, 8, 8, 32)
    m32_16 = _conv_member(ks[1], 32, 16, 4)
    m32_8 = _conv_member(ks[3], 32, 8, 4)
    roll = fused_group_ops.fused_group_rollout
    with pytest.raises(ValueError, match="2\\+ members"):
        roll(sp, (m32_16,), leak_shift=3)
    with pytest.raises(ValueError, match="start at a conv"):
        roll(sp, (("pool", 2), m32_16), leak_shift=3)
    with pytest.raises(ValueError, match="thread channels"):
        roll(sp, (m32_16, m32_8), leak_shift=3)        # 16 -> wants 32
    with pytest.raises(ValueError, match="ONE datapath width"):
        roll(sp, (m32_16, _conv_member(ks[2], 16, 16, 2)), leak_shift=3)
    with pytest.raises(ValueError, match="does not divide"):
        roll(sp, (m32_16, ("pool", 3)), leak_shift=3)  # 8x8 plane
    with pytest.raises(ValueError, match="unknown group member kind"):
        roll(sp, (m32_16, ("dense", 4)), leak_shift=3)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("soft_reset", [True, False])
def test_group_kernel_bitexact_vs_reference(bits, soft_reset):
    """conv -> pool -> conv chains, non-multiple-of-32 channels: the
    one-pallas_call rollout matches the per-layer fused_conv chain."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    members = (_conv_member(ks[0], 32, 48, bits),
               ("pool", 2),
               _conv_member(ks[1], 48, 24, bits))
    sp = _spikes(ks[2], 3, 2, 8, 8, 32)
    with use_backend("jnp"):
        v_ref, o_ref = fused_group_ops.fused_group_rollout(
            sp, members, leak_shift=3, soft_reset=soft_reset)
    with use_backend("interpret"):
        v_k, o_k = fused_group_ops.fused_group_rollout(
            sp, members, leak_shift=3, soft_reset=soft_reset)
    np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_ref))


def test_group_kernel_t0_degenerate():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    members = (_conv_member(ks[0], 32, 16, 4),
               _conv_member(ks[1], 16, 16, 4))
    sp = _spikes(ks[2], 1, 2, 4, 4, 32)[:0]      # T = 0
    with use_backend("interpret"):
        v, o = fused_group_ops.fused_group_rollout(sp, members, leak_shift=3)
    assert v.shape == (2, 4, 4, 16) and o.shape == (0, 2, 4, 4, 1)


def test_group_over_budget_falls_back_bit_exact(monkeypatch):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    members = (_conv_member(ks[0], 32, 32, 4),
               _conv_member(ks[1], 32, 32, 4))
    sp = _spikes(ks[2], 2, 1, 8, 8, 32)
    with use_backend("jnp"):
        v_ref, o_ref = fused_group_ops.fused_group_rollout(
            sp, members, leak_shift=3)
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "4096")
    with use_backend("interpret"):
        with pytest.warns(RuntimeWarning, match="falling back"):
            v, o = fused_group_ops.fused_group_rollout(
                sp, members, leak_shift=3)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))


def test_fused_conv_over_budget_falls_back_bit_exact(monkeypatch):
    """Satellite: the single-layer kernel's implicit VMEM assumption is
    now an explicit check — ops degrade with a warning, the kernel
    entry raises."""
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    (_, qct, theta) = _conv_member(ks[0], 32, 32, 4)
    sp = _spikes(ks[1], 2, 1, 8, 8, 32)
    with use_backend("jnp"):
        v_ref, o_ref = fused_conv_ops.fused_conv_rollout(
            sp, qct, leak_shift=3, threshold_q=theta)
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "4096")
    with use_backend("interpret"):
        with pytest.warns(RuntimeWarning, match="falling back"):
            v, o = fused_conv_ops.fused_conv_rollout(
                sp, qct, leak_shift=3, threshold_q=theta)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))


def test_group_kernel_entry_raises_over_budget(monkeypatch):
    """Calling the pallas entry directly with oversized geometry is a
    loud error, never a spilling kernel (ops.py is the fallback site)."""
    from repro.kernels.fused_group import kernel as gk

    monkeypatch.setenv("REPRO_VMEM_BUDGET", "4096")
    w = jnp.zeros((32, 9 * 32 * 4 // 32), jnp.int32)
    th = jnp.full((1, 32), 48, jnp.int32)
    with pytest.raises(ValueError, match="VMEM budget"):
        gk.fused_group_rollout_pallas(
            jnp.zeros((2, 1, 8, 8), jnp.int32), w, th, w, th,
            geoms=(("conv", 4, 3, 32, 8, 8, 32, 32),
                   ("conv", 4, 3, 32, 8, 8, 32, 32)),
            leak_shift=3, interpret=True)


# ---------------------------------------------------------------------------
# executor parity: grouped lowering is bit-exact with ungrouped
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["vgg9", "resnet18"])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_grouped_lowering_bit_exact(model, bits):
    """The acceptance criterion: per-call and packaged grouped forwards
    (logits AND rates) match the ungrouped lowering exactly."""
    cfg0 = small_cfg(model, bits)
    cfg1 = small_cfg(model, bits, fusion="auto")
    assert build_graph(cfg1).groups        # fusion actually engaged
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg0)
    images = make_images(cfg0)

    logits0, rates0 = snn_cnn.apply_with_rates(params, cfg0, images)
    logits1, rates1 = snn_cnn.apply_with_rates(params, cfg1, images)
    np.testing.assert_array_equal(np.asarray(logits1), np.asarray(logits0))
    assert len(rates0) == len(rates1)
    np.testing.assert_array_equal(np.asarray(rates1), np.asarray(rates0))

    pkg = deploy(params, cfg1)
    np.testing.assert_array_equal(
        np.asarray(pkg.apply(images)), np.asarray(logits0))


def test_grouped_trace_identical_to_ungrouped():
    """Executor-parity contract: fusion changes the kernel plan, not the
    traversal the trace records."""
    from repro.graph import IntExecutor, run_graph

    cfg0, cfg1 = small_cfg("vgg9", 4), small_cfg("vgg9", 4, fusion="auto")
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg0)
    images = make_images(cfg0, n=1)
    ex0 = IntExecutor(build_graph(cfg0), params)
    run_graph(build_graph(cfg0), ex0, images)
    ex1 = IntExecutor(build_graph(cfg1), params)
    run_graph(build_graph(cfg1), ex1, images)
    assert ex0.trace == ex1.trace


# ---------------------------------------------------------------------------
# deploy artifact: v2 group bundles + v1 backward compatibility
# ---------------------------------------------------------------------------

def _manifest_of(path):
    with np.load(path, allow_pickle=False) as z:
        return json.loads(str(z["__manifest__"][()]))


def test_package_v2_roundtrip_with_groups(tmp_path):
    cfg = small_cfg("vgg9", 4, fusion="auto")
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    model = deploy(params, cfg)
    path = model.save(str(tmp_path / "m.npz"))

    man = _manifest_of(path)
    assert man["version"] == PACKAGE_FORMAT_VERSION == 2
    (bundle,) = man["groups"]
    assert bundle["members"][0] == "convs.1"
    assert bundle["bits"] == 4
    assert bundle["vmem_bytes"] > 0
    # bundle bytes = the packed payload of its conv members
    assert bundle["packed_bytes"] == sum(
        model.layers[m].nbytes_packed()
        for m in bundle["members"] if m in model.layers)

    loaded = load(path)
    assert loaded.cfg.fusion == "auto"
    assert build_graph(loaded.cfg).groups
    images = make_images(cfg)
    np.testing.assert_array_equal(np.asarray(loaded.apply(images)),
                                  np.asarray(model.apply(images)))


def test_package_v1_still_loads(tmp_path):
    """A pre-fusion artifact (version 1, no groups section, no cfg.fusion
    key) loads and lowers layer by layer."""
    cfg = small_cfg("vgg9", 4)
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg)
    model = deploy(params, cfg)
    path = model.save(str(tmp_path / "m.npz"))

    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    man = json.loads(str(arrays["__manifest__"][()]))
    man["version"] = 1
    del man["groups"]
    del man["cfg"]["fusion"]
    arrays["__manifest__"] = np.array(json.dumps(man))
    v1_path = str(tmp_path / "m_v1.npz")
    with open(v1_path, "wb") as f:
        np.savez(f, **arrays)

    loaded = load(v1_path)
    assert loaded.cfg.fusion == ()
    assert build_graph(loaded.cfg).groups == ()
    images = make_images(cfg)
    np.testing.assert_array_equal(np.asarray(loaded.apply(images)),
                                  np.asarray(model.apply(images)))


# ---------------------------------------------------------------------------
# telemetry: group boundaries recorded as aggregates, stats preserved
# ---------------------------------------------------------------------------

def test_telemetry_group_boundary_aggregate():
    from repro.obs import MetricsRegistry
    from repro.obs.telemetry import instrumented_forward

    cfg0 = small_cfg("vgg9", 4)
    cfg1 = small_cfg("vgg9", 4, fusion=(("convs.2", "convs.3"),))
    params = snn_cnn.init(jax.random.PRNGKey(0), cfg0)
    images = make_images(cfg0, n=1)

    logits0, rec0 = instrumented_forward(cfg0, params, images,
                                         registry=MetricsRegistry())
    logits1, rec1 = instrumented_forward(cfg1, params, images,
                                         registry=MetricsRegistry())
    np.testing.assert_array_equal(np.asarray(logits1), np.asarray(logits0))

    by0 = {(r["node"], r["layer"]): r for r in rec0}
    by1 = {(r["node"], r["layer"]): r for r in rec1}
    # interior members coarsen into ONE aggregate row at the boundary...
    assert ("conv", "convs.2") not in by1
    assert ("conv", "convs.3") not in by1
    agg = by1[("fusion_group", "fuse.0")]
    # ...whose spike stats equal the ungrouped chain-final layer's
    last = by0[("conv", "convs.3")]
    for key in ("rate", "saturation", "silent", "resets"):
        assert agg[key] == last[key], key
    # layers outside the group are recorded identically
    for k in by0:
        if k not in (("conv", "convs.2"), ("conv", "convs.3")):
            assert k in by1
