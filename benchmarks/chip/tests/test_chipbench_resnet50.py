"""The resnet50_w4 configuration: the benchmark's layer table and op
counts against the program's graph, a CPU rehearsal of its cell at smoke
geometry against the plain reference (with a fault the check must
catch), and the ``unfused_share`` reader on the program's lowering
records."""

import time
import warnings

import pytest

import _paths  # noqa: F401
from chipbench import harness, ops, spec
from smoke_cells import CLOSED, smoke_cell, smoke_conf

# as test_chipbench_run.py sets them for the other configurations: the
# program (jnp backend) matches the reference exactly at smoke geometry
SMOKE_LIMITS = {"class_mean_gap": 0.3, "answers_off": 0.6}


def _conf():
    return spec.load_json(spec.HERE / "configs" / "resnet50_w4.json")


def test_macs_and_packed_bytes():
    """4.09 G MACs a timestep: 3.969 G in the 52 packed convs, 0.118 G in
    the 7x7 stem, 2.05 M in the head; 11.93 MB of packed W4 weights."""
    from repro.deploy import deploy_config
    from repro.graph import build_graph

    conf = _conf()
    layers = ops.layers(conf)
    packed = [l for l in layers if l.packed]
    assert len(packed) == 52 and all(l.kind == "conv" for l in packed)
    assert sum(l.macs for l in packed) == 3_969_122_304
    assert layers[0].name == "stem" and layers[0].macs == 118_013_952
    assert layers[-1].kind == "readout" and layers[-1].macs == 2_048_000
    assert ops.macs_per_image(conf) == 4 * 4_089_184_256
    cfg = deploy_config("resnet50", bits=4, smoke=False)
    assert ops.macs_per_image(conf) == build_graph(cfg).count_macs()
    assert sum(l.weight_bytes(conf["bits"]) for l in packed) == 11_934_720


@pytest.mark.parametrize("smoke", [True, False])
def test_layer_table_matches_program(smoke):
    conf = smoke_conf("resnet50_w4") if smoke else _conf()
    harness.program_config(conf)           # raises on any difference


@pytest.fixture(scope="module")
def smoke_run():
    cell = smoke_cell("resnet50_w4", CLOSED)
    cell.conf["limits"] = SMOKE_LIMITS
    return harness.measure(cell, 2**31 + 7, 1.0, False, time.perf_counter())


def test_rehearsal_is_correct(smoke_run):
    due = smoke_run.in_window()
    assert due and all(r.status == "ok" for r in due)
    assert smoke_run.compiles_in_window == 0
    checks = harness.check(smoke_run)
    assert harness.correct(checks), checks
    assert all(c["value"] == 0 for c in checks.values())  # jnp on the CPU


def test_answers_given_to_other_requests_are_not_correct(smoke_run):
    """Each answer handed to the next request of the window."""
    done = [r for r in smoke_run.records if r.status == "ok"]
    logits = [r.logits for r in done]
    for r, other in zip(done, logits[1:] + logits[:1]):
        r.logits = other
    try:
        checks = harness.check(smoke_run)
    finally:
        for r, mine in zip(done, logits):
            r.logits = mine
    assert not harness.correct(checks)
    assert checks["answers_off"]["value"] > SMOKE_LIMITS["answers_off"]


class _Run:
    def __init__(self, conf):
        self.cell = spec.Cell(name="x", chips=1, conf=conf, traffic={},
                              end_to_end=[], per_layer=[])
        self.notes = []


@pytest.fixture
def registry(monkeypatch):
    from repro import obs

    reg = obs.MetricsRegistry(enabled=False)
    monkeypatch.setattr(obs.registry, "_DEFAULT", reg)
    return reg


def test_unfused_share_reads_the_lowering_records(registry):
    read = spec.reader("unfused_share")
    conf = smoke_conf("resnet50_w4")
    assert read(_Run(conf)) is None                   # nothing recorded
    convs = [l for l in ops.layers(conf) if l.packed]
    for l in convs:
        registry.lowered(l.name, "fused")
    run = _Run(conf)
    assert read(run) == 0.0
    assert run.notes == ["lowering of the packed convs: fused 52"]
    registry.lowered(convs[-1].name, "unfused")
    share = 100.0 * convs[-1].macs / sum(l.macs for l in convs)
    assert read(_Run(conf)) == pytest.approx(share)


def test_unfused_share_of_a_traced_smoke_forward(registry, monkeypatch):
    """The records the program writes when it traces the served forward:
    with a VMEM budget that no untiled wide conv fits, every packed conv
    still runs a Pallas kernel, tiled or not."""
    import jax
    import jax.numpy as jnp

    from repro.deploy import deploy
    from repro.kernels import use_backend
    from repro.models import snn_cnn

    conf = smoke_conf("resnet50_w4")
    cfg = harness.program_config(conf)
    pkg = jax.eval_shape(lambda k: deploy(snn_cnn.init(k, cfg), cfg),
                         jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, 16, 16, 3), jnp.float32)
    monkeypatch.setenv("REPRO_VMEM_BUDGET", str(1100 * 1024))
    with use_backend("interpret"), warnings.catch_warnings():
        warnings.simplefilter("error")
        jax.eval_shape(lambda p, x: p.apply(x), pkg, x)
    run = _Run(conf)
    assert spec.reader("unfused_share")(run) == 0.0
    paths = {r.path for r in registry.lowerings()}
    assert paths == {"fused", "fused_tiled"}, run.notes
    assert all(r.vmem_bytes <= 1100 * 1024 for r in registry.lowerings())
