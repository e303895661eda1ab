"""A CPU rehearsal of a cell's run at smoke geometry, the faults the
check must catch, the lower-precision control, and the refusal to run
without a TPU."""

import json
import os
import subprocess
import sys
import time

import pytest

import _paths  # noqa: F401
from chipbench import harness, spec
from smoke_cells import CLOSED, OPEN, smoke_cell

# At smoke geometry on the CPU the program (jnp backend) matches the
# reference exactly (every number 0.0), and the W2 control reads
# class_mean_gap 1.03-1.41 (vgg16) and 0.52-0.63 (resnet18), answers_off
# 1.0, on seeds 11-13: the configuration files' limits are set from chip
# readings at full size, these from those CPU readings, for the same
# numbers.
SMOKE_LIMITS = {"vgg16_w4_fused": {"class_mean_gap": 0.3,
                                   "answers_off": 0.6},
                "resnet18_w4": {"class_mean_gap": 0.3, "answers_off": 0.6}}


def _run(name, traffic, seed, bits=None):
    cell = smoke_cell(name, traffic, bits)
    cell.conf["limits"] = SMOKE_LIMITS[name]
    run = harness.measure(cell, seed, 1.0, False, time.perf_counter())
    return run


@pytest.mark.parametrize("name,traffic", [
    ("vgg16_w4_fused", CLOSED), ("resnet18_w4", CLOSED),
    ("vgg16_w4_fused", OPEN)])
def test_rehearsal_is_correct(name, traffic):
    run = _run(name, traffic, 2**31 + 5)
    due = run.in_window()
    assert due and all(r.status == "ok" for r in due)
    assert run.compiles_in_window == 0
    checks = harness.check(run)
    assert harness.correct(checks), checks
    assert all(c["value"] == 0 for c in checks.values())  # jnp on the CPU


@pytest.mark.parametrize("name", ["vgg16_w4_fused", "resnet18_w4"])
def test_control_w2_is_not_correct(name):
    """The program's own W2 path in place of W4 fails the W4 check."""
    run = _run(name, CLOSED, 11, bits=2)
    checks = harness.check(run, bits=4)
    assert not harness.correct(checks), checks


@pytest.mark.parametrize("name", ["vgg16_w4_fused", "resnet18_w4"])
def test_altered_answers_are_not_correct(name, monkeypatch):
    """Every answer altered where the forward produces it: its class
    scores come back attributed to the next class."""
    import jax.numpy as jnp

    from repro.deploy import package

    apply = package.DeployedModel.apply
    monkeypatch.setattr(package.DeployedModel, "apply",
                        lambda self, x: jnp.roll(apply(self, x), 1, axis=1))
    run = _run(name, CLOSED, 12)
    assert not harness.correct(harness.check(run))


@pytest.mark.parametrize("name", ["vgg16_w4_fused", "resnet18_w4"])
def test_answers_given_to_other_requests_are_not_correct(name, monkeypatch):
    """Every answer of a rollout handed to the request in the next slot:
    the class means over the sample hardly move, the answers do."""
    import jax.numpy as jnp

    from repro.deploy import package

    apply = package.DeployedModel.apply
    monkeypatch.setattr(package.DeployedModel, "apply",
                        lambda self, x: jnp.roll(apply(self, x), 1, axis=0))
    checks = harness.check(_run(name, CLOSED, 15))
    assert not harness.correct(checks)
    off = checks["answers_off"]
    assert off["value"] > off["limit"], checks


def test_data_parallel_rehearsal_and_answers_sent_to_the_next_chip():
    """The four-chip cell's path on four CPU devices: correct as it is,
    not correct when each device's answers go to the next device's
    requests (the class means then read 0.05, the answers 1.0)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, str(spec.HERE / "tests" / "dp_rehearsal.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    sound, fault = [json.loads(line) for line in
                    proc.stdout.strip().splitlines()[-2:]]
    assert sound["devices"] == fault["devices"] == 4
    assert sound["correct"] and sound["answers_off"] == 0, sound
    assert not fault["correct"], fault
    assert fault["answers_off"] > SMOKE_LIMITS["vgg16_w4_fused"]["answers_off"]


def test_no_tpu_exits_nonzero_without_a_result():
    proc = subprocess.run(
        [sys.executable, str(spec.HERE / "bench.py"), "--workload",
         "vgg16_w4_fused.bulk", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        cwd=str(spec.ROOT),
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
