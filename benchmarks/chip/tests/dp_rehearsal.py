"""Run in a process of its own with four CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``): the
data-parallel cell's run loop at smoke geometry, sound and with each
chip's answers handed to the next chip's requests.  Prints one JSON line
per case: whether the check came out correct, and its numbers."""

import json
import sys
import time

import _paths  # noqa: F401
from chipbench import harness
from smoke_cells import smoke_cell

DP = {"loop": "closed", "clients": 16, "max_batch": 8, "buckets": [8],
      "data_parallel": True, "warm_s": 0.3}
LIMITS = {"class_mean_gap": 0.3, "answers_off": 0.6}


def case(fault: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.deploy import package

    apply = package.DeployedModel.apply
    if fault:
        # inside the shard_map each device sees its own rows: shift them
        # to the next device
        def shifted(self, x):
            y = apply(self, x)
            return jax.lax.ppermute(
                y, "data", [(i, (i + 1) % 4) for i in range(4)])
        package.DeployedModel.apply = shifted
    try:
        cell = smoke_cell("vgg16_w4_fused", DP)
        cell.chips = 4
        cell.conf["limits"] = LIMITS
        run = harness.measure(cell, 7, 1.0, False, time.perf_counter())
    finally:
        package.DeployedModel.apply = apply
    checks = harness.check(run)
    return {"fault": fault, "devices": len(jax.devices()),
            "correct": harness.correct(checks),
            **{k: v["value"] for k, v in checks.items()}}


if __name__ == "__main__":
    for fault in (False, True):
        print(json.dumps(case(fault)), flush=True)
    sys.exit(0)
