"""The ``program_span`` metrics that read the serving worker's phase
spans: each reads a number from a smoke run, reads nothing from a
program without a phase ring, from an empty ring or from a ring that
lost part of the window, and the phases' annotations reach the trace
reduction's host spans."""

import re
import time
import types

import pytest

import _paths  # noqa: F401
from chipbench import harness, spec, tracered
from smoke_cells import CLOSED, OPEN, smoke_cell

BULK = ["host_ms_per_forward.bulk", "fill_ms", "dispatch_ms", "resolve_ms",
        "compile_s"]
KNEE = ["host_ms_per_forward.knee", "stall_ms.knee", "compile_s"]
ALL = sorted(set(BULK + KNEE))


def _smoke_run(traffic):
    run = harness.measure(smoke_cell("vgg16_w4_fused", traffic), 2**31 + 9,
                          1.0, False, time.perf_counter())
    # a 1 s smoke window has no untraced part before a trace
    run.untraced = run.window
    return run


@pytest.mark.parametrize("traffic,names", [(CLOSED, BULK), (OPEN, KNEE)],
                         ids=["closed", "open"])
def test_readers_read_a_smoke_run(traffic, names):
    run = _smoke_run(traffic)
    got = {n: spec.reader(n)(run) for n in names}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    host = got["host_ms_per_forward." + ("bulk" if traffic is CLOSED
                                         else "knee")]
    if traffic is CLOSED:
        assert host > got["fill_ms"] + got["dispatch_ms"] + got["resolve_ms"]
    else:
        assert got["stall_ms.knee"] > 0
        assert any(n.startswith("stall_ms: longest worker span")
                   for n in run.notes)
    cover = [float(m.group(1)) for n in run.notes
             if (m := re.match(r"worker phase spans cover (\S+) %", n))]
    assert len(cover) == 1 and 0 < cover[0] <= 101, run.notes


def _registry(kind, monkeypatch):
    from repro.obs import MetricsRegistry, registry

    if kind == "no ring":                 # a program without phase spans
        return types.SimpleNamespace()
    monkeypatch.setattr(registry, "PHASE_RING", 16)
    reg = MetricsRegistry(enabled=False)
    if kind == "lost":
        # set-up compiles at 6 s, a window of [10, 20) s whose first
        # forwards no longer fit the ring
        reg.phase("compile", 6.0, 7.0, bucket=4)
        for c in range(5):
            for i, p in enumerate(("take", "fill", "dispatch", "wait",
                                   "readback", "resolve")):
                t = 10.0 + c + 0.1 * i
                reg.phase(p, t, t + 0.1, cohort=c, bucket=4, n=4)
        spans, complete = reg.phases(10.0, 20.0)
        assert spans and not complete
    return reg


@pytest.mark.parametrize("kind", ["no ring", "empty", "lost"])
def test_readers_read_nothing_without_the_whole_window(monkeypatch, kind):
    from repro.obs import registry

    monkeypatch.setattr(registry, "_DEFAULT", _registry(kind, monkeypatch))
    run = types.SimpleNamespace(window=(10.0, 20.0), untraced=(10.0, 20.0),
                                setup_s=5.0, notes=[])
    assert {n: spec.reader(n)(run) for n in ALL} == dict.fromkeys(ALL)


def test_a_span_open_at_the_traces_start_is_left_out(monkeypatch):
    """The profiler's start stalls the process: a span still open then
    is not the program's, so the readers leave it out."""
    from repro.obs import MetricsRegistry, registry

    reg = MetricsRegistry(enabled=False)
    for i, p in enumerate(("take", "fill", "dispatch", "wait", "readback",
                           "resolve")):
        reg.phase(p, 10.0 + 0.001 * i, 10.001 + 0.001 * i, cohort=0)
    reg.phase("readback", 19.99, 20.04, cohort=1)    # open at 20.0
    monkeypatch.setattr(registry, "_DEFAULT", reg)
    run = types.SimpleNamespace(window=(10.0, 24.0), untraced=(10.0, 20.0),
                                setup_s=5.0, notes=[])
    assert spec.reader("stall_ms.knee")(run) == pytest.approx(1.0)
    assert spec.reader("host_ms_per_forward.knee")(run) == \
        pytest.approx(4.0)


def test_stall_leaves_out_idle_takes(monkeypatch):
    """A take that yielded no cohort is the worker waiting for
    arrivals, however long the generator paused; a take that fed a
    cohort still counts."""
    from repro.obs import MetricsRegistry, registry

    reg = MetricsRegistry(enabled=False)
    reg.phase("take", 10.0, 10.05)                   # idle: 50 ms
    reg.phase("take", 10.05, 10.058, cohort=0, bucket=4, n=3)
    for i, p in enumerate(("fill", "dispatch", "wait", "readback",
                           "resolve")):
        reg.phase(p, 10.06 + 0.001 * i, 10.061 + 0.001 * i, cohort=0)
    monkeypatch.setattr(registry, "_DEFAULT", reg)
    run = types.SimpleNamespace(window=(10.0, 24.0), untraced=(10.0, 20.0),
                                setup_s=5.0, notes=[])
    assert spec.reader("stall_ms.knee")(run) == pytest.approx(8.0)
    assert run.notes[-1].startswith(
        "stall_ms: longest worker span take, cohort 0, bucket 4 (3 rows)")


def test_phase_annotations_name_the_trace_reductions_gaps():
    """Every phase's annotation is a host span the reduction keeps, and
    an idle gap on the device is named by the phase open at its
    middle."""
    from repro.deploy.engine import annotation
    from repro.obs import PHASES

    names = {p: annotation(p, 64) for p in PHASES}
    assert all(n.startswith(tracered.HOST_SPANS) for n in names.values())
    host = [[tracered.WINDOW_SPAN, 0, 1000], [names["take"], 0, 100],
            [names["fill"], 100, 100], [names["dispatch"], 200, 100],
            [names["wait"], 300, 600], [names["resolve"], 900, 100]]
    ops = [["fusion", False, 250, 400], ["fusion.1", False, 700, 100]]
    data = {"devices": [{"name": "/device:TPU:0", "ops": ops, "steps": []}],
            "host": host}
    gaps = tracered.reduce(data)["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == [names["fill"], names["resolve"],
                                    names["wait"]]
