"""The reduction from trace to device numbers: busy time as a union,
the Pallas / glue split inside the forwards, idle gaps attributed to
the host span open at the time."""

import json
from pathlib import Path

import pytest

import _paths  # noqa: F401
from chipbench import tracered

FIXTURE = Path(__file__).resolve().parent / "fixtures"


def test_op_names_from_hlo_text():
    text = ('%fused_group_rollout_pallas.6 = (s32[64,512,24]) custom-call('
            's32[4,64,16,42] %copy.62), custom_call_target="tpu_custom_call"')
    assert tracered._op(text) == ("fused_group_rollout_pallas.6", True)
    assert tracered._op("%copy.84 = f32[1,64] copy(f32[1,64] %x)") == \
        ("copy.84", False)


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31)]
    assert tracered.union_ns(iv) == 26
    assert tracered.gaps(iv, 0, 40) == [(15, 20), (31, 40)]
    assert tracered.gaps(iv, 2, 25) == [(15, 20)]
    assert tracered.union_ns([]) == 0


def _synthetic():
    # one device: a forward [100, 200] holding a kernel [110, 180] and a
    # glue op [180, 195] that overlaps nothing; an op outside forwards
    # [300, 320] half cut by the window's end at 310
    dev = {"name": "/device:TPU:0",
           "ops": [["fusion.1", False, 100, 10],
                   ["custom-call.2", True, 110, 70],
                   ["fusion.3", False, 180, 15], ["copy.4", False, 300, 20]],
           "steps": [["jit_fwd", 100, 100], ["jit_other", 290, 20]]}
    host = [["bench/trace_window", 50, 260],
            ["snn_serve_step/b64", 40, 30],
            ["snn_serve_step/b32", 195, 110],
            ["snn_serve_step/b8", 250, 10]]
    return {"devices": [dev], "host": host}


def test_reduce_synthetic():
    r = tracered.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(260e-9)
    assert r["busy_s"] == pytest.approx((95 + 10) * 1e-9)
    assert r["glue_s"] == pytest.approx(25e-9)
    assert r["forwards"] == [{"device": 0, "pallas_s": pytest.approx(70e-9)}]
    gaps = r["breakdown"]["idle_gaps"]
    # [195, 300) has the b32 step's span open at its middle; at the
    # middle of [50, 100) the b64 step's span has already closed
    assert gaps[0] == ["snn_serve_step/b32", pytest.approx(105e-9)]
    assert gaps[1] == ["no host span", pytest.approx(50e-9)]
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["custom-call.2"] == pytest.approx(70e-9)
    assert ops["copy.4"] == pytest.approx(10e-9)


def test_reduce_needs_the_window_span():
    data = _synthetic()
    data["host"] = data["host"][1:]
    with pytest.raises(RuntimeError):
        tracered.reduce(data)


def test_reduce_recorded_trace():
    """Three forwards of vgg16_w4_fused.bulk at bucket 64, recorded on a
    TPU v5e (`bench.py --trace 1 --keep-trace`) and trimmed to them."""
    data = json.loads((FIXTURE / "trace_vgg16_bulk.json").read_text())
    r = tracered.reduce(data)
    assert len(r["forwards"]) == 3
    assert r["window_s"] == pytest.approx(0.041060092)
    assert r["busy_s"] == pytest.approx(0.029086704)
    assert r["glue_s"] == pytest.approx(0.005908212)
    assert 0 < r["glue_s"] < r["busy_s"] < r["window_s"]
    for f in r["forwards"]:
        assert f["pallas_s"] == pytest.approx(0.007726, rel=1e-3)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fused_group_rollout_pallas.6"] == pytest.approx(0.006810985)
    assert all(name.startswith(("fused_", "while", "convert", "broadcast",
                                "constant", "copy"))
               for name in ops)
    gaps = r["breakdown"]["idle_gaps"]
    assert len(gaps) == tracered.TOP
    assert gaps[0] == ["bench/wait", pytest.approx(0.006164131)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    # busy + idle gaps tile the window
    (_, lo, dur), = [h for h in data["host"]
                     if h[0] == tracered.WINDOW_SPAN]
    ops = [(s, s + d) for _, _, s, d in data["devices"][0]["ops"]]
    idle = sum(e - s for s, e in tracered.gaps(ops, lo, lo + dur)) / 1e9
    assert idle + r["busy_s"] == pytest.approx(r["window_s"])


def test_device_idle_from_busy_per_forward_and_untraced_rate():
    """One bucket: the device's busy time per forward from the trace,
    times the forwards per second of the untraced part, on two chips."""
    from types import SimpleNamespace

    from chipbench import spec

    trace = {"busy_s": 3.0, "window_s": 4.0,
             "forwards": [{"device": d, "pallas_s": 0.0}
                          for _ in range(300) for d in (0, 1)]}
    one_bucket = SimpleNamespace(traffic={"buckets": [64]})
    run = SimpleNamespace(cell=one_bucket, trace=trace,
                          counters={"batches": 2500}, untraced=(10.0, 36.0))
    idle = spec.reader("device_idle.bulk")(run)
    assert idle == pytest.approx(100.0 * (1 - 0.01 * 2500 / 26.0))
    # mixed buckets: the traced part as measured
    run.cell = SimpleNamespace(traffic={"buckets": [1, 2, 4]})
    assert spec.reader("device_idle.knee")(run) == pytest.approx(25.0)
    run.trace = None
    assert spec.reader("device_idle.knee")(run) is None
