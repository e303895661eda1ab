"""Reduction from a profiler trace to the benchmark's device numbers.

:func:`load` reads the ``.xplane.pb`` the JAX profiler wrote into a small
plain structure (kept as JSON for the tests' recorded fixture):

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[name, pallas, start_ns, dur_ns], ...],
                  "steps": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the device's XLA op events, ``pallas`` marking a Pallas
(Mosaic ``tpu_custom_call``) kernel; ``steps`` are executions of whole
XLA programs; ``host`` are the host spans the reduction attributes idle
time to.  :func:`reduce` cuts all of it to the harness's
``bench/trace_window`` span and computes busy time (the union of op
intervals), the Pallas and the glue time inside the served forwards,
the top ops and the longest idle gaps.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Dict, Iterable, List, Tuple

WINDOW_SPAN = "bench/trace_window"
HOST_SPANS = ("bench/", "snn_serve_step/")
FORWARD = "fwd"                     # the engine's jitted forward: jit_fwd
TOP = 10


def _op(text: str) -> Tuple[str, bool]:
    """(op name, whether it is a Pallas kernel) of a device op event.  On
    a TPU the event's name is the op's HLO text,
    ``%fused_group_rollout_pallas.6 = (...) custom-call(...),
    custom_call_target="tpu_custom_call", ...``."""
    pallas = 'custom_call_target="tpu_custom_call"' in text
    if text.startswith("%") and " = " in text:
        text = text[1:text.index(" = ")]
    return text, pallas


def load(logdir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found "
                           f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = {"name": plane.name, "ops": [], "steps": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        dev["ops"].append([*_op(e.name), e.start_ns,
                                           e.duration_ns])
                elif line.name == "XLA Modules":
                    dev["steps"] = [[e.name, e.start_ns, e.duration_ns]
                                    for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPANS):
                        host.append([e.name, e.start_ns, e.duration_ns])
    return {"devices": devices, "host": host}


def describe(logdir: str, per_line: int = 3) -> str:
    """A readable outline of a raw trace: planes, lines, event counts,
    the commonest event names and a few events with their stats.  For
    looking at a trace by hand before relying on its names."""
    from jax.profiler import ProfileData

    out = []
    for path in glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                       "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            lines = list(plane.lines)
            out.append(f"plane {plane.name!r}: {len(lines)} lines")
            for line in lines:
                events = list(line.events)
                names = collections.Counter(e.name for e in events)
                out.append(f"  line {line.name!r}: {len(events)} events, "
                           f"top {names.most_common(8)}")
                for e in events[:per_line]:
                    stats = {k: str(v)[:160] for k, v in e.stats}
                    out.append(f"    {e.name!r} {e.start_ns} "
                               f"{e.duration_ns} {stats}")
    return "\n".join(out)


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """Stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(s: float, d: float, lo: float, hi: float):
    a, b = max(s, lo), min(s + d, hi)
    return (a, b) if b > a else None


def _open_span(host: List[list], t: float) -> str:
    """The innermost host span open at time ``t`` (the latest started)."""
    best = None
    for name, s, d in host:
        if s <= t <= s + d and name != WINDOW_SPAN:
            if best is None or s > best[1]:
                best = (name, s)
    return best[0] if best else "no host span"


def reduce(data: dict) -> dict:
    win = [h for h in data["host"] if h[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"trace holds {len(win)} {WINDOW_SPAN} spans")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    if not data["devices"]:
        raise RuntimeError("trace holds no TPU device plane")
    busy, glue, forwards = [], [], []
    op_time: Dict[str, float] = collections.Counter()
    dev0_gaps: List[Tuple[float, float]] = []
    for i, dev in enumerate(data["devices"]):
        ops = [(name, c, pallas) for name, pallas, s, d in dev["ops"]
               if (c := _clip(s, d, lo, hi))]
        intervals = [c for _, c, _ in ops]
        busy.append(union_ns(intervals))
        for name, (a, b), _ in ops:
            op_time[name] += b - a
        fwd = [(s, s + d) for name, s, d in dev["steps"]
               if FORWARD in name and s >= lo and s + d <= hi]
        g = 0.0
        for fs, fe in fwd:
            inside = [(c, pallas) for _, c, pallas in ops
                      if c[0] >= fs and c[1] <= fe]
            pallas_ns = sum(b - a for (a, b), p in inside if p)
            g += union_ns(c for c, p in inside if not p)
            forwards.append({"device": i, "pallas_s": pallas_ns / 1e9})
        glue.append(g)
        if i == 0:
            dev0_gaps = gaps(intervals, lo, hi)
    n = len(data["devices"])
    window_s = (hi - lo) / 1e9
    longest = sorted(dev0_gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n / 1e9,
        "glue_s": sum(glue) / n / 1e9,
        "forwards": forwards,
        "breakdown": {
            "device_ops": [[name, t / n / 1e9]
                           for name, t in op_time.most_common(TOP)],
            "idle_gaps": [[_open_span(data["host"], (s + e) / 2),
                           (e - s) / 1e9] for s, e in longest],
        },
        "notes": [f"{n} device(s), {len(forwards)} forwards inside the "
                  f"{window_s:.3f} s window, "
                  f"{sum(len(d['ops']) for d in data['devices'])} device "
                  f"ops, {len(dev0_gaps)} idle gaps on device 0"],
    }
