"""Run one cell once: set up, warm, measure a window, check the answers.

    python benchmarks/chip/bench.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``, from process start to the first request
of the window): the weights and image pool from the seed in one jitted
call, the program's one-shot pack (``deploy``), compiles of the cell's
own buckets, and ``warm_s`` seconds of the cell's own traffic.  The
window then runs the traffic for ``--seconds`` through
``AsyncSNNServeEngine.submit``.  With ``--trace 1`` the profiler records
the last seconds of the window and the run reports the per-layer
metrics instead of the end-to-end ones.

Once the window has closed and the peak memory has been read, the
program's state is freed and the plain reference (chipbench/reference.py)
recomputes a seeded sample of the window's answers.  The last line of
standard output is the result as one JSON object.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import threading
import time
import warnings
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from chipbench import ops as _ops
from chipbench import spec as _spec
from chipbench import traffic as _traffic

POOL = 1024               # distinct request images per run
SAMPLE = 256              # window answers the reference recomputes
TRACE_S = 4.0             # traced part of the window in --trace 1 runs
ANSWER_OFF = 0.1          # an answer's RMS gap, over the reference logits'
                          # RMS, beyond which it counts as off


class NoChip(SystemExit):
    """The run cannot measure here; exit non-zero, print no result."""


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    raise NoChip(1)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read (see ``metrics/``)."""

    cell: _spec.Cell
    seed: int
    chips: int
    peaks: object
    records: List[_traffic.Record]
    window: tuple                     # (w0, w1) perf_counter seconds
    setup_s: float
    ops_per_image: int
    params: object = None             # the float weights, for the reference
    images: Optional[np.ndarray] = None   # the request image pool
    memory_peak_bytes: int = 0
    trace: Optional[dict] = None      # chipbench.tracered.reduce output
    trace_window: Optional[tuple] = None
    untraced: Optional[tuple] = None  # the window's part before the trace
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    compiles_in_window: int = 0
    notes: List[str] = dataclasses.field(default_factory=list)

    def in_window(self, window=None) -> List[_traffic.Record]:
        """Requests due in the window."""
        w0, w1 = window or self.window
        return [r for r in self.records if w0 <= r.due < w1]

    def latencies(self) -> List[float]:
        """Sorted due-to-answer seconds of the requests due in the window;
        an answer that never came counts until the harness gave up."""
        give_up = self.window[1] + _traffic.RESULT_WAIT_S
        return sorted((r.done if r.status == "ok" else give_up) - r.due
                      for r in self.in_window())

    def completed_in(self, window=None) -> List[_traffic.Record]:
        """Requests whose answer came back ok inside the window."""
        w0, w1 = window or self.window
        return [r for r in self.records
                if r.status == "ok" and w0 <= r.done <= w1]


# -- the program --------------------------------------------------------------

def program_config(conf: dict):
    """The program's config for a configuration file, checked against
    the file: a file that does not describe what runs is an error."""
    from repro.deploy import deploy_config
    from repro.graph import build_graph
    from repro.graph.spec import Conv, Dense, Readout

    fusion = () if conf["fusion"] == "off" else conf["fusion"]
    # ``smoke``: the program's CPU-test geometry, for the benchmark's tests
    cfg = deploy_config(conf["model"], bits=conf["bits"],
                        smoke=conf.get("smoke", False), fusion=fusion)
    lif = cfg.lif
    have = {"img_size": cfg.img_size, "in_channels": cfg.in_channels,
            "n_classes": cfg.n_classes, "timesteps": cfg.timesteps,
            "leak_shift": lif.leak_shift, "threshold": lif.threshold,
            "soft_reset": lif.soft_reset, "bits": cfg.precision.bits}
    for key, value in have.items():
        if conf[key] != value:
            raise ValueError(f"{conf['name']}: the program runs {key}="
                             f"{value}, the configuration says {conf[key]}")
    mine = {l.name: l for l in _ops.layers(conf)
            if l.kind in ("conv", "dense", "readout")}
    theirs = [s for s in build_graph(cfg).param_specs()]
    if sorted(mine) != sorted(s.name for s in theirs):
        raise ValueError(f"{conf['name']}: layers differ from the program's")
    for s in theirs:
        m = mine[s.name]
        if isinstance(s, Conv):
            geo = (s.c_in, s.c_out, s.k, s.stride, s.out_hw)
            ref = (m.c_in, m.c_out, m.k, m.stride, m.hw_out)
        elif isinstance(s, (Dense, Readout)):
            geo, ref = (s.d_in, s.d_out), (m.c_in, m.c_out)
        if geo != ref:
            raise ValueError(f"{conf['name']}: {s.name} is {geo} in the "
                             f"program and {ref} in the configuration")
    return cfg


def build_engine(cfg, params, traffic: dict):
    """Pack with the program's deploy step (one jitted call) and start
    the async tier over the sync engine, every bucket compiled."""
    import jax

    from repro.deploy import (SNNEngineConfig, SNNRequest, SNNServeEngine,
                              deploy)
    from repro.serve_async import AsyncSNNServeEngine

    pkg = jax.block_until_ready(jax.jit(lambda p: deploy(p, cfg))(params))
    eng = SNNServeEngine(pkg, SNNEngineConfig(
        max_batch=traffic["max_batch"], buckets=tuple(traffic["buckets"]),
        data_parallel=traffic.get("data_parallel", False)))
    eng.warmup()
    # run every bucket once: a program's first execution pays one-time
    # costs that compiling alone does not
    blank = np.zeros((cfg.img_size, cfg.img_size, cfg.in_channels),
                     np.float32)
    uid = 0
    for bucket in eng.buckets:
        uids = range(uid, uid + bucket)
        for u in uids:
            eng.add_request(SNNRequest(uid=u, image=blank))
        eng.step()
        for u in uids:
            eng.pop_result(u)
        uid += bucket
    return AsyncSNNServeEngine(eng).start()


# -- the run ------------------------------------------------------------------

class _WindowClock(threading.Thread):
    """Keeps the window's time on its own thread, so the traffic loop
    keeps its timing: from ``w0`` it counts XLA backend compiles until
    ``w1``, and reads the engine's slot counters at ``w0`` and at the
    start of the window's last part, ``t0``.  With a ``logdir`` it
    profiles [t0, w1] into it, marking that span for the reduction with
    a host annotation; [w0, t0] stays untraced, since the profiler slows
    a busy host (vgg16 bulk on a TPU v5e: 3,900-4,500 images/s traced,
    5,600-6,100 untraced)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self, eng, w0: float, t0: float, w1: float,
                 logdir: Optional[str]):
        super().__init__(name="bench-window", daemon=True)
        self.eng, self.w0, self.t0, self.w1 = eng, w0, t0, w1
        self.logdir = logdir
        self.compiles = 0
        self.counting = False
        self.marks: List[Dict[str, int]] = []
        self.trace_window = None
        self.error = None

    def _event(self, event: str, duration: float, **_) -> None:
        if self.counting and event == self.COMPILE:
            self.compiles += 1

    def _mark(self) -> None:
        e = self.eng
        self.marks.append({"slots": e.total_slots,
                           "padded": e.total_padded_slots,
                           "batches": e.total_batches})

    @staticmethod
    def _sleep_until(t: float) -> None:
        time.sleep(max(0.0, t - time.perf_counter()))

    def run(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._event)
        try:
            self._sleep_until(self.w0)
            self.counting = True
            self._mark()
            self._sleep_until(self.t0)
            self._mark()
            if self.logdir is None:
                self._sleep_until(self.w1)
            else:
                self._traced()
        except Exception as e:              # reported by the caller
            self.error = e
        finally:
            self.counting = False
            jax.monitoring.unregister_event_duration_listener(self._event)

    def _traced(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # level 1 keeps the annotations and drops the runtime's own
        # fine-grained host events, which slow a busy host
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench/trace_window"):
                t0 = time.perf_counter()
                self._sleep_until(self.w1)
                self.trace_window = (t0, time.perf_counter())
        finally:
            jax.profiler.stop_trace()


def setup(cell: _spec.Cell, seed: int):
    """Weights and images from the seed, packed and served: returns
    (float params, host image pool, started async engine)."""
    from chipbench import model as _model

    cfg = program_config(cell.conf)
    params, pool = _model.make(cell.conf, seed, POOL)
    images = np.asarray(pool)
    return params, images, build_engine(cfg, params, cell.traffic)


def measure(cell: _spec.Cell, seed: int, seconds: float, trace: bool,
            t_process: float, keep_trace: str = "") -> Run:
    """Set up, warm, run the window, free the program; no reference yet."""
    import jax

    conf, traffic = cell.conf, cell.traffic
    params, images, aeng = setup(cell, seed)
    eng = aeng.engine

    gc.collect()
    t_warm = time.perf_counter()
    w0 = t_warm + traffic["warm_s"]
    w1 = w0 + seconds
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    clock = _WindowClock(eng, w0, w1 - min(seconds, TRACE_S), w1, logdir)
    clock.start()
    # The load generator shares the server's process, and its bookkeeping
    # (a record and a future per request, tens of thousands) would set off
    # full garbage collections that stall generator and server alike for
    # a tenth of a second or more; real clients live in other processes.
    # Reference counting still frees what the window drops.
    gc.disable()
    try:
        records = _traffic.run(traffic, aeng.submit, images, t_warm, w1,
                               seed)
    finally:
        gc.enable()
    clock.join()
    devices = jax.devices()[:cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    aeng.close()
    del aeng, eng
    gc.collect()

    run = Run(cell=cell, seed=seed, chips=cell.chips, peaks=None,
              records=records,
              window=(w0, w1), setup_s=w0 - t_process,
              ops_per_image=_ops.ops_per_image(conf), params=params,
              images=images, memory_peak_bytes=peak,
              compiles_in_window=clock.compiles)
    run.untraced = (w0, clock.t0)
    if len(clock.marks) == 2:
        run.counters = {k: clock.marks[1][k] - clock.marks[0][k]
                        for k in clock.marks[0]}
    try:
        if clock.error is not None:
            raise clock.error
        if logdir is not None:
            from chipbench import tracered
            run.trace_window = clock.trace_window
            data = tracered.load(logdir)
            if keep_trace:
                _keep(keep_trace, data, tracered.describe(logdir))
            run.trace = tracered.reduce(data)
    finally:
        if logdir is not None:
            shutil.rmtree(logdir, ignore_errors=True)
    return run


def _keep(dest: str, data: dict, outline: str) -> None:
    """Save the reduced trace and an outline of the raw one."""
    out = Path(dest)
    out.mkdir(parents=True, exist_ok=True)
    (out / "trace.json").write_text(json.dumps(data))
    (out / "trace_outline.txt").write_text(outline)


# -- correctness -------------------------------------------------------------

def check(run: Run, bits: Optional[int] = None) -> Dict[str, dict]:
    """Compare a seeded sample of the window's answers with the plain
    reference.  Returns {name: {"value", "limit"}}: the answers due in
    the window that never came back, and each number of
    :func:`compare` that the configuration gives a limit (``limits``);
    the others go to the run's notes.  ``bits`` recomputes the reference
    at another width (a control's readings use the configuration's own).
    """
    from chipbench import reference as _reference

    conf = run.cell.conf
    due = run.in_window()
    missing = sum(r.status != "ok" for r in due)
    ok = [r for r in due if r.status == "ok"]
    rng = np.random.default_rng([run.seed, 3])
    take = sorted(rng.choice(len(ok), size=min(SAMPLE, len(ok)),
                             replace=False).tolist()) if ok else []
    if ok:
        slowest = max(range(len(ok)), key=lambda i: ok[i].done - ok[i].due)
        if slowest not in take:
            take.append(slowest)
    sample = [ok[i] for i in take]
    numbers = {}
    if sample:
        got = np.stack([r.logits for r in sample])
        ref, settled = _reference.logits(
            conf, run.params, run.images[[r.idx for r in sample]],
            bits or conf["bits"], served=got)
        numbers = compare(got, ref)
        run.notes.append(
            f"answers compared: {len(sample)}, clip ties settled by the "
            f"answers: {settled}, " + ", ".join(
                f"{k} {v!r}" for k, v in numbers.items()))
    checks = {"missing_answers": {"value": missing, "limit": 0}}
    for name, limit in conf["limits"].items():
        # no answer to compare: not correct
        checks[name] = {"value": numbers.get(name), "limit": limit}
    return checks


def compare(got: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
    """Numbers that compare served logits with the reference's, each gap
    over the root mean square of the reference's logits.

    ``class_mean_gap``, the widest gap between the two sides' mean logit
    of a class over the sample, moves with the precision of the whole
    stack; ``answers_off``, the share of answers whose own RMS gap is over
    ``ANSWER_OFF``, sees answers that are wrong one by one, such as an
    answer given to another request; ``rms_gap`` and ``widest_gap``
    follow single answers too; ``argmax_mismatch`` is the share of
    answers whose top class differs."""
    scale = max(float(np.sqrt(np.mean(ref.astype(np.float64) ** 2))), 1e-12)
    diff = (got - ref) / scale
    per_answer = np.sqrt(np.mean(diff ** 2, axis=1))
    return {"class_mean_gap": float(np.max(np.abs(diff.mean(axis=0)))),
            "answers_off": float(np.mean(per_answer > ANSWER_OFF)),
            "rms_gap": float(np.sqrt(np.mean(diff ** 2))),
            "widest_gap": float(np.max(np.abs(diff))),
            "argmax_mismatch": float(np.mean(got.argmax(1) != ref.argmax(1))),
            "reference_rms": scale}


def correct(checks: Dict[str, dict]) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


# -- reporting ---------------------------------------------------------------

def report(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        value = _spec.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(run: Run) -> dict:
    import jax

    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": run.chips, "memory_peak_bytes": run.memory_peak_bytes}
    if run.trace is not None:
        info["busy_s"] = run.trace["busy_s"]
        info["window_s"] = run.trace["window_s"]
    return info


def _context_lines(run: Run) -> None:
    """Numbers that are not metrics of this cell, on standard error."""
    due = run.in_window()
    lat = sorted(r.done - r.due for r in due)
    late = sorted(r.sub - r.due for r in due)
    n = len(lat)
    if n:
        pct = lambda xs, q: xs[max(0, math.ceil(q * len(xs)) - 1)]
        print(f"bench: window {run.window[1] - run.window[0]:.3f} s, "
              f"{n} requests due, {len(run.completed_in())} completed in "
              f"it; latency from due p50 {1e3 * pct(lat, .5):.3f} ms p95 "
              f"{1e3 * pct(lat, .95):.3f} ms p99 {1e3 * pct(lat, .99):.3f} "
              f"ms; generator late p95 {1e3 * pct(late, .95):.3f} ms max "
              f"{1e3 * late[-1]:.3f} ms", file=sys.stderr)
    if run.trace_window is not None:
        parts = {"untraced": run.untraced, "traced": run.trace_window}
        print("bench: answers per second in the window's " + ", ".join(
            f"{k} part {len(run.completed_in(w)) / (w[1] - w[0])!r}"
            for k, w in parts.items()), file=sys.stderr)
    print(f"bench: compiles inside the window: {run.compiles_in_window}",
          file=sys.stderr)
    if run.trace is not None:
        t = run.trace
        print(f"bench: trace: device idle in the traced part "
              f"{100.0 * (1.0 - t['busy_s'] / t['window_s'])!r} %",
              file=sys.stderr)
        for line in t.get("notes", []):
            print(f"bench: trace: {line}", file=sys.stderr)
    for line in run.notes:
        print(f"bench: {line}", file=sys.stderr)


def main(argv: List[str], t_process: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="", metavar="DIR",
                    help="also write the reduced trace and an outline of "
                         "the raw one into DIR")
    args = ap.parse_args(argv)

    try:
        return _main(args, t_process)
    except NoChip as e:
        return int(e.code or 1)


def start_chip(cell: _spec.Cell):
    """Put the program on the path and JAX on the chip, or fail: the
    program must sit next to the benchmark, and JAX must see a TPU with
    as many chips as the cell asks for.  Returns the chip's peaks."""
    src = _spec.ROOT / "src"
    if not (src / "repro").is_dir():
        fail(f"no program next to the benchmark ({src}/repro)")
    sys.path.insert(0, str(src))

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, comes from the cache on
    # the next run, so set-up is the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no usable device: {str(e).splitlines()[0]}")
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX's first device is {devices[0].platform}")
    if len(devices) < cell.chips:
        fail(f"the cell needs {cell.chips} chips, JAX sees {len(devices)}")
    from repro.kernels import get_backend

    if get_backend() != "pallas":
        fail(f"kernel backend is {get_backend()!r}, not 'pallas'")
    from chipbench.peaks import peaks_for

    # a fall back to the reference path would serve something else
    warnings.filterwarnings("error", message=".*falling back",
                            category=RuntimeWarning)
    return peaks_for(devices[0].device_kind)


def _main(args, t_process: float) -> int:
    try:
        cell = _spec.cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        fail(str(e))
    peaks = start_chip(cell)
    run = measure(cell, args.seed, args.seconds, bool(args.trace), t_process,
                  args.keep_trace)
    run.peaks = peaks
    metrics = report(run, cell.per_layer if args.trace else cell.end_to_end)
    checks = check(run)
    due = run.in_window()
    result = {
        "correct": correct(checks),
        "attempted": len(due),
        "failed": sum(r.status != "ok" for r in due),
        "metrics": metrics,
        "device": device_info(run),
    }
    if run.trace is not None:
        result["breakdown"] = run.trace["breakdown"]
    result["checks"] = checks
    _context_lines(run)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
