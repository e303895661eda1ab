"""One general load generator, driven by a traffic file.

A traffic file (``traffic/<mix>.json``) is one of two kinds:

``{"loop": "closed", "clients": N, ...}``
    N clients, each sending its next request as soon as its last
    result returns, so exactly N requests are outstanding.
``{"loop": "open", "rate_per_s": R, ...}``
    Arrivals at R per second on a schedule that does not wait for the
    server.  The gaps are the same stratified set of exponential gaps
    for every seed (quantiles of an exponential of mean 1/R), shuffled
    by the seed, so every seed offers the same work in another order.

Both also give the engine's ``max_batch``, ``buckets`` and
``data_parallel``, and ``warm_s``: seconds of the same traffic run
before the measured window.  Every request records when it was due,
when it was submitted, when its result came back, its status, its
queue wait and the pool index of its image.  Latency is timed from the
due time, so a late generator shows as latency, and its lateness is
reported.
"""

from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import Callable, Iterator, List, Optional

import numpy as np

RESULT_WAIT_S = 60.0      # how long past the window's close a result may
                          # take before it counts as never having come


@dataclasses.dataclass
class Record:
    idx: int              # image pool index
    due: float            # perf_counter seconds
    sub: float            # submitted
    done: float = math.inf            # result back (inf: never)
    status: str = "missing"
    queue_s: float = 0.0
    logits: Optional[np.ndarray] = None


def image_order(seed: int, pool: int) -> Iterator[int]:
    """Pool indices of successive requests: seeded permutations of the
    pool, one after another."""
    rng = np.random.default_rng([seed, 1])
    while True:
        yield from rng.permutation(pool).tolist()


def open_gaps(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Inter-arrival gaps covering ``seconds`` at ``rate``: the same set of
    exponential quantiles for every seed, scaled to sum to ``seconds``
    exactly, in a seeded order."""
    if rate <= 0:
        raise ValueError(f"rate must be positive, got {rate}")
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= seconds / gaps.sum()
    return np.random.default_rng([seed, 2]).permutation(gaps)


def _finish(rec: Record, fut, t_sub: float) -> None:
    try:
        r = fut.result(timeout=RESULT_WAIT_S)
    except TimeoutError:
        return                      # never came: stays "missing"
    rec.status = r.status
    rec.queue_s = r.queue_s
    if r.ok:
        # the engine stamps the request just after t_sub and measures
        # its latency to the moment the rollout's results were read
        rec.done = t_sub + r.latency_s
        rec.logits = r.logits


def run_closed(submit: Callable, images: np.ndarray, clients: int,
               t_start: float, t_end: float, seed: int) -> List[Record]:
    """Closed loop from ``t_start`` to ``t_end`` (perf_counter): each
    client's next request is due the moment its last result is read.
    Results come back in submission order (one FIFO queue), so waiting
    on the oldest outstanding request keeps exactly ``clients`` in
    flight apart from the instant of each resubmit."""
    order = image_order(seed, len(images))
    records: List[Record] = []
    outstanding: deque = deque()

    def send(now: float) -> None:
        idx = next(order)
        t_sub = time.perf_counter()
        fut = submit(images[idx])
        rec = Record(idx=idx, due=now, sub=t_sub)
        records.append(rec)
        outstanding.append((rec, fut))

    while time.perf_counter() < t_start:
        time.sleep(0.0005)
    for _ in range(clients):
        send(time.perf_counter())
    while outstanding:
        rec, fut = outstanding.popleft()
        _finish(rec, fut, rec.sub)
        now = time.perf_counter()
        if now < t_end:
            send(now)
    return records


def run_open(submit: Callable, images: np.ndarray, rate: float,
             t_start: float, t_end: float, seed: int) -> List[Record]:
    """Open loop: submit at each due time from ``t_start`` to ``t_end``
    whatever the server does, then collect every result."""
    gaps = open_gaps(rate, t_end - t_start, seed)
    due = t_start + np.cumsum(gaps) - gaps[0]
    order = image_order(seed, len(images))
    records: List[Record] = []
    futs = []
    for t_due in due:
        wait = t_due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        idx = next(order)
        t_sub = time.perf_counter()
        futs.append(submit(images[idx]))
        records.append(Record(idx=idx, due=float(t_due), sub=t_sub))
    for rec, fut in zip(records, futs):
        _finish(rec, fut, rec.sub)
    return records


def run(traffic: dict, submit: Callable, images: np.ndarray,
        t_start: float, t_end: float, seed: int) -> List[Record]:
    if traffic["loop"] == "closed":
        return run_closed(submit, images, traffic["clients"], t_start,
                          t_end, seed)
    if traffic["loop"] == "open":
        return run_open(submit, images, traffic["rate_per_s"], t_start,
                        t_end, seed)
    raise ValueError(f"unknown loop {traffic['loop']!r}")
