"""The one general load generator: open-loop arrivals or closed-loop
clients, driving ``RetrievalEngine.submit``/``step`` in this process.

A traffic file (``bench/traffic/<name>.json``) holds its parameters:

    {"loop": "open", "arrivals": "poisson", "rate_qps": 4000,
     "query_noise": 0.15}
    {"loop": "closed", "clients": 128, "query_noise": 0.15}

The engine is synchronous, so one thread submits what is due, runs one
engine tick while anything is pending, and waits for the next arrival
while nothing is. An open-loop request is timed from its due time, so a
tick that runs long delays the requests that arrive during it; a
closed-loop request is timed from when its client sent it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench.data import poisson_arrivals

# how long an unanswered request is waited for after the window closes
GRACE_S = 60.0


@dataclasses.dataclass
class Requests:
    """Per-request times in seconds from the window's start, by stream
    index; NaN where a request never completed. ``answers`` holds
    (keys, dists) of each request that came back without error, else
    None: the engine's request objects are let go as soon as they are
    answered, so the benchmark adds no garbage-collected objects of its
    own beyond one key list per request."""
    due: np.ndarray
    start: np.ndarray            # start of the engine tick that served it
    done: np.ndarray
    answers: list


def _settle(handles: dict, answers: list, start, done, s: float,
            e: float) -> dict:
    """Record the answered requests of one tick; -> those still pending."""
    left = {}
    for j, h in handles.items():
        if h.done:
            start[j], done[j] = s, e
            answers[j] = None if h.error is not None else (h.keys, h.dists)
        else:
            left[j] = h
    return left


class Spans:
    """Host spans of the benchmark's own, on the host clock and, when a
    trace is being taken, as ``TraceAnnotation``s on the profiler's."""

    def __init__(self, t0: float, annotate: bool):
        self.t0 = t0
        self.annotate = annotate
        self.steps: list[tuple[float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield


def _step(engine, spans: Spans, clock) -> tuple[float, float]:
    s = clock()
    with spans.span("engine.step"):
        try:
            engine.step()
        except Exception:          # noqa: BLE001 - the engine marks the
            pass                   # failed requests; they count as failed
    e = clock()
    spans.steps.append((s, e))
    return s, e


def _wait_until(t: float, clock, spans: Spans) -> None:
    with spans.span("client.wait"):
        while True:
            left = t - clock()
            if left <= 0:
                return
            if left > 2e-4:
                time.sleep(left - 1e-4)


def run_open(engine, queries, due: np.ndarray, k: int, spans: Spans,
             clock) -> Requests:
    """Submit query i at due[i]; tick while anything is pending."""
    n = len(due)
    start = np.full(n, np.nan)
    done = np.full(n, np.nan)
    answers: list = [None] * n
    pending: dict = {}
    i = 0
    limit = float(due[-1]) + GRACE_S
    while i < n or pending:
        now = clock()
        if now > limit:
            break
        while i < n and due[i] <= now:
            pending[i] = engine.submit(queries[i], k=k)
            i += 1
        if pending:
            s, e = _step(engine, spans, clock)
            pending = _settle(pending, answers, start, done, s, e)
        elif i < n:
            _wait_until(float(due[i]), clock, spans)
    return Requests(due=np.asarray(due, float), start=start, done=done,
                    answers=answers)


def run_closed(engine, queries, clients: int, seconds: float, k: int,
               spans: Spans, clock) -> Requests:
    """``clients`` callers, each sending its next query as soon as its
    last result returns, until the window closes; then drain."""
    due: list[float] = []
    answers: list = []
    start = np.full(1 << 16, np.nan)
    done = np.full(1 << 16, np.nan)
    pending: dict = {}

    def send(t: float) -> None:
        nonlocal start, done
        idx = len(due)
        if idx == len(start):
            start = np.concatenate([start, np.full(idx, np.nan)])
            done = np.concatenate([done, np.full(idx, np.nan)])
        pending[idx] = engine.submit(queries[idx], k=k)
        due.append(t)
        answers.append(None)

    t = clock()
    for _ in range(clients):
        send(t)
    limit = seconds + GRACE_S
    while pending and clock() <= limit:
        s, e = _step(engine, spans, clock)
        before = len(pending)
        pending = _settle(pending, answers, start, done, s, e)
        if e < seconds:
            for _ in range(before - len(pending)):
                send(e)
    n = len(due)
    return Requests(due=np.asarray(due, float), start=start[:n],
                    done=done[:n], answers=answers)


def planned_requests(traffic: dict, seconds: float) -> int:
    """Queries to draw in set-up so that none is drawn in the window: the
    open loop's exact count, or for a closed loop a pool good for 20,000
    requests a second (further queries are drawn as they are needed)."""
    if traffic["loop"] == "open":
        return max(int(round(traffic["rate_qps"] * seconds)), 1)
    return int(20_000 * seconds) + traffic["clients"]


def run(traffic: dict, engine, queries, seed: int, seconds: float, k: int,
        spans: Spans) -> Requests:
    t0 = spans.t0

    def clock() -> float:
        return time.perf_counter() - t0

    if traffic["loop"] == "open":
        if traffic["arrivals"] != "poisson":
            raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
        due = poisson_arrivals(seed, traffic["rate_qps"], seconds)
        return run_open(engine, queries, due, k, spans, clock)
    if traffic["loop"] == "closed":
        return run_closed(engine, queries, int(traffic["clients"]),
                          seconds, k, spans, clock)
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def batch_shapes(traffic: dict, max_batch: int) -> list[int]:
    """The engine's bucket sizes this traffic can produce."""
    if traffic["loop"] == "closed":
        c = int(traffic["clients"])
        shapes = {min(c, max_batch)}
        if c > max_batch and c % max_batch:
            shapes.add(c % max_batch)
    else:
        shapes = set(range(1, max_batch + 1))
    out = set()
    for n in shapes:
        b = 1
        while b < n:
            b <<= 1
        out.add(min(b, max_batch))
    return sorted(out)
