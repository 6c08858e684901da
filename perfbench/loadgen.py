"""The one traffic generator: a traffic file's parameters -> requests -> a
measured window against a service.

A traffic file (`traffic/<name>.json`) holds only data:

  loop               "closed": ``callers`` callers, each with one request
                     outstanding, send the next as soon as the last is
                     answered.  "open": requests are due on a schedule
                     of Poisson arrivals at ``rate_per_s``, whether or
                     not earlier ones have been answered.
  keys_per_request   keys in one request.
  present_frac       share of keys drawn from the key set; the rest are
                     absent keys uniform over [min - absent_margin,
                     max + absent_margin] (`make_point_queries`'s
                     semantics).
  present_ranks      "uniform", or "ycsb_scrambled_zipfian" (`zipf.py`).
  pool_requests      closed loop: how many distinct requests are drawn;
                     callers cycle through them in order.

Everything is drawn from the run's seed.  The open loop's arrival gaps
are the exact quantiles of the exponential distribution, shuffled by
the seed: every seed offers the same set of gaps, in another order.

Latency runs from when a request was due (open loop) or sent (closed
loop) to when the generator saw its answer.  Requests still in flight
when the window closes are drained and checked; they count toward the
latency percentiles but not toward work done in the window.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np

from perfbench import zipf

#: how long the generator waits for the last answers after the close
DRAIN_S = 60.0


@dataclasses.dataclass
class Traffic:
    """The requests of one run: ``queries[i]`` is request i's keys."""

    params: dict
    queries: np.ndarray          # [n_requests, keys_per_request] uint64
    offsets: Optional[np.ndarray] = None   # open loop: due times (s)


@dataclasses.dataclass
class Window:
    """What one measured window did, request by request."""

    request: np.ndarray          # index into Traffic.queries
    t_due: np.ndarray            # perf_counter seconds
    t_done: np.ndarray           # NaN: never answered
    answers: List[Optional[np.ndarray]]
    errors: int
    t_start: float
    t_end: float
    lag: Optional[np.ndarray] = None   # open loop: sent minus due (s)

    @property
    def in_window(self) -> np.ndarray:
        return self.t_done <= self.t_end


def _ranks(rng, params: dict, size: int, n_keys: int) -> np.ndarray:
    kind = params.get("present_ranks", "uniform")
    if kind == "uniform":
        return rng.integers(0, n_keys, size=size, dtype=np.int64)
    if kind == "ycsb_scrambled_zipfian":
        return zipf.scrambled_zipfian(rng, size, n_keys)
    raise ValueError(f"unknown present_ranks {kind!r}")


def make(params: dict, keys: np.ndarray, seed: int, seconds: float) -> Traffic:
    """Draw one run's requests from the seed."""
    rng = np.random.default_rng(seed)
    k = int(params["keys_per_request"])
    if params["loop"] == "closed":
        n_req, offsets = int(params["pool_requests"]), None
    elif params["loop"] == "open":
        n_req = max(1, int(round(float(params["rate_per_s"]) * seconds)))
        gaps = -np.log1p(-(np.arange(n_req) + 0.5) / n_req)
        gaps = rng.permutation(gaps) / float(params["rate_per_s"])
        offsets = np.cumsum(gaps)
        offsets *= seconds * n_req / (n_req + 0.5) / offsets[-1]
    else:
        raise ValueError(f"unknown loop {params['loop']!r}")
    m = n_req * k
    n_present = int(round(m * float(params.get("present_frac", 1.0))))
    present = keys[_ranks(rng, params, n_present, keys.size)]
    margin = int(params.get("absent_margin", 1000))
    lo = max(int(keys[0]) - margin, 0)
    hi = min(int(keys[-1]) + margin, (1 << 64) - 1)
    absent = rng.integers(lo, hi, size=m - n_present, dtype=np.uint64)
    q = np.concatenate([present, absent]).astype(np.uint64)
    rng.shuffle(q)
    return Traffic(params, q.reshape(n_req, k), offsets)


def run(service, traffic: Traffic, seconds: float, on_start=None) -> Window:
    """Drive ``service.submit`` for ``seconds``, then drain."""
    if traffic.params["loop"] == "closed":
        return _closed(service, traffic, seconds, on_start)
    return _open(service, traffic, seconds, on_start)


def _collect(fut, deadline: float):
    """(answer or None, error?) of one future, waiting at most until
    ``deadline``; a request that never answers returns (None, False)."""
    try:
        return fut.result(timeout=max(deadline - time.perf_counter(), 0.0)), \
            False
    except TimeoutError:
        return None, False
    except Exception:  # noqa: BLE001 — an errored request is counted
        return None, True


def _closed(service, traffic: Traffic, seconds: float, on_start) -> Window:
    """One thread keeps ``callers`` requests outstanding.  The service
    answers in admission order, so waiting on the oldest is waiting on
    whichever caller is answered next."""
    pool, callers = traffic.queries, int(traffic.params["callers"])
    req, t_sub, t_done, answers = [], [], [], []
    errors = 0
    out = collections.deque()
    nxt = 0
    if on_start is not None:
        on_start()
    t_start = time.perf_counter()
    t_end = t_start + seconds
    for _ in range(callers):
        out.append((nxt, time.perf_counter(),
                    service.submit(pool[nxt % len(pool)])))
        nxt += 1
    while out:
        i, t0, fut = out.popleft()
        ans, err = _collect(fut, t_end + DRAIN_S)
        now = time.perf_counter()
        errors += err
        req.append(i % len(pool))
        t_sub.append(t0)
        t_done.append(now if ans is not None else np.nan)
        answers.append(ans)
        if now < t_end:
            out.append((nxt, time.perf_counter(),
                        service.submit(pool[nxt % len(pool)])))
            nxt += 1
    return Window(np.asarray(req, np.int64), np.asarray(t_sub),
                  np.asarray(t_done), answers, errors, t_start, t_end)


def _open(service, traffic: Traffic, seconds: float, on_start) -> Window:
    """A sender thread submits each request when it falls due; this
    thread collects answers in admission order."""
    queries, offsets = traffic.queries, traffic.offsets
    n = len(queries)
    sent = collections.deque()
    cv = threading.Condition()
    lag = np.zeros(n)

    if on_start is not None:
        on_start()
    t_start = time.perf_counter()
    t_end = t_start + seconds
    due = t_start + offsets

    def sender():
        i = 0
        try:
            while i < n:
                now = time.perf_counter()
                j = i
                batch = []
                while j < n and due[j] <= now:
                    try:
                        batch.append((j, service.submit(queries[j])))
                    except Exception:  # noqa: BLE001 — counted as an error
                        batch.append((j, None))
                    lag[j] = time.perf_counter() - due[j]
                    j += 1
                if batch:
                    with cv:
                        sent.extend(batch)
                        cv.notify()
                i = j
                if i < n:
                    wait = due[i] - time.perf_counter()
                    if wait > 2e-4:
                        time.sleep(wait - 1e-4)
        finally:
            with cv:
                sent.append(None)
                cv.notify()

    th = threading.Thread(target=sender, name="perfbench-sender", daemon=True)
    th.start()
    t_done = np.full(n, np.nan)
    answers: List[Optional[np.ndarray]] = [None] * n
    errors = 0
    try:
        while True:
            with cv:
                while not sent:
                    cv.wait()
                item = sent.popleft()
            if item is None:
                break
            j, fut = item
            if fut is None:
                errors += 1
                continue
            ans, err = _collect(fut, t_end + DRAIN_S)
            errors += err
            if ans is not None:
                t_done[j] = time.perf_counter()
                answers[j] = ans
    finally:
        th.join()
    return Window(np.arange(n, dtype=np.int64), due, t_done, answers,
                  errors, t_start, t_end, lag=lag)
