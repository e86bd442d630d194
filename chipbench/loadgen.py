"""The one traffic generator: reads a mix's parameters, drives the runtime.

A mix (``chipbench/traffic/<name>.json``) is data:

* ``{"kind": "open", "rate_per_s": R}``: open-loop arrivals of single-query
  requests at R per second.  A window of S seconds holds exactly round(R*S)
  arrivals at sorted uniform times: the Poisson process conditioned on its
  count, so every seed offers the same work in another order.  A request's
  latency runs from its scheduled arrival (so a late generator shows in the
  tail) to its completion.  Copied from the repository's
  ``serve/loadgen.py`` (``arrival_schedule``, ``run_open_loop``) and made
  window-based.
* ``{"kind": "closed", "concurrency": C, "max_rate_per_s": M}``: C
  single-query requests outstanding at all times; each completion sends the
  next.  M bounds the query pool (M * S + C distinct queries).

Each request carries a query drawn fresh from the configuration's query
generator: none repeats within a window.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Window:
    """What the window sent and got back, on the host's perf_counter."""

    t0: float                    # window start
    t_end: float                 # window end (t0 + seconds)
    due: np.ndarray              # (R,) scheduled send time, absolute
    sent: np.ndarray             # (R,) actual send time
    requests: list               # the runtime's request objects, in order
    n_queries_left: int = 0      # unused queries of the pool

    @property
    def done(self) -> np.ndarray:
        """(R,) completion time; nan where no answer came."""
        return np.array([r.done_t if r.event.is_set() and r.error is None
                         else np.nan for r in self.requests])


def pool_size(mix: dict, seconds: float) -> int:
    """How many distinct queries a window of this mix can use."""
    if mix["kind"] == "open":
        return int(round(mix["rate_per_s"] * seconds))
    return int(np.ceil(mix["max_rate_per_s"] * seconds)) + mix["concurrency"]


def schedule(rate_per_s: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets (s) of round(rate * seconds) requests in [0, seconds)."""
    if rate_per_s <= 0:
        raise ValueError(f"rate must be positive, got {rate_per_s}")
    n = int(round(rate_per_s * seconds))
    return np.sort(rng.uniform(0.0, seconds, size=n))


def _settle(requests, deadline: float) -> None:
    for r in requests:
        r.event.wait(max(0.0, deadline - time.perf_counter()))


def run_open(runtime, queries: np.ndarray, mix: dict, seconds: float,
             rng: np.random.Generator, grace_s: float) -> Window:
    offsets = schedule(mix["rate_per_s"], seconds, rng)
    reqs = []
    sent = np.empty(len(offsets))
    t0 = time.perf_counter()
    for j, off in enumerate(offsets):
        delay = t0 + off - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        sent[j] = time.perf_counter()
        reqs.append(runtime.submit(queries[j]))
    t_end = t0 + seconds
    delay = t_end - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    _settle(reqs, t_end + grace_s)
    return Window(t0, t_end, t0 + offsets, sent, reqs,
                  len(queries) - len(reqs))


def run_closed(runtime, queries: np.ndarray, mix: dict, seconds: float,
               rng: np.random.Generator, grace_s: float) -> Window:
    del rng                      # the order is the pool's own
    conc = int(mix["concurrency"])
    reqs, sent = [], []
    outstanding = collections.deque()
    t0 = time.perf_counter()
    t_end = t0 + seconds

    def send():
        sent.append(time.perf_counter())
        r = runtime.submit(queries[len(reqs)])
        reqs.append(r)
        outstanding.append(r)

    for _ in range(min(conc, len(queries))):
        send()
    while outstanding:
        head = outstanding[0]
        if not head.event.wait(max(0.0, t_end - time.perf_counter())):
            break                # the window closed with requests in flight
        outstanding.popleft()
        if time.perf_counter() >= t_end:
            break
        if len(reqs) < len(queries):
            send()
    _settle(reqs, t_end + grace_s)
    sent = np.asarray(sent)
    return Window(t0, t_end, sent.copy(), sent, reqs,
                  len(queries) - len(reqs))


def nearest_rank(values, q: float) -> float:
    """The q-th quantile by nearest rank (a missing answer is +inf)."""
    v = np.sort(np.asarray(values, float))
    if v.size == 0:
        return float("nan")
    return float(v[max(0, int(np.ceil(q * v.size)) - 1)])


KINDS = {"open": run_open, "closed": run_closed}


def drive(runtime, queries: np.ndarray, mix: dict, seconds: float,
          rng: np.random.Generator, grace_s: float = 60.0) -> Window:
    return KINDS[mix["kind"]](runtime, queries, mix, seconds, rng, grace_s)
