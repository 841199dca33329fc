"""The one traffic generator: every mix is a data file of parameters.

``traffic/<mix>.json`` names a closed loop and its parameters:
``{"loop": "closed", "clients_per_chip": C, "flows_per_request": F,
"templates": T}``. ``C`` clients per chip of the cell each send
``F``-flow requests and wait for the verdict before they send the next. The requests' rows are ``T`` fixed draws of ``F`` rows from
the flow pool; client ``c``'s ``j``-th request takes template
``(c + j * clients) % T``.

The comparison with the reference takes about ``S`` of the window's
requests with their answers, whatever the rate (``S`` is the
configuration's ``check.sample_requests``, sized to what its reference
costs): each client keeps a uniform sample of ``ceil(S / clients)`` of its
own requests (reservoir sampling from a generator seeded by the seed and the
client's number), so the sample spreads over every client, every stream
they reach and the whole window.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

_MASK = (1 << 64) - 1


def mix64(x: int) -> int:
    """splitmix64's finaliser: a well-spread hash of one integer."""
    x = (x + 0x9E3779B97F4A7C15) & _MASK
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK
    return x ^ (x >> 31)


def seed31(seed: int) -> int:
    """A 31-bit key for ``jax.random.PRNGKey`` from any whole-number seed,
    however large."""
    return mix64(seed & _MASK ^ mix64(seed >> 64)) & 0x7FFFFFFF


def closed_templates(traffic: dict, seed: int, pool_rows: int) -> np.ndarray:
    """``[T, F]`` row indices of the closed loop's request templates."""
    rng = np.random.default_rng([seed31(seed), 0xC105ED])
    return rng.integers(0, pool_rows, (int(traffic["templates"]),
                                      int(traffic["flows_per_request"])))


def warm_sizes(traffic: dict, max_batch: int) -> list[int]:
    """The request sizes the mix sends, cut to the server's chunk."""
    return [min(int(traffic["flows_per_request"]), max_batch)]


class Reservoir:
    """A uniform sample of ``k`` items of a stream of unknown length
    (Algorithm R), drawn from its own seeded generator."""

    def __init__(self, k: int, seed: list[int]):
        self.k, self.n = k, 0
        self.items: list = []
        self.rng = np.random.default_rng(seed)

    def offer(self, item) -> None:
        if self.n < self.k:
            self.items.append(item)
        else:
            r = int(self.rng.integers(0, self.n + 1))
            if r < self.k:
                self.items[r] = item
        self.n += 1


class Recorder:
    """Per-request records, appended from several threads."""

    def __init__(self):
        self.lock = threading.Lock()
        self.rows: list = []      # (rid, sent, done, flows, ok, qwait_ms)
        self.submit_s: list = []
        self.reservoirs: list[Reservoir] = []

    def samples(self) -> dict:
        """``rid -> (request rows, output)`` of every client's sample."""
        return {rid: s for r in self.reservoirs for rid, s in r.items}


def run_closed(submit, clients: int, x_pool: np.ndarray,
               templates: np.ndarray, seed: int, sample: int, t_stop_event,
               rec: Recorder, annotate) -> list[threading.Thread]:
    """Start the closed-loop clients; each runs until ``t_stop_event`` and
    keeps about ``sample / clients`` of its requests for the check."""
    per_client = max(1, math.ceil(sample / clients))
    tmpl = [x_pool[t] for t in templates]
    rec.reservoirs = [Reservoir(per_client, [seed31(seed), 0x5A3B, ci])
                      for ci in range(clients)]

    def client(ci: int) -> None:
        keep = rec.reservoirs[ci]
        j = 0
        while not t_stop_event.is_set():
            rid = ci * 10_000_000 + j
            ti = (ci + j * clients) % len(tmpl)
            sent = time.perf_counter()
            with annotate("bench.submit"):
                fut = submit(tmpl[ti])
            s1 = time.perf_counter()
            try:
                res = fut.result()
                ok, out, qw = True, res.output, res.queue_wait_ms
            except Exception:
                ok, out, qw = False, None, None
            done = time.perf_counter()
            keep.offer((rid, (templates[ti], out)))
            with rec.lock:
                rec.rows.append((rid, sent, done, len(tmpl[ti]), ok, qw))
                rec.submit_s.append(s1 - sent)
            j += 1

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"bench-client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    return threads
