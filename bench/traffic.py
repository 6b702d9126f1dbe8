"""The general load generator.  A cell's file gives its parameters; this
module turns them into requests and drives them.

* Prompts: request ``i`` of seed ``s`` carries ``prompt(s, i, ...)``,
  seeded random token ids, so any request can be rebuilt for the check.
* Open loop: Poisson arrivals at a fixed rate.  The inter-arrival gaps
  are the exponential distribution's quantiles at (k + 0.5) / n, k < n,
  in an order drawn once from the cell's ``arrival_seed``, not from the
  run's seed: at 0.8 x the knee the queue's tail depends on where the
  short gaps cluster, and an order drawn per run spread p95 by a fifth
  to a third across seeds.  Each request is timed from when it was DUE,
  so a late generator or a stall is charged to every request it delays;
  how late the generator sent is reported.
* Closed loop: a fixed number of clients, each sending its next request
  when the last one's tokens arrive.

A request is done when its output is on the host: per-row results come
back as device arrays that may still be computing, so a receiver thread
waits for each result and stamps it then.
"""
import dataclasses
import gc
import itertools
import queue
import threading
import time
from typing import Any, Callable, List, Optional

import jax
import numpy as np

WAIT_AFTER_S = 60.0


@dataclasses.dataclass
class Request:
    idx: int
    due: float = 0.0
    sent: float = 0.0
    done: Optional[float] = None
    out: Any = None
    error: Optional[str] = None
    batched: bool = False


def prompt(seed: int, idx: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``idx`` (>= -2; the warm-up uses -1 and -2)."""
    rng = np.random.default_rng([int(seed), 7, idx + 2])
    return rng.integers(0, vocab, length, dtype=np.int32)


def poisson_dues(rate: float, seconds: float,
                 arrival_seed: int) -> np.ndarray:
    """Due times in [0, seconds) of round(rate * seconds) arrivals."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng([int(arrival_seed), 0]).permutation(gaps)
    starts = np.cumsum(gaps) - gaps + 0.5 * gaps[0]
    return starts * (seconds / (gaps.sum() + gaps.max()))


class Receiver:
    """Stamps each request when its output is on the host."""

    def __init__(self):
        self.q: "queue.Queue" = queue.Queue()
        self.t = threading.Thread(target=self._loop, daemon=True)
        self.t.start()

    def _loop(self):
        while True:
            item = self.q.get()
            if item is None:
                return
            req, fut = item
            with jax.profiler.TraceAnnotation("bench.result"):
                finish(req, fut)

    def put(self, req: Request, fut) -> None:
        fut.add_done_callback(lambda f: self.q.put((req, f)))

    def close(self):
        self.q.put(None)
        self.t.join()


def finish(req: Request, fut) -> None:
    """Wait for the output on the host and stamp it.  A batched dispatch
    gathers its rows to the host before it answers; the one-row path
    answers with device arrays: that is how a request's path is known."""
    try:
        out = fut.result()
        req.batched = not any(isinstance(v, jax.Array)
                              for v in out.rows[0].values)
        req.out = jax.device_get(out.rows[0].values)
        req.done = time.perf_counter()
    except Exception as e:              # a failed request stays un-done
        req.error = f"{type(e).__name__}: {e}"[:300]


def open_loop(send: Callable[[int], Any], rate: float, seconds: float,
              arrival_seed: int, t0: float) -> List[Request]:
    """Send on the schedule from ``t0``; returns once every request is
    done or failed, or ``WAIT_AFTER_S`` after the window closed."""
    dues = poisson_dues(rate, seconds, arrival_seed)
    reqs = [Request(i, due=t0 + float(d)) for i, d in enumerate(dues)]
    recv = Receiver()
    futs = []
    gc.collect()
    gc.disable()
    try:
        for r in reqs:
            wait = r.due - time.perf_counter()
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(wait)
            with jax.profiler.TraceAnnotation("bench.send"):
                r.sent = time.perf_counter()
                fut = send(r.idx)
            recv.put(r, fut)
            futs.append(fut)
        _wait_all(futs, t0 + seconds + WAIT_AFTER_S)
    finally:
        recv.close()
        gc.enable()
    return reqs


def closed_loop(send: Callable[[int], Any], clients: int, seconds: float,
                t0: float) -> List[Request]:
    """``clients`` threads, each sending its next request when the last
    one's output arrived, until ``t0 + seconds``."""
    reqs: List[Request] = []
    lock = threading.Lock()
    counter = itertools.count()
    t_end = t0 + seconds

    def client():
        while time.perf_counter() < t_end:
            r = Request(next(counter))
            with jax.profiler.TraceAnnotation("bench.send"):
                r.sent = r.due = time.perf_counter()
                fut = send(r.idx)
            with lock:
                reqs.append(r)
            with jax.profiler.TraceAnnotation("bench.wait"):
                _wait_all([fut], t_end + WAIT_AFTER_S)
                finish(r, fut)
            if r.done is None:
                return

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    gc.collect()
    gc.disable()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(0.0, t_end + WAIT_AFTER_S
                               - time.perf_counter()) + 5.0)
    finally:
        gc.enable()
    return sorted(reqs, key=lambda r: r.idx)


def _wait_all(futs, deadline: float) -> None:
    for f in futs:
        try:
            f.exception(timeout=max(0.0, deadline - time.perf_counter()))
        except Exception:
            return
