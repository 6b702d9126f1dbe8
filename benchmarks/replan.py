"""Blue/green replan benchmark: a live deployment, driven at a steady
open-loop arrival rate, survives a CONTROLLER-initiated blue/green swap.

Setup: a per-row-lowered GPU chain (the live plan cannot express
batching) with a synthetic profile that saturates per-row at the driven
rate while the batched path is comfortably cheap — so ``SLOController``
must escalate a compile-time replan.  Its default
:class:`~repro.profiling.replan.BlueGreenReplanner` then compiles the
batched green plan off the hot path, pre-warms every (chain, bucket)
executable through the shared ``EXECUTABLE_CACHE``, canary-verifies, and
atomically swaps generations — all while the Poisson driver keeps
sending.

Measured and asserted (``BENCH_replan.json``):

* **zero dropped / errored requests** across the swap — in-flight
  requests finish on blue, new requests route to green, retired batchers
  drain on quiescence;
* **zero executable re-traces after the swap** — the cache trace counter
  is flat from swap-end to run-end (the warm phase paid them off-path);
* **during-swap p99 within 2x steady-state p99** — the swap window is
  the WHOLE controller escalation (compile + warm + canary + swap), the
  most honest accounting of what traffic experiences.

Network costs are simulated at scale=0 (single host); the effects under
test are generation handoff, cache warming and drain behavior, not
transfer time.
"""
from __future__ import annotations

import gc
import json
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.common import percentile, row

import jax
import jax.numpy as jnp

SLO_MS = 50.0


def _m1(x: "jax.Array") -> "jax.Array":
    return x * 2.0


def _m2(x: "jax.Array") -> "jax.Array":
    return x + 1.0


def _build_flow():
    from repro.core.dataflow import Dataflow
    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(_m1, names=["x"], gpu=True, batching=True) \
        .map(_m2, names=["x"], gpu=True, batching=True)
    return fl


def _sample():
    from repro.core.table import Table
    return Table([("x", jax.Array)], [(jnp.ones(32, jnp.float32),)])


def _forcing_profile(op_id: int):
    """A curve under which per-row lowering saturates at the driven rate
    while batching is cheap: the optimizer MUST propose the batched flip,
    which needs a recompile — exactly the escalation under test.  (The
    swap mechanics being measured — drops, traces, during-swap p99 — are
    all real; only the planning signal is synthetic.)"""
    from repro.profiling import BucketStats, FlowProfile, OpLatencyCurve
    c = OpLatencyCurve(key=op_id, name="chain", per_row_s=8e-3)
    for b in (1, 2, 4, 8, 16):
        c.buckets[b] = BucketStats(mean_s=1e-3 + 5e-5 * b,
                                   p99_s=1.5e-3 + 7e-5 * b,
                                   cv=0.05, runs=3, out_bytes=256 * b)
    return FlowProfile(curves={op_id: c})


def _drive(dep, rate_hz: float, stop: threading.Event, seed: int = 0):
    """Open-loop Poisson driver; returns the recorder state it appends to:
    (t_send_rel, latency_s, ok) per completed request + a sent counter."""
    records: List[Tuple[float, float, bool]] = []
    lock = threading.Lock()
    sent = [0]
    pending: List = []

    def loop():
        rng = np.random.default_rng(seed)
        t0 = time.perf_counter()
        next_t = t0
        while not stop.is_set():
            next_t += rng.exponential(1.0 / rate_hz)
            now = time.perf_counter()
            if next_t > now:
                time.sleep(next_t - now)
            t_send = time.perf_counter()
            fut = dep.execute(_sample())
            sent[0] += 1

            def cb(f, t_send=t_send):
                ok = True
                try:
                    if f.exception() is not None:
                        ok = False
                except BaseException:
                    ok = False
                with lock:
                    records.append((t_send - t0, time.perf_counter()
                                    - t_send, ok))
            fut.add_done_callback(cb)
            pending.append(fut)

    th = threading.Thread(target=loop, daemon=True)
    return th, records, lock, sent, pending


def run(duration_s: float = 8.0, rate_hz: float = 100.0,
        json_path: Optional[str] = None) -> List[str]:
    from repro.core.lowering import (EXECUTABLE_CACHE, BatchedJittedFuse,
                                    JittedFuse)
    from repro.profiling import SLOController
    from repro.runtime.netmodel import NetModel
    from repro.runtime.runtime import Runtime

    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0),
                 max_batch=8, batch_wait_ms=2.0)
    rows: List[str] = []
    try:
        fl = _build_flow()
        dep = fl.deploy(rt, fusion=True, batched_lowering=False,
                        name="replan_bench")
        node = next(n for n in dep.dag.nodes.values() if n.batching)
        op_id = node.plan_op_id
        assert isinstance(dep.plan.op(op_id).op, JittedFuse)
        ctl = SLOController(rt, dep, slo_p99_s=SLO_MS / 1e3,
                            profile=_forcing_profile(op_id),
                            window_s=2.0, min_rate=1.0,
                            replan_sample=_sample())
        for _ in range(4):                  # warm blue off the clock
            dep.execute(_sample()).result(timeout=30)

        stop = threading.Event()
        th, records, lock, sent, pending = _drive(dep, rate_hz, stop)
        steady_s = duration_s * 0.4
        # a gen-2 GC pause mid-run reads as a fake p99 outlier on either
        # side of the ratio: collect now, hold collection during the drive
        gc.collect()
        gc.disable()
        try:
            th.start()
            t0 = time.perf_counter()
            time.sleep(steady_s)

            # the controller tick that escalates: compile + warm +
            # canary + swap all happen inside, driver still sending
            swap_t0 = time.perf_counter() - t0
            ev = ctl.tick()
            swap_t1 = time.perf_counter() - t0
            report = ev.detail.get("replan_report", {})
            traces_post_swap = EXECUTABLE_CACHE.traces()
            swapped = bool(report.get("ok"))
            batched_now = isinstance(dep.plan.op(op_id).op,
                                     BatchedJittedFuse)

            time.sleep(duration_s - steady_s)
            stop.set()
            th.join(timeout=5)
            for f in list(pending):         # wait out every in-flight
                try:
                    f.result(timeout=30)
                except BaseException:
                    pass
        finally:
            gc.enable()
        traces_end = EXECUTABLE_CACHE.traces()
        confirm_ev = ctl.tick()             # post-swap SLO confirmation

        with lock:
            recs = list(records)
        dropped = sent[0] - len(recs)
        errors = sum(1 for _, _, ok in recs if not ok)
        during = sorted(lat for t, lat, ok in recs
                        if ok and swap_t0 <= t <= swap_t1)
        steady = sorted(lat for t, lat, ok in recs
                        if ok and not (swap_t0 <= t <= swap_t1))
        blue_phase = sorted(lat for t, lat, ok in recs
                            if ok and t < swap_t0)
        green_phase = sorted(lat for t, lat, ok in recs
                             if ok and t > swap_t1)
        p99_steady = percentile(steady, 99) if steady else float("nan")
        p99_during = percentile(during, 99) if during else None
        ratio = (p99_during / p99_steady
                 if during and p99_steady > 0 else None)
        retraces_post_swap = traces_end - traces_post_swap

        result = {
            "suite": "replan",
            "pipeline": "jit[m1,m2](gpu, per-row) -> swap -> vjit[m1,m2]",
            "rate_hz": rate_hz,
            "duration_s": duration_s,
            "slo_ms": SLO_MS,
            "requests_sent": sent[0],
            "requests_completed": len(recs),
            "dropped": dropped,
            "errors": errors,
            "swapped": swapped,
            "batched_after_swap": batched_now,
            "escalation_kind": ev.kind,
            "swap_window_s": swap_t1 - swap_t0,
            "p50_steady_ms": (percentile(steady, 50) * 1e3
                              if steady else None),
            "p99_steady_ms": p99_steady * 1e3 if steady else None,
            "p99_during_swap_ms": (p99_during * 1e3
                                   if p99_during is not None else None),
            "during_over_steady_p99": ratio,
            "during_requests": len(during),
            # the honest segmentation: blue is what traffic experienced
            # under the config being replanned AWAY; green is the payoff
            "p99_blue_phase_ms": (percentile(blue_phase, 99) * 1e3
                                  if blue_phase else None),
            "p99_green_phase_ms": (percentile(green_phase, 99) * 1e3
                                   if green_phase else None),
            "p50_green_phase_ms": (percentile(green_phase, 50) * 1e3
                                   if green_phase else None),
            "retraces_post_swap": retraces_post_swap,
            "post_replan_confirm": confirm_ev.detail.get(
                "post_replan_confirm"),
            "replan_report": report,
            "cache_stats": EXECUTABLE_CACHE.stats(),
        }
        if json_path:
            with open(json_path, "w") as f:
                json.dump(result, f, indent=1, sort_keys=True,
                          default=str)

        rows.append(row("replan_steady", p99_steady * 1e3 * 1e3
                        if steady else 0.0,
                        f"p99={p99_steady*1e3:.1f}ms "
                        f"n={len(steady)}" if steady else "no data"))
        rows.append(row("replan_during_swap",
                        (p99_during or 0.0) * 1e6,
                        f"p99={(p99_during or 0)*1e3:.1f}ms "
                        f"ratio={ratio if ratio is None else round(ratio, 2)} "
                        f"window={swap_t1-swap_t0:.2f}s "
                        f"n={len(during)}"))
        rows.append(row("replan_integrity", float(errors + dropped),
                        f"dropped={dropped} errors={errors} "
                        f"retraces_post_swap={retraces_post_swap} "
                        f"swapped={swapped}"))
        return rows
    finally:
        rt.stop()
        time.sleep(0.3)


def check_flows():
    """Static-verifier hook (``python -m repro.check``)."""
    return [{"name": "replan", "flow": _build_flow(),
             "compile": {"fusion": True, "batched_lowering": False},
             "sample": _sample()}]
