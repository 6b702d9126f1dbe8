"""Chaos benchmark: the serving chain under injected executor faults.

The same pipeline as the overload benchmark (a batched-jitted GPU pair
feeding a fixed-service-time CPU bottleneck, so capacity is known in
closed form) is driven OPEN LOOP at half capacity — comfortably inside
the envelope, so every latency/outcome effect in the sweep is caused by
the injected faults, not by saturation.  Each sweep point installs a
seeded :class:`~repro.serving.faults.FaultPlan` applying crash + straggle
+ transient faults, each at the point's per-kind rate (so the labeled
rate triples when combined), with straggler hedging armed from the same
latency curves the admission gate models with.

What the CI gate asserts, per point:

* **zero hangs** — every offered request resolves with a TYPED outcome
  (ok | shed | expired | transient-failure) inside the driver's timeout:
  ``unresolved == 0`` and ``untyped_errors == 0`` even at the highest
  fault rate;
* **reconciliation** — ``offered == ok + shed + expired + failed``; the
  fault counters (injected vs detected crashes, retries, hedges) are
  internally consistent; every batcher returns to quiescent
  (``drained``), i.e. accepted-minus-completed accounting survived every
  crash/requeue/hedge path;
* **SLO under low fault rate** — interactive p99 stays inside the SLO
  at the low-fault point: recovery (redispatch + hedging) absorbs
  occasional faults without blowing the tail;
* **zero re-traces** — fault recovery re-executes already-compiled
  executables; no XLA tracing on the hot path;
* **no fault-free regression** — the 0-rate point's p50 is the price of
  the fault-tolerance machinery itself (tokens, idempotence journal,
  hedge timers); CI compares it against the overload benchmark's 0.5x
  point.
"""
from __future__ import annotations

import gc
import json
import threading
import time
from typing import Dict, List, Optional

from benchmarks.common import percentile, row

import jax
import jax.numpy as jnp

SERVICE_S = 0.01          # per-row service time of the CPU bottleneck
N_CPU = 2                 # capacity = N_CPU / SERVICE_S = 200 rows/s
SLO_S = 0.6               # interactive deadline == the SLO under test
OFFERED_FRAC = 0.5        # drive at half capacity: faults, not overload
HANG_S = 0.25             # injected straggle duration
HEDGE_FACTOR = 3.0        # hedge once past 3x the bottleneck's p99
MAX_BATCH = 4


def _g1(x: "jax.Array") -> "jax.Array":
    return x * 2.0


def _g2(x: "jax.Array") -> "jax.Array":
    return x + 1.0


def _cpu_slow(x: "jax.Array") -> "jax.Array":
    time.sleep(SERVICE_S)
    return jnp.asarray(x)


def _build_flow():
    from repro.core.dataflow import Dataflow
    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(_g1, names=["x"], gpu=True, batching=True) \
        .map(_g2, names=["x"], gpu=True, batching=True) \
        .map(_cpu_slow, names=["x"], batching=True)
    return fl


def _sample():
    from repro.core.table import Table
    return Table([("x", jax.Array)], [(jnp.ones(8, jnp.float32),)])


def _profile_and_config(dep):
    """Synthetic-but-honest curves matching what each op actually costs
    (the same construction the overload benchmark gates with): one
    source of truth for the admission estimate AND the hedge delays."""
    from repro.profiling import (BucketStats, FlowProfile, NodeConfig,
                                 OpLatencyCurve, PlanConfig)
    curves = {}
    cfg = PlanConfig(nodes={})
    for o in dep.plan.ops:
        per_row = SERVICE_S if o.placement != "gpu" else 1e-4
        c = OpLatencyCurve(key=o.op_id, name=o.op.name, per_row_s=per_row)
        for bkt in (1, 2, 4):
            c.buckets[bkt] = BucketStats(
                mean_s=per_row * bkt, p99_s=per_row * bkt * 1.2,
                cv=0.05, runs=3, out_bytes=64 * bkt)
        curves[o.op_id] = c
        cfg.nodes[o.op_id] = NodeConfig(
            max_batch=MAX_BATCH, batch_wait_ms=2.0, batched_lowering=True,
            target_replicas=N_CPU)
    return FlowProfile(curves=curves), cfg


def _make_admission(dep, rt, profile, cfg):
    from repro.serving.admission import AdmissionController, ClassPolicy
    classes = {"interactive": ClassPolicy("interactive", priority=2,
                                          default_deadline_s=SLO_S)}
    return AdmissionController(dep.plan, profile, cfg, net=rt.net,
                               classes=classes)


def _drive_point(rt, name: str, rate_hz: float, duration_s: float):
    """Open-loop paced driver: outcomes recorded by done-callbacks
    registered at send time; ``unresolved`` counts futures that did not
    resolve inside the timeout — the hangs fault tolerance forbids."""
    from repro.serving.admission import DeadlineExceeded, Overloaded
    from repro.serving.retry import Transient
    lock = threading.Lock()
    lat: List[float] = []
    counts = {"sent": 0, "ok": 0, "shed": 0, "expired": 0, "failed": 0,
              "errors": 0, "unresolved": 0}
    futs = []
    i = 0
    t_start = time.perf_counter()
    while time.perf_counter() - t_start < duration_s:
        t_send = time.perf_counter()
        f = rt.call_dag(name, _sample(), klass="interactive")
        counts["sent"] += 1

        def _done(fut, t0=t_send):
            dt = time.perf_counter() - t0
            try:
                exc = fut.exception()
            except BaseException as e:   # pragma: no cover
                exc = e
            with lock:
                if exc is None:
                    counts["ok"] += 1
                    lat.append(dt)
                elif isinstance(exc, DeadlineExceeded):
                    counts["expired"] += 1
                elif isinstance(exc, Overloaded):
                    counts["shed"] += 1
                elif isinstance(exc, Transient):
                    # typed fault delivery: retries exhausted or no
                    # healthy replica in time — a FAILURE, but a typed,
                    # prompt one
                    counts["failed"] += 1
                else:
                    counts["errors"] += 1
        f.add_done_callback(_done)
        futs.append(f)
        i += 1
        next_t = t_start + i / rate_hz
        pause = next_t - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
    for f in futs:
        try:
            f.result(timeout=30)
        except BaseException:
            pass
    with lock:
        done = sum(counts[k] for k in
                   ("ok", "shed", "expired", "failed", "errors"))
        counts["unresolved"] = counts["sent"] - done
    return lock, lat, counts


def _drained(rt, timeout_s: float = 10.0):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout_s:
        with rt._batchers_lock:
            bs = list(rt._batchers.values())
        if all(b.quiescent() for b in bs):
            return True, time.perf_counter() - t0
        time.sleep(0.02)
    return False, time.perf_counter() - t0


def _series_count(rt, key: str) -> int:
    return len(rt.metrics_snapshot().get(key, []))


def run(duration_s: float = 2.5,
        rates=(0.0, 0.01, 0.02, 0.05),
        json_path: Optional[str] = None) -> List[str]:
    from repro.core.lowering import EXECUTABLE_CACHE, BatchedJittedFuse
    from repro.runtime.netmodel import NetModel
    from repro.runtime.runtime import Runtime
    from repro.serving.faults import FaultPlan, install_hedging

    capacity = N_CPU / SERVICE_S
    offered = OFFERED_FRAC * capacity
    rt = Runtime(n_cpu=N_CPU, n_gpu=1, net=NetModel(scale=0.0),
                 max_batch=MAX_BATCH, batch_wait_ms=2.0,
                 hang_timeout_s=2.0, detector_interval_s=0.02)
    rows: List[str] = []
    try:
        fl = _build_flow()
        dep = fl.deploy(rt, fusion=True, name="faults_bench")
        assert any(isinstance(o.op, BatchedJittedFuse)
                   for o in dep.plan.ops), "gpu pair did not lower"
        profile, cfg = _profile_and_config(dep)
        # straggler hedging from the SAME curves the gate models with;
        # delays sized for a full batch so healthy batches never hedge
        from repro.serving.faults import hedge_delays_from_profile
        delays = hedge_delays_from_profile(dep, profile,
                                           factor=HEDGE_FACTOR,
                                           batch=MAX_BATCH)
        for node_name, d in delays.items():
            rt.configure_hedging("faults_bench", node_name, d)

        # warm every executable variant off the clock, then snapshot the
        # trace counter: recovery re-executions must hit the cache
        for _ in range(4):
            rt.call_dag("faults_bench", _sample(),
                        klass="interactive").result(timeout=30)
        _drive_point(rt, "faults_bench", offered, 0.4)
        _drained(rt)
        traces_warm = EXECUTABLE_CACHE.traces()

        points = []
        gc.collect()
        for i, fr in enumerate(rates):
            adm = _make_admission(dep, rt, profile, cfg)
            rt.set_admission("faults_bench", adm)
            injector = None
            if fr > 0.0:
                injector = rt.set_fault_plan(
                    FaultPlan(seed=1000 + i)
                    .crash(rate=fr).hang(rate=fr, hang_s=HANG_S)
                    .transient(rate=fr))
            m0 = {k: _series_count(rt, k) for k in (
                "faults/crash_t", "faults/wedge_t", "faults/requeued_t",
                "dag/faults_bench/retry_t", "dag/faults_bench/hedge_t")}
            f0 = dict(rt.pool.fault_counts)
            gc.collect()
            gc.disable()
            try:
                lock, lat, counts = _drive_point(
                    rt, "faults_bench", offered, duration_s)
            finally:
                gc.enable()
            rt.set_fault_plan(None)
            drained, drain_s = _drained(rt)

            with lock:
                ls = sorted(lat)
                resolved_typed = (counts["unresolved"] == 0
                                  and counts["errors"] == 0)
                reconciled = (counts["ok"] + counts["shed"]
                              + counts["expired"] + counts["failed"]
                              == counts["sent"])
                point = {
                    "fault_rate_per_kind": fr,
                    "fault_rate_combined": 3 * fr,
                    "offered_rps_target": offered,
                    "duration_s": duration_s,
                    "counts": dict(counts),
                    "p50_ms": (percentile(ls, 50) * 1e3 if ls else None),
                    "p99_ms": (percentile(ls, 99) * 1e3 if ls else None),
                    "served_frac": (counts["ok"] / counts["sent"]
                                    if counts["sent"] else None),
                    "injected": (injector.snapshot() if injector
                                 else {"crash": 0, "hang": 0,
                                       "transient": 0}),
                    "detected": {
                        k: rt.pool.fault_counts[k] - f0[k]
                        for k in ("crash", "wedge", "requeued",
                                  "replaced", "lost")},
                    "crashes": (_series_count(rt, "faults/crash_t")
                                - m0["faults/crash_t"]),
                    "wedges": (_series_count(rt, "faults/wedge_t")
                               - m0["faults/wedge_t"]),
                    "retries": (_series_count(
                        rt, "dag/faults_bench/retry_t")
                        - m0["dag/faults_bench/retry_t"]),
                    "hedges": (_series_count(
                        rt, "dag/faults_bench/hedge_t")
                        - m0["dag/faults_bench/hedge_t"]),
                    "drained": drained,
                    "drain_s": drain_s,
                    "resolved_typed": resolved_typed,
                    "reconciled": reconciled,
                }
            points.append(point)
            rt.set_admission("faults_bench", None)

            rows.append(row(
                f"faults_{3 * fr:g}",
                (point["p99_ms"] or 0.0) * 1e3,
                f"p50={None if point['p50_ms'] is None else round(point['p50_ms'], 1)}ms "
                f"p99={None if point['p99_ms'] is None else round(point['p99_ms'], 1)}ms "
                f"crashes={point['crashes']} retries={point['retries']} "
                f"hedges={point['hedges']} failed={counts['failed']} "
                f"typed={resolved_typed} drained={drained}"))

        retraces = EXECUTABLE_CACHE.traces() - traces_warm
        bad = sum(1 for p in points
                  if not (p["drained"] and p["reconciled"]
                          and p["resolved_typed"]))
        rows.append(row(
            "faults_integrity", float(bad + retraces),
            f"bad_points={bad} retraces_post_warm={retraces} "
            f"points={len(points)}"))

        result = {
            "suite": "faults",
            "pipeline": ("vjit[g1,g2](gpu, batched) -> "
                         f"cpu_sleep({SERVICE_S * 1e3:.0f}ms/row)"),
            "capacity_rps": capacity,
            "offered_rps": offered,
            "slo_ms": SLO_S * 1e3,
            "hang_s": HANG_S,
            "hedge_factor": HEDGE_FACTOR,
            "hedge_delays_ms": {k: v * 1e3 for k, v in delays.items()},
            "duration_s_per_point": duration_s,
            "points": points,
            "retraces_post_warm": retraces,
            "cache_stats": EXECUTABLE_CACHE.stats(),
        }
        if json_path:
            with open(json_path, "w") as f:
                json.dump(result, f, indent=1, sort_keys=True,
                          default=str)
        return rows
    finally:
        rt.stop()
        time.sleep(0.3)


def check_flows():
    """Static-verifier hook (``python -m repro.check``)."""
    return [{"name": "faults", "flow": _build_flow(),
             "compile": {"fusion": True}, "sample": _sample(),
             "max_batch": MAX_BATCH}]
