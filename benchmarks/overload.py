"""Overload-protection benchmark: offered-load sweep across the
admission gate (``repro.serving.admission``) + deadline-aware batching.

The serving chain is the smallest shape that exercises every layer the
protection stack touches:

* a fused, batched-jitted GPU pair (``BatchedJittedFuse``) — the stage
  whose executable-cache behaviour we account for (degraded requests
  route to its per-row variant; padding buckets bound recompiles);
* a CPU map with a fixed per-row service time — the *deliberate*
  bottleneck, so capacity is known in closed form
  (``n_cpu / SERVICE_S``) and "3x capacity" means what it says.

Two request classes share the deployment, the canonical protected/
sheddable split:

* ``interactive`` (priority 2, deadline = SLO): never shed, never
  degraded — the class the gate exists to protect;
* ``best_effort`` (priority 0, token bucket at 10% of capacity, a tight
  deadline, a ``DegradePolicy``): degrades first, sheds first.

For each multiplier in the sweep an open-loop Poisson-free paced driver
offers ``mult * capacity`` req/s for ``duration_s`` (open loop: arrival
times never wait on completions — the backlog is real).  Per point we
report per-class goodput / p50 / p99, shed + degrade + expiry counts,
and four integrity signals the CI gate asserts on at 3x:

* ``shed_fail_p99_ms`` — sheds must fail in a fraction of the SLO
  budget (fast-fail, not queue-then-die);
* ``expired_overrun_p99_ms`` — p99 of (failure latency − own deadline)
  for expired requests: expiry is detected promptly after the deadline
  passes, not discovered at dispatch minutes later;
* ``drained`` — every batcher returns to quiescent after the burst (no
  wedged accounting);
* ``reconciled`` — gate counters agree with observed outcomes:
  offered == admitted + degraded + shed, and every offered request
  resolved exactly once (ok | shed | expired), zero untyped errors.

``retraces_post_warm`` (top level) counts executable-cache traces taken
during the sweep itself, after a short warm-up burst: degraded serving
must route to *already-compiled* variants, never pay XLA tracing on the
overloaded hot path.
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
BE_DEADLINE_S = 0.05      # best_effort deadline: tight by design
INTERACTIVE_EVERY = 5     # 20% of offered traffic is interactive


def _g1(x: "jax.Array") -> "jax.Array":
    return x * 2.0


def _g2(x: "jax.Array") -> "jax.Array":
    return x + 1.0


def _cpu_slow(x: "jax.Array") -> "jax.Array":
    time.sleep(SERVICE_S)
    # re-assert device type: the upstream batched gpu stage can hand
    # rows across the host boundary as numpy after unpadding
    return jnp.asarray(x)


def _build_flow():
    from repro.core.dataflow import Dataflow
    fl = Dataflow([("x", jax.Array)])
    # two gpu maps fuse + lower to one BatchedJittedFuse; the cpu sleep
    # map stays un-fused (placement mismatch) and un-jitted (cpu-placed)
    fl.output = fl.map(_g1, names=["x"], gpu=True, batching=True) \
        .map(_g2, names=["x"], gpu=True, batching=True) \
        .map(_cpu_slow, names=["x"], batching=True)
    return fl


def _sample():
    from repro.core.table import Table
    return Table([("x", jax.Array)], [(jnp.ones(8, jnp.float32),)])


def _make_admission(dep, rt):
    """An honest gate: per-op curves matching what each op actually
    costs, so the M/M/c estimate — and therefore every shed/degrade
    decision in the sweep — comes from the real critical path."""
    from repro.core.lowering import DegradePolicy
    from repro.profiling import (BucketStats, FlowProfile, NodeConfig,
                                 OpLatencyCurve, PlanConfig)
    from repro.serving.admission import AdmissionController, ClassPolicy
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
            max_batch=4, batch_wait_ms=2.0, batched_lowering=True,
            target_replicas=N_CPU)
    classes = {
        "interactive": ClassPolicy("interactive", priority=2,
                                   default_deadline_s=SLO_S),
        # the bucket sits ABOVE capacity's best_effort share so the
        # estimator — not a static rate cap — is the binding constraint
        # under overload: we want to see degrade + deadline expiry, not
        # just rate_limit sheds
        "best_effort": ClassPolicy(
            "best_effort", priority=0,
            rate=0.75 * (N_CPU / SERVICE_S), burst=20,
            degrade=DegradePolicy(per_row=True, bucket_cap=4),
            default_deadline_s=BE_DEADLINE_S),
    }
    return AdmissionController(dep.plan, FlowProfile(curves=curves), cfg,
                               net=rt.net, classes=classes)


def _drive_point(rt, name: str, rate_hz: float, duration_s: float):
    """Open-loop paced driver for one sweep point.  Outcomes/latencies
    are recorded by done-callbacks registered AT SEND TIME (a post-hoc
    collection loop would time future-resolution, not request latency)."""
    from repro.serving.admission import DeadlineExceeded, Overloaded
    lock = threading.Lock()
    lat: Dict[str, List[float]] = {"interactive": [], "best_effort": []}
    shed_fail: List[float] = []
    expired_overrun: Dict[str, List[float]] = {
        "interactive": [], "best_effort": []}
    counts = {k: {"sent": 0, "ok": 0, "shed": 0, "expired": 0,
                  "errors": 0}
              for k in ("interactive", "best_effort")}
    deadline_of = {"interactive": SLO_S, "best_effort": BE_DEADLINE_S}
    futs = []
    i = 0
    t_start = time.perf_counter()
    while time.perf_counter() - t_start < duration_s:
        klass = ("interactive" if i % INTERACTIVE_EVERY == 0
                 else "best_effort")
        t_send = time.perf_counter()
        f = rt.call_dag(name, _sample(), klass=klass)
        counts[klass]["sent"] += 1

        def _done(fut, t0=t_send, k=klass):
            dt = time.perf_counter() - t0
            try:
                exc = fut.exception()
            except BaseException as e:   # pragma: no cover
                exc = e
            with lock:
                if exc is None:
                    counts[k]["ok"] += 1
                    lat[k].append(dt)
                elif isinstance(exc, DeadlineExceeded):
                    counts[k]["expired"] += 1
                    expired_overrun[k].append(dt - deadline_of[k])
                elif isinstance(exc, Overloaded):
                    counts[k]["shed"] += 1
                    shed_fail.append(dt)
                else:
                    counts[k]["errors"] += 1
        f.add_done_callback(_done)
        futs.append(f)
        i += 1
        # open loop: pace arrivals off the wall clock, never completions
        next_t = t_start + i / rate_hz
        pause = next_t - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
    for f in futs:                      # wait out every in-flight future
        try:
            f.result(timeout=30)
        except BaseException:
            pass
    return lock, lat, shed_fail, expired_overrun, counts


def _drained(rt, timeout_s: float = 10.0):
    """(drained?, seconds-to-drain): every batcher back to quiescent."""
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout_s:
        with rt._batchers_lock:
            bs = list(rt._batchers.values())
        if all(b.quiescent() for b in bs):
            return True, time.perf_counter() - t0
        time.sleep(0.02)
    return False, time.perf_counter() - t0


def run(duration_s: float = 2.5,
        multipliers=(0.5, 1.0, 2.0, 3.0),
        json_path: Optional[str] = None) -> List[str]:
    from repro.core.lowering import EXECUTABLE_CACHE, BatchedJittedFuse
    from repro.runtime.netmodel import NetModel
    from repro.runtime.runtime import Runtime

    capacity = N_CPU / SERVICE_S
    rt = Runtime(n_cpu=N_CPU, n_gpu=1, net=NetModel(scale=0.0),
                 max_batch=4, batch_wait_ms=2.0)
    rows: List[str] = []
    try:
        fl = _build_flow()
        dep = fl.deploy(rt, fusion=True, name="overload_bench")
        assert any(isinstance(o.op, BatchedJittedFuse)
                   for o in dep.plan.ops), "gpu pair did not lower"
        adm = _make_admission(dep, rt)
        rt.set_admission("overload_bench", adm)

        # warm every executable variant the sweep can touch (batch
        # padding buckets AND the degraded per-row route) with a short
        # off-the-clock burst, then snapshot the trace counter: any
        # trace taken DURING the sweep is a protection failure
        for _ in range(4):
            rt.call_dag("overload_bench", _sample(),
                        klass="interactive").result(timeout=30)
        _drive_point(rt, "overload_bench", 3.0 * capacity, 0.4)
        _drained(rt)
        rt.set_admission("overload_bench", None)
        traces_warm = EXECUTABLE_CACHE.traces()

        points = []
        gc.collect()
        for mult in multipliers:
            # a fresh gate per point: token buckets, arrival-rate window
            # and counters all start clean, so points are independent
            adm = _make_admission(dep, rt)
            rt.set_admission("overload_bench", adm)
            gc.collect()
            # a gen-2 GC pause mid-drive reads as a fake p99 outlier:
            # collect now, hold collection during the drive
            gc.disable()
            try:
                lock, lat, shed_fail, over, counts = _drive_point(
                    rt, "overload_bench", mult * capacity, duration_s)
            finally:
                gc.enable()
            drained, drain_s = _drained(rt)

            with lock:
                gate = adm.snapshot()
                ga = sum(v for k, v in gate.items()
                         if k.endswith("/admitted"))
                gd = sum(v for k, v in gate.items()
                         if k.endswith("/degraded"))
                gs = sum(v for k, v in gate.items()
                         if k.endswith("/shed"))
                go = sum(v for k, v in gate.items()
                         if k.endswith("/offered"))
                sent = sum(c["sent"] for c in counts.values())
                oks = sum(c["ok"] for c in counts.values())
                sheds = sum(c["shed"] for c in counts.values())
                expd = sum(c["expired"] for c in counts.values())
                errs = sum(c["errors"] for c in counts.values())
                reconciled = (go == sent
                              and ga + gd + gs == go
                              and gs == sheds
                              and ga + gd == oks + expd
                              and oks + sheds + expd + errs == sent)
                classes = {}
                for k, c in counts.items():
                    ls = sorted(lat[k])
                    classes[k] = {
                        **c,
                        "p50_ms": (percentile(ls, 50) * 1e3
                                   if ls else None),
                        "p99_ms": (percentile(ls, 99) * 1e3
                                   if ls else None),
                        "goodput_rps": c["ok"] / duration_s,
                        "served_frac": (c["ok"] / c["sent"]
                                        if c["sent"] else None),
                    }
                all_over = over["interactive"] + over["best_effort"]
                point = {
                    "multiplier": mult,
                    "offered_rps_target": mult * capacity,
                    "offered": sent,
                    "duration_s": duration_s,
                    "classes": classes,
                    "admitted": ga, "degraded": gd, "shed": gs,
                    "shed_fail_p99_ms": (percentile(sorted(shed_fail),
                                                    99) * 1e3
                                         if shed_fail else None),
                    "expired_overrun_p99_ms": (
                        percentile(sorted(all_over), 99) * 1e3
                        if all_over else None),
                    "errors": errs,
                    "drained": drained,
                    "drain_s": drain_s,
                    "reconciled": reconciled,
                }
            points.append(point)
            rt.set_admission("overload_bench", None)

            ip99 = classes["interactive"]["p99_ms"]
            rows.append(row(
                f"overload_{mult:g}x",
                (ip99 or 0.0) * 1e3,
                f"interactive p99={ip99 if ip99 is None else round(ip99, 1)}ms "
                f"goodput={classes['interactive']['goodput_rps']:.0f}rps "
                f"degraded={gd} shed={gs} expired={expd} "
                f"drained={drained}"))

        retraces = EXECUTABLE_CACHE.traces() - traces_warm
        bad = sum(1 for p in points
                  if not (p["drained"] and p["reconciled"]
                          and p["errors"] == 0))
        rows.append(row(
            "overload_integrity", float(bad + retraces),
            f"bad_points={bad} retraces_post_warm={retraces} "
            f"points={len(points)}"))

        result = {
            "suite": "overload",
            "pipeline": ("vjit[g1,g2](gpu, batched) -> "
                         f"cpu_sleep({SERVICE_S * 1e3:.0f}ms/row)"),
            "capacity_rps": capacity,
            "service_ms": SERVICE_S * 1e3,
            "slo_ms": SLO_S * 1e3,
            "best_effort_deadline_ms": BE_DEADLINE_S * 1e3,
            "interactive_share": 1.0 / INTERACTIVE_EVERY,
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
    return [{"name": "overload", "flow": _build_flow(),
             "compile": {"fusion": True}, "sample": _sample(),
             "max_batch": 4}]
