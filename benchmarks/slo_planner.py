"""SLO planner benchmark: estimator accuracy + optimized-vs-default SLO
attainment under open-loop traffic.

Pipeline (video-analysis-ish shape): a compute-heavy CPU preprocessing
stage feeding a batched GPU-lowered model chain.  For each arrival rate:

1. the offline profiler sweeps the compiled plan, the estimator predicts
   the DEFAULT deployment's p50/p99 (replicas = the pool, the runtime's
   global batching knobs), and the prediction is compared against
   *measured* open-loop serve latencies -> ``rel_err_p50`` / ``rel_err_p99``;
2. ``optimizer.propose`` produces a ``PlanConfig`` for the SLO at that
   rate; a fresh deployment compiled with it (per-node buckets/windows,
   M/M/c replica targets pre-provisioned) is driven with the same traffic;
3. the artifact records measured p50/p99 and SLO attainment for both
   configs — the optimized config must beat the default where the default
   misses the SLO (saturated rates), and must not lose where it meets it.

Network costs are simulated at scale=0 (single host): the effects under
test are queueing, batching and replica provisioning, not transfer time.
"""
from __future__ import annotations

import gc
import json
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.common import percentile, row

import jax
import jax.numpy as jnp

# CPU stage service time; coarse sleep timers land this near 10ms/row in
# practice (the profiler measures what it actually costs), so the default
# 2-executor pool's capacity is ~200 req/s — benchmark rates stay below it
PRE_SLEEP_S = 0.008
SLO_MS = 40.0


def _pre(x) -> "jax.Array":
    time.sleep(PRE_SLEEP_S)
    return jnp.asarray(x, jnp.float32)


def _m1(x: "jax.Array") -> "jax.Array":
    return x * 2.0


def _m2(x: "jax.Array") -> "jax.Array":
    return x + 1.0


def _build_flow():
    from repro.core.dataflow import Dataflow
    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(_pre, names=["x"]) \
        .map(_m1, names=["x"], gpu=True, batching=True) \
        .map(_m2, names=["x"], gpu=True, batching=True)
    return fl


def _runtime():
    from repro.runtime.netmodel import NetModel
    from repro.runtime.runtime import Runtime
    return Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0),
                   max_batch=10, batch_wait_ms=2.0)


def _sample():
    from repro.core.table import Table
    return Table([("x", jax.Array)], [(jnp.ones(64, jnp.float32),)])


def _pool_size(rt, rclass: str) -> int:
    return len(rt.pool.by_class(rclass))


def _default_config(rt, plan):
    """What the default deployment actually is, expressed as a PlanConfig
    (so the estimator models it honestly): global batching knobs, the
    whole class pool as replicas."""
    from repro.profiling.optimizer import NodeConfig, PlanConfig
    nodes = {}
    for o in plan.ops:
        nodes[o.op_id] = NodeConfig(
            max_batch=rt.max_batch if o.batching else 1,
            batch_wait_ms=rt.batch_wait_ms if o.batching else 0.0,
            batched_lowering=bool(o.batchable),
            target_replicas=max(1, _pool_size(rt, o.placement)))
    return PlanConfig(nodes=nodes)


def _provision(rt, dag, cfg) -> None:
    """Pre-provision the optimizer's replica targets (what the autoscaler
    would converge to, done up-front so the measurement is steady-state)."""
    for node in dag.nodes.values():
        nc = cfg.nodes.get(node.plan_op_id)
        if nc is None or nc.target_replicas < 2:
            continue
        for _ in range(nc.target_replicas):
            rt.pool.add_replica(node.name, node.resource_class)


def _drive(dep, rate_hz: float, n: int, seed: int = 0) -> List[float]:
    """Open-loop POISSON arrivals at ``rate_hz`` (the estimator models
    M/M/c — deterministic pacing would measure a D/M/c system with far
    less queueing than the model predicts); per-request e2e latency."""
    lats: List[float] = []
    lock = threading.Lock()
    done = threading.Event()
    remaining = [n]

    def _cb(t_send):
        def cb(f):
            dt = time.perf_counter() - t_send
            with lock:
                lats.append(dt)
                remaining[0] -= 1
                if remaining[0] == 0:
                    done.set()
        return cb

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, size=n))
    # a gen-2 GC pause mid-run reads as a fake p99 outlier: collect the
    # garbage of previous variants now, hold collection during the drive
    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        for i in range(n):
            target = t0 + arrivals[i]
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            t_send = time.perf_counter()
            dep.execute(_sample()).add_done_callback(_cb(t_send))
        done.wait(timeout=120)
    finally:
        gc.enable()
    return sorted(lats)


def _measure(cfg, rate_hz: float, n: int) -> Dict[str, float]:
    """Fresh runtime + deployment (optionally compiled/provisioned with an
    optimizer PlanConfig), warmed, then driven open-loop."""
    rt = _runtime()
    try:
        fl = _build_flow()
        dep = fl.deploy(rt, fusion=True, plan_config=cfg)
        if cfg is not None:
            _provision(rt, dep.dag, cfg)
        for _ in range(4):      # warm the executables off the clock
            dep.execute(_sample()).result(timeout=30)
        lats = _drive(dep, rate_hz, n)
        return {"p50_ms": percentile(lats, 50) * 1e3,
                "p99_ms": percentile(lats, 99) * 1e3,
                "attainment": sum(1 for x in lats
                                  if x * 1e3 <= SLO_MS) / len(lats)}
    finally:
        rt.stop()
        # let the stopped runtime's executor/batcher threads actually die
        # before the next variant starts — a thread die-off mid-run shows
        # up as a fake p99 outlier in the NEXT measurement
        time.sleep(0.3)


def run(n_requests: int = 150, rates=(60.0, 120.0, 170.0),
        json_path: Optional[str] = None) -> List[str]:
    from repro.profiling import LatencyEstimator, Workload, profile_plan
    from repro.profiling.optimizer import propose

    # compile once to obtain the plan + offline profile (op ids are stable
    # across recompiles of the same flow with the same flags)
    rt0 = _runtime()
    try:
        dep0 = _build_flow().deploy(rt0, fusion=True)
        plan = dep0.plan
        profile = profile_plan(plan, _sample(), batch_sizes=(1, 2, 4, 8),
                               runs=3, kvs=rt0.kvs)
        default_cfg = _default_config(rt0, plan)
        net0 = rt0.net
        est = LatencyEstimator(profile, net=net0)
    finally:
        rt0.stop()

    rows: List[str] = []
    report = {"suite": "slo_planner", "slo_ms": SLO_MS,
              "pipeline": "pre(cpu,8ms) -> vjit[m1,m2](gpu,batching)",
              "n_requests": n_requests,
              "profile": profile.to_dict(), "rates": []}
    any_win = False
    for rate in rates:
        wl = Workload(arrival_rate=rate)
        pred_default = est.estimate(plan, default_cfg, wl)
        opt = propose(plan, SLO_MS / 1e3, rate, profile=profile,
                      net=net0, max_replicas=8)
        meas_default = _measure(None, rate, n_requests)
        meas_opt = _measure(opt, rate, n_requests)

        err50 = abs(pred_default.mean_s * 1e3 - meas_default["p50_ms"]) \
            / max(meas_default["p50_ms"], 1e-9)
        err99 = abs(pred_default.p99_s * 1e3 - meas_default["p99_ms"]) \
            / max(meas_default["p99_ms"], 1e-9)
        win = meas_opt["p99_ms"] < meas_default["p99_ms"]
        any_win = any_win or win
        entry = {
            "rate_hz": rate,
            "est_default_p50_ms": pred_default.mean_s * 1e3,
            "est_default_p99_ms": pred_default.p99_s * 1e3,
            "est_default_feasible": pred_default.feasible,
            "meas_default_p50_ms": meas_default["p50_ms"],
            "meas_default_p99_ms": meas_default["p99_ms"],
            "rel_err_p50": err50,
            "rel_err_p99": err99,
            "opt_predicted_p99_ms": (opt.predicted.p99_s * 1e3
                                     if opt.predicted else None),
            "opt_meets_slo_predicted": bool(
                opt.predicted and opt.predicted.meets(SLO_MS / 1e3)),
            "meas_opt_p50_ms": meas_opt["p50_ms"],
            "meas_opt_p99_ms": meas_opt["p99_ms"],
            "attain_default": meas_default["attainment"],
            "attain_opt": meas_opt["attainment"],
            "opt_beats_default_p99": win,
            "opt_config": opt.to_dict(),
        }
        report["rates"].append(entry)
        rows.append(row(f"slo_default@{rate:.0f}",
                        meas_default["p50_ms"] * 1e3,
                        f"p99={meas_default['p99_ms']:.1f}ms "
                        f"attain={meas_default['attainment']:.2f}"))
        rows.append(row(f"slo_opt@{rate:.0f}", meas_opt["p50_ms"] * 1e3,
                        f"p99={meas_opt['p99_ms']:.1f}ms "
                        f"attain={meas_opt['attainment']:.2f}"))
        rows.append(row(f"slo_est_err@{rate:.0f}", err99 * 100.0,
                        f"p99 rel err (p50 err {err50*100:.0f}%)"))
    report["any_opt_win_p99"] = any_win
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    return rows


def check_flows():
    """Static-verifier hook (``python -m repro.check``)."""
    return [{"name": "slo-planner", "flow": _build_flow(),
             "compile": {"fusion": True}, "sample": _sample(),
             "max_batch": 10}]
