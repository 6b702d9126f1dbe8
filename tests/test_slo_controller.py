"""Online SLO controller + runtime reconfiguration + autoscaler targets.

* ``Runtime.record_metric``/``metrics_snapshot`` are safe under concurrent
  executor-callback writers;
* the controller hot-applies batch bucket / batcher window changes to a
  LIVE deployment — no flow re-registration, zero executable re-traces;
* optimizer-suggested replica targets drive the ``Autoscaler`` (spike ->
  scale-up -> settle with slack) while the depth heuristic survives for
  untargeted functions.
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dataflow import Dataflow
from repro.core.lowering import EXECUTABLE_CACHE, BatchedJittedFuse
from repro.core.table import Table
from repro.profiling import (BucketStats, FlowProfile, OpLatencyCurve,
                             SLOController)
from repro.runtime.autoscaler import Autoscaler, AutoscalerConfig
from repro.runtime.netmodel import NetModel
from repro.runtime.runtime import Runtime


def _curve(key, per_row_s=2e-3, base=2e-3, slope=1e-4,
           buckets=(1, 2, 4, 8, 16)):
    c = OpLatencyCurve(key=key, name=f"op{key}", per_row_s=per_row_s)
    for b in buckets:
        mean = base + slope * b
        c.buckets[b] = BucketStats(mean_s=mean, p99_s=1.2 * mean, cv=0.05,
                                   runs=3, out_bytes=64 * b)
    return c


# ---------------------------------------------------------------------------
# metrics thread-safety (satellite)
# ---------------------------------------------------------------------------

def test_metrics_concurrent_writers_and_snapshots():
    rt = Runtime(n_cpu=1, net=NetModel(scale=0.0))
    try:
        stop = threading.Event()
        errors = []

        def writer(i):
            for k in range(300):
                rt.record_metric(f"key/{i % 4}", float(k))

        def reader():
            while not stop.is_set():
                try:
                    snap = rt.metrics_snapshot()
                    for series in snap.values():
                        list(series)        # iterate a consistent copy
                except BaseException as e:  # pragma: no cover
                    errors.append(e)
                    return

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(8)]
        r = threading.Thread(target=reader)
        r.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        r.join(timeout=2)
        assert not errors
        snap = rt.metrics_snapshot()
        assert sum(len(snap[f"key/{i}"]) for i in range(4)) == 8 * 300
    finally:
        rt.stop()


# ---------------------------------------------------------------------------
# live reconfiguration (acceptance: sparse -> dense changes the deployed
# config without re-registration, zero executable re-traces)
# ---------------------------------------------------------------------------

def _gpu_m1(x: jax.Array) -> jax.Array:
    return x * 2.0


def _gpu_m2(x: jax.Array) -> jax.Array:
    return x + 1.0


def test_controller_hot_applies_sparse_to_dense():
    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0),
                 max_batch=4, batch_wait_ms=2.0)
    try:
        fl = Dataflow([("x", jax.Array)])
        fl.output = fl.map(_gpu_m1, names=["x"], gpu=True, batching=True) \
            .map(_gpu_m2, names=["x"], gpu=True, batching=True)
        dep = fl.deploy(rt, fusion=True)
        dag0 = rt.dags[dep.dag.name]
        node = next(n for n in dep.dag.nodes.values() if n.batching)
        op_id = node.plan_op_id
        assert isinstance(dep.plan.op(op_id).op, BatchedJittedFuse)

        # synthetic offline curve: strong batching win under load
        profile = FlowProfile(curves={op_id: _curve(op_id)})
        ctl = SLOController(rt, dep, slo_p99_s=0.2, profile=profile,
                            window_s=0.5, min_rate=1.0)

        def req():
            return Table([("x", jax.Array)],
                         [(jnp.ones(16, jnp.float32),)])

        # -- sparse phase: ~30/s, per-row wins ------------------------------
        futs = [dep.execute(req()) for _ in range(3)]
        for _ in range(6):
            futs.append(dep.execute(req()))
            time.sleep(0.03)
        for f in futs:
            f.result(timeout=10)
        ev1 = ctl.tick()
        assert ev1.kind == "apply", ev1
        cfg1 = ctl.applied.nodes[op_id]
        assert cfg1.max_batch == 1 and cfg1.batch_wait_ms == 0.0
        batcher = rt.batcher_for(dep.dag.name, node.name)
        assert batcher.max_wait == 0.0
        buckets_sparse = tuple(node.batch_buckets)

        # -- dense phase: a back-to-back burst, batching must win -----------
        time.sleep(0.6)                 # age the sparse timestamps out
        futs = [dep.execute(req()) for _ in range(80)]
        for f in futs:
            f.result(timeout=20)
        rate = ctl.arrival_rate()
        assert rate > 200.0, rate

        traces_before = EXECUTABLE_CACHE.traces()
        ev2 = ctl.tick()
        traces_after = EXECUTABLE_CACHE.traces()

        # the apply itself is pure control plane: ZERO re-traces
        assert traces_after == traces_before
        # no re-registration: same DAG object is live
        assert rt.dags[dep.dag.name] is dag0
        assert ev2.kind == "apply", ev2
        cfg2 = ctl.applied.nodes[op_id]
        # the deployed flow's batcher window and max-batch moved
        assert cfg2.max_batch > 1
        assert cfg2.batch_wait_ms > 0.0
        assert rt.batcher_for(dep.dag.name, node.name) is batcher
        assert batcher.max_wait == pytest.approx(
            cfg2.batch_wait_ms / 1e3)
        assert batcher.max_batch == cfg2.max_batch
        # and the node's padding buckets were retuned in place
        assert tuple(node.batch_buckets) != buckets_sparse
        assert dep.plan.op(op_id).op.bucket_sizes == \
            tuple(node.batch_buckets)

        # the reconfigured deployment still serves correctly
        out = dep.execute(req()).result(timeout=10)
        assert out.rows[0].values[0] == pytest.approx(
            np.ones(16, np.float32) * 2 + 1)
    finally:
        rt.stop()


def test_live_config_reads_competitive_from_expanded_topology():
    """After a competitive recompile the factor lives in the wait-any
    consumer's input count (CompetitivePass zeroes the replica ops'
    annotation) — the controller must read it back from the topology, and
    must not keep demanding a recompile for an already-expanded slot."""
    from repro.profiling import NodeConfig, PlanConfig
    rt = Runtime(n_cpu=2, net=NetModel(scale=0.0))
    try:
        def f(x: int) -> int:
            return x
        fl = Dataflow([("x", int)])
        fl.output = fl.map(f, names=["x"], high_variance=True)
        dep = fl.deploy(rt, competitive_exec=True, default_replicas=3)
        anyof_id = next(o.op_id for o in dep.plan.ops if o.wait_any)
        ctl = SLOController(rt, dep, slo_p99_s=0.05, profile=FlowProfile())
        live = ctl._live_config(None)
        assert live.nodes[anyof_id].competitive_replicas == 3
        replica_ids = dep.plan.op(anyof_id).inputs
        assert all(live.nodes[i].competitive_replicas == 3
                   for i in replica_ids)
        # a proposal demanding competitive on the (already wait-any) slot
        # is satisfied by the live topology: no recompile escalation
        proposal = PlanConfig(nodes={anyof_id: NodeConfig(
            competitive_replicas=3)})
        assert not ctl._needs_recompile(proposal)
    finally:
        rt.stop()


def test_arrival_rate_decays_after_traffic_stops():
    """The rate window is anchored on NOW, not on the newest request —
    a dead workload must read as idle, not as its last burst's rate."""
    rt = Runtime(n_cpu=1, net=NetModel(scale=0.0))
    try:
        def f(x: int) -> int:
            return x
        fl = Dataflow([("x", int)])
        fl.output = fl.map(f, names=["x"])
        dep = fl.deploy(rt)
        ctl = SLOController(rt, dep, slo_p99_s=0.05,
                            profile=FlowProfile(), window_s=0.4)
        now = time.perf_counter()
        for i in range(50):     # a burst that ended 2s ago
            rt.record_metric(f"dag/{dep.dag.name}/request_t",
                             now - 2.0 + i * 0.002)
        assert ctl.arrival_rate() == 0.0
        assert ctl.tick().kind == "idle"
    finally:
        rt.stop()


def test_plan_config_compile_without_fusion_still_lowers():
    """A config-driven recompile must realize the config's lowering and
    bucket overrides even when fusion is off (bare gpu maps lower with
    min_ops=1) — silently dropping them would defeat a replan."""
    from repro.profiling import NodeConfig, PlanConfig
    rt = Runtime(n_cpu=1, n_gpu=1, net=NetModel(scale=0.0))
    try:
        fl = Dataflow([("x", jax.Array)])
        fl.output = fl.map(_gpu_m1, names=["x"], gpu=True, batching=True)
        probe = fl.deploy(rt, fusion=False, plan_config=PlanConfig())
        op_id = next(iter(probe.plan.ops)).op_id
        cfg = PlanConfig(nodes={op_id: NodeConfig(
            max_batch=4, batch_buckets=(1, 2, 4), batched_lowering=True)})
        dep = fl.deploy(rt, fusion=False, plan_config=cfg)
        o = dep.plan.op(op_id)
        assert isinstance(o.op, BatchedJittedFuse)
        assert o.batch_buckets == (1, 2, 4)
        out = dep.execute(Table([("x", jax.Array)],
                                [(jnp.ones(4, jnp.float32),)]))
        assert out.result(timeout=10).rows[0].values[0] == pytest.approx(
            np.ones(4, np.float32) * 2)
    finally:
        rt.stop()


def test_configure_batching_before_first_dispatch():
    """Overrides set before a node's batcher exists are picked up at
    batcher creation."""
    rt = Runtime(n_cpu=2, net=NetModel(scale=0.0), max_batch=10,
                 batch_wait_ms=5.0)
    try:
        def f(x: int) -> int:
            return x * 10
        fl = Dataflow([("x", int)])
        fl.output = fl.map(f, names=["y"], batching=True)
        dep = fl.deploy(rt)
        node = next(n for n in dep.dag.nodes.values() if n.batching)
        assert rt.configure_batching(dep.dag.name, node.name, max_batch=3,
                                     batch_wait_ms=1.0)
        # unchanged values report no change
        assert not rt.configure_batching(dep.dag.name, node.name,
                                         max_batch=3, batch_wait_ms=1.0)
        out = dep.execute(Table([("x", int)], [(4,)])).result(timeout=10)
        assert out.rows[0].values[0] == 40
        b = rt.batcher_for(dep.dag.name, node.name)
        assert b.max_batch == 3 and b.max_wait == pytest.approx(1e-3)
    finally:
        rt.stop()


# ---------------------------------------------------------------------------
# autoscaler targets (satellite): spike -> scale-up -> settle with slack
# ---------------------------------------------------------------------------

def _wait_until(cond, timeout=6.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def test_autoscaler_converges_to_target():
    rt = Runtime(n_cpu=2, net=NetModel(scale=0.0))
    scaler = None
    try:
        scaler = Autoscaler(rt.pool, {"fn": "cpu"},
                            AutoscalerConfig(interval_s=0.02, slack=2,
                                             min_replicas=1)).start()
        scaler.set_target("fn", 5)
        assert _wait_until(lambda: rt.pool.replica_count("fn") >= 5)
        scaler.set_target("fn", 1)
        # settles within target + slack (hysteresis makes this take a few
        # ticks), never below min_replicas
        assert _wait_until(lambda: rt.pool.replica_count("fn") <= 3)
        time.sleep(0.3)
        assert 1 <= rt.pool.replica_count("fn") <= 3
    finally:
        if scaler:
            scaler.stop()
        rt.stop()


def test_controller_autoscaler_bursty_traffic():
    """The combined loop: a traffic spike makes the optimizer demand
    replicas (M/M/c), the controller targets them on the autoscaler, the
    pool scales up; when traffic thins the next tick lowers the target and
    the pool settles back with slack."""
    rt = Runtime(n_cpu=2, net=NetModel(scale=0.0))
    scaler = None
    try:
        def heavy(x: int) -> int:
            time.sleep(0.004)
            return x + 1

        fl = Dataflow([("x", int)])
        fl.output = fl.map(heavy, names=["x"])
        dep = fl.deploy(rt)
        node = next(iter(dep.dag.nodes.values()))
        op_id = node.plan_op_id

        scaler = Autoscaler(rt.pool, {node.name: "cpu"},
                            AutoscalerConfig(interval_s=0.02, slack=2,
                                             min_replicas=1)).start()
        profile = FlowProfile(curves={op_id: _curve(
            op_id, per_row_s=4e-3, base=4e-3, slope=0.0, buckets=(1,))})
        ctl = SLOController(rt, dep, slo_p99_s=0.05, profile=profile,
                            autoscaler=scaler, window_s=0.5, min_rate=1.0)

        # -- spike: ~500/s => 2 erlangs at 4ms/req => needs >= 3 replicas --
        futs = []
        t_end = time.time() + 0.5
        while time.time() < t_end:
            futs.append(dep.execute(Table([("x", int)], [(1,)])))
            time.sleep(0.002)
        ev = ctl.tick()
        assert ev.arrival_rate > 200.0, ev
        target_hot = scaler.target(node.name)
        assert target_hot is not None and target_hot >= 2, ev
        assert _wait_until(
            lambda: rt.pool.replica_count(node.name) >= target_hot)
        for f in futs:
            f.result(timeout=30)

        # -- settle: thin trickle => target drops, pool trims with slack ---
        time.sleep(0.6)
        for _ in range(6):
            dep.execute(Table([("x", int)], [(1,)])).result(timeout=10)
            time.sleep(0.05)
        ev2 = ctl.tick()
        target_cool = scaler.target(node.name)
        assert target_cool is not None and target_cool < target_hot, ev2
        slack = scaler.cfg.slack
        assert _wait_until(lambda: rt.pool.replica_count(node.name)
                           <= target_cool + slack)
        assert rt.pool.replica_count(node.name) >= 1
    finally:
        if scaler:
            scaler.stop()
        rt.stop()


def test_depth_heuristic_untouched_without_target():
    """No target set -> the original queue-depth rule still scales up."""
    rt = Runtime(n_cpu=2, net=NetModel(scale=0.0))
    scaler = None
    try:
        def slow(x: int) -> int:
            time.sleep(0.02)
            return x

        fl = Dataflow([("x", int)])
        fl.output = fl.map(slow, names=["x"])
        dep = fl.deploy(rt)
        fname = next(iter(dep.dag.nodes))
        scaler = Autoscaler(rt.pool, {fname: "cpu"},
                            AutoscalerConfig(interval_s=0.02)).start()
        futs = [dep.execute(Table([("x", int)], [(i,)]))
                for i in range(40)]
        assert _wait_until(lambda: rt.pool.replica_count(fname) > 1)
        for f in futs:
            f.result(timeout=30)
    finally:
        if scaler:
            scaler.stop()
        rt.stop()
