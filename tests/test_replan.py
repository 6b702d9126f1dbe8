"""Blue/green replanning + the cross-DAG state collisions that blocked it.

* deployment state (batchers, batch config, batch metrics) is keyed by
  ``(dag, generation, node)`` — two DAGs sharing a node name, or the blue
  and green generation of one DAG mid-swap, never share a batcher whose
  batch fn captured the other deployment's node closure;
* retired batchers drain on a REAL quiescence signal (no queued items and
  no flush in progress), not ``q.empty()``, which lies during a flush;
* error-path latency is recorded (separate series + counter) and a rising
  error rate counts as an SLO miss;
* re-registration under sustained load completes every in-flight request
  on the old generation with zero drops and no batcher-thread leak;
* ``BlueGreenReplanner``: compile off the hot path -> pre-warm every
  (chain, bucket) executable through the shared cache -> canary-verify ->
  atomic swap; post-swap traffic pays ZERO executable re-traces and
  hot-applied batch config carries over to green.
"""
import threading
import time

import numpy as np
import pytest

from repro.core.dataflow import Dataflow
from repro.core.table import Row, Table
from repro.runtime.dag import RuntimeDag, RuntimeNode
from repro.runtime.netmodel import NetModel
from repro.runtime.runtime import Runtime
from repro.serving.batcher import Batcher

import jax
import jax.numpy as jnp


@pytest.fixture
def rt():
    r = Runtime(n_cpu=4, net=NetModel(scale=0.0), batch_wait_ms=5.0)
    yield r
    r.stop()


# ---------------------------------------------------------------------------
# satellite: deployment state keyed by (dag, node), not bare node name
# ---------------------------------------------------------------------------

def _manual_batched_dag(dag_name: str, mult: int) -> RuntimeDag:
    """A one-node batched DAG whose node is named just "model" — the name
    two DAGs can share."""
    def fn(tables, ctx):
        t = tables[0]
        return t.with_rows([r.replace((r.values[0] * mult,))
                            for r in t.rows])
    node = RuntimeNode(name="model", fn=fn, deps=[], batching=True)
    return RuntimeDag(dag_name, {"model": node}, "model")


def test_two_dags_sharing_node_name_do_not_collide(rt):
    """Pre-fix, the second DAG's requests ran the FIRST DAG's captured
    batch closure (batchers were keyed by bare node name)."""
    rt.register_dag(_manual_batched_dag("a", 10))
    rt.register_dag(_manual_batched_dag("b", 100))
    fa = [rt.call_dag("a", Table([("x", int)], [(i,)])) for i in range(4)]
    fb = [rt.call_dag("b", Table([("x", int)], [(i,)])) for i in range(4)]
    assert [f.result(timeout=10).rows[0].values[0] for f in fa] == \
        [i * 10 for i in range(4)]
    assert [f.result(timeout=10).rows[0].values[0] for f in fb] == \
        [i * 100 for i in range(4)]
    # each deployment owns its batcher and its metric series
    assert rt.batcher_for("a", "model") is not rt.batcher_for("b", "model")
    snap = rt.metrics_snapshot()
    assert sum(snap["batch/a/model/size"]) == 4
    assert sum(snap["batch/b/model/size"]) == 4


def test_batch_config_is_per_dag(rt):
    rt.register_dag(_manual_batched_dag("a", 10))
    rt.register_dag(_manual_batched_dag("b", 100))
    assert rt.configure_batching("a", "model", max_batch=3,
                                 batch_wait_ms=1.0)
    rt.call_dag("a", Table([("x", int)], [(1,)])).result(timeout=10)
    rt.call_dag("b", Table([("x", int)], [(1,)])).result(timeout=10)
    assert rt.batcher_for("a", "model").max_batch == 3
    assert rt.batcher_for("b", "model").max_batch == rt.max_batch


# ---------------------------------------------------------------------------
# satellite: retired-batcher drain uses a real quiescence signal
# ---------------------------------------------------------------------------

def test_quiescent_false_during_active_flush():
    """``q.empty()`` lies while a flush holds popped items; ``quiescent``
    must not.  Items already dequeued by an in-progress flush complete
    instead of being failed by a premature close."""
    started, release = threading.Event(), threading.Event()

    def fn(args):
        started.set()
        assert release.wait(5.0)
        return [a * 2 for a in args]

    b = Batcher(fn, max_batch=4, max_wait_ms=1.0)
    try:
        item = b.submit(21)
        assert started.wait(2.0)
        # the flush thread holds the popped item: queue is empty but the
        # batcher is NOT drained — the old q.empty() check closed here and
        # could fail the dequeued request
        assert b.q.empty()
        assert not b.quiescent()
        release.set()
        assert item.event.wait(2.0)
        assert item.error is None and item.result == 42
        assert b.quiescent()
    finally:
        release.set()
        b.close()


def test_sweep_does_not_close_mid_flush_batcher(rt):
    """A retired batcher mid-flush survives the sweep; its in-flight
    request completes, then the next sweep closes it."""
    started, release = threading.Event(), threading.Event()

    def slow(x: int) -> int:
        started.set()
        assert release.wait(10.0)
        return x * 10

    fl = Dataflow([("x", int)])
    fl.output = fl.map(slow, names=["y"], batching=True)
    dep = fl.deploy(rt, name="drain")
    fut = dep.execute(Table([("x", int)], [(7,)]))
    assert started.wait(5.0)        # batch dispatched, executor in slow()
    # swap in a fresh generation while the old one is mid-request: the old
    # batcher must NOT be closed out from under the live request
    dep2 = fl.deploy(rt, name="drain")
    release.set()
    assert fut.result(timeout=10).rows[0].values[0] == 70
    assert dep2.execute(Table([("x", int)], [(8,)])) \
        .result(timeout=10).rows[0].values[0] == 80
    deadline = time.time() + 5.0
    while rt.sweep_retired() and time.time() < deadline:
        time.sleep(0.02)
    assert not rt._retired_batchers


# ---------------------------------------------------------------------------
# satellite: error-path latency is measured, errors count as SLO misses
# ---------------------------------------------------------------------------

def test_error_latency_recorded_separately(rt):
    def flaky(x: int) -> int:
        if x < 0:
            raise ValueError("bad input")
        return x

    fl = Dataflow([("x", int)])
    fl.output = fl.map(flaky, names=["x"])
    dep = fl.deploy(rt, name="flaky")
    oks = [dep.execute(Table([("x", int)], [(i,)])) for i in range(3)]
    bads = [dep.execute(Table([("x", int)], [(-1,)])) for _ in range(2)]
    for f in oks:
        f.result(timeout=10)
    for f in bads:
        with pytest.raises(ValueError):
            f.result(timeout=10)
    snap = rt.metrics_snapshot()
    assert len(snap["dag/flaky/latency_s"]) == 3       # successes only
    assert len(snap["dag/flaky/error_latency_s"]) == 2
    assert len(snap["dag/flaky/error_t"]) == 2         # the error counter
    assert all(v >= 0 for v in snap["dag/flaky/error_latency_s"])


def test_controller_treats_error_rate_as_slo_miss(rt):
    from repro.profiling import (BucketStats, FlowProfile, OpLatencyCurve,
                                 SLOController)

    def flaky(x: int) -> int:
        if x % 2:
            raise ValueError("boom")
        return x

    fl = Dataflow([("x", int)])
    fl.output = fl.map(flaky, names=["x"])
    dep = fl.deploy(rt)
    op_id = next(iter(dep.plan.ops)).op_id
    # a curve so fast the latency estimate trivially meets the SLO: only
    # the error rate can flag the miss
    c = OpLatencyCurve(key=op_id, name="flaky", per_row_s=1e-6)
    c.buckets[1] = BucketStats(mean_s=1e-6, p99_s=2e-6, cv=0.0, runs=3,
                               out_bytes=8)
    ctl = SLOController(rt, dep, slo_p99_s=1.0,
                        profile=FlowProfile(curves={op_id: c}),
                        window_s=5.0, min_rate=1.0)
    futs = [dep.execute(Table([("x", int)], [(i,)])) for i in range(40)]
    for i, f in enumerate(futs):
        if i % 2:
            with pytest.raises(ValueError):
                f.result(timeout=10)
        else:
            f.result(timeout=10)
    ev = ctl.tick()
    assert ev.detail["error_rate"] > ctl.max_error_rate
    assert ev.detail["slo_ok"] is False
    assert ev.detail["current_p99_ms"] < 1e3   # latency alone looked fine


# ---------------------------------------------------------------------------
# satellite: re-registration under sustained load — zero drops, no leak
# ---------------------------------------------------------------------------

def test_reregistration_under_load_zero_drops_no_thread_leak(rt):
    def mk(gen):
        def model(x: int) -> int:
            return x * 10 + gen
        fl = Dataflow([("x", int)])
        fl.output = fl.map(model, names=["y"], batching=True)
        return fl.deploy(rt, name="hotswap")

    mk(0)
    results, errors = [], []
    lock = threading.Lock()
    stop = threading.Event()

    def driver():
        while not stop.is_set():
            try:
                out = rt.call_dag("hotswap",
                                  Table([("x", int)], [(5,)])) \
                    .result(timeout=10)
                with lock:
                    results.append(out.rows[0].values[0])
            except BaseException as e:  # pragma: no cover
                with lock:
                    errors.append(e)
            time.sleep(0.001)

    threads = [threading.Thread(target=driver) for _ in range(4)]
    for t in threads:
        t.start()
    seen_batchers = set()
    try:
        for gen in range(1, 4):         # 3 swaps under live traffic
            time.sleep(0.15)
            with rt._batchers_lock:
                seen_batchers.update(rt._batchers.values())
            mk(gen)
    finally:
        time.sleep(0.15)
        stop.set()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()

    assert not errors                   # ZERO dropped/errored requests
    # every result came from a real generation's closure
    assert results and all(v in (50, 51, 52, 53) for v in results)
    # old generations' batchers all drain, close, and their threads die
    deadline = time.time() + 5.0
    while rt.sweep_retired() and time.time() < deadline:
        time.sleep(0.02)
    assert not rt._retired_batchers
    with rt._batchers_lock:
        live = set(rt._batchers.values())
    for b in seen_batchers - live:
        assert b._stop, "retired batcher never closed"
        assert not b._thread.is_alive(), "batcher thread leaked"
    # exactly the live generation's batcher remains for this dag
    assert len(live) == 1


def test_swap_back_while_draining_keeps_live_generation(rt):
    """Rollback: re-registering a generation that is still DRAINING its
    pre-swap in-flight requests must clear the draining mark — otherwise
    the drain-to-zero retires the now-live generation's batchers out
    from under traffic, recurrently."""
    started, release = threading.Event(), threading.Event()

    def slow(x: int) -> int:
        started.set()
        assert release.wait(10.0)
        return x * 10

    fl = Dataflow([("x", int)])
    fl.output = fl.map(slow, names=["y"], batching=True)
    d1 = fl.deploy(rt, name="rb")
    gen1 = d1.dag.generation
    fut = d1.execute(Table([("x", int)], [(1,)]))
    assert started.wait(5.0)                 # gen1 has an in-flight req
    fl.deploy(rt, name="rb")                 # swap to gen2: gen1 drains
    rt.register_dag(d1.dag)                  # swap BACK to gen1, draining
    release.set()
    assert fut.result(timeout=10).rows[0].values[0] == 10
    # gen1 is live again: serving works and its state is not marked dead
    assert rt.call_dag("rb", Table([("x", int)], [(2,)])) \
        .result(timeout=10).rows[0].values[0] == 20
    key = ("rb", gen1)
    assert key not in rt._draining and key not in rt._retired_gens
    assert rt.batcher_for("rb", next(iter(d1.dag.nodes))) is not None


def test_failed_replan_cooldown_suppresses_retries():
    """A failed replan must not re-run compile+warm+canary every tick:
    the controller backs off for replan_cooldown_s."""
    from repro.profiling import (BucketStats, FlowProfile, OpLatencyCurve,
                                 SLOController)
    jax_mod = pytest.importorskip("jax")
    rt2 = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0))
    try:
        fl = _gpu_flow()
        dep = fl.deploy(rt2, fusion=True, batched_lowering=False,
                        name="cool")
        op_id = next(n for n in dep.dag.nodes.values()
                     if n.batching).plan_op_id
        c = OpLatencyCurve(key=op_id, name="chain", per_row_s=8e-3)
        for b in (1, 2, 4, 8, 16):
            c.buckets[b] = BucketStats(mean_s=1e-3 + 5e-5 * b,
                                       p99_s=1.5e-3 + 7e-5 * b, cv=0.05,
                                       runs=3, out_bytes=64 * b)
        calls = []

        def failing_handler(proposal):
            calls.append(proposal)
            from repro.profiling import ReplanReport
            return ReplanReport(dag_name="cool", ok=False,
                                phase="canary", reason="forced failure")

        ctl = SLOController(rt2, dep, slo_p99_s=0.05,
                            profile=FlowProfile(curves={op_id: c}),
                            window_s=2.0, min_rate=1.0,
                            replan_cooldown_s=60.0,
                            on_replan=failing_handler)
        futs = [dep.execute(_sample()) for _ in range(60)]
        for f in futs:
            f.result(timeout=30)
        ev1 = ctl.tick()
        assert ev1.kind == "replan" and len(calls) == 1
        ev2 = ctl.tick()                 # still missing; inside cooldown
        assert ev2.kind == "replan"
        assert ev2.detail.get("replan_suppressed_s", 0) > 0
        assert len(calls) == 1           # handler NOT re-invoked
    finally:
        rt2.stop()


# ---------------------------------------------------------------------------
# tentpole: blue/green replanning
# ---------------------------------------------------------------------------

def _gm1(x: "jax.Array") -> "jax.Array":
    return x * 2.0


def _gm2(x: "jax.Array") -> "jax.Array":
    return x + 1.0


def _gpu_flow():
    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(_gm1, names=["x"], gpu=True, batching=True) \
        .map(_gm2, names=["x"], gpu=True, batching=True)
    return fl


def _sample():
    return Table([("x", jax.Array)], [(jnp.ones(8, jnp.float32),)])


def test_blue_green_swap_zero_retrace_and_state_carryover():
    from repro.core.lowering import EXECUTABLE_CACHE
    from repro.profiling import BlueGreenReplanner, NodeConfig, PlanConfig

    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0),
                 batch_wait_ms=2.0)
    try:
        fl = _gpu_flow()
        dep = fl.deploy(rt, fusion=True, name="bg")
        blue_dag = dep.dag
        node = next(n for n in dep.dag.nodes.values() if n.batching)
        op_id = node.plan_op_id
        # steady traffic on blue + a hot-applied batcher config that must
        # carry over to green (logical (dag, node) keying)
        for _ in range(6):
            dep.execute(_sample()).result(timeout=30)
        rt.configure_batching("bg", node.name, max_batch=5,
                              batch_wait_ms=3.0)

        # the proposal needs a recompile: a different bucket set
        proposal = PlanConfig(nodes={op_id: NodeConfig(
            max_batch=5, batch_buckets=(1, 2, 4), batch_wait_ms=3.0,
            batched_lowering=True)})
        rp = BlueGreenReplanner(rt, dep, sample=_sample())
        rep = rp.replan(proposal)
        assert rep.ok, rep
        assert rep.phase == "done"
        assert rep.canary.get("ok") is True
        assert rep.green_generation != rep.blue_generation

        # the swap happened: same name serves, the handle follows
        assert rt.dags["bg"] is dep.dag
        assert dep.dag is not blue_dag
        green_node = next(n for n in dep.dag.nodes.values() if n.batching)
        assert tuple(dep.plan.op(op_id).op.bucket_sizes) == (1, 2, 4)

        # post-swap traffic: correct results, ZERO executable re-traces
        # (warm already traced every bucket of the new set)
        traces0 = EXECUTABLE_CACHE.traces()
        futs = [dep.execute(_sample()) for _ in range(10)]
        for f in futs:
            out = f.result(timeout=30)
            np.testing.assert_allclose(
                np.asarray(out.rows[0].values[0]),
                np.ones(8, np.float32) * 2 + 1, rtol=1e-6)
        assert EXECUTABLE_CACHE.traces() == traces0
        # hot-applied batch config carried over to the green batcher
        b = rt.batcher_for("bg", green_node.name)
        assert b is not None and b.max_batch == 5
        assert b.max_wait == pytest.approx(3e-3)
    finally:
        rt.stop()


def test_blue_green_inflight_requests_finish_on_blue():
    """Requests in flight at swap time complete on the blue generation
    with correct results — zero drops across the swap."""
    from repro.profiling import BlueGreenReplanner, NodeConfig, PlanConfig

    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0),
                 batch_wait_ms=2.0)
    try:
        fl = _gpu_flow()
        dep = fl.deploy(rt, fusion=True, name="bg2")
        op_id = next(n for n in dep.dag.nodes.values()
                     if n.batching).plan_op_id
        dep.execute(_sample()).result(timeout=30)       # warm blue
        blue_key = (dep.dag.name, dep.dag.generation)
        futs = [dep.execute(_sample()) for _ in range(24)]   # in flight
        rep = BlueGreenReplanner(rt, dep, sample=_sample()).replan(
            PlanConfig(nodes={op_id: NodeConfig(
                max_batch=4, batch_buckets=(1, 2, 4),
                batched_lowering=True)}))
        assert rep.ok
        futs += [dep.execute(_sample()) for _ in range(8)]   # on green
        for f in futs:
            out = f.result(timeout=30)
            np.testing.assert_allclose(
                np.asarray(out.rows[0].values[0]),
                np.ones(8, np.float32) * 2 + 1, rtol=1e-6)
        # blue fully drained: its generation has no in-flight entries
        deadline = time.time() + 5.0
        while rt._inflight.get(blue_key) and time.time() < deadline:
            time.sleep(0.02)
        assert not rt._inflight.get(blue_key)
    finally:
        rt.stop()


def test_canary_failure_aborts_swap_blue_stays_live():
    from repro.profiling import BlueGreenReplanner, NodeConfig, PlanConfig

    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0))
    try:
        fl = _gpu_flow()
        dep = fl.deploy(rt, fusion=True, name="bg3")
        blue_dag, blue_plan = dep.dag, dep.plan
        op_id = next(n for n in dep.dag.nodes.values()
                     if n.batching).plan_op_id
        # poison the canary reference: green's (correct) output will not
        # match, so the replan must abort before the swap
        wrong = Table([("x", jax.Array)],
                      [(jnp.zeros(8, jnp.float32),)])
        fl.execute_local = lambda t, ctx=None: wrong
        rep = BlueGreenReplanner(rt, dep, sample=_sample(),
                                 reference="local").replan(
            PlanConfig(nodes={op_id: NodeConfig(
                max_batch=4, batch_buckets=(1, 4),
                batched_lowering=True)}))
        assert not rep.ok
        assert rep.phase == "canary"
        assert "mismatch" in str(rep.canary.get("error"))
        # blue untouched and still serving
        assert rt.dags["bg3"] is blue_dag
        assert dep.dag is blue_dag and dep.plan is blue_plan
        out = dep.execute(_sample()).result(timeout=30)
        np.testing.assert_allclose(
            np.asarray(out.rows[0].values[0]),
            np.ones(8, np.float32) * 2 + 1, rtol=1e-6)
        # the aborted green generation's canary-created batchers were
        # discarded, not leaked: only blue's generation remains live
        deadline = time.time() + 5.0
        while rt.sweep_retired() and time.time() < deadline:
            time.sleep(0.02)
        assert not rt._retired_batchers
        with rt._batchers_lock:
            gens = {k[1] for k in rt._batchers}
        assert gens <= {blue_dag.generation}
    finally:
        rt.stop()


def test_warm_deployment_pretraces_all_buckets():
    """After warm_deployment, driving every bucket size produces ZERO new
    traces — the first post-swap request is provably trace-free."""
    from repro.core.compiler import compile_flow
    from repro.core.lowering import EXECUTABLE_CACHE
    from repro.profiling import NodeConfig, PlanConfig, warm_deployment

    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0))
    try:
        fl = _gpu_flow()
        probe = fl.deploy(rt, fusion=True, name="warm0")
        op_id = next(n for n in probe.dag.nodes.values()
                     if n.batching).plan_op_id
        cfg = PlanConfig(nodes={op_id: NodeConfig(
            max_batch=4, batch_buckets=(1, 2, 4), batched_lowering=True)})
        green = compile_flow(fl, rt, fusion=True, plan_config=cfg,
                             name="warm1", register=False)
        assert "warm1" not in rt.dags           # prepared, not serving
        assert green.dag.generation > 0
        w = warm_deployment(rt, green, _sample())
        assert not w["errors"]
        traces0 = EXECUTABLE_CACHE.traces()
        for b in (1, 2, 4):
            t = Table([("x", jax.Array)],
                      [(jnp.ones(8, jnp.float32),) for _ in range(b)])
            out = rt.call_dag_object(green.dag, t).result(timeout=30)
            assert len(out) == b
        assert EXECUTABLE_CACHE.traces() == traces0, \
            "post-warm traffic re-traced an executable"
    finally:
        rt.stop()


def test_controller_default_replanner_escalates_swaps_and_confirms():
    """The full loop: a per-row-lowered deployment saturates at the
    measured rate -> the optimizer proposes a batched flip (compile-time)
    -> the controller escalates to its default BlueGreenReplanner ->
    green (batched) swaps in with zero drops -> the next tick confirms
    the post-swap SLO."""
    from repro.core.lowering import BatchedJittedFuse, JittedFuse
    from repro.profiling import (BucketStats, FlowProfile, OpLatencyCurve,
                                 SLOController)

    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0),
                 batch_wait_ms=2.0)
    try:
        fl = _gpu_flow()
        # deploy PER-ROW lowered: the live plan cannot express batching
        dep = fl.deploy(rt, fusion=True, batched_lowering=False,
                        name="ctl")
        node = next(n for n in dep.dag.nodes.values() if n.batching)
        op_id = node.plan_op_id
        op0 = dep.plan.op(op_id).op
        assert isinstance(op0, JittedFuse) \
            and not isinstance(op0, BatchedJittedFuse)

        # synthetic curve: per-row saturates at the measured rate, the
        # batched path is comfortably cheap -> propose() must flip to
        # batched lowering, which needs a recompile
        c = OpLatencyCurve(key=op_id, name="chain", per_row_s=5e-3)
        for b in (1, 2, 4, 8, 16):
            c.buckets[b] = BucketStats(mean_s=1e-3 + 5e-5 * b,
                                       p99_s=1.5e-3 + 7e-5 * b,
                                       cv=0.05, runs=3, out_bytes=64 * b)
        ctl = SLOController(rt, dep, slo_p99_s=0.05,
                            profile=FlowProfile(curves={op_id: c}),
                            window_s=1.0, min_rate=1.0,
                            replan_sample=_sample())

        futs = [dep.execute(_sample()) for _ in range(60)]
        for f in futs:
            f.result(timeout=30)
        ev = ctl.tick()
        assert ev.kind == "replan", ev
        assert ev.detail.get("replan_report", {}).get("ok") is True
        # green is live and batched-lowered
        assert isinstance(dep.plan.op(op_id).op, BatchedJittedFuse)
        assert rt.dags["ctl"] is dep.dag

        # post-swap traffic + the confirming tick
        futs = [dep.execute(_sample()) for _ in range(30)]
        for f in futs:
            out = f.result(timeout=30)
            np.testing.assert_allclose(
                np.asarray(out.rows[0].values[0]),
                np.ones(8, np.float32) * 2 + 1, rtol=1e-6)
        ev2 = ctl.tick()
        confirm = ev2.detail.get("post_replan_confirm")
        assert confirm is not None
        assert confirm["slo_ok"] is True, ev2
        # the batched flip is realized: no further escalation
        assert ev2.kind != "replan"
    finally:
        rt.stop()


def _rb1(x: "jax.Array") -> "jax.Array":
    return x * 3.0


def _rb2(x: "jax.Array") -> "jax.Array":
    return x - 1.0


def test_failed_confirm_rolls_back_to_blue_automatically():
    """Satellite: when the confirm tick after a blue/green swap shows the
    green generation missing the SLO (here: a rising error rate), the
    controller rolls back AUTOMATICALLY — blue is re-registered
    atomically (its generation un-retired), the handle follows, a
    ``replan/rollback`` metric is recorded, and the cooldown keeps the
    very next ticks from re-compiling the green that just failed."""
    from repro.core.lowering import BatchedJittedFuse, JittedFuse
    from repro.profiling import (BucketStats, FlowProfile, OpLatencyCurve,
                                 SLOController)

    rt = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0),
                 batch_wait_ms=2.0)
    try:
        # a chain signature no other test shares: refresh_profile folds
        # the process-wide live ChainProfile into the curves, and a chain
        # already driven per-row at real (fast) speed would overwrite the
        # synthetic saturated per_row_s below and suppress the escalation
        fl = Dataflow([("x", jax.Array)])
        fl.output = fl.map(_rb1, names=["x"], gpu=True, batching=True) \
            .map(_rb2, names=["x"], gpu=True, batching=True)
        dep = fl.deploy(rt, fusion=True, batched_lowering=False,
                        name="rb")
        blue_dag, blue_plan = dep.dag, dep.plan
        op_id = next(n for n in dep.dag.nodes.values()
                     if n.batching).plan_op_id
        # synthetic curve that forces the batched-flip escalation (same
        # shape as the escalate-and-confirm test above)
        c = OpLatencyCurve(key=op_id, name="chain", per_row_s=5e-3)
        for b in (1, 2, 4, 8, 16):
            c.buckets[b] = BucketStats(mean_s=1e-3 + 5e-5 * b,
                                       p99_s=1.5e-3 + 7e-5 * b,
                                       cv=0.05, runs=3, out_bytes=64 * b)
        ctl = SLOController(rt, dep, slo_p99_s=0.05,
                            profile=FlowProfile(curves={op_id: c}),
                            window_s=1.0, min_rate=1.0,
                            replan_sample=_sample())
        for f in [dep.execute(_sample()) for _ in range(60)]:
            f.result(timeout=30)
        ev = ctl.tick()
        assert ev.kind == "replan", ev
        assert ev.detail.get("replan_report", {}).get("ok") is True
        assert dep.dag is not blue_dag          # green is live

        # green "fails" in production: malformed requests drive the error
        # rate past max_error_rate, so the confirm tick judges slo_ok
        # False even though the modeled latency is fine
        bad = Table([("x", jax.Array)], [("junk",)])
        for f in [dep.execute(bad) for _ in range(30)]:
            with pytest.raises(Exception):
                f.result(timeout=30)
        ev2 = ctl.tick()
        confirm = ev2.detail.get("post_replan_confirm")
        assert confirm is not None and confirm["slo_ok"] is False, ev2
        rb = confirm.get("rollback")
        assert rb and rb["rolled_back"] is True
        assert rb["restored_generation"] == blue_dag.generation
        assert ev2.detail.get("rolled_back") is True

        # blue is live again and the shared handle follows the rollback
        assert rt.dags["rb"] is blue_dag
        assert dep.dag is blue_dag and dep.plan is blue_plan
        op0 = dep.plan.op(op_id).op
        assert isinstance(op0, JittedFuse) \
            and not isinstance(op0, BatchedJittedFuse)
        assert "replan/rollback" in rt.metrics_snapshot()
        # the rollback did NOT re-escalate in the same tick (cooldown)
        assert "replan_report" not in ev2.detail

        # blue's un-retired generation serves correctly: zero drops
        out = dep.execute(_sample()).result(timeout=30)
        np.testing.assert_allclose(
            np.asarray(out.rows[0].values[0]),
            np.ones(8, np.float32) * 3 - 1, rtol=1e-6)
        # inside the cooldown the controller must not re-compile the
        # green it just rolled back
        for f in [dep.execute(_sample()) for _ in range(10)]:
            f.result(timeout=30)
        ev3 = ctl.tick()
        assert "replan_report" not in ev3.detail
    finally:
        rt.stop()


# ---------------------------------------------------------------------------
# satellite: reserved warm-up/canary executors
# ---------------------------------------------------------------------------

def _blocked_serving_pool(rt, resource_class="cpu"):
    """Saturate every SERVING executor of a class with a blocking work
    item; returns the release event (set it to free the pool)."""
    release = threading.Event()

    def blocker(tables, ctx):
        release.wait(30.0)
        return None

    for ex in rt.pool.by_class(resource_class):
        from repro.runtime.executor import WorkItem
        ex.submit(WorkItem(fn=blocker, tables=[], produced_on=[],
                           callback=lambda *a: None))
    return release


def test_reserved_pool_keeps_canary_off_saturated_serving_pool():
    """Satellite: with ``reserved_cpu`` provisioned, a blue/green replan
    completes even while 100% of the serving pool is busy — warm-up and
    canary traffic for the prepared (not-yet-live) green generation
    routes to the reserved executors, which serving traffic never
    touches."""
    from repro.profiling import BlueGreenReplanner, NodeConfig, PlanConfig

    rt = Runtime(n_cpu=2, net=NetModel(scale=0.0), batch_wait_ms=2.0,
                 reserved_cpu=1)
    release = None
    try:
        def double(x: int) -> int:
            return x * 2

        fl = Dataflow([("x", int)])
        fl.output = fl.map(double, names=["x"], batching=True)
        dep = fl.deploy(rt, name="rsv")
        op_id = next(n for n in dep.dag.nodes.values()
                     if n.batching).plan_op_id
        # reserved executors are NOT serving candidates
        assert len(rt.pool.by_class("cpu")) == 2
        assert len(rt.pool.by_class("cpu", reserved=True)) == 1

        release = _blocked_serving_pool(rt)     # 100% serving-pool load
        # reference="local": the blue reference request would starve on
        # the saturated serving pool; ground truth runs inline
        rep = BlueGreenReplanner(
            rt, dep, sample=Table([("x", int)], [(3,)]),
            reference="local", canary_timeout_s=5.0).replan(
            PlanConfig(nodes={op_id: NodeConfig(max_batch=4,
                                                batch_wait_ms=1.0)}))
        assert rep.ok, rep
        assert rep.canary.get("ok") is True
        release.set()
        out = rt.call_dag("rsv", Table([("x", int)], [(5,)])) \
            .result(timeout=10)
        assert out.rows[0].values[0] == 10
    finally:
        if release is not None:
            release.set()
        rt.stop()


def test_canary_starves_without_reserved_pool():
    """Negative control for the reserved-pool satellite: the identical
    replan under the identical 100% serving-pool load times out in the
    canary phase when no reserved executors exist — blue stays live."""
    from repro.profiling import BlueGreenReplanner, NodeConfig, PlanConfig

    rt = Runtime(n_cpu=2, net=NetModel(scale=0.0), batch_wait_ms=2.0)
    release = None
    try:
        def double(x: int) -> int:
            return x * 2

        fl = Dataflow([("x", int)])
        fl.output = fl.map(double, names=["x"], batching=True)
        dep = fl.deploy(rt, name="nrsv")
        blue_dag = dep.dag
        op_id = next(n for n in dep.dag.nodes.values()
                     if n.batching).plan_op_id
        assert not rt.pool.by_class("cpu", reserved=True)

        release = _blocked_serving_pool(rt)
        rep = BlueGreenReplanner(
            rt, dep, sample=Table([("x", int)], [(3,)]),
            reference="local", canary_timeout_s=1.0).replan(
            PlanConfig(nodes={op_id: NodeConfig(max_batch=4,
                                                batch_wait_ms=1.0)}))
        assert not rep.ok
        assert rep.phase == "canary"
        assert rt.dags["nrsv"] is blue_dag      # blue untouched
    finally:
        if release is not None:
            release.set()
        rt.stop()
