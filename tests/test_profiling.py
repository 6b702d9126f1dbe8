"""Profiling subsystem: batch-sweep profiler curves, serialization
round-trips (FlowProfile + ChainProfile), the M/M/c + critical-path
estimator, and the SLO-aware configuration search."""
import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dataflow import Dataflow
from repro.core.ir import PhysicalPlan
from repro.core.lowering import BatchedJittedFuse, ChainProfile
from repro.core.passes import PassContext, build_pipeline
from repro.core.table import Table
from repro.profiling import (BucketStats, FlowProfile, LatencyEstimator,
                             NodeConfig, OpLatencyCurve, PlanConfig,
                             Workload, erlang_c, profile_plan, propose)
from repro.runtime.netmodel import NetModel


def _mul(x: jax.Array) -> jax.Array:
    return x * 2.0


def _add(x: jax.Array) -> jax.Array:
    return x + 1.0


def _lowered_plan():
    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(_mul, names=["x"], gpu=True, batching=True) \
        .map(_add, names=["x"], gpu=True, batching=True)
    plan = PhysicalPlan.from_dataflow(fl)
    plan = build_pipeline(fusion=True).run(plan, PassContext())
    return fl, plan


def _sample(n=1):
    t = Table([("x", jax.Array)])
    for i in range(n):
        t.insert((jnp.ones(32, jnp.float32) * i,))
    return t


def _synthetic_curve(key, per_row_s=2e-3, base=2e-3, slope=1e-4,
                     buckets=(1, 2, 4, 8, 16)):
    """Strongly sublinear batched curve: batching pays off under load."""
    c = OpLatencyCurve(key=key, name=f"op{key}", per_row_s=per_row_s)
    for b in buckets:
        mean = base + slope * b
        c.buckets[b] = BucketStats(mean_s=mean, p99_s=1.2 * mean, cv=0.05,
                                   runs=3, out_bytes=64 * b)
    return c


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------

def test_profile_plan_sweeps_buckets_and_per_row():
    _, plan = _lowered_plan()
    fp = profile_plan(plan, _sample(), batch_sizes=(1, 2, 4), runs=2)
    assert len(fp.curves) == len(plan.ops)
    lowered = [o for o in plan.ops if isinstance(o.op, BatchedJittedFuse)]
    assert lowered, "expected a batched-lowered chain"
    for o in plan.ops:
        curve = fp.curves[o.op_id]
        assert sorted(curve.buckets) == [1, 2, 4]
        for st in curve.buckets.values():
            assert st.mean_s > 0 and st.runs == 2 and st.out_bytes > 0
            assert st.p99_s >= st.mean_s
    # the batched-lowered chain also measured its per-row executable
    assert fp.curves[lowered[0].op_id].per_row_s is not None


def test_flow_profile_json_roundtrip(tmp_path):
    _, plan = _lowered_plan()
    fp = profile_plan(plan, _sample(), batch_sizes=(1, 2), runs=2)
    d = fp.to_dict()
    # JSON-stable: survives an actual dump/load cycle unchanged
    fp2 = FlowProfile.from_dict(json.loads(json.dumps(d)))
    assert fp2.to_dict() == d
    p = tmp_path / "profile.json"
    fp.save(str(p))
    fp3 = FlowProfile.load(str(p))
    assert fp3.to_dict() == d
    for k, c in fp.curves.items():
        assert fp3.curves[k].service_s(3) == c.service_s(3)


def test_curve_service_model():
    c = _synthetic_curve(1)
    # exact bucket
    assert c.service_s(4) == c.buckets[4].mean_s
    # padded up to the next measured bucket (what batched exec pays)
    assert c.service_s(3) == c.buckets[4].mean_s
    # beyond the largest bucket: linear extrapolation
    assert c.service_s(32) == pytest.approx(c.buckets[16].mean_s * 2)
    assert c.row_s() == 2e-3


def test_curve_merge_chain_profile_refreshes_means():
    c = _synthetic_curve(1)
    prof = ChainProfile()
    for _ in range(4):
        prof.note_per_row(5e-3)
        prof.note_batched(8, 3e-3)
    assert c.merge_chain_profile(prof)
    assert c.per_row_s == pytest.approx(5e-3)
    assert c.buckets[8].mean_s == pytest.approx(3e-3)
    # tail ratio preserved on refresh
    assert c.buckets[8].p99_s == pytest.approx(1.2 * 3e-3)
    # merging identical data again reports no change
    assert not c.merge_chain_profile(prof)


# ---------------------------------------------------------------------------
# ChainProfile serialization (satellite)
# ---------------------------------------------------------------------------

def test_chain_profile_json_roundtrip():
    p = ChainProfile(alpha=0.4)
    for _ in range(5):
        p.note_per_row(1e-3)
        p.note_batched(4, 2e-3)     # first batched sample is discarded
        p.note_batched(16, 3e-3)
    q = ChainProfile.from_dict(json.loads(json.dumps(p.to_dict())))
    assert q.alpha == p.alpha
    assert q.per_row_s == pytest.approx(p.per_row_s)
    assert q.per_row_samples == p.per_row_samples
    assert q.batched_s == pytest.approx(p.batched_s)
    assert q.batched_samples == p.batched_samples
    # crossover consistency: the restored profile routes identically
    assert q.crossover_rows() == p.crossover_rows()
    assert p.crossover_rows() is not None
    for n in (1, 2, 3, 5, 8, 16):
        b = 4 if n <= 4 else 16
        assert q.prefer_per_row(n, b) == p.prefer_per_row(n, b)


def test_chain_profile_empty_roundtrip():
    p = ChainProfile()
    q = ChainProfile.from_dict(p.to_dict())
    assert q.per_row_s is None and q.batched_s == {}
    assert q.crossover_rows() is None


# ---------------------------------------------------------------------------
# estimator
# ---------------------------------------------------------------------------

def test_erlang_c_known_values():
    # M/M/1: P(wait) = rho
    assert erlang_c(1, 0.5) == pytest.approx(0.5)
    assert erlang_c(1, 0.0) == 0.0
    assert erlang_c(1, 1.0) == 1.0          # saturation
    assert erlang_c(2, 0.5) < erlang_c(1, 0.5)
    # monotone in offered load
    assert erlang_c(4, 3.0) > erlang_c(4, 1.0)


def _one_node_plan():
    def slow(x: jax.Array) -> jax.Array:
        return x
    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(slow, names=["x"])
    return PhysicalPlan.from_dataflow(fl)


def test_estimator_replicas_and_rate_move_p99():
    plan = _one_node_plan()
    op_id = plan.ops[0].op_id
    fp = FlowProfile(curves={op_id: _synthetic_curve(op_id)})
    est = LatencyEstimator(fp, net=NetModel(scale=0.0))

    def p99(rate, c):
        cfg = PlanConfig(nodes={op_id: NodeConfig(target_replicas=c)})
        return est.estimate(plan, cfg, Workload(rate))

    # more replicas -> lower p99 at fixed rate (near saturation)
    assert p99(450.0, 2).p99_s < p99(450.0, 1).p99_s
    # higher rate -> higher p99 at fixed replicas
    assert p99(400.0, 1).p99_s > p99(100.0, 1).p99_s
    # saturated single replica flagged infeasible (service 2ms, 600/s)
    sat = p99(600.0, 1)
    assert not sat.feasible and not sat.meets(1.0)
    assert p99(600.0, 2).feasible


def test_estimator_batching_raises_throughput():
    plan = _one_node_plan()
    op_id = plan.ops[0].op_id
    fp = FlowProfile(curves={op_id: _synthetic_curve(op_id)})
    est = LatencyEstimator(fp, net=NetModel(scale=0.0))
    rate = 2000.0           # per-row: 2000 * 2ms = 4 erlangs, hopeless
    per_row = est.estimate(plan, PlanConfig(nodes={op_id: NodeConfig(
        max_batch=1, batched_lowering=False)}), Workload(rate))
    batched = est.estimate(plan, PlanConfig(nodes={op_id: NodeConfig(
        max_batch=16, batch_wait_ms=8.0, batched_lowering=True)}),
        Workload(rate))
    assert not per_row.feasible
    assert batched.feasible
    assert batched.p99_s < per_row.p99_s


def test_estimator_critical_path_and_wait_any():
    # diamond: source -> a -> (b slow | c fast) -> join
    def f(x: jax.Array) -> jax.Array:
        return x
    fl = Dataflow([("x", jax.Array)])
    a = fl.map(f, names=["x"])
    b = a.map(f, names=["x"])
    c = a.map(f, names=["x"])
    fl.output = b.anyof(c)
    plan = PhysicalPlan.from_dataflow(fl)
    ids = [o.op_id for o in plan.ops]
    curves = {i: _synthetic_curve(i, base=1e-3, slope=0.0) for i in ids}
    # make one branch much slower
    slow_id = ids[1]
    curves[slow_id] = _synthetic_curve(slow_id, base=50e-3, slope=0.0)
    est = LatencyEstimator(FlowProfile(curves=curves),
                           net=NetModel(scale=0.0))
    res = est.estimate(plan, PlanConfig(), Workload(10.0))
    # wait-any fires on the FAST branch: the slow op is off the path
    assert slow_id not in res.critical_path
    assert res.p99_s < 25e-3


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_propose_meets_slo_when_feasible():
    plan = _one_node_plan()
    op_id = plan.ops[0].op_id
    # batching allowed via the batching annotation
    plan = plan.with_ops([plan.ops[0].replace(batching=True)])
    fp = FlowProfile(curves={op_id: _synthetic_curve(op_id)})
    cfg = propose(plan, slo_p99=0.05, arrival_rate=2000.0, profile=fp,
                  net=NetModel(scale=0.0))
    assert cfg.predicted is not None
    assert cfg.predicted.meets(0.05), cfg.notes
    nc = cfg.nodes[op_id]
    # per-row at 2000/s is 4 erlangs: must batch and/or replicate
    assert nc.max_batch > 1 or nc.target_replicas > 1
    assert cfg.predicted.p99_s <= 0.05


def test_propose_prefers_per_row_when_sparse():
    plan = _one_node_plan()
    op_id = plan.ops[0].op_id
    plan = plan.with_ops([plan.ops[0].replace(batching=True)])
    fp = FlowProfile(curves={op_id: _synthetic_curve(op_id)})
    cfg = propose(plan, slo_p99=0.05, arrival_rate=20.0, profile=fp,
                  net=NetModel(scale=0.0))
    nc = cfg.nodes[op_id]
    # waiting (b-1)/lambda at 20/s dwarfs any batching win
    assert nc.max_batch == 1
    assert nc.batch_wait_ms == 0.0
    assert cfg.predicted.meets(0.05)


def test_propose_infeasible_reports_honestly():
    plan = _one_node_plan()
    op_id = plan.ops[0].op_id
    # brutal curve: 50ms/row, no batching win, SLO 10ms at 1000/s
    c = OpLatencyCurve(key=op_id, name="slow", per_row_s=50e-3)
    c.buckets[1] = BucketStats(mean_s=50e-3, p99_s=60e-3, cv=0.0, runs=2,
                               out_bytes=64)
    cfg = propose(plan, slo_p99=0.01, arrival_rate=1000.0,
                  profile=FlowProfile(curves={op_id: c}),
                  net=NetModel(scale=0.0), max_replicas=4)
    assert cfg.predicted is not None
    assert not cfg.predicted.meets(0.01)
    assert any("NOT met" in n for n in cfg.notes)


def test_plan_config_json_roundtrip():
    cfg = PlanConfig(nodes={
        1: NodeConfig(max_batch=8, batch_buckets=(1, 2, 4, 8),
                      batch_wait_ms=3.5, target_replicas=2),
        2: NodeConfig(batched_lowering=False, competitive_replicas=3,
                      placement="gpu"),
    }, slo_p99_s=0.05, arrival_rate=500.0, notes=["n"])
    d = json.loads(json.dumps(cfg.to_dict()))
    cfg2 = PlanConfig.from_dict(d)
    assert cfg2.nodes[1] == cfg.nodes[1]
    assert cfg2.nodes[2] == cfg.nodes[2]
    assert cfg2.slo_p99_s == 0.05 and cfg2.arrival_rate == 500.0
    assert cfg.bucket_overrides() == {1: (1, 2, 4, 8)}
    assert cfg.batched_overrides()[2] is False
    assert cfg.replica_overrides() == {2: 3}
    assert not cfg.differs_runtime(cfg2)
    assert not cfg.needs_recompile(cfg2)
    cfg2.nodes[1].batch_wait_ms = 9.0
    assert cfg.differs_runtime(cfg2)
    cfg2.nodes[2].batched_lowering = True
    assert cfg.needs_recompile(cfg2)


def test_plan_config_threads_through_pipeline():
    """PlanConfig per-op overrides reach the lowering pass: custom padding
    buckets land on the op's annotations, per-row lowering is honored."""
    fl, plan0 = _lowered_plan()
    lowered_id = next(o.op_id for o in plan0.ops
                      if isinstance(o.op, BatchedJittedFuse))
    cfg = PlanConfig(nodes={lowered_id: NodeConfig(
        max_batch=4, batch_buckets=(1, 2, 4), batched_lowering=True)})
    plan = PhysicalPlan.from_dataflow(fl)
    plan = build_pipeline(fusion=True, plan_config=cfg).run(
        plan, PassContext())
    o = plan.op(lowered_id)
    assert o.batch_buckets == (1, 2, 4)
    assert o.op.bucket_sizes == (1, 2, 4)
    # flip to per-row lowering
    cfg.nodes[lowered_id].batched_lowering = False
    plan = PhysicalPlan.from_dataflow(fl)
    plan = build_pipeline(fusion=True, plan_config=cfg).run(
        plan, PassContext())
    o = plan.op(lowered_id)
    assert not o.batchable and not isinstance(o.op, BatchedJittedFuse)
    assert o.op.name.startswith("jit[")


def test_apply_config_pass_stamps_competitive_and_placement():
    def f(x: int) -> int:
        return x + 1

    def g(x: int) -> int:
        return x - 1
    fl = Dataflow([("x", int)])
    # an unrelated high_variance-hinted op the config does NOT name: a
    # config-driven compile must not silently replicate it
    hv = fl.map(g, names=["x"], high_variance=True)
    fl.output = hv.map(f, names=["x"])
    plan = PhysicalPlan.from_dataflow(fl)
    hv_id, op_id = plan.ops[0].op_id, plan.ops[1].op_id
    cfg = PlanConfig(nodes={op_id: NodeConfig(competitive_replicas=3,
                                              placement="gpu")})
    out = build_pipeline(plan_config=cfg, jit_fusion=False).run(
        plan, PassContext())
    # competitive pass expanded ONLY the stamped op into 3 replicas +
    # wait-any; the high_variance hint alone did not expand
    anyof = [o for o in out.ops if o.wait_any]
    assert len(anyof) == 1 and anyof[0].op_id == op_id
    replicas = [o for o in out.ops
                if not o.wait_any and o.op_id != hv_id]
    assert len(replicas) == 3
    assert all(o.placement == "gpu" for o in replicas)
    assert sum(1 for o in out.ops if o.op_id == hv_id) == 1
