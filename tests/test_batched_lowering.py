"""Batched vmap execution engine (lowering layer).

* correctness: the vmapped batched path matches the interpreted path on
  multi-row tables, ragged batches, and empty tables;
* bucketing: one XLA dispatch per shape bucket, row counts padded to
  powers of two;
* executable cache: hits across re-registrations of the identical chain
  (ZERO re-traces), misses across bucket boundaries and dtype changes;
* fallback: untraceable functions latch the interpreted path instead of
  crashing at request time;
* plumbing: IR annotations (``batchable``/``batch_buckets``), runtime DAG
  ``batched_fn``, planner flag.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.core import operators as ops
from repro.core.dataflow import Dataflow
from repro.core.ir import PhysicalPlan
from repro.core.lowering import (EXECUTABLE_CACHE, BatchedJittedFuse,
                                 JittedFuse, bucket_rows, chain_signature)
from repro.core.passes import build_pipeline
from repro.core.table import Table


def _f1(x: jax.Array) -> jax.Array:
    return jnp.tanh(x * 1.01 + 0.1)


def _f2(x: jax.Array) -> jax.Array:
    return x * x - 0.5 * x


def _chain(fns=(_f1, _f2)):
    fl = Dataflow([("x", jax.Array)])
    node = fl.source
    for f in fns:
        node = node.map(f, names=["x"], gpu=True)
    fl.output = node
    return fl


def _lower(fl, batched=True):
    return build_pipeline(fusion=True, batched_lowering=batched).run(
        PhysicalPlan.from_dataflow(fl))


def _table(rows):
    return Table([("x", jax.Array)], [(r,) for r in rows])


def test_bucket_rows_pads_to_power_of_two():
    assert [bucket_rows(n) for n in (1, 2, 3, 5, 8, 9, 64, 65, 200)] == \
        [1, 2, 4, 8, 8, 16, 64, 128, 256]


def test_batched_lowering_produces_batched_op_and_annotations():
    plan = _lower(_chain())
    (op,) = plan.ops
    assert isinstance(op.op, BatchedJittedFuse)
    assert op.batchable and op.batch_buckets
    per_row = _lower(_chain(), batched=False)
    assert isinstance(per_row.ops[0].op, JittedFuse)
    assert not isinstance(per_row.ops[0].op, BatchedJittedFuse)
    assert not per_row.ops[0].batchable


def test_batched_matches_interpreted_multi_row():
    plan = _lower(_chain())
    interp = build_pipeline(fusion=True, jit_fusion=False).run(
        PhysicalPlan.from_dataflow(_chain()))
    t = _table([jnp.linspace(-2.0, 2.0, 33) * (i + 1) for i in range(5)])
    got, want = plan.execute_local(t), interp.execute_local(t)
    assert [r.row_id for r in got.rows] == [r.row_id for r in want.rows]
    for a, b in zip(got.rows, want.rows):
        np.testing.assert_allclose(np.asarray(a.values[0]),
                                   np.asarray(b.values[0]), rtol=1e-6)


def test_one_dispatch_per_batch_bucket():
    plan = _lower(_chain())
    op = plan.ops[0].op
    t = _table([jnp.ones(16) * i for i in range(5)])   # 5 rows -> bucket 8
    plan.execute_local(t)
    assert op.batch_dispatches == 1 and op.rows_batched == 5
    plan.execute_local(_table([jnp.ones(16)] * 6))     # same bucket
    assert op.batch_dispatches == 2


def test_ragged_batch_splits_into_shape_groups():
    plan = _lower(_chain())
    op = plan.ops[0].op
    t = _table([jnp.ones(8), jnp.ones(16), jnp.ones(8) * 3, jnp.ones(16) * 2])
    out = plan.execute_local(t)
    assert op.batch_dispatches == 2                    # one per shape group
    # original row order preserved across groups
    assert [r.values[0].shape for r in out.rows] == [(8,), (16,), (8,), (16,)]
    for r_in, r_out in zip(t.rows, out.rows):
        np.testing.assert_allclose(np.asarray(r_out.values[0]),
                                   np.asarray(_f2(_f1(r_in.values[0]))),
                                   rtol=1e-6)


def test_empty_table_through_batched_path():
    plan = _lower(_chain())
    out = plan.execute_local(Table([("x", jax.Array)]))
    assert len(out) == 0 and plan.ops[0].op.batch_dispatches == 0


def test_executable_cache_hits_across_reregistration():
    """Re-lowering the identical chain (same fn objects) must reuse the
    compiled executable: zero new traces, a cache hit per repeat."""
    EXECUTABLE_CACHE.clear()
    t = _table([jnp.ones(12) * i for i in range(3)])
    _lower(_chain()).execute_local(t)
    sig = chain_signature([ops.Map(_f1, ["x"]), ops.Map(_f2, ["x"])])
    stats0 = EXECUTABLE_CACHE.stats()
    assert stats0["misses"] == 1 and stats0["traces"] == 1
    # fresh Dataflow + fresh plan + fresh BatchedJittedFuse, same functions
    _lower(_chain()).execute_local(t)
    stats1 = EXECUTABLE_CACHE.stats()
    assert stats1["traces"] == stats0["traces"]        # ZERO re-traces
    assert stats1["hits"] == stats0["hits"] + 1
    assert stats1["misses"] == stats0["misses"]
    assert EXECUTABLE_CACHE.traces(sig) == 1


def test_executable_cache_misses_across_bucket_boundaries():
    EXECUTABLE_CACHE.clear()
    plan = _lower(_chain())
    plan.execute_local(_table([jnp.ones(12)] * 3))     # bucket 4
    assert EXECUTABLE_CACHE.stats()["misses"] == 1
    plan.execute_local(_table([jnp.ones(12)] * 4))     # bucket 4: hit
    assert EXECUTABLE_CACHE.stats()["misses"] == 1
    assert EXECUTABLE_CACHE.stats()["hits"] == 1
    plan.execute_local(_table([jnp.ones(12)] * 5))     # bucket 8: miss
    stats = EXECUTABLE_CACHE.stats()
    assert stats["misses"] == 2 and stats["traces"] == 2


def test_executable_cache_misses_on_dtype_change():
    EXECUTABLE_CACHE.clear()
    plan = _lower(_chain())
    plan.execute_local(_table([jnp.ones(12, jnp.float32)] * 2))
    plan.execute_local(_table([jnp.ones(12, jnp.int32)] * 2))
    stats = EXECUTABLE_CACHE.stats()
    assert stats["misses"] == 2 and stats["chains"] == 1


def test_redefined_function_gets_a_fresh_cache_entry():
    EXECUTABLE_CACHE.clear()

    def g(x: jax.Array) -> jax.Array:
        return x + 1.0

    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(_f1, names=["x"], gpu=True).map(g, names=["x"],
                                                       gpu=True)
    _lower(fl).execute_local(_table([jnp.ones(4)] * 2))
    assert EXECUTABLE_CACHE.stats()["chains"] == 1
    _lower(_chain()).execute_local(_table([jnp.ones(4)] * 2))
    assert EXECUTABLE_CACHE.stats()["chains"] == 2


def test_singleton_rows_use_per_row_executable():
    plan = _lower(_chain())
    op = plan.ops[0].op
    out = plan.execute_local(_table([jnp.linspace(0.0, 1.0, 9)]))
    assert op.row_dispatches == 1 and op.batch_dispatches == 0
    np.testing.assert_allclose(
        np.asarray(out.rows[0].values[0]),
        np.asarray(_f2(_f1(jnp.linspace(0.0, 1.0, 9)))), rtol=1e-6)


def test_vmap_failure_after_singleton_success_degrades_to_per_row():
    """A chain proven jit-traceable per row but failing under vmap must
    latch the per-row jitted path, not raise for the deployment's life."""
    calls = {"n": 0}

    def hostile(x: jax.Array) -> jax.Array:
        calls["n"] += 1
        if calls["n"] > 1:                  # first trace (per-row jit) ok,
            raise TypeError("no vmap for me")   # second trace (vmap) fails
        return x + 1.0

    def double(x: jax.Array) -> jax.Array:
        return x * 2.0

    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(hostile, names=["x"], gpu=True).map(
        double, names=["x"], gpu=True)
    plan = _lower(fl)
    op = plan.ops[0].op
    # singleton first: proves the per-row executable
    plan.execute_local(_table([jnp.ones(4)]))
    assert op._jit_succeeded and not op._vmap_fallback
    # multi-row batch: vmap trace fails -> degrade to per-row, not raise
    out = plan.execute_local(_table([jnp.ones(4), jnp.ones(4) * 2]))
    assert op._vmap_fallback and not op._fallback
    assert list(op.latched) == ["vmap"]
    assert "no vmap for me" in op.latched["vmap"]
    np.testing.assert_allclose(np.asarray(out.rows[0].values[0]),
                               np.full(4, 4.0))
    # and it stays on the per-row path afterwards
    out2 = plan.execute_local(_table([jnp.ones(4)] * 3))
    assert len(out2) == 3


def test_executable_cache_lru_eviction_bounds_chains():
    from repro.core.lowering import ExecutableCache

    cache = ExecutableCache(max_chains=2)
    x = jnp.ones((2, 4))

    def mk(i):
        def f(v, _i=i):
            return v + _i
        return f

    sigs = [(mk(i),) for i in range(3)]
    for s in sigs:
        cache.executable(s, list(s), ((2, 4),), ("float32",))(x)
    stats = cache.stats()
    assert stats["chains"] == 2 and stats["evictions"] == 1
    # evicted chain's entries went with it
    assert all(k[0] != sigs[0] for k in cache._entries)


def test_batched_falls_back_for_untraceable_fns():
    def branchy(x: jax.Array) -> jax.Array:
        return x + 1 if float(x.sum()) > 0 else x - 1   # not traceable

    def double(x: jax.Array) -> jax.Array:
        return x * 2

    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(branchy, names=["x"], gpu=True).map(
        double, names=["x"], gpu=True)
    plan = _lower(fl)
    assert isinstance(plan.ops[0].op, BatchedJittedFuse)
    out = plan.execute_local(_table([jnp.ones(4), -jnp.ones(4)]))
    np.testing.assert_allclose(np.asarray(out.rows[0].values[0]),
                               np.full(4, 4.0))
    np.testing.assert_allclose(np.asarray(out.rows[1].values[0]),
                               np.full(4, -4.0))


def test_non_stackable_values_fall_back_per_row():
    """Annotations can lie: object-typed values that numpy can't stack go
    down the per-row path instead of crashing the batch."""
    plan = _lower(_chain())
    op = plan.ops[0].op

    class Weird:
        pass

    t = Table([("x", jax.Array)])
    t.insert((Weird(),))
    with pytest.raises(Exception):
        # per-row jitted path also rejects it, but the error comes from the
        # chain, not from the stacker
        plan.execute_local(t)


def test_runtime_dag_carries_batched_fn():
    from repro.runtime.dag import RuntimeDag
    plan = _lower(_chain())
    dag = RuntimeDag.from_plan(plan, "bf")
    (node,) = dag.nodes.values()
    assert node.batched_fn is not None and node.jitted
    assert node.batch_buckets == plan.ops[0].batch_buckets
    out = node.batched_fn([_table([jnp.ones(4)] * 3)], None)
    assert len(out) == 3


# ---------------------------------------------------------------------------
# Filter-in-jit lowering (boolean masking inside the jitted body)
# ---------------------------------------------------------------------------

def _pos(x: jax.Array) -> bool:
    return x.sum() > 0


def _filter_chain():
    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(_f1, names=["x"], gpu=True).filter(_pos, gpu=True) \
        .map(_f2, names=["x"], gpu=True)
    return fl


@pytest.mark.parametrize("mk_rows", [
    # mixed: some rows pass, some are masked out
    lambda: [jnp.linspace(-1.0, 1.0, 8) + i - 2 for i in range(5)],
    # empty-result batch: every row filtered
    lambda: [-jnp.ones(8) * (i + 1) for i in range(4)],
    # all-pass batch: no row filtered
    lambda: [jnp.ones(8) * (i + 1) for i in range(4)],
], ids=["mixed", "empty-result", "all-pass"])
def test_filter_chain_lowers_and_matches_interpreted(mk_rows):
    """A Filter fuses into the jitted body as a mask column: the chain
    still executes as ONE vmapped dispatch, and the output table (row ids,
    values, dropped rows) is identical to the interpreted path."""
    plan = _lower(_filter_chain())
    op = plan.ops[0].op
    assert isinstance(op, BatchedJittedFuse) and op._has_filter
    interp = build_pipeline(fusion=True, jit_fusion=False).run(
        PhysicalPlan.from_dataflow(_filter_chain()))
    t = _table(mk_rows())
    got, want = plan.execute_local(t), interp.execute_local(t)
    assert op.batch_dispatches == 1          # masked rows cost no dispatch
    assert [r.row_id for r in got.rows] == [r.row_id for r in want.rows]
    for a, b in zip(got.rows, want.rows):
        np.testing.assert_allclose(np.asarray(a.values[0]),
                                   np.asarray(b.values[0]), rtol=1e-6)


def test_filter_chain_per_row_jitted_matches_interpreted():
    """The per-row executable threads the keep-bit too (used below the
    batching crossover and for singletons)."""
    plan = _lower(_filter_chain(), batched=False)
    op = plan.ops[0].op
    assert isinstance(op, JittedFuse) and not isinstance(op,
                                                         BatchedJittedFuse)
    interp = build_pipeline(fusion=True, jit_fusion=False).run(
        PhysicalPlan.from_dataflow(_filter_chain()))
    t = _table([jnp.linspace(-1.0, 1.0, 8) + i - 2 for i in range(5)])
    got, want = plan.execute_local(t), interp.execute_local(t)
    assert [r.row_id for r in got.rows] == [r.row_id for r in want.rows]
    for a, b in zip(got.rows, want.rows):
        np.testing.assert_allclose(np.asarray(a.values[0]),
                                   np.asarray(b.values[0]), rtol=1e-6)


def test_filter_chain_singleton_routes_per_row():
    plan = _lower(_filter_chain())
    op = plan.ops[0].op
    kept = plan.execute_local(_table([jnp.ones(8)]))
    dropped = plan.execute_local(_table([-jnp.ones(8)]))
    assert op.batch_dispatches == 0 and op.row_dispatches == 2
    assert len(kept) == 1 and len(dropped) == 0


# ---------------------------------------------------------------------------
# device residency at the operator level
# ---------------------------------------------------------------------------

def test_apply_batched_emits_and_consumes_device_tables():
    from repro.core.table import DeviceTable

    plan = _lower(_chain())
    op = plan.ops[0].op
    t = _table([jnp.linspace(-1.0, 1.0, 8) * (i + 1) for i in range(3)])
    dt = op.apply_batched([t], emit_device=True)
    assert isinstance(dt, DeviceTable)
    assert dt.nrows == 3 and dt.cap == 4      # padded to the bucket
    assert [i for i in dt.row_ids] == [r.row_id for r in t.rows]
    # the emitted DeviceTable holds the chain's output...
    want = op.apply_batched([t])
    out = dt.to_table()
    assert [r.row_id for r in out.rows] == [r.row_id for r in want.rows]
    for a, b in zip(out.rows, want.rows):
        np.testing.assert_allclose(np.asarray(a.values[0]),
                                   np.asarray(b.values[0]), rtol=1e-6)
    # ...and a chain handed a DeviceTable *input* computes the same rows
    # as the host-table path, without re-stacking
    dt_in = DeviceTable.from_table(t, pad_to=4)
    dt_in.donatable = False
    got = op.apply_batched([dt_in])
    assert [r.row_id for r in got.rows] == [r.row_id for r in want.rows]
    for a, b in zip(got.rows, want.rows):
        np.testing.assert_allclose(np.asarray(a.values[0]),
                                   np.asarray(b.values[0]), rtol=1e-6)


def test_device_chain_donates_exclusive_buffers():
    """A donatable DeviceTable handed to a chain has its buffers donated
    to XLA (donate_argnums): after the call the input arrays are deleted —
    the allocation was reused for the output batch."""
    from repro.core.table import DeviceTable

    plan = _lower(_chain())
    op = plan.ops[0].op
    t = _table([jnp.linspace(-1.0, 1.0, 8) * (i + 1) for i in range(4)])
    dt = DeviceTable.from_table(t, pad_to=4)
    assert dt.donatable
    out = op.apply_batched([dt], emit_device=True)
    assert len(out) == 4 and not dt.donatable    # consumed
    with pytest.raises(RuntimeError):
        jax.device_get(dt.columns[0])            # donated -> deleted
    # shared (non-donatable) inputs survive execution
    dt2 = DeviceTable.from_table(t, pad_to=4)
    dt2.donatable = False
    op.apply_batched([dt2])
    np.testing.assert_allclose(np.asarray(jax.device_get(dt2.columns[0]))[0],
                               np.asarray(t.rows[0].values[0]))


def test_filter_chain_stays_device_resident_until_boundary():
    """Masked (filtered) rows ride along on the device; compaction happens
    only at the device->host boundary."""
    plan = _lower(_filter_chain())
    op = plan.ops[0].op
    t = _table([jnp.ones(8) * (1 if i % 2 else -1) * (i + 1)
                for i in range(4)])
    dt = op.apply_batched([t], emit_device=True)
    assert dt.mask is not None and dt.nrows == 4   # rows masked, not gone
    out = dt.to_table()
    assert [r.row_id for r in out.rows] == \
        [r.row_id for i, r in enumerate(t.rows) if i % 2]


# ---------------------------------------------------------------------------
# cost-based exec-path routing (measured per-row vs batched crossover)
# ---------------------------------------------------------------------------

def test_router_prefers_per_row_below_measured_crossover():
    """With a profile that says n per-row dispatches are cheaper than one
    batched dispatch at n's bucket, a small batch takes the per-row
    executable — no stacking, no vmapped dispatch."""
    EXECUTABLE_CACHE.clear()
    plan = _lower(_chain())
    op = plan.ops[0].op
    prof = EXECUTABLE_CACHE.profile(op._sig)
    prof.note_per_row(0.0001)              # 0.1ms/row
    prof.note_batched(4, 0.01)             # warm-up sample (discarded)
    prof.note_batched(4, 0.01)             # 10ms per 4-row dispatch
    t = _table([jnp.ones(8) * i for i in range(4)])
    out = plan.execute_local(t)
    assert len(out) == 4
    assert op.batch_dispatches == 0 and op.row_dispatches == 4
    # flip the measurements: same batch now takes the vmapped path
    prof.batched_s[4] = 0.00001
    plan.execute_local(t)
    assert op.batch_dispatches == 1


def test_router_probes_batched_path_when_unmeasured():
    EXECUTABLE_CACHE.clear()
    plan = _lower(_chain())
    op = plan.ops[0].op
    prof = EXECUTABLE_CACHE.profile(op._sig)
    prof.note_per_row(0.0001)
    # no batched estimate for this bucket yet -> batch (the call doubles
    # as the probe that measures the batched path)
    plan.execute_local(_table([jnp.ones(8) * i for i in range(4)]))
    assert op.batch_dispatches == 1


def test_chain_profile_crossover_math():
    from repro.core.lowering import ChainProfile

    p = ChainProfile()
    assert p.crossover_rows() is None      # unmeasured
    p.note_per_row(0.001)                  # 1ms/row
    for _ in range(2):                     # first sample per bucket is
        p.note_batched(4, 0.003)           # discarded as warm-up
        p.note_batched(8, 0.004)
    assert p.batched_s == {4: 0.003, 8: 0.004}
    # n=2 -> bucket 4: 2ms < 3ms per-row wins; n=3 -> 3ms >= 3ms: batch
    assert p.prefer_per_row(2, 4) and not p.prefer_per_row(3, 4)
    assert p.crossover_rows() == 3
    assert p.snapshot()["crossover_rows"] == 3


def test_routed_per_row_timing_feeds_profile():
    """Multi-row tables routed below the crossover feed the per-row EWMA
    with warm, amortized measurements.  Singletons and cold (tracing)
    calls never record — their cost is not the marginal per-row cost —
    and plain per-row chains never consult the router, so they skip the
    timing (and its host sync) entirely."""
    EXECUTABLE_CACHE.clear()
    plan = _lower(_chain())
    op = plan.ops[0].op
    plan.execute_local(_table([jnp.ones(8)]))   # cold singleton: no sample
    plan.execute_local(_table([jnp.ones(8)]))   # warm singleton: no sample
    prof = EXECUTABLE_CACHE.profile(op._sig)
    assert prof.per_row_samples == 0
    # make the router send a multi-row table per-row: that one records
    prof.note_per_row(0.0001)
    prof.note_batched(4, 1.0)
    prof.note_batched(4, 1.0)              # first sample is warm-up
    plan.execute_local(_table([jnp.ones(8) * i for i in range(3)]))
    assert op.batch_dispatches == 0        # routed per-row
    assert prof.per_row_samples == 2       # injected + measured
    # plain per-row lowering: no router, no timing
    per_row_plan = _lower(_chain(), batched=False)
    assert not getattr(per_row_plan.ops[0].op, "adaptive_routing", False)


def test_planner_decides_batched_lowering_from_hints():
    from repro.core.planner import make_plan
    from repro.runtime.netmodel import NetModel

    def slow_np(x: jax.Array) -> jax.Array:
        return jnp.sqrt(jnp.abs(x) + 1.0)

    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(_f1, names=["x"], gpu=True).map(
        slow_np, names=["x"], gpu=True, batching=True)
    multi = _table([jnp.ones(64)] * 4)
    plan = make_plan(fl, multi, net=NetModel(scale=0.0), runs=1)
    if plan.jit_fusion:
        assert plan.batched_lowering          # batch hint present
    assert "batched_lowering" in plan.flags


def test_latch_counts_in_obs_and_shows_in_explain():
    """A latch onto the interpreted path is counted in
    ``repro.obs.EVENTS`` under its registered key and named, with the
    error behind it, by the deployment's ``explain()``."""
    from repro.obs import EVENTS, keys as okeys
    from repro.runtime import NetModel, Runtime

    def branchy(x: jax.Array) -> jax.Array:
        return x + 1 if float(x.sum()) > 0 else x - 1   # not traceable

    def double(x: jax.Array) -> jax.Array:
        return x * 2

    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(branchy, names=["x"], gpu=True).map(
        double, names=["x"], gpu=True)
    key = okeys.lowering_latch("fuse")
    before = EVENTS.snapshot().get(key, 0)
    rt = Runtime(n_cpu=1, n_gpu=1, net=NetModel(scale=0.0))
    try:
        dep = fl.deploy(rt, fusion=True, name="latching")
        assert "lowering fallbacks" not in dep.explain()
        out = dep.execute(_table([jnp.ones(4)] * 2)).result(60)
    finally:
        rt.stop()
    np.testing.assert_allclose(np.asarray(out.rows[0].values[0]),
                               np.full(4, 4.0))
    assert EVENTS.snapshot().get(key, 0) == before + 1
    text = dep.explain()
    assert "lowering fallbacks" in text and "fuse after" in text
