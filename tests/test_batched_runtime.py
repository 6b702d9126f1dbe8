"""Batched dispatch path through the runtime + Batcher robustness.

* the merged table feeds straight into the batched callable (one vmapped
  XLA dispatch per batch), results demultiplex back per request without
  per-request waiter threads;
* empty requests and zero-row tables don't crash the batch;
* duplicate ``row_id``s across requests demux exactly (no duplication, no
  drops — the old set-membership filter did both);
* ``locality_key`` steers batched placement to cache-warm executors;
* per-node batch-size/latency metrics land in ``Runtime.metrics``;
* ``Batcher`` close/drain is safe under concurrent submitters.
"""
import threading
import time

import numpy as np
import pytest

from repro.core.dataflow import Dataflow
from repro.core.table import Row, Table
from repro.runtime.netmodel import NetModel
from repro.runtime.runtime import Runtime
from repro.serving.batcher import Batcher


@pytest.fixture
def rt():
    r = Runtime(n_cpu=4, net=NetModel(scale=0.0), batch_wait_ms=5.0)
    yield r
    r.stop()


def _batched_flow(rt, fn=None):
    if fn is None:
        def fn(x: int) -> int:
            return x * 10
    fl = Dataflow([("x", int)])
    fl.output = fl.map(fn, names=["y"], batching=True)
    fl.deploy(rt)
    return fl


def test_batched_demux_concurrent_requests(rt):
    fl = _batched_flow(rt)
    futs = [fl.execute(Table([("x", int)], [(i,)])) for i in range(12)]
    outs = [f.result(timeout=10).rows[0].values[0] for f in futs]
    assert outs == [i * 10 for i in range(12)]
    b = rt._batchers[next(iter(rt._batchers))]
    assert max(b.batch_sizes) > 1


def test_empty_table_request_through_batching(rt):
    """A zero-row request used to crash the batch fn (merged[0] on an
    empty merge) — it must come back as an empty result instead."""
    fl = _batched_flow(rt)
    empty = fl.execute(Table([("x", int)]))
    full = fl.execute(Table([("x", int)], [(3,)]))
    assert len(empty.result(timeout=10)) == 0
    assert full.result(timeout=10).rows[0].values[0] == 30


def test_all_empty_batch(rt):
    fl = _batched_flow(rt)
    futs = [fl.execute(Table([("x", int)])) for _ in range(4)]
    assert all(len(f.result(timeout=10)) == 0 for f in futs)


def test_duplicate_row_ids_demux_exactly(rt):
    """Two requests sharing a row_id each get exactly their own row back
    (the old set-membership demux handed both rows to both requests)."""
    fl = _batched_flow(rt)
    t1 = Table([("x", int)])
    t1.insert(Row((7,), row_id=999))
    t2 = Table([("x", int)])
    t2.insert(Row((8,), row_id=999))
    f1, f2 = fl.execute(t1), fl.execute(t2)
    r1, r2 = f1.result(timeout=10), f2.result(timeout=10)
    assert len(r1) == 1 and len(r2) == 1
    assert sorted([r1.rows[0].values[0], r2.rows[0].values[0]]) == [70, 80]


def test_batched_filter_demux_by_row_id(rt):
    """When the fn drops rows (count changes), demux falls back to row-id
    matching and dropped rows simply vanish from their request."""
    def keep_even(x: int) -> bool:
        return x % 2 == 0

    fl = Dataflow([("x", int)])
    fl.output = fl.filter(keep_even, batching=True)
    fl.deploy(rt)
    futs = [fl.execute(Table([("x", int)], [(i,)])) for i in range(6)]
    outs = [f.result(timeout=10) for f in futs]
    assert [len(o) for o in outs] == [1, 0, 1, 0, 1, 0]


def test_batch_metrics_recorded(rt):
    fl = _batched_flow(rt)
    futs = [fl.execute(Table([("x", int)], [(i,)])) for i in range(6)]
    for f in futs:
        f.result(timeout=10)
    size_keys = [k for k in rt.metrics if k.endswith("/size")]
    lat_keys = [k for k in rt.metrics if k.endswith("/latency_s")]
    exec_keys = [k for k in rt.metrics if k.endswith("/exec_s")]
    assert size_keys and lat_keys and exec_keys
    assert sum(rt.metrics[size_keys[0]]) == 6
    assert all(v >= 0 for v in rt.metrics[lat_keys[0]])


def test_batched_error_reaches_every_request(rt):
    def boom(x: int) -> int:
        raise RuntimeError("model exploded")

    fl = _batched_flow(rt, fn=boom)
    futs = [fl.execute(Table([("x", int)], [(i,)])) for i in range(3)]
    for f in futs:
        with pytest.raises(RuntimeError, match="model exploded"):
            f.result(timeout=10)


def test_locality_key_propagates_into_batched_dispatch():
    """Batched nodes get cache-local placement: with a fused lookup and
    batching, requests land on the executor already caching the ref."""
    rt = Runtime(n_cpu=4, net=NetModel(scale=0.0), batch_wait_ms=2.0)
    try:
        rt.kvs.put("hot", np.zeros(1000), charge=False)
        ex = rt.pool.by_class("cpu")[2]
        ex.cache.get("hot")                 # warm exactly one executor

        def use(key: str, lookup) -> int:
            return 1

        fl = Dataflow([("key", str)])
        fl.output = fl.lookup("key", column=True).map(
            use, names=["v"], batching=True)
        fl.deploy(rt, locality=True)
        for _ in range(6):
            fl.execute(Table([("key", str)],
                             [("hot",)])).result(timeout=10)
        # all lookups after the first warm hit the cached executor
        assert ex.cache.hits >= 5
    finally:
        rt.stop()


# ---------------------------------------------------------------------------
# Device-resident pipelines through the runtime
# ---------------------------------------------------------------------------

def _device_chain_flow(jax, jnp, batching_first=False):
    from repro.core.dataflow import Dataflow

    def g1(x: jax.Array) -> jax.Array:
        return jnp.tanh(x * 1.01 + 0.1)

    def g2(x: jax.Array) -> jax.Array:
        return x * x - 0.5 * x

    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(g1, names=["x"], gpu=True, batching=batching_first) \
        .map(g2, names=["x"], gpu=True)
    return fl, (g1, g2)


def test_device_chain_performs_exactly_one_device_get(monkeypatch):
    """A two-GPU-node chain hands a DeviceTable from the first node's
    executor callback straight to the second node: ONE host->device stack
    at entry, ONE device_get at the output boundary — not one per node."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.compiler import compile_flow
    from repro.core.passes import LowerJaxChainsPass, PassPipeline
    from repro.core.table import Table as T

    gets = {"n": 0}
    real_get = jax.device_get

    def counting_get(*a, **kw):
        gets["n"] += 1
        return real_get(*a, **kw)

    rt2 = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0))
    try:
        fl, (g1, g2) = _device_chain_flow(jax, jnp)
        # no fusion pass: the two maps stay separate DAG nodes, each
        # individually lowered (min_ops=1) -> a device-resident edge
        dep = compile_flow(fl, rt2, pipeline=PassPipeline(
            [LowerJaxChainsPass(min_ops=1)]))
        nodes = dep.dag.topo()
        assert [n.device_resident for n in nodes] == [True, True]
        assert [n.emits_device for n in nodes] == [True, False]
        # host (numpy) request payloads, as they arrive off the network —
        # the chain entry then pays uploads only, and the single counted
        # device_get is the output-boundary gather
        t = T([("x", jax.Array)],
              [(np.linspace(-1.0, 1.0, 8) * (i + 1),) for i in range(3)])
        # warm the executables (compile-time device_gets are not the claim)
        dep.execute(t).result(timeout=30)
        monkeypatch.setattr(jax, "device_get", counting_get)
        out = dep.execute(t).result(timeout=30)
        monkeypatch.undo()
        assert gets["n"] == 1
        assert [r.row_id for r in out.rows] == [r.row_id for r in t.rows]
        for r_in, r_out in zip(t.rows, out.rows):
            np.testing.assert_allclose(
                np.asarray(r_out.values[0]),
                np.asarray(g2(g1(r_in.values[0]))), rtol=1e-6)
    finally:
        rt2.stop()


def test_device_chain_demux_after_batching_node():
    """A request-batching first stage emits ONE merged DeviceTable; the
    demux slices it per request on the device (no host copy) and each
    request's slice flows through the second stage correctly."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.compiler import compile_flow
    from repro.core.passes import LowerJaxChainsPass, PassPipeline
    from repro.core.table import Table as T

    rt2 = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0),
                  batch_wait_ms=3.0)
    try:
        fl, (g1, g2) = _device_chain_flow(jax, jnp, batching_first=True)
        dep = compile_flow(fl, rt2, pipeline=PassPipeline(
            [LowerJaxChainsPass(min_ops=1)]))
        assert [n.emits_device for n in dep.dag.topo()] == [True, False]
        futs = [dep.execute(T([("x", jax.Array)],
                              [(jnp.ones(8) * (i + 1),),
                               (jnp.ones(8) * (i + 10),)]))
                for i in range(6)]
        for i, f in enumerate(futs):
            out = f.result(timeout=30)
            assert len(out) == 2
            for j, scale in enumerate((i + 1, i + 10)):
                np.testing.assert_allclose(
                    np.asarray(out.rows[j].values[0]),
                    np.asarray(g2(g1(jnp.ones(8) * scale))), rtol=1e-6)
    finally:
        rt2.stop()


def test_device_demux_fanout_does_not_donate_shared_slices():
    """A batching device node feeding TWO device consumers: the demuxed
    per-request slice reaches both, so neither may donate its buffers —
    donation would delete arrays the sibling still needs."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.compiler import compile_flow
    from repro.core.dataflow import Dataflow
    from repro.core.passes import LowerJaxChainsPass, PassPipeline
    from repro.core.table import Table as T

    def g1(x: jax.Array) -> jax.Array:
        return jnp.tanh(x * 1.01 + 0.1)

    def g2(x: jax.Array) -> jax.Array:
        return x * 2.0

    def g3(x: jax.Array) -> jax.Array:
        return x + 1.0

    rt2 = Runtime(n_cpu=2, n_gpu=1, net=NetModel(scale=0.0),
                  batch_wait_ms=3.0)
    try:
        fl = Dataflow([("x", jax.Array)])
        a = fl.map(g1, names=["x"], gpu=True, batching=True)
        fl.output = a.map(g2, names=["x"], gpu=True).union(
            a.map(g3, names=["x"], gpu=True))
        dep = compile_flow(fl, rt2, pipeline=PassPipeline(
            [LowerJaxChainsPass(min_ops=1)]))
        emitter = next(n for n in dep.dag.nodes.values() if n.batching)
        assert emitter.emits_device
        futs = [dep.execute(T([("x", jax.Array)],
                              [(jnp.ones(8) * (i + 1),)]))
                for i in range(6)]
        for i, f in enumerate(futs):
            out = f.result(timeout=30)       # donation bug: one branch
            assert len(out) == 2             # ran on deleted arrays
            got = sorted(float(np.asarray(r.values[0])[0]) for r in out.rows)
            h = float(np.asarray(g1(jnp.ones(8) * (i + 1)))[0])
            want = sorted([h * 2.0, h + 1.0])
            np.testing.assert_allclose(got, want, rtol=1e-6)
    finally:
        rt2.stop()


def test_device_edge_consumer_pinned_to_producer_executor():
    """With several GPU executors, a node consuming a DeviceTable must run
    on the executor that produced it — the batch lives in that machine's
    device memory, so placing the consumer elsewhere would be the very
    host/network hop the residency analysis claims to eliminate."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.compiler import compile_flow
    from repro.core.passes import LowerJaxChainsPass, PassPipeline
    from repro.core.table import DeviceTable
    from repro.core.table import Table as T

    rt2 = Runtime(n_cpu=2, n_gpu=3, net=NetModel(scale=0.0), seed=7)
    shipped = []
    orig_charge = rt2.net.charge
    rt2.net.charge = lambda nbytes: (shipped.append(nbytes),
                                     orig_charge(nbytes))[1]
    try:
        fl, (g1, g2) = _device_chain_flow(jax, jnp)
        dep = compile_flow(fl, rt2, pipeline=PassPipeline(
            [LowerJaxChainsPass(min_ops=1)]))
        assert [n.emits_device for n in dep.dag.topo()] == [True, False]
        for i in range(8):
            out = dep.execute(T([("x", jax.Array)],
                                [(jnp.ones(8) * (i + 1),),
                                 (jnp.ones(8) * (i + 2),)])) \
                .result(timeout=30)
            assert len(out) == 2
        # no DeviceTable ever crossed executors -> no network charge for
        # device-resident inputs (host inputs come from the source: free)
        assert shipped == []
    finally:
        rt2.stop()


def test_device_residency_off_restores_per_node_gathers():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.ir import PhysicalPlan
    from repro.core.passes import LowerJaxChainsPass, PassPipeline
    from repro.runtime.dag import RuntimeDag

    fl, _ = _device_chain_flow(jax, jnp)
    plan = PassPipeline([LowerJaxChainsPass(min_ops=1)]).run(
        PhysicalPlan.from_dataflow(fl))
    dag = RuntimeDag.from_plan(plan, "staged", device_resident=False)
    assert all(not n.emits_device for n in dag.nodes.values())


# ---------------------------------------------------------------------------
# Adaptive batch-wait deadline
# ---------------------------------------------------------------------------

def test_adaptive_wait_full_window_under_dense_traffic():
    b = Batcher(lambda args: list(args), max_batch=64, max_wait_ms=100.0)
    try:
        for i in range(8):                    # back-to-back arrivals
            b.submit(i)
        assert b.effective_wait() == b.max_wait
    finally:
        b.close()


def test_adaptive_wait_shrinks_toward_zero_when_sparse():
    """After sparse arrivals (gaps beyond the window) a lone request must
    not sit out the full wait window."""
    b = Batcher(lambda args: list(args), max_batch=64, max_wait_ms=300.0)
    try:
        for i in range(3):                    # train the gap EWMA: ~0.5s
            b.call(i, timeout=5.0)
            time.sleep(0.5)
        assert b.effective_wait() < 0.05
        t0 = time.perf_counter()
        b.call(99, timeout=5.0)               # lone request
        assert time.perf_counter() - t0 < 0.15   # far below the 0.3s window
        # gap samples are clamped, so a dense burst after the idle spell
        # recovers the full window within a few arrivals (submit, not
        # call: a sequential caller's gaps include the wait itself)
        for i in range(10):
            b.submit(i)
        assert b.effective_wait() == b.max_wait
    finally:
        b.close()


def test_adaptive_wait_disabled_keeps_fixed_deadline():
    b = Batcher(lambda args: list(args), max_batch=4, max_wait_ms=50.0,
                adaptive_wait=False)
    try:
        b.call(1, timeout=5.0)
        time.sleep(0.2)
        b.call(2, timeout=5.0)
        assert b.effective_wait() == b.max_wait
    finally:
        b.close()


# ---------------------------------------------------------------------------
# Batcher close/drain robustness
# ---------------------------------------------------------------------------

def test_batcher_close_fails_queued_items_fast():
    started = threading.Event()

    def slow_fn(args):
        started.set()
        time.sleep(0.3)
        return [a for a in args]

    b = Batcher(slow_fn, max_batch=1, max_wait_ms=1.0)
    b.submit(1)                      # occupies the loop in slow_fn
    started.wait(2.0)
    tail = b.submit(2)               # queued behind the slow batch
    t0 = time.perf_counter()
    b.close()
    assert tail.event.wait(1.0)      # failed promptly, not after timeout
    assert isinstance(tail.error, RuntimeError)
    assert time.perf_counter() - t0 < 2.0


def test_batcher_submit_after_close_raises():
    b = Batcher(lambda args: list(args))
    b.close()
    with pytest.raises(RuntimeError):
        b.submit(1)
    b.close()                        # idempotent


def test_batcher_close_race_under_concurrent_submitters():
    """Hammer submit() from many threads while close() lands: every call
    must either complete or fail fast — nothing hangs, nothing is lost."""
    b = Batcher(lambda args: [a * 2 for a in args],
                max_batch=4, max_wait_ms=0.5)
    results, errors = [], []
    lock = threading.Lock()

    def submitter(i):
        try:
            r = b.call(i, timeout=5.0)
            with lock:
                results.append(r)
        except (RuntimeError, TimeoutError) as e:
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(32)]
    for i, t in enumerate(threads):
        t.start()
        if i == 16:
            time.sleep(0.005)
            b.close()
    for t in threads:
        t.join(timeout=6.0)
        assert not t.is_alive()
    assert len(results) + len(errors) == 32
    assert all(isinstance(r, int) for r in results)
    # nothing may sit in the queue after close
    assert b.q.empty()


def test_batcher_drain_on_reregistration(rt):
    """Re-registering under the SAME dag name retires the old batchers;
    requests before and after the swap both complete."""
    from repro.core.compiler import compile_flow

    def mk():
        def model(x: int) -> int:
            return x * 10
        fl = Dataflow([("x", int)])
        fl.output = fl.map(model, names=["y"], batching=True)
        return compile_flow(fl, rt, name="redep")

    d1 = mk()
    assert d1.execute(Table([("x", int)], [(1,)])) \
        .result(timeout=10).rows[0].values[0] == 10
    d2 = mk()                        # re-registers "redep"
    assert d2.execute(Table([("x", int)], [(2,)])) \
        .result(timeout=10).rows[0].values[0] == 20
    # the old deployment's batcher was retired (and closed once drained)
    assert rt._batchers                     # fresh batcher exists


# ---------------------------------------------------------------------------
# Device gate: one batch in flight, what waits meanwhile merges
# ---------------------------------------------------------------------------

def _until(cond, timeout=2.0):
    deadline = time.perf_counter() + timeout
    while not cond():
        if time.perf_counter() > deadline:
            return False
        time.sleep(0.005)
    return True


def _gated_batcher(max_batch=4):
    """A Batcher whose fn holds the gate for every batch it flushes; the
    test releases the holds by hand."""
    from repro.serving.batcher import DeviceGate

    gate = DeviceGate("node", lambda: 1)
    batches, tokens = [], []

    def fn(args):
        batches.append(list(args))
        tokens.append(gate.hold(time.perf_counter() + 30.0))
        return list(args)

    b = Batcher(fn, max_batch=max_batch, max_wait_ms=1.0)
    b.gate = gate
    return b, gate, batches, tokens


def test_gate_merges_items_queued_while_held_in_arrival_order():
    b, gate, batches, tokens = _gated_batcher(max_batch=4)
    try:
        assert b.submit(0).event.wait(2.0)
        items = [b.submit(i) for i in range(1, 7)]
        time.sleep(0.1)                    # the flush thread waits
        assert batches == [[0]]
        assert b.gate_waits == 0
        gate.release(tokens[0])
        assert items[3].event.wait(2.0)
        assert batches[1] == [1, 2, 3, 4]  # at most max_batch
        gate.release(tokens[1])
        assert items[5].event.wait(2.0)
        assert batches == [[0], [1, 2, 3, 4], [5, 6]]
        assert b.gate_waits == 2 and b.gate_wait_s >= 0.1
        assert b.gate_lapses == 0
    finally:
        b.close()


def test_gate_released_on_close():
    b, gate, batches, tokens = _gated_batcher()
    assert b.submit(0).event.wait(2.0)
    held = [b.submit(i) for i in range(1, 4)]
    time.sleep(0.05)
    t0 = time.perf_counter()
    b.close()
    assert time.perf_counter() - t0 < 1.0
    for it in held:                    # flushed or failed, none left hanging
        assert it.event.wait(1.0)
    assert _until(lambda: not b._thread.is_alive())
    assert gate.in_flight() == 0


def test_gate_hold_lapses_at_its_deadline():
    """A hold whose completion never comes lapses at the batch deadline,
    and the lapse is counted."""
    from repro.serving.batcher import DeviceGate

    gate = DeviceGate("node", lambda: 1)
    b = Batcher(lambda args: list(args), max_batch=4, max_wait_ms=1.0)
    b.gate = gate
    try:
        gate.hold(time.perf_counter() + 0.1)
        t0 = time.perf_counter()
        assert b.call(1, timeout=5.0) == 1
        assert 0.05 <= time.perf_counter() - t0 < 2.0
        assert b.gate_lapses == 1 and b.gate_waits == 1
    finally:
        b.close()


def test_collect_takes_queued_items_when_the_window_is_zero():
    started, go = threading.Event(), threading.Event()
    batches = []

    def fn(args):
        batches.append(list(args))
        started.set()
        go.wait(2.0)
        return list(args)

    b = Batcher(fn, max_batch=8, max_wait_ms=0.0)
    try:
        b.submit(0)
        assert started.wait(2.0)
        items = [b.submit(i) for i in range(1, 6)]
        assert b.effective_wait() == 0.0
        go.set()
        assert all(it.event.wait(2.0) for it in items)
        assert batches == [[0], [1, 2, 3, 4, 5]]
    finally:
        b.close()


def _slow_chain_runtime(jax, jnp, iters=1_000_000):
    """A lowered one-map chain on one gpu executor whose every dispatch
    runs for tens of milliseconds; no batch window, so only the gate can
    merge."""
    from repro.core.compiler import compile_flow
    from repro.core.passes import LowerJaxChainsPass, PassPipeline

    def slow(x: jax.Array) -> jax.Array:
        return jax.lax.fori_loop(
            0, iters, lambda i, v: jnp.tanh(v * 1.01 + 0.1), x)

    rt2 = Runtime(n_cpu=1, n_gpu=1, net=NetModel(scale=0.0),
                  batch_wait_ms=0.0)
    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(slow, names=["x"], gpu=True, batching=True)
    dep = compile_flow(fl, rt2, name="g", pipeline=PassPipeline(
        [LowerJaxChainsPass(min_ops=1)]))
    (chain,) = [o.op for o in dep.plan.ops]
    (node,) = dep.dag.nodes
    return rt2, slow, chain, node


def _xs(i):
    return np.linspace(-1.0, 1.0, 16).astype(np.float32) * (i + 1)


def test_gate_merges_requests_sent_while_a_dispatch_runs():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.lowering import BatchedJittedFuse, forced_batched_routing

    rt2, slow, chain, node = _slow_chain_runtime(jax, jnp)
    try:
        assert isinstance(chain, BatchedJittedFuse)

        def send(i):
            return rt2.call_dag("g", Table([("x", jax.Array)], [(_xs(i),)]))

        with forced_batched_routing([chain]):
            send(0).result(timeout=60)     # compiles the per-row program
            chain.warm([Table([("x", jax.Array)],
                              [(_xs(i),) for i in range(4)])])
            b = rt2.batcher_for("g", node)
            assert b.gate is not None
            n0, d0 = len(b.batch_sizes), chain.batch_dispatches
            futs = [send(0)]
            assert _until(lambda: len(b.batch_sizes) == n0 + 1)
            futs += [send(i) for i in range(1, 5)]
            outs = [f.result(timeout=60) for f in futs]
        assert b.batch_sizes[n0:] == [1, 4]
        assert chain.batch_dispatches - d0 >= 1
        assert b.gate_waits >= 1
        waits = rt2.metrics_snapshot("batch/g/")
        assert any(k.endswith("/gate_wait_s") and v
                   for k, v in waits.items())
        ref = jax.jit(slow)
        for i, out in enumerate(outs):
            np.testing.assert_allclose(np.asarray(out.rows[0].values[0]),
                                       np.asarray(ref(_xs(i))), rtol=1e-5)
    finally:
        rt2.stop()


@pytest.mark.parametrize("ending", ["error", "deadline"])
def test_gate_released_when_the_batch_does_not_complete(ending):
    """A batch that fails, or expires in the executor's queue, gives its
    slot back: the next request is not held until the hold lapses."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.runtime.executor import WorkItem

    rt2, _, chain, node = _slow_chain_runtime(jax, jnp, iters=10)
    try:
        def table(v):
            return Table([("x", jax.Array)], [(v,)])

        rt2.call_dag("g", table(_xs(0))).result(timeout=60)
        b = rt2.batcher_for("g", node)
        if ending == "error":
            # not an array: the proven executable refuses it at dispatch
            fut = rt2.call_dag("g", table("not an array"))
        else:
            (ex,) = rt2.pool.by_class("gpu")
            ex.submit(WorkItem(fn=lambda tables, ctx: time.sleep(0.3),
                               tables=[], produced_on=[],
                               callback=lambda *a: None))
            fut = rt2.call_dag("g", table(_xs(1)), deadline_s=0.05)
        with pytest.raises(Exception):
            fut.result(timeout=10)
        assert _until(lambda: b.gate.in_flight() == 0)
        t0 = time.perf_counter()
        rt2.call_dag("g", table(_xs(2))).result(timeout=10)
        assert time.perf_counter() - t0 < 5.0
        assert b.gate_lapses == 0
    finally:
        rt2.stop()


def test_cpu_batched_node_still_runs_batches_concurrently():
    """CPU nodes get no gate: two executors run two batches at once."""
    lock = threading.Lock()
    running = {"now": 0, "peak": 0}

    def fn(x: int) -> int:
        with lock:
            running["now"] += 1
            running["peak"] = max(running["peak"], running["now"])
        time.sleep(0.2)
        with lock:
            running["now"] -= 1
        return x * 10

    rt2 = Runtime(n_cpu=2, net=NetModel(scale=0.0), max_batch=2,
                  batch_wait_ms=5.0)
    try:
        fl = _batched_flow(rt2, fn)
        futs = [fl.execute(Table([("x", int)], [(i,)])) for i in range(4)]
        outs = [f.result(timeout=10).rows[0].values[0] for f in futs]
        assert outs == [i * 10 for i in range(4)]
        assert running["peak"] == 2
        (b,) = rt2._batchers.values()
        assert b.gate is None
    finally:
        rt2.stop()
