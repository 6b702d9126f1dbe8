"""End-to-end request tracing, histogram metrics, SLO-miss attribution.

* span/trace model: ``kind@node`` names, tail-keep policy (SLO-miss /
  error / shed / retried traces always kept), deterministic head
  sampling, bounded kept ring;
* runtime integration: a traced request's timeline carries
  admission -> queue -> exec -> demux spans, batched members link to ONE
  shared batch span, and the Chrome exporter renders it all;
* adversarial paths: a hedged request keeps exactly one winning exec
  span with the loser marked cancelled; a crash-requeued item's spans
  chain across executors; a shed request's trace is always kept with the
  shed reason — even at 0% head sampling;
* metric primitives: log-bucketed mergeable histograms, windowed
  counters, prefix-filtered snapshots that stay live under concurrent
  writers;
* fault-aware estimator: measured fault pressure inflates the predicted
  p99 (zero rates leave it exactly unchanged);
* clock audit: every rate window and trace timestamp reads the ONE
  monotonic clock in ``repro.obs.clock``;
* profiler regions: a lowered flow served under ``jax.profiler`` leaves
  one ``repro.<kind>`` annotation per host boundary, with its arguments,
  a dispatch inside its executor item, and a clock stamp that maps the
  retroactive spans onto the profiler's timeline; with the profiler off
  a region is one shared inert object.
"""
import collections
import json
import statistics
import threading
import time

import pytest

from repro.core.dataflow import Dataflow
from repro.core.table import Table
from repro.obs import (Histogram, HistogramSnapshot, Tracer, WindowedCounter,
                       attribute, export_chrome, to_chrome_events, to_json)
from repro.obs.attribution import REQUEST_NODE
from repro.obs.clock import now as obs_now
from repro.profiling.estimator import FaultStats
from repro.runtime.netmodel import NetModel
from repro.runtime.runtime import Runtime
from repro.serving.admission import AdmissionController, ClassPolicy, \
    Overloaded
from repro.serving.faults import FaultPlan


def _t(i=1):
    return Table([("i", int)], [(i,)])


def _flow(seen=None, service_s=0.0, batching=True):
    def fn(i: int) -> int:
        if seen is not None:
            seen.append(i)
        if service_s:
            time.sleep(service_s)
        return i + 1

    fl = Dataflow([("i", int)])
    fl.output = fl.map(fn, names=["i"], batching=batching)
    return fl


def _traced_runtime(sample_rate=1.0, **kw):
    return Runtime(n_cpu=kw.pop("n_cpu", 2), net=NetModel(scale=0.0),
                   tracer=Tracer(enabled=True, sample_rate=sample_rate),
                   **kw)


# ---------------------------------------------------------------------------
# span / trace model (unit)
# ---------------------------------------------------------------------------

def test_span_name_carries_node():
    tr = Tracer(enabled=True, sample_rate=1.0)
    t = tr.start("d")
    s = t.span("exec@stage1", 1.0, 2.0, link=7, executor="e0")
    assert s.kind == "exec" and s.node == "stage1"
    assert s.duration_s == pytest.approx(1.0)
    assert s.link == 7 and s.attrs["executor"] == "e0"
    a = t.span("admission", 1.0, 1.0)
    assert a.kind == "admission" and a.node is None


def test_tail_keep_policy_and_reason_priority():
    tr = Tracer(enabled=True, sample_rate=0.0)
    # nothing went wrong, not head-sampled: dropped
    t = tr.start("d")
    assert t.finish() is False and t.kept_reason is None
    # retried (via event) is kept at 0% sampling
    t = tr.start("d")
    t.event("retry@n", attempt=2)
    assert t.retried and t.finish() is True
    assert t.kept_reason == "retried"
    # slo_miss outranks everything
    t = tr.start("d")
    t.event("retry@n")
    assert t.finish(slo_miss=True) is True
    assert t.kept_reason == "slo_miss"
    # finish is idempotent: second close neither keeps nor double-counts
    kept_before = tr.stats()["kept"]
    assert t.finish(slo_miss=True) is False
    assert tr.stats()["kept"] == kept_before
    # hedge_launch flips hedged (observability flag, not a keep reason)
    t = tr.start("d")
    t.event("hedge_launch@n", delay_s=0.01)
    assert t.hedged


def test_deterministic_head_sampling_is_exact():
    for rate, expect in ((0.0, 0), (0.1, 100), (1.0, 1000)):
        tr = Tracer(enabled=True, sample_rate=rate)
        kept = sum(1 for _ in range(1000) if tr.start("d").finish())
        assert kept == expect, f"rate={rate}"


def test_kept_ring_is_bounded():
    tr = Tracer(enabled=True, sample_rate=1.0, capacity=16)
    for _ in range(100):
        tr.start("d").finish()
    assert tr.stats()["kept"] == 100          # policy counted them all
    assert len(tr.kept()) == 16               # ring kept the newest 16


def test_disabled_tracer_returns_none():
    tr = Tracer(enabled=False, sample_rate=1.0)
    assert tr.start("d") is None
    assert tr.stats()["started"] == 0


# ---------------------------------------------------------------------------
# metric primitives (unit)
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_bucket_error():
    h = Histogram()
    vals = [i / 1000.0 for i in range(1, 1001)]    # 1ms .. 1s uniform
    for v in vals:
        h.record(v)
    assert h.n == 1000
    assert h.mean == pytest.approx(sum(vals) / len(vals))
    # log-bucketed: <=12.5% relative overestimate (growth 1.25), never under
    for p, true in ((50, 0.5), (99, 0.99)):
        est = h.percentile(p)
        assert true * 0.999 <= est <= true * 1.25, (p, est)
    assert h.percentile(100) == pytest.approx(1.0)


def test_histogram_snapshots_merge():
    a, b = Histogram(), Histogram()
    for v in (0.001, 0.002, 0.004):
        a.record(v)
    for v in (0.1, 0.2):
        b.record(v)
    m = a.snapshot().merge(b.snapshot())
    assert m.n == 5
    assert m.total == pytest.approx(0.307)
    assert m.vmin == pytest.approx(0.001)
    assert m.vmax == pytest.approx(0.2)
    # merged percentile == percentile of the union recorded directly
    u = Histogram()
    for v in (0.001, 0.002, 0.004, 0.1, 0.2):
        u.record(v)
    assert m.percentile(50) == pytest.approx(u.percentile(50))
    assert HistogramSnapshot.merge_all([a.snapshot(), b.snapshot()]).n == 5
    with pytest.raises(ValueError):
        m.merge(Histogram(lo=1e-3).snapshot())


def test_windowed_counter_windows_by_event_time():
    c = WindowedCounter(slot_s=0.25, horizon_s=10.0)
    for t in (100.0, 100.1, 100.2, 105.0):
        c.note(t)
    assert c.total == 4
    assert c.count(1.0, now=100.5) == 3       # the burst, not the late one
    assert c.count(1.0, now=105.0) == 1
    assert c.rate(10.0, now=105.0) == pytest.approx(0.4)
    # memory stays bounded well past the horizon
    for i in range(100_000):
        c.note(200.0 + i * 0.01)
    assert len(c._slots) <= 2 * int(c.horizon_s / c.slot_s) + 1


# ---------------------------------------------------------------------------
# runtime metric store: histograms, prefix filtering, concurrency
# ---------------------------------------------------------------------------

def test_metrics_snapshot_prefix_filtering():
    rt = Runtime(n_cpu=1, net=NetModel(scale=0.0))
    try:
        rt.record_metric("dag/a/latency_s", 0.01)
        rt.record_metric("dag/b/latency_s", 0.02)
        rt.record_metric("faults/crash_t", obs_now())
        assert set(rt.metrics_snapshot(prefix="dag/a/")) == \
            {"dag/a/latency_s"}
        both = rt.metrics_snapshot(prefix=("dag/a/", "faults/"))
        assert set(both) == {"dag/a/latency_s", "faults/crash_t"}
        # unfiltered view still returns everything
        assert set(rt.metrics_snapshot()) >= \
            {"dag/a/latency_s", "dag/b/latency_s", "faults/crash_t"}
        # the histogram twin of a latency series answers percentiles
        h = rt.metric_histogram("dag/a/latency_s")
        assert h is not None and h.n == 1
        # the counter twin of a *_t series answers rates without a scan
        assert rt.metric_rate("faults/crash_t", window_s=60.0) > 0
    finally:
        rt.stop()


def test_metrics_snapshot_live_under_concurrent_writers():
    rt = Runtime(n_cpu=1, net=NetModel(scale=0.0))
    stop = threading.Event()

    def hammer(k):
        while not stop.is_set():
            rt.record_metric(f"dag/w{k}/latency_s", 0.001)
            rt.record_metric(f"dag/w{k}/done_t", obs_now())

    threads = [threading.Thread(target=hammer, args=(k,), daemon=True)
               for k in range(4)]
    try:
        for t in threads:
            t.start()
        deadline = time.perf_counter() + 1.0
        reads = 0
        while time.perf_counter() < deadline:
            snap = rt.metrics_snapshot(prefix="dag/w0/")
            assert all(k.startswith("dag/w0/") for k in snap)
            reads += 1
        # the filtered read path must stay fast while writers hammer the
        # store: a coarse floor catches an accidental O(all-keys-copied)
        # or lock-convoy regression
        assert reads > 50
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=2.0)
        rt.stop()


# ---------------------------------------------------------------------------
# end-to-end: spans on the serving path
# ---------------------------------------------------------------------------

def test_traced_request_has_full_span_chain():
    rt = _traced_runtime(sample_rate=1.0)
    try:
        fl = _flow(batching=True)
        fl.deploy(rt, name="e2e")
        assert fl.execute(_t(1)).result(timeout=10).rows[0].values[0] == 2
        kept = rt.tracer.kept("e2e")
        assert len(kept) == 1
        tr = kept[0]
        assert tr.kept_reason == "sampled" and tr.finished
        kinds = [s.kind for s in tr.spans]
        for kind in ("admission", "queue", "exec", "demux"):
            assert kind in kinds, kinds
        node = next(s.node for s in tr.spans if s.kind == "exec")
        assert node in rt.dags["e2e"].nodes
        # admission precedes queue precedes exec start; demux after exec
        by = {s.kind: s for s in tr.spans}
        assert by["admission"].t0 <= by["queue"].t0 <= by["exec"].t1
        assert by["demux"].t1 >= by["exec"].t0
        # the exec span carries the measured queue/service split
        assert by["exec"].attrs["attempts"] == 1
        assert by["exec"].attrs["exec_s"] >= 0.0
    finally:
        rt.stop()


def test_batched_members_share_one_linked_batch_span():
    rt = _traced_runtime(sample_rate=1.0, batch_wait_ms=20.0)
    try:
        fl = _flow(batching=True)
        fl.deploy(rt, name="bt")
        futs = [fl.execute(_t(i)) for i in range(4)]
        for f in futs:
            f.result(timeout=10)
        kept = rt.tracer.kept("bt")
        assert len(kept) == 4
        links = {s.link for t in kept for s in t.spans
                 if s.kind == "exec" and s.link is not None}
        assert links, "exec spans must link to their batch span"
        batch = rt.tracer.batch_spans(links)
        # all members that merged share the SAME batch span (one span per
        # merged dispatch, not per member)
        assert sum(b.attrs["n_requests"] for b in batch) == 4
        for b in batch:
            assert b.kind == "batch"
            assert b.attrs["size"] >= 1
    finally:
        rt.stop()


def test_shed_trace_always_kept_with_reason():
    rt = _traced_runtime(sample_rate=0.0)     # tail-keep only
    try:
        _flow().deploy(rt, name="sh")
        rt.set_admission("sh", AdmissionController(classes={
            "best_effort": ClassPolicy("best_effort", priority=0,
                                       rate=0.001, burst=1)}))
        rt.call_dag("sh", _t(1), klass="best_effort").result(timeout=10)
        shed = rt.call_dag("sh", _t(2), klass="best_effort")
        with pytest.raises(Overloaded):
            shed.result(timeout=10)
        kept = rt.tracer.kept("sh")
        assert len(kept) == 1                 # ONLY the shed one
        tr = kept[0]
        assert tr.kept_reason == "shed"
        assert tr.shed_reason == "rate_limit"
        adm = next(s for s in tr.spans if s.kind == "admission")
        assert adm.attrs["action"] == "shed"
        assert adm.attrs["reason"] == "rate_limit"
    finally:
        rt.stop()


def test_slo_missed_trace_kept_at_zero_sampling():
    rt = _traced_runtime(sample_rate=0.0)
    try:
        fl = _flow(service_s=0.05)
        fl.deploy(rt, name="miss")
        fut = rt.call_dag("miss", _t(1), deadline_s=0.5)
        assert fut.result(timeout=10).rows[0].values[0] == 2
        # fast request under a generous deadline: dropped
        assert rt.tracer.kept("miss") == []
        slow = rt.call_dag("miss", _t(2), deadline_s=0.01)
        try:
            slow.result(timeout=10)
        except Exception:
            pass                              # expiry is also an SLO miss
        kept = rt.tracer.kept("miss")
        assert len(kept) == 1 and kept[0].slo_miss
        assert kept[0].kept_reason == "slo_miss"
    finally:
        rt.stop()


# ---------------------------------------------------------------------------
# adversarial paths: hedging, crash requeue
# ---------------------------------------------------------------------------

def test_hedged_trace_one_winning_exec_span():
    rt = _traced_runtime(sample_rate=1.0, n_cpu=3, hang_timeout_s=30.0)
    try:
        seen = []
        fl = _flow(seen, batching=False)
        dep = fl.deploy(rt, name="h")
        fl.execute(_t(1)).result(timeout=10)
        seen.clear()
        rt.tracer.clear()
        rt.configure_hedging("h", dep.dag.output, 0.03)
        rt.set_fault_plan(FaultPlan(seed=5).hang(rate=1.0, hang_s=0.8,
                                                 limit=1))
        assert fl.execute(_t(3)).result(timeout=10).rows[0].values[0] == 4
        rt.set_fault_plan(None)
        kept = rt.tracer.kept("h")
        assert len(kept) == 1
        tr = kept[0]
        assert tr.hedged
        hl = [s for s in tr.spans if s.kind == "hedge_launch"]
        assert len(hl) == 1 and hl[0].attrs["delay_s"] == \
            pytest.approx(0.03)
        # exactly ONE exec span — the winner's; the loser never delivers
        execs = [s for s in tr.spans if s.kind == "exec"]
        assert len(execs) == 1
        assert execs[0].attrs["attempts"] == 2    # primary + hedge ran
        assert execs[0].attrs["executor"] is not None
        # loser cancellation: the straggler wakes, finds the token
        # claimed, and skips — user code ran exactly once
        time.sleep(1.0)
        assert seen == [3]
    finally:
        rt.stop()


def test_loser_cancellation_is_marked_and_replayable():
    # deterministic loser cancellation: the winner claims the token
    # BEFORE the loser's executor dequeues its clone, so the skip path
    # logs ("cancelled", loser_id) — and the replay helper turns it into
    # a cancelled@node span on the trace
    from repro.runtime.executor import Executor, WorkItem
    from repro.runtime.kvs import KVS
    from repro.runtime.runtime import _trace_exec_events
    a = Executor(KVS(), NetModel(scale=0.0))
    b = Executor(KVS(), NetModel(scale=0.0))
    try:
        gate = threading.Event()
        blocker = WorkItem(fn=lambda tables, ctx: gate.wait(5.0),
                           tables=[_t()], produced_on=[None],
                           callback=lambda r, e, x: None)
        done = threading.Event()
        item = WorkItem(fn=lambda tables, ctx: tables[0],
                        tables=[_t()], produced_on=[None],
                        callback=lambda r, e, x: done.set())
        a.submit(blocker)                 # wedge A behind the gate
        time.sleep(0.05)
        a.submit(item)                    # the loser, stuck behind it
        b.submit(item.clone())            # the winner, runs immediately
        assert done.wait(5.0)
        gate.set()                        # A wakes, dequeues the loser
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            if any(e[0] == "cancelled" for e in item.attempt_log):
                break
            time.sleep(0.01)
        log = list(item.attempt_log)
        cancelled = [e for e in log if e[0] == "cancelled"]
        assert len(cancelled) == 1 and cancelled[0][1] == a.id
        assert sum(1 for e in log if e[0] == "done") == 1
        # replay onto a trace: the loser shows up as a cancelled@ span
        tr = Tracer(enabled=True, sample_rate=1.0).start("d")
        _trace_exec_events(tr, "n1", log)
        spans = [s for s in tr.spans if s.kind == "cancelled"]
        assert len(spans) == 1
        assert spans[0].node == "n1"
        assert spans[0].attrs["executor"] == a.id
    finally:
        a.stop()
        b.stop()


def test_crash_requeued_trace_chains_across_executors():
    rt = _traced_runtime(sample_rate=0.0, n_cpu=3,
                         detector_interval_s=0.02)
    try:
        fl = _flow(batching=False)
        fl.deploy(rt, name="cr")
        fl.execute(_t(1)).result(timeout=10)
        rt.set_fault_plan(FaultPlan(seed=1).crash(rate=1.0, limit=1))
        assert fl.execute(_t(5)).result(timeout=10).rows[0].values[0] == 6
        rt.set_fault_plan(None)
        kept = rt.tracer.kept("cr")
        assert len(kept) == 1, \
            "a crash-requeued request is tail-kept at 0% sampling"
        tr = kept[0]
        assert tr.kept_reason == "retried" and tr.retried
        rq = [s for s in tr.spans if s.kind == "requeue"]
        assert len(rq) >= 1
        execs = [s for s in tr.spans if s.kind == "exec"]
        assert len(execs) == 1                # exactly one delivery
        # the span chain names BOTH executors: the requeue's target (or
        # the winner) differs from nothing — at minimum the winning
        # executor is recorded and >=2 attempts started
        assert execs[0].attrs["attempts"] >= 2
        assert execs[0].attrs["requeues"] >= 1
        # timeline ordering: the requeue happened inside the exec span
        assert execs[0].t0 <= rq[0].t0 <= execs[0].t1
    finally:
        rt.stop()


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _run_traced_chain(rt, name="exp", n=3):
    fl = _flow(batching=True)
    fl.deploy(rt, name=name)
    futs = [fl.execute(_t(i)) for i in range(n)]
    for f in futs:
        f.result(timeout=10)
    return rt.tracer.kept(name)


def test_json_export_roundtrips(tmp_path):
    rt = _traced_runtime(sample_rate=1.0)
    try:
        kept = _run_traced_chain(rt)
        doc = json.loads(to_json(kept))
        assert len(doc) == 3
        assert all(t["kept_reason"] == "sampled" for t in doc)
        assert all(any(s["name"].startswith("exec@") for s in t["spans"])
                   for t in doc)
    finally:
        rt.stop()


def test_chrome_export_is_perfetto_shaped(tmp_path):
    rt = _traced_runtime(sample_rate=1.0, batch_wait_ms=20.0)
    try:
        _run_traced_chain(rt, name="chrome", n=4)
        path = tmp_path / "trace.json"
        export_chrome(rt.tracer, str(path), dag="chrome")
        doc = json.loads(path.read_text())
        evs = doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"
        phases = {e["ph"] for e in evs}
        assert {"X", "M"} <= phases
        cats = {e.get("cat") for e in evs if e["ph"] == "X"}
        for cat in ("admission", "queue", "exec", "demux", "batch",
                    "request"):
            assert cat in cats, cats
        # every complete event is JSON-clean µs with non-negative duration
        for e in evs:
            if e["ph"] == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0
        # flow arrows connect member exec spans to the shared batch span
        starts = [e for e in evs if e["ph"] == "s"]
        finishes = [e for e in evs if e["ph"] == "f"]
        assert starts and finishes
        assert {e["id"] for e in finishes} <= {e["id"] for e in starts}
        # batch spans live on their own process lane
        pids = {e["pid"] for e in evs if e.get("cat") == "batch"}
        assert pids and pids.isdisjoint(
            {e["pid"] for e in evs if e.get("cat") == "exec"})
    finally:
        rt.stop()


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

def test_attribution_names_slow_node_dominant():
    rt = Runtime(n_cpu=4, net=NetModel(scale=0.0), batch_wait_ms=1.0,
                 tracer=Tracer(enabled=True, sample_rate=1.0))
    try:
        def fast(i: int) -> int:
            time.sleep(0.0003)
            return i

        def slow(i: int) -> int:
            time.sleep(0.004)
            return i

        fl = Dataflow([("i", int)])
        n1 = fl.map(fast, names=["i"], batching=True)
        n2 = n1.map(slow, names=["i"], batching=True)
        n3 = n2.map(fast, names=["i"], batching=True)
        fl.output = n3
        fl.deploy(rt, name="chain")
        futs = []
        for k in range(12):
            futs.append(rt.call_dag("chain", _t(k), deadline_s=0.010))
            time.sleep(0.003)
        for f in futs:
            try:
                f.result(timeout=10)
            except Exception:
                pass
        kept = rt.tracer.kept("chain")
        assert len(kept) == 12
        att = attribute(kept)
        node, component, seconds = att.dominant()
        assert node.endswith("/2:map"), (node, component)
        assert component == "service"
        assert seconds > 0
        # the report table renders and names the dominant contributor
        text = att.table()
        assert "dominant contributor:" in text
        assert node in text
        d = att.to_dict()
        assert d["dominant"]["node"] == node
        assert set(d["nodes"][node]) >= {"queue_s", "service_s", "total_s"}
    finally:
        rt.stop()


def test_attribution_folds_admission_and_slo_only_filter():
    tr = Tracer(enabled=True, sample_rate=1.0)
    t = tr.start("d")
    t.span("admission", 0.0, 0.002, action="admit")
    t.span("queue@n1", 0.002, 0.004)
    t.span("exec@n1", 0.004, 0.010, queue_s=0.001, exec_s=0.005,
           attempts=1)
    t.span("demux@n1", 0.010, 0.011)
    t.finish()
    t2 = tr.start("d")
    t2.span("admission", 0.0, 0.001, action="admit")
    t2.finish(slo_miss=True)
    att = attribute(tr.kept())
    assert att.n_traces == 2 and att.n_miss == 1
    assert att.nodes[REQUEST_NODE].admission_s == pytest.approx(0.003)
    n1 = att.nodes["n1"]
    assert n1.queue_s == pytest.approx(0.002 + 0.001)   # queue span + wait
    assert n1.service_s == pytest.approx(0.005)
    assert n1.transfer_s == pytest.approx(0.001)
    # slo_only drops the clean trace
    only = attribute(tr.kept(), slo_only=True)
    assert only.n_traces == 1 and only.n_miss == 1


def test_attribution_classifies_retry_gap():
    tr = Tracer(enabled=True, sample_rate=1.0)
    t = tr.start("d")
    t.span("retry@n1", 0.004, 0.004, attempt=2)
    # 10ms wall, 1ms queue + 3ms exec measured: 6ms unexplained gap on a
    # retried node is retry overhead, not service
    t.span("exec@n1", 0.0, 0.010, queue_s=0.001, exec_s=0.003,
           attempts=2)
    t.finish()
    att = attribute(tr.kept())
    n1 = att.nodes["n1"]
    assert n1.retry_s == pytest.approx(0.006)
    assert n1.service_s == pytest.approx(0.003)


# ---------------------------------------------------------------------------
# fault-aware estimator
# ---------------------------------------------------------------------------

def test_fault_stats_inflation():
    f = FaultStats()
    # zero rates: exactly unchanged
    assert f.inflate_p99(0.1, arrival_rate=100.0) == 0.1
    f = FaultStats(retry_rate=10.0, requeue_rate=10.0, detection_s=0.05)
    # 20% of requests disturbed: p99 * 1.2 + 0.2 * detection
    assert f.disturbed_fraction(100.0) == pytest.approx(0.2)
    assert f.inflate_p99(0.1, 100.0) == pytest.approx(0.1 * 1.2 + 0.01)
    # inflation is monotone in fault pressure and capped at p=1
    assert f.inflate_p99(0.1, 100.0) < \
        FaultStats(retry_rate=50.0, detection_s=0.05).inflate_p99(0.1, 100.0)
    assert FaultStats(retry_rate=1e9).disturbed_fraction(1.0) == 1.0


def test_controller_detail_carries_fault_inflation():
    # exercised end-to-end in test_slo_controller; here: the windowed
    # fault counters feed FaultStats through a live runtime
    rt = Runtime(n_cpu=1, net=NetModel(scale=0.0))
    try:
        now = obs_now()
        for _ in range(5):
            rt.record_metric("faults/retry_t", now)
        assert rt.metric_rate("faults/retry_t", window_s=10.0) >= 0.5
    finally:
        rt.stop()


# ---------------------------------------------------------------------------
# clock audit
# ---------------------------------------------------------------------------

def test_rate_windows_share_the_monotonic_clock():
    import repro.obs.clock as clock
    import repro.profiling.controller as controller
    import repro.runtime.runtime as runtime
    import repro.serving.admission as admission
    import repro.serving.retry as retry
    assert clock.now is time.perf_counter
    for mod in (runtime, admission, controller, retry):
        assert getattr(mod, "_mono") is clock.now, mod.__name__
    # trace timestamps come from the same clock: a span recorded "now"
    # nests inside perf_counter readings taken around it
    tr = Tracer(enabled=True, sample_rate=1.0)
    t0 = time.perf_counter()
    t = tr.start("d")
    s = t.event("retry@n")
    t1 = time.perf_counter()
    assert t0 <= s.t0 <= t1


# ---------------------------------------------------------------------------
# profiler regions
# ---------------------------------------------------------------------------

REGION_ARGS = {
    "repro.call": {"dag"},
    "repro.flush": {"node", "requests", "rows"},
    "repro.exec": {"node", "executor"},
    "repro.dispatch": {"node", "path", "rows"},
    "repro.stack": {"node", "bytes"},
    "repro.gather": {"node", "bytes"},
    "repro.demux": {"node", "rows"},
    "repro.gate": {"node", "in_flight"},
}


def _region_flow():
    import jax
    import jax.numpy as jnp

    def scale(x: jax.Array) -> jax.Array:
        return jnp.tanh(x * 1.5)

    def shift(x: jax.Array) -> jax.Array:
        return x - 0.25

    fl = Dataflow([("x", jax.Array)])
    fl.output = fl.map(scale, names=["x"], gpu=True, batching=True) \
        .map(shift, names=["x"], gpu=True, batching=True)
    return fl


def _host_regions(xplane):
    """``[(line index, name, start_ns, end_ns, args)]`` of the
    ``repro.*`` events on the host planes of a profiler trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((i, e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def region_trace(tmp_path_factory):
    """One singleton and one merged burst of four through a lowered
    chain, under the profiler: the regions and the kept traces."""
    import glob

    import jax
    import numpy as np

    from repro.core.compiler import compile_flow
    from repro.core.lowering import BatchedJittedFuse, forced_batched_routing

    rt = Runtime(n_cpu=1, n_gpu=1, net=NetModel(scale=0.0),
                 tracer=Tracer(enabled=True, sample_rate=1.0),
                 batch_wait_ms=50.0)
    try:
        dep = compile_flow(_region_flow(), rt, fusion=True, name="rg")
        (chain,) = [o.op for o in dep.plan.ops]
        assert isinstance(chain, BatchedJittedFuse)

        def table(i):
            return Table([("x", jax.Array)],
                         [(np.linspace(-1.0, 1.0, 16) * (i + 1),)])

        def serve(n):
            futs = [rt.call_dag("rg", table(i)) for i in range(n)]
            return [f.result(timeout=60) for f in futs]

        (node,) = dep.dag.nodes
        with forced_batched_routing([chain]):
            serve(1)
            serve(4)                          # compiles bucket 4
            batcher = rt.batcher_for("rg", node)
            batcher.adaptive_wait = False     # the burst merges whole
            rt.tracer.clear()
            log_dir = str(tmp_path_factory.mktemp("regions"))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                serve(1)
                serve(4)
            finally:
                jax.profiler.stop_trace()
        (xplane,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        yield {"regions": _host_regions(xplane), "node": node,
               "chain": chain.name, "traces": rt.tracer.kept("rg")}
    finally:
        rt.stop()


def test_each_region_appears_with_its_arguments(region_trace):
    regs = region_trace["regions"]
    names = {r[1] for r in regs}
    assert names == set(REGION_ARGS), names
    for _, name, t0, t1, args in regs:
        assert REGION_ARGS[name] | {"perf_ns"} <= set(args), (name, args)
        assert t1 >= t0
    by = collections.defaultdict(list)
    for r in regs:
        by[r[1]].append(r[4])
    assert [a["dag"] for a in by["repro.call"]] == ["rg"] * 5
    node, chain = region_trace["node"], region_trace["chain"]
    for kind in ("repro.flush", "repro.exec", "repro.demux", "repro.gate"):
        assert {a["node"] for a in by[kind]} == {node}, kind
    # the burst's flush finds the singleton's device result not yet
    # waited for; the singleton's finds the gathered burst before it
    assert sorted(a["in_flight"] for a in by["repro.gate"]) == [0, 1]
    for kind in ("repro.dispatch", "repro.stack", "repro.gather"):
        assert {a["node"] for a in by[kind]} == {chain}, kind
    assert sorted((a["requests"], a["rows"])
                  for a in by["repro.flush"]) == [(1, 1), (4, 4)]
    assert sorted((a["path"], a["rows"], a.get("bucket"))
                  for a in by["repro.dispatch"]) == [("batch", 4, 4),
                                                     ("row", 1, None)]
    # the burst's four rows of 16 float32 go up once and come back once
    assert [a["bytes"] for a in by["repro.stack"]] == [4 * 16 * 4]
    assert [a["bytes"] for a in by["repro.gather"]] == [4 * 16 * 4]
    assert sorted(a["rows"] for a in by["repro.demux"]) == [1, 4]


def test_dispatch_nests_inside_exec(region_trace):
    regs = region_trace["regions"]
    execs = [r for r in regs if r[1] == "repro.exec"]
    for line, _, t0, t1, _ in (r for r in regs
                               if r[1] in ("repro.dispatch", "repro.stack",
                                           "repro.gather")):
        assert any(e[0] == line and e[2] <= t0 and t1 <= e[3]
                   for e in execs)


def test_clock_offset_maps_exec_spans_onto_exec_regions(region_trace):
    """The median of (profiler start - perf_ns) over every region maps
    each ``exec@`` span onto the profiler's clock: the span (executor
    queue + service) holds its ``repro.exec`` region, and the end of its
    service, start + ``queue_s`` + ``exec_s``, lies within 100 us of the
    region's end."""
    regs = region_trace["regions"]
    offset = statistics.median(t0 - a["perf_ns"] for _, _, t0, _, a in regs)
    execs = [(t0, t1) for _, name, t0, t1, _ in regs if name == "repro.exec"]
    spans = [s for t in region_trace["traces"] for s in t.spans
             if s.kind == "exec"]
    assert len(spans) == 5 and len(execs) == 2
    for s in spans:
        t0, t1 = s.t0 * 1e9 + offset, s.t1 * 1e9 + offset
        done = t0 + (s.attrs["queue_s"] + s.attrs["exec_s"]) * 1e9
        a, b = min(execs, key=lambda r: abs(r[1] - done))
        assert abs(b - done) < 100e3
        assert t0 <= a and b <= t1


def test_regions_record_nothing_with_the_profiler_off(tmp_path):
    """Off, every region is one shared falsy object; a trace taken after
    traffic served with the profiler off holds no region."""
    import glob

    import jax

    from repro.obs import region
    from repro.obs.trace import _OFF
    assert region("call", dag="d") is region("exec") is _OFF
    assert not region("gather")
    rt = _traced_runtime()
    try:
        fl = _flow(batching=True)
        fl.deploy(rt, name="off")
        fl.execute(_t(1)).result(timeout=10)
        jax.profiler.start_trace(str(tmp_path))
        jax.profiler.stop_trace()
    finally:
        rt.stop()
    (xplane,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    assert _host_regions(xplane) == []
