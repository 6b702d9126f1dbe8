"""The region reduction matches the program's dispatches to their runs on
the device, splits the chain's device time by stage scope, labels idle
gaps with the program's regions, and leaves ``devtrace.reduce`` exactly
as it was.

A synthetic trace whose answers are worked out by hand checks the rules;
a trace recorded on one TPU v5e (``data/region_trace.xplane.pb.gz``,
made by ``record_region_trace.py``) is checked against the profiler's own
Perfetto JSON of the same session, read with ``json`` alone."""
import collections
import gzip
import json
import statistics
from pathlib import Path

import pytest

from bench import devregions, devtrace, readers

DATA = Path(__file__).resolve().parent / "data"


def _ev(meta, a_ns, b_ns, **stats):
    st = "".join(f" stats {{ metadata_id: {k} {v} }}"
                 for k, v in stats.values())
    return (f"events {{ metadata_id: {meta} offset_ps: {int(a_ns * 1000)}"
            f" duration_ps: {int((b_ns - a_ns) * 1000)}{st} }}")


def _region(meta, a, b):
    return _ev(meta, a, b, perf=(11, f"int64_value: {int(a) - 1000}"))


# times in ns; the window is [0, 100000)
US = 1000
HOST = f"""
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_ev(1, 0, 100 * US)}
    {_region(2, 30 * US, 50 * US)} }}
  lines {{ id: 2 name: "executor" timestamp_ns: 0
    {_region(3, 10 * US, 30 * US)}
    {_region(4, 12 * US, 13.2 * US)}
    %s
    {_region(3, 40 * US, 60 * US)}
    {_region(4, 41 * US, 42 * US)}
    %s
    {_region(3, 72 * US, 80 * US)}
    {_region(5, 72 * US, 80 * US)} }}
  %s
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.traced" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "repro.call" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "repro.exec" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "repro.dispatch" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "repro.gather" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "DoEnqueueProgram" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "Execute" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "IssueEvent" }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "CompleteCallbacks" }} }}
  stat_metadata {{ key: 11 value {{ id: 11 name: "perf_ns" }} }}
  stat_metadata {{ key: 12 value {{ id: 12 name: "run_id" }} }}
  stat_metadata {{ key: 13 value {{ id: 13 name: "_p" }} }}
  stat_metadata {{ key: 14 value {{ id: 14 name: "_c" }} }} }}
"""
# dispatch 1 enqueues its run on its own thread; dispatch 2 hands it to
# another thread, along a flow that starts inside the dispatch
ENQUEUES = (_ev(6, 13 * US, 13.1 * US, r=(12, "int64_value: 7")),
            _ev(7, 41.5 * US, 41.8 * US, f=(13, "uint64_value: 99")),
            f"""lines {{ id: 3 name: "runtime" timestamp_ns: 0
    {_ev(8, 42.5 * US, 43 * US, f=(14, "uint64_value: 99"))}
    {_ev(6, 42.6 * US, 42.7 * US, r=(12, "int64_value: 8"))} }}""")


def _meta(key, name, tf_op=None):
    st = (f' stats {{ metadata_id: 21 str_value: "{tf_op}" }}'
          if tf_op else "")
    return (f'event_metadata {{ key: {key} value {{ id: {key} '
            f'name: "{name}"{st} }} }}')


DEVICE = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_ev(1, 12.8 * US, 25 * US, r=(22, "int64_value: 7"))}
    {_ev(1, 50 * US, 70 * US, r=(22, "int64_value: 8"))}
    {_ev(2, 80 * US, 90 * US, r=(22, "int64_value: 9"))} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_ev(3, 12.8 * US, 15 * US)}
    {_ev(4, 15 * US, 25 * US)}
    {_ev(5, 16 * US, 20 * US)}
    {_ev(6, 26 * US, 27 * US)}
    {_ev(7, 50 * US, 70 * US)}
    {_ev(8, 80 * US, 85 * US)} }}
  {_meta(1, "jit_composed(1)")}
  {_meta(2, "jit_other(2)")}
  {_meta(3, "%fusion.1 = f32[] fusion()", "jit(composed)/prefill/dot")}
  {_meta(4, "%while.2 = f32[] while()", "jit(composed)/vmap(decode)/while")}
  {_meta(5, "%fusion.3 = f32[] fusion()",
         "jit(composed)/vmap(decode)/while/body/dot")}
  {_meta(6, "%copy.4 = f32[] copy()")}
  {_meta(7, "%fusion.5 = f32[] fusion()", "jit(composed)/decode/dot")}
  {_meta(8, "%fusion.6 = f32[] fusion()", "jit(other)/decode/dot")}
  stat_metadata {{ key: 21 value {{ id: 21 name: "tf_op" }} }}
  stat_metadata {{ key: 22 value {{ id: 22 name: "run_id" }} }} }}
"""


def _synthetic(enqueues=True, done=()):
    """The trace; ``done`` adds the host's completion callbacks of those
    run ids."""
    from jax.profiler import ProfileData
    runtime = ENQUEUES[2] if enqueues else ""
    if done:
        runtime = (runtime or 'lines { id: 3 name: "runtime" timestamp_ns: 0 }')
        runtime = runtime[:-1] + "".join(
            _ev(9, 95 * US, 96 * US, r=(12, f"int64_value: {r}"))
            for r in done) + " }"
    host = HOST % ((ENQUEUES[0], ENQUEUES[1], runtime) if enqueues
                   else ("", "", runtime))
    raw = ProfileData.text_proto_to_serialized_xspace(DEVICE + host)
    return ProfileData.from_serialized_xspace(raw), raw


def test_dispatches_match_runs_by_run_id():
    """Runs 7 and 8 start 0.2 us before and 7.4 us after their enqueues,
    so device times move 0.2 us late: queues -0.2 and 8.2 us, runs 12.2
    and 20 us; run 9 is another program."""
    prof, raw = _synthetic()
    r = devregions.reduce(prof, devregions.op_scopes(raw))
    assert (r["match"], r["dispatches"], r["matched"]) == ("run_id", 2, 2)
    assert r["device_lead_ms"] == pytest.approx(0.2e-3)
    assert r["queue_ms"] == [pytest.approx(-0.2e-3), pytest.approx(8.2e-3)]
    assert r["run_ms"] == [pytest.approx(12.2e-3), pytest.approx(20e-3)]
    assert r["queue_p50_ms"] == pytest.approx(4e-3)
    assert r["run_p50_ms"] == pytest.approx(16.1e-3)
    assert r["clock_offset_ns"] == pytest.approx(1000)
    assert r["regions"] == {"repro.call": 1, "repro.exec": 3,
                            "repro.dispatch": 2, "repro.gather": 1}


def test_runs_after_the_last_completion_are_left_out():
    """The host saw run 7 complete and not run 8, which the profiler's
    stop may have cut short: only the first dispatch is matched."""
    prof, raw = _synthetic(done=(7,))
    r = devregions.reduce(prof, devregions.op_scopes(raw))
    assert (r["matched"], r["chain_runs"]) == (1, 1)
    assert r["run_ms"] == [pytest.approx(12.2e-3)]
    prof, raw = _synthetic(done=(8,))
    assert devregions.reduce(prof)["matched"] == 2


def test_dispatches_match_runs_in_order_without_run_ids():
    prof, raw = _synthetic(enqueues=False)
    r = devregions.reduce(prof, devregions.op_scopes(raw))
    assert (r["match"], r["matched"], r["device_lead_ms"]) == ("order", 2, 0)
    assert r["queue_ms"] == [pytest.approx(-0.4e-3), pytest.approx(8e-3)]


def test_stage_shares_count_each_instant_once():
    """Prefill 2.2 us; decode the loop [15, 25) holding its body [16,
    20), and [50, 70): 30 us; the decode op inside run 9 is not the
    chain's.  The chain's runs hold 32.2 us."""
    prof, raw = _synthetic()
    r = devregions.reduce(prof, devregions.op_scopes(raw))
    assert r["chain_s"] == pytest.approx(32.2e-6)
    assert r["stage_s"] == {"prefill": pytest.approx(2.2e-6),
                            "decode": pytest.approx(30e-6), "logits": 0}
    assert r["decode_share"] == pytest.approx(100 * 30 / 32.2)
    assert devregions.reduce(prof)["decode_share"] is None


def test_idle_gaps_are_devtraces_labelled_by_region():
    """The gaps devtrace finds, each named by the region open over most
    of it, at least half: the call on the generator's thread over [30,
    50) (20 us against the executor's 10); none after 85 us, nor before
    12.8 us, where the exec opens for 2.8 us of it; and of the exec and
    gather that both cover [72, 80) the inner one."""
    prof, _ = _synthetic()
    r = devregions.reduce(prof)
    assert r["idle_gaps_program"] == [
        ["repro.call", pytest.approx(23e-6)], ["none", pytest.approx(15e-6)],
        ["none", pytest.approx(12.8e-6)],
        ["repro.gather", pytest.approx(10e-6)],
        ["repro.exec", pytest.approx(1e-6)]]
    plain = devtrace.reduce(prof)["idle_gaps"]
    assert [s for _, s in plain] == [s for _, s in r["idle_gaps_program"]]


def test_stage_of_reads_bare_and_transformed_segments():
    assert devregions.stage_of("jit(composed)/vmap(decode)/while") == \
        "decode"
    assert devregions.stage_of("jit(composed)/prefill/dot") == "prefill"
    assert devregions.stage_of("jit(composed)/decoder/dot") is None
    assert devregions.stage_of(None) is None


def test_devtrace_reduce_is_unchanged():
    """``devtrace.reduce`` of the PR 12 chip trace, exactly as it read
    before the program had regions."""
    got = devtrace.reduce(devtrace.load(str(DATA / "chip_trace.xplane.pb")))
    want = json.loads((DATA / "chip_trace.reduce.json").read_text())
    assert json.loads(json.dumps(got)) == want


# -- the chip trace, against its Perfetto JSON --------------------------------

@pytest.fixture(scope="module")
def chip():
    from jax.profiler import ProfileData
    with gzip.open(DATA / "region_trace.xplane.pb.gz", "rb") as f:
        raw = f.read()
    return devregions.reduce(ProfileData.from_serialized_xspace(raw),
                             devregions.op_scopes(raw))


@pytest.fixture(scope="module")
def witness():
    """Dispatch regions, enqueues, chain runs, stage ops and the window,
    from the Perfetto JSON (microseconds)."""
    with gzip.open(DATA / "region_trace.perfetto.json.gz", "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e["name"] == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        if e.get("ph") == "M" and e["name"] == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    spans = [e for e in events if e.get("ph") == "X"]

    def on(e, line):
        return (devtrace.DEVICE_PLANE.match(procs.get(e["pid"], ""))
                and threads.get((e["pid"], e["tid"])) == line)

    win = next(e for e in spans if e["name"] == devtrace.WINDOW_SPAN)
    return {
        "window": (win["ts"], win["ts"] + win["dur"]),
        "dispatch": [e for e in spans if e["name"] == devregions.DISPATCH],
        "regions": [e for e in spans if e["name"].startswith("repro.")],
        "enqueue": [e for e in spans if e["name"] == devregions.ENQUEUE],
        "runs": [e for e in spans if on(e, devtrace.MODULES_LINE)
                 and readers.CHAIN_PROGRAM in e["name"]],
        "ops": [e for e in spans if on(e, devtrace.OPS_LINE)],
    }


def test_chip_trace_holds_every_region(chip):
    assert set(chip["regions"]) == {
        "repro.call", "repro.flush", "repro.exec", "repro.dispatch",
        "repro.stack", "repro.gather", "repro.demux"}
    assert chip["paths"] == {"row": 4, "batch": 1}


def test_chip_dispatches_match_the_witness(chip, witness):
    """The JSON carries no flows, so the witness pairs in order: each
    dispatch's run is the first chain run enqueued after the dispatch
    starts and not taken by an earlier one.  The queues behind the 0.1 s
    program are long, the others short."""
    lo, hi = witness["window"]
    runs = {int(e["args"]["run_id"]): e for e in witness["runs"]}
    enqueues = sorted((q for q in witness["enqueue"]
                       if int(q["args"]["run_id"]) in runs),
                      key=lambda q: q["ts"])
    queue, run = [], []
    for d in sorted(witness["dispatch"], key=lambda e: e["ts"]):
        end = d["ts"] + d["dur"]
        if not lo <= end <= hi:
            continue
        enq = next(q for q in enqueues if q["ts"] >= d["ts"])
        enqueues.remove(enq)
        r = runs[int(enq["args"]["run_id"])]
        queue.append((r["ts"], end, enq["ts"]))
        run.append(r["dur"] / 1e3)
    lead = max(max(q - s for s, _, q in queue), 0.0)
    want_q = [(s + lead - end) / 1e3 for s, end, _ in queue]
    assert chip["match"] == "run_id" and chip["matched"] == len(run) == 5
    assert chip["run_ms"] == pytest.approx(run, abs=2e-3)
    assert chip["queue_ms"] == pytest.approx(want_q, abs=2e-3)
    assert sum(q > 20 for q in chip["queue_ms"]) == 2
    assert chip["run_p50_ms"] == pytest.approx(statistics.median(run),
                                               abs=2e-3)


def test_chip_decode_share_matches_the_witness(chip, witness):
    """Union of the decode-scoped ops' time inside the chain's runs, over
    the runs' time, from the ops' ``tf_op`` in the JSON."""
    runs = sorted((e["ts"], e["ts"] + e["dur"]) for e in witness["runs"])
    per = collections.defaultdict(list)
    for e in witness["ops"]:
        stage = devregions.stage_of(e.get("args", {}).get("tf_op"))
        if stage:
            per[stage].append((e["ts"], e["ts"] + e["dur"]))

    def inside(ivs):
        u = devtrace._union(ivs)
        return sum(min(b, rb) - max(a, ra) for a, b in u
                   for ra, rb in runs if b > ra and a < rb)

    chain = sum(b - a for a, b in runs)
    assert chip["chain_s"] == pytest.approx(chain / 1e6, rel=1e-3)
    assert set(per) == {"prefill", "decode"}
    for stage in ("prefill", "decode"):
        assert chip["stage_s"][stage] == pytest.approx(
            inside(per[stage]) / 1e6, rel=1e-2)
    assert 0 < chip["decode_share"] < 100


def test_chip_idle_gaps_carry_program_labels(chip, witness):
    gaps = chip["idle_gaps_program"]
    assert gaps and all(s > 0 for _, s in gaps)
    labels = {g for g, _ in gaps}
    assert labels <= {r["name"] for r in witness["regions"]} | {"none"}
    assert labels - {"none"}, "the program's own waits label some gap"
