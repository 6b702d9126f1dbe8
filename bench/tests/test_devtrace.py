"""The trace reduction gives a recorded chip trace's busy and idle time
and its top operations.  The trace (``data/chip_trace.xplane.pb``, made by
``record_trace.py`` on one TPU v5e) comes with the profiler's own Perfetto
JSON of the same session, which this test reads with ``json`` alone as
the independent witness."""
import collections
import gzip
import json
from pathlib import Path

import pytest

from bench import devtrace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def reduced():
    return devtrace.reduce(devtrace.load(str(DATA / "chip_trace.xplane.pb")))


@pytest.fixture(scope="module")
def witness():
    """Busy time, window and per-op time from the Perfetto JSON."""
    with gzip.open(DATA / "chip_trace.perfetto.json.gz", "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e["name"] == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        if e.get("ph") == "M" and e["name"] == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    spans = [e for e in events if e.get("ph") == "X"]
    win = next(e for e in spans if e["name"] == devtrace.WINDOW_SPAN)
    lo, hi = win["ts"], win["ts"] + win["dur"]
    ops = [e for e in spans
           if devtrace.DEVICE_PLANE.match(procs.get(e["pid"], ""))
           and threads.get((e["pid"], e["tid"])) == devtrace.OPS_LINE]
    ivs = sorted((max(e["ts"], lo), min(e["ts"] + e["dur"], hi))
                 for e in ops if e["ts"] + e["dur"] > lo and e["ts"] < hi)
    busy, end = 0.0, None
    for a, b in ivs:
        if end is None or a > end:
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    per_op = collections.Counter()
    for e in ops:
        per_op[e["name"]] += e["dur"]
    return {"busy_s": busy / 1e6, "window_s": (hi - lo) / 1e6,
            "top": [k for k, _ in per_op.most_common(3)]}


def test_busy_and_window(reduced, witness):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(witness["window_s"],
                                                rel=1e-3)
    assert reduced["busy_s"] == pytest.approx(witness["busy_s"], rel=1e-2)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_top_operations(reduced, witness):
    assert [n for n, _ in reduced["device_ops"][:3]] == witness["top"]


def test_idle_gaps_are_labelled(reduced, witness):
    gaps = reduced["idle_gaps"]
    assert gaps and all(s > 0 for _, s in gaps)
    assert {label for label, _ in gaps} <= {"bench.send", "bench.sleep",
                                            "none"}
    idle = witness["window_s"] - witness["busy_s"]
    assert sum(s for _, s in gaps) <= idle * 1.01


SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "convolution.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_composed" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.sleep" } } }
"""


def test_reduction_of_a_known_trace():
    """Ops at [1, 3) and [2, 4) and [7, 8) us inside a window [0.5, 9.5)
    us, with the generator asleep over [3.5, 6.5) us."""
    from jax.profiler import ProfileData
    r = devtrace.reduce(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC)))
    assert r["busy_s"] == pytest.approx(4e-6)
    assert r["window_s"] == pytest.approx(9e-6)
    assert r["device_ops"] == [["fusion.1", pytest.approx(3e-6)],
                               ["convolution.2", pytest.approx(2e-6)]]
    assert r["idle_gaps"] == [["bench.sleep", pytest.approx(3e-6)],
                              ["none", pytest.approx(1.5e-6)],
                              ["none", pytest.approx(0.5e-6)]]
    assert r["programs"] == {"jit_composed": {
        "runs": 2, "seconds": pytest.approx(4e-6)}}
