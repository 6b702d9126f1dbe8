"""The per-layer readers, on readings made up so the answer is known."""
import importlib.util
from pathlib import Path

import pytest

from bench import harness

METRICS = Path(__file__).resolve().parents[1] / "metrics"
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ctx(**kw):
    base = {"counters": {"row_dispatches": 8, "batch_dispatches": 2,
                         "rows_batched": 12},
            "batch_rows": [5, 7],
            "window_counters": {"row_dispatches": 8, "batch_dispatches": 2},
            "trace": {"busy_s": 9.0, "window_s": 10.0,
                      "programs": {"jit_composed": {"runs": 5,
                                                    "seconds": 6.0}}},
            "peaks": PEAKS,
            "least_seconds": lambda rows, peaks: 0.1 * rows,
            "request_flops": 50.0, "traces": []}
    base.update(kw)
    return base


def test_step_roofline():
    # least time: 8 x 0.1 + 0.5 + 0.7 = 2.0 s over 10 host dispatches, so
    # 0.2 s a dispatch; 5 runs took 6 s on the device
    assert reader("step_roofline.open")(ctx()) == pytest.approx(
        100 * 0.2 * 5 / 6.0)


def test_mfu():
    # 20 rows x 50 operations over 10 dispatches; 5 runs in 6 s; peak 100
    assert reader("mfu.open")(ctx()) == pytest.approx(
        100 * (20 * 50 / 10) * 5 / 6.0 / 100.0)


def test_row_share():
    assert reader("chain.row_dispatch_share.open")(ctx()) == pytest.approx(
        80.0)


def test_nothing_to_read_gives_nothing():
    for name in ("step_roofline.open", "mfu.open", "batcher.queue_p95_ms",
                 "executor.service_p50_ms"):
        assert reader(name)(ctx(trace=None, counters=None)) is None


@pytest.mark.parametrize("name", ["step_roofline.open", "mfu.open"])
def test_no_chain_program_in_the_trace_gives_nothing(name):
    """Only the chain's own programs are read: a trace without them, or
    rows of batched dispatches that are not known, leaves the metric
    out rather than reading some other time."""
    other = {"busy_s": 9.0, "window_s": 10.0,
             "programs": {"jit_other": {"runs": 5, "seconds": 6.0}}}
    assert reader(name)(ctx(trace=other)) is None
    assert reader(name)(ctx(batch_rows=None)) is None


def test_batch_rows_from_spans_and_counters():
    from repro.obs.trace import Span
    spans = [Span("batch@n", t, t + 0.1, {"size": n})
             for t, n in ((1.0, 1), (2.0, 3), (3.0, 5), (9.0, 4))]
    c = {"row_dispatches": 1, "batch_dispatches": 2, "rows_batched": 8}
    assert harness.batch_rows(spans, 0.5, 5.0, c) == [3, 5]
    one = dict(c, batch_dispatches=1, rows_batched=4)
    assert harness.batch_rows(spans, 0.5, 5.0, one) == [4]
    none = dict(c, batch_dispatches=0, rows_batched=0)
    assert harness.batch_rows(spans, 0.5, 5.0, none) == []
    assert harness.batch_rows(spans, 0.5, 5.0,
                              dict(c, rows_batched=9)) is None


def test_least_seconds_takes_the_larger_bound_per_stage():
    cell = harness.load_cell("yi-9b-16l.chat-open")
    stages = cell.costs.stages(cell.cfg, 8, cell.prompt_len, cell.steps)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    want = sum(max(f / 197e12, b / 819e9) for f, b in stages)
    assert harness.least_seconds(cell, 8, peaks) == pytest.approx(want)
    assert want > max(sum(f for f, _ in stages) / 197e12,
                      sum(b for _, b in stages) / 819e9)
