"""What the per-layer metric readers share.  Each reader is
``metrics/<metric>.py`` with ``read(ctx) -> float | None``; ``ctx`` holds

* ``cell``: the ``harness.Cell``;
* ``traces``: the traced run's kept request traces (``Tracer`` with
  ``sample_rate=1``);
* ``window_counters``: the chain's counters over the whole window, and
  ``counters``: over the profiler's part of it (``harness.counter_delta``);
* ``batch_rows``: the rows of each batched dispatch in the profiler's
  part (``harness.batch_rows``), or None where they are not known;
* ``trace``: ``devtrace.reduce`` of the profiler's trace, or None;
* ``peaks``: the device's row of ``peaks.json``;
* ``least_seconds(rows, peaks)``: least time of one dispatch of that many
  rows; ``request_flops``: operations one request needs.

A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""
from bench import stats

#: what the XLA programs of the lowered chain are named after: the
#: function ``core/lowering.py`` composes the chain's steps into
CHAIN_PROGRAM = "jit_composed"


def spans(ctx, kind):
    return [s for t in ctx["traces"] for s in t.spans if s.kind == kind]


def share(part, whole):
    return 100.0 * part / whole if whole else None


def chain_runs(ctx):
    """(dispatches the host issued, runs of the chain's programs on the
    device, their device seconds) over the profiler's window, or None
    where the trace holds no program named ``CHAIN_PROGRAM`` or the
    host's dispatches are not known.  The runs and seconds are of the
    programs wholly inside the window; the host's dispatch mix is scaled
    to that many runs."""
    red, c = ctx["trace"], ctx["counters"]
    if red is None or c is None or ctx["batch_rows"] is None:
        return None
    n_host = c["row_dispatches"] + c["batch_dispatches"]
    chain = [v for k, v in red["programs"].items() if CHAIN_PROGRAM in k]
    runs = sum(v["runs"] for v in chain)
    secs = sum(v["seconds"] for v in chain)
    if not n_host or not runs or secs <= 0:
        return None
    return n_host, runs, secs


def roofline(ctx):
    """Least time of the dispatches, at the rows each carried, over the
    device time they took."""
    got = chain_runs(ctx)
    if got is None:
        return None
    n_host, runs, secs = got
    least = ctx["counters"]["row_dispatches"] * ctx["least_seconds"](
        1, ctx["peaks"])
    least += sum(ctx["least_seconds"](n, ctx["peaks"])
                 for n in ctx["batch_rows"])
    return share(least / n_host * runs, secs)


def mfu(ctx):
    """Operations the served requests needed over the device time of
    their dispatches, against the peak bf16 rate."""
    got = chain_runs(ctx)
    if got is None:
        return None
    n_host, runs, secs = got
    c = ctx["counters"]
    useful = (c["rows_batched"] + c["row_dispatches"]) * ctx["request_flops"]
    return share(useful / n_host * runs / secs,
                 ctx["peaks"]["bf16_flops_per_s"])


def percentile_ms(values_s, p):
    return stats.percentile([x * 1e3 for x in values_s], p)
