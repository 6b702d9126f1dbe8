"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (stdout).  Network costs in
runtime benchmarks are modeled (single-host container) — see DESIGN.md §2;
the validated claims are the relative effects from the paper's figures.

  PYTHONPATH=src python -m benchmarks.run [--only fusion,batching] [--fast]
               [--json]

``--json`` additionally writes machine-readable artifacts for suites that
support it (``batching`` -> ``BENCH_batching.json``: p50/p99 latency,
dispatches/row, batch-size histogram, executable-cache stats, plus the
``device_resident`` section — per-stage host-copy counts for the staged
vs device-resident 3-node chain, the learned per-chain crossover table,
and the filter-in-jit equivalence check; ``slo_planner`` ->
``BENCH_slo_planner.json``: estimator predicted vs measured p50/p99 with
relative error, and SLO attainment of the optimizer's PlanConfig vs the
default config across arrival rates; ``replan`` -> ``BENCH_replan.json``:
steady-state vs during-swap p99 across a controller-initiated blue/green
swap, dropped/errored request counts, and the post-swap executable
re-trace count — all must stay at zero drops / zero re-traces;
``model_serving`` -> ``BENCH_model_serving.json``: per-request p50/p99
for the video pipeline and the prefill->decode cascade, greedy-token
parity for the fused cascade, Pallas-kernel-vs-reference step latency
and chain parity, single-dispatch-per-batch and zero-retrace checks for
placed kernel chains, and the SLO controller's propose->hot-apply
outcome against ModelOp-measured curves; ``overload`` ->
``BENCH_overload.json``: an offered-load sweep from 0.5x to 3x capacity
through the admission gate — per-class goodput/p50/p99,
shed/degrade/expiry counts, shed fast-fail p99, expiry-overrun p99, and
per-point drain + counter-reconciliation integrity bits; at 3x the CI
gate asserts interactive p99 within SLO, sheds failing in <10% of the
SLO budget, zero wedged batchers and zero hot-path re-traces;
``faults`` -> ``BENCH_faults.json``: a chaos sweep of crash + straggle +
transient fault rates over the same chain at half capacity with
straggler hedging armed — per-point p50/p99, injected-vs-detected fault
counts, retry/hedge/requeue counters, and integrity bits; CI asserts
every request resolves TYPED (zero hangs, zero untyped errors), counters
reconcile, batchers drain, zero hot-path re-traces, low-fault p99 within
SLO, and no fault-free p50 regression vs the overload 0.5x point;
``observability`` -> ``BENCH_observability.json``: tracing overhead at
1%/10%/100% head sampling vs disabled on a 3-node chain with a known
slow middle node, the SLO-miss attribution's dominant (node, component)
against that ground truth, Chrome-export span coverage, and a
zero-retrace check — CI asserts the 10%-sampling p50 within 5% of the
disabled baseline and the dominant contributor correctly named) so CI
can track the perf trajectory across PRs.
"""
from __future__ import annotations

import argparse
import sys
import time

SUITES = ("fusion", "jit_fusion", "competitive", "autoscaling", "locality",
          "batching", "slo_planner", "replan", "overload", "faults",
          "model_serving", "pipelines", "roofline", "observability")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help=f"comma list from {SUITES}")
    p.add_argument("--fast", action="store_true",
                   help="fewer requests per point")
    p.add_argument("--json", action="store_true",
                   help="write BENCH_<suite>.json artifacts (batching)")
    args = p.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else set(SUITES)

    print("name,us_per_call,derived")
    t0 = time.time()
    rows = []

    def emit(new_rows):
        for r in new_rows:
            print(r, flush=True)
        rows.extend(new_rows)

    if "fusion" in only:
        from benchmarks import fusion_chain
        emit(fusion_chain.run(n_requests=6 if args.fast else 12))
    if "jit_fusion" in only:
        from benchmarks import fusion_chain
        emit(fusion_chain.run_jit(n_requests=10 if args.fast else 30))
    if "competitive" in only:
        from benchmarks import competitive
        emit(competitive.run(n_requests=15 if args.fast else 40))
    if "autoscaling" in only:
        from benchmarks import autoscaling
        emit(autoscaling.run(duration_s=6.0 if args.fast else 12.0))
    if "locality" in only:
        from benchmarks import locality
        emit(locality.run(n_requests=10 if args.fast else 30))
    if "batching" in only:
        from benchmarks import batching
        emit(batching.run(n_requests=16 if args.fast else 48,
                          json_path="BENCH_batching.json" if args.json
                          else None))
    if "slo_planner" in only:
        from benchmarks import slo_planner
        emit(slo_planner.run(
            n_requests=60 if args.fast else 150,
            rates=(60.0, 170.0) if args.fast else (60.0, 120.0, 170.0),
            json_path="BENCH_slo_planner.json" if args.json else None))
    if "replan" in only:
        from benchmarks import replan
        emit(replan.run(
            duration_s=5.0 if args.fast else 10.0,
            rate_hz=80.0 if args.fast else 120.0,
            json_path="BENCH_replan.json" if args.json else None))
    if "overload" in only:
        from benchmarks import overload
        emit(overload.run(
            duration_s=1.5 if args.fast else 2.5,
            multipliers=(0.5, 3.0) if args.fast
            else (0.5, 1.0, 2.0, 3.0),
            json_path="BENCH_overload.json" if args.json else None))
    if "faults" in only:
        from benchmarks import faults
        emit(faults.run(
            duration_s=1.5 if args.fast else 2.5,
            rates=(0.0, 0.02) if args.fast
            else (0.0, 0.01, 0.02, 0.05),
            json_path="BENCH_faults.json" if args.json else None))
    if "model_serving" in only:
        from benchmarks import model_serving
        emit(model_serving.run(
            n_requests=12 if args.fast else 30,
            json_path="BENCH_model_serving.json" if args.json else None))
    if "pipelines" in only:
        from benchmarks import pipelines
        emit(pipelines.run(n=8 if args.fast else 16))
    if "roofline" in only:
        from benchmarks import roofline_table
        emit(roofline_table.run())
    if "observability" in only:
        from benchmarks import observability
        emit(observability.run(
            n_requests=120 if args.fast else 250,
            json_path="BENCH_observability.json" if args.json else None))
    print(f"# {len(rows)} rows in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
