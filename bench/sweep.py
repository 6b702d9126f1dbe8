#!/usr/bin/env python3
"""Find an open cell's knee: the highest Poisson rate the served system
sustains without a growing backlog.  One process, one set-up, then:

1. one client sends a request at a time for ``--seconds`` / 2: the
   median of those latencies is the unloaded service time ``S``, and
   ``1 / S`` the capacity of the one-row path the open loop takes;
2. open-loop steps at ``FRACTIONS`` of that capacity, lowest first, each
   for ``--seconds`` in the cell's own arrival order (its
   ``arrival_seed``), each drained before the next.

A step is sustained when every request finished within 5 s of its
window's close and the median latency stayed under ``P50_LIMIT`` x S: a
single-server queue crosses that near 0.9 of its capacity, where the
backlog, not the service, sets the latency.  The sweep stops at the
first step that is not sustained; the knee is the step before it, and
the rate chosen for the cell is 0.8 x the knee.  With ``--write`` the
cell file gets ``rate_per_s`` and the sweep under ``knee``, and, given
``--out``, a copy goes to that directory.

    python3 bench/sweep.py --workload <open cell> --seed <n> --seconds 20 --write
"""
import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import json                                                 # noqa: E402
import sys                                                  # noqa: E402
from pathlib import Path                                    # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]

from bench import run as bench_run                          # noqa: E402

FRACTIONS = (0.5, 0.6, 0.7, 0.8, 0.9, 1.0, 1.1, 1.2)
P50_LIMIT = 4.0
DRAIN_S = 5.0


def unloaded(system, seconds):
    """Median latency of one request at a time, in seconds."""
    from bench import stats, traffic
    t0 = time.perf_counter()
    reqs = traffic.closed_loop(system.send, 1, seconds, t0)
    return stats.percentile([r.done - r.sent for r in reqs
                             if r.done is not None], 50)


def step(system, rate, seconds, arrival_seed, service_s):
    from bench import stats, traffic
    t0 = time.perf_counter()
    reqs = traffic.open_loop(system.send, rate, seconds, arrival_seed, t0)
    done = [r for r in reqs if r.done is not None]
    lat = [(r.done - r.due) * 1e3 for r in done]
    late = [r for r in done if r.done > t0 + seconds + DRAIN_S]
    p50 = stats.percentile(lat, 50)
    return {"rate_per_s": rate, "requests": len(reqs),
            "done": len(done), "p50_ms": p50,
            "p95_ms": stats.percentile(lat, 95),
            "p50_over_service": p50 / (service_s * 1e3),
            "sustained": (len(done) == len(reqs) and not late
                          and p50 <= P50_LIMIT * service_s * 1e3)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--write", action="store_true")
    p.add_argument("--out", default=None)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    bench_run.setup_jax(args.rehearse)
    from bench import harness
    device = bench_run.find_device(1, args.rehearse)
    cell = harness.load_cell(args.workload, rehearse=args.rehearse)
    params = harness.make_weights(cell, args.seed)
    system = harness.System(cell, params, args.seed)
    system.warm()
    steps = []
    try:
        service_s = unloaded(system, args.seconds / 2)
        cap = 1.0 / service_s
        harness.say(f"unloaded service {service_s * 1e3:.3f} ms: capacity "
                    f"{cap:.3f} requests/s")
        for f in FRACTIONS:
            s = step(system, round(f * cap, 2), args.seconds,
                     cell.spec["arrival_seed"], service_s)
            harness.say(json.dumps(s))
            steps.append(s)
            if not s["sustained"]:
                break
    finally:
        system.stop()
    ok = [s["rate_per_s"] for s in steps if s["sustained"]]
    knee = ok[-1] if ok else None
    result = {"device": device["kind"], "seed": args.seed,
              "seconds": args.seconds, "service_ms": service_s * 1e3,
              "capacity_per_s": cap, "p50_limit_x_service": P50_LIMIT,
              "knee_rate_per_s": knee, "steps": steps,
              "rate_per_s": round(0.8 * knee, 2) if knee else None}
    print(json.dumps(result), flush=True)
    if args.write and knee:
        path = harness.BENCH / "workloads" / f"{cell.name}.json"
        spec = harness.load_json(path)
        spec["rate_per_s"] = result["rate_per_s"]
        spec["knee"] = {k: v for k, v in result.items() if k != "rate_per_s"}
        text = json.dumps(spec, indent=2) + "\n"
        path.write_text(text)
        if args.out:
            out = Path(args.out)
            out.mkdir(parents=True, exist_ok=True)
            (out / f"{cell.name}.json").write_text(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
