#!/usr/bin/env python3
"""Run one benchmark cell once, in this process, on the chip it finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, the cell's flow compiled onto a Runtime,
its shapes warmed) is timed as ``setup_s``; then the cell's traffic runs
for ``--seconds``; then what the window served is checked against the
plain reference.  Progress and every number the check compares, beside
its limit, go to standard error; the last line of standard output is the
result as one JSON object.  ``--trace 1`` reports the cell's per-layer
metrics from a traced run instead of its end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 1 and prints no result.  ``--rehearse`` runs the same path on the
CPU at the configuration's tiny rehearsal sizes and prints no result
line either; it exercises the harness, and measures nothing.
"""
import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import sys                                                  # noqa: E402
from pathlib import Path                                    # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on the CPU; prints no result line")
    p.add_argument("--keep-trace", default=None,
                   help="copy the profiler's trace directory here")
    return p.parse_args(argv)


def setup_jax(rehearse: bool) -> None:
    """The platform, and the persistent compile cache at a fixed path
    inside the checkout (or where JAX_COMPILATION_CACHE_DIR says)."""
    # a failed TPU initialisation must raise, never fall back to the CPU
    os.environ["JAX_PLATFORMS"] = "cpu" if rehearse else "tpu"
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def find_device(chips: int, rehearse: bool) -> dict:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: no accelerator ({e}); refusing to measure")
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    if rehearse:
        return info
    if d.platform != "tpu":
        raise SystemExit(f"bench: no TPU (platform {d.platform!r}); "
                         f"refusing to measure")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    from bench import harness
    info["peaks"] = harness.peaks_for(d.device_kind)
    return info


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    setup_jax(args.rehearse)
    from bench import harness
    entry = next((w for w in harness.benchmark()["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        raise SystemExit(f"bench: no workload {args.workload!r} in "
                         f"BENCHMARK.json")
    device = find_device(entry["chips"], args.rehearse)
    harness.say(f"device: {device['platform']} {device['kind']} x"
                f"{device['count']}")
    cell = harness.load_cell(args.workload, rehearse=args.rehearse)
    res = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, device=device,
                      keep_trace=args.keep_trace, rehearse=args.rehearse)
    if args.rehearse:
        print(f"rehearsal only, no result: correct={res.line['correct']} "
              f"metrics={sorted(res.line['metrics'])} "
              f"readings={json.dumps(res.readings, default=str)}",
              flush=True)
        return 0 if res.line["correct"] else 1
    print(json.dumps(res.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
