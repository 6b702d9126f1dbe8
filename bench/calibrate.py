#!/usr/bin/env python3
"""Readings that the check's limit is set from, for one cell, in one
process: for every seed, a whole run of the cell (its own traffic for
``--seconds``) whose served tokens are compared with the float32
reference (the program's reading), and with the fp8 reference put in
the program's place at the same positions (the control's reading).  The
control is judged by the same verdict as ``correct``
(``harness.verdict``) and has to come out not correct on every seed.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 6

One JSON line per seed on standard output, then a summary line: the
largest program reading (the lower end of the limit) and the smallest
control reading (the upper end).  Exits 1 where a program run is not
correct or a control run is.  The benchmark's own runs never run the
control.
"""
import time

import argparse                                             # noqa: E402
import json                                                 # noqa: E402
import sys                                                  # noqa: E402
from pathlib import Path                                    # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]

from bench import run as bench_run                          # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=6.0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    bench_run.setup_jax(args.rehearse)
    from bench import harness
    device = bench_run.find_device(1, args.rehearse)
    cell = harness.load_cell(args.workload, rehearse=args.rehearse)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run(cell, seed, args.seconds, False,
                          t_start=time.perf_counter(), device=device,
                          control=True, rehearse=args.rehearse)
        r = res.readings
        row = {"seed": seed, "correct": res.line["correct"],
               "failed": res.line["failed"], "sampled": r["sampled"],
               "served_tokens": r["served_tokens"],
               "logit_gap": r.get("logit_gap"),
               "control_logit_gap": r.get("control_logit_gap"),
               "control_correct": r.get("control_correct")}
        print(json.dumps(row), flush=True)
        rows.append(row)
    gaps = [r["logit_gap"] for r in rows if r["logit_gap"] is not None]
    ctl = [r["control_logit_gap"] for r in rows
           if r["control_logit_gap"] is not None]
    bad = [r["seed"] for r in rows
           if not r["correct"] or r["control_correct"] is not False]
    print(json.dumps({"workload": cell.name, "seeds": len(rows),
                      "program_max": max(gaps) if gaps else None,
                      "control_min": min(ctl) if ctl else None,
                      "limit": cell.spec["check"]["max_logit_gap"],
                      "seeds_at_fault": bad}),
          flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
