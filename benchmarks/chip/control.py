"""Readings from which a cell's limits are set: the program's numbers on
many seeds, and the control's on some of them, in one process.

    python benchmarks/chip/control.py --workload zamba2.gen \\
        --seeds 101,102,103 --control-seeds 101,102,103 --seconds 8

For each seed the cell runs as ``run.py`` runs it (set-up, a window of
``--seconds``, the program's state freed) and prints one JSON line with
the numbers its check compares and whether they make the run
``correct``.  On a control seed it also prints the control's: the
reference computed with fp8 matmul operands in the program's place,
compared with the float32 reference by the same checks and limits, and
``control_correct``, which has to come out false.  The benchmark's own
runs never run this; it needs the chip as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import harness
import run as bench_run
from harness import Check


def readings(name: str, seed: int, seconds: float, control: bool,
             **where) -> dict:
    t0 = time.perf_counter()
    _, ctx, r = bench_run.prepare(name, seed, **where)
    r.setup()
    r.window(seconds)
    r.free()
    gc.collect()
    checks = r.check() + [Check("failed_units", r.failed, 0)]
    out = {"seed": seed, "attempted": r.attempted, "failed": r.failed,
           "program": harness.checks_line(checks),
           "correct": harness.correct(checks)}
    out["program_s"] = time.perf_counter() - t0
    if control:
        checks = r.control()
        out["control"] = harness.checks_line(checks)
        out["control_correct"] = harness.correct(checks)
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for s in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(readings(args.workload, s, args.seconds, s in ctl)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
