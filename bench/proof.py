"""Readings for the limits of a cell's correctness check: the program's
numbers and the bfloat16 control's on many seeds, in one process.

    python -m bench.proof --workload <cell> --seconds <s> --seeds 1,2,3

Each seed is one whole run of the cell (set-up, window, check), and the
control's answers are made for the same sampled rows and judged by the
same comparison (``bench.check.compare``) against the same limits. One line
per seed: ``proof <seed> correct <bool> program {...} control_correct
<bool> control {...} metrics {...}``. A cell that ``BENCHMARK.json`` does
not list yet is built from its configuration and mix files. Not part of
the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    devs = run.tpu_devices(cell.chips)
    if devs is None:
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(cell, devs, seed, args.seconds, bool(args.trace),
                           time.perf_counter(), control=True)
        print(f"proof {seed} correct {out.check.correct} program "
              f"{json.dumps(out.check.values)} control_correct "
              f"{out.control.correct} control "
              f"{json.dumps(out.control.values)} metrics "
              f"{json.dumps(out.result['metrics'])}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
