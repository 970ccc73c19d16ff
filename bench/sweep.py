"""Find the knee of an open-loop cell: the highest offered rate at which
the backlog does not grow (at least 99% of the requests due in the window
answered before it closes).

    python -m bench.sweep --workload <cell> --seed <n> --seconds <s> --rates 150,200

Each rate is one whole run of the cell with the mix's ``rate_rps``
replaced. One line per rate: ``sweep <rate> answered_in_window <share>
p50_ms .. p95_ms .. p99_ms .. correct ..``. Not part of the benchmark's
own runs; the rate the cell offers is written into its mix by hand. A
cell that ``BENCHMARK.json`` does not list yet is built from its
configuration and mix files.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

from bench import drive, measure, run, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    devs = run.tpu_devices(cell.chips)
    if devs is None:
        return 2
    for rate in (float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   rate_rps=rate))
        out = run.run_cell(c, devs, args.seed, args.seconds, False,
                           time.perf_counter())
        w = out.window
        share = sum(r.answered and r.t_done <= w.t_close
                    for r in w.sent) / len(w.sent)
        lat = measure.latencies_ms(w, w.t_close + drive.GRACE_S)
        print(f"sweep {rate} answered_in_window {share:.4f} "
              f"p50_ms {measure.percentile(lat, 50):.3f} "
              f"p95_ms {measure.percentile(lat, 95):.3f} "
              f"p99_ms {measure.percentile(lat, 99):.3f} "
              f"correct {out.result['correct']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
