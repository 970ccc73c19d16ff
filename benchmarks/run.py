"""Benchmark driver: one module per paper table/claim. Prints
``name,us_per_call,derived`` CSV rows (CPU timings are relative;
TPU-derived numbers come from the dry-run roofline — EXPERIMENTS.md).

A module's ``main`` may return a dict of structured results; it is then
persisted to ``BENCH_<suffix>.json`` at the repo root (e.g.
``bench_service`` -> ``BENCH_service.json``) so perf trajectories are
recorded run over run, not just printed.
"""
import importlib
import json
import os
import sys
import traceback

from repro import telemetry
from repro.compile_cache import configure_compile_cache
from repro.core.route_table import hardware_fingerprint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODULES = [
    "bench_construction",   # §2.6 morton 32/64 + build variants
    "bench_traversal",      # §2.6 stackless vs stack
    "bench_bruteforce",     # §1 brute-force index, crossover
    "bench_callbacks",      # §2.2 callback vs store-then-reduce
    "bench_early_exit",     # §2.6 early termination
    "bench_dbscan",         # §2.4 FDBSCAN vs DenseBox
    "bench_emst",           # §2.4 Boruvka EMST
    "bench_raytracing",     # §2.5 three predicates
    "bench_mls",            # §1 interpolation
    "bench_distributed",    # §2.3 callback comm saving + weak scaling
    "bench_service",        # DESIGN.md §5 refit + bucketed serving
    "bench_pipeline",       # DESIGN.md §7 async deadline-aware load gen
    "bench_sharded",        # DESIGN.md §11 sharded serving weak scaling
]

# JSON keys owned by MERGE_INTO modules, preserved when the owning module
# rewrites its file: BENCH_<suffix>.json -> keys to carry over
PRESERVE = {"service": ("pipeline",), "distributed": ("weak_scaling",)}


def main():
    only = sys.argv[1:] or None
    print(f"# compile cache: {configure_compile_cache()}", file=sys.stderr)
    print("name,us_per_call,derived")
    failed = []
    # telemetry on for the whole sweep; drained per module so each
    # BENCH_*.json carries a span summary of the run that produced it
    telemetry.enable(capacity=65536)
    for name in MODULES:
        if only and name not in only:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            telemetry.get_tracer().drain()      # spans of THIS module only
            out = mod.main()
            spans = telemetry.get_tracer().drain()
            if isinstance(out, dict):
                # a module may target another module's JSON (MERGE_INTO):
                # bench_pipeline folds its metrics into BENCH_service.json
                # under MERGE_KEY instead of owning a separate file
                target = getattr(mod, "MERGE_INTO", None)
                suffix = target or name.removeprefix("bench_")
                path = os.path.join(REPO, f"BENCH_{suffix}.json")
                old = {}
                if os.path.exists(path):
                    with open(path) as f:
                        old = json.load(f)
                if target is not None:
                    data = old
                    key = getattr(mod, "MERGE_KEY",
                                  name.removeprefix("bench_"))
                    data[key] = out
                else:
                    # keep sections owned by merge modules (a bench_service-
                    # only run must not drop the pipeline metrics) and the
                    # telemetry section other modules contributed to
                    data = {k: v for k, v in old.items()
                            if k in PRESERVE.get(suffix, ())
                            or k == "telemetry"}
                    data.update(out)
                # every BENCH_*.json gains a telemetry section: span
                # summaries keyed by the module whose run produced them
                data.setdefault("telemetry", {})[name] = \
                    telemetry.summarize_spans(spans)
                # every persisted payload records WHERE it was measured —
                # latencies without a hardware fingerprint are
                # unattributable (previously only implied by the checkout)
                data["fingerprint"] = hardware_fingerprint()
                with open(path, "w") as f:
                    json.dump(data, f, indent=2, sort_keys=True)
                print(f"# wrote {os.path.basename(path)}", file=sys.stderr)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
