"""Run one cell of the benchmark once.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``, from process start to the first
submission): the cloud and its moved copies from the seed, the index
build, one refit to the same points where the mix moves points (compiles
the refit), and one request of every bucket shape the mix can form. Then
the clients drive the window (``bench.drive``); with ``--trace 1`` the JAX
profiler records its first ``TRACE_S`` seconds and the per-layer metrics are
read from that trace and from the program's counters instead of the
end-to-end metrics; such a run also prints, after the window, what XLA's
``memory_analysis`` gives for each query program the engine ran.

After the window, with the device memory peak read and the pipeline shut,
a sample of the answers is compared with the plain reference
(``bench.check``). The last line of standard output is the result; the
numbers compared, with their limits, are the last lines of standard error
and the last key of the result.

The run needs a TPU with at least the cell's chips and exits with 2, and
no result, without one.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

from bench import spec  # noqa: E402

#: rows of answers compared with the reference in each run
CHECK_ROWS = 2048
#: the compile cache, at a fixed place inside the checkout
CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")
#: seconds of the window that a ``--trace 1`` run records
TRACE_S = 10.0


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a per-layer metric's ``read(run)`` gets: the window's requests
    and updates, and the trace with the pipeline's counters at its ends."""
    cell: object
    traffic: object
    window: object            # bench.drive.Window: the whole window
    stats0: object            # PipelineStatsSnapshot when the trace began
    stats1: object            # ... when it ended
    trace: object             # bench.trace.Trace


class Profile:
    """The JAX profiler over the first `seconds` of the window (a trace of
    the whole window grows by millions of events a second in a traversal's
    while loop, and writing it out takes minutes). A timer stops it, or
    ``stop`` at the window's end if that comes first; `counters` is read
    at both ends."""

    def __init__(self, log_dir: str, seconds: float, counters):
        import jax
        self._counters = counters
        self._lock = threading.Lock()
        self._stopped = False
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.stats0 = counters()
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        self._t0 = time.perf_counter()
        self._timer = threading.Timer(seconds, self.stop)
        self._timer.daemon = True
        self._timer.start()

    def stop(self):
        import jax
        with self._lock:
            if self._stopped:
                return
            self.window_s = time.perf_counter() - self._t0
            self.stats1 = self._counters()
            jax.profiler.stop_trace()
            self._stopped = True
        self._timer.cancel()


class CompileCounter:
    """Counts the XLA compiles (and compile-cache reads) the process makes
    while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _seen(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._seen)


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@dataclasses.dataclass
class Outcome:
    result: dict              # the result line
    window: object            # bench.drive.Window
    check: object             # bench.check.Check of the program's answers
    control: object           # ... of the bfloat16 reference's, or None


def run_cell(cell, devices, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False) -> Outcome:
    """Set up, drive and check one cell on `devices` (the harness's look
    for a chip is the caller's). With `control`, the bfloat16 reference's
    answers to the same sampled rows are compared as well."""
    t0_cell = time.perf_counter()
    import jax

    from bench import check, drive, measure
    from bench import trace as TR
    from bench.system import SETUP_WAIT_S, System
    from bench.traffic import Traffic

    phases, t = [], time.perf_counter()

    def phase(name):
        nonlocal t
        now = time.perf_counter()
        phases.append(f"{name} {now - t:.3f} s")
        t = now

    traffic = Traffic(cell.traffic, cell.config, seed)
    pts = traffic.points()
    moves = traffic.moves(pts, len(traffic.update_times(seconds))) \
        if traffic.mix.get("updates") else []
    phase("data")
    system = System(cell.config, devices, traffic)
    versions = {}
    tmp = tempfile.TemporaryDirectory() if trace else None
    try:
        versions[system.build(system.place(pts))] = pts
        phase("build")
        if traffic.mix.get("updates"):
            v, _ = system.update(system.place(pts), SETUP_WAIT_S)
            versions[v] = pts
            phase("refit warm-up")
        system.warm(traffic.buckets(int(cell.config["min_bucket"]),
                                    int(cell.config["max_bucket"])),
                    traffic.rng(5))
        phase("bucket warm-up")
        placed = [system.place(m) for m in moves]
        jax.block_until_ready([p.coords for p in placed])
        phase("moved clouds placed")
        log(f"set-up: before run_cell {t0_cell - t_start:.3f} s; "
            + "; ".join(phases))
        traces0 = system.jit_traces()
        with CompileCounter() as compiles:
            stats0 = system.pipeline_stats()
            if trace:
                profile = Profile(tmp.name, min(seconds, TRACE_S),
                                  system.pipeline_stats)
            if traffic.loop == "closed":
                window = drive.closed_loop(system, traffic, seconds)
            else:
                window = drive.open_loop(system, traffic, seconds, placed)
            if trace:
                profile.stop()
            stats1 = system.pipeline_stats()
        memory_peak = peak_bytes(devices)
        traces = system.jit_traces() - traces0
        del placed
    finally:
        system.close()
    log(f"compiles in window: {compiles.count} (engine jit traces: {traces})")
    if trace:
        t = time.perf_counter()
        for line in system.memory_report():
            log(line)
        log(f"memory report: {time.perf_counter() - t:.3f} s")

    tr = None
    if trace:
        t = time.perf_counter()
        path = TR.find_xplane(tmp.name)
        size = os.path.getsize(path)
        tr = TR.load(path, profile.window_s,
                     cut=profile.window_s < window.t_end - window.t0)
        tmp.cleanup()
        log(f"trace: {size} bytes, read in {time.perf_counter() - t:.3f} s; "
            + ", ".join(f"{d}: {len(tr.devices[d].modules)} executable runs"
                        for d in tr.device_names()))

    for m in window.moved:
        if m.error is None:
            versions[m.version] = moves[m.index]
    limits = cell.traffic["limits"]
    t = time.perf_counter()
    ours = check.compare(window, traffic, versions, limits, CHECK_ROWS)
    log(f"reference: {ours.rows_checked} rows in "
        f"{time.perf_counter() - t:.3f} s over versions {sorted(versions)}")
    theirs = None
    if control:
        theirs = check.compare(window, traffic, versions, limits, CHECK_ROWS,
                               control=True)
        log("control (bfloat16 reference): " + "; ".join(theirs.lines()))

    n_sent = len(window.sent)
    service = [r.resp.stats.service_us * 1e-3 for r in window.sent
               if r.answered]
    log(f"window: {n_sent} requests, {sum(r.rows for r in window.sent)} "
        f"rows, {window.t_end - window.t0:.6f} s; batches "
        f"{stats1.batches - stats0.batches}; service ms "
        + (f"min {min(service):.3f} median {measure.percentile(service, 50):.3f}"
           f" max {max(service):.3f}" if service else "none")
        + "; updates " + ", ".join(
            f"v{m.version} {m.action} {(m.t_visible - m.t_call) * 1e3:.3f} ms"
            for m in window.moved))
    metrics = {}
    if not trace:
        found = {"setup_s": window.t0 - t_start}
        if traffic.loop == "closed":
            found["qps"] = measure.qps(window)
        elif window.sent:
            found["p95_ms"] = measure.p95_ms(
                window, window.t_close + drive.GRACE_S)
        if window.moved:
            found["publish_ms"] = measure.publish_ms(window)
        for m in cell.end_to_end:
            if found.get(m.name) is not None:
                metrics[m.name] = {"value": found[m.name], "unit": m.unit}
    else:
        run = Run(cell, traffic, window, profile.stats0, profile.stats1, tr)
        for m in cell.per_layer:
            value = m.reader(run)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": ours.correct, "attempted": n_sent,
              "failed": ours.values["missing"], "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    result["checks"] = ours.as_json()
    for line in ours.lines():
        log(line)
    return Outcome(result, window, ours, theirs)


def tpu_devices(chips: int):
    """Put the library on the path and the compile cache in the checkout,
    start JAX, and return the first `chips` TPU devices; None (with the
    reason on standard error) where there are not that many."""
    src = os.path.join(spec.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"bench: the library is not at {src}")
        return None
    sys.path.insert(0, src)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: needs a TPU, JAX found {devs[0].platform}")
        return None
    if len(devs) < chips:
        log(f"bench: needs {chips} chips, JAX found {len(devs)}")
        return None
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    # keep every program, however quick to compile, so that later runs
    # read all of them from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return devs[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    devs = tpu_devices(cell.chips)
    if devs is None:
        return 2
    out = run_cell(cell, devs, args.seed, args.seconds,
                   bool(args.trace), T_START)
    print(json.dumps(out.result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
