"""Device microseconds of radius traversal per query row served.

The engine's cached query executables run as ``jit_body`` modules; in a
cell that serves only radius queries they are the radius traversal. Their
device time over the real (unpadded) rows the pipeline dispatched in the
window (``PipelineStats.batch_rows``).
"""


def read(run):
    if run.trace is None or run.traffic.kind != "within":
        return None
    rows = run.stats1.batch_rows - run.stats0.batch_rows
    devs = run.trace.device_names()
    busy = sum(e.dur_ns for d in devs for e in run.trace.modules(d, r"^jit_body"))
    if not rows or not busy:
        return None
    return busy / len(devs) / rows * 1e-3
