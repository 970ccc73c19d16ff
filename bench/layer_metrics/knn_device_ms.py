"""Device milliseconds per run of the kNN traversal executable.

The engine's cached query executables run as ``jit_body`` modules (the
name of the function it jits); in a cell that serves only kNN they are
the kNN traversal. Mean over every run in the trace, on every device.
"""


def read(run):
    if run.trace is None or run.traffic.kind != "knn":
        return None
    evs = [e for d in run.trace.device_names()
           for e in run.trace.modules(d, r"^jit_body")]
    if not evs:
        return None
    return sum(e.dur_ns for e in evs) / len(evs) * 1e-6
