"""Device milliseconds of collective operations (all-gather, all-to-all,
all-reduce, ...) per batch the pipeline dispatched, on the device that
spent most."""
from bench.trace import COLLECTIVE


def read(run):
    tr = run.trace
    batches = run.stats1.batches - run.stats0.batches
    if tr is None or not batches or not tr.device_names():
        return None
    ms = max(tr.op_ns(d, COLLECTIVE) for d in tr.device_names()) * 1e-6
    return ms / batches if ms else None
